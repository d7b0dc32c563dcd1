"""The comparison that decides ``correct`` for a training cell.

Both sides are reduced to the same readings (``reference/gpt_plain.follow``
gives the reference's; the driver gives the program's from what the timed
program returned and left in its state):

- ``losses``: the loss of each step of the first dispatch;
- ``m_norms``: per leaf, the norm of Adam's first moment after that dispatch,
  the fixed combination of the gradients that the optimizer was given;
- ``dp_norms``: per leaf, the norm of the parameters' change over it.

A leaf's gap is the gap between the two sides' norms (not the norm of their
difference), against the reference's norm of that leaf or of the median leaf,
whichever is larger: some gradients are all but zero. The numbers compared are
the WORST leaf's gap of the first moment and of the change, which one broken
leaf moves, and the change's gap at the 90th percentile of the leaves, which is
steady from seed to seed and which a lower precision moves in every leaf. The
losses compared are those of the first ``LOSS_STEPS`` steps: later ones swing
with the trajectory, and the state after the dispatch holds them to account.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

#: the steps whose losses are compared
LOSS_STEPS = 3
#: the share of the leaves under the steady reading of the change
STEADY_QUANTILE = 0.9
#: a leaf whose first gradient in the reference is under this share of the
#: median leaf's moves under Adam by round-off alone: left out of the change
QUIET_LEAF = 1e-3


def _flat(norms: Dict[str, Any]) -> Tuple[List[str], np.ndarray]:
    names, vals = [], []
    for k in sorted(norms):
        v = np.atleast_1d(np.asarray(norms[k], np.float64))
        names += [k if v.size == 1 else f"{k}[{i}]" for i in range(v.size)]
        vals.append(v)
    return names, np.concatenate(vals)


def norm_gaps(prog: Dict[str, Any], ref: Dict[str, Any],
              keep: np.ndarray = None) -> Tuple[List[str], np.ndarray]:
    """Every leaf's gap, with the leaves' names; ``keep`` picks leaves."""
    names, r = _flat(ref)
    names_p, p = _flat(prog)
    if names_p != names:
        raise ValueError("the two sides have different leaves")
    floor = np.median(r)
    gaps = np.abs(p - r) / np.maximum(r, floor)
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    if keep is not None:
        names, gaps = [n for n, k in zip(names, keep) if k], gaps[keep]
    return names, gaps


def _worst(names: List[str], gaps: np.ndarray) -> Dict[str, Any]:
    i = int(np.argmax(gaps))
    return {"value": float(gaps[i]), "at": names[i]}


def training_gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers compared, each with the leaf or step that set it."""
    lp = np.asarray(prog["losses"], np.float64)[:LOSS_STEPS]
    lr = np.asarray(ref["losses"], np.float64)[:LOSS_STEPS]
    if lp.shape != lr.shape:
        raise ValueError("the two sides report different numbers of losses")
    rel = np.abs(lp - lr) / np.abs(lr)
    rel = np.where(np.isfinite(rel), rel, np.inf)
    _, g1 = _flat(ref["g1_norms"])
    moving = g1 >= QUIET_LEAF * np.median(g1)
    upd_names, upd = norm_gaps(prog["dp_norms"], ref["dp_norms"], moving)
    return {
        "loss_gap": {"value": float(rel.max()),
                     "at": f"step {int(np.argmax(rel)) + 1}"},
        "grad_norm_gap": _worst(*norm_gaps(prog["m_norms"], ref["m_norms"])),
        "update_norm_gap": _worst(upd_names, upd),
        "update_p90_gap": {"value": float(np.quantile(upd, STEADY_QUANTILE)),
                           "at": f"the {100 * STEADY_QUANTILE:g}th percentile "
                                 f"of {upd.size} leaves"},
        "quiet_leaves": int((~moving).sum()),
    }


def judge(gaps: Dict[str, Any], limits: Dict[str, Any]):
    """``(correct, compared)``: every number that has a limit is held to it.
    ``compared`` maps each short name to its number and its limit."""
    compared = {}
    ok = True
    for name, spec in limits.items():
        if not isinstance(spec, dict) or "limit" not in spec:
            continue
        value = gaps[name]["value"]
        compared[name] = {"value": value, "limit": spec["limit"],
                          "at": gaps[name]["at"]}
        if not (np.isfinite(value) and value <= spec["limit"]):
            ok = False
    if not compared:
        raise ValueError("no number has a limit: nothing would be compared")
    return ok, compared
