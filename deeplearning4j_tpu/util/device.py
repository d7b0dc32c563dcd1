"""What machine is this: the ONE place that decides Pallas interpret
mode, and the ONE table of hardware peaks.

Nothing here falls back. A backend the kernels were not written for,
or a device whose peaks nobody looked up, is an error — a quiet default
would run the Pallas interpreter (a hundredth of the speed) or divide
by another chip's peak and call the result a utilization.
"""

from __future__ import annotations

import dataclasses
from typing import Dict


def pallas_interpret() -> bool:
    """Whether Pallas kernels run under the interpreter: ``True`` on the
    CPU backend (tests, rehearsals), ``False`` on TPU (Mosaic compiles
    them), an error on anything else."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here target TPU (compiled) or CPU (interpreted); "
        f"default backend is {backend!r}")


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks."""

    bf16_flops: float   # FLOP/s, bf16 matmul
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


#: keyed by ``jax.devices()[0].device_kind`` exactly as JAX reports it
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM2e at 819 GB/s per chip"),
}


def device_peaks() -> DevicePeaks:
    """Peaks of the default device; a kind not in the table (``cpu``
    included) is an error, never a default."""
    import jax

    kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {kind!r} — utilization "
            f"numbers need a real chip in DEVICE_PEAKS "
            f"(known: {sorted(DEVICE_PEAKS)})") from None


def device_info() -> Dict[str, object]:
    """The device as JAX reports it — what every benchmark payload and
    ``chip_smoke.py`` result line carries."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
