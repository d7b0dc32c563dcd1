"""One attention sublayer on the chip, forward and backward, by layout.

Usage: python scripts/profile_flash.py [--parent DIR] [--shapes b,h,t,d ...]

For each shape, ``x @ Wqkv -> causal self-attention -> @ Wo`` and its
gradients (x, Wqkv, Wo) run as one jitted call per variant, ten calls under
a device trace, and the device self time of a call is printed by op group
(``util/profiler.scoped_self_times``). The projections are in the program so
that XLA decides about the copies between them and the kernels as it does in
a model's step; they cost the same in every variant, and the difference of
two totals is what a layout costs. Variants:

- ``packed``: ``flash_attention_qkv`` (the resident kernels on the fused
  projection's own layout, where ``flash_path`` chooses them);
- ``folded``: split, [b, t, h, d] -> [b*h, t, d] copies, the same resident
  body on the folded arrays, the copy back;
- ``parent``: ``flash_attention`` of another checkout's
  ``ops/flash_attention.py`` (``--parent``: a ``git archive`` of the commit
  to compare with), behind the split the block made there.
"""
import argparse
import collections
import importlib.util
import os
import sys
from importlib import import_module

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.util import profiler
from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

# the module itself: ``ops/__init__`` re-exports the function under its name
FA = import_module("deeplearning4j_tpu.ops.flash_attention")
#: the cells' calls: batch, heads, length, head width (the three GPT cells,
#: the looped cell, the hybrid cell's one attention layer)
CELLS = ("8,16,1024,64", "32,16,256,64", "2,12,2048,128",
         "2,16,4096,128", "2,32,4096,64")
CALLS = 10


def _split(qkv, heads):
    b, t, features = qkv.shape
    return (z.reshape(b, t, heads, -1) for z in jnp.split(qkv, 3, axis=-1))


def variants(heads, parent):
    def folded(qkv):
        q, k, v = _split(qkv, heads)
        b, t, h, d = q.shape
        fold = lambda z: z.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        o = FA._flash_resident(fold(q), fold(k), fold(v), 1, True,
                               FA.pallas_interpret())
        return o.reshape(b, h, t, d).transpose(0, 2, 1, 3).reshape(b, t, h * d)

    out = {"packed": lambda qkv: FA.flash_attention_qkv(qkv, heads, True),
           "folded": folded}

    if parent:
        spec = importlib.util.spec_from_file_location(
            "parent_flash_attention", os.path.join(
                parent, "deeplearning4j_tpu", "ops", "flash_attention.py"))
        old = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(old)

        def parent_fn(qkv):
            q, k, v = _split(qkv, heads)
            o = old.flash_attention(q, k, v, causal=True)
            return o.reshape(qkv.shape[0], qkv.shape[1], -1)
        out["parent"] = parent_fn
    return out


def profile(shape, parent):
    b, h, t, d = shape
    width = h * d
    rng = np.random.default_rng(0)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.02, jnp.bfloat16)
    x, wqkv, wo, r = (mk(b, t, width) * 50, mk(width, 3 * width),
                      mk(width, width), mk(b, t, width))
    print(f"\n== b{b} h{h} t{t} d{d}: path "
          f"{FA.flash_path(t, t, d, jnp.bfloat16, heads=h)}")
    totals = {}
    for name, attend in variants(h, parent).items():
        def loss(x, wqkv, wo):
            with jax.named_scope("qkv_proj"):
                qkv = x @ wqkv
            with jax.named_scope("attention"):
                o = attend(qkv)
            with jax.named_scope("attn_out_proj"):
                return jnp.sum((o @ wo).astype(jnp.float32) * r)
        step = jax.jit(jax.grad(loss, (0, 1, 2)))
        jax.block_until_ready(step(x, wqkv, wo))
        log_dir = os.path.join("chiprun_out", "trace-flash",
                               f"{b}x{h}x{t}x{d}-{name}")
        with profiler.trace(log_dir):
            for _ in range(CALLS):
                out = step(x, wqkv, wo)
            jax.block_until_ready(out)
        groups = collections.Counter()
        for group, _, _, ns in profiler.scoped_self_times(
                profiler.load_trace(log_dir), ()):
            groups[group] += ns / CALLS / 1e6
        totals[name] = sum(groups.values())
        print(f"  {name:8s} {totals[name]:7.3f} ms a call: " + ", ".join(
            f"{g} {ms:.3f}" for g, ms in groups.most_common(9)))
    return totals


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="a checkout of the commit to compare "
                    "with (git archive), for the 'parent' variant")
    ap.add_argument("--shapes", nargs="*", default=CELLS,
                    help="batch,heads,length,head width")
    args = ap.parse_args()
    enable_compile_cache()
    print("device:", jax.devices()[0].device_kind)
    for shape in args.shapes:
        profile(tuple(int(n) for n in shape.split(",")), args.parent)


if __name__ == "__main__":
    main()
