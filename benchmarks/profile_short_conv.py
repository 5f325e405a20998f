"""Time the gated short convolution's two paths (dev tool).

``ops/short_conv.py gated_short_conv`` runs, on the TPU, the Pallas
kernels of ``ops/pallas/short_conv.py``; elsewhere ``taps`` shifted
multiply-adds in ``jax.numpy``, which XLA fuses. This script is where
that choice, and the kernels' block sizes, come from: both paths at
``lfm2-8b-a1b-ep4.steady``'s shape (``[4, 8192, 3 x 2048]`` in bf16,
three taps), forward and the gradients' program (``dbcu`` and
``dw`` for a given ``dy``), with the least time the memory allows
beside each (8 and 14 bytes a token and channel at 819 GB/s).
``--rows`` lists the kernels' blocks of time to try as
``forward:backward`` pairs.

On no cell's path. Only a TPU run says anything:
``chiprun -- python3 benchmarks/profile_short_conv.py``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp

from dlrover_tpu.ops.pallas import short_conv as kernels
from dlrover_tpu.ops.short_conv import gated_short_conv_plain

HBM_BYTES_PER_S = 819e9  # yardstick/peaks.json, "TPU v5 lite"


def timed(fn, *args, n=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--taps", type=int, default=3)
    ap.add_argument("--rows", default="256:128,128:128,128:64,64:64")
    ap.add_argument("--n", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/short_conv.jsonl")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not a TPU: a CPU run times nothing", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    keys = jax.random.split(jax.random.key(0), 3)
    shape = (args.batch, args.seq, args.hidden)
    bcu = jax.random.normal(
        keys[0], (*shape[:2], 3 * args.hidden), jnp.bfloat16)
    w = (jax.random.normal(keys[1], (args.hidden, args.taps))
         * args.taps ** -0.5).astype(jnp.bfloat16)
    dy = jax.random.normal(keys[2], shape, jnp.bfloat16)
    cells = args.batch * args.seq * args.hidden
    least = {"forward_ms": 1e3 * 8 * cells / HBM_BYTES_PER_S,
             "gradients_ms": 1e3 * 14 * cells / HBM_BYTES_PER_S}

    def plain_gradients(bcu, w, dy):
        _, back = jax.vjp(gated_short_conv_plain, bcu, w)
        return back(dy)

    paths = [("plain", jax.jit(gated_short_conv_plain),
              jax.jit(plain_gradients))]
    for pair in args.rows.split(","):
        fwd_rows, bwd_rows = (int(r) for r in pair.split(":"))
        paths.append((
            f"pallas {pair}",
            jax.jit(lambda b, w, r=fwd_rows: kernels.short_conv(
                b, w, rows=r)),
            jax.jit(lambda b, w, dy, r=bwd_rows: kernels.short_conv(
                b, w, dy, rows=r)),
        ))
    want = plain_gradients(bcu, w, dy)
    with open(args.out, "a") as f:
        for name, forward, gradients in paths:
            row = {"path": name, "shape": list(bcu.shape), **{
                "least_" + k: round(v, 4) for k, v in least.items()}}
            try:
                row["forward_ms"] = 1e3 * timed(forward, bcu, w, n=args.n)
                row["gradients_ms"] = 1e3 * timed(
                    gradients, bcu, w, dy, n=args.n)
                got = gradients(bcu, w, dy)
                row["dbcu_max_off"] = float(jnp.abs(
                    got[0].astype(jnp.float32)
                    - want[0].astype(jnp.float32)).max())
                row["dw_max_off"] = float(jnp.abs(
                    got[1].astype(jnp.float32)
                    - want[1].astype(jnp.float32)).max())
            except Exception as e:  # a block the compiler refuses
                row["refused"] = str(e)[:300]
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
