"""Time a delta-rule layer's convolution, ``silu`` and l2 norm alone
(dev tool).

``ops/kda_conv.py conv_silu_norm`` runs, on the TPU, the Pallas
kernels of ``ops/pallas/kda_conv.py``; elsewhere shifted multiply-adds
and the norm through a view in ``jax.numpy``, which XLA fuses. This
script is where that choice, and the kernels' blocks, come from: both
paths at ``solar-open2-250b-ep32.steady``'s shape (``[1, 8192, 64 x
128]`` in bf16, four taps), with the heads' norm (q and k) and without
(v), forward and the gradients' program (``dx`` and ``dw`` for a given
``dy``), beside the least time the memory allows (4 and 6 bytes a
token and channel at 819 GB/s) and the share of it each holds.
``--blocks`` lists the kernels' blocks to try as
``rows:lanes/rows:lanes`` pairs, forward/backward.

On no cell's path. Only a TPU run says anything:
``chiprun -- python3 benchmarks/profile_kda_conv.py``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp

from dlrover_tpu.ops.kda_conv import conv_silu_norm_plain
from dlrover_tpu.ops.pallas import kda_conv as kernels

HBM_BYTES_PER_S = 819e9  # yardstick/peaks.json, "TPU v5 lite"


def timed(fn, *args, n=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def off(got, want):
    return float(jnp.abs(
        got.astype(jnp.float32) - want.astype(jnp.float32)).max())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--taps", type=int, default=4)
    ap.add_argument(
        "--blocks", default="256:1024/128:1024,256:512/128:512,"
        "512:512/256:512,128:1024/64:1024,256:1024/256:1024")
    ap.add_argument("--n", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/kda_conv.jsonl")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not a TPU: a CPU run times nothing", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    keys = jax.random.split(jax.random.key(0), 3)
    shape = (args.batch, args.seq, args.heads * args.head_dim)
    x = jax.random.normal(keys[0], shape, jnp.bfloat16)
    w = (jax.random.normal(keys[1], (shape[2], args.taps))
         * args.taps ** -0.5).astype(jnp.bfloat16)
    dy = jax.random.normal(keys[2], shape, jnp.bfloat16)
    cells = args.batch * args.seq * shape[2]
    least = {"forward": 1e3 * 4 * cells / HBM_BYTES_PER_S,
             "gradients": 1e3 * 6 * cells / HBM_BYTES_PER_S}
    rows = []
    for l2_heads in (args.heads, None):
        def plain_gradients(x, w, dy, l2_heads=l2_heads):
            _, back = jax.vjp(
                lambda x, w: conv_silu_norm_plain(x, w, l2_heads), x, w)
            return back(dy)

        paths = [("plain", jax.jit(
            lambda x, w, h=l2_heads: conv_silu_norm_plain(x, w, h)),
            jax.jit(plain_gradients))]
        for pair in args.blocks.split(","):
            (fr, fl), (br, bl) = (
                (int(n) for n in half.split(":"))
                for half in pair.split("/"))
            paths.append((
                f"pallas {pair}",
                jax.jit(lambda x, w, r=fr, c=fl, h=l2_heads:
                        kernels.kda_conv(
                            x, w, l2_heads=h, rows=r, lanes=c)),
                jax.jit(lambda x, w, dy, r=br, c=bl, h=l2_heads:
                        kernels.kda_conv(
                            x, w, dy, l2_heads=h, rows=r, lanes=c)),
            ))
        want = paths[0][1](x, w), *paths[0][2](x, w, dy)
        for name, forward, gradients in paths:
            row = {"path": name, "l2_heads": l2_heads, "shape": list(shape)}
            try:
                for kind, fn, operands in (
                        ("forward", forward, (x, w)),
                        ("gradients", gradients, (x, w, dy))):
                    ms = 1e3 * timed(fn, *operands, n=args.n)
                    row[kind + "_ms"] = round(ms, 4)
                    row[kind + "_share_of_819_GB_s"] = round(
                        least[kind] / ms, 4)
                got = forward(x, w), *gradients(x, w, dy)
                for key, a, b in zip(("y", "dx", "dw"), got, want):
                    row[key + "_max_off"] = off(a, b)
            except Exception as e:  # a block the compiler refuses
                row["refused"] = str(e)[:300]
            rows.append(row)
            print(json.dumps(row), flush=True)
    with open(args.out, "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
