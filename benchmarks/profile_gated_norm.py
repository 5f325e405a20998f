"""Time a Mamba-2 mixer's gate and grouped RMSNorm alone (dev tool).

``ops/gated_norm.py gated_group_norm`` runs, on the TPU, the Pallas
kernels of ``ops/pallas/gated_norm.py``; elsewhere the same lines in
``jax.numpy`` through a view that names a group's columns, which XLA
fuses. This script is where that choice, and the kernels' blocks, come
from: both paths at ``nemotron-3-super-120b-a12b-ep64.steady``'s shape
(``[1, 8192, 8 x 1024]`` in bf16), forward and the gradients' program
(``do``, ``dz`` and ``d scale`` for a given ``dy``; the plain path's
once with the cotangent in bf16, as the kernel reads it, and once in
float32, as XLA kept it in the whole step), beside the least time the
memory allows (6 and 10 bytes a token and column at 819 GB/s: 0.49 and
0.82 ms) and the share of it each holds. ``--blocks`` lists the
kernels' blocks to try as ``rows:walk/rows:walk`` pairs,
forward/backward: a grid step's time steps and one walk's inside it.

On no cell's path. Only a TPU run says anything:
``chiprun -- python3 benchmarks/profile_gated_norm.py``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp

from dlrover_tpu.ops.gated_norm import gated_group_norm_plain
from dlrover_tpu.ops.pallas import gated_norm as kernels

HBM_BYTES_PER_S = 819e9  # yardstick/peaks.json, "TPU v5 lite"
F32 = jnp.float32


def timed(fn, *args, n=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def off(got, want):
    return float(jnp.abs(got.astype(F32) - want.astype(F32)).max())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--group-width", type=int, default=1024)
    ap.add_argument("--eps", type=float, default=1e-5)
    ap.add_argument(
        "--blocks", default="512:64/512:32,256:32/256:32,256:16/256:16,"
        "256:64/256:64,128:32/128:32,512:32/512:32,512:128/512:16,64:32/64:32")
    ap.add_argument("--n", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/gated_norm.jsonl")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not a TPU: a CPU run times nothing", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    keys = jax.random.split(jax.random.key(0), 4)
    groups, eps = args.groups, args.eps
    shape = (args.batch, args.seq, groups * args.group_width)
    o, z, dy = (
        jax.random.normal(k, shape, jnp.bfloat16) for k in keys[:3])
    scale = 1.0 + 0.1 * jax.random.normal(keys[3], shape[-1:], F32)
    cells = args.batch * args.seq * shape[2]
    least = {"forward": 1e3 * 6 * cells / HBM_BYTES_PER_S,
             "gradients": 1e3 * 10 * cells / HBM_BYTES_PER_S}

    def plain(o, z, scale):
        return gated_group_norm_plain(o, z, scale, groups, eps)

    def plain_gradients(o, z, scale, dy):
        return jax.vjp(plain, o, z, scale)[1](dy)

    def plain_gradients_f32(o, z, scale, dy):
        """The cotangent as the whole step's XLA kept it: float32."""
        return jax.vjp(
            lambda *a: plain(*a).astype(F32), o, z, scale)[1](dy.astype(F32))

    paths = [
        ("plain", jax.jit(plain), jax.jit(plain_gradients)),
        ("plain, float32 dy", jax.jit(plain), jax.jit(plain_gradients_f32)),
    ]
    for pair in args.blocks.split(","):
        (fr, fw), (br, bw) = (
            (int(n) for n in half.split(":")) for half in pair.split("/"))
        paths.append((
            f"pallas {pair}",
            jax.jit(lambda o, z, scale, r=fr, w=fw: kernels.gated_norm(
                o, z, scale, groups=groups, eps=eps, rows=r, walk=w)),
            jax.jit(lambda o, z, scale, dy, r=br, w=bw: kernels.gated_norm(
                o, z, scale, dy, groups=groups, eps=eps, rows=r, walk=w)),
        ))
    want = paths[0][1](o, z, scale), *paths[0][2](o, z, scale, dy)
    rows = []
    for name, forward, gradients in paths:
        row = {"path": name, "shape": list(shape), "groups": groups}
        try:
            for kind, fn, operands in (
                    ("forward", forward, (o, z, scale)),
                    ("gradients", gradients, (o, z, scale, dy))):
                ms = 1e3 * timed(fn, *operands, n=args.n)
                row[kind + "_ms"] = round(ms, 4)
                row[kind + "_share_of_819_GB_s"] = round(least[kind] / ms, 4)
            got = forward(o, z, scale), *gradients(o, z, scale, dy)
            for key, a, b in zip(("y", "do", "dz", "dscale"), got, want):
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
