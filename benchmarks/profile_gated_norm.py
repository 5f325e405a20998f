"""Time a gate and a grouped RMSNorm between a scan and its output
projection alone (dev tool).

``ops/gated_norm.py``'s two entries run, on the TPU, the Pallas kernels
of ``ops/pallas/gated_norm.py`` (one frame, a body each); elsewhere the
same lines in ``jax.numpy`` through a view that names a group's
columns, which XLA fuses. This script is where that choice, and the
kernels' blocks, come from: ``--body`` names the body (``gate, norm`` a
Mamba-2 mixer's, at ``nemotron-3-super-120b-a12b-ep64.steady``'s 8
groups of 1,024; ``norm, gate`` a linear-attention layer's heads of
128, with the gate's bias as ``solar`` and ``kimi`` have it or, with
``--no-bias``, without as ``minicpm-sala`` has it), and both paths run
at ``[1, 8192, 8192]`` and ``[1, 16384, 4096]`` in bf16 (``--shapes``),
forward and the gradients' program (``do``, ``dz`` and the vectors'
for a given ``dy``; the plain path's once with the cotangent in bf16,
as the kernel reads it, and once in float32, as XLA kept it in the
whole step), beside the least time the memory allows (6 and 10 bytes a
token and column at 819 GB/s: 0.49 and 0.82 ms) and the share of it
each holds. ``--blocks`` lists the kernels' blocks to try as
``rows:walk/rows:walk`` pairs, forward/backward: a grid step's time
steps and one walk's inside it; ``--lanes`` gives a step another most
lanes than ``BLOCK_LANES`` for the whole run (PR 67's sweep: 1,024 is
within 2% of the best, one head a block 1.8 and 2.9 times slower).

On no cell's path. Only a TPU run says anything:
``chiprun -- python3 benchmarks/profile_gated_norm.py --body "norm,
gate"``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp

from dlrover_tpu.ops.gated_norm import (
    gated_group_norm_plain, head_norm_gate_plain,
)
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


def profile(args, shape):
    """The rows of one shape: the plain path twice, then each block."""
    body = args.body
    heads = body == "norm, gate"
    width = shape[2]
    groups = width // args.group_width if heads else args.groups
    eps = args.eps
    keys = jax.random.split(jax.random.key(0), 5)
    o, z, dy = (
        jax.random.normal(k, shape, jnp.bfloat16) for k in keys[:3])
    scale = 1.0 + 0.1 * jax.random.normal(keys[3], (width,), F32)
    vectors = (scale,)
    if heads:
        vectors = (scale[:args.group_width],)
        if not args.no_bias:
            vectors += (0.5 * jax.random.normal(keys[4], (width,), F32),)
    cells = shape[0] * shape[1] * width
    least = {"forward": 1e3 * 6 * cells / HBM_BYTES_PER_S,
             "gradients": 1e3 * 10 * cells / HBM_BYTES_PER_S}

    def plain(o, z, vectors):
        if heads:
            return head_norm_gate_plain(
                o, z, *(*vectors, None)[:2], eps)
        return gated_group_norm_plain(o, z, *vectors, groups, eps)

    def plain_gradients(o, z, vectors, dy):
        return jax.vjp(plain, o, z, vectors)[1](dy)

    def plain_gradients_f32(o, z, vectors, dy):
        """The cotangent as the whole step's XLA kept it: float32."""
        return jax.vjp(
            lambda *a: plain(*a).astype(F32), o, z, vectors)[1](
                dy.astype(F32))

    paths = [
        ("plain", jax.jit(plain), jax.jit(plain_gradients)),
        ("plain, float32 dy", jax.jit(plain), jax.jit(plain_gradients_f32)),
    ]
    for pair in args.blocks.split(","):
        (fr, fw), (br, bw) = (
            (int(n) for n in half.split(":")) for half in pair.split("/"))
        paths.append((
            f"pallas {pair}",
            jax.jit(lambda o, z, v, r=fr, w=fw: kernels.gated_norm(
                o, z, v, body=body, groups=groups, eps=eps, rows=r, walk=w)),
            jax.jit(lambda o, z, v, dy, r=br, w=bw: kernels.gated_norm(
                o, z, v, dy, body=body, groups=groups, eps=eps, rows=r,
                walk=w)),
        ))
    want = jax.tree.leaves(
        (paths[0][1](o, z, vectors), paths[0][2](o, z, vectors, dy)))
    rows = []
    for name, forward, gradients in paths:
        row = {"body": args.body, "bias": heads and not args.no_bias,
               "path": name, "shape": list(shape), "groups": groups,
               "lanes": kernels.BLOCK_LANES}
        try:
            for kind, fn, operands in (
                    ("forward", forward, (o, z, vectors)),
                    ("gradients", gradients, (o, z, vectors, dy))):
                ms = 1e3 * timed(fn, *operands, n=args.n)
                row[kind + "_ms"] = round(ms, 4)
                row[kind + "_share_of_819_GB_s"] = round(least[kind] / ms, 4)
            got = jax.tree.leaves(
                (forward(o, z, vectors), gradients(o, z, vectors, dy)))
            for key, a, b in zip(
                    ("y", "do", "dz", "dscale", "dbias"), got, want):
                row[key + "_max_off"] = off(a, b)
        except Exception as e:  # a block the compiler refuses
            row["refused"] = str(e)[:300]
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--body", default="gate, norm", choices=list(
        kernels.BODIES))
    ap.add_argument("--no-bias", action="store_true",
                    help="the heads' gate without its learned bias")
    ap.add_argument("--lanes", type=int, default=kernels.BLOCK_LANES,
                    help="the most lanes of a block, for a re-sweep")
    ap.add_argument(
        "--shapes", default="1x8192x8192,1x16384x4096",
        help="batch x seq x width of each shape to time")
    ap.add_argument("--groups", type=int, default=8,
                    help="the mixer's groups")
    ap.add_argument("--group-width", type=int, default=128,
                    help="a head's width")
    ap.add_argument("--eps", type=float, default=1e-5)
    ap.add_argument(
        "--blocks", default="512:64/512:32,256:32/256:32,256:64/256:64,"
        "512:32/512:32,512:128/512:16,1024:64/1024:32")
    ap.add_argument("--n", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/p67/gated_norm.jsonl")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not a TPU: a CPU run times nothing", file=sys.stderr)
        return 1
    kernels.BLOCK_LANES = args.lanes  # before any call is traced
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rows = []
    for shape in args.shapes.split(","):
        rows += profile(args, tuple(int(n) for n in shape.split("x")))
    with open(args.out, "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
