"""Time the gated delta rule's kernels, and hold them to the plain
chunked path's values (dev tool).

``ops/delta_rule.py gated_delta_rule`` runs, on the TPU, the Pallas
kernels of ``ops/pallas/delta_rule.py``; elsewhere the chunked
equations under a ``lax.scan``. This script times the kernels at
``solar-open2-250b-ep32.steady``'s shape (64 heads of 128 at 8,192
positions in bf16, the log decay in float32): the forward, the
forward that keeps the chunks' entry states with the backward over
them, with the least time the memory allows beside each
(``yardstick/families/solar.py delta_rule_step``'s bytes at 819
GB/s). Each on rows ``[1, 8192, 8192]``, which is what the step
calls (``gated_delta_rule_rows``), and beside it through the 4-D
entry on ``[1, 8192, 64, 128]``, whose fold to rows is a pass over
every operand and result on the chip; and compares ``o`` and the
five gradients with the plain path's on ``--check-heads`` of the
heads (the plain path holds ``[heads, 64, 64, 128]`` float32 a
chunk), at the decays ``--decay`` lists (``g`` uniform in ``-decay x
[0.2, 1]``). The entry ``gated_delta_rule`` takes no step under
``G_FLOOR`` (-10): a decay of 20 is compared through the entry, which
is what the kernels' exactness rests on there.

On no cell's path. Only a TPU run says anything:
``chiprun -- python3 benchmarks/profile_delta_rule.py``.
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.ops.delta_rule import (  # noqa: E402
    gated_delta_rule, gated_delta_rule_plain, gated_delta_rule_rows,
)
from dlrover_tpu.ops.pallas import delta_rule as kernels  # noqa: E402

HBM_BYTES_PER_S = 819e9  # yardstick/peaks.json, "TPU v5 lite"
NAMES = ("q", "k", "v", "g", "beta")


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def operands(batch, seq, heads, decay, dtype, seed=0):
    keys = jax.random.split(jax.random.key(seed), 6)
    shape = (batch, seq, heads, kernels.HEAD)
    # keys that resemble each other, as silu's leave them
    q, k = (jax.nn.silu(jax.random.normal(key, shape)) for key in keys[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.nn.silu(jax.random.normal(keys[2], shape))
    g = -decay * jax.random.uniform(keys[3], shape, minval=0.2)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    do = jax.random.normal(keys[5], shape).astype(dtype)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), do


def gradients_of(fn):
    def gradients(args, do):
        _, back = jax.vjp(fn, *args)
        return back(do)

    return jax.jit(gradients)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--check-heads", type=int, default=4)
    ap.add_argument("--check-seq", type=int, default=2048)
    ap.add_argument("--decay", type=float, nargs="+",
                    default=[0.3, 5.0, 20.0])
    ap.add_argument("--n", type=int, default=10,
                    help="calls timed; 0 skips the timing")
    ap.add_argument("--out", default="chiprun_out/delta_rule.jsonl")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not a TPU: a CPU run times nothing", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    column = args.batch * args.seq * args.heads * kernels.HEAD
    betas = 4 * args.batch * args.seq * args.heads
    least = {
        "forward_ms": 1e3 * ((8 + 4) * column + betas) / HBM_BYTES_PER_S,
        "gradients_ms": 1e3 * (
            (8 + 4) * column + betas + (6 + 4) * column + betas
        ) / HBM_BYTES_PER_S,
    }
    rows = []
    ops, do = operands(args.batch, args.seq, args.heads, 0.3, jnp.bfloat16)
    row = {"what": "kernels timed", "shape": list(ops[0].shape),
           "chunk": kernels.CHUNK, "sub": kernels.SUB,
           **{"least_" + k: round(v, 4) for k, v in least.items()}}
    if args.n:
        flat = (*(x.reshape(*x.shape[:2], -1) for x in ops[:4]), ops[4])
        on_rows = functools.partial(gated_delta_rule_rows, heads=args.heads)
        row["forward_ms"] = 1e3 * timed(jax.jit(on_rows), *flat, n=args.n)
        row["forward_keeping_states_ms"] = 1e3 * timed(jax.jit(
            lambda *a: kernels.delta_rule(*a, keep_states=True)),
            *flat, n=args.n)
        row["forward_and_gradients_ms"] = 1e3 * timed(
            gradients_of(on_rows), flat, do.reshape(flat[2].shape),
            n=args.n)
        row["forward_from_heads_ms"] = 1e3 * timed(
            jax.jit(gated_delta_rule), *ops, n=args.n)
        row["forward_and_gradients_from_heads_ms"] = 1e3 * timed(
            gradients_of(gated_delta_rule), ops, do, n=args.n)
        rows.append(row)
    for dtype in (jnp.float32, jnp.bfloat16):
        for decay in args.decay:
            ops, do = operands(
                args.batch, args.check_seq, args.check_heads, decay, dtype,
                seed=1)
            # the plain path's float32 products at the highest
            # precision: the chip's default rounds them to bfloat16
            with jax.default_matmul_precision("highest"):
                want_o = gated_delta_rule_plain(
                    *(x.astype(jnp.float32) for x in ops))
                want = gradients_of(gated_delta_rule_plain)(
                    tuple(x.astype(jnp.float32) for x in ops),
                    do.astype(jnp.float32))
            got_o = gated_delta_rule(*ops)
            got = gradients_of(gated_delta_rule)(ops, do)
            row = {"what": "kernels against the plain path",
                   "dtype": jnp.dtype(dtype).name, "decay": decay,
                   "least_g": float(ops[3].min()),
                   "shape": list(ops[0].shape)}

            def off(a, b):
                scale = float(jnp.abs(b).max())
                return float(jnp.abs(
                    a.astype(jnp.float32) - b).max()) / scale

            row["o_off"] = off(got_o, want_o)
            for name, a, b in zip(NAMES, got, want):
                row[f"d{name}_off"] = off(a, b)
            row["finite"] = all(
                bool(jnp.isfinite(x.astype(jnp.float32)).all())
                for x in (got_o, *got))
            rows.append(row)
    with open(args.out, "a") as f:
        for row in rows:
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
