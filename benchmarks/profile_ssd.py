"""Time the state-space scan's kernels, and hold them to the plain
chunked path's values (dev tool).

``ops/ssd.py ssd_scan`` runs, on the TPU, the Pallas kernels of
``ops/pallas/ssd.py``; elsewhere the chunked equations under a
``lax.scan``. This script times the kernels at
``nemotron-3-super-120b-a12b-ep64.steady``'s shape (128 heads of 64 in
8 groups of 128 states at 8,192 positions in bf16, the step and the
rate in float32): the forward, and the forward that keeps the chunks'
entry states with the backward over them, with the microseconds a
chunk of one head costs and the least time the memory allows beside
each (``yardstick/families/nemotron.py ssd_step``'s bytes at 819
GB/s); and compares ``o`` and the six gradients with the plain path's
on ``--check-groups`` of the groups, at the rates ``--decay`` lists
(the log decay of a step about ``-decay``).

One JSON line a reading, on stdout and in
``chiprun_out/profile_ssd.jsonl``. On no cell's path. Only a TPU run
says anything: ``chiprun -- python3 benchmarks/profile_ssd.py``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.ops.pallas import ssd as kernels  # noqa: E402
from dlrover_tpu.ops.ssd import CHUNK, ssd_plain, ssd_scan  # noqa: E402

HBM_BYTES_PER_S = 819e9  # yardstick/peaks.json, "TPU v5 lite"
NAMES = ("x", "B", "C", "Delta", "A", "D")


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def operands(batch, seq, heads, groups, p, n, decay, dtype, seed=0):
    """Rows as a mixer's convolution leaves them, a step whose log
    decay ``A Delta`` is about ``-decay``."""
    keys = jax.random.split(jax.random.key(seed), 7)
    x = jax.nn.silu(jax.random.normal(keys[0], (batch, seq, heads * p)))
    B, C = (jax.nn.silu(jax.random.normal(key, (batch, seq, groups * n)))
            for key in keys[1:3])
    dt = jax.nn.softplus(jax.random.normal(keys[3], (batch, seq, heads)))
    A = -decay * jax.random.uniform(keys[4], (heads,), minval=0.2)
    D = jnp.ones((heads,))
    do = jax.random.normal(keys[5], x.shape).astype(dtype)
    return (x.astype(dtype), B.astype(dtype), C.astype(dtype), dt, A, D), do


def least_ms(seq, heads, groups, p, n):
    """The family's count for one layer: ``(forward, both)`` ms."""
    x_like, bc_like, dt_like = (
        2 * seq * heads * p, 2 * seq * groups * n, 4 * seq * heads)
    forward = 2 * x_like + 2 * bc_like + dt_like
    both = forward + 3 * x_like + 4 * bc_like + 2 * dt_like
    return (1e3 * forward / HBM_BYTES_PER_S, 1e3 * both / HBM_BYTES_PER_S)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=128)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--states", type=int, default=128)
    ap.add_argument("--decay", type=float, nargs="+", default=[0.1, 2.0, 30.0])
    ap.add_argument("--check-groups", type=int, default=1)
    ap.add_argument("--out", default="chiprun_out/profile_ssd.jsonl")
    args = ap.parse_args(argv)
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit("no TPU: the kernels are timed on the chip")
    shape = (args.heads, args.groups, args.head_dim, args.states)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def write(**row):
        line = json.dumps({"platform": platform, **row})
        print(line, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")

    heads, groups = args.heads, args.groups
    forward = jax.jit(
        lambda *o: ssd_scan(*o, heads=heads, groups=groups))
    both = jax.jit(jax.grad(
        lambda ops, do: jnp.sum(
            ssd_scan(*ops, heads=heads, groups=groups).astype(jnp.float32)
            * do.astype(jnp.float32))))
    chunks = args.seq // CHUNK
    for decay in args.decay:
        ops, do = operands(1, args.seq, *shape, decay, jnp.bfloat16)
        fwd_ms = 1e3 * timed(forward, *ops)
        both_ms = 1e3 * timed(both, ops, do)
        least = least_ms(args.seq, *shape)
        write(
            what="kernels", decay=decay, seq=args.seq, heads=heads,
            groups=groups, heads_a_step=kernels.heads_a_step(heads, groups),
            forward_ms=fwd_ms, forward_and_backward_ms=both_ms,
            forward_us_a_head_chunk=1e3 * fwd_ms / (heads * chunks),
            both_us_a_head_chunk=1e3 * both_ms / (heads * chunks),
            least_forward_ms=least[0], least_both_ms=least[1],
        )
        # the values, on the first groups' heads, against the plain path
        per = heads // groups
        some = args.check_groups
        cut = tuple(
            a[..., :some * width] if a.ndim == 3 else a[:some * per]
            for a, width in zip(ops, (
                per * args.head_dim, args.states, args.states, per, 0, 0)))
        cut = tuple(a.astype(jnp.float32) for a in cut)
        do_cut = do[..., :some * per * args.head_dim].astype(jnp.float32)

        def plain(*o):
            def apart(a, by):
                return a.reshape(*a.shape[:2], by, -1)

            x, B, C, dt, A, D = o
            # float32 products at full precision, as the kernels'
            # float32 products are (the chip's default is one bf16
            # pass, which alone reads 2e-3 off)
            with jax.default_matmul_precision("highest"):
                return ssd_plain(
                    apart(x, some * per), apart(B, some), apart(C, some),
                    dt, A, D).reshape(x.shape)

        def through(f):
            return jax.jit(jax.value_and_grad(
                lambda o: jnp.sum(f(*o) * do_cut)))(cut)

        (_, got), (_, want) = (
            through(lambda *o: ssd_scan(
                *o, heads=some * per, groups=some)), through(plain))
        write(what="values", decay=decay, float32=True, **{
            name: float(jnp.abs(g - w).max() / (jnp.abs(w).max() + 1e-30))
            for name, g, w in zip(NAMES, got, want)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
