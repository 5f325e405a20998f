"""Time the selective scan's kernels, and hold them to the plain
path's values (dev tool).

``ops/selective_scan.py selective_scan`` runs, on the TPU, the Pallas
kernels of ``ops/pallas/selective_scan.py``; elsewhere the recurrence
position by position in chunks under a ``lax.scan``. This script times
the kernels at ``jamba2-3b-l14.steady``'s shape (5,120 channels of 16
states at 8,192 positions, ``x``, ``B`` and ``C`` in bf16, the step and
``A`` in float32): the forward, and the forward that keeps the chunks'
entry states with the backward over them, with the nanoseconds a
position of a 128-channel tile costs and the least time the memory
allows beside each (``yardstick/families/jamba.py
selective_scan_step``'s bytes at 819 GB/s); and compares ``o`` and the
six gradients with the plain path's on the first ``--check-channels``
channels, at the rates ``--decay`` lists (the log decay of a step about
``-decay``; 200 underflows). ``--lanes`` sets the channels of a grid
step (``ops/pallas/selective_scan.py LANES``) for a re-sweep.

One JSON line a reading, on stdout and in
``chiprun_out/profile_selective_scan.jsonl``. On no cell's path. Only a
TPU run says anything: ``chiprun -- python3
benchmarks/profile_selective_scan.py``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.ops.pallas import selective_scan as kernels  # noqa: E402
from dlrover_tpu.ops.selective_scan import (  # noqa: E402
    selective_scan, selective_scan_plain,
)

HBM_BYTES_PER_S = 819e9  # yardstick/peaks.json, "TPU v5 lite"
NAMES = ("x", "Delta", "B", "C", "A", "D")


def timed(fn, *args, n=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def operands(batch, seq, channels, n, decay, dtype, seed=0):
    """Rows as a mixer's convolution and second projection leave them,
    a step whose log decay ``A Delta`` is about ``-decay``."""
    keys = jax.random.split(jax.random.key(seed), 6)
    x = jax.nn.silu(jax.random.normal(keys[0], (batch, seq, channels)))
    B, C = (jax.random.normal(key, (batch, seq, n)) for key in keys[1:3])
    delta = jax.nn.softplus(jax.random.normal(keys[3], x.shape))
    A = -decay * jax.random.uniform(keys[4], (channels, n), minval=0.2)
    D = jnp.ones((channels,))
    do = jax.random.normal(keys[5], x.shape).astype(dtype)
    return (x.astype(dtype), delta, B.astype(dtype), C.astype(dtype), A,
            D), do


def least_ms(seq, channels, n):
    """The family's count for one layer: ``(forward, both)`` ms."""
    x_like, bc_like = 2 * seq * channels, 2 * seq * n
    forward = 3 * x_like + 2 * bc_like
    backward = 5 * x_like + 4 * bc_like
    return (1e3 * forward / HBM_BYTES_PER_S,
            1e3 * (forward + backward) / HBM_BYTES_PER_S)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--channels", type=int, default=5120)
    ap.add_argument("--states", type=int, default=16)
    ap.add_argument("--decay", type=float, nargs="+",
                    default=[0.1, 2.0, 200.0])
    ap.add_argument("--lanes", type=int, nargs="+", default=[0])
    ap.add_argument("--check-channels", type=int, default=512)
    ap.add_argument(
        "--out", default="chiprun_out/profile_selective_scan.jsonl")
    args = ap.parse_args(argv)
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit("no TPU: the kernels are timed on the chip")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def write(**row):
        line = json.dumps({"platform": platform, **row})
        print(line, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")

    seq, channels, n = args.seq, args.channels, args.states
    tiles = seq * channels // 128
    stock = kernels.LANES
    for lanes in args.lanes:
        kernels.LANES = (lanes,) if lanes else stock
        jax.clear_caches()
        forward = jax.jit(selective_scan)
        both = jax.jit(jax.grad(
            lambda ops, do: jnp.sum(
                selective_scan(*ops).astype(jnp.float32)
                * do.astype(jnp.float32))))
        for decay in args.decay:
            ops, do = operands(1, seq, channels, n, decay, jnp.bfloat16)
            fwd_ms = 1e3 * timed(forward, *ops)
            both_ms = 1e3 * timed(both, ops, do)
            least = least_ms(seq, channels, n)
            write(
                what="kernels", decay=decay, seq=seq, channels=channels,
                states=n, chunk=kernels.CHUNK,
                lanes=kernels._lanes(channels),
                forward_ms=fwd_ms, forward_and_backward_ms=both_ms,
                forward_ns_a_tile_position=1e6 * fwd_ms / tiles,
                both_ns_a_tile_position=1e6 * both_ms / tiles,
                least_forward_ms=least[0], least_both_ms=least[1],
            )
            # the values, on the first channels, against the plain path
            some = args.check_channels
            cut = tuple(a.astype(jnp.float32) for a in (
                ops[0][..., :some], ops[1][..., :some], ops[2], ops[3],
                ops[4][:some], ops[5][:some]))
            do_cut = do[..., :some].astype(jnp.float32)

            def through(f):
                return jax.jit(jax.value_and_grad(
                    lambda o: jnp.sum(f(*o) * do_cut)))(cut)

            (_, got), (_, want) = (
                through(selective_scan), through(selective_scan_plain))
            write(what="values", decay=decay, float32=True,
                  lanes=kernels._lanes(some), **{
                      name: float(jnp.abs(g - w).max()
                                  / (jnp.abs(w).max() + 1e-30))
                      for name, g, w in zip(NAMES, got, want)})
    kernels.LANES = stock
    return 0


if __name__ == "__main__":
    sys.exit(main())
