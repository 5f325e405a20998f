"""Time the selective scan's kernels, and hold them to the plain
path's values (dev tool).

``ops/selective_scan.py selective_scan`` runs, on the TPU, the Pallas
kernels of ``ops/pallas/selective_scan.py``; elsewhere the recurrence
position by position in chunks under a ``lax.scan``. This script times
the kernels at ``jamba2-3b-l14.steady``'s shape (5,120 channels of 16
states at 8,192 positions, ``x``, ``B`` and ``C`` in bf16, the step and
``A`` in float32): the forward, the backward kernel alone over the
entry states that the forward kept, and the two together as a step
differentiates them, with the nanoseconds a position of a grid step's
tile costs and the least time the memory allows beside each
(``yardstick/families/jamba.py selective_scan_step``'s bytes at 819
GB/s); and compares ``o`` and the six gradients with the plain path's
on the first ``--check-channels`` channels, at the rates ``--decay``
lists (the log decay of a step about ``-decay``; 200 underflows).
``--lanes`` sets the channels of a grid step
(``ops/pallas/selective_scan.py LANES``) for a re-sweep.

``--floors`` times the backward kernel with this script's own copy of
its body (``backward_body``; ``ops/`` has no switch) at each width,
parts of the work left out, so that what a position costs whatever the
tile's width and what scales with the channels are numbers and not
differences of a sweep: ``FLOORS`` names them. A floor's gradients are
wrong by what it leaves out; ``the_copy``, which leaves nothing out,
is held to the plain path like the kernels.

One JSON line a reading, on stdout and in
``chiprun_out/profile_selective_scan.jsonl``. On no cell's path. Only a
TPU run says anything: ``chiprun -- python3
benchmarks/profile_selective_scan.py``.
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
from jax.experimental import pallas as pl  # noqa: E402

from dlrover_tpu.ops.pallas import selective_scan as kernels  # noqa: E402
from dlrover_tpu.ops.selective_scan import (  # noqa: E402
    selective_scan, selective_scan_plain,
)

HBM_BYTES_PER_S = 819e9  # yardstick/peaks.json, "TPU v5 lite"
NAMES = ("x", "Delta", "B", "C", "A", "D")




def backward_body(lane_sums="chunk", spreads=True, row_sums=True,
                  walk=True):
    """A copy of ``ops/pallas/selective_scan.py _bwd_kernel`` with parts
    left out:

    - ``lane_sums``: ``"chunk"`` as the kernel has them (a position's
      products summed over the lane tiles into its own lane tile of the
      chunk's scratch, the lanes summed once a chunk); ``"tiles"`` the
      same without the sums across the lanes; ``None`` neither the
      products nor the sums;
    - ``spreads``: off, ``B_t`` and ``C_t`` are not spread along the
      lanes at a chunk's first tile (the loops read the scratch as it
      lies);
    - ``row_sums``: off, the two sums down the sublanes (``du`` and
      ``dDelta``) take one state's row each, unsummed;
    - ``walk``: off, the states are made again and nothing else."""
    F32, LANE, GROUP, CHUNK = (
        kernels.F32, kernels.LANE, kernels.GROUP, kernels.CHUNK)
    row = kernels._row

    def rows_summed(p):
        if row_sums:
            return jnp.sum(p, axis=0, keepdims=True)
        return p[:1]

    def body(x_ref, dl_ref, do_ref, bg_ref, cg_ref, a_ref, d_ref,
             entry_ref, dx_ref, ddl_ref, dbg_ref, dcg_ref, da_ref, dd_ref,
             g_scr, h_scr, u_scr, du_scr, do_scr, b_scr, c_scr, db_scr,
             dc_scr):
        last_chunk = pl.program_id(1) == 0
        tile = pl.program_id(2)

        @pl.when((pl.program_id(0) == 0) & last_chunk)
        def _():
            da_ref[tile] = jnp.zeros(da_ref.shape[1:], F32)
            dd_ref[tile] = jnp.zeros(dd_ref.shape[1:], F32)

        @pl.when(last_chunk)
        def _():
            g_scr[tile] = jnp.zeros(g_scr.shape[1:], F32)

        @pl.when(tile == 0)
        def _():
            db_scr[...] = jnp.zeros_like(db_scr)
            dc_scr[...] = jnp.zeros_like(dc_scr)

            def group(g, _):
                bt, ct = bg_ref[g], cg_ref[g]
                for i in range(GROUP):
                    b_scr[g * GROUP + i] = jnp.broadcast_to(
                        bt[:, i:i + 1], bt.shape)
                    c_scr[g * GROUP + i] = jnp.broadcast_to(
                        ct[:, i:i + 1], ct.shape)

            jax.lax.fori_loop(
                0, CHUNK // GROUP if spreads else 0, group, None)

        x = x_ref[...].astype(F32)
        do = do_ref[...].astype(F32)
        do_scr[...] = do
        u_scr[...] = dl_ref[...] * x
        A = a_ref[...]

        def column(scr, t):
            return jnp.concatenate([scr[t]] * (A.shape[1] // LANE), axis=1)

        def up(g, h):
            for i in range(GROUP):
                t = g * GROUP + i
                h = (jnp.exp(row(dl_ref, t) * A) * h
                     + column(b_scr, t) * row(u_scr, t))
                h_scr[t + 1] = h
            return h

        h_scr[0] = entry_ref[...]
        jax.lax.fori_loop(0, CHUNK // GROUP, up, entry_ref[...])

        def down(k, carry):
            g, dA = carry
            at = CHUNK // GROUP - 1 - k
            for i in reversed(range(GROUP)):
                t = at * GROUP + i
                delta, do_t = row(dl_ref, t), row(do_scr, t)
                g = g + column(c_scr, t) * do_t
                if lane_sums:
                    mine = pl.ds(i * LANE, LANE)
                    dc_scr[at, :, mine] += kernels._over_lane_tiles(
                        h_scr[t + 1] * do_t)
                    db_scr[at, :, mine] += kernels._over_lane_tiles(
                        g * row(u_scr, t))
                du_scr[pl.ds(t, 1), :] = rows_summed(g * column(b_scr, t))
                g = g * jnp.exp(delta * A)
                q = g * h_scr[t]
                dA = dA + q * delta
                ddl_ref[pl.ds(t, 1), :] = rows_summed(q * A)
            return g, dA

        g, dA = jax.lax.fori_loop(
            0, CHUNK // GROUP if walk else 0, down,
            (g_scr[tile], jnp.zeros_like(A)))
        g_scr[tile] = g
        da_ref[tile] += dA
        dd_ref[tile] += jnp.sum(do * x, axis=0, keepdims=True)
        du = du_scr[...]
        ddl_ref[...] = ddl_ref[...] + du * x
        dx_ref[...] = (du * dl_ref[...] + d_ref[...] * do).astype(
            dx_ref.dtype)

        if lane_sums == "chunk":
            @pl.when(tile == pl.num_programs(2) - 1)
            def _():
                dbg_ref[...] = kernels._over_lanes(db_scr)
                dcg_ref[...] = kernels._over_lanes(dc_scr)

    return body


#: name -> (what ``backward_body`` is built with, whether its gradients
#: are the plain path's)
FLOORS = {
    "the_copy": (dict(), True),
    "no_sums_across_lanes": (dict(lane_sums="tiles"), False),
    "no_lane_sums": (dict(lane_sums=None), False),
    "no_spreads": (dict(spreads=False), False),
    "no_row_sums": (dict(row_sums=False), False),
    "no_walk": (dict(walk=False), False),
}


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
    ap.add_argument("--floors", nargs="*", choices=list(FLOORS),
                    help="the backward with parts left out: all, or these")
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

    def backward_alone(ops, do):
        entry = jax.jit(functools.partial(
            kernels.selective_scan, keep_states=True))(*ops)[1]
        return 1e3 * timed(jax.jit(
            lambda ops, entry, do: kernels.selective_scan(
                *ops, entry=entry, do=do)), ops, entry, do)

    def values(ops, do, decay, **said):
        """On the first channels (two of the grid step's tiles at
        least), against the plain path in float32."""
        some = max(args.check_channels, 2 * kernels._lanes(channels))
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
              lanes=kernels._lanes(some), **said, **{
                  name: float(jnp.abs(g - w).max()
                              / (jnp.abs(w).max() + 1e-30))
                  for name, g, w in zip(NAMES, got, want)})

    stock, stock_body = kernels.LANES, kernels._bwd_kernel
    for lanes in args.lanes:
        kernels.LANES = (lanes,) if lanes else stock
        wide = kernels._lanes(channels)
        positions = seq * channels // wide  # of a grid step's tile
        jax.clear_caches()
        forward = jax.jit(selective_scan)
        both = jax.jit(jax.grad(
            lambda ops, do: jnp.sum(
                selective_scan(*ops).astype(jnp.float32)
                * do.astype(jnp.float32))))
        for decay in args.decay:
            ops, do = operands(1, seq, channels, n, decay, jnp.bfloat16)
            fwd_ms = 1e3 * timed(forward, *ops)
            bwd_ms = backward_alone(ops, do)
            both_ms = 1e3 * timed(both, ops, do)
            least = least_ms(seq, channels, n)
            write(
                what="kernels", decay=decay, seq=seq, channels=channels,
                states=n, chunk=kernels.CHUNK, lanes=wide,
                forward_ms=fwd_ms, backward_ms=bwd_ms,
                forward_and_backward_ms=both_ms,
                forward_ns_a_position=1e6 * fwd_ms / positions,
                backward_ns_a_position=1e6 * bwd_ms / positions,
                least_forward_ms=least[0], least_both_ms=least[1],
            )
            values(ops, do, decay)
        if args.floors is None:
            continue
        ops, do = operands(1, seq, channels, n, args.decay[0], jnp.bfloat16)
        for name in args.floors or FLOORS:
            parts, exact = FLOORS[name]
            kernels._bwd_kernel = backward_body(**parts)
            jax.clear_caches()
            bwd_ms = backward_alone(ops, do)
            write(what="floor", floor=name, lanes=wide, backward_ms=bwd_ms,
                  backward_ns_a_position=1e6 * bwd_ms / positions)
            if exact:
                values(ops, do, args.decay[0], floor=name)
            kernels._bwd_kernel = stock_body
    kernels.LANES = stock
    return 0


if __name__ == "__main__":
    sys.exit(main())
