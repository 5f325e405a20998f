"""Time one expert layer that holds a share of its experts (dev tool).

``parallel/moe.py dropless_moe_mlp`` walks a share's sorted
assignments in chunks of ``CHUNK_ROWS`` and does a chunk's work only
where the chunk starts before the held experts' rows end. This script
is where that constant comes from: the layer alone at
``smallthinker-21b-a3b-ep4``'s shapes (16,384 tokens of 2560, top-6 of
64 experts of 768, ReLU gate, 16 held), forward and forward-and-
backward (the gradients of the input, the logits and the three
matrices), for every share of the assignments on the held experts in
``--shares`` and every chunk in ``--chunks`` (rows as given: the
script sets ``CHUNK_WIDTH`` to its own rows' width). ``--hidden 2048
--mlp 1792 --experts 32 --top 4 --held 8 --tokens 32768 --act silu``
are ``lfm2-8b-a1b-ep4``'s. The router's logits are
drawn once and the held experts' columns shifted until the share is
the one asked for (``"held_share"`` in a row is what came out;
``"live_chunks"`` of ``"chunks"`` what the walk then does).

``--held 64`` times the one pass over every row that a layer with all
of the router's experts takes; ``--shares 1`` the walk with every
chunk live, the same products in pieces. In a checkout from before the
walk the same command times the one pass over all ``tokens x k`` rows
that a share took then (``"walk": false`` in its rows).

``--autodiff`` adds, for every share and chunk, the walk as JAX
differentiates it: ``parallel/moe.py _walk`` without its hand-written
backward pass, the rows' sum into their tokens given the gather as
its transpose, a turn of the loop once kept whole (``"backward":
"autodiff"``) and once made again under ``jax.checkpoint``
(``"autodiff_remat"``). ``"planned_bytes"`` in a row is the compiled
gradient program's ``peak_memory_in_bytes``.

The layer alone before and after the backward walk's in-place sums
stopped visiting the experts a chunk holds no row of (PERF.md, PR 39;
one v5e chip, the parent ``f484514`` beside the change in one call,
held share 0.25, ``--n 20``; forward / the gradients' program, ms):
at ``lfm2-8b-a1b-ep4``'s shapes and 10,240 rows a chunk (4 live of
13) 12.47 / 30.05 -> 12.13 / 26.46; at ``smallthinker-21b-a3b-ep4``'s
and 8,192 (3 live of 12) 6.51 / 15.66 -> 6.51 / 13.44.

On no cell's path. Only a TPU run says anything:
``chiprun -- python3 benchmarks/profile_moe_share.py``.
"""

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.parallel import moe



@contextlib.contextmanager
def backward(how):
    """While a layer is traced: ``"walk"`` leaves it as it is;
    ``"autodiff"`` has JAX differentiate ``moe._walk`` in place of
    the hand-written ``moe._walk_held_rows`` (scan and cond transpose,
    the grouped matmuls have their own rule, and ``add_rows`` gets
    the one its scatter-add has: the cotangent's rows of those
    indices); ``"autodiff_remat"`` also makes a turn of the loop
    again in the backward pass instead of keeping what it made."""
    if how == "walk":
        yield
        return
    from dlrover_tpu.ops import grouped_matmul as gm

    real = gm.add_rows, moe._over_live_chunks, moe._walk_held_rows

    @jax.custom_vjp
    def add_rows(out, index, rows):
        return real[0](out, index, rows)

    add_rows.defvjp(
        lambda out, index, rows: (
            real[0](out, index, rows), (index, rows[:0])
        ),
        lambda kept, g: (g, None, g[kept[0]].astype(kept[1].dtype)),
    )

    def loop(live, *rest):
        return real[1](
            jax.checkpoint(live) if how == "autodiff_remat" else live,
            *rest,
        )

    gm.add_rows, moe._over_live_chunks, moe._walk_held_rows = (
        add_rows, loop, moe._walk
    )
    try:
        yield
    finally:
        gm.add_rows, moe._over_live_chunks, moe._walk_held_rows = real


def timeit(fn, *args, n=10, warmup=2):
    """Mean wall-clock seconds per call."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def held_share(logits, shift, held, top):
    """The share of the top-k assignments on experts ``0 .. held - 1``
    with their logits shifted by ``shift``."""
    moved = logits.copy()
    moved[:, :held] += shift
    chosen = np.argpartition(-moved, top - 1, axis=-1)[:, :top]
    return float((chosen < held).mean())


def shift_for(logits, share, held, top):
    """The shift that puts ``share`` of the assignments on the held
    experts, by bisection (the share rises with the shift)."""
    if share <= 0 or share >= 1:
        return 100.0 if share >= 1 else -100.0
    low, high = -20.0, 20.0
    for _ in range(40):
        mid = (low + high) / 2
        if held_share(logits, mid, held, top) < share:
            low = mid
        else:
            high = mid
    return (low + high) / 2


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shares", default="0.1,0.25,0.35,0.5,1")
    ap.add_argument("--chunks", default="2048,4096,8192,12288")
    ap.add_argument("--held", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--hidden", type=int, default=2560)
    ap.add_argument("--mlp", type=int, default=768)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--top", type=int, default=6)
    ap.add_argument("--act", default="relu")
    ap.add_argument("--n", type=int, default=10)
    ap.add_argument("--autodiff", action="store_true")
    ap.add_argument("--out", default="chiprun_out/moe_share.jsonl")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not a TPU: a CPU run times nothing", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rng = np.random.default_rng(0)
    hidden, mlp, experts, top = (
        args.hidden, args.mlp, args.experts, args.top
    )
    walk = hasattr(moe, "walk_chunks") and args.held < experts
    if hasattr(moe, "CHUNK_WIDTH"):
        moe.CHUNK_WIDTH = hidden  # ``--chunks`` are rows at this width
    rows = args.tokens * top
    chunks = [int(c) for c in args.chunks.split(",")] if walk else [None]
    drawn = rng.standard_normal((args.tokens, experts)).astype(np.float32)
    x = jnp.asarray(
        rng.standard_normal((1, args.tokens, hidden)), jnp.bfloat16
    )
    router = jnp.zeros((hidden, experts), jnp.bfloat16)  # logits given
    w_gate, w_up, w_down = (
        jnp.asarray(
            rng.standard_normal((args.held, *shape)) * shape[0] ** -0.5,
            jnp.bfloat16,
        )
        for shape in ((hidden, mlp), (hidden, mlp), (mlp, hidden))
    )

    for share in (float(s) for s in args.shares.split(",")):
        shift = shift_for(drawn, share, args.held, top)
        logits = drawn.copy()
        logits[:, :args.held] += shift
        came_out = held_share(drawn, shift, args.held, top)
        for chunk in chunks:
            if walk:
                moe.CHUNK_ROWS = chunk

            operands = (
                x, jnp.asarray(logits)[None], w_gate, w_up, w_down
            )
            for how in (
                ("walk", "autodiff", "autodiff_remat")
                if walk and args.autodiff else ("walk",)
            ):
                row = {"share": share, "held_share": came_out,
                       "held": args.held, "walk": walk}
                if walk:
                    row.update(
                        chunk=chunk, chunks=-(-rows // chunk),
                        live_chunks=-(-round(came_out * rows) // chunk),
                        backward=how,
                    )
                # new functions each time: the constant and the backward
                # pass are read at tracing, and a function is traced once
                # for its shapes
                def layer(x, logits, w_gate, w_up, w_down):
                    return moe.dropless_moe_mlp(
                        x, router, w_gate, w_up, w_down, k=top,
                        norm_topk_prob=True, logits=logits, act=args.act,
                    )

                def loss(*operands):
                    out, aux = layer(*operands)  # noqa: B023
                    return out.astype(jnp.float32).mean() + aux

                with backward(how):
                    fwd = jax.jit(layer).lower(*operands).compile()
                    both = jax.jit(
                        jax.grad(loss, argnums=(0, 1, 2, 3, 4))
                    ).lower(*operands).compile()
                row["planned_bytes"] = (
                    both.memory_analysis().peak_memory_in_bytes
                )
                row["fwd_ms"] = 1e3 * timeit(fwd, *operands, n=args.n)
                row["fwd_bwd_ms"] = 1e3 * timeit(
                    both, *operands, n=args.n
                )
                print(json.dumps(row), flush=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
