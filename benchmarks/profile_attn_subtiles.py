"""Sweep the causal sub-tile of the flash-attention kernels (dev tool).

``ops/pallas/flash_attention.py`` walks a grid block that straddles
the diagonal in square sub-tiles whose edge ``_sub_tiles`` gives for
each of its three kernels. This script is how those edges were found: at
the shapes the yardstick's cells run, at the blocks the static
heuristic gives them, it times the forward alone for every edge of
``--subs`` in the forward kernel, and forward-and-backward (the
forward whole) for every edge in the dq and in the dk/dv kernel; no
edge is the whole-block body. Beside them, forward and forward-and-
backward of what the file's own rule gives (``"rule": true``) and of
the whole-block body at each smaller pair of grid blocks of
``--blocks``: what the grid's own skipping gives with no walk at all.
And the rule's kernels with the backward the other way than
``_one_backward_kernel`` has it for the shape: the dq and dk/dv pair
(``"backward_kernels": 2``) against the one kernel (``1``) that keeps
in VMEM a head's dQ without a group, a kv head's dK and dV with one:
how that rule's edges were read.
A shape with a window (``smallthinker``: 16,384 positions, 28 query
heads on 4, the window 4096; ``trinity-mini``: 32 on 4, 2048) is timed
at the rule's blocks with the rule's backward: without the window,
and with it at every edge of ``--tiles`` for the column tiles in which
the forward and the backward kernels take a block that an edge of the
band crosses (``flash_attention._window_tile``; ``whole`` is the
block: the band's compact grid and nothing else; ``rule`` the file's
own edge for each kernel), at ``block_k = 512`` whole blocks (the
control that adds no body), and once with the other backward at the
rule's tiles (``"window"`` and ``"window_tile"`` in the row). A tree
from before the band's grid has no such rule: run it with ``--tiles
whole`` and its rows are its own kernels (``--label`` names the tree
in every row). Its batch of one sequence runs ``--layers`` calls like
the others.
``--key-blocks`` sweeps the forward kernel's key block alone
(``flash_attention_tpu(fwd_block_k=)``, the backward at the rule's
pair): forward, and forward-and-backward, at every width that tiles the
sequence, beside the row's live grid blocks a kv head and its
row-steps (one query row in one live grid step, over the call), so
that what a row-step costs is read, not inferred
(``"ns_a_row_step"``). ``--bwd-key-blocks`` sweeps the backward's key
block the same way, the forward at the rule's, and ``--fwd-vmem-mib``
what the forward states of VMEM at the rule's key block (the limit is
not only a ceiling: PR 37). The two shapes at a
group of 16 (``nemotron``: 8,192 positions; ``minicpm-sala``: 16,384,
with a seeded selection of 64 blocks of 64 keys a query, block 0 and
the 32 nearest forced, whose words hold at most 2,048 keys: wider is
left out) are what ``ops/tuning.py forward_key_block`` was read at.
``kimi`` is latent attention's call at 16,384 positions, q and k in
parts of 128 and 64 columns, v 128 wide: its rows with the backward
the other way are the one kernel with a head's dQ resident against
the pair.
Each timed call runs ``--layers`` attention calls in one ``lax.scan``
so that the host's clock times tens of milliseconds. Only a TPU run
says anything: ``chiprun -- python3 benchmarks/profile_attn_subtiles.py``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.ops import tuning
from dlrover_tpu.ops.pallas import flash_attention as fa

#: name: (batch, seq, heads, kv_heads, head_dim) of a cell's step.
#: A group is never sub-tiled (``flash_attention._fits``): no edge is
#: swept at Mistral's, ``smallthinker``'s or ``lfm2``'s. Mistral's
#: sub-tile row in PERF.md, and the rolled-loop, ``--unroll`` and
#: ``--scratch-state`` readings there, came from earlier versions of
#: this script and of the kernel file that are not in the tree
SHAPES = {
    "gpt2-xl": (12, 1024, 25, 25, 64),
    "olmoe": (3, 4096, 16, 16, 128),
    "mistral": (3, 4096, 32, 8, 128),
    "smallthinker": (1, 16384, 28, 4, 128),
    "lfm2": (4, 8192, 32, 8, 64),
    "trinity-mini": (1, 16384, 32, 4, 128),
    "nemotron": (1, 8192, 32, 2, 128),
    "minicpm-sala": (1, 16384, 32, 2, 128),
    "kimi": (1, 16384, 32, 32, 128),
    "ouro": (1, 8192, 16, 16, 128),
}
#: the window of a shape's windowed layers
WINDOWS = {"smallthinker": 4096, "trinity-mini": 2048}
#: the width of the rotated parts of q and k that a shape hands beside
#: its ``head_dim`` un-rotated columns (the key's one for every head)
ROPE = {"kimi": 64}
#: a shape with a selection: (keys a block, blocks a query selects,
#: the nearest blocks forced beside block 0)
SELECTIONS = {"minicpm-sala": (64, 64, 32)}


def seeded_selection(rng, batch, kv_heads, seq, block, picks, nearest):
    """bool [batch, kv_heads, seq, seq / block]: block 0, the
    ``nearest`` blocks up to a query's own, and random earlier blocks
    up to ``picks`` in all."""
    blocks = seq // block
    own = (np.arange(seq) // block)[:, None]
    each = np.arange(blocks)[None, :]
    forced = (each == 0) | ((each <= own) & (each > own - nearest))
    score = rng.random((batch, kv_heads, seq, blocks), dtype=np.float32)
    score = np.where(forced, 2.0, score)
    score = np.where(each <= own, score, -1.0)
    kth = np.partition(score, blocks - picks, axis=-1)[
        ..., blocks - picks, None]
    return (score >= kth) & (each <= own)


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


def _stack(layers, block_q, block_k, window=None, fwd_block_k=None,
           **operands):
    """``operands``: ``q_rope`` and ``k_rope``, ``selected``."""
    def attn(q, k, v):
        return fa.flash_attention_tpu(
            q, k, v, causal=True, block_q=block_q, block_k=block_k,
            window=window, fwd_block_k=fwd_block_k, **operands,
        )

    def forward(q, k, v):
        out, _ = jax.lax.scan(
            lambda x, _: (attn(x, k, v), None), q, None, length=layers
        )
        return out

    def loss(q, k, v):
        return forward(q, k, v).astype(jnp.float32).mean()

    return jax.jit(forward), jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--subs", default="128,256,512")
    ap.add_argument("--blocks", default="512x512,256x256")
    ap.add_argument("--tiles", default="whole,rule,512,256,128")
    ap.add_argument("--key-blocks", default="")
    ap.add_argument("--bwd-key-blocks", default="")
    ap.add_argument("--fwd-vmem-mib", default="",
                    help="what the forward states of VMEM at the "
                         "rule's key block, where it states any")
    ap.add_argument("--only-key-blocks", action="store_true",
                    help="the two sweeps of key blocks and the rule's "
                         "rows, no edge of --subs, --blocks or --tiles")
    ap.add_argument("--label", default=None)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--n", type=int, default=10)
    ap.add_argument("--out", default="chiprun_out/attn_subtiles.jsonl")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not a TPU: interpret mode times nothing", file=sys.stderr)
        return 1
    subs = [int(s) for s in args.subs.split(",") if s]
    smaller = [
        tuple(int(n) for n in pair.split("x"))
        for pair in args.blocks.split(",") if pair
    ]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rng = np.random.default_rng(0)
    rule, one_kernel = fa._sub_tiles, fa._one_backward_kernel
    stated = fa._fwd_vmem_bytes
    tile_rule = getattr(fa, "_window_tile", None)
    tiles = [None if t == "whole" else t if t == "rule" else int(t)
             for t in args.tiles.split(",") if t]
    whole = {"fwd": None, "dq": None, "dkv": None}
    both = ("fwd_ms", "fwd_bwd_ms")
    key_blocks, bwd_key_blocks, fwd_vmem_mib = (
        [int(b) for b in arg.split(",") if b]
        for arg in (args.key_blocks, args.bwd_key_blocks,
                    args.fwd_vmem_mib))
    for name in args.shapes.split(","):
        batch, seq, heads, kv_heads, d = SHAPES[name]
        group = heads // kv_heads
        blocks = tuning.heuristic_blocks(seq, group)
        q, k, v = (
            jnp.asarray(
                rng.standard_normal((batch, seq, h, d)), jnp.bfloat16
            )
            for h in (heads, kv_heads, kv_heads)
        )
        operands = {}
        if name in ROPE:
            operands.update(
                (part, jnp.asarray(rng.standard_normal(
                    (batch, seq, h, ROPE[name])), jnp.bfloat16))
                for part, h in (("q_rope", heads), ("k_rope", 1)))
        if name in SELECTIONS:
            operands["selected"] = jnp.asarray(seeded_selection(
                rng, batch, kv_heads, seq, *SELECTIONS[name]))
        # the widest key block a selection's word holds the blocks of
        widest = (tuning.WORD_BITS * SELECTIONS[name][0]
                  if name in SELECTIONS else seq)
        ruled = 1 if one_kernel(group, seq, d + ROPE.get(name, 0)) else 2
        ruled_fwd = tuning.forward_key_block(
            seq, group, blocks,
            selection_block=SELECTIONS.get(name, (None,))[0])
        # (grid blocks, an edge a kernel or None for the file's rule,
        # backward kernels, what to time); the sweeps of an edge with
        # the backward as the pair they were read with
        settings = [
            (blocks, whole, 2, both), (blocks, None, ruled, both),
            (blocks, None, 3 - ruled, both[1:]),
        ] + [
            (pair, whole, 2, both) for pair in smaller
        ] + [
            (blocks, dict(whole, **{kernel: sub}), 2,
             both[:1] if kernel == "fwd" else both[1:])
            for kernel in whole for sub in subs
            if sub < max(blocks) and group == 1
        ]
        if args.only_key_blocks:
            settings = settings[1:3]
        # (setting, window, the windowed tile's edge: None the block,
        # "rule" the file's own, the forward's key block: a smaller
        # pair's own, what the forward states of VMEM in MiB: None
        # the file's own)
        runs = [
            (setting, None, "rule",
             ruled_fwd if setting[0] == blocks else setting[0][1], None)
            for setting in settings
        ] + [
            ((blocks, None, ruled, both), None, "rule", wide, None)
            for wide in key_blocks
            if seq % wide == 0 and wide <= widest and wide != ruled_fwd
        ] + [
            (((blocks[0], wide), None, ruled, both[1:]), None, "rule",
             max(wide, ruled_fwd), None)
            for wide in bwd_key_blocks
            if seq % wide == 0 and wide <= widest and wide != blocks[1]
        ] + [
            ((blocks, None, ruled, both[:1]), None, "rule", ruled_fwd, mib)
            for mib in fwd_vmem_mib if ruled_fwd != blocks[1]
        ]
        if name in WINDOWS:
            window = WINDOWS[name]
            ruled_blocks = (blocks, None, ruled, both)
            runs = [(ruled_blocks, None, "rule", ruled_fwd, None)] + [
                (ruled_blocks, window, tile, ruled_fwd, None)
                for tile in tiles
            ] + [
                (((blocks[0], 512), None, ruled, both), window, None, 512,
                 None),
                ((blocks, None, 3 - ruled, both[1:]), window, "rule",
                 ruled_fwd, None),
            ]
        for ((block_q, block_k), edges, kernels, keys), window, tile, \
                wide, vmem_mib in runs:
            fa._fwd_vmem_bytes = stated if vmem_mib is None else (
                lambda *shape: vmem_mib * 2 ** 20)  # noqa: B023
            fa._sub_tiles = rule if edges is None else (
                lambda kernel, bq, bk, g, d: fa._fits(  # noqa: B023
                    edges[kernel], g, bq, bk
                )
            )
            fa._one_backward_kernel = (
                lambda g, seq, d: kernels == 1  # noqa: B023
            )
            if tile_rule is not None:
                fa._window_tile = tile_rule if tile == "rule" else (
                    lambda kernel, bk: (  # noqa: B023
                        tile if tile and bk > tile else bk)  # noqa: B023
                )
            fns = dict(zip(both, _stack(
                args.layers, block_q, block_k, window,
                None if wide == block_k else wide, **operands)))
            # the forward's live grid blocks a kv head, and the query
            # rows that meet one, over the call
            live = fa.causal_tile_census(
                seq, block_q, wide, block_q, wide, window)[0]
            row_steps = batch * kv_heads * live * group * block_q
            row = {
                "shape": name, "blocks": [block_q, block_k],
                "fwd_block_k": wide, "fwd_live_blocks": live,
                "fwd_row_steps": row_steps,
                **({} if vmem_mib is None else {"fwd_vmem_mib": vmem_mib}),
                **(edges or {"rule": True}),
                "backward_kernels": kernels,
                **({"window": window, "window_tile": tile}
                   if name in WINDOWS else {}),
                **({"tree": args.label} if args.label else {}),
            }
            try:
                for key in keys:
                    row[key] = 1e3 / args.layers * timeit(
                        fns[key], q, k, v, n=args.n, warmup=2
                    )
                if "fwd_ms" in row:
                    row["ns_a_row_step"] = 1e6 * row["fwd_ms"] / row_steps
            except Exception as e:  # an edge the chip's compiler refuses
                row["error"] = str(e)[-300:]
            print(json.dumps(row), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
