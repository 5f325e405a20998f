"""The flash-attention kernels' block sizes, from the shapes alone.

One static rule for the pair, which every kernel runs up to a group of
8 and the backward kernels at any: the largest power-of-two
``(block_q, block_k)`` that tiles the sequence while the kernel's fp32
score block (``group * block_q`` rows by ``block_k`` columns) stays
within ``ROWS_CAP`` rows by 1024 columns. That is (1024, 1024) without
a group (``gpt2-xl``, OLMoE) and (256, 1024) at Mistral's group of 4.
And one for the forward kernel's key block past a group of 8
(``forward_key_block``, below).

It is static because nothing smaller read faster on a v5e (PERF.md
section 6, PR 31): whole (512, 512) blocks took 7.47 ms against 6.27 for
``gpt2-xl``'s attention forward and backward and (256, 256) 12.65, in
the cell 8,490 and 6,819 tokens/s against 9,282; a grid step costs its
rows' rescale, reductions and state traffic whatever its width. The
walk of the diagonal block in ``ops/pallas/flash_attention.py
_sub_tiles`` was settled on the chip at these blocks and engages only
with equal blocks and no group, so the rule and the kernel file move
together or not at all.

The rule does not read a window, and needs not: a windowed call's
grid counts the key blocks of a query block's band, and its backward
kernel takes a block that an edge of the band crosses in 512-wide
column tiles of its own (``flash_attention._Band``,
``_window_tile``), so a 1024-wide key block costs a windowed layer's
backward no column outside the band's tiles. The narrower key block
that would leave out the same columns with no body more reads slower,
as without a window: whole (128, 512) blocks took 10.64 ms forward
and 22.96 forward and backward at a window of 2048 (32 query heads on
4 at 16,384 positions) where (128, 1024) take 6.68 and 18.50, and
15.32 and 31.77 against 8.73 and 23.66 at 4096 (PERF.md section 6,
PR 52).

The forward kernel takes a key block of its own where the pair's
shrink binds (``forward_key_block``). A group of more than 8 has more
than ``ROWS_CAP`` rows in its least query block, and the pair gives up
columns for them: (128, 512) at a group of 16, (128, 256) at 32. That
keeps the scores inside the 16 MiB that a kernel call gets of VMEM
when it asks for nothing; a v5e core has 128 MiB, and a call can state
what it takes (``flash_attention._fwd_vmem_bytes``, as the one
backward kernel has since PR 37). The forward's grid step is bound by
its rows (above; PR 52): a row pays a fixed cost every step it is
live in, 2.6 ns of the 3.3 a row-step takes at 512 columns, and a
1,024-wide key block pays it half as often (4.0 ns a row-step over 53%
of the steps). Wider reads slower again, 8.1 ns a row-step at 2,048:
the block on the diagonal wastes more, and [2048, 2048] float32
scores are 16 MiB a copy. In Nemotron's cell 13,880 tokens/s at the
pair's 512, 14,138 at 1,024 and 14,075 at 2,048 (PERF.md section 6, PR
66, where the sweep's rows are). The backward kernels have no such
cost a row: at 1,024 columns theirs read level in Nemotron's call and
cell, and 7% faster in ``minicpm-sala``'s call alone but 0.1% slower
in its cell, so they keep the pair.
"""

from typing import Dict, Optional, Tuple

# s/p are [group*block_q, block_k] fp32 in VMEM; cap rows x block_k so
# the block pair stays inside the 16 MiB of VMEM that a kernel call
# gets when it states nothing (1024 rows x 1024 cols measured fastest
# in-model on v5e: 50.2% MFU vs 48.5% for the best
# per-query-head-grid config)
ROWS_CAP = 1024
_POW2 = (128, 256, 512, 1024)


def block_caps(
    group: int,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> Tuple[int, int]:
    """Caps on the pair (block_q, block_k) for a GQA group size,
    honoring the caller's explicit caps: what fits a call that states
    no VMEM. For high GQA ratios (g > 8, where even the
    128-row-minimum q block overshoots ROWS_CAP) block_k shrinks to
    keep the fp32 s/p blocks' rows*cols footprint constant; the
    forward kernel, which states what it takes, does not follow
    (``forward_key_block``)."""
    rows_min = 128 * group
    bq_cap = min(block_q or ROWS_CAP, max(ROWS_CAP // group, 128))
    bk_cap = min(
        block_k or 1024,
        max(128, ROWS_CAP * 1024 // max(rows_min, ROWS_CAP)),
    )
    return bq_cap, bk_cap


#: the widest key block the forward kernel takes, the width the chip
#: reads fastest at a group of 16 (a call of 32 query heads on 2: 7.43
#: ms at 512 columns, 4.76 at 1024, 5.31 at 2048 and 6.43 at 4096 of
#: 8,192 positions; 25.67, 17.89 and 18.72 at 16,384 with a selection;
#: PERF.md section 6, PR 66), and the float32 scores and ``p`` of a
#: grid step it may hold of VMEM: what [16 x 128, 1024] take. A larger
#: group gives up columns to stay inside it, as the pair does inside
#: ``ROWS_CAP`` x 1024
FWD_KEY_BLOCK = 1024
FWD_SCORE_BYTES = 2 * 4 * 2048 * 1024
#: the selection blocks whose bits one int32 word of a key block holds
#: (``flash_attention._selection_words``)
WORD_BITS = 32


def score_bytes(group: int, block_q: int, block_k: int) -> int:
    """What a grid step's float32 scores and ``p`` hold of VMEM."""
    return 2 * 4 * group * block_q * block_k


def forward_key_block(
    seq: int,
    group: int,
    blocks: Tuple[int, int],
    block_k: Optional[int] = None,
    window: Optional[int] = None,
    selection_block: Optional[int] = None,
) -> int:
    """The forward kernel's key block beside the pair ``blocks`` that
    ``heuristic_blocks`` gave the call: the pair's, unless the group's
    128 rows a head already exceed ``ROWS_CAP`` and the pair gave up
    columns for them. Then the widest power of two up to
    ``FWD_KEY_BLOCK`` that tiles ``seq`` with the scores within
    ``FWD_SCORE_BYTES``, which the call states. Never under the
    pair's, nor over the caller's ``block_k``; with a selection of
    ``selection_block`` keys a block, the ``WORD_BITS`` blocks a word
    holds. A windowed call keeps the pair's: no cell has a window at
    such a group and none was timed."""
    bq, bk = blocks
    if 128 * group <= ROWS_CAP or window is not None:
        return bk
    cap = min(block_k or FWD_KEY_BLOCK, FWD_KEY_BLOCK,
              FWD_SCORE_BYTES // score_bytes(group, bq, 1))
    if selection_block is not None:
        cap = min(cap, WORD_BITS * selection_block)
    wide = bk
    while 2 * wide <= cap and seq % (2 * wide) == 0:
        wide *= 2
    return wide


def heuristic_blocks(
    seq: int,
    group: int,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> Optional[Tuple[int, int]]:
    """The largest power-of-two block pair that tiles ``seq`` within
    the caps (the kernel's causal mask requires power-of-two block_q).
    None when nothing does."""
    bq_cap, bk_cap = block_caps(group, block_q, block_k)
    bqs = [b for b in _POW2 if seq % b == 0 and b <= bq_cap]
    bks = [b for b in _POW2 if seq % b == 0 and b <= bk_cap]
    if not bqs or not bks:
        return None
    return max(bqs), max(bks)


_last_selection: Optional[Dict] = None


def record(**selection) -> None:
    """Keep what ``ops/attention.py flash_attention`` dispatched."""
    global _last_selection
    _last_selection = dict(selection, source="static")


def last_selection() -> Optional[Dict]:
    """The blocks of the last kernel traced (what a run reports):
    kernel/seq/head_dim/gqa_group/dtype/causal/block_q/block_k/source,
    ``fwd_block_k`` where the forward kernel's key block is not the
    pair's, and ``backward``, the form the backward takes
    (``dq_resident``, ``dkv_resident`` or ``pair``); or None if no
    Pallas dispatch has happened."""
    return _last_selection
