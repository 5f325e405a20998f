"""The flash-attention kernel's block sizes, from the shapes alone.

One static rule: the largest power-of-two ``(block_q, block_k)`` that
tiles the sequence while the kernel's fp32 score block
(``group * block_q`` rows by ``block_k`` columns) stays within
``ROWS_CAP`` rows by 1024 columns. That is (1024, 1024) without a group
(``gpt2-xl``, OLMoE) and (256, 1024) at Mistral's group of 4.

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
"""

from typing import Dict, Optional, Tuple

# s/p are [group*block_q, block_k] fp32 in VMEM; cap rows x block_k so
# the block pair stays inside the ~16MB VMEM budget alongside the rest
# of a fused train step (1024 rows x 1024 cols measured fastest
# in-model on v5e: 50.2% MFU vs 48.5% for the best
# per-query-head-grid config)
ROWS_CAP = 1024
_POW2 = (128, 256, 512, 1024)


def block_caps(
    group: int,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> Tuple[int, int]:
    """VMEM-safety caps on (block_q, block_k) for a GQA group size,
    honoring the caller's explicit caps. For high GQA ratios (g > 8,
    where even the 128-row-minimum q block overshoots ROWS_CAP)
    block_k shrinks to keep the fp32 s/p blocks' rows*cols footprint
    constant."""
    rows_min = 128 * group
    bq_cap = min(block_q or ROWS_CAP, max(ROWS_CAP // group, 128))
    bk_cap = min(
        block_k or 1024,
        max(128, ROWS_CAP * 1024 // max(rows_min, ROWS_CAP)),
    )
    return bq_cap, bk_cap


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
    or None if no Pallas dispatch has happened."""
    return _last_selection
