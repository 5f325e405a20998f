"""Attention over the key blocks each query selects (InfLLM-v2,
arXiv:2509.24663, as MiniCPM4 runs it, arXiv:2506.07900): a selection
without parameters, a block of ``block`` adjacent keys at a time, one
for all the query heads of a kv head.

In three steps, on the models' [batch, seq, heads, head_dim] layout,
for query ``t`` and kv head ``g`` (``kernel`` a whole number of
``stride``s, ``block`` too)::

    Kc_j = mean(k[stride j : stride j + kernel])          # compress_keys
    visible(j, t) = stride j + kernel - 1 <= t
    p_h = softmax_j(q_h . Kc_j * scale)   over the visible j, a head of g
    s_j = sum_h p_h[j]
    score_b = max(s_j over the j whose keys overlap block b)
    forced: the first ``init_blocks`` blocks, and the ``window /
        block`` blocks that end with the query's own, t // block
    selected: the ``topk`` blocks at or before the query's own with
        the most score, the forced ones first    # select_blocks
    o_t = softmax over the selected blocks' keys at or before t
                                                 # selected_attention

``select_blocks`` walks the queries in chunks of ``ROWS``, each
against the compressed keys its last query sees and no later one (a
static slice: half the products of the whole rectangle), so the
compressed scores [heads, seq, seq / stride] (2.1 GB in float32 at 32
heads of 16,384 positions) are never whole in memory. The walk is a
Python loop, a body a chunk: under a ``lax.map`` every chunk takes
all the compressed keys, and the chip's compiler moved the masks,
which depend on the chunk's number alone, into a loop of their own
ahead of it, all sixteen chunks' ``pred[16, 1, 2, 16, 1024, 1023]``
whole, 536 MB (PERF.md section 6, PR 64). The scores are float32
products of the operands as they come and the softmax is float32. A query with no visible compressed key
scores nothing and takes its forced blocks. The ``topk`` blocks are
those of rank under ``topk``, a block's rank the count of the blocks
that score more or as much at a lower place: ``lax.top_k``'s choice,
ties and all, as compares and a sum over [blocks, blocks] a query,
where the chip's ``top_k`` is a full sort of the blocks' scores (100
of the cell's first 1,248 ms a step, PERF.md section 6, PR 64). The
selection is a bool mask [batch,
kv_heads, seq, seq / block], not a list of indices: it is what the
attention's kernels read (``ops/pallas/flash_attention.py
_selection_words``), and a block past a short query's own is simply
not in it. Nothing here has a gradient: the compressed keys and q are
read under ``stop_gradient``.

``selected_attention`` is ``ops/attention.py flash_attention`` with
the selection as an operand: on the TPU, where the shapes tile, the
flash kernels with one more mask; elsewhere the dense reference under
the mask spread over the keys. Exact for the selection either way.
"""

import jax
import jax.numpy as jnp

from dlrover_tpu.ops import attention

#: queries whose compressed scores are held at once
ROWS = 1024


def _count(path: str):
    """Say, at trace time, which path the attend stage took: the
    counters of docs/TELEMETRY.md. The select stage has one path, no
    kernel, and so no counter."""
    from dlrover_tpu.telemetry.registry import counter

    counter(
        f"sparse_attention_{path}_calls",
        "calls of block-selected attention's attend stage traced on "
        f"the {path} path",
    ).inc()


def compress_keys(k, kernel: int, stride: int):
    """``k`` [batch, seq, kv_heads, d] to its compressed keys [batch,
    seq / stride - kernel / stride + 1, kv_heads, d]: the mean of
    ``kernel`` adjacent keys every ``stride``, summed in float32 and
    rounded once to ``k``'s dtype."""
    b, s, kvh, d = k.shape
    if kernel % stride or s % stride or s < kernel:
        raise ValueError(
            f"compress_keys: {s} keys in windows of {kernel} every "
            f"{stride}"
        )
    per = kernel // stride
    sums = jnp.sum(
        k.reshape(b, s // stride, stride, kvh, d), axis=2,
        dtype=jnp.float32,
    )
    n = s // stride - per + 1
    total = sum(sums[:, i:i + n] for i in range(per))
    return (total / kernel).astype(k.dtype)


def _pooled(s, block: int, kernel: int, stride: int, blocks: int):
    """``s`` [..., compressed keys] to a block's score [..., blocks]:
    the most of the compressed keys whose ``kernel`` keys overlap the
    block's, ``block / stride b - (kernel - 1) // stride`` to ``block
    / stride (b + 1) - 1``. ``s`` is at least 0, which is what a
    compressed key that does not exist scores."""
    per, lead = block // stride, (kernel - 1) // stride
    width = blocks * per + lead
    s = jnp.pad(
        s, [(0, 0)] * (s.ndim - 1) + [(lead, width - lead - s.shape[-1])]
    )
    return jnp.max(jnp.stack([
        s[..., o:o + blocks * per:per] for o in range(per + lead)
    ]), axis=0)


def select_blocks(q, kc, *, block: int, kernel: int, stride: int,
                  topk: int, window: int, init_blocks: int,
                  rows: int = ROWS):
    """The selection, bool [batch, kv_heads, seq, seq / block], of
    ``q`` [batch, seq, heads, d] over the compressed keys ``kc``
    (``compress_keys``): the equations of the module's docstring, the
    scores' scale ``d ** -0.5``. ``rows`` is the tests': a walk of
    several chunks at a sequence the CPU holds."""
    b, s, h, d = q.shape
    kvh = kc.shape[2]
    if (block % stride or kernel % stride or s % block or window % block
            or init_blocks + window // block > topk):
        raise ValueError(
            f"select_blocks: blocks of {block} keys over {s}, "
            f"compressed keys of {kernel} every {stride}, a window of "
            f"{window} and {init_blocks} first blocks forced within "
            f"the {topk} selected"
        )
    q, kc = jax.lax.stop_gradient((q, kc))
    blocks, local = s // block, window // block
    rows = min(rows, s)
    if s % rows:
        raise ValueError(f"select_blocks: {s} queries in chunks of {rows}")
    at = jnp.arange(blocks)
    q = q.reshape(b, s, kvh, h // kvh, d)

    def chunk(t0):
        """The selection of queries ``t0`` to ``t0 + rows - 1``."""
        t = t0 + jnp.arange(rows)
        # the compressed keys that the chunk's last query sees
        n = min(max(0, (t0 + rows - kernel) // stride + 1), kc.shape[1])
        # the last key of a compressed key's window
        last = stride * jnp.arange(n) + kernel - 1
        visible = last[None, :] <= t[:, None]  # [rows, n]
        logits = jnp.einsum(
            "bqhgd,bjhd->bhgqj", q[:, t0:t0 + rows], kc[:, :n],
            preferred_element_type=jnp.float32,
        ) * d ** -0.5
        top = jnp.max(
            jnp.where(visible, logits, -jnp.inf), axis=-1, keepdims=True,
            initial=-jnp.inf)
        p = jnp.where(visible, jnp.exp(logits - top), 0.0)
        total = jnp.sum(p, axis=-1, keepdims=True)
        p = p / jnp.where(total == 0.0, 1.0, total)
        score = _pooled(
            jnp.sum(p, axis=2), block, kernel, stride, blocks
        )  # [b, kvh, rows, blocks]
        own = (t // block)[:, None]
        forced = (at < init_blocks) | (at > own - local)
        score = jnp.where(
            at > own, -jnp.inf, jnp.where(forced, jnp.inf, score))
        # a block's rank among its query's: the blocks that score
        # more, or as much at a lower place (``lax.top_k``'s order)
        ahead = (score[..., None, :] > score[..., :, None]) | (
            (score[..., None, :] == score[..., :, None])
            & (at[None, :] < at[:, None]))
        rank = jnp.sum(ahead, axis=-1, dtype=jnp.int32)
        return (rank < topk) & (at <= own)

    return jnp.concatenate(
        [chunk(t0) for t0 in range(0, s, rows)], axis=2)


def selected_attention(q, k, v, selected):
    """Causal softmax attention of ``q`` [batch, seq, heads, d] over
    the keys of the blocks ``selected`` [batch, kv_heads, seq, blocks]
    names for each query of a kv head's heads, exact for the
    selection. Differentiable in q, k and v."""
    # the entry's own rule says which path it takes
    _count("kernel" if attention._use_pallas(q, k) else "plain")
    return attention.flash_attention(
        q, k, v, causal=True, selected=selected)
