"""Block-selected attention (ops/sparse_attention.py): the selection
against a query-by-query walk in numpy, the attention exact for the
selection (a selection that differs between neighbouring queries is
what a union over a tile would get wrong), the flash kernels with the
selection as an operand against the plain path in interpret mode,
forward and in every gradient, in each backward form, and no gradient
through the selection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import sparse_attention as sa
from dlrover_tpu.ops.attention import flash_attention, mha_reference
from dlrover_tpu.ops.pallas import flash_attention as fa
from dlrover_tpu.telemetry.registry import counter

SIZES = dict(block=16, kernel=8, stride=4, topk=6, window=32, init_blocks=1)


def operands(heads, kv_heads, s=256, d=64, b=2, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return tuple(
        jax.random.normal(key, (b, s, n, d))
        for key, n in zip(keys, (heads, kv_heads, kv_heads)))


def selection_by_hand(q, k, block, kernel, stride, topk, window,
                      init_blocks):
    """The module's equations a query and kv head at a time."""
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    b, s, heads, d = q.shape
    kv_heads = k.shape[2]
    group, blocks = heads // kv_heads, s // block
    picked = np.zeros((b, kv_heads, s, blocks), bool)
    for bi in range(b):
        for g in range(kv_heads):
            kc = np.stack([
                k[bi, j:j + kernel, g].mean(0)
                for j in range(0, s - kernel + 1, stride)])
            for t in range(s):
                seen = [j for j in range(len(kc))
                        if stride * j + kernel - 1 <= t]
                score = np.zeros(len(kc))
                for h in range(g * group, (g + 1) * group):
                    if seen:
                        z = q[bi, t, h] @ kc[seen].T * d ** -0.5
                        e = np.exp(z - z.max())
                        score[seen] += e / e.sum()
                own = t // block
                by_block = np.full(blocks, -np.inf)
                for at in range(own + 1):
                    over = [j for j in range(len(kc))
                            if stride * j + kernel - 1 >= block * at
                            and stride * j <= block * at + block - 1]
                    by_block[at] = score[over].max()
                    if at < init_blocks or at > own - window // block:
                        by_block[at] = np.inf
                order = sorted(range(own + 1), key=lambda at: (-by_block[at], at))
                picked[bi, g, t, order[:topk]] = True
    return picked


def test_the_selection_is_the_equations_walked_by_hand():
    q, k, _ = operands(4, 2, s=128, d=16, b=1)
    got = sa.select_blocks(
        q, sa.compress_keys(k, 8, 4), rows=32, **SIZES)
    want = selection_by_hand(q, k, **SIZES)
    assert got.shape == (1, 2, 128, 8) and got.dtype == jnp.bool_
    assert np.array_equal(np.asarray(got), want)
    count = np.asarray(got).sum(-1)
    # a query's own block and all before it while they are few, then 6
    assert (count == np.minimum(np.arange(128) // 16 + 1, 6)).all()
    # forced: block 0 and the two nearest
    own = np.arange(128) // 16
    assert got[0, :, :, 0].all()
    assert all(got[0, 0, t, own[t]] and got[0, 0, t, max(own[t] - 1, 0)]
               for t in range(128))
    # free picks exist and differ between the kv heads
    assert (np.asarray(got)[0, 0] != np.asarray(got)[0, 1]).any()


def test_the_compressed_keys_are_means_rounded_once():
    k = jax.random.normal(jax.random.key(1), (1, 64, 2, 16), jnp.bfloat16)
    got = sa.compress_keys(k, 8, 4)
    want = np.stack([
        np.asarray(k, np.float32)[:, j:j + 8].mean(1)
        for j in range(0, 57, 4)], axis=1)
    assert got.shape == (1, 15, 2, 16) and got.dtype == jnp.bfloat16
    assert np.array_equal(
        np.asarray(got), np.asarray(jnp.asarray(want).astype(jnp.bfloat16)))
    with pytest.raises(ValueError, match="compress_keys"):
        sa.compress_keys(k, 6, 4)


def test_sizes_that_are_not_built_are_refused():
    q, k, _ = operands(2, 2, s=128, d=16, b=1)
    kc = sa.compress_keys(k, 8, 4)
    with pytest.raises(ValueError, match="select_blocks"):
        sa.select_blocks(q, kc, **{**SIZES, "topk": 2})  # 3 forced
    with pytest.raises(ValueError, match="select_blocks"):
        sa.select_blocks(q, kc, **{**SIZES, "window": 24})


def neighbours_differ(b, kv_heads, s, block):
    """A selection in which neighbouring queries take different
    blocks: query t its own block and, of the earlier ones, those of
    t's parity; a kv head's shifted by one."""
    t = np.arange(s)[:, None]
    at = np.arange(s // block)[None, :]
    own = t // block
    picked = (at == own) | ((at < own) & ((at + t) % 2 == 0))
    both = np.stack([picked, (at == own) | ((at < own) & ((at + t) % 2 == 1))])
    return jnp.asarray(np.broadcast_to(
        both[None, :kv_heads], (b, kv_heads, s, s // block)))


@pytest.mark.parametrize("heads,kv_heads,pair", [
    (4, 2, False), (2, 2, False), (4, 2, True), (2, 2, True)],
    ids=["dkv_resident", "dq_resident", "pair_grouped", "pair"])
def test_the_kernels_are_exact_for_a_selection_that_differs_by_query(
        heads, kv_heads, pair, monkeypatch):
    """Interpret mode, float32: the kernels with the selection's words
    against the dense reference under the mask spread over the keys,
    forward and in q's, k's and v's gradients, in every backward
    form; and against what a union over a query tile's selections
    would give, which is another result."""
    if pair:
        monkeypatch.setattr(fa, "_one_backward_kernel", lambda *a: False)
    q, k, v = operands(heads, kv_heads)
    selected = neighbours_differ(2, kv_heads, 256, 16)
    mask = jnp.repeat(selected, 16, axis=-1)
    weights = jax.random.normal(jax.random.key(5), q.shape)

    def plain(q, k, v, mask=mask):
        return (mha_reference(q, k, v, mask=mask) * weights).sum()

    def kernels(q, k, v):
        return (fa.flash_attention_tpu(
            q, k, v, block_q=128, block_k=128, selected=selected
        ) * weights).sum()

    want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    got = jax.value_and_grad(kernels, (0, 1, 2))(q, k, v)
    assert abs(float(got[0]) - float(want[0])) < 1e-3
    for g, w in zip(got[1], want[1]):
        assert float(jnp.abs(g - w).max()) < 1e-4
    # a tile's union: every query of a 128-row tile sees what any does
    union = jnp.repeat(
        mask.reshape(2, kv_heads, 2, 128, 256).any(3), 128, axis=2)
    assert abs(float(plain(q, k, v, union)) - float(want[0])) > 1.0


def test_the_entry_takes_the_selection_and_counts_its_path():
    q, k, v = operands(4, 2, s=128, d=16, b=1)
    selected = neighbours_differ(1, 2, 128, 16)
    calls = [counter(f"sparse_attention_{path}_calls", "")
             for path in ("plain", "kernel")]
    before = [c.value for c in calls]
    got = sa.selected_attention(q, k, v, selected)
    assert [c.value - was for c, was in zip(calls, before)] == [1, 0]
    want = mha_reference(q, k, v, mask=jnp.repeat(selected, 16, axis=-1))
    assert float(jnp.abs(got - want).max()) < 1e-6
    # the entry every attention goes by, with one operand more
    same = flash_attention(q, k, v, causal=True, selected=selected)
    assert float(jnp.abs(same - want).max()) < 1e-6
    # every block selected is plain causal attention
    full = sa.selected_attention(q, k, v, jnp.ones_like(selected))
    assert float(jnp.abs(full - mha_reference(q, k, v)).max()) < 1e-6


def test_no_gradient_passes_the_selection():
    """The gradient with the selection made inside is the gradient
    with the same selection held from outside."""
    q, k, v = operands(4, 2, s=128, d=16, b=1)

    def inside(q, k, v):
        selected = sa.select_blocks(q, sa.compress_keys(k, 8, 4), **SIZES)
        return (sa.selected_attention(q, k, v, selected) ** 2).sum()

    held = sa.select_blocks(q, sa.compress_keys(k, 8, 4), **SIZES)

    def outside(q, k, v):
        return (sa.selected_attention(q, k, v, held) ** 2).sum()

    for g, w in zip(jax.grad(inside, (0, 1, 2))(q, k, v),
                    jax.grad(outside, (0, 1, 2))(q, k, v)):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_the_kernels_refuse_a_selection_they_do_not_take():
    q, k, v = operands(2, 2, s=256, d=64, b=1)
    selected = neighbours_differ(1, 2, 256, 16)
    with pytest.raises(ValueError, match="selected"):
        fa.flash_attention_tpu(q, k, v, selected=selected, window=64)
    with pytest.raises(ValueError, match="selected"):
        fa.flash_attention_tpu(q, k, v, selected=selected[:, :1])
    # 64 blocks of 4 keys to a 256-wide key block: past a word's bits
    with pytest.raises(ValueError, match="1 to 32 whole blocks"):
        fa._selection_words(jnp.ones((2, 256, 64), bool), 256)
    words = fa._selection_words(selected.reshape(2, 256, 16), 128)
    assert words.shape == (2, 2, 1, 256) and words.dtype == jnp.int32
    # query 200's word for keys 128-255: its blocks 8-15, bit by bit
    want = sum(int(selected[0, 0, 200, 8 + c]) << c for c in range(8))
    assert int(words[0, 1, 0, 200]) == want
