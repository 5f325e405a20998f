"""The static block rule (ops/tuning.py) and its dispatch
(ops/attention.py flash_attention), on the CPU: the kernel is traced in
interpret mode where a test needs it traced, and nothing is timed."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.ops import attention, tuning
from dlrover_tpu.ops.attention import flash_attention, mha_reference
from tests.test_chip_compile import CELL_ATTENTION


def test_heuristic_matches_pre_tuning_logic():
    # g=1: full 1024x1024; g=8: q rows capped at 128
    assert tuning.heuristic_blocks(2048, 1) == (1024, 1024)
    assert tuning.heuristic_blocks(2048, 8) == (128, 1024)
    # caller cap below the 128 minimum -> nothing tiles
    assert tuning.heuristic_blocks(2048, 1, block_q=64) is None
    # nothing divides a non-pow2-multiple seq
    assert tuning.heuristic_blocks(100, 1) is None


def _llama_1b():
    cfg = llama.llama_1b()
    return 3, 2048, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim


#: [batch, seq, heads, kv_heads, head_dim] -> the pair every ledger
#: line of these cells was read at, and the forward kernel's key
#: block; the high groups at which ``block_caps`` narrows the pair's
#: block_k to keep the score block's area are those at which the
#: forward takes a key block of its own (Nemotron's and
#: ``minicpm-sala``'s calls at a group of 16, and a group of 32)
@pytest.mark.parametrize("shape,blocks,fwd_block_k", [
    (CELL_ATTENTION["gpt2-xl"], (1024, 1024), 1024),
    (CELL_ATTENTION["olmoe"], (1024, 1024), 1024),
    (CELL_ATTENTION["mistral"], (256, 1024), 1024),
    ((1, 16384, 28, 4, 128), (128, 1024), 1024),
    (_llama_1b(), (128, 1024), 1024),
    ((1, 4096, 32, 2, 128), (128, 512), 1024),
    ((1, 8192, 32, 2, 128), (128, 512), 1024),
    ((1, 16384, 32, 2, 128), (128, 512), 1024),
    ((1, 4096, 32, 1, 128), (128, 256), 512),
], ids=["gpt2-xl", "olmoe", "mistral", "g7", "llama_1b", "g16", "g16-8192",
        "g16-16384", "g32"])
def test_rule_at_the_measured_shapes(shape, blocks, fwd_block_k):
    """What a kernel's float32 score blocks take of VMEM is within
    what its call states: nothing where they fit the default scoped
    limit (``ROWS_CAP`` x 1024 elements: every pair, which is what the
    backward kernels run, and the forward's up to a group of 8), and
    where the forward's do not, its own statement
    (``_fwd_vmem_bytes``), itself inside a v5e core's 128 MiB."""
    from dlrover_tpu.ops.pallas import flash_attention as fa

    _, seq, heads, kv_heads, d = shape
    group = heads // kv_heads
    assert tuning.heuristic_blocks(seq, group) == blocks
    assert tuning.forward_key_block(seq, group, blocks) == fwd_block_k
    default = 16 * 2 ** 20
    # the pair: s and p within half the default limit, as ever
    assert tuning.score_bytes(group, *blocks) <= default // 2
    assert tuning.score_bytes(group, *blocks) == (
        2 * 4 * group * blocks[0] * blocks[1])
    scores = tuning.score_bytes(group, blocks[0], fwd_block_k)
    stated = fa._fwd_vmem_bytes(group * blocks[0], fwd_block_k, d, d, 2)
    if group <= 8:
        assert fwd_block_k == blocks[1] and stated is None
    else:
        assert fwd_block_k == 2 * blocks[1]
        assert scores <= tuning.FWD_SCORE_BYTES
        assert scores < stated <= 2 * scores < 128 * 2 ** 20


def test_forward_key_block_reads_the_call():
    """From shapes and the caller's caps alone: never under the
    pair's nor over the caller's ``block_k``, a width that tiles the
    sequence, the 32 blocks of a selection's word, and the pair's
    with a window."""
    assert tuning.forward_key_block(8192, 16, (128, 512)) == 1024
    assert tuning.forward_key_block(8192, 16, (128, 512), block_k=512) == 512
    assert tuning.forward_key_block(8192, 16, (128, 256), block_k=256) == 256
    assert tuning.forward_key_block(512, 16, (128, 512)) == 512
    assert tuning.forward_key_block(1536, 16, (128, 512)) == 512
    assert tuning.forward_key_block(8192, 16, (128, 512), window=2048) == 512
    assert tuning.forward_key_block(
        16384, 16, (128, 512), selection_block=64) == 1024
    assert tuning.forward_key_block(
        16384, 16, (128, 512), selection_block=16) == 512
    # a group of 12: the pair gives 512 of its 1536 rows' 682
    assert tuning.heuristic_blocks(4096, 12) == (128, 512)
    assert tuning.forward_key_block(4096, 12, (128, 512)) == 1024
    for group in (1, 4, 7, 8):
        blocks = tuning.heuristic_blocks(16384, group)
        assert tuning.forward_key_block(16384, group, blocks) == blocks[1]


def test_adopted_loose_dir_is_tightened(tmp_path):
    from dlrover_tpu.common.cachedir import ensure_private_dir

    d = str(tmp_path / "world_readable")
    os.makedirs(d, mode=0o755)
    os.chmod(d, 0o755)  # defeat umask
    assert ensure_private_dir(d) == d
    assert (os.stat(d).st_mode & 0o777) == 0o700


def test_cpu_path_never_measures():
    """Off the TPU ``flash_attention`` is the reference, bit for bit."""
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
        for _ in range(3)
    )
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k, v)),
        np.asarray(mha_reference(q, k, v)),
    )


def _trace(monkeypatch, heads, kv_heads, **caps):
    """Trace ``flash_attention``'s kernel branch at [1, 2048, heads,
    64]. The undecorated function: a jitted one whose trace another
    test of this process has cached would not run its body again."""
    monkeypatch.setattr(attention, "_use_pallas", lambda q, k: True)
    q, kv = (
        jax.ShapeDtypeStruct((1, 2048, h, 64), jnp.bfloat16)
        for h in (heads, kv_heads)
    )
    return jax.eval_shape(
        lambda q, k, v: flash_attention.__wrapped__(q, k, v, **caps),
        q, kv, kv,
    )


def test_caller_caps_join_the_filter(monkeypatch):
    assert tuning.heuristic_blocks(2048, 1, 512, 256) == (512, 256)
    _trace(monkeypatch, 2, 2, block_q=512, block_k=256)
    sel = tuning.last_selection()
    assert (sel["block_q"], sel["block_k"]) == (512, 256)
    # an explicit cap below every valid block: an error, never a
    # silent dense fallback
    with pytest.raises(ValueError, match="no kernel blocks tile"):
        _trace(monkeypatch, 2, 2, block_q=32)


@pytest.mark.parametrize("heads,kv_heads,backward,fwd", [
    (8, 2, "dkv_resident", {}), (2, 2, "dq_resident", {}),
    (32, 2, "dkv_resident", {"fwd_block_k": 1024}),
], ids=["grouped", "ungrouped", "g16"])
def test_dispatch_is_recorded(monkeypatch, heads, kv_heads, backward, fwd):
    """The pair, the backward's form, and the forward's key block
    where it is not the pair's: which rule engaged, without a trace."""
    from dlrover_tpu.telemetry.registry import default_registry

    out = _trace(monkeypatch, heads, kv_heads)
    assert out.shape == (1, 2048, heads, 64)
    group = heads // kv_heads
    bq, bk = tuning.heuristic_blocks(2048, group)
    assert tuning.last_selection() == {
        "kernel": "flash_attention", "seq": 2048, "head_dim": 64,
        "gqa_group": group, "dtype": "bfloat16", "causal": True,
        "block_q": bq, "block_k": bk, "window": None,
        "backward": backward, "source": "static", **fwd,
    }
    assert default_registry().get("attn_key_block").labels(
        kernel="fwd").value == fwd.get("fwd_block_k", bk)


def _g16(key, seq, blocks=None):
    """q, k, v [1, seq, 16 heads on 1, 64] and, with ``blocks``, a
    causal selection of about half of every query's blocks, its own
    and block 0 among them."""
    kq, kk, kv, ks = jax.random.split(key, 4)
    q = jax.random.normal(kq, (1, seq, 16, 64), jnp.float32)
    k, v = (jax.random.normal(key, (1, seq, 1, 64), jnp.float32)
            for key in (kk, kv))
    if blocks is None:
        return q, k, v, None
    own = (jnp.arange(seq) // (seq // blocks))[:, None]
    each = jnp.arange(blocks)[None, :]
    picked = jax.random.bernoulli(ks, 0.5, (1, 1, seq, blocks))
    return q, k, v, (picked | (each == 0) | (each == own)) & (each <= own)


@pytest.mark.parametrize("selected", [False, True],
                         ids=["dense", "selected"])
def test_a_group_of_16_with_the_forwards_wider_key_block(selected):
    """Forward and gradients of the kernels in interpret mode at a
    group of 16, the forward at twice the backward's key block (the
    rule's (128, 512) and 1,024), against ``mha_reference``; with a
    selection each kernel makes its words from its own key block, 16
    bits a word in the forward and 8 in the backward."""
    from dlrover_tpu.ops.pallas import flash_attention as fa
    from dlrover_tpu.telemetry.registry import default_registry

    seq = 1024
    q, k, v, sel = _g16(jax.random.key(66), seq, 16 if selected else None)
    assert tuning.forward_key_block(
        seq, 16, (128, 512), selection_block=64 if selected else None
    ) == 1024
    mask = None if sel is None else jnp.repeat(sel, 64, axis=-1)

    def kernels(q, k, v):
        return fa.flash_attention_tpu(
            q, k, v, causal=True, block_q=128, block_k=512,
            fwd_block_k=1024, selected=sel)

    def reference(q, k, v):
        return mha_reference(q, k, v, causal=True, mask=mask)

    np.testing.assert_allclose(
        kernels(q, k, v), reference(q, k, v), rtol=2e-3, atol=2e-3)
    built = default_registry().get("attn_key_block")
    for got, want in zip(*(
        jax.grad(lambda *a: (fn(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        for fn in (kernels, reference)
    )):
        np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-3)
    assert [built.labels(kernel=name).value
            for name in ("fwd", "dq_dkv")] == [1024, 512]


def test_a_words_sign_bit_is_a_block_like_another():
    """32 selection blocks a key block fill an int32 word, sign bit
    included (``WORD_BITS``: what a 2,048-wide key block over blocks
    of 64 keys would hold; here 512 over blocks of 16)."""
    from dlrover_tpu.ops.pallas import flash_attention as fa

    seq = 512
    q, k, v, sel = _g16(jax.random.key(67), seq, 32)
    assert sel[0, 0, -1, 31]  # the last query's own block: bit 31
    assert tuning.forward_key_block(
        seq, 16, (128, 256), selection_block=16) == 512
    got = fa.flash_attention_tpu(
        q, k, v, causal=True, block_q=128, block_k=256, fwd_block_k=512,
        selected=sel)
    want = mha_reference(
        q, k, v, causal=True, mask=jnp.repeat(sel, 16, axis=-1))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("seq,block", [(256, 128), (512, 128)])
def test_a_long_latent_heads_dq_stays_resident_on_two_part_operands(
    monkeypatch, seq, block
):
    """(1, s, 192 | 128): q and k in parts of 128 and 64 columns, the
    rotated key one for every head, v 128 wide, a head's dQ resident
    over two and four blocks each way (``kimi``'s form since the one
    budget), against ``mha_reference`` and against the pair."""
    from dlrover_tpu.ops.pallas import flash_attention as fa

    keys = jax.random.split(jax.random.key(68), 5)
    q, k, v = (jax.random.normal(key, (1, seq, 2, 128), jnp.float32)
               for key in keys[:3])
    q_rope = jax.random.normal(keys[3], (1, seq, 2, 64), jnp.float32)
    k_rope = jax.random.normal(keys[4], (1, seq, 1, 64), jnp.float32)

    def kernels(*operands):
        return fa.flash_attention_tpu(
            *operands[:3], causal=True, block_q=block, block_k=block,
            q_rope=operands[3], k_rope=operands[4])

    def reference(*operands):
        return mha_reference(
            *operands[:3], causal=True, q_rope=operands[3],
            k_rope=operands[4])

    def grads(fn):
        return jax.grad(
            lambda *a: (fn(*a) ** 2).sum(), argnums=(0, 1, 2, 3, 4)
        )(q, k, v, q_rope, k_rope)

    assert fa.backward_form(1, seq, 192) == "dq_resident"
    one, want = grads(kernels), grads(reference)
    monkeypatch.setattr(fa, "_one_backward_kernel", lambda *a: False)
    pair = grads(kernels)
    for got, ref, two in zip(one, want, pair):
        np.testing.assert_allclose(got, ref, rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(got, two, rtol=1e-5, atol=1e-5)
