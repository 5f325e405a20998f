"""The window of the flash-attention kernels (interpret mode on the
CPU) against a dense mask: forward, gradients, the census and its
gauges. A module of its own beside ``test_flash_attention.py``, so that
the two run on two workers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.ops.pallas import flash_attention
from dlrover_tpu.ops.pallas.flash_attention import flash_attention_tpu

from .test_flash_attention import _rand_qkv


# (seq, block_q, block_k, g, window): the window below, equal to and
# above the block; one that both edges cross in one block; a window
# of one key; groups 1, 4 and 7 (7: the folded rows are no power of
# two); unequal blocks, so that the clamps at both ends of a row's
# and of a column's live blocks are exercised
WINDOWED = [
    (256, 64, 64, 1, 32),
    (256, 64, 64, 1, 64),
    (256, 64, 64, 1, 100),
    (256, 64, 64, 4, 64),
    (256, 64, 128, 7, 80),
    (512, 64, 256, 7, 128),
    (256, 128, 64, 1, 50),
    (128, 64, 64, 1, 1),
    (256, 64, 64, 1, 255),
]


def _windowed(seq, bq, bk, g, window, d=32):
    q, k, v = _rand_qkv(jax.random.key(seq + window), 1, seq, g, 1, d)

    def kernel(q, k, v):
        return flash_attention_tpu(
            q, k, v, causal=True, block_q=bq, block_k=bk, window=window)

    def dense(q, k, v):
        i = np.arange(seq)
        keep = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < window)
        return mha_reference(q, k, v, causal=False, mask=jnp.asarray(keep))

    return (q, k, v), kernel, dense


@pytest.mark.parametrize("what", ["forward", "gradients", "census"])
@pytest.mark.parametrize("seq,bq,bk,g,window", WINDOWED)
def test_window_against_a_dense_mask(seq, bq, bk, g, window, what):
    qkv, kernel, dense = _windowed(seq, bq, bk, g, window)
    if what == "forward":
        np.testing.assert_allclose(
            kernel(*qkv), dense(*qkv), rtol=2e-3, atol=2e-3)
        # the reference's own window is the same band
        np.testing.assert_allclose(
            mha_reference(*qkv, causal=True, window=window), dense(*qkv),
            rtol=1e-6, atol=1e-6)
    elif what == "gradients":
        grads = [
            jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2))(*qkv)
            for f in (kernel, dense)
        ]
        for got, want, name in zip(*grads, "qkv"):
            np.testing.assert_allclose(
                got, want, rtol=5e-3, atol=5e-3, err_msg=f"d{name}")
    else:
        i = np.arange(seq)
        keep = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < window)
        t = keep.reshape(seq // bq, bq, seq // bk, bk)
        some, every = t.any(axis=(1, 3)), t.all(axis=(1, 3))
        assert flash_attention.causal_tile_census(
            seq, bq, bk, bq, bk, window
        ) == (int(some.sum()), int(some.sum()), int((some & ~every).sum()))


@pytest.mark.parametrize("g", [1, 4, 7])
def test_window_that_reaches_every_key_is_plain_causal(g):
    """To the last bit, forward and gradients: such a call builds the
    kernels of a call without a window."""
    (q, k, v), _, _ = _windowed(256, 64, 64, g, 256)

    def run(window):
        f = lambda q, k, v: flash_attention_tpu(  # noqa: E731
            q, k, v, causal=True, block_q=64, block_k=64, window=window)
        return f(q, k, v), jax.grad(
            lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2))(q, k, v)

    for got, want in zip(jax.tree.leaves(run(256)),
                         jax.tree.leaves(run(None))):
        np.testing.assert_array_equal(got, want)
    assert jax.make_jaxpr(lambda *a: run(4096)[0])(q, k, v).pretty_print() \
        == jax.make_jaxpr(lambda *a: run(None)[0])(q, k, v).pretty_print()


def test_windowed_census_at_16k_and_its_gauges():
    """The new cell's windowed layers: 58.7 M live pairs a head of
    134.2 M, in (128, 1024) blocks."""
    seq, window = 16384, 4096
    covered, computed, masked = flash_attention.causal_tile_census(
        seq, 128, 1024, 128, 1024, window)
    full = flash_attention.causal_tile_census(seq, 128, 1024, 128, 1024)
    assert full == (1088, 1088, 128)
    assert (covered, computed, masked) == (560, 560, 224)
    from dlrover_tpu.telemetry.registry import default_registry

    (q, k, v), kernel, _ = _windowed(256, 64, 64, 1, 64)
    kernel(q, k, v)
    text = default_registry().to_prometheus_text()
    line = next(ln for ln in text.splitlines() if ln.startswith(
        'attn_tiles_masked_share{kernel="fwd",window="64"} '))
    # 7 live blocks of 16: 4 on the diagonal, 3 that the window's edge
    # crosses (by one pair each)
    assert float(line.split()[1]) == 1.0


def test_window_needs_causal():
    (q, k, v), _, _ = _windowed(128, 64, 64, 1, 32)
    with pytest.raises(ValueError, match="causal band"):
        flash_attention_tpu(q, k, v, causal=False, window=32)
    with pytest.raises(ValueError, match="causal band"):
        mha_reference(q, k, v, causal=False, window=32)
