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
#: line of these cells was read at; and the two high groups at which
#: ``block_caps`` narrows block_k to keep the score block's area
@pytest.mark.parametrize("shape,blocks", [
    (CELL_ATTENTION["gpt2-xl"], (1024, 1024)),
    (CELL_ATTENTION["olmoe"], (1024, 1024)),
    (CELL_ATTENTION["mistral"], (256, 1024)),
    (_llama_1b(), (128, 1024)),
    ((1, 4096, 32, 2, 128), (128, 512)),
    ((1, 4096, 32, 1, 128), (128, 256)),
], ids=["gpt2-xl", "olmoe", "mistral", "llama_1b", "g16", "g32"])
def test_rule_at_the_measured_shapes(shape, blocks):
    _, seq, heads, kv_heads, _ = shape
    group = heads // kv_heads
    assert tuning.heuristic_blocks(seq, group) == blocks
    # the fp32 score block never outgrows ROWS_CAP x 1024
    assert group * blocks[0] * blocks[1] <= tuning.ROWS_CAP * 1024


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


@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (2, 2)],
                         ids=["grouped", "ungrouped"])
def test_dispatch_is_recorded(monkeypatch, heads, kv_heads):
    out = _trace(monkeypatch, heads, kv_heads)
    assert out.shape == (1, 2048, heads, 64)
    group = heads // kv_heads
    bq, bk = tuning.heuristic_blocks(2048, group)
    assert tuning.last_selection() == {
        "kernel": "flash_attention", "seq": 2048, "head_dim": 64,
        "gqa_group": group, "dtype": "bfloat16", "causal": True,
        "block_q": bq, "block_k": bk, "window": None,
        "source": "static",
    }
