"""The static path of the families that were there before ISSUE 49's
norms on a branch's result, embedding factor and bias rule: each new
branch is chosen in Python by the config, so a config without the new
keys has the parameter tree and the training step it had."""

import jax
import numpy as np
import pytest

from .test_moe_bias_rule import (
    _batch, _biases, _drawn_bias, _trainer, config,
)

SCOPES = ("embed.mup", "norm.post_attn", "norm.post_mlp", "moe.bias_update")


def _step_of(name):
    """``(cfg, trainer, state, microbatches, the lowered training
    step's text)`` of the tiny configuration ``name`` through
    ``make_trainer_for_llama``."""
    cfg_file = config(name)
    cfg, trainer, state = _trainer(cfg_file)
    mb = trainer.microbatch(_batch(cfg_file, 0))
    text = trainer.train_step.lower(*state, mb).as_text(debug_info=True)
    return cfg, trainer, state, mb, text


#: the leaves of each tiny configuration's tree before ISSUE 49 (read
#: on the parent commit)
LEAVES = {"tiny-llama": 12, "tiny-olmoe": 15, "tiny-lfm2": 53,
          "tiny-solar": 96}


@pytest.mark.parametrize("name", list(LEAVES))
def test_the_families_that_were_there_compile_what_they_compiled(name):
    """The new branches are chosen in Python by the config: a config
    without the new keys has no new leaf, none of the new scopes in
    its lowered training step, and a bias buffer that a step leaves
    bit for bit."""
    cfg, trainer, (params, opt_state), mb, text = _step_of(name)
    assert not (cfg.post_norms or cfg.mup_enabled
                or cfg.moe_bias_update_rate)
    assert trainer._move_buffers is None
    names = {
        path[-1].key for path, _ in
        jax.tree_util.tree_leaves_with_path(params)
        if hasattr(path[-1], "key")
    }
    assert not names & {"post_attn_norm", "post_mlp_norm"}
    assert len(jax.tree.leaves(params)) == LEAVES[name]
    assert not [scope for scope in SCOPES if scope in text]
    if not cfg.use_expert_bias:
        assert "expert_bias" not in names
        return
    params = _drawn_bias(params)
    before = _biases(params)
    params, _, _ = trainer.train_step(params, opt_state, mb)
    after = _biases(params)
    assert before and before.keys() == after.keys()
    for path, was in before.items():
        assert np.abs(was).max() > 0 and (was == after[path]).all(), path


def test_trinitys_step_carries_the_four_scopes():
    _, trainer, _, _, text = _step_of("tiny-trinity")
    assert trainer._move_buffers is not None
    assert [scope for scope in SCOPES if scope in text] == list(SCOPES)
    for scope in ("attn.window", "attn.full", "attn.gate", "moe.shared"):
        assert scope in text, scope


def test_the_head_is_drawn_at_its_fan_in_deviation_unless_stated():
    from dlrover_tpu.models import llama

    key = jax.random.key(1)
    plain = llama.llama_tiny(dtype=np.float32)
    head = np.asarray(llama.init_params(key, plain)["lm_head"])
    assert head.std() == pytest.approx(plain.hidden_size ** -0.5, rel=0.05)
    stated = llama.llama_tiny(dtype=np.float32, head_init_std=0.25)
    larger = llama.init_params(key, stated)
    # the same draw, scaled; nothing else of the tree moves
    np.testing.assert_allclose(
        np.asarray(larger["lm_head"]),
        head * 0.25 * plain.hidden_size ** 0.5, rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(larger["embed"]),
        np.asarray(llama.init_params(key, plain)["embed"]))
