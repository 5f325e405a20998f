"""The rule that moves the router's selection bias (auxiliary-loss-free
balancing, ``LlamaConfig.moe_bias_update_rate``): the trainer's bias
after a few steps against ``yardstick/references/trinity.py``'s rule on
the reference's own counts; what leaves the bias alone (a rate of 0,
the optimizer, weight decay); the counts summed over microbatches. The
static path of the families that were there before the rule is held
in ``tests/test_llama_static_path.py``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.parallel import moe
from dlrover_tpu.parallel.mesh import create_mesh
from dlrover_tpu.trainer.sharded import make_trainer_for_llama
from yardstick import cells, worker
from yardstick.references import trinity as ref

SEQ = 128
TRAFFIC = {"seq": SEQ, "remat": "minimal", "loss_chunk": 0}


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _trainer(cfg_file, accum_steps=1, **optimizer):
    cfg = worker.program_config(cfg_file, TRAFFIC)
    mesh = create_mesh(
        [("data", 1), ("fsdp", 1)], devices=jax.devices()[:1])
    trainer = make_trainer_for_llama(
        cfg, mesh, accum_steps=accum_steps,
        optimizer=optax.adamw(1e-3, **optimizer))
    return cfg, trainer, trainer.init(jax.random.key(4))


def _batch(cfg_file, start, sequences=2):
    return worker.SeededTokens(3, SEQ, cfg_file["vocab_size"])(
        start, start + sequences)


def _biases(params):
    """Every selection bias of ``params``, by its path."""
    return {
        jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in
        jax.tree_util.tree_leaves_with_path(params)
        if getattr(path[-1], "key", None) == "expert_bias"
    }


def _by_layer(params):
    """The scanned expert layers' biases in layer order, float32
    [layers, experts]: a period's positions are kept a stack each."""
    return np.stack(
        [np.asarray(p["expert_bias"]) for p in params["period"]], axis=1
    ).reshape(-1, params["period"][0]["expert_bias"].shape[-1])


def _drawn_bias(params):
    keys = iter(jax.random.split(jax.random.key(9), 64))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: 0.3 * jax.random.normal(
            next(keys), leaf.shape, leaf.dtype)
        if getattr(path[-1], "key", None) == "expert_bias" else leaf,
        params)


def test_moved_bias_raises_the_idle_and_lowers_the_busy():
    counts = jnp.asarray([[0, 4, 8, 4], [5, 5, 5, 5]])
    bias = jnp.asarray([[0.1, 0.0, -0.1, 0.2], [0.3, 0.0, 0.0, 0.0]])
    moved = np.asarray(moe.moved_bias(bias, counts, 0.5))
    # sign(mean - c) = (1, 0, -1, 0), whose mean is 0; an even load
    # moves nothing
    np.testing.assert_allclose(moved[0], [0.6, 0.0, -0.6, 0.2], atol=1e-7)
    np.testing.assert_allclose(moved[1], bias[1], atol=1e-7)
    # the step is taken less its own mean: the bias keeps its mean
    uneven = moe.moved_bias(jnp.zeros(4), jnp.asarray([9, 1, 1, 1]), 0.5)
    np.testing.assert_allclose(
        np.asarray(uneven), [-0.75, 0.25, 0.25, 0.25], atol=1e-7)
    np.testing.assert_allclose(np.asarray(ref.moved_bias(
        jnp.zeros(4), jnp.asarray([9, 1, 1, 1]), 0.5)), uneven, atol=1e-7)


def test_the_rule_needs_a_bias_to_move():
    with pytest.raises(ValueError, match="moe_bias_update_rate"):
        llama.llama_moe_tiny(moe_bias_update_rate=1e-3)
    with pytest.raises(ValueError, match="moe_bias_update_rate"):
        llama.llama_tiny(moe_bias_update_rate=1e-3, use_expert_bias=True)
    # the launcher's preset (examples/llama_train.py --model
    # llama_sandwich_tiny) has one, and the rule
    cfg = llama.llama_sandwich_tiny()
    assert cfg.use_expert_bias and cfg.moe_bias_update_rate == 1e-3
    assert cfg.post_norms and cfg.mup_enabled
    lead, period = cfg.layer_plan()
    assert [k.ffn for k in lead] == ["dense"]
    assert [(k.window, k.rope) for k in period] == [
        (32, True), (None, False), (32, True), (32, True)]


def test_three_steps_move_the_bias_as_the_references_rule_does():
    """Before each of three steps the reference counts, from the
    trainer's own parameters of that moment, the assignments of the
    step's tokens in each expert layer, and its rule moves the bias of
    that moment: the trainer's bias after the step is that, in every
    layer. The optimizer (AdamW with weight decay) has not touched it:
    its moments there are zero."""
    cfg_file = dict(config("tiny-trinity"), dtype="float32")
    cfg, trainer, (params, opt_state) = _trainer(cfg_file, weight_decay=0.1)
    rate = cfg_file["load_balance_coeff"]
    assert cfg.moe_bias_update_rate == rate == 0.001
    assert not _by_layer(params).any()
    for step in range(3):
        tokens, targets = _batch(cfg_file, 2 * step)
        counts = ref.expert_counts(cfg_file, params, jnp.asarray(tokens))
        assert counts.shape == (8, 16)
        assert (np.asarray(counts).sum(axis=1) == 2 * SEQ * 4).all()
        want = np.stack([
            np.asarray(ref.moved_bias(jnp.asarray(bias), c, rate))
            for bias, c in zip(_by_layer(params), counts)
        ])
        params, opt_state, _ = trainer.train_step(
            params, opt_state, trainer.microbatch((tokens, targets)))
        got = _by_layer(params)
        np.testing.assert_allclose(got, want, atol=1e-9)
        # an uneven load moved every layer, and by no more than a step
        assert (np.abs(got).max(axis=1) > 0).all()
        assert np.abs(got).max() <= (step + 1) * 2 * rate
        np.testing.assert_allclose(got.mean(axis=1), 0, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(llama.expert_bias_abs_max(params, cfg)),
        np.abs(got).max(axis=1))
    moments = [
        np.asarray(leaf) for path, leaf in
        jax.tree_util.tree_leaves_with_path(opt_state)
        if "expert_bias" in jax.tree_util.keystr(path)
    ]
    assert len(moments) == 2 * 4  # mu and nu of the period's positions
    assert not any(m.any() for m in moments)


def test_a_rate_of_zero_leaves_the_buffer_bit_for_bit():
    cfg_file = dict(config("tiny-trinity"), load_balance_coeff=0.0)
    cfg, trainer, (params, opt_state) = _trainer(cfg_file, weight_decay=0.1)
    assert trainer._move_buffers is None
    params = _drawn_bias(params)
    before = _biases(params)
    batch = _batch(cfg_file, 0)
    text = trainer.train_step.lower(
        params, opt_state, trainer.microbatch(batch)
    ).as_text(debug_info=True)
    assert "moe.bias_update" not in text and "norm.post_mlp" in text
    params, _, _ = trainer.train_step(
        params, opt_state, trainer.microbatch(batch))
    after = _biases(params)
    assert len(before) == 4 and before.keys() == after.keys()
    for path, was in before.items():
        assert np.abs(was).max() > 0 and (was == after[path]).all(), path


def test_accumulation_sums_the_counts_of_its_microbatches():
    """Two microbatches of one sequence each: the rule reads the sum
    of their counts, once, with the optimizer's one update."""
    cfg_file = dict(config("tiny-trinity"), dtype="float32")
    cfg, trainer, (params, opt_state) = _trainer(cfg_file, accum_steps=2)
    rate = cfg_file["load_balance_coeff"]
    tokens, targets = _batch(cfg_file, 0)
    both = ref.expert_counts(cfg_file, params, jnp.asarray(tokens))
    first = ref.expert_counts(cfg_file, params, jnp.asarray(tokens[:1]))
    assert (both.sum(axis=1) == 2 * first.sum(axis=1)).all()

    def ruled(counts):
        return np.stack([
            np.asarray(ref.moved_bias(jnp.zeros(16), c, rate))
            for c in counts
        ])

    assert np.abs(ruled(both) - ruled(first)).max() > rate / 2
    mb = trainer.microbatch((tokens, targets))
    assert mb[0].shape == (2, 1, SEQ)
    params, _, _ = trainer.train_step(params, opt_state, mb)
    np.testing.assert_allclose(_by_layer(params), ruled(both), atol=1e-9)


def test_a_prediction_modules_router_is_moved_with_the_stacks():
    cfg = llama.llama_latent_tiny(
        moe_bias_update_rate=1e-3, dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg)
    tokens, targets = worker.SeededTokens(3, SEQ, cfg.vocab_size)(0, 2)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    (loss, counts), plain = jax.jit(lambda p, b: (
        llama.loss_and_expert_counts(p, b, cfg),
        llama.next_token_loss(p, b, cfg)))(params, batch)
    assert float(loss) == pytest.approx(float(plain), abs=1e-6)
    assert counts["stack"].shape == (2, 8) and counts["mtp"].shape == (8,)
    assert int(counts["mtp"].sum()) == 2 * SEQ * cfg.moe_top_k
    moved = llama.moved_expert_bias(params, counts, cfg)
    for got, c in (
            (moved["mtp"][0]["block"]["expert_bias"], counts["mtp"]),
            (moved["period"][0]["expert_bias"], counts["stack"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(
            moe.moved_bias(jnp.zeros(c.shape), c, 1e-3)), atol=1e-9)
        assert np.abs(np.asarray(got)).max() > 0


def test_a_buffer_rule_brings_what_is_differentiated():
    """``move_buffers`` is the pair: a ``value_and_grad`` beside it
    would be a second answer to what the step differentiates."""
    from dlrover_tpu.trainer.sharded import ShardedTrainer

    cfg = worker.program_config(config("tiny-trinity"), TRAFFIC)
    mesh = create_mesh(
        [("data", 1), ("fsdp", 1)], devices=jax.devices()[:1])
    loss = lambda p, b: llama.next_token_loss(p, b, cfg)  # noqa: E731
    with pytest.raises(ValueError, match="move_buffers"):
        ShardedTrainer(
            loss, lambda rng: llama.init_params(rng, cfg),
            llama.param_axes(cfg), mesh,
            value_and_grad=jax.value_and_grad(loss),
            move_buffers=(
                lambda p, b: llama.loss_and_expert_counts(p, b, cfg),
                lambda p, c: llama.moved_expert_bias(p, c, cfg)),
        )
