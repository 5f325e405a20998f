"""A looped stack in models/llama.py (``LlamaConfig.total_ut_steps``):
the whole stack walked several times with one set of weights and the
final norm inside the loop, an exit gate a position, and the loss that
weights every pass's cross entropy by the exit distribution less an
entropy term. The program against ``yardstick/references/ouro.py``,
gradients and all, is in ``tests/yardstick/test_yardstick_ouro.py``;
here the tree, the hand-made cases, the scopes and what a config of
one pass keeps."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import llama

SCOPES = ("loop.pass", "loop.exit_gate", "loop.exit_loss")


def config(**kw):
    return llama.llama_loop_tiny(**{"dtype": jnp.float32, **kw})


def batch(cfg, sequences=2, seed=1):
    tokens = jax.random.randint(
        jax.random.key(seed), (sequences, 128), 0, cfg.vocab_size)
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.full((sequences, 1), -1, jnp.int32)], axis=1)
    return tokens, targets


def drawn(params):
    """``params`` with the gate's bias off zero, where ``init_params``
    puts it."""
    gate = dict(params["exit_gate"], b=jnp.full((1,), 0.7, jnp.float32))
    return {**params, "exit_gate": gate}


def lowered(cfg):
    """The text of the loss's gradient as it is lowered, scopes and
    all."""
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    return jax.jit(jax.grad(
        lambda p, b: llama.next_token_loss(p, b, cfg)
    )).lower(params, (tokens, tokens)).as_text(debug_info=True)


def test_the_tree_the_axes_and_the_count_agree():
    cfg = config()
    assert (cfg.total_ut_steps, cfg.post_norms, cfg.num_layers) == (
        4, True, 2)
    params = llama.init_params(jax.random.key(0), cfg)
    assert params["exit_gate"]["w"].shape == (64,)
    assert params["exit_gate"]["b"].shape == (1,)
    assert params["exit_gate"]["b"].dtype == jnp.float32
    assert float(params["exit_gate"]["b"][0]) == 0.0
    # drawn fan-in normal: a logit of unit deviation from a normed state
    assert float(jnp.std(params["exit_gate"]["w"])) == pytest.approx(
        64 ** -0.5, rel=0.3)
    assert sum(x.size for x in jax.tree.leaves(params)) == (
        llama.param_count(cfg))
    plain = dataclasses.replace(cfg, total_ut_steps=1)
    assert llama.param_count(cfg) == llama.param_count(plain) + 64 + 1
    axes = llama.param_axes(cfg)
    assert axes["exit_gate"] == {"w": ("norm",), "b": (None,)}
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=is_axes)
    for leaf, names in zip(jax.tree.leaves(params),
                           jax.tree.leaves(axes, is_leaf=is_axes)):
        assert leaf.ndim == len(names)
    # the one set of weights: the stack is as deep as without the loop
    assert params["blocks"]["wq"].shape == (2, 64, 64)
    assert llama.frozen_params(cfg) is None
    # the draws that were there stay what they were
    older = llama.init_params(jax.random.key(0), plain)
    for name in ("embed", "lm_head"):
        assert (params[name] == older[name]).all()
    assert (params["blocks"]["w_up"] == older["blocks"]["w_up"]).all()


def test_one_pass_is_the_program_it_was():
    """With one pass (where ``p_1`` is the empty product, 1, and the
    entropy 0) there is no gate leaf, no ``loop.*`` scope in the
    lowered step, and the loss is ``llama_tiny(post_norms=True)``'s to
    the bit."""
    once = config(total_ut_steps=1)
    assert once == llama.llama_tiny(post_norms=True, dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), once)
    assert "exit_gate" not in params
    assert "exit_gate" not in llama.param_axes(once)
    text = lowered(once)
    assert [s for s in SCOPES if s in text] == []
    # the loop's own loss at one pass is that loss too
    looped = config()
    gate = llama.init_params(jax.random.key(0), looped)["exit_gate"]
    b = batch(once)
    want = llama.next_token_loss(params, b, once)
    nll, p, log_p, mask = llama._exit_terms(
        {**params, "exit_gate": gate}, b,
        dataclasses.replace(looped, total_ut_steps=1))
    assert (p == 1.0).all() and (log_p == 0.0).all()
    assert float(jnp.sum(nll) / jnp.sum(mask)) == pytest.approx(
        float(want), abs=1e-6)


@pytest.mark.parametrize("remat", ["off", "dots", "dots_attn_out",
                                   "minimal"])
def test_every_new_op_carries_its_scope(remat):
    """The three scopes are in the lowered step's ``op_name``s under
    every remat policy."""
    text = lowered(config(remat=remat))
    for scope in SCOPES:
        assert scope in text, scope
    # nothing of the evaluation's gauges rides on the step
    assert "callback" not in text and "outfeed" not in text


def test_the_exit_distribution_sums_to_one_and_the_case_of_two():
    logits = jax.random.normal(jax.random.key(5), (4, 3, 7)) * 3.0
    p, log_p = llama._exit_distribution(logits)
    assert p.shape == log_p.shape == (4, 3, 7)
    np.testing.assert_allclose(np.asarray(p.sum(axis=0)), 1.0, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(jnp.exp(log_p)), np.asarray(p), rtol=1e-6)
    lam = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(np.asarray(p[0]), np.asarray(lam[0]),
                               rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(p[2]),
        np.asarray(lam[2] * (1 - lam[0]) * (1 - lam[1])), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(p[3]),
        np.asarray((1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])), rtol=1e-4)
    # the last pass's own logit is not read
    other = logits.at[3].set(100.0)
    assert (llama._exit_distribution(other)[0] == p).all()
    # T = 2 by hand: a gate at logit log 3 exits with 3/4
    two, _ = llama._exit_distribution(
        jnp.array([[float(np.log(3.0))], [0.0]]))
    np.testing.assert_allclose(np.asarray(two[:, 0]), [0.75, 0.25],
                               rtol=1e-6)
    # a gate that is sure leaves a finite logarithm
    sure, log_sure = llama._exit_distribution(jnp.array([[60.0], [0.0]]))
    assert np.isfinite(np.asarray(log_sure)).all()
    assert float(sure[0, 0]) == 1.0


def test_the_loss_of_two_passes_by_hand():
    """T = 2 on the program's own states: ``lambda nll_1 + (1 -
    lambda) nll_2 - beta H``."""
    cfg = config(total_ut_steps=2, exit_entropy_weight=0.25)
    params = drawn(llama.init_params(jax.random.key(2), cfg))
    tokens, targets = batch(cfg)
    states, logits = llama._run_loop(params, tokens, cfg)
    assert states.shape == (2, 2, 128, 64) and logits.shape == (2, 2, 128)
    # a state is a normed one, and the second is the stack on the first
    np.testing.assert_allclose(
        np.asarray(jnp.mean(states ** 2, axis=-1)), 1.0, atol=1e-3)
    lam = jax.nn.sigmoid(
        states[0] @ params["exit_gate"]["w"] + 0.7)
    np.testing.assert_allclose(
        np.asarray(jax.nn.sigmoid(logits[0])), np.asarray(lam), atol=1e-6)
    logp = [jax.nn.log_softmax(s @ params["lm_head"]) for s in states]
    nll = [-jnp.take_along_axis(
        lp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
        for lp in logp]
    entropy = -(lam * jnp.log(lam) + (1 - lam) * jnp.log1p(-lam))
    by_position = lam * nll[0] + (1 - lam) * nll[1] - 0.25 * entropy
    keep = targets >= 0
    want = jnp.sum(jnp.where(keep, by_position, 0.0)) / jnp.sum(keep)
    got = llama.next_token_loss(params, (tokens, targets), cfg)
    assert float(got) == pytest.approx(float(want), abs=2e-6)
    # and what hidden_states and forward hand out is the last pass's
    last, aux = llama.hidden_states(params, tokens, cfg)
    assert (last == states[-1]).all() and float(aux) == 0.0
    assert llama.forward(params, tokens, cfg).shape == (2, 128, 256)


@pytest.fixture(scope="module")
def plain_case():
    """The loss and its gradients without remat or chunks."""
    cfg = config(remat="off")
    params = drawn(llama.init_params(jax.random.key(2), cfg))
    b = batch(cfg)
    return cfg, params, b, jax.jit(jax.value_and_grad(
        lambda p: llama.next_token_loss(p, b, cfg)))(params)


@pytest.mark.parametrize("remat,chunk", [
    ("minimal", 0), ("dots", 64), ("dots_attn_out", 48), ("off", 256)])
def test_remat_and_a_chunked_loss_change_nothing(remat, chunk, plain_case):
    """``loss_chunk`` 0 against chunks that divide the tokens and that
    do not, under every remat policy: the loss and every gradient."""
    cfg, params, b, (want, want_grads) = plain_case
    other = dataclasses.replace(cfg, remat=remat, loss_chunk=chunk)
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: llama.next_token_loss(p, b, other)))(params)
    assert float(got) == pytest.approx(float(want), abs=2e-6)
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree.leaves(want_grads)):
        assert float(jnp.abs(g - w).max()) < 2e-5 * max(
            1.0, float(jnp.abs(w).max())), path
        assert float(jnp.abs(w).max()) > 0, path


def test_the_stats_read_every_pass():
    from dlrover_tpu.telemetry.registry import default_registry

    cfg = config()
    params = drawn(llama.init_params(jax.random.key(2), cfg))
    b = batch(cfg)
    pass_loss, exit_share = jax.jit(
        lambda p, b: llama.loop_stats(p, b, cfg))(params, b)
    assert pass_loss.shape == exit_share.shape == (4,)
    assert float(exit_share.sum()) == pytest.approx(1.0, abs=1e-5)
    # a pass's own mean cross entropy, by the plain loss's function
    states, _ = llama._run_loop(params, b[0], cfg)
    for t in range(4):
        assert float(pass_loss[t]) == pytest.approx(float(llama._mean_ce(
            states[t], params["lm_head"], b[1], 0)), abs=1e-5)
    # the weighted sum lies inside the passes' range, less the entropy
    loss = float(llama.next_token_loss(params, b, cfg))
    assert loss < float(pass_loss.max())
    losses, shares = llama.set_loop_gauges(pass_loss, exit_share)
    text = default_registry().to_prometheus_text()
    for t in range(4):
        assert f'loop_pass_loss{{pass="{t + 1}"}} {losses[t]}' in text
        assert f'loop_exit_share{{pass="{t + 1}"}} {shares[t]}' in text
    # a gate pushed shut early puts the mass on the first pass
    shut = {**params, "exit_gate": dict(
        params["exit_gate"], b=jnp.full((1,), 30.0))}
    _, collapsed = llama.loop_stats(shut, b, cfg)
    assert float(collapsed[0]) > 0.999


def test_flops_count_every_pass():
    cfg = config()
    once = dataclasses.replace(cfg, total_ut_steps=1)
    h, vocab, seq = 64, 256, 128
    layer = 2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128 + 4 * 64
    met = 2 * layer + h * vocab + h  # the layers, the head, final norm
    assert llama.flops_per_token(once, seq) == pytest.approx(
        6 * met + 6 * 4 * 32 * 2 * seq, rel=1e-12)
    assert llama.flops_per_token(cfg, seq) == pytest.approx(
        4 * (6 * met + 6 * 4 * 32 * 2 * seq) + 4 * 6 * (h + 1),
        rel=1e-12)


@pytest.mark.parametrize("change,sentence", [
    (dict(total_ut_steps=0), "at least once"),
    (dict(num_experts=4), "refused, not guessed"),
    (dict(mtp_layers=1), "refused, not guessed"),
    (dict(layer_types=("full_attention", "conv")), "refused, not guessed"),
])
def test_what_the_config_refuses(change, sentence):
    with pytest.raises(ValueError, match=sentence):
        config(**change)


def test_the_trainer_steps_a_looped_model_as_every_other():
    """``trainer/sharded.py`` is not changed: the loss is one scalar
    of ``(params, batch)``; every leaf moves, the gate's two among
    them."""
    from dlrover_tpu.parallel.mesh import create_mesh
    from dlrover_tpu.trainer.sharded import make_trainer_for_llama

    cfg = config(remat="minimal")
    mesh = create_mesh([("data", 1), ("fsdp", 1)],
                       devices=jax.devices()[:1])
    trainer = make_trainer_for_llama(
        cfg, mesh, optimizer=optax.adamw(1e-2))
    params, opt_state = trainer.init(jax.random.key(0))
    before = jax.tree.map(jnp.copy, params)
    losses = []
    with mesh:
        for step in range(3):
            mb = trainer.microbatch(
                tuple(np.asarray(a) for a in batch(cfg, seed=step)))
            params, opt_state, loss = trainer.train_step(
                params, opt_state, mb)
            losses.append(float(loss))
    assert np.isfinite(losses).all()
    for (path, new), old in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree.leaves(before)):
        assert float(jnp.abs(new - old).max()) > 0, path
