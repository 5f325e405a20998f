"""The head's cross entropy with its own backward rule
(``models/llama.py _head_nll``) against the plain rule it stands for,
``_position_nll((x @ head).astype(float32), targets)``: the same value
to float32 rounding, and the gradients of ``x`` and ``head`` to float32
rounding in float32 and to one bfloat16 ulp in bfloat16, where the
plain rule's transpose rounds the same float32 gradient of the logits.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import gpt, llama

ROWS, SEQ, HIDDEN = 2, 24, 32
#: an odd number of columns, as 50,257 is
VOCAB = 257


def _operands(dtype, targets="some masked"):
    kx, kh, kt = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(kx, (ROWS, SEQ, HIDDEN), jnp.float32)
    head = jax.random.normal(kh, (HIDDEN, VOCAB), jnp.float32) * 0.3
    low = {"none masked": 0, "some masked": -1}.get(targets)
    if low is None:
        t = jnp.full((ROWS, SEQ), -1, jnp.int32)
    else:
        t = jnp.maximum(
            jax.random.randint(kt, (ROWS, SEQ), 8 * low, VOCAB), low)
        assert bool(jnp.any(t < 0)) == (low < 0)
    return (x.astype(dtype), head.astype(dtype)), t


def _plain_nll(x, head, targets):
    return llama._position_nll((x @ head).astype(jnp.float32), targets)[0]


def _rule_nll(x, head, targets):
    return llama._head_nll(x, head, targets)[0]


def _weights(targets):
    """A cotangent a position, as a looped stack's exit distribution
    hands ``_ce_by_position``'s."""
    return jax.random.uniform(jax.random.key(7), targets.shape) - 0.25


def _summed(use, targets="some masked"):
    """A case ``dtype -> (operands, the rule's loss of them, the plain
    rule's)`` from what a model file does with a rule's nll a
    position, ``use(nll, x, head, targets)``."""
    def case(dtype):
        wrt, t = _operands(dtype, targets)
        return wrt, *(
            (lambda wrt, nll=nll: jnp.sum(use(nll, *wrt, t)))
            for nll in (_rule_nll, _plain_nll))
    return case


def chunked(dtype):
    """``_chunked_ce`` and ``_ce_by_position`` with a chunk that does
    not divide the 48 tokens, against the plain rule on the whole."""
    wrt, t = _operands(dtype)
    weights = _weights(t)

    def rule(wrt):
        nll_sum, count = llama._chunked_ce(*wrt, t, 20)
        return nll_sum / count + jnp.sum(
            llama._ce_by_position(*wrt, t, 20) * weights)

    def plain(wrt):
        nll = _plain_nll(*wrt, t)
        return jnp.sum(nll) / jnp.sum(t >= 0) + jnp.sum(nll * weights)
    return wrt, rule, plain


def gpt_tied(dtype):
    """``gpt.next_token_loss`` through the embedding's rows as the
    head, against the mean over ``gpt.forward``'s float32 logits."""
    cfg = dataclasses.replace(gpt.gpt_tiny(dtype=dtype), vocab_size=VOCAB)
    assert cfg.tie_lm_head and cfg.loss_chunk == 0
    params = gpt.init_params(jax.random.key(1), cfg)
    tokens = jax.random.randint(jax.random.key(2), (ROWS, SEQ), 0, VOCAB)
    targets = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)

    def plain(params):
        nll, count = llama._masked_nll(
            gpt.forward(params, tokens, cfg), targets)
        return nll / count
    return params, lambda params: gpt.next_token_loss(
        params, (tokens, targets), cfg), plain


def _ulp(values, dtype):
    """The spacing of ``dtype`` at each of ``values``' magnitudes."""
    tiny, eps = float(jnp.finfo(dtype).tiny), float(jnp.finfo(dtype).eps)
    return eps * 2.0 ** np.floor(np.log2(np.maximum(np.abs(values), tiny)))


def _as_it_is(nll, x, head, t):
    return nll(x, head, t)


CASES = {
    "whole": _summed(_as_it_is, "none masked"),
    "masked": _summed(_as_it_is),
    "all_masked": _summed(_as_it_is, "all masked"),
    "by_position": _summed(
        lambda nll, x, head, t: nll(x, head, t) * _weights(t)),
    # as ``_exit_terms`` calls it: the forward runs again in the
    # backward and nothing is kept from the first
    "under_checkpoint": _summed(lambda nll, x, head, t: jax.checkpoint(
        lambda x, head: nll(x, head, t))(x, head)),
    "chunked": chunked,
    "gpt_tied": gpt_tied,
}


@pytest.mark.parametrize("name,dtype", [
    (name, dtype) for name in CASES for dtype in ("float32", "bfloat16")
    # a sum of rounded parts (a chunk's, a layer's) is not the rounded
    # sum: the bfloat16 cases are those of one call of the rule
    if dtype == "float32" or name not in ("chunked", "gpt_tied")
])
def test_the_rule_is_the_plain_rule(name, dtype):
    """Value and gradients of every way the model files reach the
    rule."""
    case, dtype = CASES[name], jnp.dtype(dtype)
    wrt, rule, plain = case(dtype)
    got, got_grads = jax.value_and_grad(rule)(wrt)
    want, want_grads = jax.value_and_grad(plain)(wrt)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        assert (g.dtype, g.shape) == (w.dtype, w.shape)
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if dtype == jnp.bfloat16:
            assert np.all(np.abs(g - w) <= _ulp(w, jnp.bfloat16))
        else:
            np.testing.assert_allclose(
                g, w, rtol=1e-5, atol=1e-6 * max(1.0, np.abs(w).max()))
    if name == "all_masked":
        assert float(got) == 0.0 and not any(
            np.any(np.asarray(g, np.float32))
            for g in jax.tree.leaves(got_grads))


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_the_gradient_scatters_nothing_and_keeps_no_float32_logits():
    """The jaxpr of the gradient for bfloat16 operands: no ``gather``
    and no ``scatter`` (the target's logit is read, and its gradient
    placed, by a comparison), and of the residuals that the forward
    hands the backward nothing of [tokens, vocab] is float32: the
    logits in the product's bfloat16 and a float32 row sum."""
    (x, head), t = _operands(jnp.bfloat16)
    names = {eqn.primitive.name for eqn in _equations(jax.make_jaxpr(
        jax.grad(lambda x, head: jnp.sum(llama._head_nll(x, head, t)[0]),
                 (0, 1)))(x, head).jaxpr)}
    assert not {n for n in names if "gather" in n or "scatter" in n}, names
    assert "dot_general" in names
    _, kept = jax.eval_shape(llama._head_nll_fwd, x, head, t)
    wide = [k for k in kept if k.shape == (ROWS, SEQ, VOCAB)]
    assert [k.dtype for k in wide] == [jnp.bfloat16]
    assert (ROWS, SEQ) in [k.shape for k in kept if k.dtype == jnp.float32]
    # and the plain rule, which the same search does find out
    plain = {eqn.primitive.name for eqn in _equations(jax.make_jaxpr(
        jax.grad(lambda x, head: jnp.sum(_plain_nll(x, head, t)),
                 (0, 1)))(x, head).jaxpr)}
    assert {n for n in plain if "gather" in n or "scatter" in n}


def test_the_scope_names_the_head_forward_and_backward():
    """``loss.head`` is in the lowered gradient's ``op_name``s on both
    of the head's sides, and ``benchmarks/trace_by_scope.py`` reads it
    ahead of ``loss`` and of a looped stack's ``loop.exit_loss``, and
    behind a prediction module's ``mtp.head``."""
    (x, head), t = _operands(jnp.bfloat16)
    text = jax.jit(jax.grad(
        lambda x, head: jnp.sum(llama._head_nll(x, head, t)[0]), (0, 1)
    )).lower(x, head).as_text(debug_info=True)
    # a location stands once in the text, whatever the ops it names
    assert '/jvp(loss.head)/dot_general"' in text
    assert '/transpose(jvp(loss.head))/dot_general"' in text
    assert '/transpose(jvp(loss.head))/exp"' in text

    spec = importlib.util.spec_from_file_location(
        "trace_by_scope", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "trace_by_scope.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    def scope_of(op_name):
        return next(s for s in tool.SCOPES if s in op_name)

    assert scope_of("jit(step)/loss/jvp(loss.head)/dot_general") == (
        "loss.head")
    assert scope_of("jit(step)/loss/transpose(jvp(loop.exit_loss))/loss/jvp("
                    "loop.exit_loss)/checkpoint/loss.head/dot_general") == (
        "loss.head")
    assert scope_of("jit(step)/loss/jvp(loop.exit_loss)/mul") == (
        "loop.exit_loss")
    assert scope_of("jit(step)/loss/jvp(mtp.head)/loss.head/exp") == (
        "mtp.head")
    assert scope_of("jit(step)/loss/div") == "loss"
