"""The dropless expert path (parallel/moe.py ``dropless_moe_mlp``),
and the grouped matmul under it (ops/grouped_matmul.py); what
models/llama.py gained with them is in test_llama_experts.py. Compared with a dense-mask computation
that shares no line with the path: every expert on every token, masked
by the top-k of the float32 softmax. A share's walk of the sorted rows
in chunks: test_moe_share_walk.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.grouped_matmul import grouped_matmul
from dlrover_tpu.parallel import moe

H, M, E = 16, 32, 8
#: experts no token may choose, and the one every token chooses first
DEAD, CROWDED = (5, 6, 7), 2


def _layer(seed, tied=(0, 1)):
    """A routed layer's weights and inputs in float32, with experts
    ``tied`` given the same router column (equal logits: forced ties),
    ``CROWDED`` the largest logit of every token and ``DEAD`` the
    smallest (feature 0 of every token is a large constant)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (2, 12, H))
    x = x.at[..., 0].set(2.0)
    router = jax.random.normal(ks[1], (H, E)) * 0.3
    router = router.at[:, tied[1]].set(router[:, tied[0]])
    router = router.at[0].set(0.0)
    router = router.at[:, CROWDED].set(0.0).at[0, CROWDED].set(2.0)
    router = router.at[0, jnp.array(DEAD)].set(-5.0)
    w_gate = jax.random.normal(ks[2], (E, H, M)) * H ** -0.5
    w_up = jax.random.normal(ks[3], (E, H, M)) * H ** -0.5
    w_down = jax.random.normal(ks[4], (E, M, H)) * M ** -0.5
    return x, router, w_gate, w_up, w_down


def _dense_mask(x, router, w_gate, w_up, w_down, k, norm):
    """Every expert on every token; the top-k of the float32 softmax
    (ties to the lower index) as a mask over the experts."""
    flat = x.reshape(-1, x.shape[-1])
    logits = flat @ router
    probs = jax.nn.softmax(logits, axis=-1)
    chosen = jax.lax.top_k(probs, k)[1]
    mask = jnp.sum(jax.nn.one_hot(chosen, E), axis=1)
    weights = probs * mask
    if norm and k > 1:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    every = jnp.einsum(
        "nem,emh->neh",
        jax.nn.silu(jnp.einsum("nh,ehm->nem", flat, w_gate))
        * jnp.einsum("nh,ehm->nem", flat, w_up),
        w_down,
    )
    out = jnp.einsum("ne,neh->nh", weights, every).reshape(x.shape)
    f = jnp.sum(mask, axis=0) / (flat.shape[0] * k)
    aux = 0.01 * E * jnp.sum(f * jnp.mean(probs, axis=0)) + 0.001 * (
        jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    )
    return out, aux


def _objective(fn, k, norm):
    def objective(args):
        out, aux = fn(*args, k, norm)
        return jnp.sum(jnp.sin(out)) + 7.0 * aux

    return objective


@pytest.mark.parametrize("k,norm", [(1, False), (2, False), (2, True),
                                    (4, False)])
def test_dropless_equals_dense_mask(k, norm):
    args = _layer(k)
    with jax.default_matmul_precision("highest"):
        out, aux = moe.dropless_moe_mlp(*args, k, norm)
        ref_out, ref_aux = _dense_mask(*args, k, norm)
        grads = jax.grad(_objective(moe.dropless_moe_mlp, k, norm))(
            args)
        ref_grads = jax.grad(_objective(_dense_mask, k, norm))(args)
    np.testing.assert_allclose(out, ref_out, atol=2e-5)
    np.testing.assert_allclose(aux, ref_aux, rtol=1e-6)
    for name, g, r in zip(("x", "router", "w_gate", "w_up", "w_down"),
                          grads, ref_grads):
        np.testing.assert_allclose(g, r, atol=3e-5, err_msg=name)
    # the crowded expert's weights get gradient, the dead ones' none
    assert float(jnp.abs(grads[2][CROWDED]).sum()) > 0
    assert float(jnp.abs(grads[2][jnp.array(DEAD)]).sum()) == 0


@pytest.mark.parametrize("k", [1, 2, 4])
def test_no_token_is_lost(k):
    x, router = _layer(3)[:2]
    n = x.shape[0] * x.shape[1]
    counts = np.asarray(moe.tokens_per_expert(x, router, k))
    assert counts.sum() == n * k  # group sizes sum to N x k
    assert counts[CROWDED] == n  # one expert takes every token
    assert (counts[list(DEAD)] == 0).all()  # several take none
    # the tied pair: ties go to the lower index, so expert 0 never
    # has fewer tokens than its twin
    assert counts[0] >= counts[1]
    flat = x.reshape(n, H)
    weights, experts, _ = moe.route(flat, router, k, False)
    assert experts.shape == (n, k)
    # each token's k experts are distinct
    assert all(len(set(row)) == k for row in np.asarray(experts))


def test_top1_keeps_the_raw_gate():
    """k = 1: the weight is the raw probability whether or not the
    config renormalises (a renormalised single weight is 1.0 and the
    router gets no gradient through the output)."""
    args = _layer(5)
    flat = args[0].reshape(-1, H)
    for norm in (True, False):
        weights, _, _ = moe.route(flat, args[1], 1, norm)
        probs = jax.nn.softmax(flat @ args[1], axis=-1)
        np.testing.assert_allclose(
            weights[:, 0], jnp.max(probs, axis=-1), rtol=1e-6)
        g = jax.grad(lambda r: jnp.sum(moe.dropless_moe_mlp(
            args[0], r, *args[2:], 1, norm)[0] ** 2))(args[1])
        assert float(jnp.linalg.norm(g)) > 1e-5


def test_unnormalised_weights_are_the_softmax_own():
    args = _layer(6)
    flat = args[0].reshape(-1, H)
    raw, _, _ = moe.route(flat, args[1], 4, False)
    normed, _, _ = moe.route(flat, args[1], 4, True)
    assert float(jnp.max(jnp.sum(raw, axis=-1))) < 1.0
    np.testing.assert_allclose(jnp.sum(normed, axis=-1), 1.0,
                               rtol=1e-6)


@pytest.mark.parametrize("sizes", [
    (5, 0, 7, 0, 0, 12), (24, 0, 0, 0, 0, 0), (0, 0, 0, 0, 0, 24),
    (4, 4, 4, 4, 4, 4),
])
def test_grouped_matmul_against_a_loop(sizes):
    """Forward and both gradients against a Python loop over the
    groups, empty groups among them."""
    ks = jax.random.split(jax.random.key(sum(sizes) + sizes[0]), 3)
    lhs = jax.random.normal(ks[0], (24, H))
    rhs = jax.random.normal(ks[1], (len(sizes), H, M))
    cot = jax.random.normal(ks[2], (24, M))
    group_sizes = jnp.array(sizes, jnp.int32)

    def loop(lhs, rhs):
        out, start = [], 0
        for g, size in enumerate(sizes):
            out.append(lhs[start:start + size] @ rhs[g])
            start += size
        return jnp.concatenate(out)

    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(
            lambda a, b: grouped_matmul(a, b, group_sizes), lhs, rhs)
        ref, ref_vjp = jax.vjp(loop, lhs, rhs)
        np.testing.assert_allclose(out, ref, atol=1e-5)
        for g, r in zip(vjp(cot), ref_vjp(cot)):
            np.testing.assert_allclose(g, r, atol=1e-5)


def test_grouped_matmul_refuses_mismatched_shapes():
    with pytest.raises(ValueError):
        grouped_matmul(jnp.zeros((4, 3)), jnp.zeros((2, 5, 3)),
                       jnp.array([2, 2]))
    with pytest.raises(ValueError):
        grouped_matmul(jnp.zeros((4, 3)), jnp.zeros((2, 3, 5)),
                       jnp.array([2, 1, 1]))


def test_capacity_path_follows_the_config_too():
    """The einsum path takes ``norm_topk_prob`` and counts ``f_e``
    over all k choices, as the dropless one: with room for every
    token the two agree."""
    args = _layer(9)
    with jax.default_matmul_precision("highest"):
        for norm in (True, False):
            out, aux = moe.moe_mlp(*args, k=2, capacity_factor=float(E),
                                   norm_topk_prob=norm)
            ref, ref_aux = moe.dropless_moe_mlp(*args, 2, norm)
            np.testing.assert_allclose(out, ref, atol=2e-5)
            np.testing.assert_allclose(aux, ref_aux, rtol=1e-6)


_GATES = {"sigmoid": jax.nn.sigmoid,
          "softmax": lambda logits: jax.nn.softmax(logits, axis=-1)}


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _tied_logits(seed, e, tokens=48):
    """Logits in steps of a quarter (scores tie all over a row), the
    first two experts the same column, and a bias drawn in steps of an
    eighth (score plus bias ties too)."""
    ks = jax.random.split(jax.random.key(seed), 3)
    logits = jnp.round(4.0 * jax.random.normal(ks[0], (tokens, e))) / 4.0
    logits = logits.at[:, 1].set(logits[:, 0])
    bias = jnp.round(8.0 * 0.3 * jax.random.normal(ks[1], (e,))) / 8.0
    return logits, bias, ks[2]


@pytest.mark.parametrize("e,k,gate", [
    (32, 4, "sigmoid"), (128, 8, "sigmoid"), (256, 8, "sigmoid"),
    (320, 8, "sigmoid"), (512, 22, "sigmoid"), (64, 8, "softmax"),
])
def test_biased_weights_are_take_along_axis_bit_for_bit(e, k, gate):
    """A biased router's weights and ``d weights / d logits`` against
    ``jnp.take_along_axis`` and its scatter-add, written here: the
    same float32 bits, with scores that tie and a drawn bias, whether
    the route is run op by op or as one jitted program."""
    logits, bias, key = _tied_logits(e + k, e)
    cot = jax.random.normal(key, (logits.shape[0], k))

    def reference(logits):
        probs = _GATES[gate](logits)
        _, experts = jax.lax.top_k(probs + bias, k)
        return jnp.take_along_axis(probs, experts, axis=-1), experts

    def routed(logits):
        weights, experts, _ = moe.route_logits(
            logits, k, False, gate=gate, bias=bias)
        return weights, experts

    for wrap in (lambda f: f, jax.jit):
        want, want_vjp, want_experts = jax.vjp(
            wrap(reference), logits, has_aux=True)
        got, got_vjp, got_experts = jax.vjp(
            wrap(routed), logits, has_aux=True)
        np.testing.assert_array_equal(got_experts, want_experts)
        assert got.dtype == jnp.float32
        np.testing.assert_array_equal(_bits(got), _bits(want))
        np.testing.assert_array_equal(
            _bits(got_vjp(cot)[0]), _bits(want_vjp(cot)[0]))
    # the ties are there: some token's k-th score plus bias is its
    # next one's
    ranked = -np.sort(-np.asarray(_GATES[gate](logits) + bias), axis=-1)
    assert (ranked[:, k - 1] == ranked[:, k]).any()
    # renormalised and scaled, the weights still are the reference's
    normed, _, _ = moe.route_logits(
        logits, k, True, gate=gate, bias=bias, scaling=2.5)
    total = jnp.sum(want, axis=-1, keepdims=True)
    if gate == "sigmoid":
        total = total + 1e-6
    np.testing.assert_array_equal(normed, want / total * 2.5)


@pytest.mark.parametrize("gate", ["softmax", "sigmoid"])
def test_a_router_without_a_bias_returns_top_ks_own_values(gate):
    logits, _, _ = _tied_logits(11, 64)
    weights, experts, _ = moe.route_logits(logits, 8, False, gate=gate)
    values, indices = jax.lax.top_k(_GATES[gate](logits), 8)
    np.testing.assert_array_equal(experts, indices)
    np.testing.assert_array_equal(_bits(weights), _bits(values))
