"""Experts without a gate matrix (``W_down act(W_up u)``, ``relu2``)
and experts in a latent (parallel/moe.py ``dropless_moe_mlp`` with
``w_gate`` None and with ``latent``): the one pass and the share's
walk, with its written-out backward, against a dense loop over the
experts; router and shared expert on the stream while the experts read
the latent; sixty-four shares adding up to the whole layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.parallel import moe

H, LATENT, M, MS, E, K = 48, 24, 20, 36, 16, 5
ROUTING = dict(
    k=K, norm_topk_prob=True, z_coef=0.0, gate="sigmoid", norm_eps=1e-20,
    scaling=5.0, act="relu2",
)


def relu2(a):
    return jnp.square(jax.nn.relu(a))


def layer(seed=3, latent=True):
    ks = jax.random.split(jax.random.key(seed), 9)
    wide = LATENT if latent else H
    p = {
        "x": jax.random.normal(ks[0], (2, 24, H)),
        "router": jax.random.normal(ks[1], (H, E)) * H ** -0.5,
        "bias": 0.3 * jax.random.normal(ks[2], (E,)),
        "w_up": jax.random.normal(ks[3], (E, wide, M)) * wide ** -0.5,
        "w_down": jax.random.normal(ks[4], (E, M, wide)) * M ** -0.5,
        "ws_up": jax.random.normal(ks[5], (H, MS)) * H ** -0.5,
        "ws_down": jax.random.normal(ks[6], (MS, H)) * MS ** -0.5,
    }
    if latent:
        p["down"] = jax.random.normal(ks[7], (H, LATENT)) * H ** -0.5
        p["up"] = jax.random.normal(ks[8], (LATENT, H)) * LATENT ** -0.5
    return p


def dense(p, first=0, held=E, shared=True):
    """The layer as a loop over the held experts, each on every token
    and kept where the router chose it."""
    x = p["x"]
    score = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(score + p["bias"], K)
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    picked = 5.0 * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    weights = jnp.einsum(
        "bsk,bske->bse", picked, jax.nn.one_hot(chosen, E))
    u = x @ p["down"] if "down" in p else x
    total = sum(
        weights[..., e, None] * (relu2(u @ p["w_up"][e]) @ p["w_down"][e])
        for e in range(first, first + held))
    if "up" in p:
        total = total @ p["up"]
    if shared:
        total = total + relu2(x @ p["ws_up"]) @ p["ws_down"]
    return total


def program(p, first=0, held=E, shared=True):
    cut = slice(first, first + held)
    kw = dict(ROUTING, first_held=first, bias=p["bias"])
    if shared:
        kw["shared"] = (None, p["ws_up"], p["ws_down"])
    if "down" in p:
        kw["latent"] = (p["down"], p["up"])
    return moe.dropless_moe_mlp(
        p["x"], p["router"], None, p["w_up"][cut], p["w_down"][cut], **kw
    )[0]


@pytest.mark.parametrize("latent", [True, False], ids=["latent", "stream"])
@pytest.mark.parametrize("first,held", [(0, E), (4, 6), (0, 1)],
                         ids=["every expert", "a share", "one expert"])
def test_ungated_experts_are_the_dense_loop(first, held, latent):
    p = layer(latent=latent)
    want = dense(p, first, held)
    got = program(p, first, held)
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max() + 1)


@pytest.mark.parametrize("first,held", [(0, E), (4, 6)],
                         ids=["every expert", "a share"])
def test_ungated_gradients_are_the_dense_loops(first, held):
    """The one pass under JAX's rule and the walk under its
    written-out backward: every operand's gradient, the latent
    projections' and the shared expert's among them."""
    p = layer()

    def objective(f):
        return jax.grad(lambda p: jnp.sum(jnp.sin(f(p, first, held))))(p)

    got, want = objective(program), objective(dense)
    cut = slice(first, first + held)
    for name in want:
        g, w = got[name], want[name]
        if name in ("w_up", "w_down"):  # the absent experts get none
            g, w = g[cut], w[cut]
        if name == "bias":  # a buffer: no gradient reaches it
            assert float(jnp.abs(g).max()) == 0.0
            continue
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=3e-4, err_msg=name)


def test_the_walk_in_several_chunks_is_the_one_pass(monkeypatch):
    """Two live chunks of a share's walk, forward and backward."""
    monkeypatch.setattr(moe, "CHUNK_ROWS", 64)
    monkeypatch.setattr(moe, "CHUNK_WIDTH", LATENT)
    assert moe.walk_chunks(48 * K, LATENT) == (64, 4)
    p = layer()

    def objective(f):
        return jax.value_and_grad(
            lambda p: jnp.sum(jnp.sin(f(p, 2, 10))))(p)

    (got, g), (want, w) = objective(program), objective(dense)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in ("x", "router", "down", "up"):
        np.testing.assert_allclose(
            g[name], w[name], rtol=2e-4, atol=3e-4, err_msg=name)
    np.testing.assert_allclose(
        g["w_up"][2:12], w["w_up"][2:12], rtol=2e-4, atol=3e-4)


def test_router_and_shared_expert_read_the_stream_not_the_latent():
    """Fed the wrong operand, each shows: the experts on the stream's
    first columns in the latent's place, the shared expert on the
    latent padded to the stream's width."""
    p = layer()
    right = program(p)
    wrong = dict(p)
    wrong.pop("down"), wrong.pop("up")
    wrong["w_up"] = jnp.pad(p["w_up"], ((0, 0), (0, H - LATENT), (0, 0)))
    wrong["w_down"] = jnp.pad(p["w_down"], ((0, 0), (0, 0), (0, H - LATENT)))
    assert float(jnp.abs(program(wrong) - right).max()) > 0.1
    assert float(jnp.abs(program(p) - dense(p)).max()) < 1e-4


def test_relu2_is_no_relu_and_no_gated_expert():
    p = layer(latent=False)
    right = program(p, shared=False)
    for act in ("relu", "silu"):
        other = moe.dropless_moe_mlp(
            p["x"], p["router"], None, p["w_up"], p["w_down"],
            **dict(ROUTING, act=act), bias=p["bias"])[0]
        assert float(jnp.abs(other - right).max()) > 0.05, act
    gated = moe.dropless_moe_mlp(
        p["x"], p["router"], p["w_up"], p["w_up"], p["w_down"],
        **ROUTING, bias=p["bias"])[0]
    assert float(jnp.abs(gated - right).max()) > 0.05
    assert set(moe.ACTIVATIONS) == {"silu", "relu", "relu2"}


def test_sixty_four_shares_add_up_to_the_whole_layer():
    """The deployment's number: 64 shares of one expert each, of a
    router 64 wide, top-22. The routed parts, with the shared expert's
    term (which every share computes alike, for its own tokens)
    counted once, add up to the layer that holds all 64: the way up is
    linear, so partial latent sums add past it as before it."""
    global E, K
    ks = jax.random.split(jax.random.key(5), 9)
    e, k = 64, 22
    p = layer(seed=5)
    p.update(
        router=jax.random.normal(ks[1], (H, e)) * H ** -0.5,
        bias=0.3 * jax.random.normal(ks[2], (e,)),
        w_up=jax.random.normal(ks[3], (e, LATENT, M)) * LATENT ** -0.5,
        w_down=jax.random.normal(ks[4], (e, M, LATENT)) * M ** -0.5,
    )

    def share(first, held, shared):
        kw = dict(ROUTING, k=k, first_held=first, bias=p["bias"],
                  latent=(p["down"], p["up"]))
        if shared:
            kw["shared"] = (None, p["ws_up"], p["ws_down"])
        return moe.dropless_moe_mlp(
            p["x"], p["router"], None, p["w_up"][first:first + held],
            p["w_down"][first:first + held], **kw)[0]

    whole = share(0, e, True)
    once = relu2(p["x"] @ p["ws_up"]) @ p["ws_down"]
    parts = [share(rank, 1, False) for rank in range(e)]
    assert float(jnp.abs(sum(parts) + once - whole).max()) < 1e-4
    assert float(jnp.abs(sum(parts)).max()) > 0.1
    # a token's 22 experts are on 22 of the 64 shares
    live = sum(float(jnp.abs(part[0, 0]).max()) > 0 for part in parts)
    assert live == k
    with_shared = share(3, 1, True) - share(3, 1, False)
    assert float(jnp.abs(with_shared - once).max()) < 1e-5
