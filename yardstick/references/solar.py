"""The ``solar`` family's plain forward loss: Solar-Open2, a stack of
pre-norm blocks whose operator is full attention in the layers
``gqa_layers`` names and the gated delta rule with a decay for every
key channel (Kimi Delta Attention, which the source's ``kda_*`` keys
name) in the others, as the builder knows both. Every norm is an
RMSNorm with a scale at ``rms_norm_eps``; nothing is rotated
(``use_rope`` false). Every layer ``l``, with ``y = RMSNorm(x;
attn_norm)``::

    x = x + operator_l(y)
    x = x + experts_l(RMSNorm(x; mlp_norm))

Full attention (``num_attention_heads`` query heads on
``num_key_value_heads`` of ``head_dim``), gated (``use_gqa_gate``)::

    q, k, v = y Wq, y Wk, y Wv                 # no bias, no position
    a = softmax(q k^T / sqrt(d)) v             # causal, every earlier key
    out = (sigmoid(y Wg) * a) Wo               # the gate elementwise

The delta-rule operator (``linear_attn_config``: ``num_heads`` heads
of ``head_dim`` keys and as many values), a head at a time::

    q_t = l2norm(silu(conv(y Wq))_t)           # conv: causal, depthwise,
    k_t = l2norm(silu(conv(y Wk))_t)           # short_conv_kernel_size
    v_t = silu(conv(y Wv))_t                   # taps a channel, no bias
    g_t = -exp(A_log_h) softplus(y_t F_a F_b + dt_bias)   # a channel each
    beta_t = 2 sigmoid(y_t . w_beta_h)         # kda_allow_neg_eigval
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(d)                  # S_0 = 0
    out = (RMSNorm(o_t; o_norm) * sigmoid(y_t G_a G_b + g_bias)) Wo

``l2norm(x) = x / sqrt(sum x^2 + 1e-6)`` over a head's columns;
``o_norm`` is one ``head_dim``-wide scale for all heads. The
recurrence is walked token by token with the heads' states ``[heads,
d, d]``: no chunk, no folded updates.

The experts (every layer: ``first_k_dense_replace`` 0), routed in
float32::

    s = sigmoid(h W_r)                  # over all the router's experts
    e_1..e_k = top-k of s + b           # b: expert_bias, a buffer
    w_j = s[e_j] / (sum_j s[e_j] + 1e-20)      # routed_scaling_factor 1
    out = sum_j w_j expert_{e_j}(h) + shared_expert(h)

every expert ``W_down (silu(W_gate h) * W_up h)``. The objective is
``L_CE + a L_LB``: a layer, ``L_LB = E sum_e f_e p_e`` (``f_e`` the
share of the ``N x k`` assignments that expert e received, held or
not, ``p_e`` the mean of its score normalised to sum to one over the
experts), summed over the layers; ``a`` is the configuration's
``assumed`` coefficient.

Attention walks the query rows in blocks against an explicit mask
over all keys; the convolution is an explicit sum over taps of shifted
copies; the routing is a dense mask over all of the router's experts
and a Python loop over the ones held here, each run on every token and
kept where the mask has it.

The share. This chip holds ``n_routed_experts`` experts of each layer
(``share.first_expert_held`` is the first) of the
``share.router_width`` the router ranks, the shared expert whole, and
a slice of the vocabulary. What the absent experts would have added
is left out, and that partial sum goes on to the next layer; logits
and cross entropy are over the slice.

The parameters are the program's tree: in ``period`` a stack
``[periods, ...]`` for each position of the scanned period, so that
layer ``l`` is position ``l % period`` of period ``l // period``.

Departures from the source as the builder knows it, each stated. The
source's config has no scoring function, no loss key and no width for
the low ranks: sigmoid scores with a selection bias held at zero, the
balance term and a rank of ``head_dim`` are ``assumed``, as are the
forms of the two gates."""

import functools

import jax
import jax.numpy as jnp

from yardstick.reference import (
    F32, HIGHEST, embed, final_rms, layer, mean_nll, rms_norm,
)

EXPERTS = ("w_gate", "w_up", "w_down")
#: query rows whose scores against every key are held at once
ROWS = 256


def attention(q, k, v, rows=ROWS):
    """q [b, s, heads, d]; k, v [b, s, kv_heads, d]; causal. Query
    head i reads kv head ``i // group``. ``rows`` query positions at
    a time."""
    b, s, heads, d = q.shape
    kv_heads = k.shape[2]
    rows = min(rows, s)
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows}")
    j = jnp.arange(s)

    def block(args):
        r0, qr = args  # qr [b, rows, kv_heads, group, d]
        keep = j[None, :] <= (r0 + jnp.arange(rows))[:, None]
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k)
        scores = jnp.where(keep, scores / jnp.sqrt(F32(d)), -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)

    blocks = q.reshape(b, s // rows, rows, kv_heads, heads // kv_heads, d)
    out = jax.lax.map(
        block, (jnp.arange(0, s, rows), jnp.moveaxis(blocks, 1, 0))
    )
    return jnp.moveaxis(out, 0, 1).reshape(b, s, heads * d)


def full_attention(y, p, heads, kv_heads):
    b, s, _ = y.shape
    q = (y @ p["wq"]).reshape(b, s, heads, -1)
    k = (y @ p["wk"]).reshape(b, s, kv_heads, -1)
    v = (y @ p["wv"]).reshape(b, s, kv_heads, -1)
    a = attention(q, k, v)
    a = jax.nn.sigmoid(y @ p["wg"]) * a
    return a @ p["wo"]


def conv_silu(x, w):
    """x [b, s, channels]; w [channels, taps], oldest tap first."""
    s, taps = x.shape[1], w.shape[1]
    c = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        earlier = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :s]
        c = c + w[:, j] * earlier
    return jax.nn.silu(c)


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def recurrence(q, k, v, g, beta):
    """The gated delta rule, a position at a time. q, k, v, g [b, s,
    heads, d]; beta [b, s, heads]. Returns ``o`` [b, s, heads, d]."""
    b, s, heads, d = q.shape

    def step(state, x):  # state [b, heads, keys, values]
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None] * state
        held = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", beta_t[..., None] * k_t, v_t - held)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    _, o = jax.lax.scan(
        step, jnp.zeros((b, heads, d, d), F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(o, 0, 1) / jnp.sqrt(F32(d))


def linear_attention(y, p, heads, eps):
    b, s, _ = y.shape

    def by_head(x):
        return x.reshape(b, s, heads, -1)

    q = l2norm(by_head(conv_silu(y @ p["wq"], p["conv_q"])))
    k = l2norm(by_head(conv_silu(y @ p["wk"], p["conv_k"])))
    v = by_head(conv_silu(y @ p["wv"], p["conv_v"]))
    rate = jnp.exp(p["A_log"])[:, None]
    g = -rate * by_head(
        jax.nn.softplus(y @ p["f_a"] @ p["f_b"] + p["dt_bias"]))
    beta = 2.0 * jax.nn.sigmoid(y @ p["w_beta"])
    o = recurrence(q, k, v, g, beta)
    o = rms_norm(o, p["o_norm"], eps).reshape(b, s, -1)
    o = o * jax.nn.sigmoid(y @ p["g_a"] @ p["g_b"] + p["g_bias"])
    return o @ p["wo"]


def _expert(blocks, name, i, e):
    """Held expert ``e`` of layer ``i`` of the stack, in float32: the
    only float32 copy of an expert's matrix that lives at a time."""
    one_layer = jax.lax.dynamic_index_in_dim(
        blocks[name], i, axis=0, keepdims=False
    )
    return one_layer[e].astype(F32)


def gated(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def experts(y, blocks, p, i, per_token, first_held, norm_topk, eps):
    """``(the held experts' part of the routed sum and the shared
    expert's term, L_LB)``."""
    b, s, _ = y.shape
    logits = y @ p["router"]  # [b, s, width]
    width = logits.shape[-1]
    score = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(score + p["expert_bias"], per_token)
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    if norm_topk:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps)
    hot = jax.nn.one_hot(chosen, width, dtype=F32)  # [b, s, k, width]
    weights = jnp.einsum("bsk,bske->bse", picked, hot)
    total = jnp.zeros_like(y)
    for e in range(blocks["w_gate"].shape[1]):  # the experts held here
        out = gated(y, *(_expert(blocks, name, i, e) for name in EXPERTS))
        total = total + weights[..., first_held + e, None] * out
    total = total + gated(y, p["ws_gate"], p["ws_up"], p["ws_down"])
    shares = score / jnp.sum(score, axis=-1, keepdims=True)
    load = jnp.sum(hot, axis=(0, 1, 2)) / (b * s * per_token)
    return total, width * jnp.sum(load * jnp.mean(shares, axis=(0, 1)))


@functools.partial(jax.jit, static_argnames=(
    "operator", "heads", "kv_heads", "linear_heads", "eps", "per_token",
    "first_held", "norm_topk", "topk_eps"))
def _block(x, blocks, i, *, operator, heads, kv_heads, linear_heads, eps,
           per_token, first_held, norm_topk, topk_eps):
    """``(x, L_LB)`` of layer ``i`` of the stack ``blocks``."""
    with HIGHEST():
        p = layer(
            {k: v for k, v in blocks.items() if k not in EXPERTS}, i
        )
        y = rms_norm(x, p["attn_norm"], eps)
        if operator == "full_attention":
            x = x + full_attention(y, p, heads, kv_heads)
        else:
            x = x + linear_attention(y, p, linear_heads, eps)
        y = rms_norm(x, p["mlp_norm"], eps)
        out, balance = experts(
            y, blocks, p, i, per_token, first_held, norm_topk, topk_eps
        )
        return x + out, balance


def loss(config, params, tokens, targets):
    if tokens.shape[1] > config["max_position_embeddings"]:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the "
            f"{config['max_position_embeddings']} positions the "
            "source declares"
        )
    assumed = config["assumed"]
    eps = float(config["rms_norm_eps"])
    period = len(params["period"])
    full = set(config["gqa_layers"])
    x = embed(params["embed"], tokens)
    balance = 0.0
    for l in range(config["num_hidden_layers"]):
        x, layer_balance = _block(
            x, params["period"][l % period], l // period,
            operator="full_attention" if l in full else "linear_attention",
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            linear_heads=config["linear_attn_config"]["num_heads"],
            eps=eps, per_token=config["num_experts_per_tok"],
            first_held=config["share"]["first_expert_held"],
            norm_topk=bool(config["norm_topk_prob"]),
            topk_eps=float(assumed["topk_norm_eps"]),
        )
        balance = balance + layer_balance
    x = final_rms(x, params["final_norm"], eps)
    return (
        mean_nll(x, params["lm_head"], targets)
        + assumed["router_aux_loss_coef"] * balance
    )
