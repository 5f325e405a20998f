"""The ``kimi`` family's plain forward loss: Kimi-Linear, a stack of
pre-norm blocks whose operator is Kimi Delta Attention (the gated
delta rule with a decay for every key channel) in the layers
``linear_attn_config.kda_layers`` names and latent attention without
positions in those ``full_attn_layers`` names (both lists count
layers as 1, 2, ...), as the builder knows both by the source's
config and the family's paper. Every norm is an RMSNorm with a scale at
``rms_norm_eps``; nothing is rotated (``mla_use_nope``). Every layer
``l``, with ``y = RMSNorm(x; attn_norm)``::

    x = x + operator_l(y)
    x = x + ffn_l(RMSNorm(x; mlp_norm))

Latent attention (``num_attention_heads`` heads; ``q_lora_rank``
null: q is one matrix's product, with no latent and no norm)::

    q = y W_q                                   # a head: nope + rope
    [c | k_r] = y W_kva                         # kv_lora_rank + rope
    c = RMSNorm(c; kv_a_norm)
    [k_nope | v] = c W_kvb                      # a head: nope + v_head_dim
    scores = (q_nope . k_nope + q_r . k_r) * (nope + rope) ** -0.5
    out = softmax(scores) v W_o                 # causal, every earlier key

``k_r`` is one head's and every head scores against it; no position
enters anywhere.

The delta-rule operator (``linear_attn_config``: ``num_heads`` heads
of ``head_dim`` keys and as many values), a head at a time::

    q_t = l2norm(silu(conv(y Wq))_t)           # conv: causal, depthwise,
    k_t = l2norm(silu(conv(y Wk))_t)           # short_conv_kernel_size
    v_t = silu(conv(y Wv))_t                   # taps a channel, no bias
    g_t = -exp(A_log_h) softplus(y_t F_a F_b + dt_bias)   # a channel each
    beta_t = sigmoid(y_t . w_beta_h)           # no factor: in (0, 1)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(d)                  # S_0 = 0
    out = (RMSNorm(o_t; o_norm) * sigmoid(y_t G_a G_b + g_bias)) Wo

``l2norm(x) = x / sqrt(sum x^2 + 1e-6)`` over a head's columns;
``o_norm`` is one ``head_dim``-wide scale for all heads. The
recurrence is walked token by token with the heads' states ``[heads,
d, d]``: no chunk, no folded updates.

The first ``first_k_dense_replace`` layers' ``ffn`` is the gated MLP
at ``intermediate_size``, ``W_down (silu(W_gate h) * W_up h)``; the
others' is experts of the same form at ``moe_intermediate_size``,
routed in float32 (``num_expert_group`` 1 and ``topk_group`` 1: the
group step chooses the one group there is, and is left out)::

    s = sigmoid(h W_r)                  # over all the router's experts
    e_1..e_k = top-k of s + b           # b: expert_bias, a buffer
    w_j = s[e_j] / (sum_j s[e_j] + 1e-20) * routed_scaling_factor
    out = sum_j w_j expert_{e_j}(h) + shared_expert(h)

The objective is ``L_CE + a L_LB``: a layer, ``L_LB = E sum_e f_e
p_e`` (``f_e`` the share of the ``N x k`` assignments that expert e
received, held or not, ``p_e`` the mean of its score normalised to
sum to one over the experts), summed over the expert layers; ``a`` is
the configuration's ``assumed`` coefficient.

Attention walks the query rows in blocks against an explicit mask
over all keys (16,384 positions of 32 heads are 537 MB of scores a
block of 256 rows); the convolution is an explicit sum over taps of
shifted copies; the routing is a dense mask over all of the router's
experts and a Python loop over the ones held here, each run on every
token and kept where the mask has it.

The share. This chip holds ``num_experts`` experts of each layer
(``share.first_expert_held`` is the first) of the
``share.router_width`` the router ranks, the shared expert whole, and
a slice of the vocabulary. What the absent experts would have added
is left out, and that partial sum goes on to the next layer; logits
and cross entropy are over the slice.

The parameters are the program's tree: the leading layers one by one
in ``lead``, and in ``period`` a stack ``[periods, ...]`` for each
position of the scanned period of the layers that follow, so that
layer ``l`` past the ``n`` leading ones is position ``(l - n) %
period`` of period ``(l - n) // period``.

Departures from the source as the builder knows it, each stated. The
source's config has no loss key and no width for the low ranks: the
balance term and a rank of ``head_dim`` are ``assumed``, as are the
forms of the two gates; the bias, which the source moves by a rule of
its own, is held fixed."""

import functools

import jax
import jax.numpy as jnp

from yardstick.reference import (
    F32, HIGHEST, embed, final_rms, layer, mean_nll, rms_norm,
)

EXPERTS = ("w_gate", "w_up", "w_down")
#: query rows whose scores against every key are held at once
ROWS = 256


def attention(q_nope, q_r, k_nope, k_r, v, rows=ROWS):
    """q_nope, k_nope [b, s, heads, nope]; q_r [b, s, heads, rope];
    k_r [b, s, rope], which every head reads; v [b, s, heads, dv];
    causal. ``rows`` query positions at a time."""
    b, s, heads, nope = q_nope.shape
    rope = q_r.shape[3]
    rows = min(rows, s)
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows}")
    j = jnp.arange(s)

    def block(args):
        r0, qn, qr = args  # [b, rows, heads, .]
        keep = j[None, :] <= (r0 + jnp.arange(rows))[:, None]
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope)
            + jnp.einsum("bqhd,bkd->bhqk", qr, k_r)
        )
        scores = jnp.where(
            keep, scores / jnp.sqrt(F32(nope + rope)), -jnp.inf
        )
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def blocks_of(q):
        return jnp.moveaxis(
            q.reshape(b, s // rows, rows, heads, -1), 1, 0
        )

    out = jax.lax.map(block, (
        jnp.arange(0, s, rows), blocks_of(q_nope), blocks_of(q_r)
    ))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, -1)


def latent_attention(y, p, heads, nope, eps):
    b, s, _ = y.shape
    q = (y @ p["wq"]).reshape(b, s, heads, -1)
    rank = p["wkv_b"].shape[0]
    down = y @ p["wkv_a"]
    c = rms_norm(down[..., :rank], p["kv_a_norm"], eps)
    k_r = down[..., rank:]
    kv = (c @ p["wkv_b"]).reshape(b, s, heads, -1)
    return attention(
        q[..., :nope], q[..., nope:], kv[..., :nope], k_r, kv[..., nope:]
    ) @ p["wo"]


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


def delta_attention(y, p, heads, eps):
    b, s, _ = y.shape

    def by_head(x):
        return x.reshape(b, s, heads, -1)

    q = l2norm(by_head(conv_silu(y @ p["wq"], p["conv_q"])))
    k = l2norm(by_head(conv_silu(y @ p["wk"], p["conv_k"])))
    v = by_head(conv_silu(y @ p["wv"], p["conv_v"]))
    rate = jnp.exp(p["A_log"])[:, None]
    g = -rate * by_head(
        jax.nn.softplus(y @ p["f_a"] @ p["f_b"] + p["dt_bias"]))
    beta = jax.nn.sigmoid(y @ p["w_beta"])
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


def experts(y, blocks, p, i, per_token, first_held, norm_topk, eps,
            scaling):
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
    picked = picked * scaling
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
    "operator", "dense", "heads", "nope", "linear_heads", "eps",
    "per_token", "first_held", "norm_topk", "topk_eps", "scaling"))
def _block(x, blocks, i, *, operator, dense, heads, nope, linear_heads,
           eps, per_token, first_held, norm_topk, topk_eps, scaling):
    """``(x, L_LB)`` of layer ``i`` of the stack ``blocks``."""
    with HIGHEST():
        matrices = EXPERTS if not dense else ()
        p = layer(
            {k: v for k, v in blocks.items() if k not in matrices}, i
        )
        y = rms_norm(x, p["attn_norm"], eps)
        if operator == "latent_attention":
            x = x + latent_attention(y, p, heads, nope, eps)
        else:
            x = x + delta_attention(y, p, linear_heads, eps)
        y = rms_norm(x, p["mlp_norm"], eps)
        if dense:
            return x + gated(y, p["w_gate"], p["w_up"], p["w_down"]), F32(0.0)
        out, balance = experts(
            y, blocks, p, i, per_token, first_held, norm_topk, topk_eps,
            scaling,
        )
        return x + out, balance


def operators(config):
    """The operator of each layer that is run, from the source's two
    lists, which count from 1."""
    full = set(config["linear_attn_config"]["full_attn_layers"])
    return tuple(
        "latent_attention" if l in full else "linear_attention"
        for l in range(1, config["num_hidden_layers"] + 1)
    )


def loss(config, params, tokens, targets):
    if tokens.shape[1] > config["model_max_length"]:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the "
            f"{config['model_max_length']} positions the source declares"
        )
    assumed = config["assumed"]
    eps = float(config["rms_norm_eps"])
    lead = config["first_k_dense_replace"]
    period = len(params["period"])
    block = functools.partial(
        _block,
        heads=config["num_attention_heads"],
        nope=config["qk_nope_head_dim"],
        linear_heads=config["linear_attn_config"]["num_heads"],
        eps=eps, per_token=config["num_experts_per_token"],
        first_held=config["share"]["first_expert_held"],
        norm_topk=bool(config["moe_renormalize"]),
        topk_eps=float(assumed["topk_norm_eps"]),
        scaling=float(config["routed_scaling_factor"]),
    )
    x = embed(params["embed"], tokens)
    balance = 0.0
    for l, operator in enumerate(operators(config)):
        if l < lead:
            stack = jax.tree.map(lambda a: a[None], params["lead"][l])
            i = 0
        else:
            stack = params["period"][(l - lead) % period]
            i = (l - lead) // period
        x, layer_balance = block(
            x, stack, i, operator=operator, dense=l < lead
        )
        balance = balance + layer_balance
    x = final_rms(x, params["final_norm"], eps)
    return (
        mean_nll(x, params["lm_head"], targets)
        + assumed["router_aux_loss_coef"] * balance
    )
