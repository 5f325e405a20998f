"""The ``lfm2`` family's plain forward loss, following the published
block of LFM2-8B-A1B (``Lfm2MoeDecoderLayer``, ``Lfm2MoeShortConv``,
``Lfm2MoeAttention``, ``Lfm2MoeSparseMoeBlock`` as the builder knows
them). Every layer ``l``::

    x = x + operator_l(RMSNorm(x; operator_norm))
    x = x + ffn_l(RMSNorm(x; ffn_norm))

``layer_types[l]`` says which operator. ``conv``, the gated short
convolution (``conv_L_cache`` taps, no bias, no activation)::

    B, C, u = split3(h W_in)
    v = B * u
    c[t] = sum_j w[:, j] v[t - (taps - 1) + j]     # v = 0 before t = 0
    out = (C * c) W_out

``full_attention``::

    q, k, v = h Wq, h Wk, h Wv    # no bias
    q, k = RMSNorm(q; q_layernorm), RMSNorm(k; k_layernorm)
                                  # over each head's own values, one
                                  # head_dim-wide scale each
    q, k = RoPE(q), RoPE(k)       # rotate_half
    out = softmax(q k^T / sqrt(d)) v Wo      # causal, every earlier key

The first ``num_dense_layers`` layers' ``ffn`` is SwiGLU at
``intermediate_size``, ``W2 (silu(W1 h) * W3 h)``; the others' is
experts of the same form at ``moe_intermediate_size``, routed in
float32::

    s = sigmoid(h Wr)                 # over all the router's experts
    e_1..e_k = top-k of s + b         # b: expert_bias, a buffer
    w_j = s[e_j] / (sum_j s[e_j] + 1e-6)   # routed_scaling_factor 1
    out = sum_j w_j expert_{e_j}(h)

then one more RMSNorm (the source's ``embedding_norm``) and logits
over the tied embedding, ``h E^T``. Attention walks the query rows in
blocks against an explicit mask over all keys; the convolution is an
explicit sum over taps of shifted copies; the routing is a dense mask
over all of the router's experts and a Python loop over the ones held
here, each run on every token and kept where the mask has it.

The share. This chip holds ``num_experts`` experts of each layer
(``share.first_expert_held`` is the first) of the
``share.router_width`` the router ranks, and a slice of the
vocabulary. What the absent experts
would have added is left out, and that partial sum goes on to the
next layer; logits and cross entropy are over the slice.

The objective is ``L_CE + a L_LB``: a layer, ``L_LB = E sum_e f_e
p_e`` (``f_e`` the share of the ``N x k`` assignments that expert e
received, held or not, ``p_e`` the mean of its score normalised to
sum to one over the experts), summed over the expert layers; ``a``
is the configuration's ``assumed`` coefficient.

The parameters are the program's tree: the leading layers one by one
in ``lead``, and in ``period`` a stack ``[periods, ...]`` for each
position of the scanned period, so that layer ``l`` past the leading
ones is position ``(l - lead) % period`` of period ``(l - lead) //
period``.

Departures from the source as the builder knows it, each stated. The
source's config has no loss key: the balance term is ``assumed``, and
the bias, which the source moves by a rule it does not publish, is
held fixed. ``tie_word_embeddings``, the heads' norms, the 1e-6 and
``silu`` are the modelling file's, not ``config.json``'s."""

import functools

import jax
import jax.numpy as jnp

from yardstick.reference import (
    F32, HIGHEST, embed, final_rms, layer, mean_nll, rms_norm, rotate,
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


def short_conv(y, p):
    """The gated short convolution of the normed stream ``y``."""
    s = y.shape[1]
    B, C, u = jnp.split(y @ p["conv_in"], 3, axis=-1)
    v = B * u
    taps = p["conv_w"].shape[1]
    c = jnp.zeros_like(v)
    for j in range(taps):
        back = taps - 1 - j
        earlier = jnp.pad(v, ((0, 0), (back, 0), (0, 0)))[:, :s]
        c = c + p["conv_w"][:, j] * earlier
    mixed = C * c
    return mixed @ p["conv_out"]


def full_attention(y, p, heads, kv_heads, theta, eps):
    b, s, _ = y.shape
    q = (y @ p["wq"]).reshape(b, s, heads, -1)
    k = (y @ p["wk"]).reshape(b, s, kv_heads, -1)
    q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    q, k = rotate(q, theta), rotate(k, theta)
    v = (y @ p["wv"]).reshape(b, s, kv_heads, -1)
    return attention(q, k, v) @ p["wo"]


def _expert(blocks, name, i, e):
    """Held expert ``e`` of layer ``i`` of the stack, in float32: the
    only float32 copy of an expert's matrix that lives at a time."""
    one_layer = jax.lax.dynamic_index_in_dim(
        blocks[name], i, axis=0, keepdims=False
    )
    return one_layer[e].astype(F32)


def experts(y, blocks, p, i, per_token, first_held, norm_topk):
    """``(the held experts' part of the routed sum, L_LB)``."""
    b, s, _ = y.shape
    logits = y @ p["router"]  # [b, s, width]
    width = logits.shape[-1]
    score = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(score + p["expert_bias"], per_token)
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    if norm_topk:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    hot = jax.nn.one_hot(chosen, width, dtype=F32)  # [b, s, k, width]
    weights = jnp.einsum("bsk,bske->bse", picked, hot)
    total = jnp.zeros_like(y)
    for e in range(blocks["w_gate"].shape[1]):  # the experts held here
        out = (
            jax.nn.silu(y @ _expert(blocks, "w_gate", i, e))
            * (y @ _expert(blocks, "w_up", i, e))
        ) @ _expert(blocks, "w_down", i, e)
        total = total + weights[..., first_held + e, None] * out
    shares = score / jnp.sum(score, axis=-1, keepdims=True)
    load = jnp.sum(hot, axis=(0, 1, 2)) / (b * s * per_token)
    return total, width * jnp.sum(load * jnp.mean(shares, axis=(0, 1)))


@functools.partial(jax.jit, static_argnames=(
    "operator", "dense", "heads", "kv_heads", "theta", "eps",
    "per_token", "first_held", "norm_topk"))
def _block(x, blocks, i, *, operator, dense, heads, kv_heads, theta,
           eps, per_token, first_held, norm_topk):
    """``(x, L_LB)`` of layer ``i`` of the stack ``blocks``."""
    with HIGHEST():
        matrices = EXPERTS if not dense else ()
        p = layer(
            {k: v for k, v in blocks.items() if k not in matrices}, i
        )
        y = rms_norm(x, p["attn_norm"], eps)
        if operator == "conv":
            x = x + short_conv(y, p)
        else:
            x = x + full_attention(y, p, heads, kv_heads, theta, eps)
        y = rms_norm(x, p["mlp_norm"], eps)
        if dense:
            out = (
                jax.nn.silu(y @ p["w_gate"]) * (y @ p["w_up"])
            ) @ p["w_down"]
            return x + out, F32(0.0)
        out, balance = experts(
            y, blocks, p, i, per_token, first_held, norm_topk
        )
        return x + out, balance


def loss(config, params, tokens, targets):
    if tokens.shape[1] > config["max_position_embeddings"]:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the "
            f"{config['max_position_embeddings']} positions the "
            "source declares"
        )
    eps = float(config["norm_eps"])
    lead, period = config["num_dense_layers"], len(params["period"])
    x = embed(params["embed"], tokens)
    balance = 0.0
    for l in range(config["num_hidden_layers"]):
        if l < lead:  # one layer, as a stack of one
            stack = jax.tree.map(lambda a: a[None], params["lead"][l])
            i = 0
        else:
            stack = params["period"][(l - lead) % period]
            i = (l - lead) // period
        x, layer_balance = _block(
            x, stack, i,
            operator=config["layer_types"][l], dense=l < lead,
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            theta=float(config["rope_theta"]), eps=eps,
            per_token=config["num_experts_per_tok"],
            first_held=config["share"]["first_expert_held"],
            norm_topk=bool(config["norm_topk_prob"]),
        )
        balance = balance + layer_balance
    x = final_rms(x, params["final_norm"], eps)
    head = params["embed"].T  # tied
    return (
        mean_nll(x, head, targets)
        + config["assumed"]["router_aux_loss_coef"] * balance
    )
