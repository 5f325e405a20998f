"""The ``trinity`` family's plain forward loss, following the
published block of Trinity-Mini (``AfmoeDecoderLayer``,
``AfmoeAttention``, ``AfmoeMoE`` and its token-choice router as the
builder knows them, with torchtitan's ``MoEArgs`` for the keys that
are its own). Every norm is an RMSNorm with a learned scale at
``rms_norm_eps``. The stream starts at ``x = Emb(t) * sqrt(hidden)``
(``mup_enabled``), and every layer ``l`` is::

    a = attention_l(RMSNorm(x; attn_norm))          # input_layernorm
    x = x + RMSNorm(a; post_attn_norm)              # post_attention_layernorm
    m = ffn_l(RMSNorm(x; mlp_norm))                 # pre_mlp_layernorm
    x = x + RMSNorm(m; post_mlp_norm)               # post_mlp_layernorm

then the final RMSNorm and the untied head. Attention (``y`` the
normed stream, ``layer_types[l]`` sliding or full)::

    q, k, v = y W_q, y W_k, y W_v       # heads of head_dim; no bias
    q, k = RMSNorm(q; q_norm), RMSNorm(k; k_norm)   # a head, one
                                        # head_dim-wide scale each
    sliding: q, k = RoPE(q), RoPE(k)    # rotate_half; full: no position
    a = softmax(q k^T * head_dim ** -0.5) v  # query i sees key j iff j <= i
                                        # and (full or i - j < sliding_window)
    out = (sigmoid(y W_g) * a) W_o      # the gate elementwise, no bias

The first ``num_dense_layers`` layers' ``ffn`` is ``W_down (silu(W_gate
h) * W_up h)`` at ``intermediate_size``; the others' is experts of the
same form at ``moe_intermediate_size``, routed in float32::

    s = sigmoid(h W_r)                  # over all the router's experts
    e_1..e_k = top-k of s + b           # b: expert_bias, a buffer
    w_j = s[e_j] / (sum_j s[e_j] + 1e-20) * route_scale   # route_norm
    m = sum_j w_j expert_{e_j}(h) + shared_expert(h)

There is no auxiliary loss: the objective is the cross entropy alone.
The balance is the bias's, which a rule moves ahead of every optimizer
step (``moved_bias``: auxiliary-loss-free balancing, arXiv:2408.15664,
at ``load_balance_coeff``) by the assignments each expert received in
the step (``assignment_counts``; ``expert_counts`` gives every expert
layer's for a batch).

Attention walks the query rows in blocks against an explicit mask over
all keys; the routing is a dense mask over all of the router's experts
and a Python loop over the ones held here, each run on every token and
kept where the mask has it.

The share. This chip holds ``num_experts`` experts of each layer
(``share.first_expert_held`` is the first) of the
``share.router_width`` the router ranks, the shared expert whole, and
a slice of the vocabulary. What the absent experts would have added
is left out, and that partial sum goes on to the next layer; logits
and cross entropy are over the slice; the counts are over all the
router's experts, held or not.

The parameters are the program's tree: the leading layers one by one
in ``lead``, then for each position of the scanned period a stack
``[periods, ...]`` in ``period``.

Departures from the source as the builder knows it, each stated and
each under the configuration's ``assumed``: config.json names the
gate, the four norms, the embedding's factor and the bias rule by a
switch or a rate only; their forms here are the ``afmoe`` modelling
file's and torchtitan's as the builder knows them."""

import functools

import jax
import jax.numpy as jnp

from yardstick.reference import (
    F32, HIGHEST, embed, final_rms, layer, mean_nll, rms_norm, rotate,
)

EXPERTS = ("w_gate", "w_up", "w_down")
#: query rows whose scores against every key are held at once
ROWS = 256


def banded_attention(q, k, v, window, rows=ROWS):
    """q [b, s, heads, d]; k, v [b, s, kv_heads, d]; ``window`` None
    for every earlier key. Query head i reads kv head ``i // group``.
    ``rows`` query positions at a time."""
    b, s, heads, d = q.shape
    kv_heads = k.shape[2]
    rows = min(rows, s)
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows}")
    j = jnp.arange(s)

    def block(args):
        r0, qr = args  # qr [b, rows, kv_heads, group, d]
        i = r0 + jnp.arange(rows)
        keep = j[None, :] <= i[:, None]
        if window is not None:
            keep &= i[:, None] - j[None, :] < window
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k)
        scores = jnp.where(keep, scores / jnp.sqrt(F32(d)), -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)

    blocks = q.reshape(b, s // rows, rows, kv_heads, heads // kv_heads, d)
    out = jax.lax.map(
        block, (jnp.arange(0, s, rows), jnp.moveaxis(blocks, 1, 0))
    )
    return jnp.moveaxis(out, 0, 1).reshape(b, s, heads * d)


def attention(y, p, heads, kv_heads, theta, eps, window, rope):
    b, s, _ = y.shape
    q = (y @ p["wq"]).reshape(b, s, heads, -1)
    k = (y @ p["wk"]).reshape(b, s, kv_heads, -1)
    v = (y @ p["wv"]).reshape(b, s, kv_heads, -1)
    q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    if rope:
        q, k = rotate(q, theta), rotate(k, theta)
    a = banded_attention(q, k, v, window)
    return (jax.nn.sigmoid(y @ p["wg"]) * a) @ p["wo"]


def _expert(blocks, name, i, e):
    """Held expert ``e`` of layer ``i`` of the stack, in float32: the
    only float32 copy of an expert's matrix that lives at a time."""
    one_layer = jax.lax.dynamic_index_in_dim(
        blocks[name], i, axis=0, keepdims=False
    )
    return one_layer[e].astype(F32)


def gated(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def assignment_counts(score, bias, per_token):
    """How many of the ``tokens x per_token`` assignments each of the
    router's experts receives, int32 [width]: a token's experts are
    the top ``per_token`` of its ``score`` [..., width] plus ``bias``
    [width] (ties: the lower index)."""
    _, chosen = jax.lax.top_k(score + bias, per_token)
    width = score.shape[-1]
    return jnp.sum(
        chosen.reshape(-1, 1) == jnp.arange(width), axis=0, dtype=jnp.int32
    )


def moved_bias(bias, counts, rate):
    """The rule: an expert that received fewer assignments than the
    mean is raised by ``rate``, one that received more lowered, and
    the step is taken less its own mean. ``bias`` float32 [width],
    ``counts`` [width]."""
    c = counts.astype(F32)
    delta = rate * jnp.sign(jnp.mean(c) - c)
    return bias + (delta - jnp.mean(delta))


def experts(y, blocks, p, i, per_token, first_held, norm_topk, eps,
            scaling):
    """``(the held experts' part of the routed sum and the shared
    expert's term, the assignments an expert)``."""
    score = jax.nn.sigmoid(y @ p["router"])  # [b, s, width]
    width = score.shape[-1]
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
    return total, assignment_counts(score, p["expert_bias"], per_token)


@functools.partial(jax.jit, static_argnames=(
    "dense", "heads", "kv_heads", "theta", "eps", "window", "rope",
    "per_token", "first_held", "norm_topk", "topk_eps", "scaling"))
def _block(x, blocks, i, *, dense, heads, kv_heads, theta, eps, window,
           rope, per_token, first_held, norm_topk, topk_eps, scaling):
    """``(x, counts)`` of layer ``i`` of the stack ``blocks``
    (``counts`` None for a dense layer)."""
    with HIGHEST():
        matrices = EXPERTS if not dense else ()
        p = layer(
            {k: v for k, v in blocks.items() if k not in matrices}, i
        )
        y = rms_norm(x, p["attn_norm"], eps)
        a = attention(y, p, heads, kv_heads, theta, eps, window, rope)
        x = x + rms_norm(a, p["post_attn_norm"], eps)
        y = rms_norm(x, p["mlp_norm"], eps)
        if dense:
            m, counts = gated(y, p["w_gate"], p["w_up"], p["w_down"]), None
        else:
            m, counts = experts(
                y, blocks, p, i, per_token, first_held, norm_topk,
                topk_eps, scaling,
            )
        return x + rms_norm(m, p["post_mlp_norm"], eps), counts


def _through_layers(config, params, tokens):
    """``(the stream past the last layer, [counts of each expert
    layer])``."""
    if tokens.shape[1] > config["max_position_embeddings"]:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the "
            f"{config['max_position_embeddings']} positions the "
            "source declares"
        )
    lead = config["num_dense_layers"]
    block = functools.partial(
        _block,
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        per_token=config["num_experts_per_tok"],
        first_held=config["share"]["first_expert_held"],
        norm_topk=bool(config["route_norm"]),
        topk_eps=float(config["assumed"]["topk_norm_eps"]),
        scaling=float(config["route_scale"]),
    )
    x = embed(params["embed"], tokens)
    if config["mup_enabled"]:
        x = x * jnp.sqrt(F32(config["hidden_size"]))
    positions = len(params["period"])
    counts = []
    for l, kind in enumerate(config["layer_types"]):
        if l < lead:
            stack, i = jax.tree.map(lambda a: a[None], params["lead"][l]), 0
        else:
            stack = params["period"][(l - lead) % positions]
            i = (l - lead) // positions
        sliding = kind == "sliding_attention"
        x, layer_counts = block(
            x, stack, i, dense=l < lead,
            window=config["sliding_window"] if sliding else None,
            rope=sliding,
        )
        if layer_counts is not None:
            counts.append(layer_counts)
    return x, counts


def loss(config, params, tokens, targets):
    x, _ = _through_layers(config, params, tokens)
    x = final_rms(x, params["final_norm"], float(config["rms_norm_eps"]))
    return mean_nll(x, params["lm_head"], targets)


def expert_counts(config, params, tokens):
    """The assignments each of the router's experts receives in each
    expert layer on ``tokens``, int32 [expert layers, width]."""
    return jnp.stack(_through_layers(config, params, tokens)[1])
