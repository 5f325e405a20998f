"""The ``joyai`` family's plain forward loss: JoyAI-LLM-Flash, whose
block is the published DeepSeek-V3 one (``DeepseekV3Attention``,
``DeepseekV3MoE``, ``DeepseekV3TopkRouter`` as the builder knows
them, and the technical report's multi-token prediction) at this
source's sizes. Every norm is an RMSNorm with a scale at
``rms_norm_eps``. Every layer ``l``::

    x = x + attention_l(RMSNorm(x; attn_norm))
    x = x + ffn_l(RMSNorm(x; mlp_norm))

Latent attention, every layer (``y`` the normed stream)::

    c_q = RMSNorm(y W_qa; q_a_norm)             # q_lora_rank wide
    [q_nope | q_rope] = c_q W_qb                # a head: nope + rope
    [c_kv | k_rope] = y W_kva                   # kv_lora_rank + rope
    c_kv = RMSNorm(c_kv; kv_a_norm)
    [k_nope | v] = c_kv W_kvb                   # a head: nope + v_head_dim
    q_rope, k_rope = RoPE(q_rope), RoPE(k_rope) # pairs (2i, 2i + 1)
    scores = (q_nope . k_nope + q_rope . k_rope) * (nope + rope) ** -0.5
    out = softmax(scores) v W_o                 # causal, every earlier key

``k_rope`` is one head's and every head scores against it;
``rope_interleave`` is true, so the rotation turns neighbouring
columns (2i, 2i + 1) at ``theta ** (-2i / rope)``; there is no rope
scaling and so no mscale.

The first ``first_k_dense_replace`` layers' ``ffn`` is SwiGLU at
``intermediate_size``, ``W_down (silu(W_gate h) * W_up h)``; the
others' is experts of the same form at ``moe_intermediate_size``,
routed in float32 (``noaux_tc`` with ``n_group`` 1 and ``topk_group``
1: the group step chooses the one group there is, and is left out)::

    s = sigmoid(h W_r)                  # over all the router's experts
    e_1..e_k = top-k of s + b           # b: expert_bias, a buffer
    w_j = s[e_j] / (sum_j s[e_j] + 1e-20) * routed_scaling_factor
    out = sum_j w_j expert_{e_j}(h) + shared_expert(h)

Past the last layer, with ``h`` the stream before the final norm,
one multi-token-prediction module (``num_nextn_predict_layers`` 1)::

    h'_i = W_eh [RMSNorm(Emb(t_{i+1}); embed_norm) ; RMSNorm(h_i; hidden_norm)]
    h'' = block(h')                     # one expert layer of its own
    L_mtp = CE(head(RMSNorm(h''; the module's final_norm)), t_{i+2})

with the model's own embedding and head. The last position has no
``t_{i+1}``: it is handed the sequence's first token (a roll) and
its target masks it out, as the one before it, which has no
``t_{i+2}``. The objective is ``L_main + w L_mtp + a L_LB``: a layer,
``L_LB = E sum_e f_e p_e`` (``f_e`` the share of the ``N x k``
assignments that expert e received, held or not, ``p_e`` the mean of
its score normalised to sum to one over the experts), summed over the
expert layers, the module's among them; ``w`` and ``a`` are the
configuration's ``assumed``.

Attention walks the query rows in blocks against an explicit mask
over all keys; the routing is a dense mask over all of the router's
experts and a Python loop over the ones held here, each run on every
token and kept where the mask has it.

The share. This chip holds ``n_routed_experts`` experts of each layer
(``share.first_expert_held`` is the first) of the
``share.router_width`` the router ranks, the shared expert whole, and
a slice of the vocabulary. What the absent experts would have added
is left out, and that partial sum goes on to the next layer; logits
and cross entropy are over the slice.

The parameters are the program's tree: the leading layers one by one
in ``lead``, the expert layers as one stack ``[layers, ...]`` in
``period[0]``, the module in ``mtp[0]``.

Departures from the source as the builder knows it, each stated. The
source's config has no loss key: the balance term, the weight ``w``
and the order of the concatenation are ``assumed``; the bias, which
the source moves by a rule of its own, is held fixed."""

import functools

import jax
import jax.numpy as jnp

from yardstick.reference import (
    F32, HIGHEST, embed, final_rms, layer, mean_nll, rms_norm,
)

EXPERTS = ("w_gate", "w_up", "w_down")
#: query rows whose scores against every key are held at once
ROWS = 256


def rotate_pairs(x, theta):
    """Rotary embedding over neighbouring columns: (x[2i], x[2i + 1])
    turned by ``position * theta ** (-2i / d)``. x [b, s, n, d]."""
    s, d = x.shape[1], x.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    angles = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).reshape(x.shape)


def attention(q_nope, q_rope, k_nope, k_rope, v, rows=ROWS):
    """q_nope, k_nope [b, s, heads, nope]; q_rope [b, s, heads, rope];
    k_rope [b, s, 1, rope], which every head reads; v [b, s, heads,
    dv]; causal. ``rows`` query positions at a time."""
    b, s, heads, nope = q_nope.shape
    rope = q_rope.shape[3]
    rows = min(rows, s)
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows}")
    j = jnp.arange(s)
    one_key = k_rope[:, :, 0]

    def block(args):
        r0, qn, qr = args  # [b, rows, heads, .]
        keep = j[None, :] <= (r0 + jnp.arange(rows))[:, None]
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope)
            + jnp.einsum("bqhd,bkd->bhqk", qr, one_key)
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
        jnp.arange(0, s, rows), blocks_of(q_nope), blocks_of(q_rope)
    ))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, -1)


def latent_attention(y, p, heads, nope, rope, theta, eps):
    b, s, _ = y.shape
    c_q = rms_norm(y @ p["wq_a"], p["q_a_norm"], eps)
    q = (c_q @ p["wq_b"]).reshape(b, s, heads, nope + rope)
    rank = p["wkv_b"].shape[0]
    down = y @ p["wkv_a"]
    c_kv = rms_norm(down[..., :rank], p["kv_a_norm"], eps)
    k_rope = down[..., rank:][:, :, None, :]
    kv = (c_kv @ p["wkv_b"]).reshape(b, s, heads, -1)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope, k_rope = rotate_pairs(q_rope, theta), rotate_pairs(k_rope, theta)
    return attention(q_nope, q_rope, k_nope, k_rope, v) @ p["wo"]


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
    "dense", "heads", "nope", "rope", "theta", "eps", "per_token",
    "first_held", "norm_topk", "topk_eps", "scaling"))
def _block(x, blocks, i, *, dense, heads, nope, rope, theta, eps,
           per_token, first_held, norm_topk, topk_eps, scaling):
    """``(x, L_LB)`` of layer ``i`` of the stack ``blocks``."""
    with HIGHEST():
        matrices = EXPERTS if not dense else ()
        p = layer(
            {k: v for k, v in blocks.items() if k not in matrices}, i
        )
        y = rms_norm(x, p["attn_norm"], eps)
        x = x + latent_attention(y, p, heads, nope, rope, theta, eps)
        y = rms_norm(x, p["mlp_norm"], eps)
        if dense:
            return x + gated(y, p["w_gate"], p["w_up"], p["w_down"]), F32(0.0)
        out, balance = experts(
            y, blocks, p, i, per_token, first_held, norm_topk, topk_eps,
            scaling,
        )
        return x + out, balance


@jax.jit
def _merge(e, h, eh_proj):
    with HIGHEST():
        return jnp.concatenate([e, h], axis=-1) @ eh_proj.astype(F32)


def loss(config, params, tokens, targets):
    if tokens.shape[1] > config["max_position_embeddings"]:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the "
            f"{config['max_position_embeddings']} positions the "
            "source declares"
        )
    assumed = config["assumed"]
    eps = float(config["rms_norm_eps"])
    lead = config["first_k_dense_replace"]
    block = functools.partial(
        _block,
        heads=config["num_attention_heads"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        theta=float(config["rope_theta"]), eps=eps,
        per_token=config["num_experts_per_tok"],
        first_held=config["share"]["first_expert_held"],
        norm_topk=bool(config["norm_topk_prob"]),
        topk_eps=float(assumed["topk_norm_eps"]),
        scaling=float(config["routed_scaling_factor"]),
    )

    def stack_of_one(one_layer):
        return jax.tree.map(lambda a: a[None], one_layer)

    x = embed(params["embed"], tokens)
    balance = 0.0
    for l in range(config["num_hidden_layers"]):
        if l < lead:
            stack, i = stack_of_one(params["lead"][l]), 0
        else:
            stack, i = params["period"][0], l - lead
        x, layer_balance = block(x, stack, i, dense=l < lead)
        balance = balance + layer_balance
    head = params["lm_head"]
    main = mean_nll(final_rms(x, params["final_norm"], eps), head, targets)

    (module,) = params["mtp"]
    ahead = jnp.roll(tokens, -1, axis=1)  # t_{i+1}; the last is masked
    merged = _merge(
        final_rms(embed(params["embed"], ahead), module["embed_norm"], eps),
        final_rms(x, module["hidden_norm"], eps),
        module["eh_proj"],
    )
    y, layer_balance = block(
        merged, stack_of_one(module["block"]), 0, dense=False
    )
    balance = balance + layer_balance
    further = jnp.concatenate(
        [targets[:, 1:], jnp.full_like(targets[:, :1], -1)], axis=1
    )  # t_{i+2}
    mtp = mean_nll(final_rms(y, module["final_norm"], eps), head, further)
    return (
        main + assumed["mtp_loss_weight"] * mtp
        + assumed["router_aux_loss_coef"] * balance
    )
