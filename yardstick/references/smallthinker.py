"""The ``smallthinker`` family's plain forward loss, following the
published block of SmallThinker-21B-A3B (``SmallThinkerDecoderLayer``,
``SmallThinkerAttention``, ``SmallThinkerMoeBlock`` as the builder
knows them). For layer ``l`` with ``w = sliding_window_layout[l]`` and
``p = rope_layout[l]``::

    r = x Wr                      # router logits from the block's INPUT,
                                  # before any norm (a router placed
                                  # before attention)
    h = RMSNorm(x; input_layernorm)
    q, k, v = h Wq, h Wk, h Wv    # no bias, no q/k norm
    if p: q, k = RoPE(q), RoPE(k) # rotate_half; p = 0: no position signal
    a = softmax(q k^T / sqrt(d)) v    # query i sees key j iff j <= i and
                                      # (w == 0 or i - j < window)
    x = x + a Wo
    h = RMSNorm(x; post_attention_layernorm)
    l_1..l_k, e_1..e_k = top-k of r, over all the router's experts
    w_1..w_k = softmax(l_1..l_k)  # float32, over the chosen
    x = x + sum_j w_j (relu(h Wgate[e_j]) * (h Wup[e_j])) Wdown[e_j]

then the final RMSNorm and the head. Attention walks the query rows in
blocks against an explicit ``(j <= i) & (i - j < window)`` mask over
all keys, so the scores held at once are ``[heads, rows, seq]``. The
routing is a dense mask over all of the router's experts and a Python
loop over the ones held here, each run on every token and kept where
the mask has it: no sort, no grouped matmul, no capacity.

The share. This chip holds ``moe_num_primary_experts`` experts of each
layer, from ``share.first_expert_held`` on, of the
``share.router_width`` the router ranks, and a slice of the
vocabulary. What the absent experts would have added is left out, and
that partial sum goes on to the next layer; logits and cross entropy
are over the slice.

The objective is ``L_CE + a L_LB + b L_Z``: a layer, ``L_LB = E sum_e
f_e p_e`` over the router's full softmax (``f_e`` the share of the ``N
x k`` assignments that expert e received, held or not, ``p_e`` its
mean probability) and ``L_Z = mean(logsumexp(r)^2)``, both summed over
layers; ``a`` and ``b`` are the configuration's ``assumed``
coefficients (``b`` is 0).

Departures from the source as the builder knows it, each stated. The
source's config has no loss key and its modelling file no auxiliary
loss: both terms are ``assumed``. ``SmallThinkerMoeBlock`` takes the
softmax of the chosen logits when ``moe_primary_router_apply_softmax``
is true and ``norm_topk_prob`` then divides by a sum that is already
one; that is what is here. Its experts are ``down(relu(gate(h)) *
up(h))``. Its sliding-window layers take the window through the
attention mask of the implementation in use; here it is ``i - j <
sliding_window_size``, the query's own position counted in the
window, as Mistral's reference implementation has it."""

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


def _expert(blocks, name, i, e):
    """Held expert ``e`` of layer ``i``, in float32: the only float32
    copy of an expert's matrix that lives at a time."""
    one_layer = jax.lax.dynamic_index_in_dim(
        blocks[name], i, axis=0, keepdims=False
    )
    return one_layer[e].astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "per_token", "first_held",
    "window", "rope"))
def _block(x, blocks, i, *, heads, kv_heads, theta, eps, per_token,
           first_held, window, rope):
    """``(x, L_LB, L_Z)`` of layer ``i``."""
    with HIGHEST():
        p = layer(
            {k: v for k, v in blocks.items() if k not in EXPERTS}, i
        )
        b, s, _ = x.shape
        logits = x @ p["router"]  # [b, s, width], from the block's input
        y = rms_norm(x, p["attn_norm"], eps)
        q = (y @ p["wq"]).reshape(b, s, heads, -1)
        k = (y @ p["wk"]).reshape(b, s, kv_heads, -1)
        if rope:
            q, k = rotate(q, theta), rotate(k, theta)
        attn = banded_attention(
            q, k, (y @ p["wv"]).reshape(b, s, kv_heads, -1), window
        )
        x = x + attn @ p["wo"]
        y = rms_norm(x, p["mlp_norm"], eps)

        width = logits.shape[-1]
        top, chosen = jax.lax.top_k(logits, per_token)  # ties: lower index
        hot = jax.nn.one_hot(chosen, width, dtype=F32)  # [b, s, k, width]
        weights = jnp.einsum(
            "bsk,bske->bse", jax.nn.softmax(top, axis=-1), hot
        )
        total = jnp.zeros_like(x)
        for e in range(blocks["w_gate"].shape[1]):  # the experts held here
            out = (
                jax.nn.relu(y @ _expert(blocks, "w_gate", i, e))
                * (y @ _expert(blocks, "w_up", i, e))
            ) @ _expert(blocks, "w_down", i, e)
            total = total + weights[..., first_held + e, None] * out
        x = x + total

        probs = jax.nn.softmax(logits, axis=-1)
        share = jnp.sum(hot, axis=(0, 1, 2)) / (b * s * per_token)
        balance = width * jnp.sum(share * jnp.mean(probs, axis=(0, 1)))
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        return x, balance, z


def loss(config, params, tokens, targets):
    if tokens.shape[1] > config["max_position_embeddings"]:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the "
            f"{config['max_position_embeddings']} positions the "
            "source declares"
        )
    eps = float(config["rms_norm_eps"])
    x = embed(params["embed"], tokens)
    balance = z = 0.0
    for i in range(config["num_hidden_layers"]):
        x, layer_balance, layer_z = _block(
            x, params["blocks"], i,
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            theta=float(config["rope_theta"]), eps=eps,
            per_token=config["moe_num_active_primary_experts"],
            first_held=config["share"]["first_expert_held"],
            window=(config["sliding_window_size"]
                    if config["sliding_window_layout"][i] else None),
            rope=bool(config["rope_layout"][i]),
        )
        balance, z = balance + layer_balance, z + layer_z
    x = final_rms(x, params["final_norm"], eps)
    assumed = config["assumed"]
    return (
        mean_nll(x, params["lm_head"], targets)
        + assumed["router_aux_loss_coef"] * balance
        + assumed["router_z_loss_coef"] * z
    )
