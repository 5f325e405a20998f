"""The ``olmoe`` family's plain forward loss, following the published
block of OLMoE-1B-7B (``OlmoeDecoderLayer``) and the objective of its
paper (arXiv:2409.02060): pre-RMSNorm, attention whose whole q and k
projections are RMS-normed before the split into heads and the rotary
embedding (``rotate_half`` convention), and in place of the MLP a
router over all experts, softmax in float32, the top
``num_experts_per_tok`` of its values as weights (renormalised only
where ``norm_topk_prob`` says so), each chosen expert a SwiGLU.

The routing is a dense mask over the experts: every expert is run on
every token, one expert's float32 copy at a time, and its result kept
where the mask has it. No sort, no grouped matmul, no capacity: no
token is dropped because none is ever moved.

The objective is ``L_CE + a L_LB + b L_Z`` with, a layer, ``L_LB = E
sum_e f_e p_e`` (``f_e`` the share of the ``N x k`` assignments that
expert e received, ``p_e`` its mean probability) and ``L_Z =
mean(logsumexp(router logits)^2)``, both summed over layers; ``a`` and
``b`` are the configuration's ``assumed`` coefficients.

Departures from ``OlmoeForCausalLM`` as the builder knows it, each
stated: its ``load_balancing_loss_func`` pools the layers' router
probabilities before the product (one ``f`` and ``p`` for the whole
model) and sums over the k ranks without dividing by k, so it reads k
times a pooled ``L_LB``; the paper's per-layer sum is what is here.
It has no z-loss; the paper trains with one. ``clip_qkv`` is null in
the source and absent here."""

import functools

import jax
import jax.numpy as jnp

from yardstick.reference import (
    F32, HIGHEST, causal_attention, embed, final_rms, layer, mean_nll,
    rms_norm, rotate,
)

EXPERTS = ("w_gate", "w_up", "w_down")


def _expert(blocks, name, i, e):
    """Expert ``e`` of layer ``i``, in float32: the only float32 copy
    of an expert's matrix that lives at a time."""
    one_layer = jax.lax.dynamic_index_in_dim(
        blocks[name], i, axis=0, keepdims=False
    )
    return jax.lax.dynamic_index_in_dim(
        one_layer, e, axis=0, keepdims=False
    ).astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "per_token", "norm_topk"))
def _block(x, blocks, i, *, heads, kv_heads, theta, eps, per_token,
           norm_topk):
    """``(x, L_LB, L_Z)`` of layer ``i``."""
    with HIGHEST():
        p = layer(
            {k: v for k, v in blocks.items() if k not in EXPERTS}, i
        )
        b, s, _ = x.shape
        y = rms_norm(x, p["attn_norm"], eps)
        q = rms_norm(y @ p["wq"], p["q_norm"], eps)
        k = rms_norm(y @ p["wk"], p["k_norm"], eps)
        attn = causal_attention(
            rotate(q.reshape(b, s, heads, -1), theta),
            rotate(k.reshape(b, s, kv_heads, -1), theta),
            (y @ p["wv"]).reshape(b, s, kv_heads, -1),
        )
        x = x + attn @ p["wo"]
        y = rms_norm(x, p["mlp_norm"], eps)

        logits = y @ p["router"]  # [b, s, experts]
        experts = logits.shape[-1]
        probs = jax.nn.softmax(logits, axis=-1)
        chosen = jax.lax.top_k(probs, per_token)[1]  # ties: lower index
        mask = jnp.sum(jax.nn.one_hot(chosen, experts, dtype=F32), axis=-2)
        weights = probs * mask
        if norm_topk:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

        def add_expert(e, total):
            out = (
                jax.nn.silu(y @ _expert(blocks, "w_gate", i, e))
                * (y @ _expert(blocks, "w_up", i, e))
            ) @ _expert(blocks, "w_down", i, e)
            mine = jax.lax.dynamic_index_in_dim(weights, e, axis=-1)
            return total + mine * out

        x = x + jax.lax.fori_loop(
            0, experts, add_expert, jnp.zeros_like(x)
        )
        share = jnp.sum(mask, axis=(0, 1)) / (b * s * per_token)
        balance = experts * jnp.sum(share * jnp.mean(probs, axis=(0, 1)))
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
            per_token=config["num_experts_per_tok"],
            norm_topk=bool(config["norm_topk_prob"]),
        )
        balance, z = balance + layer_balance, z + layer_z
    x = final_rms(x, params["final_norm"], eps)
    assumed = config["assumed"]
    return (
        mean_nll(x, params["lm_head"], targets)
        + assumed["router_aux_loss_coef"] * balance
        + assumed["router_z_loss_coef"] * z
    )
