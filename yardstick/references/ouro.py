"""The ``ouro`` family's plain forward loss, following the published
block and loop of Ouro-2.6B (``OuroForCausalLM``, a looped language
model: ``OuroDecoderLayer``, ``OuroModel``'s loop over
``total_ut_steps`` and its ``early_exit_gate`` as the builder knows
them, and the first-stage objective of the family's paper). Every
norm is an RMSNorm with a learned scale at ``rms_norm_eps``. Layer
``l`` on the stream ``x``::

    a = x + RMSNorm(attention_l(RMSNorm(x; attn_norm)); post_attn_norm)
    y = a + RMSNorm(W_down (silu(W_gate u) * W_up u); post_mlp_norm)
        with u = RMSNorm(a; mlp_norm)

(the source's ``input_layernorm``, ``input_layernorm_2``,
``post_attention_layernorm``, ``post_attention_layernorm_2``).
Attention: ``q, k, v = u W_q, u W_k, u W_v`` in heads of ``head_dim``,
no bias; ``q, k = RoPE(q), RoPE(k)`` over the whole head at
``rope_theta`` (``rotate_half``: the first and second half of a head
form the pairs); ``softmax(q k^T * head_dim ** -0.5) v`` with query i
seeing key j iff j <= i; then ``W_o``. The stack ``F`` is the layers
one after the other, with ONE set of weights, and the loop walks it
``T = total_ut_steps`` times::

    h_0 = Emb(tokens)
    h_t = RMSNorm(F(h_{t-1}); final_norm)        t = 1..T

so the final norm is inside the loop and its result is what the next
pass reads; the positions are the same in every pass. A gate reads
each pass's state, a position::

    lambda_t = sigmoid(w_g . h_t + b_g)          one w_g, b_g for all t
    p_1 = lambda_1
    p_t = lambda_t prod_{j<t} (1 - lambda_j)     1 < t < T
    p_T = prod_{j<T} (1 - lambda_j)              (lambda_T is not read)

and the loss is, with ``nll_t(i)`` the cross entropy of ``h_t(i)
W_head`` on target i and ``H(p) = -sum_t p_t log p_t``::

    mean over the positions with a target of
        sum_t p_t(i) nll_t(i) - beta H(p(i))     beta = assumed.entropy_weight

Attention walks the query rows in blocks against an explicit mask over
all keys, so that 8,192 positions fit.

The parameters are the program's tree: ``blocks`` a stack ``[layers,
...]`` of like layers, ``exit_gate`` the gate's ``w`` [hidden] and
``b`` [1].

Departures from the source as the builder knows it, each stated and
each under the configuration's ``assumed``: config.json gives the
widths, ``total_ut_steps`` and ``early_exit_threshold`` only; where
the four norms stand, that the final norm is inside the loop, the
gate's form and the loss are the modelling file's and the paper's as
the builder knows them. ``early_exit_threshold`` is an inference-time
rule and is not read; the paper's later gate-only stage is not
built."""

import functools

import jax
import jax.numpy as jnp

from yardstick.reference import (
    F32, HIGHEST, embed, final_rms, layer, rms_norm, rotate,
)

#: query rows whose scores against every key are held at once
ROWS = 256


def causal_attention(q, k, v, rows=ROWS):
    """q, k, v [b, s, heads, d], as many kv heads as heads: ``rows``
    query positions at a time against every key."""
    b, s, heads, d = q.shape
    rows = min(rows, s)
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows}")
    j = jnp.arange(s)

    def block(args):
        r0, qr = args  # qr [b, rows, heads, d]
        keep = j[None, :] <= (r0 + jnp.arange(rows))[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qr, k)
        scores = jnp.where(keep, scores / jnp.sqrt(F32(d)), -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    blocks = q.reshape(b, s // rows, rows, heads, d)
    out = jax.lax.map(
        block, (jnp.arange(0, s, rows), jnp.moveaxis(blocks, 1, 0))
    )
    return jnp.moveaxis(out, 0, 1).reshape(b, s, heads * d)


@functools.partial(jax.jit, static_argnames=("heads", "theta", "eps"))
def _block(x, blocks, i, *, heads, theta, eps):
    with HIGHEST():
        p = layer(blocks, i)
        b, s, _ = x.shape
        u = rms_norm(x, p["attn_norm"], eps)
        q = (u @ p["wq"]).reshape(b, s, heads, -1)
        k = (u @ p["wk"]).reshape(b, s, heads, -1)
        v = (u @ p["wv"]).reshape(b, s, heads, -1)
        a = causal_attention(rotate(q, theta), rotate(k, theta), v)
        x = x + rms_norm(a @ p["wo"], p["post_attn_norm"], eps)
        u = rms_norm(x, p["mlp_norm"], eps)
        m = (jax.nn.silu(u @ p["w_gate"]) * (u @ p["w_up"])) @ p["w_down"]
        return x + rms_norm(m, p["post_mlp_norm"], eps)


@jax.jit
def position_nll(x, head, targets):
    """Cross entropy a position, [b, s]; x [b, s, h] float32 (already
    normed), head [h, vocab]. 0 where the target is < 0."""
    with HIGHEST():
        logits = x @ head.astype(F32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[..., None], axis=-1
    )[..., 0]
    return jnp.where(targets >= 0, nll, 0.0)


@jax.jit
def exit_logit(x, gate):
    """The gate's logit a position, [b, s]."""
    with HIGHEST():
        return x @ gate["w"].astype(F32) + gate["b"].astype(F32)


def exit_distribution(logits):
    """``[p_1, ..., p_T]`` from the gate's logits of the passes 1 to
    T - 1 (each [b, s]); the last pass takes what they left."""
    left, p = 1.0, []
    for logit in logits:
        lam = jax.nn.sigmoid(logit)
        p.append(lam * left)
        left = left * (1.0 - lam)
    return p + [left]


def states(config, params, tokens):
    """The normed state out of each pass, ``[h_1, ..., h_T]``."""
    if config["sliding_window"] is not None:
        raise ValueError(
            f"sliding_window {config['sliding_window']}: the family "
            "attends to every earlier key"
        )
    if tokens.shape[1] > config["max_position_embeddings"]:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the "
            f"{config['max_position_embeddings']} positions the "
            "source declares"
        )
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("the family's heads are not grouped")
    eps = float(config["rms_norm_eps"])
    x = embed(params["embed"], tokens)
    out = []
    for _ in range(config["total_ut_steps"]):
        for i in range(config["num_hidden_layers"]):
            x = _block(
                x, params["blocks"], i,
                heads=config["num_attention_heads"],
                theta=float(config["rope_theta"]), eps=eps,
            )
        x = final_rms(x, params["final_norm"], eps)
        out.append(x)
    return out


def loss(config, params, tokens, targets):
    hs = states(config, params, tokens)
    nll = [position_nll(h, params["lm_head"], targets) for h in hs]
    p = exit_distribution(
        [exit_logit(h, params["exit_gate"]) for h in hs[:-1]]
    )
    expected = sum(p_t * nll_t for p_t, nll_t in zip(p, nll))
    entropy = -sum(
        jnp.where(p_t > 0, p_t * jnp.log(jnp.maximum(p_t, 1e-38)), 0.0)
        for p_t in p
    )
    beta = float(config["assumed"]["entropy_weight"])
    keep = targets >= 0
    return jnp.sum(
        jnp.where(keep, expected - beta * entropy, 0.0)
    ) / jnp.maximum(jnp.sum(keep), 1)
