"""The ``jamba`` family's plain forward loss: AI21 Jamba2
(``model_type`` ``jamba``), a stack of blocks of two branches whose
first branch is a Mamba-1 mixer in most layers and attention in a few,
as the builder knows the family's modelling code. Every norm is an
RMSNorm with a learned scale at ``rms_norm_eps``; no projection has a
bias; nothing is rotated. Layer ``l``::

    x = x + mixer_l(RMSNorm(x; input_layernorm))
    x = x + W_down(silu(W_gate y) * W_up y),  y = RMSNorm(x; pre_ff_layernorm)

``mixer_l`` is attention where ``l % attn_layer_period ==
attn_layer_offset`` and a Mamba mixer elsewhere (``num_experts`` 1:
every feed-forward is the one dense MLP, and ``expert_layer_period``,
``expert_layer_offset`` and ``num_experts_per_tok`` are not read).
After the last block one RMSNorm, then the logits through the
embedding's own rows (``tie_word_embeddings``).

Attention (``num_attention_heads`` query heads on
``num_key_value_heads`` of ``hidden_size / num_attention_heads``)::

    q, k, v = y Wq, y Wk, y Wv                 # no bias, no position
    out = softmax(q k^T / sqrt(d)) v W_o       # causal, every earlier key

The Mamba mixer, with ``d = mamba_expand x hidden_size`` channels,
``n = mamba_d_state`` states a channel and ``r = mamba_dt_rank``::

    [x | z] = y W_in                           # hidden -> 2 d
    x = silu(conv(x) + b_conv)                 # causal, depthwise,
                                               # mamba_d_conv taps a channel
    [dt | B | C] = x W_x                       # d -> r + n + n
    dt, B, C = RMSNorm(dt), RMSNorm(B), RMSNorm(C)   # a scale each
    Delta = softplus(dt W_dt + b_dt)           # r -> d, a channel
    A = -exp(A_log)                            # [d, n]
    h_t[d, n] = exp(Delta_t[d] A[d, n]) h_{t-1}[d, n]
                + Delta_t[d] x_t[d] B_t[n]     # h_0 = 0
    o_t[d] = sum_n h_t[d, n] C_t[n] + D[d] x_t[d]
    out = (o * silu(z)) W_out                  # no norm past the gate

The recurrence is walked position by position with the channels'
states ``[d, n]``: no chunk, no kept states, no kernel. Attention
walks the query rows in blocks against an explicit mask over all keys;
the convolution is an explicit sum over taps of shifted copies.

The parameters are the program's tree: in ``period`` a stack
``[periods, ...]`` for each position of the scanned period, so that
layer ``l`` is position ``l % period`` of period ``l // period``.

Departures from the source as the builder knows it, each stated
(``assumed`` in the configuration's file has each one's origin).
``config.json`` names sizes only: which layers attend (inferred from
``attn_layer_period`` and ``attn_layer_offset`` as ``JambaConfig``
reads them), the order ``x | z`` and ``dt | B | C`` of the two
projections' columns, the three norms, attention without positions and
the draws are ``assumed``."""

import functools

import jax
import jax.numpy as jnp

from yardstick.reference import (
    F32, HIGHEST, embed, final_rms, layer, mean_nll, rms_norm,
)

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
    return attention(q, k, v) @ p["wo"]


def conv_silu(x, w, bias):
    """x [b, s, channels]; w [channels, taps], oldest tap first; bias
    [channels]."""
    s, taps = x.shape[1], w.shape[1]
    c = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        earlier = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :s]
        c = c + w[:, j] * earlier
    return jax.nn.silu(c + bias)


def recurrence(x, delta, B, C, A, D):
    """The selective recurrence, a position at a time. x, delta [b, s,
    d]; B, C [b, s, n]; A [d, n] (negative); D [d]. Returns ``o`` [b,
    s, d]."""
    b, s, d = x.shape

    def step(h, at):  # h [b, d, n]
        x_t, delta_t, b_t, c_t = at
        h = jnp.exp(delta_t[..., None] * A) * h + jnp.einsum(
            "bd,bd,bn->bdn", delta_t, x_t, b_t)
        return h, jnp.einsum("bdn,bn->bd", h, c_t) + D * x_t

    _, o = jax.lax.scan(
        step, jnp.zeros((b, d, A.shape[1]), F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, delta, B, C)),
    )
    return jnp.moveaxis(o, 0, 1)


def mamba(y, p, eps):
    d, n = p["A_log"].shape
    rank = p["mamba_dt"].shape[0]
    proj = y @ p["mamba_in"]
    x, z = proj[..., :d], proj[..., d:]
    x = conv_silu(x, p["mamba_conv_w"], p["mamba_conv_b"])
    low = x @ p["mamba_x"]
    dt = rms_norm(low[..., :rank], p["mamba_dt_norm"], eps)
    B = rms_norm(low[..., rank:rank + n], p["mamba_b_norm"], eps)
    C = rms_norm(low[..., rank + n:], p["mamba_c_norm"], eps)
    delta = jax.nn.softplus(dt @ p["mamba_dt"] + p["dt_bias"])
    o = recurrence(x, delta, B, C, -jnp.exp(p["A_log"]), p["D"])
    o = o * jax.nn.silu(z)
    return o @ p["mamba_out"]


def mlp(y, p):
    return (jax.nn.silu(y @ p["w_gate"]) * (y @ p["w_up"])) @ p["w_down"]


@functools.partial(jax.jit, static_argnames=(
    "attends", "heads", "kv_heads", "eps"))
def _block(x, blocks, i, *, attends, heads, kv_heads, eps):
    """Layer ``i`` of the stack ``blocks``: the mixer's branch, then
    the feed-forward's."""
    with HIGHEST():
        p = layer(blocks, i)
        y = rms_norm(x, p["attn_norm"], eps)
        if attends:
            x = x + full_attention(y, p, heads, kv_heads)
        else:
            x = x + mamba(y, p, eps)
        return x + mlp(rms_norm(x, p["mlp_norm"], eps), p)


def attends(config, l):
    """Whether layer ``l`` is an attention layer, as ``JambaConfig``
    reads its two keys."""
    return l % config["attn_layer_period"] == config["attn_layer_offset"]


def loss(config, params, tokens, targets):
    if tokens.shape[1] > config["max_position_embeddings"]:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the "
            f"{config['max_position_embeddings']} positions the "
            "source declares"
        )
    eps = float(config["rms_norm_eps"])
    block = functools.partial(
        _block, heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], eps=eps,
    )
    period = len(params["period"])
    x = embed(params["embed"], tokens)
    for l in range(config["num_hidden_layers"]):
        x = block(
            x, params["period"][l % period], l // period,
            attends=attends(config, l),
        )
    head = params["embed"].T  # tie_word_embeddings: the embedding's rows
    return mean_nll(final_rms(x, params["final_norm"], eps), head, targets)
