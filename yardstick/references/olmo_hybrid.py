"""The ``olmo_hybrid`` family's plain forward loss: Olmo-Hybrid, a
stack of dense blocks whose operator is, layer by layer as
``layer_types`` names it, the gated delta rule with ONE decay a head
(Gated DeltaNet, which the source's ``linear_*`` keys size:
``"linear_attention"``) or full attention (``"full_attention"``), as
the builder knows both. Every norm is an RMSNorm with a scale at
``rms_norm_eps``, and a block's norms stand on its branches' results
alone, as in the Olmo 2 and 3 block the model type descends from: no
norm ahead of either branch. Every layer ``l``::

    x = x + RMSNorm(operator_l(x); post_attention_layernorm)
    x = x + RMSNorm(MLP(x); post_feedforward_layernorm)
    MLP(x) = W_down (silu(W_gate x) * W_up x)         # no bias

then one RMSNorm past the last block and the untied head
(``tie_word_embeddings`` false).

Full attention (``num_attention_heads`` query heads on
``num_key_value_heads`` of ``hidden_size / num_attention_heads``, no
bias: ``attention_bias`` false)::

    q = RMSNorm(x Wq; q_norm)       # over the WHOLE projection, one
    k = RMSNorm(x Wk; k_norm)       # scale a column, then the heads
    v = x Wv
    a = softmax(q k^T / sqrt(d)) v  # causal, every earlier key
    out = a Wo

Nothing is rotated: ``rope_parameters.rope_theta`` is null, the file
gives no base to rotate with, and the convolutions and the recurrence
carry the order (``assumed.attention``).

The delta-rule operator, with ``h = linear_num_value_heads`` heads
(``linear_num_key_heads`` the same) of ``dk = linear_key_head_dim``
keys by ``dv = linear_value_head_dim`` values, a head at a time::

    q_t = l2norm(silu(conv(x Wq))_t)           # conv: causal, depthwise,
    k_t = l2norm(silu(conv(x Wk))_t)           # linear_conv_kernel_dim
    v_t = silu(conv(x Wv))_t                   # taps a channel, no bias
    g_t = -exp(A_log_h) softplus(x_t . w_a_h + dt_bias_h)   # a NUMBER a head
    beta_t = 2 sigmoid(x_t . w_beta_h)         # linear_allow_neg_eigval
    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(dk)                 # S [dk, dv], S_0 = 0
    out = (RMSNorm(o_t; o_norm) * silu(x_t Wg)) Wo

``l2norm(x) = x / sqrt(sum x^2 + 1e-6)`` over a head's ``dk``
columns; ``o_norm`` is one ``dv``-wide scale for all heads; the gate
is full rank, has no bias and stands PAST the norm. The recurrence is
walked token by token with the heads' states ``[heads, dk, dv]``: no
chunk, no folded updates, no floor under any decay.

Attention walks the query rows in blocks against an explicit mask
over all keys, so that 16,384 positions fit; the convolution is an
explicit sum over taps of shifted copies.

The share. This chip is one pipeline stage of eight and holds a slice
of the vocabulary (``share``): the layers that are run are the
source's first ``num_hidden_layers`` entries of ``layer_types``, and
logits and cross entropy are over the slice.

The parameters are the program's tree: in ``period`` a stack
``[periods, ...]`` for each position of the scanned period, so that
layer ``l`` is position ``l % period`` of period ``l // period``; the
program's ``post_attn_norm`` and ``post_mlp_norm`` are the source's
``post_attention_layernorm`` and ``post_feedforward_layernorm``.

Departures from the source as the builder knows it, each stated under
the configuration's ``assumed``. ``config.json`` has no key for where
a block's norms stand: both kinds of layer are written as the Olmo 2
and 3 block (``assumed.block``). It gives no rotary base
(``assumed.attention``), and no initialisation but a range
(``assumed.draws``)."""

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


def turned(x):
    """q or k [b, s, heads, d] as the attention layer takes it: as it
    is, no rotation (``rope_theta`` null)."""
    return x


def full_attention(x, p, heads, kv_heads, eps):
    b, s, _ = x.shape
    q = rms_norm(x @ p["wq"], p["q_norm"], eps).reshape(b, s, heads, -1)
    k = rms_norm(x @ p["wk"], p["k_norm"], eps).reshape(b, s, kv_heads, -1)
    v = (x @ p["wv"]).reshape(b, s, kv_heads, -1)
    return attention(turned(q), turned(k), v) @ p["wo"]


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
    """The gated delta rule with one decay a head, a position at a
    time. q, k [b, s, heads, dk]; v [b, s, heads, dv]; g, beta [b, s,
    heads]. Returns ``o`` [b, s, heads, dv]."""
    b, s, heads, dk = q.shape

    def step(state, x):  # state [b, heads, keys, values]
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        held = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", beta_t[..., None] * k_t, v_t - held)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    _, o = jax.lax.scan(
        step, jnp.zeros((b, heads, dk, v.shape[-1]), F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(o, 0, 1) / jnp.sqrt(F32(dk))


def linear_attention(x, p, heads, eps, neg_eigval):
    b, s, _ = x.shape

    def by_head(a):
        return a.reshape(b, s, heads, -1)

    q = l2norm(by_head(conv_silu(x @ p["wq"], p["conv_q"])))
    k = l2norm(by_head(conv_silu(x @ p["wk"], p["conv_k"])))
    v = by_head(conv_silu(x @ p["wv"], p["conv_v"]))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(x @ p["w_a"] + p["dt_bias"])
    beta = jax.nn.sigmoid(x @ p["w_beta"])
    if neg_eigval:
        beta = 2.0 * beta
    o = recurrence(q, k, v, g, beta)
    o = rms_norm(o, p["o_norm"], eps).reshape(b, s, -1)
    o = o * jax.nn.silu(x @ p["wg"])
    return o @ p["wo"]


@functools.partial(jax.jit, static_argnames=(
    "operator", "heads", "kv_heads", "linear_heads", "eps", "neg_eigval"))
def _block(x, blocks, i, *, operator, heads, kv_heads, linear_heads, eps,
           neg_eigval):
    """``x`` past layer ``i`` of the stack ``blocks``."""
    with HIGHEST():
        p = layer(blocks, i)
        if operator == "full_attention":
            out = full_attention(x, p, heads, kv_heads, eps)
        else:
            out = linear_attention(x, p, linear_heads, eps, neg_eigval)
        x = x + rms_norm(out, p["post_attn_norm"], eps)
        out = (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
        return x + rms_norm(out, p["post_mlp_norm"], eps)


def head_of(params):
    """The head's matrix [hidden, vocab]: its own (``lm_head``; the
    embedding is not tied)."""
    return params["lm_head"]


def loss(config, params, tokens, targets):
    if tokens.shape[1] > config["max_position_embeddings"]:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the "
            f"{config['max_position_embeddings']} positions the "
            "source declares"
        )
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError(
            f"linear_num_key_heads {config['linear_num_key_heads']} and "
            f"linear_num_value_heads {config['linear_num_value_heads']}: "
            "a key head for every value head is what is written down"
        )
    eps = float(config["rms_norm_eps"])
    period = len(params["period"])
    operators = config["layer_types"]
    x = embed(params["embed"], tokens)
    for l in range(config["num_hidden_layers"]):
        x = _block(
            x, params["period"][l % period], l // period,
            operator=operators[l],
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            linear_heads=config["linear_num_value_heads"],
            eps=eps, neg_eigval=bool(config["linear_allow_neg_eigval"]),
        )
    x = final_rms(x, params["final_norm"], eps)
    return mean_nll(x, head_of(params), targets)
