"""The ``sala`` family's plain forward loss: MiniCPM-SALA, a stack of
pre-norm blocks of two branches whose operator is, a layer as
``mixer_types`` names it, ``minicpm4`` (InfLLM-v2, arXiv:2509.24663,
as MiniCPM4 runs it, arXiv:2506.07900: grouped-query attention over
the key blocks each query selects) or ``lightning-attn`` (Lightning
Attention-2, arXiv:2401.04658: linear attention with a fixed decay a
head), as the builder knows both by the source's config, the family's
papers and its published ``sparse_config``. Every norm is an RMSNorm
with a scale at ``rms_norm_eps``. With ``f = scale_depth / sqrt(L)``,
``L`` the **published** depth (``assumed.scale_depth_layers``, 32),
every layer ``l``::

    x = x + f * operator_l(RMSNorm(x; attn_norm))
    x = x + f * W_down (silu(W_gate h) * W_up h),  h = RMSNorm(x; mlp_norm)

The stream starts as the embedding's rows times ``scale_emb``; the
logits are ``RMSNorm(x; final_norm) / (hidden_size / dim_model_base)``
through the untied head.

``minicpm4`` (``num_attention_heads`` query heads on
``num_key_value_heads`` kv heads of ``head_dim``; ``y`` the layer's
normed input)::

    q, k, v = y Wq, y Wk, y Wv
    q_h, k_g = RMSNorm(q_h; q_norm), RMSNorm(k_g; k_norm)   # qk_norm: a head's
    (no rotation: attn_use_rope false; rotated at rope_theta where true)
    out = (attend(q, k, v) * sigmoid(y Wg)) Wo              # attn_use_output_gate

``attend``, for query ``t`` and kv head ``g``, with the sizes of
``assumed.sparse_config`` (kernel 32, stride 16, block 64, top-k 64,
window 2048, one initial block) and ``scale = head_dim ** -0.5``::

    Kc_j = mean(k_g[16 j : 16 j + 32])          # visible where 16 j + 31 <= t
    p_h = softmax_j(q_h . Kc_j * scale)         # over the visible j; h of g
    s_j = sum_h p_h[j]
    score_b = max(s_j : Kc_j's keys overlap block b = keys 64 b .. 64 b + 63)
            = max(s_j, j in 4 b - 1 .. 4 b + 3)
    forced: block 0, and blocks t // 64 - 31 .. t // 64
    selected: the 64 blocks at or before t // 64 with the most score,
              the forced ones first (they count among the 64)
    o_h = softmax over the selected blocks' keys at or before t of
          (q_h . k * scale), times v

A sequence of ``dense_len`` positions or fewer attends to every
earlier key. The softmax's normaliser is exact. The selection is
made from whole score arrays, a block of ``ROWS`` query rows at a
time, against explicit masks: the compressed keys by a gather of
their 32 keys, the blocks' scores against an explicit overlap matrix,
the attention against the mask spread over all keys.

``lightning-attn`` (``lightning_nh`` heads of ``lightning_head_dim``;
``lightning_nkv`` the same)::

    q, k, v = y Wq, y Wk, y Wv
    q_h, k_h = RMSNorm(q_h; q_norm), RMSNorm(k_h; k_norm)   # qk_norm
    q_h, k_h rotated at rope_theta                          # lightning_use_rope
    S_t = exp(-m_h) S_{t-1} + k_t v_t^T,   S_0 = 0,   m_h = 2 ** (-8 (h + 1) / heads)
    o_t = S_t^T q_t / sqrt(d)                               # lightning_scale
    out = (RMSNorm(o_h; o_norm) * sigmoid(y Wg)) Wo         # use_output_norm, use_output_gate

The recurrence is walked a position at a time with the heads' states
``[heads, d, d]``: no chunk. The three norms' scales are one
``d``-wide vector each, for all heads.

The share. This chip holds four whole layers and a slice of the
vocabulary: logits and cross entropy are over the slice.

The parameters are the program's tree: in ``period`` a stack
``[periods, ...]`` for each position of the scanned period, so that
layer ``l`` is position ``l % period`` of period ``l // period``.

Departures from the source as the builder knows it, each stated in
the configuration's ``assumed``: the selection's sizes, the decay's
rates, the form of the three norms, the exact normaliser, the draws.
"""

import functools

import jax
import jax.numpy as jnp

from yardstick.reference import (
    F32, HIGHEST, embed, final_rms, layer, mean_nll, rms_norm, rotate,
)

#: query rows whose scores against every key are held at once
ROWS = 128


def compressed_keys(k, kernel, stride):
    """k [b, s, kv_heads, d] to [b, n, kv_heads, d]: key j the mean of
    keys ``stride j .. stride j + kernel - 1``, by a gather."""
    s = k.shape[1]
    n = (s - kernel) // stride + 1
    at = stride * jnp.arange(n)[:, None] + jnp.arange(kernel)[None, :]
    return jnp.mean(k[:, at], axis=2)


def visible(first, kernel, t):
    """Whether query ``t`` sees the compressed key whose keys start at
    ``first``: all of them at or before it."""
    return first + kernel - 1 <= t


def group_score(p):
    """p [b, kv_heads, group, rows, n]: a kv head's score of each
    compressed key, the sum over its heads."""
    return jnp.sum(p, axis=2)


def forced_blocks(at, own, local, init_blocks):
    """[rows, blocks]: the blocks every query takes."""
    return (at < init_blocks) | ((at > own - local) & (at <= own))


def rows_selection(t, q, kc, sizes):
    """The blocks that the queries at positions ``t`` [rows] select:
    bool [b, kv_heads, rows, blocks]. q [b, rows, kv_heads, group, d];
    kc the compressed keys; ``sizes`` the ``sparse_config`` and
    ``seq``."""
    d = q.shape[-1]
    kernel, stride, block = (
        sizes["kernel_size"], sizes["kernel_stride"], sizes["block_size"])
    blocks = sizes["seq"] // block
    first = stride * jnp.arange(kc.shape[1])
    seen = visible(first[None, :], kernel, t[:, None])  # [rows, n]
    logits = jnp.einsum("bqhgd,bjhd->bhgqj", q, kc) / jnp.sqrt(F32(d))
    p = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    p = jnp.where(seen, p, 0.0)  # and a row that sees none: nothing
    s = group_score(p)  # [b, kv_heads, rows, n]
    at = jnp.arange(blocks)
    # compressed key j's keys against block b's
    overlap = (
        (first[None, :] + kernel - 1 >= block * at[:, None])
        & (first[None, :] <= block * at[:, None] + block - 1)
    )  # [blocks, n]
    score = jnp.max(
        jnp.where(overlap, s[..., None, :], -jnp.inf), axis=-1
    )  # [b, kv_heads, rows, blocks]
    own = (t // block)[:, None]
    forced = forced_blocks(
        at[None, :], own, sizes["window_size"] // block,
        sizes["init_blocks"])
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(at[None, :] > own, -jnp.inf, score)
    _, chosen = jax.lax.top_k(score, min(sizes["topk"], blocks))
    picked = jnp.sum(jax.nn.one_hot(chosen, blocks, dtype=F32), axis=-2) > 0
    return picked & (at[None, :] <= own)


def _row_blocks(q, kv_heads, rows):
    """q [b, s, heads, d] as [s / rows, b, rows, kv_heads, group, d]."""
    b, s, heads, d = q.shape
    return jnp.moveaxis(
        q.reshape(b, s // rows, rows, kv_heads, heads // kv_heads, d), 1, 0)


def selection(q, k, sizes, rows=ROWS):
    """The whole selection, bool [b, kv_heads, s, blocks]: what the
    tests hold the program's against."""
    b, s, _, _ = q.shape
    rows = min(rows, s)
    sizes = dict(sizes, seq=s)
    kc = compressed_keys(k, sizes["kernel_size"], sizes["kernel_stride"])
    picked = jax.lax.map(
        lambda a: rows_selection(a[0] + jnp.arange(rows), a[1], kc, sizes),
        (jnp.arange(0, s, rows), _row_blocks(q, k.shape[2], rows)),
    )
    return jnp.moveaxis(picked, 0, 2).reshape(b, k.shape[2], s, -1)


def selected_attention(q, k, v, sizes, rows=ROWS):
    """q [b, s, heads, d]; k, v [b, s, kv_heads, d] to [b, s, heads x
    d]: ``rows`` query positions at a time, their selection and then
    their attention against the mask over all keys; dense and causal
    on a sequence within ``dense_len``."""
    b, s, heads, d = q.shape
    kv_heads = k.shape[2]
    rows = min(rows, s)
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows}")
    sizes = dict(sizes, seq=s)
    select = s > sizes["dense_len"]
    kc = compressed_keys(k, sizes["kernel_size"], sizes["kernel_stride"])
    j = jnp.arange(s)

    def block(args):
        r0, qr = args  # [b, rows, kv_heads, group, d]
        t = r0 + jnp.arange(rows)
        keep = j[None, :] <= t[:, None]  # [rows, s]
        if select:
            picked = rows_selection(t, qr, kc, sizes)
            keep = keep & jnp.repeat(picked, sizes["block_size"], axis=-1)
            keep = keep[:, :, None]  # over a kv head's heads
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k) / jnp.sqrt(F32(d))
        p = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)

    out = jax.lax.map(
        block, (jnp.arange(0, s, rows), _row_blocks(q, kv_heads, rows)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, heads * d)


def sparse_layer(y, p, config):
    """``config``: ``plan``'s flat dict, as ``lightning_layer``'s."""
    b, s, _ = y.shape
    heads, kv_heads = (
        config["num_attention_heads"], config["num_key_value_heads"])
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    q = (y @ p["wq"]).reshape(b, s, heads, -1)
    k = (y @ p["wk"]).reshape(b, s, kv_heads, -1)
    v = (y @ p["wv"]).reshape(b, s, kv_heads, -1)
    if config["qk_norm"]:
        q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    if config["attn_use_rope"]:
        q, k = rotate(q, theta), rotate(k, theta)
    o = selected_attention(q, k, v, config)
    if config["attn_use_output_gate"]:
        o = o * jax.nn.sigmoid(y @ p["wg"])
    return o @ p["wo"]


def decay(heads):
    """A lightning layer's rate a head: ``2 ** (-8 (h + 1) / heads)``."""
    return 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=F32) / heads)


def recurrence(q, k, v):
    """q, k, v [b, s, heads, d] to ``o`` [b, s, heads, d]: the state a
    position at a time."""
    b, s, heads, d = q.shape
    keep = jnp.exp(-decay(heads))[None, :, None, None]

    def step(state, x):  # state [b, heads, keys, values]
        q_t, k_t, v_t = x
        state = keep * state + jnp.einsum("bhk,bhv->bhkv", k_t, v_t)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    _, o = jax.lax.scan(
        step, jnp.zeros((b, heads, d, d), F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v)),
    )
    return jnp.moveaxis(o, 0, 1) / jnp.sqrt(F32(d))


def lightning_layer(y, p, config):
    b, s, _ = y.shape
    heads = config["lightning_nh"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    q, k, v = ((y @ p[w]).reshape(b, s, heads, -1)
               for w in ("wq", "wk", "wv"))
    if config["qk_norm"]:
        q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    if config["lightning_use_rope"]:
        q, k = rotate(q, theta), rotate(k, theta)
    o = recurrence(q, k, v)
    if config["use_output_norm"]:
        o = rms_norm(o, p["o_norm"], eps)
    o = o.reshape(b, s, -1)
    if config["use_output_gate"]:
        o = o * jax.nn.sigmoid(y @ p["wg"])
    return o @ p["wo"]


#: what a block reads of a configuration, beside ``sparse_config``
KEYS = ("num_attention_heads", "num_key_value_heads", "lightning_nh",
        "rms_norm_eps", "rope_theta", "qk_norm", "attn_use_rope",
        "lightning_use_rope", "attn_use_output_gate", "use_output_gate",
        "use_output_norm", "scale_depth")


def plan(config):
    """What a block reads of ``config``, flat and hashable (a jitted
    function's static argument): the keys above, the depth that
    ``scale_depth`` is divided by and the selection's sizes."""
    flat = {key: config[key] for key in KEYS}
    flat["rms_norm_eps"] = float(flat["rms_norm_eps"])
    flat["rope_theta"] = float(flat["rope_theta"])
    flat["scale_depth_layers"] = config["assumed"]["scale_depth_layers"]
    flat.update(config["assumed"]["sparse_config"])
    return tuple(sorted(flat.items()))


@functools.partial(jax.jit, static_argnames=("mixer", "plan"))
def _block(x, blocks, i, *, mixer, plan):
    """Layer ``i`` of the stack ``blocks``, of kind ``mixer``."""
    config = dict(plan)
    with HIGHEST():
        p = layer(blocks, i)
        eps = config["rms_norm_eps"]
        f = config["scale_depth"] / jnp.sqrt(
            F32(config["scale_depth_layers"]))
        y = rms_norm(x, p["attn_norm"], eps)
        operator = sparse_layer if mixer == "minicpm4" else lightning_layer
        x = x + f * operator(y, p, config)
        y = rms_norm(x, p["mlp_norm"], eps)
        return x + f * (
            (jax.nn.silu(y @ p["w_gate"]) * (y @ p["w_up"])) @ p["w_down"])


def loss(config, params, tokens, targets):
    if tokens.shape[1] > config["max_position_embeddings"]:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the "
            f"{config['max_position_embeddings']} positions the source "
            "declares"
        )
    static = plan(config)
    period = len(params["period"])
    x = embed(params["embed"], tokens) * F32(config["scale_emb"])
    for l, mixer in enumerate(config["mixer_types"]):
        x = _block(
            x, params["period"][l % period], l // period, mixer=mixer,
            plan=static,
        )
    x = final_rms(x, params["final_norm"], float(config["rms_norm_eps"]))
    x = x / F32(config["hidden_size"] / config["dim_model_base"])
    return mean_nll(x, params["lm_head"], targets)
