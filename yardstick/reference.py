"""The plain reference: each family's forward loss as published, in
float32 ``jax.numpy`` at the highest matmul precision. No kernel, no
scan, no remat, no chunking, and no line of ``dlrover_tpu``.

It is given the program's own parameter tree (random from the seed)
and walks the layers in a Python loop, casting one layer's slice of
the stacked parameters to float32 at a time, so no float32 copy of
the whole model ever stands beside the training state. Under a mesh
it runs on the sharded parameters as they are: every function is a
plain ``jax.jit`` and the partitioner does the rest.

Published blocks followed:

- ``llama`` (Mistral-7B-v0.1, ``MistralForCausalLM``): pre-RMSNorm,
  grouped-query attention with rotary embeddings in the
  ``rotate_half`` convention (first and second half of a head form
  the pairs), SwiGLU, untied head. Sequences here never exceed the
  sliding window, so the window mask equals the causal mask; a longer
  sequence is refused and not silently attended in full.
- ``gpt`` (GPT-2, ``GPT2LMHeadModel``): learned positions,
  pre-LayerNorm with bias, biased projections, ``gelu_new`` (the tanh
  approximation), tied head. The program keeps q, k, v as three
  matrices where the published block fuses them: same mathematics.

The loss is the mean cross-entropy over positions whose target is
>= 0, as the program's ``next_token_loss`` defines it.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HIGHEST = functools.partial(
    jax.default_matmul_precision, "highest"
)


def _layer(blocks, i):
    """Layer ``i`` of the stacked parameters, in float32."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(
            x, i, axis=0, keepdims=False
        ).astype(F32),
        blocks,
    )


def _causal_attention(q, k, v):
    """q [b, s, heads, d]; k, v [b, s, kv_heads, d]. One kv head at a
    time, so the scores held at once are [b, group, s, s]."""
    b, s, heads, d = q.shape
    kv_heads = k.shape[2]
    group = heads // kv_heads
    keep = jnp.tril(jnp.ones((s, s), dtype=bool))
    outs = []
    for h in range(kv_heads):
        qh = q[:, :, h * group:(h + 1) * group]  # [b, s, g, d]
        scores = jnp.einsum("bqgd,bkd->bgqk", qh, k[:, :, h])
        scores = jnp.where(keep, scores / jnp.sqrt(F32(d)), -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("bgqk,bkd->bqgd", p, v[:, :, h]))
    return jnp.concatenate(outs, axis=2).reshape(b, s, heads * d)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * scale


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rotate(x, theta):
    """Rotary embedding, ``rotate_half`` convention. x [b, s, n, d]."""
    s, d = x.shape[1], x.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    angles = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads",
                                             "theta", "eps"))
def _llama_block(x, blocks, i, *, heads, kv_heads, theta, eps):
    with _HIGHEST():
        p = _layer(blocks, i)
        b, s, _ = x.shape
        y = _rms_norm(x, p["attn_norm"], eps)
        q = (y @ p["wq"]).reshape(b, s, heads, -1)
        k = (y @ p["wk"]).reshape(b, s, kv_heads, -1)
        v = (y @ p["wv"]).reshape(b, s, kv_heads, -1)
        attn = _causal_attention(
            _rotate(q, theta), _rotate(k, theta), v
        )
        x = x + attn @ p["wo"]
        y = _rms_norm(x, p["mlp_norm"], eps)
        return x + (
            jax.nn.silu(y @ p["w_gate"]) * (y @ p["w_up"])
        ) @ p["w_down"]


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _gpt_block(x, blocks, i, *, heads, eps):
    with _HIGHEST():
        p = _layer(blocks, i)
        b, s, _ = x.shape
        y = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], eps)
        q = (y @ p["wq"] + p["bq"]).reshape(b, s, heads, -1)
        k = (y @ p["wk"] + p["bk"]).reshape(b, s, heads, -1)
        v = (y @ p["wv"] + p["bv"]).reshape(b, s, heads, -1)
        x = x + _causal_attention(q, k, v) @ p["wo"] + p["bo"]
        y = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], eps)
        hidden = jax.nn.gelu(
            y @ p["w_fc"] + p["b_fc"], approximate=True
        )
        return x + hidden @ p["w_proj"] + p["b_proj"]


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@jax.jit
def _embed_with_positions(table, positions, tokens):
    s = tokens.shape[1]
    return (table[tokens].astype(F32)
            + positions[:s].astype(F32)[None])


@jax.jit
def _mean_nll(x, head, targets):
    """x [b, s, h] float32 (already normed); head [h, vocab]."""
    with _HIGHEST():
        logits = x @ head.astype(F32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    keep = targets >= 0
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[..., None], axis=-1
    )[..., 0]
    return jnp.sum(jnp.where(keep, nll, 0.0)) / jnp.maximum(
        jnp.sum(keep), 1
    )


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_rms(x, scale, eps):
    return _rms_norm(x, scale.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_ln(x, scale, bias, eps):
    return _layer_norm(x, scale.astype(F32), bias.astype(F32), eps)


def llama_loss(config, params, tokens, targets):
    window = config.get("sliding_window")
    if window and tokens.shape[1] > window:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the sliding "
            f"window {window}: the reference has no window mask"
        )
    x = _embed(params["embed"], tokens)
    for i in range(config["num_hidden_layers"]):
        x = _llama_block(
            x, params["blocks"], i,
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            theta=float(config["rope_theta"]),
            eps=float(config["rms_norm_eps"]),
        )
    x = _final_rms(x, params["final_norm"],
                   float(config["rms_norm_eps"]))
    return _mean_nll(x, params["lm_head"], targets)


def gpt_loss(config, params, tokens, targets):
    if tokens.shape[1] > config["n_positions"]:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than "
            f"{config['n_positions']} positions"
        )
    eps = float(config["layer_norm_epsilon"])
    x = _embed_with_positions(
        params["embed"], params["pos_embed"], tokens
    )
    for i in range(config["n_layer"]):
        x = _gpt_block(
            x, params["blocks"], i, heads=config["n_head"], eps=eps
        )
    x = _final_ln(
        x, params["final_ln_scale"], params["final_ln_bias"], eps
    )
    head = (params["embed"].T if config["tie_word_embeddings"]
            else params["lm_head"])
    return _mean_nll(x, head, targets)


LOSS = {"llama": llama_loss, "gpt": gpt_loss}


def loss(config, params, tokens, targets):
    """Mean next-token loss of ``config``'s family, float32."""
    return LOSS[config["family"]](config, params, tokens, targets)
