"""The plain reference: each family's forward loss as published, in
float32 ``jax.numpy`` at the highest matmul precision. No kernel, no
scan, no remat, no chunking, and no line of ``dlrover_tpu``.

It is given the program's own parameter tree (random from the seed)
and walks the layers in a Python loop, casting one layer's slice of
the stacked parameters to float32 at a time, so no float32 copy of
the whole model ever stands beside the training state. Under a mesh
it runs on the sharded parameters as they are: every function is a
plain ``jax.jit`` and the partitioner does the rest.

What two families share is here under public names (``layer``,
``causal_attention``, ``rms_norm``, ``layer_norm``, ``rotate``,
``embed``, ``mean_nll``, ``final_rms``, ``final_ln``); a family's
block and loop, and which published block they follow, are in
``references/<family>.py``, found by the configuration's ``family``.

The loss is the mean cross-entropy over positions whose target is
>= 0, as the program's ``next_token_loss`` defines it.
"""

import functools

import jax
import jax.numpy as jnp

from yardstick import cells

F32 = jnp.float32
HIGHEST = functools.partial(
    jax.default_matmul_precision, "highest"
)


def layer(blocks, i):
    """Layer ``i`` of the stacked parameters, in float32."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(
            x, i, axis=0, keepdims=False
        ).astype(F32),
        blocks,
    )


def causal_attention(q, k, v):
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


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * scale


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rotate(x, theta):
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


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


@jax.jit
def mean_nll(x, head, targets):
    """x [b, s, h] float32 (already normed); head [h, vocab]."""
    with HIGHEST():
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
def final_rms(x, scale, eps):
    return rms_norm(x, scale.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def final_ln(x, scale, bias, eps):
    return layer_norm(x, scale.astype(F32), bias.astype(F32), eps)


def loss(config, params, tokens, targets):
    """Mean next-token loss of ``config``'s family, float32."""
    return cells.family_module(config, "references").loss(
        config, params, tokens, targets
    )
