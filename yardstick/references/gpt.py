"""The ``gpt`` family's plain forward loss, following the published
block of GPT-2 (``GPT2LMHeadModel``): learned positions,
pre-LayerNorm with bias, biased projections, ``gelu_new`` (the tanh
approximation), tied head. The program keeps q, k, v as three
matrices where the published block fuses them: same mathematics."""

import functools

import jax

from yardstick.reference import (
    F32, HIGHEST, causal_attention, final_ln, layer, layer_norm, mean_nll,
)


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _block(x, blocks, i, *, heads, eps):
    with HIGHEST():
        p = layer(blocks, i)
        b, s, _ = x.shape
        y = layer_norm(x, p["ln1_scale"], p["ln1_bias"], eps)
        q = (y @ p["wq"] + p["bq"]).reshape(b, s, heads, -1)
        k = (y @ p["wk"] + p["bk"]).reshape(b, s, heads, -1)
        v = (y @ p["wv"] + p["bv"]).reshape(b, s, heads, -1)
        x = x + causal_attention(q, k, v) @ p["wo"] + p["bo"]
        y = layer_norm(x, p["ln2_scale"], p["ln2_bias"], eps)
        hidden = jax.nn.gelu(
            y @ p["w_fc"] + p["b_fc"], approximate=True
        )
        return x + hidden @ p["w_proj"] + p["b_proj"]


@jax.jit
def _embed_with_positions(table, positions, tokens):
    s = tokens.shape[1]
    return (table[tokens].astype(F32)
            + positions[:s].astype(F32)[None])


def loss(config, params, tokens, targets):
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
        x = _block(
            x, params["blocks"], i, heads=config["n_head"], eps=eps
        )
    x = final_ln(
        x, params["final_ln_scale"], params["final_ln_bias"], eps
    )
    head = (params["embed"].T if config["tie_word_embeddings"]
            else params["lm_head"])
    return mean_nll(x, head, targets)
