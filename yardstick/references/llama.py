"""The ``llama`` family's plain forward loss, following the
published block of Mistral-7B-v0.1 (``MistralForCausalLM``):
pre-RMSNorm, grouped-query attention with rotary embeddings in the
``rotate_half`` convention (first and second half of a head form the
pairs), SwiGLU, untied head. Sequences here never exceed the sliding
window, so the window mask equals the causal mask; a longer sequence
is refused and not silently attended in full."""

import functools

import jax

from yardstick.reference import (
    HIGHEST, causal_attention, embed, final_rms, layer, mean_nll,
    rms_norm, rotate,
)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads",
                                             "theta", "eps"))
def _block(x, blocks, i, *, heads, kv_heads, theta, eps):
    with HIGHEST():
        p = layer(blocks, i)
        b, s, _ = x.shape
        y = rms_norm(x, p["attn_norm"], eps)
        q = (y @ p["wq"]).reshape(b, s, heads, -1)
        k = (y @ p["wk"]).reshape(b, s, kv_heads, -1)
        v = (y @ p["wv"]).reshape(b, s, kv_heads, -1)
        attn = causal_attention(
            rotate(q, theta), rotate(k, theta), v
        )
        x = x + attn @ p["wo"]
        y = rms_norm(x, p["mlp_norm"], eps)
        return x + (
            jax.nn.silu(y @ p["w_gate"]) * (y @ p["w_up"])
        ) @ p["w_down"]


def loss(config, params, tokens, targets):
    window = config.get("sliding_window")
    if window and tokens.shape[1] > window:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the sliding "
            f"window {window}: the reference has no window mask"
        )
    x = embed(params["embed"], tokens)
    for i in range(config["num_hidden_layers"]):
        x = _block(
            x, params["blocks"], i,
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            theta=float(config["rope_theta"]),
            eps=float(config["rms_norm_eps"]),
        )
    x = final_rms(x, params["final_norm"],
                  float(config["rms_norm_eps"]))
    return mean_nll(x, params["lm_head"], targets)
