"""GPT-2/NeoX-style decoder family: LayerNorm + learned positions +
gelu MLP, on the same TPU-native substrate as models/llama.py.

Parity reference: the reference's non-Llama decoder zoo
(atorch/examples + model_zoo GPT-2 class workloads run through
auto_accelerate; dlrover/examples use HF GPT2 for elastic demos).

Same structural contract as llama.py so EVERY framework facility works
unchanged: scan-stacked blocks (pipeline-shardable "layers" dim),
``param_axes`` logical-axes tree (any sharding rule table applies —
ddp/zero/fsdp/tp/sequence/pipeline and planner-synthesized tables),
flash attention via ops.attention (GQA supported; attn_fn pluggable for
ring/Ulysses context parallelism), chunked cross-entropy, and the same
remat policies.

Differences from Llama, per the GPT-2/NeoX lineage:
  - learned absolute position embeddings (no RoPE)
  - pre-LayerNorm with bias (not RMSNorm)
  - fused-free gelu MLP (fc -> gelu -> proj), 4x hidden by default
  - attention and MLP projections carry biases
  - tied lm_head (embedding transpose) by default
"""

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.attention import flash_attention
from dlrover_tpu.models.llama import _mean_ce


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int = 0  # 0 = MHA (GPT-2); >0 enables GQA (NeoX-ish)
    max_seq_len: int = 1024
    norm_eps: float = 1e-5
    tie_lm_head: bool = True
    remat: str = "dots"  # off | dots | minimal
    loss_chunk: int = 0
    dtype: Any = jnp.bfloat16

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def gpt2_small(**kw) -> GPTConfig:
    return GPTConfig(**kw)


def gpt2_xl(**kw) -> GPTConfig:
    return GPTConfig(
        hidden_size=1600, intermediate_size=6400, num_layers=48,
        num_heads=25, **kw,
    )


def gpt_tiny(**kw) -> GPTConfig:
    kw.setdefault("remat", "off")
    return GPTConfig(
        vocab_size=256, hidden_size=64, intermediate_size=256,
        num_layers=2, num_heads=4, max_seq_len=64, **kw,
    )


# ---------------------------------------------------------------------------
# params

def init_params(rng: jax.Array, cfg: GPTConfig) -> Dict:
    h, m, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    k_embed, k_pos, k_blocks, k_out = jax.random.split(rng, 4)

    def dense_init(key, *shape, in_axis=0):
        fan_in = shape[in_axis]
        std = fan_in ** -0.5
        return (jax.random.normal(key, shape, dtype=jnp.float32) * std
                ).astype(cfg.dtype)

    def zeros(*shape):
        return jnp.zeros(shape, dtype=cfg.dtype)

    ks = jax.random.split(k_blocks, 6)
    params = {
        "embed": (
            jax.random.normal(
                k_embed, (cfg.vocab_size, h), dtype=jnp.float32
            ) * 0.02
        ).astype(cfg.dtype),
        "pos_embed": (
            jax.random.normal(
                k_pos, (cfg.max_seq_len, h), dtype=jnp.float32
            ) * 0.01
        ).astype(cfg.dtype),
        "blocks": {
            "ln1_scale": jnp.ones((L, h), jnp.float32),
            "ln1_bias": jnp.zeros((L, h), jnp.float32),
            "wq": dense_init(ks[0], L, h, nh * hd, in_axis=1),
            "wk": dense_init(ks[1], L, h, nkv * hd, in_axis=1),
            "wv": dense_init(ks[2], L, h, nkv * hd, in_axis=1),
            "bq": zeros(L, nh * hd),
            "bk": zeros(L, nkv * hd),
            "bv": zeros(L, nkv * hd),
            "wo": dense_init(ks[3], L, nh * hd, h, in_axis=1),
            "bo": zeros(L, h),
            "ln2_scale": jnp.ones((L, h), jnp.float32),
            "ln2_bias": jnp.zeros((L, h), jnp.float32),
            "w_fc": dense_init(ks[4], L, h, m, in_axis=1),
            "b_fc": zeros(L, m),
            "w_proj": dense_init(ks[5], L, m, h, in_axis=1),
            "b_proj": zeros(L, h),
        },
        "final_ln_scale": jnp.ones((h,), jnp.float32),
        "final_ln_bias": jnp.zeros((h,), jnp.float32),
    }
    if not cfg.tie_lm_head:
        params["lm_head"] = dense_init(
            k_out, h, cfg.vocab_size, in_axis=0
        )
    return params


def param_axes(cfg: GPTConfig) -> Dict:
    """Logical-axes tree (parallel/sharding.py conventions)."""
    axes = {
        "embed": ("vocab", "embed"),
        "pos_embed": (None, "embed"),
        "blocks": {
            "ln1_scale": ("layers", "norm"),
            "ln1_bias": ("layers", "norm"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "bq": ("layers", "heads"),
            "bk": ("layers", "kv_heads"),
            "bv": ("layers", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "bo": ("layers", "norm"),
            "ln2_scale": ("layers", "norm"),
            "ln2_bias": ("layers", "norm"),
            "w_fc": ("layers", "embed", "mlp"),
            "b_fc": ("layers", "mlp"),
            "w_proj": ("layers", "mlp", "embed"),
            "b_proj": ("layers", "norm"),
        },
        "final_ln_scale": ("norm",),
        "final_ln_bias": ("norm",),
    }
    if not cfg.tie_lm_head:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def param_count(cfg: GPTConfig) -> int:
    h, m, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    per_layer = (
        4 * h  # two LayerNorms (scale+bias)
        + h * nh * hd + nh * hd  # q
        + 2 * (h * nkv * hd + nkv * hd)  # k, v
        + nh * hd * h + h  # o
        + h * m + m + m * h + h  # mlp
    )
    n = cfg.vocab_size * h + cfg.max_seq_len * h + 2 * h + L * per_layer
    if not cfg.tie_lm_head:
        n += h * cfg.vocab_size
    return n


# ---------------------------------------------------------------------------
# forward

def layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (
        out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    ).astype(x.dtype)


def _block(cfg: GPTConfig, x, p, attn_fn):
    b, s, h = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim

    y = layer_norm(x, p["ln1_scale"], p["ln1_bias"], cfg.norm_eps)
    q = (y @ p["wq"] + p["bq"]).reshape(b, s, nh, hd)
    k = (y @ p["wk"] + p["bk"]).reshape(b, s, nkv, hd)
    v = (y @ p["wv"] + p["bv"]).reshape(b, s, nkv, hd)
    attn = attn_fn(q, k, v)
    x = x + attn.reshape(b, s, nh * hd) @ p["wo"] + p["bo"]

    y = layer_norm(x, p["ln2_scale"], p["ln2_bias"], cfg.norm_eps)
    x = x + jax.nn.gelu(y @ p["w_fc"] + p["b_fc"]) @ p["w_proj"] + (
        p["b_proj"]
    )
    return x


def _lm_head(params: Dict, cfg: GPTConfig) -> jax.Array:
    if cfg.tie_lm_head:
        return params["embed"].T
    return params["lm_head"]


def hidden_states(
    params: Dict, tokens: jax.Array, cfg: GPTConfig, attn_fn=None
) -> jax.Array:
    if attn_fn is None:
        attn_fn = partial(flash_attention, causal=True)
    s = tokens.shape[1]
    x = params["embed"][tokens] + params["pos_embed"][:s][None]

    def body(x, layer_params):
        return _block(cfg, x, layer_params, attn_fn), None

    if cfg.remat == "dots":
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    elif cfg.remat == "minimal":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return layer_norm(
        x, params["final_ln_scale"], params["final_ln_bias"],
        cfg.norm_eps,
    )


def forward(params: Dict, tokens: jax.Array, cfg: GPTConfig,
            attn_fn=None) -> jax.Array:
    x = hidden_states(params, tokens, cfg, attn_fn=attn_fn)
    return (x @ _lm_head(params, cfg)).astype(jnp.float32)


def next_token_loss(
    params: Dict, batch: Tuple[jax.Array, jax.Array], cfg: GPTConfig,
    attn_fn=None,
) -> jax.Array:
    tokens, targets = batch
    x = hidden_states(params, tokens, cfg, attn_fn=attn_fn)
    return _mean_ce(x, _lm_head(params, cfg), targets, cfg.loss_chunk)


def flops_per_token(cfg: GPTConfig, seq_len: int) -> float:
    n = param_count(cfg) - cfg.vocab_size * cfg.hidden_size
    attn = 12 * cfg.num_layers * cfg.hidden_size * seq_len
    return 6.0 * n + attn


def make_trainer(cfg: GPTConfig, mesh=None, strategy: str = "fsdp",
                 accum_steps: int = 1, optimizer=None, attn_fn=None):
    """ShardedTrainer over this family (mirrors
    trainer.sharded.make_trainer_for_llama)."""
    from dlrover_tpu.trainer.sharded import ShardedTrainer
    from dlrover_tpu.parallel.mesh import create_mesh

    if mesh is None:
        mesh = create_mesh([("data", 1), ("fsdp", -1)])
    return ShardedTrainer(
        lambda p, b: next_token_loss(p, b, cfg, attn_fn=attn_fn),
        lambda k: init_params(k, cfg),
        param_axes(cfg), mesh, strategy=strategy,
        optimizer=optimizer, accum_steps=accum_steps,
    )
