"""Llama-family decoder transformer, TPU-first.

Parity reference: the reference's flagship LLM paths — nanoGPT in
model_zoo/pytorch/nanogpt/model.py and the Megatron-style TP modules
(atorch/atorch/modules/distributed_modules/transformer.py) — re-designed
for XLA instead of translated:

 - pure-pytree params (dict of arrays) + a mirrored *logical axes* tree;
   every parallelism strategy is a rule table (parallel/sharding.py), not a
   module rewrite;
 - all decoder layers are SCAN-STACKED: one set of block weights with a
   leading "layers" dim, iterated by ``lax.scan`` — one compiled block
   regardless of depth, and the layers dim doubles as the pipeline-stage
   axis under the "pipeline" rule set;
 - ``jax.checkpoint`` with a dots-saveable policy = the reference's
   activation checkpointing (auto/opt_lib/checkpoint_optimization.py:14);
 - attention routes through ops.flash_attention (Pallas on TPU);
 - bf16 params/activations, fp32 RMSNorm accumulation and softmax.
"""

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.attention import flash_attention


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # activation checkpointing per block: "dots" saves matmul outputs;
    # "dots_attn_out" additionally keeps the attention call OUTSIDE the
    # checkpointed segments so its kernel residuals are saved and the
    # backward never re-runs the forward kernel (fastest, most memory —
    # the single-chip bench champion); "minimal" recomputes everything
    # (fits big models on small HBM); "off" disables remat
    remat: str = "dots"
    # chunked cross-entropy: compute logits + log-softmax over sequence
    # chunks of this many tokens inside a rematerialized scan, so the
    # [batch, seq, vocab] fp32 logits tensor is never materialized
    # (0 = off). Saves ~vocab/hidden x activation memory at the head.
    loss_chunk: int = 0
    # MoE (0 = dense): replaces every block's MLP with a top-k routed
    # expert SwiGLU (parallel/moe.py). With every expert on the device
    # the routing is dropless; over an ``expert`` mesh axis larger
    # than one it is bucketed by ``moe_capacity_factor`` and overflow
    # is dropped. A factor of 0 states dropless routing, which such a
    # mesh refuses instead of running it with drops.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # the source's own keys (``OlmoeConfig``): whether the k routing
    # weights are renormalised to sum to one; the load-balance loss's
    # coefficient; and, not a key of that config but of its paper, the
    # router z-loss's. Both losses are summed over layers.
    norm_topk_prob: bool = True
    router_aux_loss_coef: float = 0.01
    router_z_loss_coef: float = 0.001
    # RMSNorm of the whole q and k projections, before the split into
    # heads and the rotary embedding (``OlmoeAttention``'s q_norm,
    # k_norm)
    qk_norm: bool = False
    # the width of one head, where the source states it apart from
    # ``hidden_size // num_heads`` (which None gives)
    head_dim: Optional[int] = None
    # a layer pattern, in the source's keys (``SmallThinkerConfig``):
    # layer l attends within ``sliding_window_size`` keys where
    # ``sliding_window_layout[l]`` is 1 and to every earlier key where
    # it is 0; it rotates q and k where ``rope_layout[l]`` is 1 and
    # gives them no position at all where it is 0. One entry a layer;
    # None is full attention, and the rotary embedding, in every
    # layer. The layers are scanned a period at a time, the period the
    # shortest repeat of the two layouts.
    sliding_window_size: Optional[int] = None
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    # what the router reads: "post_attn_norm", the normed residual
    # stream the experts read, or "block_input", the block's input
    # before any norm (a router placed before attention)
    moe_router_input: str = "post_attn_norm"
    # the gate's activation in an expert: "silu" or "relu"
    moe_expert_act: str = "silu"
    # the experts this device holds of each layer's ``num_experts``:
    # ``moe_experts_held`` of them from ``moe_first_expert_held`` on
    # (0 held: all). The router keeps its width and top-k is over all;
    # the layer gives the held experts' part of the sum
    # (parallel/moe.py). Dropless on one device only.
    moe_first_expert_held: int = 0
    moe_experts_held: int = 0
    # the standard deviation ``init_params`` draws the embedding at,
    # for a run from scratch. Whoever pre-trains a model whose router
    # reads the block's input sets it near the layers' own output: at
    # 0.02 the un-normed stream is, from the third layer on, the
    # component every token of a sequence shares (uniform attention
    # hands each token the mean of the values), the router ranks by
    # it, and every token starts on the same k experts (PERF.md
    # section 6, PR 34).
    embed_init_std: float = 0.02

    def __post_init__(self):
        if self.remat not in ("off", "dots", "dots_attn_out",
                              "minimal"):
            # unknown strings would silently fall through the remat
            # if/elif chains as "off" — an unexplained OOM, not an error
            raise ValueError(f"unknown remat policy {self.remat!r}")
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.hidden_size // self.num_heads
            )
        for name in ("sliding_window_layout", "rope_layout"):
            layout = getattr(self, name)
            if layout is None:
                continue
            object.__setattr__(self, name, tuple(int(x) for x in layout))
            if len(layout) != self.num_layers:
                raise ValueError(
                    f"{name} has {len(layout)} entries for "
                    f"{self.num_layers} layers"
                )
        if self.sliding_window_layout is not None and (
                any(self.sliding_window_layout)
                and not self.sliding_window_size):
            raise ValueError(
                "sliding_window_layout asks for a window and "
                "sliding_window_size gives none"
            )
        if self.moe_router_input not in ("post_attn_norm", "block_input"):
            raise ValueError(
                f"unknown moe_router_input {self.moe_router_input!r}"
            )
        if self.moe_expert_act not in ("silu", "relu"):
            raise ValueError(
                f"unknown moe_expert_act {self.moe_expert_act!r}"
            )
        if self.num_experts > 0:
            if not self.moe_experts_held:
                object.__setattr__(
                    self, "moe_experts_held", self.num_experts
                )
            if not (0 <= self.moe_first_expert_held
                    <= self.num_experts - self.moe_experts_held):
                raise ValueError(
                    f"experts {self.moe_first_expert_held}.."
                    f"{self.moe_first_expert_held + self.moe_experts_held}"
                    f" of {self.num_experts}"
                )

    def layer_kinds(self) -> Tuple[Tuple[Optional[int], bool], ...]:
        """``(window, rope)`` of each layer of one period: the window
        (None: every earlier key) and whether q and k are rotated. The
        period is the shortest repeat of the two layouts; one layer of
        kind ``(None, True)`` where there is no layout."""
        n = self.num_layers
        windows = [
            self.sliding_window_size if on else None
            for on in self.sliding_window_layout or (0,) * n
        ]
        ropes = [bool(on) for on in self.rope_layout or (1,) * n]
        kinds = tuple(zip(windows, ropes))
        period = next(
            p for p in range(1, n + 1)
            if n % p == 0 and kinds == kinds[:p] * (n // p)
        )
        return kinds[:period]


def llama2_7b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama2_13b(**kw) -> LlamaConfig:
    return LlamaConfig(
        hidden_size=5120, intermediate_size=13824, num_layers=40,
        num_heads=40, num_kv_heads=40, **kw,
    )


def llama2_70b(**kw) -> LlamaConfig:
    """Llama-2-70B shape (GQA 64q/8kv) — BASELINE.json config #5's
    elastic v5p-64 target."""
    return LlamaConfig(
        hidden_size=8192, intermediate_size=28672, num_layers=80,
        num_heads=64, num_kv_heads=8, **kw,
    )


def llama_1b(**kw) -> LlamaConfig:
    """A ~1.1B config (TinyLlama shape) for single-chip benchmarking."""
    return LlamaConfig(
        hidden_size=2048, intermediate_size=5632, num_layers=22,
        num_heads=32, num_kv_heads=4, **kw,
    )


def llama_moe_tiny(**kw) -> LlamaConfig:
    """Test-sized MoE config (4 experts, top-2)."""
    kw.setdefault("num_experts", 4)
    return llama_tiny(**kw)


def llama_tiny(**kw) -> LlamaConfig:
    """Test-sized config that still exercises GQA + scan + remat."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("max_seq_len", 128)
    return LlamaConfig(**kw)


# ---------------------------------------------------------------------------
# params

def init_params(rng: jax.Array, cfg: LlamaConfig) -> Dict:
    """Initialize the parameter pytree. Block weights carry a leading
    layers dim (scan stacking)."""
    h, m, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k_embed, k_blocks, k_out = jax.random.split(rng, 3)

    def norm_init(*shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def dense_init(key, *shape, in_axis=0):
        fan_in = shape[in_axis]
        std = fan_in ** -0.5
        return (jax.random.normal(key, shape, dtype=jnp.float32) * std
                ).astype(cfg.dtype)

    ks = jax.random.split(k_blocks, 8)
    block = {
        "attn_norm": norm_init(L, h),
        "wq": dense_init(ks[0], L, h, nh * hd, in_axis=1),
        "wk": dense_init(ks[1], L, h, nkv * hd, in_axis=1),
        "wv": dense_init(ks[2], L, h, nkv * hd, in_axis=1),
        "wo": dense_init(ks[3], L, nh * hd, h, in_axis=1),
        "mlp_norm": norm_init(L, h),
    }
    if cfg.qk_norm:
        block["q_norm"] = norm_init(L, nh * hd)
        block["k_norm"] = norm_init(L, nkv * hd)
    if cfg.num_experts > 0:
        E, held = cfg.num_experts, cfg.moe_experts_held
        block.update({
            "router": dense_init(ks[7], L, h, E, in_axis=1),
            "w_gate": dense_init(ks[4], L, held, h, m, in_axis=2),
            "w_up": dense_init(ks[5], L, held, h, m, in_axis=2),
            "w_down": dense_init(ks[6], L, held, m, h, in_axis=2),
        })
    else:
        block.update({
            "w_gate": dense_init(ks[4], L, h, m, in_axis=1),
            "w_up": dense_init(ks[5], L, h, m, in_axis=1),
            "w_down": dense_init(ks[6], L, m, h, in_axis=1),
        })
    return {
        "embed": (
            jax.random.normal(
                k_embed, (cfg.vocab_size, h), dtype=jnp.float32
            ) * cfg.embed_init_std
        ).astype(cfg.dtype),
        "blocks": block,
        "final_norm": norm_init(h),
        "lm_head": dense_init(k_out, h, cfg.vocab_size, in_axis=0),
    }


def param_axes(cfg: LlamaConfig) -> Dict:
    """Logical-axes tree mirroring init_params (see parallel/sharding.py)."""
    blocks = {
        "attn_norm": ("layers", "norm"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "norm"),
    }
    if cfg.qk_norm:
        blocks["q_norm"] = ("layers", "norm")
        blocks["k_norm"] = ("layers", "norm")
    if cfg.num_experts > 0:
        blocks.update({
            "router": ("layers", "embed", None),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        })
    else:
        blocks.update({
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        })
    return {
        "embed": ("vocab", "embed"),
        "blocks": blocks,
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }


def param_count(cfg: LlamaConfig) -> int:
    L, h, m = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if cfg.num_experts > 0:
        mlp = h * cfg.num_experts + 3 * h * m * cfg.moe_experts_held
    else:
        mlp = 3 * h * m
    per_layer = (
        2 * h  # norms
        + (nh * hd + nkv * hd if cfg.qk_norm else 0)
        + h * nh * hd + 2 * h * nkv * hd + nh * hd * h  # attention
        + mlp
    )
    return cfg.vocab_size * h * 2 + h + L * per_layer


# ---------------------------------------------------------------------------
# forward

def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def rope_tables(
    seq_len: int, head_dim: int, theta: float
) -> Tuple[jax.Array, jax.Array]:
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    t = jnp.arange(seq_len, dtype=jnp.float32)
    angles = jnp.outer(t, freqs)  # [seq, head_dim/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [batch, seq, heads, head_dim]; rotate pairs (even, odd).

    Computed in x's own dtype: the angles (cos/sin tables) are built in
    f32 and each output element is one mul-add of unit-magnitude
    factors, so bf16 rotation adds at most half-ulp noise PER ELEMENT
    (no accumulation chain) — while an f32 rope forces the q/k
    projections to materialize f32 copies to HBM. An earlier chip run,
    not reproduced, put the f32 rope fusion alone at 1.7% of device
    time for zero accuracy benefit."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    )


# logical axes (parallel/sharding.py) of the activations a ``constrain``
# callable pins
_RESIDUAL = ("batch", "seq", "embed")
_Q = ("batch", "seq", "heads", None)
_KV = ("batch", "seq", "kv_heads", None)
_MLP = ("batch", "seq", "mlp")


def _free(x, logical_axes):
    """The default ``constrain``: every layout is the compiler's choice,
    which on one device is no choice at all."""
    return x


def _pre_attn(cfg: LlamaConfig, x, layer_params, cos, sin,
              constrain=_free, rope=True):
    """Block segment 1: attn-norm + q/k/v projections + rope (none in
    a layer whose kind says so); and, where the router reads the
    block's input, its logits, which ``_post_attn`` is handed past
    attention: ``(q, k, v, logits or None)``."""
    b, s, h = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = layer_params
    y = rms_norm(constrain(x, _RESIDUAL), p["attn_norm"], cfg.norm_eps)
    q, k = y @ p["wq"], y @ p["wk"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = constrain(q.reshape(b, s, nh, hd), _Q)
    k = constrain(k.reshape(b, s, nkv, hd), _KV)
    v = constrain((y @ p["wv"]).reshape(b, s, nkv, hd), _KV)
    if rope:
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    logits = None
    if cfg.num_experts > 0 and cfg.moe_router_input == "block_input":
        from dlrover_tpu.parallel.moe import router_logits

        logits = router_logits(x, p["router"])
    return q, k, v, logits


def _expert_mlp(cfg: LlamaConfig, expert_parallel: bool):
    """The routed MLP ``(y, router, w_gate, w_up, w_down) -> (out,
    aux)`` of an expert config: dropless where every expert is on the
    device, capacity-bucketed where they are sharded over an
    ``expert`` mesh axis (parallel/moe.py)."""
    from dlrover_tpu.parallel import moe

    routing = dict(
        k=cfg.moe_top_k, norm_topk_prob=cfg.norm_topk_prob,
        balance_coef=cfg.router_aux_loss_coef,
        z_coef=cfg.router_z_loss_coef,
    )
    if not expert_parallel:
        return partial(
            moe.dropless_moe_mlp, act=cfg.moe_expert_act,
            first_held=cfg.moe_first_expert_held, **routing
        )
    if (cfg.moe_experts_held != cfg.num_experts
            or cfg.moe_router_input != "post_attn_norm"
            or cfg.moe_expert_act != "silu"):
        raise ValueError(
            "a share of the experts held on one device "
            "(moe_experts_held), a router on the block's input and a "
            "relu gate are the dropless path's, on one device: over "
            "an 'expert' mesh axis larger than one they are refused "
            "(experts over chips: ROADMAP B9)"
        )
    if cfg.moe_capacity_factor <= 0:
        raise ValueError(
            "this configuration states dropless routing "
            "(moe_capacity_factor 0), and experts sharded over an "
            "'expert' mesh axis are bucketed by capacity and drop "
            "what overflows: refused, not run with drops"
        )
    return partial(
        moe.moe_mlp, capacity_factor=cfg.moe_capacity_factor, **routing
    )


def _post_attn(cfg: LlamaConfig, x, attn, layer_params,
               router_logits=None, constrain=_free, expert_parallel=False):
    """Block segment 2: output projection + residual + MLP.
    ``router_logits``: ``_pre_attn``'s, where the router reads the
    block's input."""
    b, s, h = x.shape
    p = layer_params
    x = constrain(x + attn.reshape(b, s, -1) @ p["wo"], _RESIDUAL)
    y = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    if cfg.num_experts > 0:
        mlp = _expert_mlp(cfg, expert_parallel)
        if router_logits is not None:
            mlp = partial(mlp, logits=router_logits)
        out, aux = mlp(
            y, p["router"], p["w_gate"], p["w_up"], p["w_down"]
        )
        return constrain(x + out, _RESIDUAL), aux
    gate = jax.nn.silu(constrain(y @ p["w_gate"], _MLP))
    up = constrain(y @ p["w_up"], _MLP)
    x = constrain(x + (gate * up) @ p["w_down"], _RESIDUAL)
    return x, jnp.zeros((), jnp.float32)


def _block(cfg: LlamaConfig, x, layer_params, cos, sin, attn_fn,
           constrain=_free, expert_parallel=False, rope=True):
    """One decoder block. x: [batch, seq, hidden]. Returns (x, aux_loss)
    where aux_loss is the MoE balance loss (0 for dense)."""
    q, k, v, logits = _pre_attn(
        cfg, x, layer_params, cos, sin, constrain, rope
    )
    attn = attn_fn(q, k, v)
    return _post_attn(
        cfg, x, attn, layer_params, logits, constrain, expert_parallel
    )


def _attention_of(cfg: LlamaConfig, attn_fn, window):
    """``attn_fn`` as a layer of one kind calls it. A config with a
    layer pattern names the two kinds of call, ``attn.full`` and
    ``attn.window``, in their device ops' ``op_name``, and hands a
    windowed layer's window to ``attn_fn`` (which has to take it)."""
    if cfg.sliding_window_layout is None:
        return attn_fn

    def attend(q, k, v):
        if window is None:
            with jax.named_scope("attn.full"):
                return attn_fn(q, k, v)
        with jax.named_scope("attn.window"):
            return attn_fn(q, k, v, window=window)

    return attend


def _scan_layers(layers, carry, blocks):
    """``carry`` through every layer, a period of the layer pattern a
    scan step: ``layers`` holds one ``layer(carry, layer_params) ->
    (carry, out)`` for each layer of a period (``layer_kinds()``),
    ``blocks`` the parameters stacked ``[layers, ...]`` as they are
    kept. Returns ``(carry, outs stacked [layers, ...])``. With one
    kind of layer this is the plain scan over ``blocks``."""
    period = len(layers)
    if period == 1:
        return jax.lax.scan(layers[0], carry, blocks)
    per_period = jax.tree.map(
        lambda a: a.reshape(-1, period, *a.shape[1:]), blocks
    )

    def body(carry, period_params):
        outs = []
        for i, layer in enumerate(layers):
            carry, out = layer(
                carry, jax.tree.map(lambda a: a[i], period_params)
            )
            outs.append(out)
        if outs[0] is None:
            return carry, None
        return carry, jax.tree.map(lambda *o: jnp.stack(o), *outs)

    carry, outs = jax.lax.scan(body, carry, per_period)
    if outs is not None:
        outs = jax.tree.map(
            lambda a: a.reshape(-1, *a.shape[2:]), outs
        )
    return carry, outs


def _dots_policy(cfg: LlamaConfig):
    """What the ``dots`` remat policies keep: the results of the plain
    matmuls and, of an expert layer, the gate and up products of the
    grouped matmul (a ``ragged_dot`` is no ``dot_general``, so the
    stock policy would recompute both in the backward pass; the sorted
    rows and the down product's input are cheap to make again)."""
    dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.num_experts == 0:
        return dots
    return jax.checkpoint_policies.save_from_both_policies(
        dots,
        jax.checkpoint_policies.save_only_these_names(
            "moe_gate", "moe_up"
        ),
    )


def hidden_states(
    params: Dict,
    tokens: jax.Array,  # int32 [batch, seq]
    cfg: LlamaConfig,
    attn_fn=None,
    constrain=None,
    expert_parallel: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Final-norm hidden states [batch, seq, hidden] + MoE aux loss.
    ``expert_parallel``: the experts are sharded over an ``expert``
    mesh axis (the trainer says so from its mesh).

    ``constrain(x, logical_axes) -> x`` pins the layout of the
    activations between the matmuls (the residual stream, q/k/v, the
    two MLP products) to the strategy's rule table, so that a
    partitioner faced with ``x[batch/n, seq, embed] @ w[embed/n, mlp]``
    gathers the weight and leaves the activation where it is. None
    leaves every layout to the compiler."""
    if attn_fn is None:
        attn_fn = partial(flash_attention, causal=True)
    if constrain is None:
        constrain = _free
    s = tokens.shape[1]
    cos, sin = rope_tables(s, cfg.head_dim, cfg.rope_theta)
    x = constrain(params["embed"][tokens], _RESIDUAL)

    def layer_of(kind):
        """One layer of ``kind`` under the config's remat policy."""
        window, rope = kind
        attend = _attention_of(cfg, attn_fn, window)

        def body(carry, layer_params):
            x, aux_sum = carry
            x, aux = _block(
                cfg, x, layer_params, cos, sin, attend, constrain,
                expert_parallel, rope,
            )
            return (x, aux_sum + aux), None

        if cfg.remat == "dots_attn_out":
            # "dots" remat on the segments AROUND attention, with the
            # attention call OUTSIDE any checkpoint: its custom_vjp
            # residuals (q, k, v, o, lse) are then kept like ordinary
            # activations, so the backward pass never re-runs the
            # forward kernel (under plain "dots" the re-fwd is ~7% of
            # the step). Costs the saved residuals' HBM (~q+k+v+o+lse
            # per layer).
            policy = _dots_policy(cfg)
            pre = jax.checkpoint(
                partial(_pre_attn, cfg, constrain=constrain, rope=rope),
                policy=policy,
            )
            post = jax.checkpoint(
                partial(_post_attn, cfg, constrain=constrain,
                        expert_parallel=expert_parallel),
                policy=policy,
            )

            def body(carry, layer_params):  # noqa: F811
                x, aux_sum = carry
                q, k, v, logits = pre(x, layer_params, cos, sin)
                attn = attend(q, k, v)
                x, aux = post(x, attn, layer_params, logits)
                return (x, aux_sum + aux), None

        elif cfg.remat == "dots":
            body = jax.checkpoint(body, policy=_dots_policy(cfg))
        elif cfg.remat == "minimal":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable
            )
        return body

    (x, aux), _ = _scan_layers(
        [layer_of(kind) for kind in cfg.layer_kinds()],
        (x, jnp.zeros((), jnp.float32)), params["blocks"],
    )
    x = constrain(x, _RESIDUAL)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def forward(
    params: Dict,
    tokens: jax.Array,  # int32 [batch, seq]
    cfg: LlamaConfig,
    attn_fn=None,
    return_aux: bool = False,
):
    """Logits [batch, seq, vocab]. ``attn_fn`` overrides attention (e.g.
    ring attention under sequence parallelism). With ``return_aux`` also
    returns the summed MoE auxiliary loss."""
    x, aux = hidden_states(params, tokens, cfg, attn_fn=attn_fn)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    if return_aux:
        return logits, aux
    return logits


def _masked_nll(logits: jax.Array, targets: jax.Array) -> Tuple[
        jax.Array, jax.Array]:
    """(sum of masked nll, mask count). targets < 0 mask positions out."""
    mask = (targets >= 0).astype(jnp.float32)
    safe_targets = jnp.maximum(targets, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, safe_targets[..., None], axis=-1
    )[..., 0]
    return jnp.sum(nll * mask), jnp.sum(mask)


def _chunked_ce(x: jax.Array, lm_head: jax.Array, targets: jax.Array,
                chunk: int) -> Tuple[jax.Array, jax.Array]:
    """Cross entropy without materializing full [tokens, vocab] logits:
    a rematerialized scan over token chunks — each chunk's logits and
    log-softmax are recomputed in the backward pass, so peak memory is
    one [chunk, vocab] block instead of [batch*seq, vocab]."""
    h = x.shape[-1]
    xf = x.reshape(-1, h)
    tf = targets.reshape(-1)
    n = xf.shape[0]
    if n % chunk:
        # pad to a chunk multiple with masked (-1) targets so chunking
        # never silently degrades to the full-logits allocation
        pad = chunk - n % chunk
        xf = jnp.concatenate([xf, jnp.zeros((pad, h), xf.dtype)])
        tf = jnp.concatenate([tf, jnp.full((pad,), -1, tf.dtype)])
        n += pad
    xc = xf.reshape(n // chunk, chunk, h)
    tc = tf.reshape(n // chunk, chunk)

    def body(carry, inp):
        nll_sum, cnt = carry
        xs, ts = inp
        logits = (xs @ lm_head).astype(jnp.float32)
        s, c = _masked_nll(logits, ts)
        return (nll_sum + s, cnt + c), None

    (nll_sum, cnt), _ = jax.lax.scan(
        jax.checkpoint(body), (jnp.zeros(()), jnp.zeros(())), (xc, tc)
    )
    return nll_sum, cnt


def next_token_loss(
    params: Dict, batch: Tuple[jax.Array, jax.Array], cfg: LlamaConfig,
    attn_fn=None, constrain=None, expert_parallel: bool = False,
) -> jax.Array:
    """Mean next-token cross entropy (plus, for an expert config, the
    scaled balance and z losses of every layer). batch = (tokens,
    targets), both int32 [batch, seq]; target < 0 masks the position
    out. ``constrain``, ``expert_parallel``: see ``hidden_states``."""
    tokens, targets = batch
    x, aux = hidden_states(
        params, tokens, cfg, attn_fn=attn_fn, constrain=constrain,
        expert_parallel=expert_parallel,
    )
    if cfg.loss_chunk > 0:
        nll_sum, cnt = _chunked_ce(
            x, params["lm_head"], targets, cfg.loss_chunk
        )
    else:
        logits = (x @ params["lm_head"]).astype(jnp.float32)
        nll_sum, cnt = _masked_nll(logits, targets)
    ce = nll_sum / jnp.maximum(cnt, 1.0)
    return ce + aux  # aux arrives scaled (the config's coefficients)


def routing_stats(params: Dict, tokens: jax.Array, cfg: LlamaConfig,
                  attn_fn=None) -> jax.Array:
    """Tokens per expert and layer, int32 [layers, experts], for
    ``tokens`` [batch, seq]: each row sums to ``batch x seq x
    moe_top_k``. A forward pass that also records, a layer, what the
    router chose for the hidden states it really sees (jit-able)."""
    if cfg.num_experts == 0:
        raise ValueError("routing_stats: a dense config has no router")
    from dlrover_tpu.parallel.moe import (
        logits_per_expert, router_logits,
    )

    if attn_fn is None:
        attn_fn = partial(flash_attention, causal=True)
    cos, sin = rope_tables(tokens.shape[1], cfg.head_dim, cfg.rope_theta)

    def layer_of(kind):
        window, rope = kind
        attend = _attention_of(cfg, attn_fn, window)

        def body(x, p):
            q, k, v, logits = _pre_attn(cfg, x, p, cos, sin, rope=rope)
            attn = attend(q, k, v)
            if logits is None:
                logits = router_logits(rms_norm(
                    x + attn.reshape(*x.shape[:2], -1) @ p["wo"],
                    p["mlp_norm"], cfg.norm_eps,
                ), p["router"])
            counts = logits_per_expert(logits, cfg.moe_top_k)
            x, _ = _post_attn(cfg, x, attn, p, logits)
            return x, counts

        return body

    _, counts = _scan_layers(
        [layer_of(kind) for kind in cfg.layer_kinds()],
        params["embed"][tokens], params["blocks"],
    )
    return counts


def flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Approximate training FLOPs per token (6N_active + attention
    quadratic, at ``num_heads x head_dim`` and by each layer's kind).
    For MoE, only the top-k routed experts execute per token, so N
    counts k experts — not all E."""
    n = param_count(cfg) - cfg.vocab_size * cfg.hidden_size  # tied-ish
    if cfg.num_experts > 0:
        L, h, m = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
        # of the experts held here a token meets its k's share
        met = (min(cfg.moe_top_k, cfg.num_experts)
               * cfg.moe_experts_held / cfg.num_experts)
        n -= L * 3 * h * m * (cfg.moe_experts_held - met)
    # scores and weighted values against every key a query's layer
    # lets it see, causality not counted: the sequence, or the window
    kinds = cfg.layer_kinds()
    keys = sum(min(window or seq_len, seq_len) for window, _ in kinds)
    attn = (12 * cfg.num_heads * cfg.head_dim
            * cfg.num_layers // len(kinds) * keys)
    return 6.0 * n + attn
