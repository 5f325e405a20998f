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
import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.attention import flash_attention
from dlrover_tpu.ops.delta_rule import gated_delta_rule_rows
from dlrover_tpu.ops.gated_norm import (
    gated_group_norm, head_norm_gate, head_norm_silu,
)
from dlrover_tpu.ops.kda_conv import conv_silu_norm
from dlrover_tpu.ops.selective_scan import selective_scan
from dlrover_tpu.ops.short_conv import gated_short_conv
from dlrover_tpu.ops.sparse_attention import (
    compress_keys, select_blocks, selected_attention,
)
from dlrover_tpu.ops.ssd import ssd_scan


#: the operators a layer's kind may name
OPERATORS = ("full_attention", "latent_attention", "linear_attention",
             "conv", "state_space", "sparse_attention",
             "lightning_attention", "mamba", "gated_delta_net", "none")
#: those whose kernels reach the step outside any ``shard_map`` (the
#: selection's compressed keys and top-k are over a whole sequence,
#: the scans, the delta rule with one decay a head among them, have no
#: state to hand to a neighbour, and the partitioner cannot cut a
#: Pallas call): the trainer refuses them on every mesh of more than
#: one device, be its axis ``seq``, ``fsdp`` or ``data`` (ROADMAP B13)
ONE_DEVICE_OPERATORS = ("sparse_attention", "lightning_attention", "mamba",
                        "gated_delta_net")


class LayerKind(NamedTuple):
    """What a layer is made of: its operator (``"full_attention"``,
    ``"latent_attention"``, whose k and v, and q unless one matrix
    makes it, come through low-rank projections, ``"conv"``, the
    gated short convolution, or
    ``"linear_attention"``, the gated delta rule, ``"state_space"``,
    a Mamba-2 mixer, ``"sparse_attention"``, full attention's q, k
    and v over the key blocks each query selects,
    ``"lightning_attention"``, linear attention with a fixed decay a
    head, ``"mamba"``, a Mamba-1 mixer (a selective scan whose decay
    differs by channel and by state), ``"gated_delta_net"``, the gated
    delta rule with one decay a head on heads of two widths, or
    ``"none"``), for attention the
    window (None: every earlier key) and whether q and
    k are rotated, and its feed-forward (``"dense"``,
    ``"experts"`` or ``"none"``). A block of one branch (``x +
    branch(RMSNorm(x))``) is a kind with one of the two ``"none"``."""
    operator: str = "full_attention"
    window: Optional[int] = None
    rope: bool = True
    ffn: str = "dense"


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
    # the untied head's, drawn by ``init_params``: None is ``hidden_size
    # ** -0.5``, logits of unit deviation from a normed stream. A loss
    # over targets that the input does not predict differs between two
    # computations by the logits' deviation times how far the streams
    # lie apart over the root of the tokens: a larger head is what a
    # comparison of such losses sees more with (PERF.md section 6,
    # PR 49)
    head_init_std: Optional[float] = None
    # a stack of two kinds of operator, in the source's keys
    # (``Lfm2MoeConfig``): ``layer_types[l]`` is "full_attention" or
    # "conv", the gated short convolution of ``conv_L_cache`` taps
    # (ops/short_conv.py) in attention's place. The first
    # ``num_dense_layers`` layers have a dense MLP of
    # ``intermediate_size`` where the others have experts of
    # ``moe_intermediate_size`` (None: ``intermediate_size``); they run
    # ahead of the scan, and the scan walks a period of what follows.
    # With either key the parameters are kept by position
    # (``init_params``): layers of two kinds own different leaves.
    layer_types: Optional[Tuple[str, ...]] = None
    num_dense_layers: int = 0
    moe_intermediate_size: Optional[int] = None
    conv_L_cache: int = 3
    # RMSNorm of each head's ``head_dim`` values of q and of k, one
    # ``head_dim``-wide scale each, before the rotary embedding
    # (``Lfm2MoeAttention``'s q_layernorm, k_layernorm; ``qk_norm``
    # above is over the whole projection)
    qk_head_norm: bool = False
    # logits over the embedding's own rows, ``h @ embed.T``: no
    # ``lm_head`` leaf
    tie_word_embeddings: bool = False
    # the router's gate ("softmax" over the experts, or "sigmoid" of
    # each) and a selection bias (``use_expert_bias``: the k experts
    # are the top-k of score plus ``expert_bias``, the weights the
    # scores without it; a float32 buffer that starts at zero, that
    # no gradient reaches and that the trainer's optimizer leaves
    # alone: parallel/moe.py ``route_logits``)
    moe_gate: str = "softmax"
    use_expert_bias: bool = False
    # what is added to the sum of a token's k weights before they are
    # divided by it (None: the gate's own, 1e-6 under "sigmoid" and
    # nothing under "softmax"), and a factor on the weights after it
    # (``routed_scaling_factor``)
    moe_topk_norm_eps: Optional[float] = None
    moe_routed_scaling: float = 1.0
    # experts that every token takes, beside the routed ones and
    # unweighted (``n_shared_experts``): one gated MLP of that many
    # times ``moe_intermediate_size``, whole on every device
    moe_shared_experts: int = 0
    # latent attention, in the source's keys (``DeepseekV3Config``,
    # ``KimiLinearConfig``): with ``kv_lora_rank`` a layer's operator
    # is "latent_attention": every layer's without ``layer_types``,
    # with it the layers it names so, beside "linear_attention" and
    # "conv" ones (attention with whole q, k and v in the same stack
    # is refused). q comes from a ``q_lora_rank``-wide projection
    # through an RMSNorm, or with ``q_lora_rank`` None from the stream
    # by one matrix ``wq`` with no norm; k's first part and v from a
    # ``kv_lora_rank``-wide projection through an RMSNorm; each head's
    # q and k are ``qk_nope_head_dim`` such columns and
    # ``qk_rope_head_dim`` further ones, that part of k one head's
    # that all heads share; v is ``v_head_dim`` wide (``head_dim`` is
    # not read). The further columns are rotated where the layer's
    # ``rope_layout`` entry is 1 (None: everywhere) and left as the
    # products made them where it is 0 (``mla_use_nope``).
    # ``rope_interleave``: the rotation pairs columns (2i, 2i + 1),
    # not (i, i + half).
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    # multi-token prediction (``num_nextn_predict_layers``): that many
    # further blocks past the stack, each fed the block before's
    # output and the next token's embedding and read by the model's
    # own head one token further ahead; their mean losses join
    # ``next_token_loss`` at ``mtp_loss_weight``. Depth 1 is what is
    # built.
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.3
    # linear attention, in the source's keys (``linear_attn_config``
    # and ``kda_*`` of a delta-rule/full-attention hybrid): where
    # ``layer_types[l]`` is "linear_attention" the operator is the
    # gated delta rule with a decay for every key channel
    # (ops/delta_rule.py) over ``linear_num_heads`` heads of
    # ``linear_head_dim`` keys and as many values. q, k and v each pass
    # a causal depthwise convolution of ``linear_conv_size`` taps and
    # ``silu``, q and k an l2 norm a head; the log decay is ``-exp(A_log)
    # softplus(f_b f_a y + dt_bias)`` through ``linear_gate_rank``
    # columns (``kda_use_full_proj`` false), the step size ``sigmoid(y
    # w_beta)``, doubled with ``linear_allow_neg_eigval``; the result
    # passes an RMSNorm a head and a sigmoid gate of the same low rank.
    linear_num_heads: int = 0
    linear_head_dim: int = 128
    linear_conv_size: int = 4
    linear_gate_rank: int = 128
    linear_allow_neg_eigval: bool = False
    # Gated DeltaNet, in the source's keys (``OlmoHybridConfig``'s
    # ``linear_*``): where ``layer_types[l]`` is "gated_delta_net" the
    # operator is the gated delta rule with ONE decay a head
    # (ops/delta_rule.py) over ``linear_num_value_heads`` heads of
    # ``linear_key_head_dim`` keys by ``linear_value_head_dim`` values
    # (``linear_num_key_heads`` the same number: a key head for every
    # value head is what is built). q, k and v by one matrix each
    # through a causal depthwise convolution of
    # ``linear_conv_kernel_dim`` taps and ``silu``, q and k an l2 norm
    # a head; the log decay ``-exp(A_log) softplus(y w_a + dt_bias)``
    # and the step size ``sigmoid(y w_beta)`` (doubled with
    # ``linear_allow_neg_eigval``) a number a head and position; the
    # result through an RMSNorm a head and then the gate ``silu(y
    # wg)``, full rank and without a bias, and ``wo``. One stack holds
    # this operator or "linear_attention", not both.
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    # a sigmoid gate on full attention's result, elementwise, from the
    # layer's normed input through ``wg`` (``use_gqa_gate``)
    attn_out_gate: bool = False
    # where a block's norms stand. False: ahead of each branch,
    # ``x + branch(RMSNorm(x))``, the leaves ``attn_norm`` and
    # ``mlp_norm``. True: four norms a block, in the source's keys
    # (``AfmoeDecoderLayer``): an RMSNorm on each branch's result too,
    # ahead of the residual sum, ``x + RMSNorm(attn(RMSNorm(x)))`` and
    # ``x + RMSNorm(ffn(RMSNorm(x)))``; the further leaves
    # ``post_attn_norm`` and ``post_mlp_norm``. "alone": on the
    # branches' results and nowhere else (``Olmo2DecoderLayer``'s
    # ``post_attention_layernorm`` and ``post_feedforward_layernorm``),
    # ``x + RMSNorm(attn(x))`` and ``x + RMSNorm(ffn(x))``: the two
    # further leaves without the first two.
    post_norms: Any = False
    # the embedding's rows times ``sqrt(hidden_size)`` as they enter
    # the stream (``mup_enabled``)
    mup_enabled: bool = False
    # the rule that moves the selection bias, every step, by the sign
    # of the load's error (auxiliary-loss-free balancing,
    # arXiv:2408.15664; ``load_balance_coeff``): 0 leaves the buffer
    # as it is. ``loss_and_expert_counts`` hands the step's
    # assignments out beside the loss, ``moved_expert_bias`` applies
    # the rule to them, and the trainer calls both
    # (``trainer/sharded.py``)
    moe_bias_update_rate: float = 0.0
    # a stack of blocks of one branch, in the source's keys
    # (``NemotronHConfig``): layer l is ``x + branch(RMSNorm(x))``
    # with the branch ``hybrid_override_pattern[l]`` names: "M" a
    # Mamba-2 mixer, "*" attention, "E" experts. With the key the
    # parameters are kept by position. The mixer: ``mamba_num_heads``
    # heads of ``mamba_head_dim`` in ``n_groups`` groups that share
    # their B and C of ``ssm_state_size``, behind one input projection
    # ``[z | x | B | C | dt]`` and a causal depthwise convolution of
    # ``conv_kernel`` taps over ``[x | B | C]`` with a bias a channel
    # (``use_conv_bias``); the scan is ops/ssd.py's, whose plain path
    # walks chunks of ``chunk_size``; its result times ``silu(z)``,
    # then an RMSNorm over each group's columns, then the output
    # projection.
    hybrid_override_pattern: Optional[str] = None
    mamba_num_heads: int = 0
    mamba_head_dim: int = 64
    n_groups: int = 1
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    # experts without a gate matrix, ``W_down act(W_up u)`` (False;
    # the shared expert alike), and experts in a latent: the routed
    # ones read and write ``moe_latent_size`` columns, between a
    # projection down from the stream and one back up, while router
    # and shared expert read the stream itself. The shared expert's
    # width where the source states it apart from
    # ``moe_shared_experts x moe_intermediate_size``
    moe_expert_gated: bool = True
    moe_latent_size: Optional[int] = None
    moe_shared_expert_intermediate_size: Optional[int] = None
    # the prediction module's own sublayers, as
    # ``hybrid_override_pattern`` names the stack's (None: one block
    # of the stack's last kind)
    mtp_hybrid_override_pattern: Optional[str] = None
    # a looped stack, in the source's keys (``OuroConfig``): the whole
    # stack is walked ``total_ut_steps`` times a step with its one set
    # of weights, the final norm inside the loop (a pass reads the
    # normed state of the pass before, the first the embedding), the
    # positions the same in every pass. A gate ``exit_gate`` reads each
    # pass's normed state, ``lambda_t = sigmoid(w . h_t + b)`` a
    # position, and the loss is every pass's cross entropy weighted
    # position by position by the exit distribution ``p_t = lambda_t
    # prod_{j<t} (1 - lambda_j)`` (the last pass takes what is left),
    # less ``exit_entropy_weight`` times that distribution's entropy
    # (``_losses_and_counts``). 1: no loop, no gate, the plain loss.
    total_ut_steps: int = 1
    exit_entropy_weight: float = 0.1
    # three scalar factors, in the source's keys (``MiniCPMConfig``):
    # the embedding's rows times ``scale_emb`` as they enter the
    # stream; each branch's result times ``scale_depth / sqrt(
    # scale_depth_layers)`` ahead of the residual sum,
    # ``scale_depth_layers`` the depth the source divides by (its
    # published ``num_hidden_layers``; None: ``num_layers``), and None
    # for ``scale_depth`` no factor; the head's input, past the final
    # norm, divided by ``hidden_size / dim_model_base`` (None: as it
    # is). No initialisation rule reads them here.
    scale_emb: float = 1.0
    scale_depth: Optional[float] = None
    scale_depth_layers: Optional[int] = None
    dim_model_base: Optional[int] = None
    # attention over selected key blocks (InfLLM-v2; the family's
    # ``sparse_config``): where ``layer_types[l]`` is
    # "sparse_attention" the layer's q, k and v are full attention's
    # (with ``qk_head_norm`` and ``attn_out_gate`` as set) and each
    # query of a kv head's heads sees the ``sparse_topk`` blocks of
    # ``sparse_block_size`` keys that ops/sparse_attention.py selects:
    # compressed keys of ``sparse_kernel_size`` every
    # ``sparse_kernel_stride``, the first ``sparse_init_blocks`` and
    # the ``sparse_window_size / sparse_block_size`` nearest blocks
    # forced. A sequence of ``sparse_dense_len`` positions or fewer
    # takes full attention.
    sparse_block_size: int = 64
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_topk: int = 64
    sparse_window_size: int = 2048
    sparse_init_blocks: int = 1
    sparse_dense_len: int = 8192
    # linear attention with a fixed decay a head (Lightning
    # Attention-2): where ``layer_types[l]`` is "lightning_attention",
    # ``lightning_num_heads`` heads of ``lightning_head_dim``, q, k
    # and v by one matrix each, an RMSNorm on each head's q and k (one
    # ``lightning_head_dim``-wide scale each), both rotated where the
    # layer's ``rope_layout`` entry is 1; ``S_t = exp(-m_h) S_{t-1} +
    # k_t v_t^T``, ``o_t = S_t^T q_t / sqrt(lightning_head_dim)``,
    # ``m_h = 2 ** (-8 (h + 1) / heads)`` a constant (``lightning_decay``);
    # the result through an RMSNorm a head, a sigmoid gate from the
    # layer's normed input through ``wg``, and ``wo``. The scan is
    # ops/ssd.py's with one head a group, a step of one and no skip.
    lightning_num_heads: int = 0
    lightning_head_dim: int = 128
    # a Mamba-1 mixer, in the source's keys (``JambaConfig``): where
    # ``layer_types[l]`` is "mamba", ``d = mamba_expand x hidden_size``
    # channels of ``mamba_d_state`` states each: ``[x | z]`` by one
    # projection without a bias, a causal depthwise convolution of
    # ``mamba_d_conv`` taps over ``x`` with a bias a channel and
    # ``silu``; ``[dt | B | C]`` from the convolved ``x`` by a second
    # projection (``mamba_dt_rank`` + 2 x ``mamba_d_state`` columns; the
    # rank is stated, nothing is derived from the width), an RMSNorm
    # with a learned scale on each of the three;
    # ``Delta = softplus(dt W_dt + b_dt)`` a channel, ``A =
    # -exp(A_log)`` [d, states]; the scan is ops/selective_scan.py's;
    # its result times ``silu(z)``, then the output projection. No norm
    # past the gate, no head, no group.
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_dt_rank: int = 0
    mamba_d_conv: int = 4

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
        if self.moe_expert_act not in ("silu", "relu", "relu2"):
            raise ValueError(
                f"unknown moe_expert_act {self.moe_expert_act!r}"
            )
        if self.moe_intermediate_size is None:
            object.__setattr__(
                self, "moe_intermediate_size", self.intermediate_size
            )
        if self.post_norms not in (False, True, "alone"):
            raise ValueError(
                f"post_norms {self.post_norms!r}: a block's norms stand "
                "ahead of its branches (False), on both sides (True) "
                "or on the branches' results 'alone'"
            )
        if self.layer_types is not None:
            object.__setattr__(
                self, "layer_types", tuple(self.layer_types)
            )
            if len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"layer_types has {len(self.layer_types)} entries "
                    f"for {self.num_layers} layers"
                )
            unknown = set(self.layer_types) - {
                "conv", "full_attention", "latent_attention",
                "linear_attention", "sparse_attention",
                "lightning_attention", "mamba", "gated_delta_net"}
            if unknown:
                raise ValueError(
                    f"layer_types names {sorted(unknown)}: the "
                    "operators here are 'conv', 'full_attention', "
                    "'latent_attention', 'linear_attention', "
                    "'sparse_attention', 'lightning_attention', "
                    "'mamba' and 'gated_delta_net'"
                )
            if self.latent != ("latent_attention" in self.layer_types) or (
                    self.latent and "full_attention" in self.layer_types):
                raise ValueError(
                    "layer_types names 'latent_attention' where "
                    "kv_lora_rank sizes it, and then no "
                    "'full_attention' beside it: one stack's attention "
                    "layers are all of whole q, k and v or all latent"
                )
            if ("linear_attention" in self.layer_types
                    and not self.linear_num_heads):
                raise ValueError(
                    "layer_types names 'linear_attention' and "
                    "linear_num_heads gives it no head"
                )
        self._check_operators_and_factors()
        if self.num_dense_layers and not (
                self.num_experts > 0
                and self.num_dense_layers < self.num_layers):
            raise ValueError(
                f"num_dense_layers {self.num_dense_layers}: dense "
                "layers lead a stack of expert layers and run ahead "
                f"of its scanned periods, of which {self.num_layers} "
                f"layers with {self.num_experts} experts leave none"
            )
        if self.moe_gate not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_gate {self.moe_gate!r}")
        if self.latent and not (
                self.qk_nope_head_dim and self.qk_rope_head_dim
                and self.v_head_dim
                and self.num_kv_heads == self.num_heads
                and self.sliding_window_layout is None
                and not (self.qk_norm or self.qk_head_norm
                         or self.attn_out_gate)):
            raise ValueError(
                "latent attention (kv_lora_rank) takes "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim "
                "and as many kv heads as heads; q through q_lora_rank "
                "or, with None, by one matrix; layer_types may name "
                "its layers beside 'linear_attention' and 'conv' ones "
                "and rope_layout those that rotate; a window, a norm "
                "of whole q and k or a gate on its result is not built"
            )
        if self.moe_bias_update_rate and not (
                self.num_experts > 0 and self.use_expert_bias):
            raise ValueError(
                f"moe_bias_update_rate {self.moe_bias_update_rate}: the "
                "rule moves an expert layer's selection bias, which "
                "use_expert_bias keeps"
            )
        for name in ("hybrid_override_pattern",
                     "mtp_hybrid_override_pattern"):
            unknown = set(getattr(self, name) or "") - set("M*E")
            if unknown:
                raise ValueError(
                    f"{name} names {sorted(unknown)}: the branches "
                    "here are 'M' (Mamba-2), '*' (attention) and 'E' "
                    "(experts)"
                )
        if self.hybrid_override_pattern is not None:
            pattern = self.hybrid_override_pattern
            if (len(pattern) != self.num_layers or self.layer_types
                    or self.num_dense_layers or self.latent
                    or self.post_norms or self.moe_bias_update_rate):
                raise ValueError(
                    f"hybrid_override_pattern {pattern!r} for "
                    f"{self.num_layers} layers: a character a layer, "
                    "and no layer_types, leading dense layers, latent "
                    "attention, norms on a branch's result or rule on "
                    "the selection bias beside it"
                )
            if "M" in pattern and not (
                    self.mamba_num_heads > 0 and self.n_groups > 0
                    and self.mamba_num_heads % self.n_groups == 0):
                raise ValueError(
                    f"hybrid_override_pattern names 'M' and "
                    f"mamba_num_heads {self.mamba_num_heads} in "
                    f"n_groups {self.n_groups} gives it no heads"
                )
            if "E" in pattern and self.num_experts == 0:
                raise ValueError(
                    "hybrid_override_pattern names 'E' and num_experts "
                    "gives it no expert"
                )
        elif self.mtp_hybrid_override_pattern is not None:
            raise ValueError(
                "mtp_hybrid_override_pattern names the sublayers of a "
                "module past a stack that hybrid_override_pattern names"
            )
        if self.total_ut_steps < 1:
            raise ValueError(
                f"total_ut_steps {self.total_ut_steps}: the stack is "
                "walked at least once"
            )
        if self.total_ut_steps > 1 and (
                self.num_experts > 0 or self.mtp_layers
                or self.by_position):
            raise ValueError(
                f"total_ut_steps {self.total_ut_steps}: the loop that "
                "is built walks one stack of like dense layers; "
                "experts (whose balance losses and bias rule's counts "
                "would be summed over the passes), a prediction module "
                "and a stack kept by position (layer_types, leading "
                "dense layers, hybrid_override_pattern) beside it are "
                "refused, not guessed"
            )
        if self.mtp_layers not in (0, 1):
            raise ValueError(
                f"mtp_layers {self.mtp_layers}: one prediction module "
                "past the stack is what is built"
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

    def _check_operators_and_factors(self):
        """Refuse what of the operators and the three factors is not
        built, rather than run it wrong."""
        types = self.layer_types or ()
        if "gated_delta_net" in types:
            heads = (self.linear_num_key_heads, self.linear_num_value_heads)
            if "linear_attention" in types:
                raise ValueError(
                    "layer_types names 'gated_delta_net' and "
                    "'linear_attention': one stack holds one form of "
                    "the delta rule's decay, a head's or a channel's "
                    "(both read linear_allow_neg_eigval)"
                )
            if heads[0] != heads[1] or heads[0] < 1:
                raise ValueError(
                    f"linear_num_key_heads {heads[0]} and "
                    f"linear_num_value_heads {heads[1]}: the gated delta "
                    "rule here has a key head for every value head, and "
                    "at least one (grouped keys: ROADMAP B13)"
                )
            if self.linear_key_head_dim < 1 or self.linear_value_head_dim < 1:
                raise ValueError(
                    f"linear_key_head_dim {self.linear_key_head_dim} and "
                    f"linear_value_head_dim {self.linear_value_head_dim}: "
                    "layer_types names 'gated_delta_net' and a head has "
                    "no width"
                )
            if (self.num_experts > 0 or self.latent or self.mtp_layers
                    or self.total_ut_steps > 1):
                raise ValueError(
                    "'gated_delta_net' layers stand in a plain stack of "
                    "dense two-branch blocks beside 'full_attention' "
                    "ones: experts, latent attention, a prediction "
                    "module or a loop beside them are not built"
                )
        sparse = "sparse_attention" in types
        lightning = "lightning_attention" in types
        if (sparse or lightning or "mamba" in types) and (
                self.num_experts > 0 or self.latent or self.mtp_layers
                or self.post_norms):
            raise ValueError(
                "'sparse_attention', 'lightning_attention' and 'mamba' "
                "layers "
                "stand in a stack of dense two-branch blocks: experts, "
                "latent attention, a prediction module or norms on a "
                "branch's result beside them are not built"
            )
        if "mamba" in types and self.mamba_dt_rank < 1:
            raise ValueError(
                f"layer_types names 'mamba': mamba_dt_rank "
                f"{self.mamba_dt_rank}, and the step's rank is the "
                "source's stated one (nothing derives it)"
            )
        if sparse:
            block, stride = self.sparse_block_size, self.sparse_kernel_stride
            if (block < 1 or block & (block - 1) or stride < 1
                    or block % stride or self.sparse_kernel_size % stride
                    or self.sparse_window_size % block
                    or self.sparse_init_blocks
                    + self.sparse_window_size // block > self.sparse_topk):
                raise ValueError(
                    f"sparse attention: blocks of {block} keys (a power "
                    f"of two), compressed keys of "
                    f"{self.sparse_kernel_size} every {stride} (both "
                    f"whole strides), a window of "
                    f"{self.sparse_window_size} (whole blocks) and "
                    f"{self.sparse_init_blocks} first blocks, all "
                    f"forced within the {self.sparse_topk} selected"
                )
        if lightning:
            rotated = any(
                on for on, t in zip(
                    self.rope_layout or (1,) * len(types), types)
                if t == "lightning_attention")
            if self.lightning_num_heads < 1 or (
                    rotated and self.lightning_head_dim != self.head_dim):
                raise ValueError(
                    f"layer_types names 'lightning_attention': "
                    f"lightning_num_heads {self.lightning_num_heads} "
                    f"heads of {self.lightning_head_dim}, which where "
                    f"they are rotated is head_dim {self.head_dim} (the "
                    "stack has one table of angles)"
                )
        factors = (self.scale_emb != 1.0 or self.scale_depth is not None
                   or self.dim_model_base is not None)
        if factors and (self.total_ut_steps > 1 or self.mtp_layers):
            raise ValueError(
                "scale_emb, scale_depth and dim_model_base are read by "
                "the plain stack and its one head: a looped stack or a "
                "prediction module beside them is not built"
            )
        if self.scale_depth_layers is not None and self.scale_depth is None:
            raise ValueError(
                f"scale_depth_layers {self.scale_depth_layers} divides "
                "a scale_depth, and there is none"
            )

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank is not None

    @property
    def branch_scale(self) -> Optional[float]:
        """What each branch's result is multiplied by ahead of the
        residual sum (``scale_depth``); None: nothing."""
        if self.scale_depth is None:
            return None
        return self.scale_depth / math.sqrt(
            self.scale_depth_layers or self.num_layers)

    def lightning_decay(self):
        """A lightning layer's rate a head, float32 [heads]: ``m_h =
        2 ** (-8 (h + 1) / heads)``, a constant that is no leaf."""
        heads = self.lightning_num_heads
        return jnp.exp2(
            -8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads)

    @property
    def mamba_widths(self) -> Tuple[int, int, int]:
        """A Mamba-1 mixer's ``(channels, states a channel, the step's
        rank)``."""
        return (self.mamba_expand * self.hidden_size, self.mamba_d_state,
                self.mamba_dt_rank)

    @property
    def rope_dim(self) -> int:
        """How many of a head's columns the rotary embedding turns."""
        return self.qk_rope_head_dim if self.latent else self.head_dim

    @property
    def by_position(self) -> bool:
        """Whether the parameters are kept by position (``lead`` and
        ``period``) and not as one stack of like layers
        (``blocks``)."""
        return (self.layer_types is not None or self.num_dense_layers > 0
                or self.hybrid_override_pattern is not None)

    def _branch(self, character: str, rope: bool = False) -> LayerKind:
        """The kind of the one-branch block a pattern's ``character``
        names."""
        if character == "M":
            return LayerKind("state_space", None, False, "none")
        if character == "*":
            return LayerKind("full_attention", None, rope, "none")
        return LayerKind("none", None, False, "experts")

    def mtp_kinds(self) -> Tuple[LayerKind, ...]:
        """The kinds of a prediction module's sublayers: those
        ``mtp_hybrid_override_pattern`` names (attention as the
        stack's first attention layer has it), or one block of the
        stack's last kind."""
        if self.mtp_hybrid_override_pattern is None:
            return self.layer_plan()[1][-1:]
        rope = bool((self.rope_layout or (1,))[0])
        return tuple(
            self._branch(c, rope) for c in self.mtp_hybrid_override_pattern
        )

    def layer_plan(self) -> Tuple[Tuple[LayerKind, ...],
                                  Tuple[LayerKind, ...]]:
        """``(lead, period)``: the kinds of the layers that run ahead
        of the scan (the ``num_dense_layers`` leading dense ones) and
        of one period of the layers that follow, the shortest repeat
        of their kinds."""
        n = self.num_layers
        windows = [
            self.sliding_window_size if on else None
            for on in self.sliding_window_layout or (0,) * n
        ]
        ropes = [bool(on) for on in self.rope_layout or (1,) * n]
        # the one-branch blocks a pattern names, or (without one) a
        # block of operator and feed-forward a layer
        kinds = tuple(
            self._branch(c, ropes[i])
            for i, c in enumerate(self.hybrid_override_pattern or "")
        ) or tuple(
            LayerKind(
                operator, *(
                    (None, False)
                    if operator in ("conv", "linear_attention", "mamba",
                                    "gated_delta_net")
                    else (windows[i], ropes[i])
                ),
                "experts" if self.num_experts > 0
                and i >= self.num_dense_layers else "dense",
            )
            for i, operator in enumerate(
                self.layer_types or (
                    "latent_attention" if self.latent
                    else "full_attention",
                ) * n
            )
        )
        lead, rest = (kinds[:self.num_dense_layers],
                      kinds[self.num_dense_layers:])
        period = next(
            p for p in range(1, len(rest) + 1)
            if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p)
        )
        return lead, rest[:period]

    def layer_kinds(self) -> Tuple[Tuple[Optional[int], bool], ...]:
        """``(window, rope)`` of each layer of one period: the window
        (None: every earlier key) and whether q and k are rotated. The
        period is the shortest repeat of the two layouts; one layer of
        kind ``(None, True)`` where there is no layout."""
        return tuple(
            (kind.window, kind.rope) for kind in self.layer_plan()[1]
        )


def llama2_7b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


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


def llama_latent_tiny(**kw) -> LlamaConfig:
    """Test-sized config with latent attention (24-wide q and k of
    which 8 columns are rotated in pairs, 16-wide v), a leading dense
    layer, a shared expert beside 8 routed ones (sigmoid scores, a
    selection bias, weights times 2.5) and one prediction module."""
    return llama_tiny(**{**dict(
        num_layers=3, num_dense_layers=1, num_kv_heads=4, num_experts=8,
        moe_top_k=2, moe_intermediate_size=32, moe_gate="sigmoid",
        use_expert_bias=True, moe_topk_norm_eps=1e-20,
        moe_routed_scaling=2.5, moe_shared_experts=1, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_interleave=True, mtp_layers=1,
    ), **kw})


def llama_linear_tiny(**kw) -> LlamaConfig:
    """Test-sized delta-rule/full-attention hybrid: a period of one
    gated full-attention layer without positions and three layers of
    the gated delta rule (4 heads of 16 behind four-tap convolutions),
    8 experts by sigmoid score with a selection bias and a shared
    one."""
    return llama_tiny(**{**dict(
        num_layers=4, layer_types=("full_attention",)
        + ("linear_attention",) * 3, rope_layout=(0,) * 4,
        attn_out_gate=True, linear_num_heads=4, linear_head_dim=16,
        linear_gate_rank=16, linear_allow_neg_eigval=True,
        num_experts=8, moe_top_k=2, moe_intermediate_size=32,
        moe_gate="sigmoid", use_expert_bias=True,
        moe_topk_norm_eps=1e-20, moe_shared_experts=1,
    ), **kw})


def llama_gdn_tiny(**kw) -> LlamaConfig:
    """Test-sized Gated DeltaNet/full-attention hybrid of dense blocks
    normed on their branches' results alone: a period of three layers
    of the gated delta rule with one decay a head (3 heads of 24 keys
    by 40 values behind four-tap convolutions, a ``silu`` gate past
    the heads' norm) and one full-attention layer of 3 ungrouped heads
    of 16 without positions, q and k normed over their whole
    projections."""
    return llama_tiny(**{**dict(
        num_layers=4, layer_types=("gated_delta_net",) * 3
        + ("full_attention",), rope_layout=(0,) * 4, num_heads=3,
        num_kv_heads=3, head_dim=16, qk_norm=True, post_norms="alone",
        linear_num_key_heads=3, linear_num_value_heads=3,
        linear_key_head_dim=24, linear_value_head_dim=40,
        linear_allow_neg_eigval=True, norm_eps=1e-6,
    ), **kw})


def llama_sandwich_tiny(**kw) -> LlamaConfig:
    """Test-sized config with four norms a block and the embedding
    times ``sqrt(hidden_size)``: a leading dense layer, then a period
    of gated windowed attention with the rotary embedding and one
    gated full-attention layer without positions, the heads' norms on
    q and k, a shared expert beside 8 routed ones by sigmoid score,
    and the selection bias moved every step by the load's error."""
    return llama_tiny(**{**dict(
        num_layers=5, num_dense_layers=1, num_kv_heads=1,
        sliding_window_size=32, sliding_window_layout=(1, 1, 0, 1, 1),
        rope_layout=(1, 1, 0, 1, 1), qk_head_norm=True,
        attn_out_gate=True, post_norms=True, mup_enabled=True,
        embed_init_std=0.125, num_experts=8, moe_top_k=2,
        moe_intermediate_size=32, moe_gate="sigmoid",
        use_expert_bias=True, moe_topk_norm_eps=1e-20,
        moe_routed_scaling=2.826, moe_shared_experts=1,
        moe_capacity_factor=0.0, router_aux_loss_coef=0.0,
        router_z_loss_coef=0.0, moe_bias_update_rate=1e-3,
    ), **kw})


def llama_mamba_tiny(**kw) -> LlamaConfig:
    """Test-sized stack of one-branch blocks: Mamba-2 mixers (8 heads
    of 16 in 4 groups of 16 states behind a four-tap convolution with
    a bias), one attention layer without positions and experts without
    a gate (``relu2``) in a 32-wide latent, 4 of 16 held by sigmoid
    score with a selection bias and a factor of 5, beside a shared
    expert on the stream; a prediction module of two sublayers."""
    pattern = "MEMEMEM*EME"
    return llama_tiny(**{**dict(
        num_layers=len(pattern), hybrid_override_pattern=pattern,
        rope_layout=(0,) * len(pattern), mamba_num_heads=8,
        mamba_head_dim=16, n_groups=4, ssm_state_size=16, chunk_size=32,
        num_experts=16, moe_top_k=4, moe_experts_held=4,
        moe_intermediate_size=24, moe_gate="sigmoid",
        use_expert_bias=True, moe_topk_norm_eps=1e-20,
        moe_routed_scaling=5.0, moe_shared_experts=1,
        moe_shared_expert_intermediate_size=48, moe_expert_act="relu2",
        moe_expert_gated=False, moe_latent_size=32,
        moe_capacity_factor=0.0, mtp_layers=1,
        mtp_hybrid_override_pattern="*E",
    ), **kw})


def llama_loop_tiny(**kw) -> LlamaConfig:
    """Test-sized looped stack: two dense layers of four norms a block
    walked four times with their one set of weights, an exit gate a
    position and the loss that weights every pass's cross entropy by
    it."""
    return llama_tiny(**{**dict(post_norms=True, total_ut_steps=4), **kw})


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

def _leaves(cfg: LlamaConfig, kind: LayerKind) -> Dict:
    """``{leaf: (shape, logical axes, deviation)}`` of one layer of
    ``kind``: its matrices, drawn fan-in normal (the fan-in second to
    last in every shape), its RMSNorm scales (deviation None: ones),
    a convolution's taps and an expert layer's selection bias
    (deviation 0: zeros)."""
    h, hd = cfg.hidden_size, cfg.head_dim
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    # a branch's norm: a block of one branch has the one, and a block
    # normed on its branches' results alone has neither
    norms = {
        name: h for name, branch in (
            ("attn_norm", kind.operator), ("mlp_norm", kind.ffn))
        if branch != "none" and cfg.post_norms != "alone"
    }
    if cfg.post_norms:
        norms.update(post_attn_norm=h, post_mlp_norm=h)
    if kind.operator == "none":
        matrices = {}
    elif kind.operator == "state_space":
        inner, conv = _ssm_widths(cfg)
        matrices = {
            # [z | x | B | C | dt]
            "ssm_in": ((h, inner + conv + cfg.mamba_num_heads),
                       ("embed", "mlp")),
            "ssm_out": ((inner, h), ("mlp", "embed")),
        }
        norms["ssm_norm"] = inner
    elif kind.operator == "mamba":
        d, n, rank = cfg.mamba_widths
        matrices = {
            "mamba_in": ((h, 2 * d), ("embed", "mlp")),  # [x | z]
            "mamba_x": ((d, rank + 2 * n), ("mlp", None)),  # [dt | B | C]
            "mamba_dt": ((rank, d), (None, "mlp")),
            "mamba_out": ((d, h), ("mlp", "embed")),
        }
        norms.update(mamba_dt_norm=rank, mamba_b_norm=n, mamba_c_norm=n)
    elif kind.operator == "conv":
        matrices = {
            "conv_in": ((h, 3 * h), ("embed", "mlp")),
            "conv_out": ((h, h), ("mlp", "embed")),
        }
    elif kind.operator == "linear_attention":
        lh, ld, rank = (cfg.linear_num_heads, cfg.linear_head_dim,
                        cfg.linear_gate_rank)
        matrices = {
            "wq": ((h, lh * ld), ("embed", "heads")),
            "wk": ((h, lh * ld), ("embed", "heads")),
            "wv": ((h, lh * ld), ("embed", "heads")),
            "wo": ((lh * ld, h), ("heads", "embed")),
            # the decay's and the output gate's low ranks
            "f_a": ((h, rank), ("embed", None)),
            "f_b": ((rank, lh * ld), (None, "heads")),
            "g_a": ((h, rank), ("embed", None)),
            "g_b": ((rank, lh * ld), (None, "heads")),
            "w_beta": ((h, lh), ("embed", None)),
        }
        norms["o_norm"] = ld
    elif kind.operator == "gated_delta_net":
        lh = cfg.linear_num_value_heads
        keys = lh * cfg.linear_key_head_dim
        values = lh * cfg.linear_value_head_dim
        matrices = {
            "wq": ((h, keys), ("embed", "heads")),
            "wk": ((h, keys), ("embed", "heads")),
            "wv": ((h, values), ("embed", "heads")),
            "wg": ((h, values), ("embed", "heads")),
            "wo": ((values, h), ("heads", "embed")),
            # the decay's and the step size's: a number a head
            "w_a": ((h, lh), ("embed", None)),
            "w_beta": ((h, lh), ("embed", None)),
        }
        norms["o_norm"] = cfg.linear_value_head_dim
    elif kind.operator == "lightning_attention":
        lh, ld = cfg.lightning_num_heads, cfg.lightning_head_dim
        matrices = {
            "wq": ((h, lh * ld), ("embed", "heads")),
            "wk": ((h, lh * ld), ("embed", "heads")),
            "wv": ((h, lh * ld), ("embed", "heads")),
            "wg": ((h, lh * ld), ("embed", "heads")),
            "wo": ((lh * ld, h), ("heads", "embed")),
        }
        norms.update(q_norm=ld, k_norm=ld, o_norm=ld)
    elif kind.operator == "latent_attention":
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        # q through its latent and a norm, or by one matrix
        matrices = {
            "wq_a": ((h, rq), ("embed", None)),
            "wq_b": ((rq, nh * (nope + rope)), (None, "heads")),
        } if rq else {
            "wq": ((h, nh * (nope + rope)), ("embed", "heads")),
        }
        matrices.update({
            # [c_kv | the one key of the further columns]
            "wkv_a": ((h, rkv + rope), ("embed", None)),
            # a head's [k_nope | v]
            "wkv_b": ((rkv, nh * (nope + vd)), (None, "heads")),
            "wo": ((nh * vd, h), ("heads", "embed")),
        })
        if rq:
            norms["q_a_norm"] = rq
        norms["kv_a_norm"] = rkv
    else:
        matrices = {
            "wq": ((h, nh * hd), ("embed", "heads")),
            "wk": ((h, nkv * hd), ("embed", "kv_heads")),
            "wv": ((h, nkv * hd), ("embed", "kv_heads")),
            "wo": ((nh * hd, h), ("heads", "embed")),
        }
        if cfg.attn_out_gate:
            matrices["wg"] = ((h, nh * hd), ("embed", "heads"))
        if cfg.qk_norm:
            norms.update(q_norm=nh * hd, k_norm=nkv * hd)
        elif cfg.qk_head_norm:
            norms.update(q_norm=hd, k_norm=hd)
    if kind.ffn == "experts":
        held, m = cfg.moe_experts_held, cfg.moe_intermediate_size
        # what a routed expert reads and writes: the stream, or the
        # latent between the two projections
        wide = cfg.moe_latent_size or h
        matrices.update({
            "router": ((h, cfg.num_experts), ("embed", None)),
            "w_gate": ((held, wide, m), ("expert", "embed", "mlp")),
            "w_up": ((held, wide, m), ("expert", "embed", "mlp")),
            "w_down": ((held, m, wide), ("expert", "mlp", "embed")),
        })
        if cfg.moe_latent_size:
            matrices.update({
                "w_latent_down": ((h, wide), ("embed", None)),
                "w_latent_up": ((wide, h), (None, "embed")),
            })
        if cfg.moe_shared_experts:
            ms = (cfg.moe_shared_expert_intermediate_size
                  or cfg.moe_shared_experts * m)
            matrices.update({
                "ws_gate": ((h, ms), ("embed", "mlp")),
                "ws_up": ((h, ms), ("embed", "mlp")),
                "ws_down": ((ms, h), ("mlp", "embed")),
            })
        if not cfg.moe_expert_gated:
            del matrices["w_gate"]
            matrices.pop("ws_gate", None)
    elif kind.ffn == "dense":
        m = cfg.intermediate_size
        matrices.update({
            "w_gate": ((h, m), ("embed", "mlp")),
            "w_up": ((h, m), ("embed", "mlp")),
            "w_down": ((m, h), ("mlp", "embed")),
        })
    leaves = {
        name: (shape, axes, shape[-2] ** -0.5)
        for name, (shape, axes) in matrices.items()
    }
    leaves.update({
        name: ((width,), ("norm",), None) for name, width in norms.items()
    })
    if kind.operator == "conv":
        taps = cfg.conv_L_cache
        leaves["conv_w"] = ((h, taps), ("mlp", None), taps ** -0.5)
    if kind.operator == "linear_attention":
        wide, taps = lh * ld, cfg.linear_conv_size
        for name in ("conv_q", "conv_k", "conv_v"):
            leaves[name] = ((wide, taps), ("heads", None), taps ** -0.5)
        leaves["g_bias"] = ((wide,), ("norm",), 0)
        # float32 vectors with draws of their own (``_DECAY_DRAWS``)
        leaves["A_log"] = ((lh,), ("norm",), "A_log")
        leaves["dt_bias"] = ((wide,), ("norm",), "dt_bias")
    if kind.operator == "gated_delta_net":
        taps = cfg.linear_conv_kernel_dim
        for name, wide in (("conv_q", keys), ("conv_k", keys),
                           ("conv_v", values)):
            leaves[name] = ((wide, taps), ("heads", None), taps ** -0.5)
        # float32 numbers a head with draws of their own
        # (``_DECAY_DRAWS``)
        leaves["A_log"] = ((lh,), ("norm",), "A_log")
        leaves["dt_bias"] = ((lh,), ("norm",), "dt_bias")
    if kind.operator == "state_space":
        taps, heads = cfg.conv_kernel, cfg.mamba_num_heads
        leaves["ssm_conv_w"] = ((conv, taps), ("mlp", None), taps ** -0.5)
        if cfg.use_conv_bias:
            leaves["ssm_conv_b"] = ((conv,), ("norm",), 0)
        # float32 vectors a head: the decay's two by draws of their
        # own (``_DECAY_DRAWS``), the skip ``D`` at one
        leaves["A_log"] = ((heads,), ("norm",), "A_log")
        leaves["dt_bias"] = ((heads,), ("norm",), "dt_bias")
        leaves["D"] = ((heads,), ("norm",), None)
    if kind.operator == "mamba":
        taps = cfg.mamba_d_conv
        leaves["mamba_conv_w"] = ((d, taps), ("mlp", None), taps ** -0.5)
        leaves["mamba_conv_b"] = ((d,), ("norm",), 0)
        # float32: the rates a channel and state at the family's
        # S4D-real start, the step's bias by the draw of its own, the
        # skip ``D`` at one
        leaves["A_log"] = ((d, n), ("mlp", None), "A_log_s4d")
        leaves["dt_bias"] = ((d,), ("norm",), "dt_bias")
        leaves["D"] = ((d,), ("norm",), None)
    if kind.ffn == "experts" and cfg.use_expert_bias:
        leaves["expert_bias"] = ((cfg.num_experts,), (None,), 0)
    return leaves


def _ssm_widths(cfg: LlamaConfig) -> Tuple[int, int]:
    """``(the mixer's inner columns, heads x head_dim; the columns
    [x | B | C] that pass its convolution)``."""
    inner = cfg.mamba_num_heads * cfg.mamba_head_dim
    return inner, inner + 2 * cfg.n_groups * cfg.ssm_state_size


#: which of ``jax.random.split(key, 8)`` draws a leaf; from 8 on the
#: key folded with the number (the eight stay what they were)
_DRAW = {"wq": 0, "wk": 1, "wv": 2, "wo": 3, "w_gate": 4, "w_up": 5,
         "w_down": 6, "router": 7, "conv_in": 0, "conv_w": 1,
         "conv_out": 3, "wq_a": 8, "wq_b": 9, "wkv_a": 10, "wkv_b": 11,
         "ws_gate": 12, "ws_up": 13, "ws_down": 14, "conv_q": 15,
         "conv_k": 16, "conv_v": 17, "f_a": 18, "f_b": 19, "g_a": 20,
         "g_b": 21, "w_beta": 22, "A_log": 23, "dt_bias": 24, "wg": 25,
         "ssm_in": 26, "ssm_out": 27, "ssm_conv_w": 28,
         "w_latent_down": 29, "w_latent_up": 30, "mamba_in": 31,
         "mamba_x": 32, "mamba_dt": 33, "mamba_out": 34, "mamba_conv_w": 35,
         "w_a": 36}


def _draw_A_log(key, shape):
    """``log`` of a head's decay rate, the rate uniform in [1, 16)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _draw_dt_bias(key, shape):
    """What ``softplus`` takes to a step ``dt`` drawn log-uniform in
    [0.001, 0.1]: ``dt + log(1 - exp(-dt))``."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)
    ))
    return dt + jnp.log(-jnp.expm1(-dt))


def _start_A_log(key, shape):
    """``log`` of a Mamba-1 channel's rates at the S4D-real start:
    state ``n`` of every channel decays at ``n + 1``. No draw."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)


#: the decay's leaves, kept in float32: how each is drawn
_DECAY_DRAWS = {"A_log": _draw_A_log, "dt_bias": _draw_dt_bias,
                "A_log_s4d": _start_A_log}


def _init_layers(key, cfg: LlamaConfig, kind: LayerKind, stack=()):
    """The leaves of layers of ``kind``, each with the leading dims
    ``stack``: the norms' scales and the selection bias in float32,
    the rest in the config's dtype."""
    ks = jax.random.split(key, 8)
    layers = {}
    for name, (shape, _, std) in _leaves(cfg, kind).items():
        if not std:  # a scale at one, a bias at zero
            layers[name] = jnp.full(
                stack + shape, 1.0 if std is None else 0.0, jnp.float32
            )
            continue
        draw = _DRAW[name]
        draw = ks[draw] if draw < 8 else jax.random.fold_in(key, draw)
        if std in _DECAY_DRAWS:
            layers[name] = _DECAY_DRAWS[std](draw, stack + shape)
            continue
        layers[name] = (
            jax.random.normal(draw, stack + shape, jnp.float32) * std
        ).astype(cfg.dtype)
    return layers


def _layer_axes(cfg: LlamaConfig, kind: LayerKind, stack=()):
    return {
        name: stack + axes
        for name, (_, axes, _) in _leaves(cfg, kind).items()
    }


def init_params(rng: jax.Array, cfg: LlamaConfig) -> Dict:
    """Initialize the parameter pytree. Block weights carry a leading
    layers dim (scan stacking): one stack ``blocks`` of like layers,
    or, where layers of several kinds own different leaves
    (``cfg.by_position``), the leading layers one by one in ``lead``
    and in ``period`` a stack ``[periods, ...]`` for each position of
    the scanned period. A tied head has no ``lm_head``. ``mtp`` holds
    the prediction modules past the stack: the norms of the two
    inputs, the merge ``eh_proj`` [2 hidden, hidden], a block of the
    last layer's kind (with ``mtp_hybrid_override_pattern`` a list, a
    block a sublayer) and a final norm of its own. A looped stack
    (``total_ut_steps`` above 1) has ``exit_gate``: the gate's weight
    ``w`` [hidden], drawn fan-in normal, and its bias ``b`` [1],
    float32 zeros, one pair for all passes."""
    h = cfg.hidden_size
    k_embed, k_blocks, k_out = jax.random.split(rng, 3)
    lead, period = cfg.layer_plan()
    params = {
        "embed": (
            jax.random.normal(
                k_embed, (cfg.vocab_size, h), dtype=jnp.float32
            ) * cfg.embed_init_std
        ).astype(cfg.dtype),
        "final_norm": jnp.ones((h,), jnp.float32),
    }
    if cfg.by_position:
        keys = jax.random.split(k_blocks, len(lead) + len(period))
        periods = (cfg.num_layers - len(lead)) // len(period)
        params["lead"] = [
            _init_layers(key, cfg, kind) for key, kind in zip(keys, lead)
        ]
        params["period"] = [
            _init_layers(key, cfg, kind, (periods,))
            for key, kind in zip(keys[len(lead):], period)
        ]
    else:
        params["blocks"] = _init_layers(
            k_blocks, cfg, period[0], (cfg.num_layers,)
        )
    if not cfg.tie_word_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_out, (h, cfg.vocab_size), jnp.float32)
            * (cfg.head_init_std or h ** -0.5)
        ).astype(cfg.dtype)
    if cfg.mtp_layers:
        k_merge, k_block = jax.random.split(jax.random.fold_in(rng, 3))
        params["mtp"] = [{
            "embed_norm": jnp.ones((h,), jnp.float32),
            "hidden_norm": jnp.ones((h,), jnp.float32),
            "eh_proj": (
                jax.random.normal(k_merge, (2 * h, h), jnp.float32)
                * (2 * h) ** -0.5
            ).astype(cfg.dtype),
            "block": _mtp_block(
                cfg, lambda i, kind: _init_layers(
                    jax.random.fold_in(k_block, i) if i else k_block,
                    cfg, kind)),
            "final_norm": jnp.ones((h,), jnp.float32),
        }]
    if cfg.total_ut_steps > 1:
        params["exit_gate"] = {
            "w": (
                jax.random.normal(
                    jax.random.fold_in(rng, 4), (h,), jnp.float32
                ) * h ** -0.5
            ).astype(cfg.dtype),
            "b": jnp.zeros((1,), jnp.float32),
        }
    return params


def _mtp_block(cfg: LlamaConfig, of):
    """A prediction module's ``block``: ``of(i, kind)`` of its one
    block, or with ``mtp_hybrid_override_pattern`` the list of it
    over the module's sublayers."""
    kinds = cfg.mtp_kinds()
    if cfg.mtp_hybrid_override_pattern is None:
        return of(0, kinds[0])
    return [of(i, kind) for i, kind in enumerate(kinds)]


def param_axes(cfg: LlamaConfig) -> Dict:
    """Logical-axes tree mirroring init_params (see parallel/sharding.py)."""
    lead, period = cfg.layer_plan()
    axes = {"embed": ("vocab", "embed"), "final_norm": ("norm",)}
    if cfg.by_position:
        axes["lead"] = [_layer_axes(cfg, kind) for kind in lead]
        axes["period"] = [
            _layer_axes(cfg, kind, ("layers",)) for kind in period
        ]
    else:
        axes["blocks"] = _layer_axes(cfg, period[0], ("layers",))
    if not cfg.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if cfg.mtp_layers:
        axes["mtp"] = [{
            "embed_norm": ("norm",), "hidden_norm": ("norm",),
            "eh_proj": ("mlp", "embed"),
            "block": _mtp_block(
                cfg, lambda _, kind: _layer_axes(cfg, kind)),
            "final_norm": ("norm",),
        }]
    if cfg.total_ut_steps > 1:
        axes["exit_gate"] = {"w": ("norm",), "b": (None,)}
    return axes


def frozen_params(cfg: LlamaConfig) -> Optional[Dict]:
    """A tree of bools mirroring init_params, True at the leaves an
    optimizer must neither update nor decay (the router's selection
    bias); None where there is no such leaf."""
    if not (cfg.num_experts > 0 and cfg.use_expert_bias):
        return None
    return jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key == "expert_bias",
        jax.eval_shape(lambda: init_params(jax.random.key(0), cfg)),
    )


def _layers_of_each_kind(cfg: LlamaConfig):
    """``[(kind, how many layers of it)]`` over the whole stack and
    the prediction modules' blocks."""
    lead, period = cfg.layer_plan()
    periods = (cfg.num_layers - len(lead)) // len(period)
    return [(kind, 1) for kind in lead] + [
        (kind, periods) for kind in period
    ] + cfg.mtp_layers * [(kind, 1) for kind in cfg.mtp_kinds()]


def operator_layers(cfg: LlamaConfig) -> Dict[str, int]:
    """``{operator: how many layers have it}`` over the stack and the
    prediction modules' blocks, the operators in the order of their
    first layer (a block of the feed-forward alone is ``"none"``)."""
    layers = {}
    for kind, n in _layers_of_each_kind(cfg):
        layers[kind.operator] = layers.get(kind.operator, 0) + n
    return layers


def param_count(cfg: LlamaConfig) -> int:
    def layer(kind):
        return sum(
            math.prod(shape) for shape, _, _ in _leaves(cfg, kind).values()
        )

    embeddings = 1 if cfg.tie_word_embeddings else 2
    h = cfg.hidden_size
    return (
        cfg.vocab_size * h * embeddings + h
        + sum(n * layer(kind) for kind, n in _layers_of_each_kind(cfg))
        + cfg.mtp_layers * (2 * h * h + 3 * h)
        + (h + 1 if cfg.total_ut_steps > 1 else 0)  # the exit gate
    )


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


def _rope_tables_of(cfg: LlamaConfig, seq_len: int):
    """``rope_tables`` at the config's width for the layers whose kind
    rotates; ``(None, None)``, and no table built, where none does."""
    if not any(kind.rope for kind, _ in _layers_of_each_kind(cfg)):
        return None, None
    return rope_tables(seq_len, cfg.rope_dim, cfg.rope_theta)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               interleaved: bool = False) -> jax.Array:
    """x: [batch, seq, heads, head_dim]; rotate the pairs of columns
    (i, i + half), or with ``interleaved`` the pairs (2i, 2i + 1),
    which then leave in the order (evens, odds): q and k permuted
    alike score the same (``DeepseekV3``'s
    ``apply_rotary_pos_emb_interleave`` leaves them so too).

    Computed in x's own dtype: the angles (cos/sin tables) are built in
    f32 and each output element is one mul-add of unit-magnitude
    factors, so bf16 rotation adds at most half-ulp noise PER ELEMENT
    (no accumulation chain) — while an f32 rope forces the q/k
    projections to materialize f32 copies to HBM. An earlier chip run,
    not reproduced, put the f32 rope fusion alone at 1.7% of device
    time for zero accuracy benefit."""
    if interleaved:
        pairs = x.reshape(*x.shape[:-1], -1, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
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


def _tie(constrain, held, weights, axes):
    """``(held, weights)`` as they came: nothing forward. In the
    backward pass the ``weights``' gradients, laid out as the weights
    are (``constrain`` to ``axes``, their logical axes), are complete
    before ``held``'s cotangent goes on: one ``optimization_barrier``
    around both.

    For a step that gathers its weights (ZeRO-3) that layout is the
    gradient's reduce-scatter, which the TPU's compiler runs as a ring
    of ``collective-permute``s through the weight's partial products,
    and whose last hop it leaves for the end of the layer loop's body,
    where nothing asks for the result sooner: all seven rings' last
    hops, 107 MB at Mistral-7B's widths, then stand in a row behind
    the body's last product (2.7 ms a layer of ``mistral-7b-l16.fsdp4``
    with no compute beside them, PERF.md section 6, PR 59). Tied to an
    activation's cotangent that the backward makes later, a ring has
    a deadline with a matmul group's compute before it. ``held`` is
    what a later part of the forward made (so an earlier part of the
    backward does not wait for it), the weights those used after
    it."""

    @jax.custom_vjp
    def tied(held, weights):
        return held, weights

    def backward(_, cotangents):
        held_ct, weights_ct = cotangents
        return jax.lax.optimization_barrier(
            (held_ct, jax.tree.map(constrain, weights_ct, axes))
        )

    tied.defvjp(lambda held, weights: ((held, weights), None), backward)
    return tied(held, weights)


def _pre_attn(cfg: LlamaConfig, x, layer_params, cos, sin,
              constrain=_free, kind=LayerKind()):
    """Block segment 1, up to the operator's call: the norm and, for
    attention, the q/k/v projections (``_latent_qkv`` for latent
    attention), the heads' norms and rope (none in a layer whose kind
    says so), for the convolution its input
    projection; and, where the router reads the block's input, its
    logits, which ``_post_attn`` is handed past the operator:
    ``(the operator's arguments, logits or None)``."""
    b, s, h = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = layer_params

    def logits():
        if (kind.ffn != "experts"
                or cfg.moe_router_input != "block_input"):
            return None
        from dlrover_tpu.parallel.moe import router_logits

        return router_logits(x, p["router"])

    if kind.operator == "none":  # a block of the feed-forward alone
        return (), logits()
    y = _ahead_of_branch(cfg, constrain(x, _RESIDUAL), p, "attn_norm")
    if kind.operator == "state_space":
        return _ssm_operands(cfg, y, p, constrain), logits()
    if kind.operator == "mamba":
        return _mamba_operands(cfg, y, p, constrain), logits()
    if kind.operator == "conv":
        with jax.named_scope("conv.in_proj"):
            bcu = constrain(y @ p["conv_in"], _MLP)
        return (bcu, p["conv_w"]), logits()
    if kind.operator == "latent_attention":
        return _latent_qkv(
            cfg, y, p, cos, sin, constrain, kind.rope
        ), logits()
    if kind.operator == "linear_attention":
        return _delta_rule_operands(cfg, y, p, constrain), logits()
    if kind.operator == "gated_delta_net":
        return _gdn_operands(cfg, y, p, constrain), logits()
    if kind.operator == "lightning_attention":
        return _lightning_operands(
            cfg, y, p, cos, sin, constrain, kind.rope
        ), logits()
    q, k = y @ p["wq"], y @ p["wk"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = constrain(q.reshape(b, s, nh, hd), _Q)
    k = constrain(k.reshape(b, s, nkv, hd), _KV)
    if cfg.qk_head_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    v = constrain((y @ p["wv"]).reshape(b, s, nkv, hd), _KV)
    if kind.rope:
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    if cfg.attn_out_gate:
        with jax.named_scope("attn.gate"):
            return (q, k, v, y @ p["wg"]), logits()
    return (q, k, v), logits()


def _ahead_of_branch(cfg: LlamaConfig, x, layer_params, norm: str):
    """What a branch reads of the stream ``x``: its RMSNorm by the
    leaf ``norm``, or, in a block normed on its branches' results
    alone (``post_norms`` "alone"), the stream itself."""
    if cfg.post_norms == "alone":
        return x
    return rms_norm(x, layer_params[norm], cfg.norm_eps)


def _ssm_operands(cfg: LlamaConfig, y, p, constrain=_free):
    """A Mamba-2 mixer's operands from the normed stream ``y``, in
    rows: ``([x | B | C] [b, s, heads x d + 2 groups x n] past its
    convolution, the step Delta [b, s, heads] and the rate A [heads]
    (negative) in float32, the skip D [heads], the gate's
    pre-activation z [b, s, heads x d], the grouped norm's scale)``.
    The scopes name every op:
    ``ssm.in_proj`` the one projection ``[z | x | B | C | dt]`` and
    its three slices, ``ssm.conv`` the convolution with its bias and
    ``silu`` (``ops/kda_conv.py``: on the TPU one Pallas pass each
    way), ``ssm.dt`` the step's softplus and the rate."""
    inner, conv = _ssm_widths(cfg)
    with jax.named_scope("ssm.in_proj"):
        z, xbc, dt = jnp.split(
            constrain(y @ p["ssm_in"], _MLP), [inner, inner + conv], axis=-1
        )
    with jax.named_scope("ssm.conv"):
        xbc = conv_silu_norm(
            xbc, p["ssm_conv_w"], bias=p.get("ssm_conv_b"))
    with jax.named_scope("ssm.dt"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        rate = -jnp.exp(p["A_log"])
    return xbc, dt, rate, p["D"], z, p["ssm_norm"]


def _mamba_operands(cfg: LlamaConfig, y, p, constrain=_free):
    """A Mamba-1 mixer's operands from the normed stream ``y``, in
    rows as ``ops/selective_scan.py selective_scan`` takes them: ``(x
    [b, s, d] past its convolution, the step Delta [b, s, d] in
    float32, B and C [b, s, n] past their norms, the rates A [d, n]
    (negative) in float32, the skip D [d], the gate's pre-activation z
    [b, s, d])``. The scopes name every op: ``mamba.in_proj`` the one
    projection ``[x | z]`` and its two slices, ``mamba.conv`` the
    convolution with its bias and ``silu`` (``ops/kda_conv.py``: on the
    TPU one Pallas pass each way), ``mamba.x_proj`` the second
    projection ``[dt | B | C]`` off the convolved ``x`` with the three
    norms, ``mamba.dt`` the low-rank step (its product kept in
    float32), its bias and softplus, and the rates."""
    d, n, rank = cfg.mamba_widths
    with jax.named_scope("mamba.in_proj"):
        x, z = jnp.split(constrain(y @ p["mamba_in"], _MLP), 2, axis=-1)
    with jax.named_scope("mamba.conv"):
        x = conv_silu_norm(
            x, p["mamba_conv_w"], bias=p["mamba_conv_b"])
    with jax.named_scope("mamba.x_proj"):
        dt, B, C = (
            rms_norm(a, p[f"mamba_{name}_norm"], cfg.norm_eps)
            for name, a in zip(("dt", "b", "c"), jnp.split(
                x @ p["mamba_x"], [rank, rank + n], axis=-1))
        )
    with jax.named_scope("mamba.dt"):
        delta = jax.nn.softplus(jnp.matmul(
            dt, p["mamba_dt"], preferred_element_type=jnp.float32,
        ) + p["dt_bias"])
        rates = -jnp.exp(p["A_log"])
    return x, delta, B, C, rates, p["D"], z


def _delta_rule_operands(cfg: LlamaConfig, y, p, constrain=_free):
    """The gated delta rule's operands from the normed stream ``y``,
    in rows, as the projections write them and the scan's kernels
    read them: ``(q, k, v [b, s, heads x d], g [b, s, heads x d]
    float32, beta [b, s, heads] float32, the output gate's
    pre-activation [b, s, heads x d])``. A head shows only inside
    ``ops/kda_conv.py conv_silu_norm``, for q's and k's l2 norm. The
    scopes name every op: ``kda.proj`` the three projections, the two
    low ranks and the step size's; ``kda.conv`` the convolutions with
    ``silu`` and the l2 norms (one operator, on the TPU one Pallas
    pass each way); ``kda.decay`` the log decay and the step size."""
    heads, d = cfg.linear_num_heads, cfg.linear_head_dim
    with jax.named_scope("kda.proj"):
        q, k, v = (constrain(y @ p[w], _MLP) for w in ("wq", "wk", "wv"))
        decay = (y @ p["f_a"]) @ p["f_b"]
        gate = (y @ p["g_a"]) @ p["g_b"]
        step = y @ p["w_beta"]
    with jax.named_scope("kda.conv"):
        q, k = (
            conv_silu_norm(x, p[w], l2_heads=heads)
            for x, w in ((q, "conv_q"), (k, "conv_k"))
        )
        v = conv_silu_norm(v, p["conv_v"])
    with jax.named_scope("kda.decay"):
        # a head's rate at each of its d columns: of the weights' size
        rate = jnp.repeat(jnp.exp(p["A_log"]), d)
        g = -rate * jax.nn.softplus(
            decay.astype(jnp.float32) + p["dt_bias"]
        )
        beta = jax.nn.sigmoid(step.astype(jnp.float32))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
    return q, k, v, g, beta, gate


def _gdn_operands(cfg: LlamaConfig, y, p, constrain=_free):
    """A Gated DeltaNet layer's operands from what its branch reads,
    ``y``, in rows, as the projections write them and the scan's
    kernels read them: ``(q, k [b, s, heads x dk], v [b, s, heads x
    dv], g and beta [b, s, heads] float32, the output gate's
    pre-activation [b, s, heads x dv])``. The log decay is a number a
    head and position and is never spread over a head's columns. The
    scopes name every op: ``gdn.proj`` the five projections and the
    two a head; ``gdn.conv`` the three convolutions with ``silu`` and
    q's and k's l2 norm a head (``ops/kda_conv.py``); ``gdn.decay``
    the log decay and the step size."""
    heads = cfg.linear_num_value_heads
    with jax.named_scope("gdn.proj"):
        q, k, v, gate = (
            constrain(y @ p[w], _MLP) for w in ("wq", "wk", "wv", "wg"))
        decay, step = y @ p["w_a"], y @ p["w_beta"]
    with jax.named_scope("gdn.conv"):
        q, k = (
            conv_silu_norm(x, p[w], l2_heads=heads)
            for x, w in ((q, "conv_q"), (k, "conv_k"))
        )
        v = conv_silu_norm(v, p["conv_v"])
    with jax.named_scope("gdn.decay"):
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
            decay.astype(jnp.float32) + p["dt_bias"])
        beta = jax.nn.sigmoid(step.astype(jnp.float32))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
    return q, k, v, g, beta, gate


def _lightning_operands(cfg: LlamaConfig, y, p, cos, sin, constrain=_free,
                        rotate: bool = True):
    """A lightning layer's operands from the normed stream ``y``, in
    rows [b, s, heads x d] as ``ops/ssd.py ssd_scan`` takes them:
    ``(q, k, v, the output gate's pre-activation)``. q and k each
    through an RMSNorm a head and, where the layer rotates, the rotary
    embedding; the scores' ``d ** -0.5`` rides on q's norm's scale, in
    float32, so that no rounding is its own. Scope
    ``lightning.proj``."""
    heads, d = cfg.lightning_num_heads, cfg.lightning_head_dim
    b, s, _ = y.shape
    with jax.named_scope("lightning.proj"):
        q, k, v, gate = (
            constrain(y @ p[w], _MLP) for w in ("wq", "wk", "wv", "wg"))
        q = rms_norm(
            q.reshape(b, s, heads, d), p["q_norm"] * d ** -0.5,
            cfg.norm_eps)
        k = rms_norm(k.reshape(b, s, heads, d), p["k_norm"], cfg.norm_eps)
        if rotate:
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    return q.reshape(b, s, -1), k.reshape(b, s, -1), v, gate


def _evens_then_odds(w):
    """The columns of a weight whose products are rotated, in the
    order ``apply_rope(..., interleaved=True)`` leaves a head's
    columns in: (0, 2, ..., 1, 3, ...). Taken on the weight, a few
    megabytes a layer, the product comes out in that order and the
    rotation is the half-split form with no shuffle of the
    activations; each column is the dot product it was."""
    return jnp.concatenate([w[..., 0::2], w[..., 1::2]], axis=-1)


def _latent_qkv(cfg: LlamaConfig, y, p, cos, sin, constrain=_free,
                rotate: bool = True):
    """Latent attention's operands from the normed stream ``y``, in
    the parts their products make and ``flash_attention`` takes
    (``_latent_up``): q through its low-rank projection and an
    RMSNorm, or with ``q_lora_rank`` None straight from ``y`` by the
    one matrix ``wq``; the first part of every head's k, and v,
    through another low-rank projection; the further part of k
    straight from ``y``, one head's, which every head shares.
    ``rotate``: whether the layer turns the further parts by position
    (its kind's ``rope``)."""
    rank, wkv_a = cfg.kv_lora_rank, p["wkv_a"]
    if cfg.rope_interleave and rotate:
        wkv_a = jnp.concatenate(
            [wkv_a[:, :rank], _evens_then_odds(wkv_a[:, rank:])], axis=-1
        )
    if cfg.q_lora_rank:
        with jax.named_scope("mla.q_down"):
            c_q = rms_norm(y @ p["wq_a"], p["q_a_norm"], cfg.norm_eps)
    else:
        c_q = y
    with jax.named_scope("mla.kv_down"):
        c_kv, k_rope = jnp.split(y @ wkv_a, [rank], axis=-1)
        c_kv = rms_norm(c_kv, p["kv_a_norm"], cfg.norm_eps)
    return _latent_up(
        cfg, c_q, c_kv, k_rope, p, cos, sin, constrain, rotate
    )


def _latent_up(cfg: LlamaConfig, c_q, c_kv, k_rope, p, cos, sin,
               constrain=_free, rotate: bool = True):
    """``(q_nope, k_nope, v, q_rope, k_rope)`` from what q is
    multiplied out of (``c_q``: its normed latent, or with
    ``q_lora_rank`` None the normed stream itself), the normed latent
    ``c_kv`` and the one key ``k_rope`` [b, s, rope] as its product
    made it: a head's first part of q and k [b, s, heads, nope], v [b,
    s, heads, v_head_dim], the further part of its q [b, s, heads,
    rope] and the key of that width that every head shares [b, s, 1,
    rope], both rotated where ``rotate`` says so. Each part is a
    product of its own on static columns of ``wq_b`` (``wq``) and
    ``wkv_b``: no [b, s, heads, nope + rope] array is built, split or
    shuffled, and the one key is never copied to a head. The scores
    are over a head's whole q and k, so attention's own default scale
    is ``(nope + rope) ** -0.5``. With ``rope_interleave`` the rotated
    columns, q's here and k's as they come, are in ``apply_rope``'s
    (evens, odds) order, which scores as the source's order does.
    q's two products stand under ``mla.up`` beside k's and v's, or,
    where one matrix makes q from the stream, under ``mla.q``."""
    nh, nope = cfg.num_heads, cfg.qk_nope_head_dim
    wq = p["wq_b"] if cfg.q_lora_rank else p["wq"]
    wq = wq.reshape(-1, nh, nope + cfg.qk_rope_head_dim)
    wkv_b = p["wkv_b"].reshape(-1, nh, nope + cfg.v_head_dim)
    wq_rope = wq[..., nope:]
    if cfg.rope_interleave and rotate:
        wq_rope = _evens_then_odds(wq_rope)

    def up(c, w, axes):
        return constrain(jnp.einsum("bsr,rhd->bshd", c, w), axes)

    def turned(x):
        return apply_rope(x, cos, sin) if rotate else x

    with jax.named_scope("mla.up" if cfg.q_lora_rank else "mla.q"):
        q_nope = up(c_q, wq[..., :nope], _Q)
        q_rope = turned(up(c_q, wq_rope, _Q))
    with jax.named_scope("mla.up"):
        k_nope = up(c_kv, wkv_b[..., :nope], _KV)
        v = up(c_kv, wkv_b[..., nope:], _KV)
        k_rope = turned(k_rope[:, :, None, :])
    return q_nope, k_nope, v, q_rope, k_rope


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
            first_held=cfg.moe_first_expert_held, gate=cfg.moe_gate,
            norm_eps=cfg.moe_topk_norm_eps,
            scaling=cfg.moe_routed_scaling, **routing
        )
    if (cfg.moe_experts_held != cfg.num_experts
            or cfg.moe_router_input != "post_attn_norm"
            or cfg.moe_expert_act != "silu"
            or cfg.moe_gate != "softmax" or cfg.use_expert_bias
            or cfg.moe_topk_norm_eps is not None
            or cfg.moe_routed_scaling != 1.0 or cfg.moe_shared_experts
            or not cfg.moe_expert_gated or cfg.moe_latent_size
            or cfg.hybrid_override_pattern is not None):
        raise ValueError(
            "a share of the experts held on one device "
            "(moe_experts_held), a router on the block's input, a "
            "relu gate, a sigmoid router and its selection bias, a "
            "factor on the routing weights, a shared expert, experts "
            "without a gate or in a latent, and a stack of one-branch "
            "blocks (whose state-space scan takes a whole sequence) "
            "are the dropless path's, on one device: over an 'expert' "
            "mesh axis larger than one they are refused (experts "
            "over chips: ROADMAP B9)"
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


def _operator_out(x, out, layer_params, kind: LayerKind,
                  norm_eps: float = 1e-5):
    """The operator's result through its output projection; with
    the gate's logits beside it (``_operator_of``), through its gate
    first, and linear attention's through the heads' norm at
    ``norm_eps``."""
    b, s, _ = x.shape
    p = layer_params
    if kind.operator == "state_space":
        with jax.named_scope("ssm.out_proj"):
            return out @ p["ssm_out"]
    if kind.operator == "mamba":
        with jax.named_scope("mamba.out_proj"):
            return out @ p["mamba_out"]
    if kind.operator == "conv":
        with jax.named_scope("conv.out_proj"):
            return out @ p["conv_out"]
    if kind.operator == "linear_attention":
        # an RMSNorm a head with one learned scale, a sigmoid gate with
        # a learned bias: rows in, rows to ``wo``
        with jax.named_scope("kda.out"):
            return head_norm_gate(
                *out, p["o_norm"], p["g_bias"], norm_eps) @ p["wo"]
    if kind.operator == "gated_delta_net":
        # an RMSNorm a head with one learned scale, then ``silu`` of
        # the gate's pre-activation: rows in, rows to ``wo``
        with jax.named_scope("gdn.out"):
            return head_norm_silu(*out, p["o_norm"], norm_eps) @ p["wo"]
    if kind.operator == "lightning_attention":
        # an RMSNorm a head with one learned scale, a sigmoid gate
        with jax.named_scope("lightning.out"):
            return head_norm_gate(
                *out, p["o_norm"], None, norm_eps) @ p["wo"]
    if isinstance(out, tuple):  # full attention and its gate's logits
        with jax.named_scope("attn.gate"):
            out, gate = out
            out = out.reshape(b, s, -1) * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(out.dtype)
    return out.reshape(b, s, -1) @ p["wo"]


def _past_operator(cfg: LlamaConfig, x, out, layer_params,
                   kind: LayerKind):
    """The residual stream past the operator: ``x`` plus the
    operator's result ``out`` through its output projection and, with
    ``post_norms``, an RMSNorm (scope ``norm.post_attn``); ``x``
    itself past no operator."""
    if kind.operator == "none":
        return x
    branch = _operator_out(x, out, layer_params, kind, cfg.norm_eps)
    if cfg.post_norms:
        with jax.named_scope("norm.post_attn"):
            branch = rms_norm(
                branch, layer_params["post_attn_norm"], cfg.norm_eps
            )
    return x + _scaled_branch(cfg, branch)


def _scaled_branch(cfg: LlamaConfig, branch):
    """A branch's result as it joins the stream: times ``scale_depth
    / sqrt(scale_depth_layers)`` where the config has the factor
    (scope ``branch.scale``)."""
    if cfg.branch_scale is None:
        return branch
    with jax.named_scope("branch.scale"):
        return _times(branch, cfg.branch_scale)


def _post_attn(cfg: LlamaConfig, x, out, layer_params,
               router_logits=None, constrain=_free, expert_parallel=False,
               kind=LayerKind(), tie=None):
    """Block segment 2, from the operator's result ``out``: output
    projection + residual + MLP, ``(x, aux, counts)``.
    ``router_logits``: ``_pre_attn``'s, where the router reads the
    block's input. ``counts``: where a rule moves the selection bias
    (``moe_bias_update_rate``) the assignments each of the router's
    experts received, int32 [experts] (``dropless_moe_mlp``); None
    without one and for a dense MLP. ``tie``: ``_block``'s; here a
    dense MLP's ``w_down``, whose gradient is the backward's first,
    is due when the gate's and the up's backward are through."""
    p = layer_params
    x = constrain(_past_operator(cfg, x, out, p, kind), _RESIDUAL)
    counts = None
    if kind.ffn == "none":  # a block of the operator alone
        return x, jnp.zeros((), jnp.float32), counts
    y = _ahead_of_branch(cfg, x, p, "mlp_norm")
    if kind.ffn == "experts":
        mlp = _expert_mlp(cfg, expert_parallel)
        if router_logits is not None:
            mlp = partial(mlp, logits=router_logits)
        if cfg.use_expert_bias:
            mlp = partial(mlp, bias=p["expert_bias"])
        if cfg.moe_shared_experts:
            mlp = partial(
                mlp, shared=(p.get("ws_gate"), p["ws_up"], p["ws_down"])
            )
        if cfg.moe_latent_size:
            mlp = partial(
                mlp, latent=(p["w_latent_down"], p["w_latent_up"])
            )
        # experts without a gate matrix have no ``w_gate``
        experts = (p.get("w_gate"), p["w_up"], p["w_down"])
        if cfg.moe_bias_update_rate:
            out, aux, counts = mlp(y, p["router"], *experts, count=True)
        else:
            out, aux = mlp(y, p["router"], *experts)
    else:
        w_down = p["w_down"]
        if tie is not None:
            y, w_down = tie(
                y, w_down, _layer_axes(cfg, kind)["w_down"]
            )
        gate = jax.nn.silu(constrain(y @ p["w_gate"], _MLP))
        up = constrain(y @ p["w_up"], _MLP)
        out, aux = (gate * up) @ w_down, jnp.zeros((), jnp.float32)
    if cfg.post_norms:
        with jax.named_scope("norm.post_mlp"):
            out = rms_norm(out, p["post_mlp_norm"], cfg.norm_eps)
    return constrain(x + _scaled_branch(cfg, out), _RESIDUAL), aux, counts


def _block(cfg: LlamaConfig, x, layer_params, cos, sin, operate,
           constrain=_free, expert_parallel=False, kind=LayerKind(),
           tie=None):
    """One decoder block of ``kind`` around its operator's call
    ``operate`` (``_operator_of``). x: [batch, seq, hidden]. Returns
    (x, aux_loss, counts) where aux_loss is the MoE balance loss (0
    for dense) and counts ``_post_attn``'s. ``tie(held, weights,
    axes)``: ``_tie`` where the step gathers its weights (None
    elsewhere, and nothing more is traced): the gradients of what
    ``_post_attn`` reads are due when the operator's backward is
    through."""
    operands, logits = _pre_attn(
        cfg, x, layer_params, cos, sin, constrain, kind
    )
    if tie is not None:
        operands, layer_params = tie(
            operands, layer_params, _layer_axes(cfg, kind)
        )
    return _post_attn(
        cfg, x, operate(*operands), layer_params, logits, constrain,
        expert_parallel, kind, tie,
    )


def _operator_of(cfg: LlamaConfig, attn_fn, kind: LayerKind):
    """The call at a layer of ``kind``'s heart, on what ``_pre_attn``
    hands it: ``attn_fn`` on q, k, v, or the gated short convolution
    on its projection and taps. A config with a layer pattern names
    the kinds of call, ``attn.full``, ``attn.window`` and
    ``conv.mix``, in their device ops' ``op_name``, and hands a
    windowed layer's window to ``attn_fn`` (which has to take it);
    latent attention's call is ``attn.latent``, and hands ``attn_fn``
    the further parts (rotated where the layer rotates) as ``q_rope``
    and ``k_rope`` (which it has to take, as ``flash_attention`` and
    ``mha_reference`` do). Linear
    attention's call is ``kda.scan``, the gated delta rule; it hands
    the output gate's logits on beside its result, as attention does
    with ``attn_out_gate``, for ``_operator_out``; a Gated DeltaNet
    layer's is ``gdn.scan``, the same rule with one decay a head, and
    hands its gate's logits on the same way. A Mamba-2 mixer's
    is ``ssm.scan`` and, on its result, ``ssm.gate_norm``; a block
    of the feed-forward alone calls nothing. Sparse attention's is
    ``sparse.compress``, ``sparse.select`` and ``sparse.attn``
    (ops/sparse_attention.py; ``attn_fn`` under ``attn.full`` on a
    sequence within ``sparse_dense_len``), a lightning layer's
    ``lightning.scan``, the state-space scan's own entry; a Mamba-1
    mixer's ``mamba.scan`` (ops/selective_scan.py) and, on its result,
    ``mamba.gate``."""
    if kind.operator == "none":
        return lambda: None
    if kind.operator == "state_space":
        inner, _ = _ssm_widths(cfg)
        groups = cfg.n_groups

        def scan(xbc, dt, rate, skip, z, scale):
            with jax.named_scope("ssm.scan"):
                x, b, c = jnp.split(
                    xbc, [inner, inner + groups * cfg.ssm_state_size],
                    axis=-1)
                o = ssd_scan(
                    x, b, c, dt, rate, skip, cfg.mamba_num_heads, groups,
                    cfg.chunk_size,
                )
            # the gate, then an RMSNorm over each group's columns
            with jax.named_scope("ssm.gate_norm"):
                return gated_group_norm(o, z, scale, groups, cfg.norm_eps)

        return scan
    if kind.operator == "mamba":

        def selective(x, delta, B, C, rates, skip, z):
            with jax.named_scope("mamba.scan"):
                o = selective_scan(x, delta, B, C, rates, skip)
            # the gate, in float32 and rounded once; no norm follows
            with jax.named_scope("mamba.gate"):
                return (o.astype(jnp.float32) * jax.nn.silu(
                    z.astype(jnp.float32))).astype(o.dtype)

        return selective
    if kind.operator == "conv":

        def mix(bcu, w):
            with jax.named_scope("conv.mix"):
                return gated_short_conv(bcu, w)

        return mix
    if kind.operator == "linear_attention":

        def scan(q, k, v, g, beta, gate):
            with jax.named_scope("kda.scan"):
                return gated_delta_rule_rows(
                    q, k, v, g, beta, cfg.linear_num_heads
                ), gate

        return scan
    if kind.operator == "gated_delta_net":

        def scan_a_head(q, k, v, g, beta, gate):
            with jax.named_scope("gdn.scan"):
                return gated_delta_rule_rows(
                    q, k, v, g, beta, cfg.linear_num_value_heads
                ), gate

        return scan_a_head
    if kind.operator == "lightning_attention":
        heads = cfg.lightning_num_heads

        def lightning(q, k, v, gate):
            # the state-space scan with one head a group: x = v, B =
            # k, C = q (scaled), a step of one, the rate -m_h, no skip
            with jax.named_scope("lightning.scan"):
                return ssd_scan(
                    v, k, q, jnp.ones((*v.shape[:2], heads), jnp.float32),
                    -cfg.lightning_decay(), jnp.zeros((heads,), jnp.float32),
                    heads, heads,
                ), gate

        return lightning
    if kind.operator == "sparse_attention":

        def selected_keys(q, k, v):
            if q.shape[1] <= cfg.sparse_dense_len:
                with jax.named_scope("attn.full"):
                    return attn_fn(q, k, v)
            kernel, stride = cfg.sparse_kernel_size, cfg.sparse_kernel_stride
            with jax.named_scope("sparse.compress"):
                compressed = compress_keys(k, kernel, stride)
            with jax.named_scope("sparse.select"):
                selected = select_blocks(
                    q, compressed, block=cfg.sparse_block_size,
                    kernel=kernel, stride=stride, topk=cfg.sparse_topk,
                    window=cfg.sparse_window_size,
                    init_blocks=cfg.sparse_init_blocks,
                )
            with jax.named_scope("sparse.attn"):
                return selected_attention(q, k, v, selected)

        def attend_selected(q, k, v, *gate):
            # the gate's logits, where the config has the gate, go on
            # beside the result (``_operator_out``)
            out = selected_keys(q, k, v)
            return (out, *gate) if gate else out

        return attend_selected
    if kind.operator == "latent_attention":

        def attend_latent(q, k, v, q_rope, k_rope):
            with jax.named_scope("attn.latent"):
                return attn_fn(q, k, v, q_rope=q_rope, k_rope=k_rope)

        return attend_latent
    def scoped(q, k, v):
        if kind.window is None:
            with jax.named_scope("attn.full"):
                return attn_fn(q, k, v)
        with jax.named_scope("attn.window"):
            return attn_fn(q, k, v, window=kind.window)

    attend = scoped
    if (cfg.sliding_window_layout is None and cfg.layer_types is None
            and cfg.hybrid_override_pattern is None):
        attend = attn_fn
    if cfg.attn_out_gate:
        return lambda q, k, v, gate: (attend(q, k, v), gate)
    return attend


def _scan_layers(layers, carry, blocks):
    """``carry`` through every layer, a period of the layer pattern a
    scan step: ``layers`` holds one ``layer(carry, layer_params) ->
    (carry, out)`` for each layer of a period (``layer_plan()``),
    ``blocks`` the parameters as they are kept: one stack ``[layers,
    ...]`` of like layers, or a list with a stack ``[periods, ...]``
    of its own leaves for each position of the period. Returns
    ``(carry, outs stacked [layers, ...])``. With one stack and one
    kind of layer this is the plain scan over ``blocks``."""
    period = len(layers)
    if isinstance(blocks, dict):
        if period == 1:
            return jax.lax.scan(layers[0], carry, blocks)
        per_period = jax.tree.map(
            lambda a: a.reshape(-1, period, *a.shape[1:]), blocks
        )

        def position(period_params, i):
            return jax.tree.map(lambda a: a[i], period_params)
    else:
        per_period = blocks

        def position(period_params, i):
            return period_params[i]

    def body(carry, period_params):
        outs = []
        for i, layer in enumerate(layers):
            carry, out = layer(carry, position(period_params, i))
            outs.append(out)
        # a layer with nothing to hand out (a block of another
        # branch) is left out of the stack
        outs = [out for out in outs if out is not None]
        if not outs:
            return carry, None
        return carry, jax.tree.map(lambda *o: jnp.stack(o), *outs)

    carry, outs = jax.lax.scan(body, carry, per_period)
    if outs is not None:
        outs = jax.tree.map(
            lambda a: a.reshape(-1, *a.shape[2:]), outs
        )
    return carry, outs


def _through_layers(cfg: LlamaConfig, layer_of, carry, params):
    """``carry`` through the whole stack: the leading layers one by
    one, then the periods under ``_scan_layers``; ``layer_of(kind)``
    makes a layer. Returns ``(carry, the scanned layers' outs)``."""
    lead, period = cfg.layer_plan()
    for kind, layer_params in zip(lead, params.get("lead", ())):
        carry, _ = layer_of(kind)(carry, layer_params)
    return _scan_layers(
        [layer_of(kind) for kind in period], carry,
        params["period" if cfg.by_position else "blocks"],
    )


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


def _embed(params, tokens, cfg: LlamaConfig):
    """The embedding's rows of ``tokens``; with ``mup_enabled`` times
    ``sqrt(hidden_size)``, in their own dtype (scope ``embed.mup``)."""
    x = params["embed"][tokens]
    if cfg.mup_enabled:
        with jax.named_scope("embed.mup"):
            x = x * jnp.asarray(math.sqrt(cfg.hidden_size), x.dtype)
    if cfg.scale_emb != 1.0:
        with jax.named_scope("embed.scale"):
            x = _times(x, cfg.scale_emb)
    return x


def _times(x, factor: float):
    """``x`` times a constant, the product in float32 and rounded
    once: a factor that ``x``'s dtype does not hold (1.4 / sqrt(32))
    is not rounded to it first."""
    return (x.astype(jnp.float32) * factor).astype(x.dtype)


def _head_input(x, params, cfg: LlamaConfig):
    """What the head reads of the stream out of the last layer: its
    final RMSNorm, with ``dim_model_base`` over ``hidden_size /
    dim_model_base`` (scope ``head.scale``)."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.dim_model_base is not None:
        with jax.named_scope("head.scale"):
            x = _times(x, cfg.dim_model_base / cfg.hidden_size)
    return x


def _run_stack(params, tokens, cfg: LlamaConfig, attn_fn=None,
               constrain=None, expert_parallel: bool = False,
               gathered_weights: bool = False):
    """``(the residual stream out of the last layer, before the final
    norm; the MoE aux loss; layer_of)``: ``layer_of(kind)`` makes one
    more layer of ``kind`` under the config's remat policy, for a
    prediction module past the stack. The arguments are
    ``hidden_states``'. Last, where a rule moves the selection bias
    (``moe_bias_update_rate``; else None), the assignments each
    expert received in each scanned layer, int32 [layers, experts],
    as this one forward pass selected: a layer's body hands them out
    beside its carry, so a remat's second forward counts nothing
    again.

    ``constrain(x, logical_axes) -> x`` pins the layout of the
    activations between the matmuls (the residual stream, q/k/v, the
    two MLP products) to the strategy's rule table, so that a
    partitioner faced with ``x[batch/n, seq, embed] @ w[embed/n, mlp]``
    gathers the weight and leaves the activation where it is. None
    leaves every layout to the compiler. ``gathered_weights``:
    ``next_token_loss``'."""
    x, layer_of, constrain = _stack_entry(
        params, tokens, cfg, attn_fn, constrain, expert_parallel,
        gathered_weights,
    )
    (x, aux), counts = _through_layers(
        cfg, layer_of, (x, jnp.zeros((), jnp.float32)), params
    )
    return constrain(x, _RESIDUAL), aux, layer_of, counts


def _stack_entry(params, tokens, cfg: LlamaConfig, attn_fn, constrain,
                 expert_parallel, gathered_weights=False):
    """``(the embedded tokens, layer_of, constrain)``: what enters the
    stack and what makes its layers (``_run_stack``), with
    ``constrain`` as it is run (``_free`` for None).
    ``gathered_weights``: ``next_token_loss``'; the layers then hold
    ``_tie``s."""
    if attn_fn is None:
        attn_fn = partial(flash_attention, causal=True)
    if constrain is None:
        constrain = _free
    tie = partial(_tie, constrain) if gathered_weights else None
    s = tokens.shape[1]
    cos, sin = _rope_tables_of(cfg, s)
    x = constrain(_embed(params, tokens, cfg), _RESIDUAL)

    def layer_of(kind):
        """One layer of ``kind`` under the config's remat policy."""
        operate = _operator_of(cfg, attn_fn, kind)

        def body(carry, layer_params):
            x, aux_sum = carry
            x, aux, counts = _block(
                cfg, x, layer_params, cos, sin, operate, constrain,
                expert_parallel, kind, tie,
            )
            return (x, aux_sum + aux), counts

        if cfg.remat == "dots_attn_out":
            # "dots" remat on the segments AROUND the operator, with
            # the operator's call OUTSIDE any checkpoint: its
            # custom_vjp residuals (attention's q, k, v, o, lse) are
            # then kept like ordinary activations, so the backward
            # pass never re-runs the forward kernel (under plain
            # "dots" the re-fwd is ~7% of the step). Costs the saved
            # residuals' HBM (~q+k+v+o+lse per layer).
            policy = _dots_policy(cfg)
            pre = jax.checkpoint(
                partial(_pre_attn, cfg, constrain=constrain, kind=kind),
                policy=policy,
            )
            post = jax.checkpoint(
                partial(_post_attn, cfg, constrain=constrain,
                        expert_parallel=expert_parallel, kind=kind,
                        tie=tie),
                policy=policy,
            )

            def body(carry, layer_params):  # noqa: F811
                x, aux_sum = carry
                operands, logits = pre(x, layer_params, cos, sin)
                if tie is not None:
                    operands, layer_params = tie(
                        operands, layer_params, _layer_axes(cfg, kind)
                    )
                out = operate(*operands)
                x, aux, counts = post(x, out, layer_params, logits)
                return (x, aux_sum + aux), counts

        elif cfg.remat == "dots":
            body = jax.checkpoint(body, policy=_dots_policy(cfg))
        elif cfg.remat == "minimal":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable
            )
        return body

    return x, layer_of, constrain


def _run_loop(params, tokens, cfg: LlamaConfig, attn_fn=None,
              constrain=None, gathered_weights: bool = False):
    """A looped stack's ``(states, gate logits)``: the normed state
    out of each of the ``total_ut_steps`` passes, [passes, batch, seq,
    hidden], and from them the exit gate's logits, float32 [passes,
    batch, seq]. A pass (scope ``loop.pass``) is the whole stack and
    then the final norm, on the normed state of the pass before (the
    first on the embedding), with the one set of weights.

    The passes are calls in a Python loop, not a ``lax.scan`` around
    ``_through_layers``: on the chip at 16 layers x 4 passes of the
    2048-wide model the unrolled step took 2.2555 s against the
    scan's 2.2996 (1.9%), for 26.7 s against 18.9 s of compiling and
    14.87 against 14.12 GB planned (PERF.md section 6, PR 58). The
    step's program holds a layer body a pass; under remat ``minimal``
    what is kept for the backward is a layer's input a layer and pass
    either way.

    A weight's gradient is a sum over the passes, which jax adds in
    the cotangents' dtype, the weight's own: bfloat16 in the cells. A
    float32 accumulator would stand beside every layer's weights for
    the length of the backward (4 bytes a parameter where the gradient
    itself is 2: 3.3 GB at 16 layers of 51.4 M, two layers' worth of
    depth), to save three roundings of 2 ** -9 ahead of the one the
    gradient gets anyway when it is handed to the optimizer in
    bfloat16; ``tests/yardstick/test_yardstick_ouro.py`` holds the sum
    against the float32 reference's gradient, leaf by leaf, in float32
    and in bfloat16."""
    x, layer_of, constrain = _stack_entry(
        params, tokens, cfg, attn_fn, constrain, False, gathered_weights
    )
    states = []
    for _ in range(cfg.total_ut_steps):
        with jax.named_scope("loop.pass"):
            (x, _), _ = _through_layers(
                cfg, layer_of, (x, jnp.zeros((), jnp.float32)), params
            )
            x = rms_norm(
                constrain(x, _RESIDUAL), params["final_norm"],
                cfg.norm_eps,
            )
        states.append(x)
    states = jnp.stack(states)
    with jax.named_scope("loop.exit_gate"):
        gate = params["exit_gate"]
        logits = jnp.einsum(
            "tbsh,h->tbs", states, gate["w"],
            preferred_element_type=jnp.float32,
        ) + gate["b"]
    return states, logits


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
    mesh axis (the trainer says so from its mesh). A looped stack's
    are its last pass's (no early exit: there is no decode path)."""
    if cfg.total_ut_steps > 1:
        states, _ = _run_loop(params, tokens, cfg, attn_fn, constrain)
        return states[-1], jnp.zeros((), jnp.float32)
    x, aux, _, _ = _run_stack(
        params, tokens, cfg, attn_fn, constrain, expert_parallel
    )
    return _head_input(x, params, cfg), aux


def _head(params: Dict, cfg: LlamaConfig) -> jax.Array:
    """The output head [hidden, vocab]: its own leaf, or the
    embedding's rows where the two are tied."""
    if cfg.tie_word_embeddings:
        return params["embed"].T
    return params["lm_head"]


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
    logits = (x @ _head(params, cfg)).astype(jnp.float32)
    if return_aux:
        return logits, aux
    return logits


def _position_nll(logits: jax.Array, targets: jax.Array) -> Tuple[
        jax.Array, jax.Array]:
    """(nll a position, times the mask; the mask), both float32 in
    ``targets``' shape. targets < 0 mask positions out."""
    mask = (targets >= 0).astype(jnp.float32)
    safe_targets = jnp.maximum(targets, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, safe_targets[..., None], axis=-1
    )[..., 0]
    return nll * mask, mask


def _masked_nll(logits: jax.Array, targets: jax.Array) -> Tuple[
        jax.Array, jax.Array]:
    """(sum of masked nll, mask count)."""
    nll, mask = _position_nll(logits, targets)
    return jnp.sum(nll), jnp.sum(mask)


@jax.custom_vjp
def _head_nll(x: jax.Array, head: jax.Array, targets: jax.Array) -> Tuple[
        jax.Array, jax.Array]:
    """``_position_nll((x @ head).astype(float32), targets)`` of the
    normed states ``x`` [..., hidden] through ``head`` [hidden,
    vocab], with a backward rule of its own: the logits are kept
    once, in the product's dtype (bfloat16 on the chip: the float32
    copy held no bit that they do not), beside a float32 row ``lse``,
    and the backward forms their gradient in one pass over them,
    rounded to their dtype where the plain rule's transpose rounds it
    (the chip's compiler makes that pass inside both gradient
    products and writes it nowhere: PERF.md section 5, PR 63).
    Nothing of [tokens, vocab] is float32 in memory, and the target's
    logit is read, and its gradient placed, by a comparison: no
    gather, no zeros, no scatter. Every loss goes through here;
    ``forward()`` keeps the plain product for callers that want
    logits. Scope ``loss.head``."""
    return _head_nll_fwd(x, head, targets)[0]


def _head_nll_fwd(x, head, targets):
    with jax.named_scope("loss.head"):
        logits = x @ head
        wide = logits.astype(jnp.float32)  # inside the reductions only
        top = jnp.max(wide, axis=-1)
        lse = top + jnp.log(jnp.sum(jnp.exp(wide - top[..., None]), axis=-1))
        hit = _is_target(logits.shape, targets)
        mask = (targets >= 0).astype(jnp.float32)
        nll = (lse - jnp.sum(jnp.where(hit, wide, 0.0), axis=-1)) * mask
    return (nll, mask), (x, head, targets, logits, lse)


def _head_nll_bwd(kept, cts):
    x, head, targets, logits, lse = kept
    with jax.named_scope("loss.head"):
        scale = cts[0] * (targets >= 0)
        p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
        g = ((p - _is_target(logits.shape, targets)) * scale[..., None]
             ).astype(logits.dtype)
        dx = (g @ head.T).astype(x.dtype)
        rows = tuple(range(x.ndim - 1))
        dhead = jnp.tensordot(x, g, (rows, rows)).astype(head.dtype)
    return dx, dhead, None


_head_nll.defvjp(_head_nll_fwd, _head_nll_bwd)


def _is_target(shape, targets: jax.Array) -> jax.Array:
    """Bool ``shape`` [..., vocab]: the column that is the row's
    target; none in a row whose target is < 0."""
    columns = jax.lax.broadcasted_iota(targets.dtype, shape, len(shape) - 1)
    return columns == targets[..., None]


def _in_chunks(x: jax.Array, targets: jax.Array, chunk: int):
    """``x`` [..., h] and ``targets`` as rows of ``chunk`` tokens,
    [chunks, chunk, h] and [chunks, chunk]."""
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
    return xf.reshape(n // chunk, chunk, h), tf.reshape(n // chunk, chunk)


def _chunked_ce(x: jax.Array, lm_head: jax.Array, targets: jax.Array,
                chunk: int) -> Tuple[jax.Array, jax.Array]:
    """Cross entropy without materializing full [tokens, vocab] logits:
    a rematerialized scan over token chunks — each chunk's logits and
    log-softmax are recomputed in the backward pass, so peak memory is
    one [chunk, vocab] block instead of [batch*seq, vocab]."""
    xc, tc = _in_chunks(x, targets, chunk)

    def body(carry, inp):
        nll_sum, cnt = carry
        xs, ts = inp
        s, c = map(jnp.sum, _head_nll(xs, lm_head, ts))
        return (nll_sum + s, cnt + c), None

    (nll_sum, cnt), _ = jax.lax.scan(
        jax.checkpoint(body), (jnp.zeros(()), jnp.zeros(())), (xc, tc)
    )
    return nll_sum, cnt


def _mean_ce(x, head, targets, chunk: int) -> jax.Array:
    """Mean cross entropy of the normed states ``x`` through ``head``
    over the positions whose target is >= 0."""
    if chunk > 0:
        nll_sum, cnt = _chunked_ce(x, head, targets, chunk)
    else:
        nll_sum, cnt = map(jnp.sum, _head_nll(x, head, targets))
    return nll_sum / jnp.maximum(cnt, 1.0)


def _ce_by_position(x, head, targets, chunk: int) -> jax.Array:
    """Cross entropy of the normed states ``x`` through ``head`` a
    position, float32 in ``targets``' shape, 0 where the target is
    < 0; with ``chunk`` in ``_chunked_ce``'s rematerialized chunks."""
    def nll(xs, ts):
        return _head_nll(xs, head, ts)[0]

    if chunk <= 0:
        return nll(x, targets)
    _, chunks = jax.lax.scan(
        jax.checkpoint(lambda _, inp: (None, nll(*inp))), None,
        _in_chunks(x, targets, chunk),
    )
    return chunks.reshape(-1)[:targets.size].reshape(targets.shape)


def _exit_distribution(gate_logits: jax.Array) -> Tuple[
        jax.Array, jax.Array]:
    """``(p, log p)`` over the passes, float32 [passes, ...], from the
    exit gate's logits [passes, ...]: ``p_t = lambda_t prod_{j<t} (1 -
    lambda_j)`` with ``lambda = sigmoid(logit)``, and the last pass
    what is left, ``prod_{j<T} (1 - lambda_j)`` (its own logit is not
    read), so the ``p_t`` sum to one. In logarithms: the entropy term
    reads ``log p`` and no gate is so sure that it is not finite."""
    logits = gate_logits[:-1].astype(jnp.float32)
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-logits), axis=0)
    before = jnp.concatenate(
        [jnp.zeros_like(gate_logits[:1], jnp.float32), stayed[:-1]]
    )
    log_p = jnp.concatenate(
        [before + jax.nn.log_sigmoid(logits), stayed[-1:]]
    )
    return jnp.exp(log_p), log_p


def _exit_terms(params, batch, cfg: LlamaConfig, attn_fn=None,
                constrain=None, gathered_weights: bool = False):
    """A looped stack's ``(nll, p, log p, mask)``: every pass's cross
    entropy a position (0 where there is no target), the exit
    distribution and its logarithm, all float32 [passes, batch, seq],
    and the positions that have a target, float32 [batch, seq]. Each
    pass goes through the head under a checkpoint of its own, so its
    logits are made again in the backward and none is kept from the
    forward: four passes' bfloat16 logits would be 3.2 GB at 8,192 x
    49,152 beside a step that plans 14.39 GB (14.87 while they were
    widened to float32, PERF.md section 6, PR 63; the calls are unrolled
    like the passes: a ``lax.map`` over them, which holds them to one
    at a time by construction, planned 14.59 GB beside the unrolled
    passes and ran 3% slower, PERF.md section 6, PR 58). Scope
    ``loop.exit_loss``."""
    tokens, targets = batch
    states, gate_logits = _run_loop(
        params, tokens, cfg, attn_fn, constrain, gathered_weights
    )
    head = _head(params, cfg)
    with jax.named_scope("loop.exit_loss"):
        ce = jax.checkpoint(
            lambda x: _ce_by_position(x, head, targets, cfg.loss_chunk)
        )
        nll = jnp.stack([ce(x) for x in states])
        p, log_p = _exit_distribution(gate_logits)
    return nll, p, log_p, (targets >= 0).astype(jnp.float32)


def _exit_loss(params, batch, cfg: LlamaConfig, attn_fn=None,
               constrain=None, gathered_weights: bool = False) -> jax.Array:
    """A looped stack's loss: the mean over the positions with a
    target of ``sum_t p_t nll_t - exit_entropy_weight H(p)``, ``H(p)
    = -sum_t p_t log p_t``; gradients reach the gate and the trunk
    through ``p``."""
    nll, p, log_p, mask = _exit_terms(
        params, batch, cfg, attn_fn, constrain, gathered_weights
    )
    with jax.named_scope("loop.exit_loss"):
        by_position = jnp.sum(
            p * (nll + cfg.exit_entropy_weight * log_p), axis=0
        )
        return jnp.sum(by_position * mask) / jnp.maximum(
            jnp.sum(mask), 1.0
        )


def loop_stats(params: Dict, batch, cfg: LlamaConfig,
               attn_fn=None) -> Tuple[jax.Array, jax.Array]:
    """``(each pass's mean cross entropy, each pass's mean exit
    probability)`` on ``batch``, float32 [passes] each, over the
    positions with a target: a forward pass (jit-able). The second
    sums to one; a gate that has collapsed puts it all on one pass."""
    nll, p, _, mask = _exit_terms(params, batch, cfg, attn_fn)
    count = jnp.maximum(jnp.sum(mask), 1.0)
    return (jnp.sum(nll, axis=(1, 2)) / count,
            jnp.sum(p * mask, axis=(1, 2)) / count)


def set_loop_gauges(pass_loss, exit_share) -> Tuple[list, list]:
    """Set the gauges ``loop_pass_loss{pass}`` and
    ``loop_exit_share{pass}`` (``GET /metrics``) to ``loop_stats``'
    values at an evaluation, passes counted from 1."""
    from dlrover_tpu.telemetry.registry import gauge

    pass_loss = [float(v) for v in pass_loss]
    exit_share = [float(v) for v in exit_share]
    losses = gauge(
        "loop_pass_loss",
        "mean cross entropy of a looped stack's pass through the "
        "head, unweighted, at the last evaluation", ("pass",),
    )
    shares = gauge(
        "loop_exit_share",
        "mean probability that the exit gate gives a pass of a looped "
        "stack, at the last evaluation", ("pass",),
    )
    for t, (loss, share) in enumerate(zip(pass_loss, exit_share), 1):
        # ``pass`` is a keyword: the label goes in as a mapping
        losses.labels(**{"pass": str(t)}).set(loss)
        shares.labels(**{"pass": str(t)}).set(share)
    return pass_loss, exit_share


def _mtp_states(cfg: LlamaConfig, params, module, x, ahead, layer_of):
    """``(normed hidden states, aux, counts)`` of the prediction
    module ``module``: position i's state ``x[i]`` out of the stack
    (before the final norm) and the embedding of the token after it,
    ``ahead[i]``, each normed, side by side through ``eh_proj``; one
    block of the stack's last kind, or the module's own sublayers
    (``mtp_kinds``); the module's own final norm. ``counts``: its
    block's, as ``_run_stack``'s."""
    with jax.named_scope("mtp.merge"):
        merged = jnp.concatenate([
            rms_norm(_embed(params, ahead, cfg), module["embed_norm"],
                     cfg.norm_eps),
            rms_norm(x, module["hidden_norm"], cfg.norm_eps),
        ], axis=-1) @ module["eh_proj"]
    blocks = module["block"]
    if cfg.mtp_hybrid_override_pattern is None:
        blocks = [blocks]
    with jax.named_scope("mtp.block"):
        carry = (merged, jnp.zeros((), jnp.float32))
        for kind, block in zip(cfg.mtp_kinds(), blocks):
            carry, counts = layer_of(kind)(carry, block)
    x, aux = carry
    return rms_norm(x, module["final_norm"], cfg.norm_eps), aux, counts


def _losses_and_counts(params, batch, cfg: LlamaConfig, attn_fn=None,
                       constrain=None, expert_parallel: bool = False,
                       gathered_weights: bool = False):
    """``((the main head's mean cross entropy, the prediction module's
    (0 without one), the scaled aux losses of every expert layer),
    counts)``. ``counts``: None, or where a rule moves the selection
    bias the assignments an expert, ``{"stack": [scanned layers,
    experts], "mtp": [experts]}`` (``_run_stack``; ``mtp`` with a
    prediction module).

    The prediction module reads position i's state and token i + 1
    and is scored, through the model's own head, on token i + 2
    (``targets[i + 1]``): a sequence's last position has no token
    after it (the roll hands it the first, and its target masks it
    out) and its last two no target."""
    if cfg.total_ut_steps > 1:
        # the weighted sum over the passes stands where the one cross
        # entropy does; no module, no experts beside it
        nothing = jnp.zeros((), jnp.float32)
        loss = _exit_loss(
            params, batch, cfg, attn_fn, constrain, gathered_weights
        )
        return (loss, nothing, nothing), None
    tokens, targets = batch
    x, aux, layer_of, counts = _run_stack(
        params, tokens, cfg, attn_fn, constrain, expert_parallel,
        gathered_weights,
    )
    if counts is not None:
        counts = {"stack": counts}
    normed = _head_input(x, params, cfg)
    head = _head(params, cfg)
    ce = _mean_ce(normed, head, targets, cfg.loss_chunk)
    if not cfg.mtp_layers:
        return (ce, jnp.zeros((), jnp.float32), aux), counts
    (module,) = params["mtp"]
    y, mtp_aux, mtp_counts = _mtp_states(
        cfg, params, module, x, jnp.roll(tokens, -1, axis=1), layer_of
    )
    if counts is not None:
        counts["mtp"] = mtp_counts
    with jax.named_scope("mtp.head"):
        mtp_ce = _mean_ce(
            y, head,
            jnp.pad(targets[:, 1:], ((0, 0), (0, 1)), constant_values=-1),
            cfg.loss_chunk,
        )
    return (ce, mtp_ce, aux + mtp_aux), counts


def _losses(params, batch, cfg: LlamaConfig, attn_fn=None,
            constrain=None, expert_parallel: bool = False):
    """``_losses_and_counts``' three losses, one by one (``mtp_loss``
    and the tests read them apart)."""
    return _losses_and_counts(
        params, batch, cfg, attn_fn, constrain, expert_parallel
    )[0]


def next_token_loss(
    params: Dict, batch: Tuple[jax.Array, jax.Array], cfg: LlamaConfig,
    attn_fn=None, constrain=None, expert_parallel: bool = False,
    gathered_weights: bool = False,
) -> jax.Array:
    """Mean next-token cross entropy (plus, for an expert config, the
    scaled balance and z losses of every layer, and with a prediction
    module its own mean cross entropy at ``mtp_loss_weight``). batch
    = (tokens, targets), both int32 [batch, seq]; target < 0 masks
    the position out. ``constrain``, ``expert_parallel``: see
    ``hidden_states``. ``gathered_weights``: the step gathers each
    layer's weights before it uses them and reduce-scatters their
    gradients (ZeRO-3; the trainer says so from its mesh and rule
    table): the gradients' reductions then have deadlines inside the
    layer (``_tie``). The loss and its gradient are the same either
    way."""
    return loss_and_expert_counts(
        params, batch, cfg, attn_fn, constrain, expert_parallel,
        gathered_weights,
    )[0]


def loss_and_expert_counts(
    params: Dict, batch: Tuple[jax.Array, jax.Array], cfg: LlamaConfig,
    attn_fn=None, constrain=None, expert_parallel: bool = False,
    gathered_weights: bool = False,
) -> Tuple[jax.Array, Dict]:
    """``(next_token_loss, counts)``: beside the loss, where a rule
    moves the selection bias (``moe_bias_update_rate``; else None),
    the assignments each expert received in each expert layer, from
    the same forward pass (``_losses_and_counts``), for
    ``moved_expert_bias``. What a trainer differentiates with
    ``has_aux``."""
    (ce, mtp_ce, aux), counts = _losses_and_counts(
        params, batch, cfg, attn_fn, constrain, expert_parallel,
        gathered_weights,
    )
    if cfg.mtp_layers:
        ce = ce + cfg.mtp_loss_weight * mtp_ce
    # aux arrives scaled (the config's coefficients)
    return ce + aux, counts


def moved_expert_bias(params: Dict, counts: Dict,
                      cfg: LlamaConfig) -> Dict:
    """``params`` with every expert layer's selection bias moved by
    the rule (``parallel/moe.py moved_bias``, at
    ``moe_bias_update_rate``) on the step's ``counts``
    (``loss_and_expert_counts``', summed over the step's
    microbatches). Scope ``moe.bias_update``."""
    from dlrover_tpu.parallel.moe import moved_bias

    def moved(layers, counts):
        return {**layers, "expert_bias": moved_bias(
            layers["expert_bias"], counts, cfg.moe_bias_update_rate
        )}

    params = dict(params)
    with jax.named_scope("moe.bias_update"):
        stack = counts["stack"]
        if cfg.by_position:
            # [periods, positions, experts]: a stack a position
            per_period = stack.reshape(
                -1, len(params["period"]), stack.shape[-1]
            )
            params["period"] = [
                moved(layers, per_period[:, i])
                for i, layers in enumerate(params["period"])
            ]
        else:
            params["blocks"] = moved(params["blocks"], stack)
        if cfg.mtp_layers:
            params["mtp"] = [
                {**module, "block": moved(module["block"], counts["mtp"])}
                for module in params["mtp"]
            ]
    return params


def expert_bias_abs_max(params: Dict, cfg: LlamaConfig) -> jax.Array:
    """The largest magnitude of each scanned expert layer's selection
    bias, float32 [expert layers]: how far the rule has moved it."""
    if cfg.by_position:
        return jnp.stack([
            jnp.max(jnp.abs(layers["expert_bias"]), axis=-1)
            for layers in params["period"] if "expert_bias" in layers
        ], axis=1).reshape(-1)
    return jnp.max(jnp.abs(params["blocks"]["expert_bias"]), axis=-1)


def mtp_loss(params: Dict, batch, cfg: LlamaConfig, attn_fn=None):
    """The prediction module's own mean cross entropy on ``batch``,
    unweighted: what ``next_token_loss`` adds at
    ``mtp_loss_weight``."""
    return _losses(params, batch, cfg, attn_fn)[1]


def set_mtp_loss_gauge(value) -> float:
    """Set the gauge ``mtp_loss`` (``GET /metrics``) to ``mtp_loss``'
    value at an evaluation: beside the step's loss it says whether
    the prediction module learns with the trunk."""
    from dlrover_tpu.telemetry.registry import gauge

    value = float(value)
    gauge(
        "mtp_loss",
        "mean cross entropy of the multi-token prediction module, "
        "unweighted, at the last evaluation",
    ).set(value)
    return value


def _seen_in_layers(params, tokens, cfg: LlamaConfig, attn_fn, see):
    """``see(kind, x, layer_params, operands, out, logits)`` of every
    scanned layer, stacked [layers, ...], for ``tokens`` [batch, seq]:
    a forward pass that also records, a layer, something of what the
    layer made on the way (jit-able). ``operands`` and ``logits`` are
    ``_pre_attn``'s, ``out`` the operator's result; what ``see``
    returns in ``logits``' place, if anything, goes on to
    ``_post_attn``."""
    if attn_fn is None:
        attn_fn = partial(flash_attention, causal=True)
    cos, sin = _rope_tables_of(cfg, tokens.shape[1])

    def layer_of(kind):
        operate = _operator_of(cfg, attn_fn, kind)

        def body(x, p):
            operands, logits = _pre_attn(cfg, x, p, cos, sin, kind=kind)
            out = operate(*operands)
            seen, logits = see(kind, x, p, operands, out, logits)
            x, _, _ = _post_attn(cfg, x, out, p, logits, kind=kind)
            return x, seen

        return body

    return _through_layers(
        cfg, layer_of, _embed(params, tokens, cfg), params
    )[1]


def _routed(params, tokens, cfg: LlamaConfig, attn_fn, stat):
    """``stat(logits, layer_params)`` of every expert layer, stacked
    [expert layers, ...], for ``tokens`` [batch, seq]: what the router
    saw (its logits for the hidden states it really reads)."""
    if cfg.num_experts == 0:
        raise ValueError("routing_stats: a dense config has no router")
    from dlrover_tpu.parallel.moe import router_logits

    def see(kind, x, p, operands, out, logits):
        if kind.ffn != "experts":
            return None, logits
        if logits is None:
            logits = router_logits(_ahead_of_branch(
                cfg, _past_operator(cfg, x, out, p, kind), p, "mlp_norm",
            ), p["router"])
        return stat(logits, p), logits

    return _seen_in_layers(params, tokens, cfg, attn_fn, see)


def _selection(cfg: LlamaConfig, layer_params) -> Dict:
    """How ``layer_params``' router selects: ``route_logits``' gate
    and bias."""
    routing = {"gate": cfg.moe_gate}
    if cfg.use_expert_bias:
        routing["bias"] = layer_params["expert_bias"]
    return routing


def routing_stats(params: Dict, tokens: jax.Array, cfg: LlamaConfig,
                  attn_fn=None) -> jax.Array:
    """Tokens per expert and expert layer, int32 [layers, experts],
    for ``tokens`` [batch, seq]: each row sums to ``batch x seq x
    moe_top_k``."""
    from dlrover_tpu.parallel.moe import logits_per_expert

    return _routed(
        params, tokens, cfg, attn_fn,
        lambda logits, p: logits_per_expert(
            logits, cfg.moe_top_k, **_selection(cfg, p)
        ),
    )


def bias_changed_stats(params: Dict, tokens: jax.Array,
                       cfg: LlamaConfig, attn_fn=None) -> jax.Array:
    """Assignments an expert layer's selection bias changed, int32
    [layers], of its ``batch x seq x moe_top_k``: those among a
    token's top-k of score plus bias and not of the score alone."""
    from dlrover_tpu.parallel.moe import bias_changed

    return _routed(
        params, tokens, cfg, attn_fn,
        lambda logits, p: bias_changed(
            logits, cfg.moe_top_k, **_selection(cfg, p)
        ),
    )


def decay_min(params: Dict, tokens: jax.Array, cfg: LlamaConfig,
              attn_fn=None) -> jax.Array:
    """The least ``alpha = exp(g)`` that a channel of each scanned
    layer's gated delta rule takes on ``tokens`` [batch, seq], float32
    [layers]; 1 for a layer of another kind. A forward pass
    (jit-able), before the scan's floor: ``gated_delta_rule_rows``
    takes a step of a decay a channel under ``exp(-10)`` as
    ``exp(-10)`` (ops/delta_rule.py ``G_FLOOR``), and this is the
    number that says whether a run's channels get there; a Gated
    DeltaNet layer's is the least ``alpha`` of a head, which no floor
    touches. A state-space layer's is the least ``a =
    exp(A Delta)`` of a head (ops/ssd.py has no floor), a Mamba-1
    mixer's the least ``exp(Delta_t[d] A[d, n])`` of a channel's
    states (ops/selective_scan.py has none either)."""
    def see(kind, x, p, operands, out, logits):
        if kind.operator == "mamba":
            # the largest step of a channel against its fastest rate:
            # no array of [seq, channels, states]
            _, delta, _, _, rates = operands[:5]
            return jnp.exp(jnp.min(
                jnp.max(delta, axis=(0, 1)) * jnp.min(rates, axis=1)
            )), logits
        if kind.operator == "state_space":
            _, dt, rate = operands[:3]  # a head's a = exp(A Delta)
            return jnp.exp(jnp.min(dt * rate)), logits
        if kind.operator not in ("linear_attention", "gated_delta_net"):
            return jnp.ones((), jnp.float32), logits
        return jnp.exp(jnp.min(operands[3])), logits

    return _seen_in_layers(params, tokens, cfg, attn_fn, see)


def set_decay_min_gauge(least, name: str = "kda_decay_min") -> float:
    """Set the gauge ``kda_decay_min`` (``GET /metrics``), or for a
    stack of state-space layers ``ssm_decay_min``, or for one of the
    delta rule with one decay a head ``gdn_decay_min``, to the least
    of ``decay_min``'s values at an evaluation."""
    from dlrover_tpu.telemetry.registry import gauge

    value = float(jnp.min(least))
    gauge(
        name,
        "least decay of a step (alpha = exp(g) of a key channel of the "
        "gated delta rule, or of a head where the decay is one a head; "
        "a = exp(A Delta) of a head of the state-space scan), over the "
        "layers, at the last evaluation",
    ).set(value)
    return value


def flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Approximate training FLOPs per token (6N_active + attention
    quadratic, at ``num_heads x head_dim`` and by each layer's kind;
    the convolution's taps are not counted, and of the gated delta
    rule in either form of decay, of a state-space mixer, of a
    lightning layer and of a Mamba-1 mixer the
    projections and low ranks but not the recurrence; sparse
    attention at the keys of a query's ``sparse_topk`` blocks, the
    selection's own scores not counted), a
    prediction module's
    block and second pass through the head included. For MoE, only
    the top-k routed experts execute per token, so N counts k experts
    — not all E. A looped stack meets every weight but the embedding's
    once a pass."""
    n = param_count(cfg)
    if not cfg.tie_word_embeddings:
        n -= cfg.vocab_size * cfg.hidden_size  # tied-ish
    n += cfg.mtp_layers * cfg.vocab_size * cfg.hidden_size
    n *= cfg.total_ut_steps
    kinds = [
        (kind, count * cfg.total_ut_steps)
        for kind, count in _layers_of_each_kind(cfg)
    ]
    if cfg.num_experts > 0:
        # an expert's two or three matrices on what it reads
        expert = (3 if cfg.moe_expert_gated else 2) * (
            cfg.moe_latent_size or cfg.hidden_size
        ) * cfg.moe_intermediate_size
        # of the experts held here a token meets its k's share
        met = (min(cfg.moe_top_k, cfg.num_experts)
               * cfg.moe_experts_held / cfg.num_experts)
        n -= expert * (cfg.moe_experts_held - met) * sum(
            count for kind, count in kinds if kind.ffn == "experts"
        )
    # scores and weighted values against every key a query's layer
    # lets it see, causality not counted: the sequence, or the window
    keys = sum(
        count * min(kind.window or seq_len, seq_len)
        for kind, count in kinds
        if kind.operator in ("full_attention", "latent_attention")
    )
    # a query's selected blocks, on a sequence long enough to select
    keys += sum(
        count * (seq_len if seq_len <= cfg.sparse_dense_len else min(
            cfg.sparse_topk * cfg.sparse_block_size, seq_len))
        for kind, count in kinds if kind.operator == "sparse_attention"
    )
    # a head's scores contract over q and k's width, its weighted
    # values are v's wide
    qk, v = cfg.head_dim, cfg.head_dim
    if cfg.latent:
        qk, v = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim
    return 6.0 * n + 6 * cfg.num_heads * (qk + v) * keys
