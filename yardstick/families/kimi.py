"""The ``kimi`` family: configurations in Kimi-Linear's key names
(``KimiLinearConfig``'s), run through ``models/llama.py`` with its two
kinds of operator (Kimi Delta Attention, the gated delta rule with a
decay for every key channel behind four-tap convolutions, in the
layers ``linear_attn_config.kda_layers`` names; latent attention
without positions, q by one matrix straight from the stream, in those
``full_attn_layers`` names; both lists count from 1), its leading
dense layers, a sigmoid router that selects by a biased score and
scales the weights, a shared expert beside the dropless routed ones,
of which this chip holds a share, and an untied head. No JAX at
import: the parent reads the counts.

``num_experts`` is the number of experts held here and ``vocab_size``
the slice of the vocabulary held here (both listed in the file's
``reduced``); the router's published width, and where the held range
starts, are in the file's ``share`` group. The two lists of layers
stay the source's: of the layers that are run, each is of the list
that names it.

Counts, in ``counts.py``'s conventions: attention is causal and only
the latent layers have it, its scores contracting over ``qk_nope_head_dim
+ qk_rope_head_dim`` columns and its weighted values ``v_head_dim``
wide, whatever the kernels pad; a delta-rule layer's token meets its
three projections, the output projection, the two low ranks and the
step size's row; the convolutions' taps and the recurrence are no
matrix products and count as nothing in ``train_flops_per_token``
(``delta_rule_step`` has the recurrence); a token meets, of the
experts held here, ``k x held / width`` under even routing (an
expectation, stated as one), the shared expert whole, in a leading
dense layer the three matrices of ``intermediate_size``."""


def layer_types(config):
    """The operator of each layer that is run, from the source's two
    lists (layer l of the program is the source's layer l + 1)."""
    linear = config["linear_attn_config"]
    kda, full = set(linear["kda_layers"]), set(linear["full_attn_layers"])
    if kda & full:
        raise ValueError(
            f"layers {sorted(kda & full)} are in kda_layers and in "
            "full_attn_layers"
        )
    types = []
    for l in range(1, config["num_hidden_layers"] + 1):
        if l not in kda | full:
            raise ValueError(
                f"layer {l} is in neither kda_layers nor full_attn_layers"
            )
        types.append(
            "linear_attention" if l in kda else "latent_attention"
        )
    return tuple(types)


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    assumed, share = config["assumed"], config["share"]
    linear = config["linear_attn_config"]
    if config["num_expert_group"] != 1 or config["topk_group"] != 1:
        raise ValueError(
            f"num_expert_group {config['num_expert_group']}, topk_group "
            f"{config['topk_group']}: the source's router has one "
            "group of one, where choosing the best groups first is "
            "the identity; parallel/moe.py has no selection by groups "
            "to pass another on to"
        )
    want = dict(
        q_lora_rank=None, mla_use_nope=True, num_nextn_predict_layers=0,
        moe_router_activation_func="sigmoid", hidden_act="silu",
        rope_scaling=None, moe_layer_freq=1, tie_word_embeddings=False,
        num_key_value_heads=config["num_attention_heads"],
    )
    for key, value in want.items():
        if config[key] != value:
            raise ValueError(
                f"{key} {config[key]!r}: the family runs {value!r} "
                "(the source's), and nothing in models/llama.py takes "
                "another beside this stack"
            )
    layers = config["num_hidden_layers"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=layers,
        num_dense_layers=config["first_k_dense_replace"],
        layer_types=layer_types(config),
        rope_layout=(0,) * layers,  # mla_use_nope: no position at all
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],  # not read: no layer has whole q
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        linear_num_heads=linear["num_heads"],
        linear_head_dim=linear["head_dim"],
        linear_conv_size=linear["short_conv_kernel_size"],
        linear_gate_rank=assumed["kda_gate_rank"],
        # the source has no kda_allow_neg_eigval: beta = sigmoid
        linear_allow_neg_eigval=False,
        max_seq_len=traffic["seq"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
        num_experts=share["router_width"],
        moe_top_k=config["num_experts_per_token"],
        # the source has no capacity: dropless, stated
        moe_capacity_factor=0.0,
        norm_topk_prob=config["moe_renormalize"],
        moe_gate="sigmoid",
        use_expert_bias=True,  # top-k of score plus bias
        moe_topk_norm_eps=assumed["topk_norm_eps"],
        moe_routed_scaling=float(config["routed_scaling_factor"]),
        moe_shared_experts=config["num_shared_experts"],
        router_aux_loss_coef=assumed["router_aux_loss_coef"],
        router_z_loss_coef=assumed["router_z_loss_coef"],
        moe_first_expert_held=share["first_expert_held"],
        moe_experts_held=config["num_experts"],
        embed_init_std=assumed["embed_init_std"],
    )


def shape(config):
    types = layer_types(config)
    linear = config["linear_attn_config"]
    return {
        "hidden": config["hidden_size"],
        "ffn": config["moe_intermediate_size"],  # one expert's width
        "dense_ffn": config["intermediate_size"],
        "layers": config["num_hidden_layers"],
        "dense_layers": config["first_k_dense_replace"],
        "attention_layers": types.count("latent_attention"),
        "linear_layers": types.count("linear_attention"),
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        # q and k's of a latent layer
        "head_dim": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        "nope_dim": config["qk_nope_head_dim"],
        "rope_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "kv_rank": config["kv_lora_rank"],
        "linear_heads": linear["num_heads"],
        "linear_head_dim": linear["head_dim"],
        "taps": linear["short_conv_kernel_size"],
        "gate_rank": config["assumed"]["kda_gate_rank"],
        "vocab": config["vocab_size"],
        "ffn_matrices": 3,  # gate, up, down
        "experts": config["share"]["router_width"],
        "experts_held": config["num_experts"],
        "experts_per_token": config["num_experts_per_token"],
        "shared_experts": config["num_shared_experts"],
    }


def _expert_layers(s):
    return s["layers"] - s["dense_layers"]


def matmul_params(config):
    """What a token is multiplied by in one forward pass: in a latent
    layer q's one matrix, the projection down to ``[c | k_r]``, the
    one up to ``[k_nope | v]`` and the output projection; in a
    delta-rule layer q, k, v and the output projection (hidden x
    heads x d each), the decay's and the gate's low ranks (hidden x
    rank and rank x heads x d each) and the step size's hidden x
    heads; in a leading dense layer three matrices of
    ``intermediate_size``, in an expert layer the router, the shared
    expert and the experts held here that a token meets (``k x held
    / width`` of them, the expectation under even routing); and the
    head over the slice of the vocabulary held here."""
    s = shape(config)
    h = s["hidden"]
    latent = (
        h * s["heads"] * s["head_dim"]
        + h * (s["kv_rank"] + s["rope_dim"])
        + s["kv_rank"] * s["heads"] * (s["nope_dim"] + s["v_head_dim"])
        + s["heads"] * s["v_head_dim"] * h
    )
    wide = s["linear_heads"] * s["linear_head_dim"]
    linear = (
        4 * h * wide + 2 * s["gate_rank"] * (h + wide)
        + h * s["linear_heads"]
    )
    expert = s["ffn_matrices"] * h * s["ffn"]
    met = s["experts_per_token"] * s["experts_held"] / s["experts"]
    sparse = h * s["experts"] + (s["shared_experts"] + met) * expert
    dense = s["ffn_matrices"] * h * s["dense_ffn"]
    return (
        s["attention_layers"] * latent + s["linear_layers"] * linear
        + s["dense_layers"] * dense + _expert_layers(s) * sparse
        + h * s["vocab"]
    )


def attention_forward_flops_per_token(config, seq):
    """Scores (``seq x (nope + rope)`` operations a token and head,
    causal) and weighted values (``seq x v_head_dim``), over the
    latent layers only."""
    s = shape(config)
    return (
        1.0 * s["attention_layers"] * s["heads"]
        * (s["head_dim"] + s["v_head_dim"]) * seq
    )


def train_flops_per_token(config, seq):
    """Forward and backward, no recomputation."""
    forward = (
        2.0 * matmul_params(config)
        + attention_forward_flops_per_token(config, seq)
    )
    return 3.0 * forward


def attention_kernel_step(config, sequences, seq):
    """What the attention kernels of one training step must do for
    ``sequences`` sequences on one chip: ``(flops, bytes)`` as
    ``counts.attention_kernel_step`` has them, with the two widths
    apart as ``families/joyai.py`` has them, over the latent layers
    only. Of the seven causal products four contract over or give
    ``nope + rope`` columns (q k^T twice, dq, dk) and three
    ``v_head_dim`` (p v, dv, dp); q, k, dq and dk are as wide as the
    first, v, o, dO and dv as the second, each read or written once a
    pass. The work of the mathematics: the one key of ``rope``
    columns counted a head's like the rest, a pair of backward
    kernels that computes the scores twice reads as a lower share,
    not as more work."""
    s = shape(config)
    blocks = s["attention_layers"]
    d, dv = s["head_dim"], s["v_head_dim"]
    flops = (
        1.0 * blocks * sequences * s["heads"] * seq * seq * (4 * d + 3 * dv)
    )
    a_column = sequences * seq * s["heads"] * 2
    return flops, float(blocks * a_column * (6 * d + 6 * dv))


def expert_matmul_step(config, tokens):
    """What the grouped expert matmuls of one training step must do
    for ``tokens`` tokens on this chip, over the expert layers:
    ``(flops, bytes)``, as ``families/lfm2.py`` counts them, for the
    rows that fall on the experts held here: ``tokens x k x held /
    width``, the expectation under even routing (a seed's routing
    moves it), and the held experts' matrices. The shared expert is a
    plain matrix product, not a grouped one, and is not in it. No
    reader calls it for this family's cell yet: a call on 512 rows an
    expert is near the 200 operation names a reduced trace keeps, and
    a list that names a cell obliges it (PERF.md section 7)."""
    s = shape(config)
    h, m = s["hidden"], s["ffn"]
    rows = (
        tokens * s["experts_per_token"] * s["experts_held"]
        / s["experts"]
    )
    flops = 3 * 2 * rows * s["ffn_matrices"] * h * m
    weights = 3 * s["experts_held"] * s["ffn_matrices"] * h * m
    # (operand width, result width) of gate, up, down
    per_row = sum(
        (a + b) + (b + a + a) for a, b in ((h, m), (h, m), (m, h))
    )
    nbytes = 2 * (weights + rows * per_row)
    blocks = _expert_layers(s)
    return float(blocks * flops), float(blocks * nbytes)


def delta_rule_step(config, tokens):
    """What the gated delta rule of one training step must do for
    ``tokens`` tokens on this chip, over the delta-rule layers:
    ``(flops, bytes)``, the recurrence's own whatever implements it,
    as ``families/solar.py`` counts it.

    Operations, a token and head, with ``d`` keys and ``d`` values
    (a multiply-add 2): forward the decay of the state (``d x d``
    multiplies), ``S^T k`` (``2 d d``), the rank-one update (``2 d
    d``) and ``S^T q`` (``2 d d``), ``7 d d``; backward twice that,
    as a product's is: ``21 d d`` in all. No chunk, no recomputation
    of the state, no solve: those are an implementation's.

    Bytes, the least: forward q, k, v read and o written at the
    operator's dtype (bf16), g read in float32 and beta (a number a
    head); backward the five operands and the result's cotangent
    read, and the five gradients written at their operand's dtype."""
    s = shape(config)
    heads, d = s["linear_heads"], s["linear_head_dim"]
    flops = 21.0 * tokens * heads * d * d
    column = tokens * heads * d
    forward = 4 * 2 * column + 4 * column + 4 * tokens * heads
    backward = (
        4 * 2 * column + 4 * column + 4 * tokens * heads  # read
        + 3 * 2 * column + 4 * column + 4 * tokens * heads  # written
    )
    return (float(s["linear_layers"] * flops),
            float(s["linear_layers"] * (forward + backward)))
