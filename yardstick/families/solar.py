"""The ``solar`` family: configurations in Solar-Open2's key names, run
through ``models/llama.py`` with its two kinds of operator (full
attention without positions, with a sigmoid gate on its result, in the
layers ``gqa_layers`` names; the gated delta rule with a decay for
every key channel, behind four-tap convolutions, in the others), a
sigmoid router that selects by a biased score, a shared expert beside
the dropless routed ones, of which this chip holds a share, and an
untied head. No JAX at import: the parent reads the counts.

``n_routed_experts`` is the number of experts held here and
``vocab_size`` the slice of the vocabulary held here (both listed in
the file's ``reduced``); the router's published width, and where the
held range starts, are in the file's ``share`` group. ``gqa_layers``
stays the source's list: of the layers that are run, those it names
are full attention.

Counts, in ``counts.py``'s conventions: attention is causal and only
the layers ``gqa_layers`` names have it (the gate is one more matrix
of theirs); a linear-attention layer's token meets its three
projections, the output projection, the two low ranks and the step
size's row; the convolutions' taps and the recurrence are no matrix
products and count as nothing in ``train_flops_per_token``
(``delta_rule_step`` has the recurrence); a token meets, of the
experts held here, ``k x held / width`` under even routing (an
expectation, stated as one) and the shared expert whole."""


def layer_types(config):
    """The operator of each layer that is run."""
    full = set(config["gqa_layers"])
    return tuple(
        "full_attention" if l in full else "linear_attention"
        for l in range(config["num_hidden_layers"])
    )


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    assumed, share = config["assumed"], config["share"]
    linear = config["linear_attn_config"]
    want = dict(
        use_rope=False, kda_use_full_proj=False, first_k_dense_replace=0,
        tie_word_embeddings=False,
    )
    for key, value in want.items():
        if config[key] != value:
            raise ValueError(
                f"{key} {config[key]!r}: the family runs {value!r} "
                "(the source's), and nothing in models/llama.py takes "
                "another"
            )
    if linear["num_kv_heads"] not in (None, linear["num_heads"]):
        raise ValueError(
            f"linear_attn_config.num_kv_heads {linear['num_kv_heads']}: "
            "the gated delta rule here has a key and a value head for "
            "every query head (the source's null)"
        )
    layers = config["num_hidden_layers"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=layers,
        layer_types=layer_types(config),
        rope_layout=(0,) * layers,  # use_rope false: no position at all
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        attn_out_gate=config["use_gqa_gate"],
        linear_num_heads=linear["num_heads"],
        linear_head_dim=linear["head_dim"],
        linear_conv_size=linear["short_conv_kernel_size"],
        linear_gate_rank=assumed["kda_gate_rank"],
        linear_allow_neg_eigval=config["kda_allow_neg_eigval"],
        max_seq_len=traffic["seq"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
        num_experts=share["router_width"],
        moe_top_k=config["num_experts_per_tok"],
        # the source has no capacity: dropless, stated
        moe_capacity_factor=0.0,
        norm_topk_prob=config["norm_topk_prob"],
        moe_gate="sigmoid",
        use_expert_bias=True,
        moe_topk_norm_eps=assumed["topk_norm_eps"],
        moe_routed_scaling=float(config["routed_scaling_factor"]),
        moe_shared_experts=config["n_shared_experts"],
        router_aux_loss_coef=assumed["router_aux_loss_coef"],
        router_z_loss_coef=assumed["router_z_loss_coef"],
        moe_first_expert_held=share["first_expert_held"],
        moe_experts_held=config["n_routed_experts"],
        embed_init_std=assumed["embed_init_std"],
    )


def shape(config):
    types = layer_types(config)
    linear = config["linear_attn_config"]
    return {
        "hidden": config["hidden_size"],
        "ffn": config["moe_intermediate_size"],  # one expert's width
        "layers": config["num_hidden_layers"],
        "attention_layers": types.count("full_attention"),
        "linear_layers": types.count("linear_attention"),
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "linear_heads": linear["num_heads"],
        "linear_head_dim": linear["head_dim"],
        "taps": linear["short_conv_kernel_size"],
        "gate_rank": config["assumed"]["kda_gate_rank"],
        "vocab": config["vocab_size"],
        "ffn_matrices": 3,  # gate, up, down
        "experts": config["share"]["router_width"],
        "experts_held": config["n_routed_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "shared_experts": config["n_shared_experts"],
    }


def matmul_params(config):
    """What a token is multiplied by in one forward pass: in an
    attention layer q, k, v, the gate and the output projection; in a
    linear-attention layer q, k, v and the output projection (hidden x
    heads x d each), the decay's and the gate's low ranks (hidden x
    rank and rank x heads x d each) and the step size's hidden x
    heads; in every layer the router, the shared expert and the
    experts held here that a token meets (``k x held / width`` of
    them, the expectation under even routing); and the head over the
    slice of the vocabulary held here."""
    s = shape(config)
    h, d = s["hidden"], s["head_dim"]
    attention = (
        3 * h * s["heads"] * d + 2 * h * s["kv_heads"] * d
    )
    wide = s["linear_heads"] * s["linear_head_dim"]
    linear = (
        4 * h * wide + 2 * s["gate_rank"] * (h + wide)
        + h * s["linear_heads"]
    )
    expert = s["ffn_matrices"] * h * s["ffn"]
    met = s["experts_per_token"] * s["experts_held"] / s["experts"]
    sparse = h * s["experts"] + (s["shared_experts"] + met) * expert
    return (
        s["attention_layers"] * attention + s["linear_layers"] * linear
        + s["layers"] * sparse + h * s["vocab"]
    )


def attention_forward_flops_per_token(config, seq):
    """Scores and weighted values, causal, over the attention layers
    only: ``seq x head_dim`` operations a product, token and head."""
    s = shape(config)
    return (
        2.0 * s["attention_layers"] * s["heads"] * s["head_dim"] * seq
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
    ``counts.attention_kernel_step`` has them (seven causal products;
    every operand read once and every result written once), over the
    attention layers only: one layer in four has scores."""
    s = shape(config)
    d = s["head_dim"]
    flops = (
        7.0 * s["attention_layers"] * sequences * s["heads"]
        * seq * seq * d
    )
    q_like = sequences * seq * s["heads"] * d * 2
    kv_like = sequences * seq * s["kv_heads"] * d * 2
    return flops, float(
        s["attention_layers"] * (6 * q_like + 6 * kv_like)
    )


def expert_matmul_step(config, tokens):
    """What the grouped expert matmuls of one training step must do
    for ``tokens`` tokens on this chip, over every layer: ``(flops,
    bytes)``, as ``families/lfm2.py`` counts them, for the rows that
    fall on the experts held here: ``tokens x k x held / width``, the
    expectation under even routing (a seed's routing moves it), and
    the held experts' matrices. The shared expert is a plain matrix
    product, not a grouped one, and is not in it. No reader calls it
    for this family's cell yet: a call on 205 rows an expert is under
    the 200 operation names a reduced trace keeps, so the cell is in
    neither ``moe_expert_*`` list until the reduction sums a reader's
    kernels before that cut (ROADMAP B12 (m))."""
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
    return float(s["layers"] * flops), float(s["layers"] * nbytes)


def delta_rule_step(config, tokens):
    """What the gated delta rule of one training step must do for
    ``tokens`` tokens on this chip, over the linear-attention layers:
    ``(flops, bytes)``, the recurrence's own whatever implements it.

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
