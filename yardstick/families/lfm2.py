"""The ``lfm2`` family: configurations in ``Lfm2MoeForCausalLM``'s key
names, run through ``models/llama.py`` with its two kinds of operator
(``layer_types``: the gated short convolution and full attention with
a norm on each head's q and k), its leading dense layers, its sigmoid
router that selects by a biased score, its tied head, and its
dropless experts, of which this chip holds a share. No JAX at import:
the parent reads the counts.

``num_experts`` is the number of experts held here and ``vocab_size``
the slice of the vocabulary held here (both listed in the file's
``reduced``); the router's published width, and where the held range
starts, are in the file's ``share`` group. ``layer_types`` holds the
layers that are run, one entry each.

Counts, in ``counts.py``'s conventions: attention is causal and only
the ``full_attention`` layers have it; a token meets, of the experts
held here, ``k x held / width`` under even routing (an expectation,
stated as one), in a leading dense layer the three matrices of
``intermediate_size``, in a ``conv`` layer the input projection (three
times the hidden size wide) and the output projection; the
convolution's own ``taps`` multiply-adds a channel are not a matrix
product and count as nothing in ``train_flops_per_token``
(``short_conv_step`` has them); the tied head is a product like any
other."""


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    assumed, share = config["assumed"], config["share"]
    if not config["norm_topk_prob"] and config["num_experts_per_tok"] == 1:
        raise ValueError(
            "norm_topk_prob false at one expert a token: "
            "parallel/moe.py keeps a single weight raw either way, "
            "so the key would not be passed on"
        )
    if config["routed_scaling_factor"] != 1:
        raise ValueError(
            f"routed_scaling_factor {config['routed_scaling_factor']}: "
            "the source's is 1, and parallel/moe.py has no factor on "
            "the routing weights to pass another on to"
        )
    if config["conv_bias"]:
        raise ValueError(
            "conv_bias: the gated short convolution here "
            "(ops/short_conv.py) has no bias on its projections or "
            "its taps"
        )
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError(
            f"layer_types has {len(config['layer_types'])} entries "
            f"for {config['num_hidden_layers']} layers: the file "
            "holds the layers that are run"
        )
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_dense_layers=config["num_dense_layers"],
        layer_types=tuple(config["layer_types"]),
        conv_L_cache=config["conv_L_cache"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=assumed["head_dim"],
        qk_head_norm=True,  # every Lfm2MoeAttention has both
        tie_word_embeddings=assumed["tie_word_embeddings"],
        max_seq_len=traffic["seq"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["norm_eps"],
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
        num_experts=share["router_width"],
        moe_top_k=config["num_experts_per_tok"],
        # the source has no capacity: dropless, stated
        moe_capacity_factor=0.0,
        norm_topk_prob=config["norm_topk_prob"],
        moe_gate="sigmoid",
        use_expert_bias=config["use_expert_bias"],
        router_aux_loss_coef=assumed["router_aux_loss_coef"],
        router_z_loss_coef=assumed["router_z_loss_coef"],
        moe_first_expert_held=share["first_expert_held"],
        moe_experts_held=config["num_experts"],
        embed_init_std=assumed["embed_init_std"],
    )


def shape(config):
    types = tuple(config["layer_types"])
    return {
        "hidden": config["hidden_size"],
        "ffn": config["moe_intermediate_size"],  # one expert's width
        "dense_ffn": config["intermediate_size"],
        "layers": config["num_hidden_layers"],
        "dense_layers": config["num_dense_layers"],
        "attention_layers": types.count("full_attention"),
        "conv_layers": types.count("conv"),
        "taps": config["conv_L_cache"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["assumed"]["head_dim"],
        "vocab": config["vocab_size"],
        "ffn_matrices": 3,  # gate, up, down
        "experts": config["share"]["router_width"],
        "experts_held": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
    }


def matmul_params(config):
    """What a token is multiplied by in one forward pass: in an
    attention layer its four matrices, in a convolution layer the
    input projection (hidden x 3 hidden) and the output projection;
    in a leading dense layer three matrices of ``intermediate_size``,
    in the others the router and the experts held here that a token
    meets (``k x held / width`` of them, the expectation under even
    routing); and the tied head over the slice of the vocabulary held
    here."""
    s = shape(config)
    h, d = s["hidden"], s["head_dim"]
    attention = (
        h * s["heads"] * d + 2 * h * s["kv_heads"] * d
        + s["heads"] * d * h
    )
    conv = 3 * h * h + h * h
    met = s["experts_per_token"] * s["experts_held"] / s["experts"]
    sparse = h * s["experts"] + met * s["ffn_matrices"] * h * s["ffn"]
    dense = s["ffn_matrices"] * h * s["dense_ffn"]
    return (
        s["attention_layers"] * attention + s["conv_layers"] * conv
        + s["dense_layers"] * dense
        + (s["layers"] - s["dense_layers"]) * sparse
        + h * s["vocab"]
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
    attention layers only."""
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
    for ``tokens`` tokens on this chip, over the expert layers:
    ``(flops, bytes)``, as ``families/smallthinker.py`` counts them,
    for the rows that fall on the experts held here: ``tokens x k x
    held / width``, the expectation under even routing (a seed's
    routing moves it), and the held experts' matrices."""
    s = shape(config)
    h, m = s["hidden"], s["ffn"]
    layers = s["layers"] - s["dense_layers"]
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
    return float(layers * flops), float(layers * nbytes)


def short_conv_step(config, tokens):
    """What the gated short convolutions of one training step must do
    for ``tokens`` tokens on this chip, over the convolution layers:
    ``(flops, bytes)``. Operations, a token and channel: forward the
    gate ``B * u``, ``taps`` multiply-adds and the gate ``C``;
    backward ``dy * C``, the convolution again for ``dC``, ``taps``
    multiply-adds for ``dv`` and as many for the taps' gradient, and
    the four products that ``dC``, ``dB`` and ``du`` end in. Bytes,
    bf16, the least: forward ``B, C, u`` read and ``y`` written;
    backward ``B, C, u, dy`` read and ``dB, dC, du`` written; no
    recomputation (the taps and their gradient are nothing beside
    them). Bound by bytes: 12 operations a byte would be needed."""
    s = shape(config)
    taps = s["taps"]
    flops = tokens * s["hidden"] * (
        (2 + 2 * taps) + (1 + 3 * 2 * taps + 4)
    )
    nbytes = tokens * s["hidden"] * 2 * (4 + 7)
    return (float(s["conv_layers"] * flops),
            float(s["conv_layers"] * nbytes))
