"""The ``joyai`` family: configurations in JoyAI-LLM-Flash's key names
(``DeepseekV3Config``'s), run through ``models/llama.py`` with latent
attention in every layer (two low-rank projections with a norm
between, 192-wide q and k of which 64 columns are rotated in
neighbouring pairs and the rotated key is one head's, 128-wide v),
its leading dense layers, its sigmoid router that selects by a biased
score and scales the weights, a shared expert beside the dropless
routed ones, of which this chip holds a share, and one multi-token
prediction module in the loss. No JAX at import: the parent reads the
counts.

``n_routed_experts`` is the number of experts held here and
``vocab_size`` the slice of the vocabulary held here (both listed in
the file's ``reduced``); the router's published width, and where the
held range starts, are in the file's ``share`` group.

Counts, in ``counts.py``'s conventions: attention is causal, its
scores contract over ``qk_head_dim`` and its weighted values are
``v_head_dim`` wide, whatever the kernels pad; every layer and the
prediction module's block have it; a token meets, of the experts held
here, ``k x held / width`` under even routing (an expectation, stated
as one), the shared expert whole, in a leading dense layer the three
matrices of ``intermediate_size``; the module adds its merge
(``2 hidden x hidden``), a block of the expert kind and a second
product with the head over the slice of the vocabulary held here."""


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    assumed, share = config["assumed"], config["share"]
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError(
            f"n_group {config['n_group']}, topk_group "
            f"{config['topk_group']}: the source's router has one "
            "group of one, where choosing the best groups first is "
            "the identity; parallel/moe.py has no selection by groups "
            "to pass another on to"
        )
    want = dict(
        scoring_func="sigmoid", topk_method="noaux_tc", hidden_act="silu",
        rope_scaling=None, attention_bias=False, moe_layer_freq=1,
        tie_word_embeddings=False, num_nextn_predict_layers=1,
        qk_head_dim=(config["qk_nope_head_dim"]
                     + config["qk_rope_head_dim"]),
        num_key_value_heads=config["num_attention_heads"],
    )
    for key, value in want.items():
        if config[key] != value:
            raise ValueError(
                f"{key} {config[key]!r}: the family runs {value!r} "
                "(the source's), and nothing in models/llama.py takes "
                "another"
            )
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_dense_layers=config["first_k_dense_replace"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_interleave=config["rope_interleave"],
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
        use_expert_bias=True,  # noaux_tc: top-k of score plus bias
        moe_topk_norm_eps=assumed["topk_norm_eps"],
        moe_routed_scaling=config["routed_scaling_factor"],
        moe_shared_experts=config["n_shared_experts"],
        router_aux_loss_coef=assumed["router_aux_loss_coef"],
        router_z_loss_coef=assumed["router_z_loss_coef"],
        moe_first_expert_held=share["first_expert_held"],
        moe_experts_held=config["n_routed_experts"],
        embed_init_std=assumed["embed_init_std"],
        mtp_layers=config["num_nextn_predict_layers"],
        mtp_loss_weight=assumed["mtp_loss_weight"],
    )


def shape(config):
    return {
        "hidden": config["hidden_size"],
        "ffn": config["moe_intermediate_size"],  # one expert's width
        "dense_ffn": config["intermediate_size"],
        "layers": config["num_hidden_layers"],
        "dense_layers": config["first_k_dense_replace"],
        "mtp_layers": config["num_nextn_predict_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["qk_head_dim"],  # q and k's
        "nope_dim": config["qk_nope_head_dim"],
        "rope_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "q_rank": config["q_lora_rank"],
        "kv_rank": config["kv_lora_rank"],
        "vocab": config["vocab_size"],
        "ffn_matrices": 3,  # gate, up, down
        "experts": config["share"]["router_width"],
        "experts_held": config["n_routed_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "shared_experts": config["n_shared_experts"],
    }


def _expert_blocks(s):
    """Blocks of the expert kind: the stack's and the prediction
    module's."""
    return s["layers"] - s["dense_layers"] + s["mtp_layers"]


def matmul_params(config):
    """What a token is multiplied by in one forward pass: in every
    block the five matrices of latent attention (q down and up, k and
    v down and up, out); in a leading dense layer three matrices of
    ``intermediate_size``, in an expert block the router, the shared
    expert and the experts held here that a token meets (``k x held /
    width`` of them, the expectation under even routing); the
    prediction module's merge; and the head over the slice of the
    vocabulary held here, once for each prediction."""
    s = shape(config)
    h = s["hidden"]
    attention = (
        h * s["q_rank"] + s["q_rank"] * s["heads"] * s["head_dim"]
        + h * (s["kv_rank"] + s["rope_dim"])
        + s["kv_rank"] * s["heads"] * (s["nope_dim"] + s["v_head_dim"])
        + s["heads"] * s["v_head_dim"] * h
    )
    expert = s["ffn_matrices"] * h * s["ffn"]
    met = s["experts_per_token"] * s["experts_held"] / s["experts"]
    sparse = h * s["experts"] + (s["shared_experts"] + met) * expert
    dense = s["ffn_matrices"] * h * s["dense_ffn"]
    return (
        (s["layers"] + s["mtp_layers"]) * attention
        + s["dense_layers"] * dense + _expert_blocks(s) * sparse
        + s["mtp_layers"] * 2 * h * h
        + (1 + s["mtp_layers"]) * h * s["vocab"]
    )


def attention_forward_flops_per_token(config, seq):
    """Scores (``seq x qk_head_dim`` operations a token and head,
    causal) and weighted values (``seq x v_head_dim``), over every
    block."""
    s = shape(config)
    return (
        1.0 * (s["layers"] + s["mtp_layers"]) * s["heads"]
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
    apart. Of the seven causal products four contract over or give
    ``qk_head_dim`` columns (q k^T twice, dq, dk) and three
    ``v_head_dim`` (p v, dv, dp); q, k, dq and dk are as wide as the
    first, v, o, dO and dv as the second, each read or written once a
    pass. The work of the mathematics: a kernel that pads v to q's
    width reads as a lower share, not as more work."""
    s = shape(config)
    blocks = s["layers"] + s["mtp_layers"]
    d, dv = s["head_dim"], s["v_head_dim"]
    flops = (
        1.0 * blocks * sequences * s["heads"] * seq * seq * (4 * d + 3 * dv)
    )
    a_column = sequences * seq * s["heads"] * 2
    return flops, float(blocks * a_column * (6 * d + 6 * dv))


def expert_matmul_step(config, tokens):
    """What the grouped expert matmuls of one training step must do
    for ``tokens`` tokens on this chip, over the stack's expert layers
    and the prediction module's block: ``(flops, bytes)``, as
    ``families/lfm2.py`` counts them, for the rows that fall on the
    experts held here: ``tokens x k x held / width``, the expectation
    under even routing (a seed's routing moves it), and the held
    experts' matrices. The shared expert is a plain matrix product,
    not a grouped one, and is not in it."""
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
    blocks = _expert_blocks(s)
    return float(blocks * flops), float(blocks * nbytes)
