"""The ``smallthinker`` family: configurations in
``SmallThinkerForCausalLM``'s key names, run through
``models/llama.py`` with its layer pattern (full attention without
any position signal, and windowed attention with the rotary
embedding, by ``sliding_window_layout`` and ``rope_layout``), its
router on the block's input and its ReLU-gated dropless experts, of
which this chip holds a share. No JAX at import: the parent reads the
counts.

``moe_num_primary_experts`` is the number of experts held here and
``vocab_size`` the slice of the vocabulary held here (both listed in
the file's ``reduced``); the router's published width, and where the
held ranges start, are in the file's ``share`` group. The layouts are
the source's whole lists, of which the first ``num_hidden_layers``
entries are run.

Counts, in ``counts.py``'s conventions: a query sees the keys inside
its band, so a head of a full layer has ``s^2 / 2`` live pairs a
sequence and one of a windowed layer ``W^2 / 2 + (s - W) W`` (for
``s >= W``); a product over the pairs costs ``2 x head_dim x pairs``
operations, two products forward and seven forward and backward. A
token meets, of the experts held here, ``k x held / width`` under
even routing: an expectation, stated as one."""


def _layouts(config):
    n = config["num_hidden_layers"]
    return (tuple(config["sliding_window_layout"][:n]),
            tuple(config["rope_layout"][:n]))


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    if config.get("rope_scaling"):
        raise ValueError(
            "models/llama.py does not scale the rotary embedding: "
            "this configuration asks for it"
        )
    if not config["moe_primary_router_apply_softmax"]:
        raise ValueError(
            "parallel/moe.py routes by a softmax over the router's "
            "logits: this configuration asks for another gate"
        )
    assumed, share = config["assumed"], config["share"]
    windows, ropes = _layouts(config)
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_ffn_hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=traffic["seq"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
        num_experts=share["router_width"],
        moe_top_k=config["moe_num_active_primary_experts"],
        # the source has no capacity: dropless, stated
        moe_capacity_factor=0.0,
        norm_topk_prob=config["norm_topk_prob"],
        router_aux_loss_coef=assumed["router_aux_loss_coef"],
        router_z_loss_coef=assumed["router_z_loss_coef"],
        sliding_window_size=config["sliding_window_size"],
        sliding_window_layout=windows, rope_layout=ropes,
        moe_router_input="block_input", moe_expert_act="relu",
        moe_first_expert_held=share["first_expert_held"],
        moe_experts_held=config["moe_num_primary_experts"],
        embed_init_std=assumed["embed_init_std"],
    )


def shape(config):
    windows, ropes = _layouts(config)
    return {
        "hidden": config["hidden_size"],
        "ffn": config["moe_ffn_hidden_size"],  # one expert's width
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "vocab": config["vocab_size"],
        "ffn_matrices": 3,  # gate, up, down
        "experts": config["share"]["router_width"],
        "experts_held": config["moe_num_primary_experts"],
        "experts_per_token": config["moe_num_active_primary_experts"],
        "window": config["sliding_window_size"],
        "sliding_window_layout": windows,
        "rope_layout": ropes,
    }


def matmul_params(config):
    """Attention's four matrices, the router, and the experts held
    here that a token meets (``k x held / width`` of them, the
    expectation under even routing), a layer; and the head over the
    slice of the vocabulary held here."""
    s = shape(config)
    h, d = s["hidden"], s["head_dim"]
    met = s["experts_per_token"] * s["experts_held"] / s["experts"]
    per_layer = (
        h * s["heads"] * d + 2 * h * s["kv_heads"] * d
        + s["heads"] * d * h
        + h * s["experts"]
        + met * s["ffn_matrices"] * h * s["ffn"]
    )
    return s["layers"] * per_layer + h * s["vocab"]


def live_pairs(config, seq):
    """(query, key) pairs inside the band, a head and sequence, summed
    over the layers: ``seq^2 / 2`` in a full layer, ``W^2 / 2 + (seq -
    W) W`` in a windowed one (``seq^2 / 2`` where ``seq <= W``)."""
    s = shape(config)
    w = min(s["window"], seq)
    full, windowed = seq * seq / 2, w * w / 2 + (seq - w) * w
    return sum(
        windowed if on else full for on in s["sliding_window_layout"]
    )


def attention_forward_flops_per_token(config, seq):
    """Scores and weighted values over the live pairs, all layers: two
    products of ``2 x head_dim`` operations a pair and head."""
    s = shape(config)
    return (
        2 * 2.0 * s["head_dim"] * s["heads"] * live_pairs(config, seq)
        / seq
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
    ``sequences`` sequences on one chip, all layers: ``(flops,
    bytes)``. Operations: seven products over the live pairs (two
    forward, five backward) of ``2 x head_dim`` operations a pair.
    Bytes as ``counts.attention_kernel_step`` has them: every operand
    read once and every result written once whatever the band."""
    s = shape(config)
    d = s["head_dim"]
    flops = (
        7 * 2.0 * d * s["heads"] * sequences * live_pairs(config, seq)
    )
    q_like = sequences * seq * s["heads"] * d * 2
    kv_like = sequences * seq * s["kv_heads"] * d * 2
    forward = 2 * q_like + 2 * kv_like
    backward = 4 * q_like + 4 * kv_like
    return flops, float(s["layers"] * (forward + backward))


def expert_matmul_step(config, tokens):
    """What the grouped expert matmuls of one training step must do
    for ``tokens`` tokens on this chip, all layers: ``(flops,
    bytes)``, as ``families/olmoe.py`` counts them, for the rows that
    fall on the experts held here: ``tokens x k x held / width``, the
    expectation under even routing (a seed's routing moves it), and
    the held experts' matrices."""
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
