"""The ``trinity`` family: configurations in Trinity-Mini's key names
(``AfmoeConfig``'s), run through ``models/llama.py`` with four norms a
block (one on each branch's result ahead of the residual sum), the
embedding times ``sqrt(hidden_size)``, a layer pattern of windowed
attention with the rotary embedding and full attention without any
position signal, an RMSNorm a head on q and k and a sigmoid gate on
attention's result in layers of both kinds, its leading dense layers,
and a sigmoid router that selects by a biased score, renormalises and
scales the weights, beside a shared expert and the dropless routed
ones, of which this chip holds a share; the trainer moves the
selection bias every step by the sign of the load's error
(``load_balance_coeff``). No JAX at import: the parent reads the
counts.

``num_experts`` is the number of experts held here and ``vocab_size``
the slice of the vocabulary held here (both listed in the file's
``reduced``); the router's published width, and where the held range
starts, are in the file's ``share`` group. ``layer_types`` holds the
layers that are run, one entry each.

Counts, in ``counts.py``'s conventions: a query sees the keys inside
its band, so a head of a full layer has ``s^2 / 2`` live pairs a
sequence and one of a windowed layer ``W^2 / 2 + (s - W) W`` (for
``s >= W``); a product over the pairs costs ``2 x head_dim x pairs``
operations, two products forward and seven forward and backward. The
output gate's matrix is a fifth projection of every layer. A token
meets, of the experts held here, ``k x held / width`` under even
routing (an expectation, stated as one), the shared expert whole, in
a leading dense layer the three matrices of ``intermediate_size``.
The bias rule's few thousand operations a layer are not counted."""

KINDS = ("sliding_attention", "full_attention")


def _windowed(config):
    """True for each layer that is run and attends within the window."""
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"] or set(types) - set(KINDS):
        raise ValueError(
            f"layer_types {types}: one of {KINDS} for each of the "
            f"{config['num_hidden_layers']} layers"
        )
    return tuple(kind == "sliding_attention" for kind in types)


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    groups = ("n_group", "topk_group", "num_expert_groups",
              "num_limited_groups")
    want = dict(
        dict.fromkeys(groups, 1), score_func="sigmoid", hidden_act="silu",
        rope_scaling=None, tie_word_embeddings=False,
    )
    for key, value in want.items():
        if config[key] != value:
            raise ValueError(
                f"{key} {config[key]!r}: the family runs {value!r} "
                "(the source's; a router of one group of one, where "
                "choosing the best groups first is the identity), and "
                "nothing in models/llama.py or parallel/moe.py takes "
                "another"
            )
    assumed, share = config["assumed"], config["share"]
    windowed = tuple(int(on) for on in _windowed(config))
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_dense_layers=config["num_dense_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=traffic["seq"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
        # a sliding layer rotates q and k, a full one gives them no
        # position at all
        sliding_window_size=config["sliding_window"],
        sliding_window_layout=windowed, rope_layout=windowed,
        qk_head_norm=True, attn_out_gate=True, post_norms=True,
        mup_enabled=config["mup_enabled"],
        num_experts=share["router_width"],
        moe_top_k=config["num_experts_per_tok"],
        # the source has no capacity: dropless, stated
        moe_capacity_factor=0.0,
        norm_topk_prob=config["route_norm"],
        moe_gate="sigmoid", use_expert_bias=True,
        moe_topk_norm_eps=assumed["topk_norm_eps"],
        moe_routed_scaling=config["route_scale"],
        moe_shared_experts=config["num_shared_experts"],
        moe_bias_update_rate=config["load_balance_coeff"],
        # the source balances by the bias alone: no auxiliary loss
        router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
        moe_first_expert_held=share["first_expert_held"],
        moe_experts_held=config["num_experts"],
        embed_init_std=assumed["embed_init_std"],
        # None: the program's own, hidden_size ** -0.5
        head_init_std=assumed.get("head_init_std"),
    )


def shape(config):
    return {
        "hidden": config["hidden_size"],
        "ffn": config["moe_intermediate_size"],  # one expert's width
        "dense_ffn": config["intermediate_size"],
        "layers": config["num_hidden_layers"],
        "dense_layers": config["num_dense_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "vocab": config["vocab_size"],
        "ffn_matrices": 3,  # gate, up, down
        "experts": config["share"]["router_width"],
        "experts_held": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "shared_experts": config["num_shared_experts"],
        "window": config["sliding_window"],
        "windowed": _windowed(config),
    }


def _expert_layers(s):
    return s["layers"] - s["dense_layers"]


def matmul_params(config):
    """What a token is multiplied by in one forward pass: in every
    layer attention's five matrices (q, k, v, the output gate's and
    out); in a leading dense layer three matrices of
    ``intermediate_size``, in an expert layer the router, the shared
    expert and the experts held here that a token meets (``k x held /
    width`` of them, the expectation under even routing); and the
    head over the slice of the vocabulary held here."""
    s = shape(config)
    h, d = s["hidden"], s["head_dim"]
    attention = 3 * h * s["heads"] * d + 2 * h * s["kv_heads"] * d
    expert = s["ffn_matrices"] * h * s["ffn"]
    met = s["experts_per_token"] * s["experts_held"] / s["experts"]
    sparse = h * s["experts"] + (s["shared_experts"] + met) * expert
    dense = s["ffn_matrices"] * h * s["dense_ffn"]
    return (
        s["layers"] * attention + s["dense_layers"] * dense
        + _expert_layers(s) * sparse + h * s["vocab"]
    )


def live_pairs(config, seq):
    """(query, key) pairs inside the band, a head and sequence, summed
    over the layers: ``seq^2 / 2`` in a full layer, ``W^2 / 2 + (seq -
    W) W`` in a windowed one (``seq^2 / 2`` where ``seq <= W``)."""
    s = shape(config)
    w = min(s["window"], seq)
    full, windowed = seq * seq / 2, w * w / 2 + (seq - w) * w
    return sum(windowed if on else full for on in s["windowed"])


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
    read once and every result written once whatever the band. The
    heads' norms and the output gate are fusions outside the kernels
    and not in it."""
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
