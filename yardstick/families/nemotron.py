"""The ``nemotron`` family: configurations in Nemotron-3's key names
(``NemotronHConfig``'s, ``model_type`` ``nemotron_h``), run through
``models/llama.py`` as a stack of blocks of one branch, ``x +
branch(RMSNorm(x))``, the branch of layer ``l`` named by
``hybrid_override_pattern[l]``: ``M`` a Mamba-2 mixer (one input
projection ``[z | x | B | C | dt]``, a four-tap convolution with a
bias, the state-space scan of ``ops/ssd.py``, the gate ``silu(z)``
ahead of an RMSNorm a group), ``*`` grouped-query attention without
positions, ``E`` experts without a gate matrix (``relu2``) in a
latent ``moe_latent_size`` wide, routed by sigmoid scores of the
stream with a selection bias and a factor, beside a shared expert on
the stream itself, of which this chip holds a share; one multi-token
prediction module of the sublayers ``mtp_hybrid_override_pattern``
names; an untied head. No JAX at import: the parent reads the counts.

``n_routed_experts`` is the number of experts held here,
``vocab_size`` the slice of the vocabulary held here and
``hybrid_override_pattern`` the layers that are run (all listed in
the file's ``reduced``); the router's published width, and where the
held range starts, are in the file's ``share`` group.

Counts, in ``counts.py``'s conventions: a token of a mixer meets its
two projections (the convolution's taps and the recurrence are no
matrix products and count as nothing in ``train_flops_per_token``;
``ssd_step`` has the recurrence); attention is causal, in the ``*``
layers and the module's; a token of an expert layer meets the
router, the two latent projections, the shared expert whole and, of
the experts held here, ``k x held / width`` under even routing (an
expectation, stated as one); the module adds its merge (``2 hidden x
hidden``), its sublayers and a second product with the head over the
slice of the vocabulary held here."""

BRANCHES = "M*E"


def _pattern(config, key="hybrid_override_pattern"):
    pattern = config[key]
    if set(pattern) - set(BRANCHES):
        raise ValueError(
            f"{key} {pattern!r}: the branches run here are 'M', '*' "
            "and 'E' (a dense '-' layer is in no part of the source's "
            "pattern)"
        )
    return pattern


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    want = dict(
        n_group=1, topk_group=1, mlp_hidden_act="relu2",
        mamba_hidden_act="silu", attention_bias=False,
        mamba_proj_bias=False, mlp_bias=False, use_bias=False,
        tie_word_embeddings=False, num_nextn_predict_layers=1,
        sliding_window=None,
        num_hidden_layers=len(_pattern(config)),
        expand=(config["mamba_num_heads"] * config["mamba_head_dim"]
                // config["hidden_size"]),
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
    layers = config["num_hidden_layers"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=layers,
        hybrid_override_pattern=_pattern(config),
        mtp_hybrid_override_pattern=_pattern(
            config, "mtp_hybrid_override_pattern"),
        # no rotary embedding (``assumed.positions``): rope_theta and
        # partial_rotary_factor are not read
        rope_layout=(0,) * layers,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        mamba_num_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        n_groups=config["n_groups"],
        ssm_state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"],
        chunk_size=config["chunk_size"],
        use_conv_bias=config["use_conv_bias"],
        max_seq_len=traffic["seq"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["layer_norm_epsilon"],
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
        num_experts=share["router_width"],
        moe_top_k=config["num_experts_per_tok"],
        # the source has no capacity: dropless, stated
        moe_capacity_factor=0.0,
        norm_topk_prob=config["norm_topk_prob"],
        moe_gate="sigmoid", use_expert_bias=True,
        moe_topk_norm_eps=assumed["topk_norm_eps"],
        moe_routed_scaling=float(config["routed_scaling_factor"]),
        moe_shared_experts=config["n_shared_experts"],
        moe_shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        moe_expert_act=config["mlp_hidden_act"], moe_expert_gated=False,
        moe_latent_size=config["moe_latent_size"],
        router_aux_loss_coef=assumed["router_aux_loss_coef"],
        router_z_loss_coef=assumed["router_z_loss_coef"],
        moe_first_expert_held=share["first_expert_held"],
        moe_experts_held=config["n_routed_experts"],
        embed_init_std=assumed["embed_init_std"],
        # None: the program's own, hidden_size ** -0.5
        head_init_std=assumed.get("head_init_std"),
        mtp_layers=config["num_nextn_predict_layers"],
        mtp_loss_weight=assumed["mtp_loss_weight"],
    )


def shape(config):
    pattern = _pattern(config)
    module = _pattern(config, "mtp_hybrid_override_pattern")
    return {
        "hidden": config["hidden_size"],
        "ffn": config["moe_intermediate_size"],  # one expert's width
        "shared_ffn": config["moe_shared_expert_intermediate_size"],
        "latent": config["moe_latent_size"],
        "layers": config["num_hidden_layers"],
        "ssm_layers": pattern.count("M"),
        "attention_layers": pattern.count("*"),
        "expert_layers": pattern.count("E"),
        "mtp_layers": config["num_nextn_predict_layers"],
        "mtp_attention_layers": module.count("*"),
        "mtp_expert_layers": module.count("E"),
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "ssm_heads": config["mamba_num_heads"],
        "ssm_head_dim": config["mamba_head_dim"],
        "ssm_groups": config["n_groups"],
        "ssm_state": config["ssm_state_size"],
        "taps": config["conv_kernel"],
        "vocab": config["vocab_size"],
        "ffn_matrices": 2,  # up, down: no gate
        "experts": config["share"]["router_width"],
        "experts_held": config["n_routed_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "shared_experts": config["n_shared_experts"],
    }


def _layer_params(s):
    """``(a mixer's, an attention layer's, an expert layer's)``
    weights that a token is multiplied by."""
    h, d = s["hidden"], s["head_dim"]
    inner = s["ssm_heads"] * s["ssm_head_dim"]
    # [z | x | B | C | dt] in, the inner columns out
    mixer = h * (
        2 * inner + 2 * s["ssm_groups"] * s["ssm_state"] + s["ssm_heads"]
    ) + inner * h
    attention = 2 * h * s["heads"] * d + 2 * h * s["kv_heads"] * d
    met = s["experts_per_token"] * s["experts_held"] / s["experts"]
    experts = (
        h * s["experts"] + 2 * h * s["latent"]
        + s["shared_experts"] * s["ffn_matrices"] * h * s["shared_ffn"]
        + met * s["ffn_matrices"] * s["latent"] * s["ffn"]
    )
    return mixer, attention, experts


def matmul_params(config):
    """What a token is multiplied by in one forward pass: in a mixer
    its input and output projections; in an attention layer q, k, v
    and the output projection; in an expert layer the router, the two
    latent projections, the shared expert's two matrices and the
    experts held here that a token meets (``k x held / width`` of
    them, the expectation under even routing, two matrices of latent
    x width each); the head over the slice of the vocabulary held
    here; and the prediction module: its merge, its sublayers and the
    head again."""
    s = shape(config)
    h = s["hidden"]
    mixer, attention, experts = _layer_params(s)
    head = h * s["vocab"]
    module = s["mtp_layers"] * (
        2 * h * h + s["mtp_attention_layers"] * attention
        + s["mtp_expert_layers"] * experts + head
    )
    return (
        s["ssm_layers"] * mixer + s["attention_layers"] * attention
        + s["expert_layers"] * experts + head + module
    )


def _attention_layers(s):
    return s["attention_layers"] + s["mtp_layers"] * s["mtp_attention_layers"]


def attention_forward_flops_per_token(config, seq):
    """Scores and weighted values, causal, over the attention layers
    and the module's: ``seq x head_dim`` operations a product, token
    and head."""
    s = shape(config)
    return 2.0 * _attention_layers(s) * s["heads"] * s["head_dim"] * seq


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
    stack's attention layers and the prediction module's."""
    s = shape(config)
    d, layers = s["head_dim"], _attention_layers(s)
    flops = 7.0 * layers * sequences * s["heads"] * seq * seq * d
    q_like = sequences * seq * s["heads"] * d * 2
    kv_like = sequences * seq * s["kv_heads"] * d * 2
    return flops, float(layers * (6 * q_like + 6 * kv_like))


def ssd_step(config, tokens):
    """What the state-space scans of one training step must do for
    ``tokens`` tokens on this chip, over the mixers: ``(flops,
    bytes)``, the recurrence's own whatever implements it.

    Operations, a token and head, with ``p`` values and ``n`` states
    (a multiply-add 2): forward the decay of the state (``p x n``
    multiplies), the write ``Delta x B^T`` (``2 p n``) and the read
    ``S C`` (``2 p n``), ``5 p n``; backward twice that, as a
    product's is: ``15 p n`` in all. No chunk, no mask, no entry
    states: those are an implementation's.

    Bytes, the least: forward ``x`` read and ``o`` written at the
    operator's dtype (bf16), ``B`` and ``C`` read (a group's, once for
    its heads) and ``Delta`` in float32 (a number a head); backward
    the four operands and the result's cotangent read, and the four
    gradients written at their operand's dtype."""
    s = shape(config)
    heads, p, n = s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"]
    flops = 15.0 * tokens * heads * p * n
    x_like = 2 * tokens * heads * p
    bc_like = 2 * tokens * s["ssm_groups"] * n
    dt_like = 4 * tokens * heads
    forward = 2 * x_like + 2 * bc_like + dt_like
    backward = (
        2 * x_like + 2 * bc_like + dt_like  # read
        + x_like + 2 * bc_like + dt_like  # written
    )
    return (float(s["ssm_layers"] * flops),
            float(s["ssm_layers"] * (forward + backward)))
