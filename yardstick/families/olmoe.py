"""The ``olmoe`` family: configurations in ``OlmoeForCausalLM``'s key
names, run through ``models/llama.py`` with its q/k norms and its
dropless routed experts. No JAX at import: the parent reads the
counts.

The source's ``intermediate_size`` is the width of one expert (it has
no key of its own for it); a token meets the router and
``num_experts_per_tok`` of the ``num_experts`` experts, never a
shared one."""


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    if config.get("clip_qkv") is not None or config.get("rope_scaling"):
        raise ValueError(
            "models/llama.py neither clips q, k, v nor scales the "
            "rotary embedding: this configuration asks for one"
        )
    assumed = config["assumed"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        max_seq_len=traffic["seq"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
        num_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        # the source has no capacity: dropless, stated
        moe_capacity_factor=0.0,
        norm_topk_prob=config["norm_topk_prob"],
        router_aux_loss_coef=assumed["router_aux_loss_coef"],
        router_z_loss_coef=assumed["router_z_loss_coef"],
        qk_norm=True,  # every OlmoeAttention has q_norm and k_norm
    )


def shape(config):
    from yardstick.families import llama

    return {
        # the llama family's names; "ffn" is one expert's width
        **llama.shape(config),
        "experts": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
    }


def matmul_params(config):
    """Attention's four matrices, the router, and the experts a token
    is routed to (not all of them), a layer; and the head."""
    s = shape(config)
    h, d = s["hidden"], s["head_dim"]
    per_layer = (
        h * s["heads"] * d + 2 * h * s["kv_heads"] * d
        + s["heads"] * d * h
        + h * s["experts"]
        + s["experts_per_token"] * s["ffn_matrices"] * h * s["ffn"]
    )
    return s["layers"] * per_layer + h * s["vocab"]


def expert_matmul_step(config, tokens):
    """What the grouped expert matmuls of one training step must do
    for ``tokens`` tokens on one chip that holds every expert, all
    layers: ``(flops, bytes)``.

    Operations: each of the ``tokens x experts_per_token`` rows meets
    three ``hidden x ffn`` matrices; forward once, backward twice (the
    rows' gradient and the weights'), no recomputation.

    Bytes, bf16: every expert's three matrices read in the forward
    pass, read again in the backward pass and their gradients written;
    and for each of the three products its operand and result rows
    once forward, and backward the result's gradient and the operand
    read and the operand's gradient written."""
    s = shape(config)
    h, m = s["hidden"], s["ffn"]
    rows = tokens * s["experts_per_token"]
    flops = 3 * 2 * rows * s["ffn_matrices"] * h * m
    weights = 3 * s["experts"] * s["ffn_matrices"] * h * m
    # (operand width, result width) of gate, up, down
    per_row = sum(
        (a + b) + (b + a + a) for a, b in ((h, m), (h, m), (m, h))
    )
    nbytes = 2 * (weights + rows * per_row)
    return float(s["layers"] * flops), float(s["layers"] * nbytes)
