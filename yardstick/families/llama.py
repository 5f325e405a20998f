"""The ``llama`` family: configurations in ``LlamaForCausalLM``'s or
``MistralForCausalLM``'s key names, run through ``models/llama.py``.
No JAX at import: the parent reads the counts."""

from yardstick import counts


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    heads = config["num_attention_heads"]
    if config["hidden_size"] != heads * config["head_dim"]:
        raise ValueError(
            "models/llama.py derives the head size from the "
            "hidden size: this configuration's differs"
        )
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=config["num_key_value_heads"],
        max_seq_len=traffic["seq"],
        rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
    )


def shape(config):
    heads = config["num_attention_heads"]
    return {
        "hidden": config["hidden_size"],
        "ffn": config["intermediate_size"],
        "layers": config["num_hidden_layers"],
        "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get(
            "head_dim", config["hidden_size"] // heads
        ),
        "vocab": config["vocab_size"],
        "ffn_matrices": 3,  # gate, up, down
    }


def matmul_params(config):
    return counts.dense_matmul_params(shape(config))
