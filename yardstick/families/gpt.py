"""The ``gpt`` family: configurations in ``GPT2LMHeadModel``'s key
names, run through ``models/gpt.py``. No JAX at import: the parent
reads the counts."""

from yardstick import counts


def _inner(config):
    # GPT-2's rule where the source leaves it null
    return config["n_inner"] or 4 * config["n_embd"]


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["n_embd"],
        intermediate_size=_inner(config),
        num_layers=config["n_layer"],
        num_heads=config["n_head"],
        max_seq_len=config["n_positions"],
        norm_eps=config["layer_norm_epsilon"],
        tie_lm_head=config["tie_word_embeddings"],
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
    )


def shape(config):
    heads = config["n_head"]
    return {
        "hidden": config["n_embd"],
        "ffn": _inner(config),
        "layers": config["n_layer"],
        "heads": heads,
        "kv_heads": heads,
        "head_dim": config["n_embd"] // heads,
        "vocab": config["vocab_size"],
        "ffn_matrices": 2,  # fc, proj
    }


def matmul_params(config):
    return counts.dense_matmul_params(shape(config))
