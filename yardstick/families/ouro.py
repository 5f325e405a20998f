"""The ``ouro`` family: configurations in Ouro-2.6B's key names
(``OuroConfig``'s), run through ``models/llama.py`` as a looped stack:
dense blocks of four norms (an RMSNorm on each branch's input and on
its result ahead of the residual sum), ungrouped causal attention with
the rotary embedding, SwiGLU, the whole stack walked
``total_ut_steps`` times a step with one set of weights and the final
norm inside the loop, an exit gate a position, and a loss that weights
every pass's cross entropy by the exit distribution less an entropy
term. No JAX at import: the parent reads the counts.

Counts, in ``counts.py``'s conventions, are of what a token is
multiplied by: every layer's seven matrices once a pass and the head
once a pass (each pass's state goes through it for its own cross
entropy), so ``total_ut_steps`` times a dense decoder's; the attention
kernels run a pass and layer. The gate's ``hidden_size`` weights a
pass and the loss's few operations a position are not counted."""

from yardstick import counts


def passes(config):
    """How often the stack is walked: the source's own key, which the
    family refuses to guess."""
    if "total_ut_steps" not in config:
        raise ValueError(
            "total_ut_steps is not given: the family runs its layers "
            "that many times a step and does not assume a number"
        )
    steps = config["total_ut_steps"]
    if not (isinstance(steps, int) and steps >= 1):
        raise ValueError(f"total_ut_steps {steps!r}: a whole number >= 1")
    return steps


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    want = dict(
        sliding_window=None, use_sliding_window=False, rope_scaling=None,
        hidden_act="silu", tie_word_embeddings=False,
    )
    for key, value in want.items():
        if config[key] != value:
            raise ValueError(
                f"{key} {config[key]!r}: the family runs {value!r} "
                "(the source's), and nothing here passes another on"
            )
    # the list is the source's, whole; the layers that are run are its
    # first ``num_hidden_layers`` entries
    types = config["layer_types"][:config["num_hidden_layers"]]
    if (len(types) != config["num_hidden_layers"]
            or set(types) != {"full_attention"}):
        raise ValueError(
            f"layer_types {types}: 'full_attention' for each of the "
            f"{config['num_hidden_layers']} layers that are run"
        )
    assumed = config["assumed"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=traffic["seq"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
        post_norms=True,
        total_ut_steps=passes(config),
        exit_entropy_weight=assumed["entropy_weight"],
        embed_init_std=assumed["embed_init_std"],
        # None: the program's own, hidden_size ** -0.5
        head_init_std=assumed.get("head_init_std"),
    )


def shape(config):
    return {
        "hidden": config["hidden_size"],
        "ffn": config["intermediate_size"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "vocab": config["vocab_size"],
        "ffn_matrices": 3,  # gate, up, down
    }


def matmul_params(config):
    """What a token is multiplied by in one forward pass of a step:
    the layers' matrices and the head, each once a pass of the loop."""
    return passes(config) * counts.dense_matmul_params(shape(config))


def attention_forward_flops_per_token(config, seq):
    """Scores and weighted values, causal, every layer of every pass:
    the dense count (``counts.py``'s own, under the wrapper that finds
    this one first) a pass. ``counts.train_flops_per_token`` sums it
    with ``matmul_params`` above, so a trained token's count is the
    dense decoder's ``total_ut_steps`` times over."""
    return passes(config) * (
        counts.attention_forward_flops_per_token.__wrapped__(config, seq)
    )


def attention_kernel_step(config, sequences, seq):
    """``(flops, bytes)`` of a step's attention kernels: the dense
    decoder's seven causal products and operands read and written
    once, a layer and pass."""
    flops, nbytes = counts.attention_kernel_step.__wrapped__(
        config, sequences, seq
    )
    return passes(config) * flops, passes(config) * nbytes
