"""The ``jamba`` family: configurations in AI21 Jamba's key names
(``JambaConfig``'s, ``model_type`` ``jamba``), run through
``models/llama.py`` as a stack of two-branch blocks kept by
``layer_types``: layer ``l`` is ``full_attention`` where ``l %
attn_layer_period == attn_layer_offset`` and ``mamba`` elsewhere, a
Mamba-1 mixer (one projection ``[x | z]``, a four-tap convolution with
a bias over ``x``, a second projection ``[dt | B | C]`` off the
convolved ``x`` with an RMSNorm on each of the three, a low-rank step
with a bias, the selective scan of ``ops/selective_scan.py`` whose
decay differs by channel and by state, the gate ``silu(z)`` and no
norm past it); grouped-query attention without positions; a dense
SwiGLU in every block (``num_experts`` 1); a tied head. No JAX at
import: the parent reads the counts.

``num_hidden_layers`` is the layers that are run (the file's
``reduced``); the two keys that place attention are the source's own.

Counts, in ``counts.py``'s conventions: a token of a mixer meets its
four matrices (the convolution's taps and the recurrence are no matrix
products and count as nothing in ``train_flops_per_token``;
``selective_scan_step`` has the recurrence); attention is causal, in
the attention layers alone; the head is one product over the whole
vocabulary, tied or not."""


def layer_types(config):
    """The operator of each layer that is run."""
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return tuple(
        "full_attention" if l % period == offset else "mamba"
        for l in range(config["num_hidden_layers"])
    )


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    want = dict(
        num_experts=1, hidden_act="silu", mamba_proj_bias=False,
        mamba_conv_bias=True, tie_word_embeddings=True, sliding_window=None,
    )
    for key, value in want.items():
        if config[key] != value:
            raise ValueError(
                f"{key} {config[key]!r}: the family runs {value!r} "
                "(the source's: every feed-forward the one dense MLP, "
                "no bias on a mixer's projections and one on its "
                "convolution, a tied head, every "
                "earlier key), and nothing in models/llama.py takes "
                "another beside this stack"
            )
    types = layer_types(config)
    if "full_attention" not in types or "mamba" not in types:
        raise ValueError(
            f"attn_layer_period {config['attn_layer_period']} and "
            f"attn_layer_offset {config['attn_layer_offset']} over "
            f"{config['num_hidden_layers']} layers: the family runs "
            "both kinds of layer"
        )
    assumed = config["assumed"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        layer_types=types,
        # no rotary embedding (``assumed.positions``)
        rope_layout=(0,) * len(types),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        mamba_expand=config["mamba_expand"],
        mamba_d_state=config["mamba_d_state"],
        mamba_dt_rank=config["mamba_dt_rank"],
        mamba_d_conv=config["mamba_d_conv"],
        max_seq_len=traffic["seq"],
        norm_eps=config["rms_norm_eps"],
        tie_word_embeddings=True,
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
        embed_init_std=assumed["embed_init_std"],
    )


def shape(config):
    types = layer_types(config)
    return {
        "hidden": config["hidden_size"],
        "ffn": config["intermediate_size"],
        "layers": config["num_hidden_layers"],
        "attention_layers": types.count("full_attention"),
        "mamba_layers": types.count("mamba"),
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "channels": config["mamba_expand"] * config["hidden_size"],
        "states": config["mamba_d_state"],
        "dt_rank": config["mamba_dt_rank"],
        "taps": config["mamba_d_conv"],
        "vocab": config["vocab_size"],
        "ffn_matrices": 3,  # gate, up, down
    }


def matmul_params(config):
    """What a token is multiplied by in one forward pass: in a mixer
    ``[x | z]`` (hidden x 2 channels), ``[dt | B | C]`` (channels x
    (rank + 2 states)), the step (rank x channels) and the output
    projection (channels x hidden); in an attention layer q and the
    output projection (hidden x heads x d each) and k and v (hidden x
    kv_heads x d each); in every layer the three matrices of
    ``intermediate_size``; the head over the whole vocabulary."""
    s = shape(config)
    h, d = s["hidden"], s["channels"]
    mixer = (2 * h * d + d * (s["dt_rank"] + 2 * s["states"])
             + s["dt_rank"] * d + d * h)
    attention = 2 * h * s["head_dim"] * (s["heads"] + s["kv_heads"])
    mlp = s["ffn_matrices"] * h * s["ffn"]
    return (s["mamba_layers"] * mixer + s["attention_layers"] * attention
            + s["layers"] * mlp + h * s["vocab"])


def attention_forward_flops_per_token(config, seq):
    """Scores and weighted values, causal, over the attention layers:
    ``seq x head_dim`` operations a product, token and head."""
    s = shape(config)
    return 2.0 * s["attention_layers"] * s["heads"] * s["head_dim"] * seq


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
    attention layers alone."""
    s = shape(config)
    d, layers = s["head_dim"], s["attention_layers"]
    flops = 7.0 * layers * sequences * s["heads"] * seq * seq * d
    q_like = sequences * seq * s["heads"] * d * 2
    kv_like = sequences * seq * s["kv_heads"] * d * 2
    return flops, float(layers * (6 * q_like + 6 * kv_like))


def selective_scan_step(config, tokens):
    """What the selective scans of one training step must do for
    ``tokens`` tokens on this chip, over the mixers: ``(flops,
    bytes)``, the recurrence's own whatever implements it.

    Operations, a token, channel and state (a multiply-add 2): forward
    the decay's product ``Delta A`` (1), the state's multiply-add ``a
    h + .`` (2), the write ``(Delta x) B`` (1; ``Delta x`` itself is a
    channel's, one in ``n``) and the read ``h C`` summed (2): 6;
    backward twice that, as a product's is: 18 in all. No ``exp``, no
    chunk, no entry state, no second forward: those are an
    implementation's.

    Bytes, the least: forward ``x`` and ``Delta`` read and ``o``
    written at the operator's dtype (bf16: a kernel that is handed
    ``Delta`` in float32, as the program's is, moves more, and that is
    its own), ``B`` and ``C`` read once for all channels; backward
    those four and the result's cotangent read, and the four gradients
    written at the same widths. ``A``, ``D`` and their gradients are a
    leaf's size, not a token's, and are left out."""
    s = shape(config)
    d, n = s["channels"], s["states"]
    flops = 18.0 * tokens * d * n
    x_like, bc_like = 2 * tokens * d, 2 * tokens * n
    forward = 3 * x_like + 2 * bc_like
    backward = (
        3 * x_like + 2 * bc_like  # read
        + 2 * x_like + 2 * bc_like  # written
    )
    return (float(s["mamba_layers"] * flops),
            float(s["mamba_layers"] * (forward + backward)))
