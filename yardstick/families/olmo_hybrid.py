"""The ``olmo_hybrid`` family: configurations in Olmo-Hybrid's key
names (``OlmoHybridConfig``'s), run through ``models/llama.py`` with
its two kinds of operator, one a layer as ``layer_types`` names them:
``linear_attention``, the gated delta rule with ONE decay a head on
heads of ``linear_key_head_dim`` keys by ``linear_value_head_dim``
values behind ``linear_conv_kernel_dim``-tap convolutions (the
program's ``"gated_delta_net"``), and ``full_attention``, ungrouped
heads without positions, q and k normed over their whole projections;
dense SwiGLU blocks whose norms stand on the branches' results alone,
and an untied head. No JAX at import: the parent reads the counts.

``vocab_size`` is the slice of the vocabulary held here and
``layer_types`` the layers that are run (both, with
``num_hidden_layers``, in the file's ``reduced``): one pipeline stage
of the ``share`` group's ``stages``.

Counts, in ``counts.py``'s conventions: attention is causal and only
the ``full_attention`` layers have it; a linear layer's token meets
its five projections (q, k, v, the gate, the output) and the two a
head (the decay's and the step size's); the convolutions' taps and the
recurrence are no matrix products and count as nothing in
``train_flops_per_token`` (``delta_rule_step`` has the recurrence)."""

#: the program's operator for each of the source's names
OPERATORS = {"linear_attention": "gated_delta_net",
             "full_attention": "full_attention"}


def layer_types(config):
    """The program's operator of each layer that is run."""
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"] or set(types) - set(
            OPERATORS):
        raise ValueError(
            f"layer_types {types} for {config['num_hidden_layers']} "
            f"layers: one of {sorted(OPERATORS)} a layer"
        )
    return tuple(OPERATORS[t] for t in types)


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    want = dict(
        attention_bias=False, hidden_act="silu", tie_word_embeddings=False,
        rope_parameters={"rope_theta": None},
    )
    for key, value in want.items():
        if config[key] != value:
            raise ValueError(
                f"{key} {config[key]!r}: the family runs {value!r} "
                "(the source's), and nothing in models/llama.py takes "
                "another beside this stack"
            )
    assumed = config["assumed"]
    layers = config["num_hidden_layers"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=layers,
        layer_types=layer_types(config),
        rope_layout=(0,) * layers,  # rope_theta null: no position at all
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        qk_norm=True,  # Olmo's: an RMSNorm of the whole q and k
        post_norms="alone",  # the Olmo 2 and 3 block (assumed.block)
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=config["linear_allow_neg_eigval"],
        max_seq_len=traffic["seq"],
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
        embed_init_std=assumed["embed_init_std"],
        head_init_std=assumed["head_init_std"],
    )


def shape(config):
    types = layer_types(config)
    heads = config["num_attention_heads"]
    return {
        "hidden": config["hidden_size"],
        "ffn": config["intermediate_size"],
        "layers": config["num_hidden_layers"],
        "attention_layers": types.count("full_attention"),
        "linear_layers": types.count("gated_delta_net"),
        "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // heads,
        "linear_heads": config["linear_num_value_heads"],
        "linear_key_dim": config["linear_key_head_dim"],
        "linear_value_dim": config["linear_value_head_dim"],
        "taps": config["linear_conv_kernel_dim"],
        "vocab": config["vocab_size"],
        "ffn_matrices": 3,  # gate, up, down
    }


def matmul_params(config):
    """What a token is multiplied by in one forward pass: in an
    attention layer q, k, v and the output projection; in a linear
    layer q and k (hidden x heads x dk each), v, the gate and the
    output projection (hidden x heads x dv each) and the decay's and
    the step size's hidden x heads; in every layer the MLP's three
    matrices; and the head over the slice of the vocabulary held
    here."""
    s = shape(config)
    h, d = s["hidden"], s["head_dim"]
    attention = 2 * h * s["heads"] * d + 2 * h * s["kv_heads"] * d
    heads = s["linear_heads"]
    linear = (
        2 * h * heads * s["linear_key_dim"]
        + 3 * h * heads * s["linear_value_dim"] + 2 * h * heads
    )
    mlp = s["ffn_matrices"] * h * s["ffn"]
    return (
        s["attention_layers"] * attention + s["linear_layers"] * linear
        + s["layers"] * mlp + h * s["vocab"]
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
    attention layers only: one layer in four has scores, on ungrouped
    heads."""
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


def delta_rule_step(config, tokens):
    """What the gated delta rule of one training step must do for
    ``tokens`` tokens on this chip, over the linear layers: ``(flops,
    bytes)``, the recurrence's own whatever implements it.

    Operations, a token and head, with ``dk`` keys and ``dv`` values
    (a multiply-add 2): forward the decay of the state (``dk x dv``
    multiplies), ``S^T k`` (``2 dk dv``), the rank-one update (``2 dk
    dv``) and ``S^T q`` (``2 dk dv``), ``7 dk dv``; backward twice
    that, as a product's is: ``21 dk dv`` in all. No chunk, no solve,
    no padding, no entry state: those are an implementation's.

    Bytes, the least: forward q, k (``dk`` columns a head) and v
    (``dv``) read and o (``dv``) written at the operator's dtype
    (bf16), g and beta read, ONE float32 a head and position each;
    backward the five operands and the result's cotangent read, and
    the five gradients written at their operand's dtype."""
    s = shape(config)
    heads, dk, dv = (
        s["linear_heads"], s["linear_key_dim"], s["linear_value_dim"])
    flops = 21.0 * tokens * heads * dk * dv
    keys, values = 2 * tokens * heads * dk, 2 * tokens * heads * dv
    a_head = 4 * tokens * heads  # g or beta
    forward = 2 * keys + 2 * values + 2 * a_head
    backward = (
        2 * keys + 2 * values + 2 * a_head  # q, k, v, do, g, beta read
        + 2 * keys + values + 2 * a_head  # dq, dk, dv, dg, dbeta written
    )
    return (float(s["linear_layers"] * flops),
            float(s["linear_layers"] * (forward + backward)))
