"""The ``sala`` family: configurations in MiniCPM-SALA's key names
(``MiniCPMSALAConfig``'s), run through ``models/llama.py`` with its
two kinds of operator, one a layer as ``mixer_types`` names them:
``minicpm4``, grouped-query attention without positions over the key
blocks each query selects (InfLLM-v2, ops/sparse_attention.py), and
``lightning-attn``, linear attention with a fixed decay a head on the
state-space scan (ops/ssd.py); a norm on each head's q and k, a
sigmoid gate on each operator's result, dense SwiGLU blocks, and the
three scalar factors (``scale_emb``, ``scale_depth``,
``dim_model_base``). No JAX at import: the parent reads the counts.

``vocab_size`` is the slice of the vocabulary held here and
``mixer_types`` the layers that are run (both, with
``num_hidden_layers``, in the file's ``reduced``); the depth that
``scale_depth`` is divided by stays the published one
(``assumed.scale_depth_layers``). The selection's sizes, which the
source keeps in a ``sparse_config`` the catalog's row lacks, are
``assumed.sparse_config``.

Counts, in ``counts.py``'s conventions. Attention is causal and only
the ``minicpm4`` layers have it, over the keys of the blocks a query
selects: query ``t`` sees ``min(t // block + 1, topk)`` blocks, its
own only up to itself (``selected_keys``); the forced blocks are
among the ``topk``, so the count does not depend on which are picked.
The selection's own scores (a query's heads against the compressed
keys it can see) are forward work that no gradient passes: counted
once, not three times. A lightning layer's token meets its four
projections and the output's; its recurrence is no matrix product and
counts as nothing in ``train_flops_per_token`` (``ssd_step`` has it).
"""

MIXERS = {"minicpm4": "sparse_attention",
          "lightning-attn": "lightning_attention"}


def layer_types(config):
    """The operator of each layer that is run, from ``mixer_types``."""
    mixers = config["mixer_types"]
    if len(mixers) != config["num_hidden_layers"] or set(mixers) - set(MIXERS):
        raise ValueError(
            f"mixer_types {mixers} for {config['num_hidden_layers']} "
            f"layers: one of {sorted(MIXERS)} a layer"
        )
    return tuple(MIXERS[m] for m in mixers)


def program_config(config, traffic):
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    want = dict(
        qk_norm=True, use_output_gate=True, use_output_norm=True,
        attn_use_output_gate=True, attention_bias=False,
        hidden_act="silu", tie_word_embeddings=False,
        lightning_scale="1/sqrt(d)", lightning_nkv=config["lightning_nh"],
    )
    for key, value in want.items():
        if config[key] != value:
            raise ValueError(
                f"{key} {config[key]!r}: the family runs {value!r} "
                "(the source's), and nothing in models/llama.py takes "
                "another beside this stack"
            )
    assumed = config["assumed"]
    sparse = assumed["sparse_config"]
    types = layer_types(config)
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        layer_types=types,
        # a layer rotates q and k where its kind's key says so
        rope_layout=tuple(
            int(config["attn_use_rope"] if t == "sparse_attention"
                else config["lightning_use_rope"]) for t in types),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        qk_head_norm=True,  # qk_norm: an RMSNorm on each head's q and k
        attn_out_gate=True,  # attn_use_output_gate
        lightning_num_heads=config["lightning_nh"],
        lightning_head_dim=config["lightning_head_dim"],
        sparse_block_size=sparse["block_size"],
        sparse_kernel_size=sparse["kernel_size"],
        sparse_kernel_stride=sparse["kernel_stride"],
        sparse_topk=sparse["topk"],
        sparse_window_size=sparse["window_size"],
        sparse_init_blocks=sparse["init_blocks"],
        sparse_dense_len=sparse["dense_len"],
        scale_emb=float(config["scale_emb"]),
        scale_depth=float(config["scale_depth"]),
        scale_depth_layers=assumed["scale_depth_layers"],
        dim_model_base=config["dim_model_base"],
        max_seq_len=traffic["seq"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]), remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
        embed_init_std=assumed["embed_init_std"],
        head_init_std=assumed["head_init_std"],
    )


def shape(config):
    types = layer_types(config)
    sparse = config["assumed"]["sparse_config"]
    return {
        "hidden": config["hidden_size"],
        "ffn": config["intermediate_size"],
        "layers": config["num_hidden_layers"],
        "attention_layers": types.count("sparse_attention"),
        "lightning_layers": types.count("lightning_attention"),
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "lightning_heads": config["lightning_nh"],
        "lightning_head_dim": config["lightning_head_dim"],
        "vocab": config["vocab_size"],
        "ffn_matrices": 3,  # gate, up, down
        "block": sparse["block_size"],
        "topk": sparse["topk"],
        "kernel": sparse["kernel_size"],
        "stride": sparse["kernel_stride"],
        "dense_len": sparse["dense_len"],
    }


def matmul_params(config):
    """What a token is multiplied by in one forward pass: in a
    ``minicpm4`` layer q, the gate and the output projection (hidden
    x heads x d each) and k and v (hidden x kv_heads x d each); in a
    lightning layer q, k, v, the gate and the output projection
    (hidden x heads x d each); in every layer the three matrices of
    ``intermediate_size``; and the head over the slice of the
    vocabulary held here."""
    s = shape(config)
    h = s["hidden"]
    mlp = s["ffn_matrices"] * h * s["ffn"]
    attention = (
        3 * h * s["heads"] * s["head_dim"]
        + 2 * h * s["kv_heads"] * s["head_dim"]
    )
    lightning = 5 * h * s["lightning_heads"] * s["lightning_head_dim"]
    return (
        s["attention_layers"] * (attention + mlp)
        + s["lightning_layers"] * (lightning + mlp) + h * s["vocab"]
    )


def selected_keys(config, seq):
    """The keys that the queries of one sequence of ``seq`` positions
    see in a ``minicpm4`` layer, summed over the queries: query ``t``
    its ``min(t // block + 1, topk)`` selected blocks, the own one up
    to itself; every earlier key on a sequence within ``dense_len``."""
    s = shape(config)
    if seq <= s["dense_len"]:
        return seq * (seq + 1) // 2
    block = s["block"]
    return sum(
        (min(t // block + 1, s["topk"]) - 1) * block + t % block + 1
        for t in range(seq)
    )


def visible_compressed_keys(config, seq):
    """The compressed keys that the queries of one sequence see,
    summed over the queries: key ``j`` from query ``stride j + kernel
    - 1`` on; none on a sequence within ``dense_len``."""
    s = shape(config)
    if seq <= s["dense_len"]:
        return 0
    return sum(
        max(0, (t - s["kernel"] + 1) // s["stride"] + 1) for t in range(seq)
    )


def attention_forward_flops_per_token(config, seq):
    """Scores and weighted values over the selected keys (``2 x d``
    operations a product, head and key), over the ``minicpm4`` layers;
    a mean over the sequence's positions."""
    s = shape(config)
    return (
        4.0 * s["attention_layers"] * s["heads"] * s["head_dim"]
        * selected_keys(config, seq) / seq
    )


def selection_flops_per_token(config, seq):
    """The selection's own scores, forward only: a query's heads
    against the compressed keys it sees."""
    s = shape(config)
    return (
        2.0 * s["attention_layers"] * s["heads"] * s["head_dim"]
        * visible_compressed_keys(config, seq) / seq
    )


def train_flops_per_token(config, seq):
    """Forward and backward, no recomputation; the selection's scores
    once, since no gradient passes them."""
    forward = (
        2.0 * matmul_params(config)
        + attention_forward_flops_per_token(config, seq)
    )
    return 3.0 * forward + selection_flops_per_token(config, seq)


def attention_kernel_step(config, sequences, seq):
    """What the attention over the selected blocks of one training
    step must do for ``sequences`` sequences on one chip, over the
    ``minicpm4`` layers: ``(flops, bytes)``, the selected keys' work
    whatever implements it. Operations: the seven products of
    ``counts.attention_kernel_step`` (two forward, five backward),
    each ``2 x d`` a head and selected key (``selected_keys``).
    Bytes, the least: forward q, k, v read and o written, backward q,
    k, v, o, dO read and dq, dk, dv written, in bf16, and the
    selection once a pass at a bit a query, kv head and block."""
    s = shape(config)
    d, layers = s["head_dim"], s["attention_layers"]
    flops = (
        7.0 * 2 * d * layers * sequences * s["heads"]
        * selected_keys(config, seq)
    )
    q_like = sequences * seq * s["heads"] * d * 2
    kv_like = sequences * seq * s["kv_heads"] * d * 2
    selection = 0
    if seq > s["dense_len"]:
        selection = sequences * seq * s["kv_heads"] * (seq // s["block"]) // 8
    return flops, float(
        layers * (6 * q_like + 6 * kv_like + 2 * selection))


def ssd_step(config, tokens):
    """What the lightning layers' scans of one training step must do
    for ``tokens`` tokens on this chip: ``(flops, bytes)``, the
    recurrence's own whatever implements it, as ``families/nemotron.py
    ssd_step`` counts a state-space scan, with one head a group, ``p``
    values and ``n = p`` states a head: ``15 p n`` operations a token
    and head (forward the decay of the state, the write and the read,
    ``5 p n``; backward twice that). Bytes, the least: forward v, k
    and q read and o written, backward those and the cotangent read
    and three gradients written, in bf16. The layer has no step
    ``Delta`` and no skip ``D``: neither is counted."""
    s = shape(config)
    heads, p = s["lightning_heads"], s["lightning_head_dim"]
    flops = 15.0 * tokens * heads * p * p
    column = 2 * tokens * heads * p
    forward, backward = 4 * column, 4 * column + 3 * column
    return (float(s["lightning_layers"] * flops),
            float(s["lightning_layers"] * (forward + backward)))
