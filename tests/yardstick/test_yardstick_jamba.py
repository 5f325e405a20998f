"""The ``jamba`` family: its program (models/llama.py as a stack of
two-branch blocks kept by ``layer_types``: thirteen Mamba-1 mixers
whose scan is ops/selective_scan.py's, one attention layer of a group
that is no power of two on a single key head, without positions, a
tied head) against ``references/jamba.py`` (the recurrence a position
at a time) at the tiny size, in the loss and in every leaf's gradient,
each term of the blocks showing when it is changed; its counts against
integers worked by hand; what the configuration's file states; the
benchmark's entries found by name."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import llama
from yardstick import cells, counts, reference, worker
from yardstick.families import jamba
from yardstick.layer_metrics import (
    selective_scan_ms, selective_scan_roofline_pct as share,
)

SEQ, SEQUENCES = 128, 4
NAME = "jamba2-3b-l14"
CELL = NAME + ".steady"
REFERENCE = os.path.join(cells.HERE, "references", "jamba.py")
TRAFFIC = {"seq": SEQ, "remat": "off", "loss_chunk": 0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _case(dtype, draw=True, sequences=SEQUENCES, seed=7):
    cfg_file = dict(config("tiny-jamba"), dtype=dtype)
    cfg = worker.program_config(cfg_file, TRAFFIC)
    params = llama.init_params(jax.random.key(2), cfg)
    if draw:
        params = drawn(params)
    tokens, targets = worker.SeededTokens(
        seed, SEQ, cfg_file["vocab_size"])(0, sequences)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    return cfg_file, cfg, params, batch


def drawn(params):
    """``params`` with every convolution bias drawn at 0.3, ``D`` and
    the three norms' scales at 1 +/- 0.5 (the program starts them at
    zero and one, where they change nothing), and the embedding, which
    is the head, at three times the tiny file's: over random targets a
    changed trunk moves the mean loss by a sum of mean zero over the
    positions, whose size goes with the logits' (the stream's first
    norm takes the rows' own size out again)."""
    keys = iter(jax.random.split(jax.random.key(3), 128))

    def draw(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else None
        if name == "mamba_conv_b":
            return 0.3 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if name in ("D", "mamba_dt_norm", "mamba_b_norm", "mamba_c_norm"):
            return leaf * jax.random.uniform(
                next(keys), leaf.shape, leaf.dtype, 0.5, 1.5)
        return leaf * 3.0 if name == "embed" else leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def entry(entries, name):
    """The entry of a list of ``BENCHMARK.json`` by its name, wherever
    it stands."""
    (found,) = [e for e in entries if e["name"] == name]
    return found


def test_program_config_takes_the_sources_keys():
    cfg = worker.program_config(
        config(NAME), {"seq": 8192, "remat": "minimal", "loss_chunk": 0})
    assert (cfg.hidden_size, cfg.intermediate_size) == (2560, 8192)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (20, 1, 128)
    assert (cfg.mamba_expand, cfg.mamba_d_state, cfg.mamba_dt_rank,
            cfg.mamba_d_conv) == (2, 16, 160, 4)
    assert cfg.mamba_widths == (5120, 16, 160)
    assert cfg.norm_eps == 1e-6 and cfg.tie_word_embeddings
    assert cfg.num_experts == 0 and cfg.mtp_layers == 0
    assert (cfg.vocab_size, cfg.num_layers, cfg.max_seq_len) == (
        65536, 14, 8192)
    assert cfg.rope_layout == (0,) * 14  # no rotary embedding
    assert cfg.layer_types == ("mamba",) * 7 + ("full_attention",) + (
        "mamba",) * 6
    lead, period = cfg.layer_plan()
    assert lead == () and len(period) == 14
    assert all(k.ffn == "dense" and not k.rope for k in period)
    assert llama.operator_layers(cfg) == {"mamba": 13, "full_attention": 1}
    assert cfg.embed_init_std == config(NAME)["assumed"]["embed_init_std"]
    # ISSUE 68's arithmetic, by the program's own count: a mixer's
    # four matrices, its taps and bias, A_log, the step's bias and D,
    # three norms and the block's; the MLP and its norm; attention's
    # four matrices and the block's norm
    mixer = (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
             + 5120 * 5 + 5120 * 16 + 2 * 5120 + 160 + 2 * 16 + 2560)
    mlp = 3 * 2560 * 8192 + 2560
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128 + 2560
    assert (mixer, mlp, attention) == (41_244_352, 62_917_120, 13_765_120)
    assert llama.param_count(cfg) == (
        13 * mixer + 14 * mlp + attention + 65536 * 2560 + 2560)
    assert llama.param_count(cfg) == 1_598_556_096  # 9.59 GB at 6 bytes
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 1_598_556_096
    assert shapes["period"][0]["mamba_in"].shape == (1, 2560, 10240)
    assert shapes["period"][0]["mamba_x"].shape == (1, 5120, 192)
    assert shapes["period"][0]["A_log"].shape == (1, 5120, 16)
    assert shapes["period"][7]["wk"].shape == (1, 2560, 128)
    assert "mamba_in" not in shapes["period"][7] and "lm_head" not in shapes


def test_float32_program_agrees_with_the_reference():
    cfg_file, cfg, params, batch = _case("float32")
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < 2e-5, (program, ref)


@pytest.mark.parametrize("seed", [5, 6])
def test_bf16_program_is_inside_the_chip_tolerance(seed):
    """As the program starts (the bias at zero, the scales at one):
    seeds 5-7 read 0.0001-0.0008 against the 0.003."""
    cfg_file, cfg, params, batch = _case("bfloat16", False, 8, seed=seed)
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < worker.REFERENCE_TOLERANCE


def test_remat_and_chunked_loss_change_nothing():
    cfg_file, cfg, params, batch = _case("float32")
    want = float(llama.next_token_loss(params, batch, cfg))
    for remat, chunk in (("minimal", 0), ("dots", 256)):
        other = worker.program_config(
            cfg_file, {"seq": SEQ, "remat": remat, "loss_chunk": chunk})
        got = jax.jit(
            lambda p, b: llama.next_token_loss(p, b, other))(params, batch)
        assert float(got) == pytest.approx(want, abs=2e-5), (remat, chunk)


def test_every_leafs_gradient_is_the_references():
    """The program's backward (JAX's through the plain chunked scan)
    against the reference differentiated position by position, leaf by
    leaf, ``A_log``, the step's bias, ``D`` and the three norms among
    them."""
    cfg_file, cfg, params, batch = _case("float32", sequences=2)
    got = jax.grad(lambda p: llama.next_token_loss(p, batch, cfg))(params)
    want = jax.grad(lambda p: reference.loss(cfg_file, p, *batch))(params)
    flat = jax.tree_util.tree_leaves_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-7, path


def edited(name, *pairs):
    """A scratch copy of the reference with ``pairs`` replaced."""
    with open(REFERENCE) as f:
        src = f.read()
    for old, new in pairs:
        assert src.count(old) == 1, old
        src = src.replace(old, new)
    spec = importlib.util.spec_from_loader(f"scratch_ref_{name}", None)
    module = importlib.util.module_from_spec(spec)
    exec(compile(src, name, "exec"), module.__dict__)
    return module


def _norm_left_out(name, columns):
    return ((
        f'rms_norm(low[..., {columns}], p["mamba_{name}_norm"], eps)\n',
        f"low[..., {columns}]\n"),)


#: the controls of ISSUE 68, as edits to the reference
CONTROLS = {
    # one rate a channel, its first state's: the SSD form
    "A constant over the states": ((
        '-jnp.exp(p["A_log"]), p["D"])\n',
        '-jnp.exp(jnp.broadcast_to(\n'
        '        p["A_log"][:, :1], p["A_log"].shape)), p["D"])\n'),),
    "no dt norm": _norm_left_out("dt", ":rank"),
    "no B norm": _norm_left_out("b", "rank:rank + n"),
    "no C norm": _norm_left_out("c", "rank + n:"),
    "no step bias": ((
        '    delta = jax.nn.softplus(dt @ p["mamba_dt"] + p["dt_bias"])\n',
        '    delta = jax.nn.softplus(dt @ p["mamba_dt"])\n'),),
    "no D": ((
        '        return h, jnp.einsum("bdn,bn->bd", h, c_t) + D * x_t\n',
        '        return h, jnp.einsum("bdn,bn->bd", h, c_t)\n'),),
    "no conv bias": ((
        "    return jax.nn.silu(c + bias)\n", "    return jax.nn.silu(c)\n"),),
    "no gate silu(z)": (("    o = o * jax.nn.silu(z)\n", ""),),
    "q and k rotated": ((
        '    return attention(q, k, v) @ p["wo"]\n',
        "    from yardstick.reference import rotate\n"
        "    return attention(rotate(q, 10000.0), rotate(k, 10000.0), v) "
        '@ p["wo"]\n'),),
    # a head of its own rows: the embedding's in another order
    "an untied head": ((
        '    head = params["embed"].T  #',
        '    head = params["embed"][::-1].T  #'),),
    # attention at layer 6, a mixer at 7 (``exchanged`` hands each the
    # other's leaves)
    "attention in layer 6's place": ((
        'config["attn_layer_offset"]\n',
        'config["attn_layer_offset"] - 1\n'),),
}
#: the reference in the nearest precision below the program's
#: bfloat16, throughout: every matrix, the embedding's rows (which are
#: the head), a branch's normed stream and the final normed stream
#: rounded to float8 (e4m3, a scale a tensor), as the program keeps
#: each in bfloat16; the sums and the recurrence in float32
FLOAT8 = (
    ('    x = embed(params["embed"], tokens)\n',
     '    x = q8(embed(params["embed"], tokens))\n'),
    ('    head = params["embed"].T  #',
     '    head = q8(params["embed"].astype(F32)).T  #'),
    ('    return mean_nll(final_rms(x, params["final_norm"], eps), head, '
     'targets)\n',
     '    return mean_nll(q8(final_rms(x, params["final_norm"], eps)), '
     'head, targets)\n'),
    ('ROWS = 256\n',
     'ROWS = 256\n\n\n'
     'def q8(a):\n'
     '    s = jnp.max(jnp.abs(a)) / 448.0\n'
     '    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s\n'),
    ('        p = layer(blocks, i)\n',
     '        p = layer(blocks, i)\n'
     '        p = {k: q8(v) if v.ndim > 1 and k != "A_log" else v\n'
     '             for k, v in p.items()}\n'),
    ('        y = rms_norm(x, p["attn_norm"], eps)\n',
     '        y = q8(rms_norm(x, p["attn_norm"], eps))\n'),
    ('        return x + mlp(rms_norm(x, p["mlp_norm"], eps), p)\n',
     '        return x + mlp(q8(rms_norm(x, p["mlp_norm"], eps)), p)\n'),
)


def exchanged(params, name):
    """``params`` with the attention layer's leaves and its
    neighbour's in each other's place, for the control that moves
    attention: the reference then finds each kind's leaves where it
    looks for them."""
    period = list(params["period"])
    period[6], period[7] = period[7], period[6]
    return {**params, "period": period}


@pytest.fixture(scope="module")
def float32_cases():
    """Two batches on the same weights, each with the program's
    loss."""
    cases = [_case("float32", seed=seed) for seed in (7, 8)]
    return [
        (case, float(llama.next_token_loss(case[2], case[3], case[1])))
        for case in cases
    ]


def most_off(changed, cases, term=""):
    """The larger |program - changed reference| of the batches."""
    return max(
        abs(program - float(changed.loss(
            cfg_file, exchanged(params, term) if "place" in term else params,
            *batch)))
        for (cfg_file, _, params, batch), program in cases
    )


@pytest.mark.parametrize("term", list(CONTROLS))
def test_a_changed_term_shows(term, float32_cases):
    """A reference with one term of a block altered is off by more
    than the chip's tolerance, in float32, where the unchanged pair
    agrees to 2e-5 (the bias, ``D``, the norms' scales and the
    embedding drawn: ``drawn``)."""
    difference = most_off(
        edited(term.split()[0], *CONTROLS[term]), float32_cases, term)
    # off by more than the tolerance, or no number at all
    assert not difference <= worker.REFERENCE_TOLERANCE, (term, difference)


def test_the_unchanged_reference_agrees_on_the_drawn_weights(float32_cases):
    assert most_off(edited("same"), float32_cases) < 2e-5


def test_the_reference_in_float8_shows(float32_cases):
    assert most_off(
        edited("float8", *FLOAT8), float32_cases
    ) > worker.REFERENCE_TOLERANCE


def test_reference_refuses_more_positions_than_the_source_declares():
    cfg_file, _, params, batch = _case("float32")
    with pytest.raises(ValueError):
        reference.loss({**cfg_file, "max_position_embeddings": 64},
                       params, *batch)


def test_reference_imports_no_line_of_the_program():
    with open(REFERENCE) as f:
        src = f.read()
    imports = [ln for ln in src.splitlines(True)
               if ln.startswith(("import ", "from "))]
    assert imports == [
        "import functools\n", "import jax\n", "import jax.numpy as jnp\n",
        "from yardstick.reference import (\n",
    ]
    body = src.split('"""', 2)[2]
    assert "dlrover_tpu" not in body and "selective_scan" not in body
    # a position at a time: no chunk, no kept states, no checkpoint
    assert "lax.scan" in body and "chunk" not in body
    assert "checkpoint" not in body and "rotate" not in body
    with open(os.path.join(cells.HERE, "families", "jamba.py")) as f:
        top = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top == []  # no JAX, nothing of the program, at import


def test_reference_recurrence_is_the_programs_scan():
    """The reference's position-by-position walk against the program's
    entry (ops/selective_scan.py) on operands of their own."""
    from dlrover_tpu.ops.selective_scan import selective_scan

    ref = edited("recurrence")
    keys = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(keys[0], (2, 96, 40))
    B, C = (jax.random.normal(key, (2, 96, 8)) for key in keys[1:3])
    delta = jax.nn.softplus(jax.random.normal(keys[3], (2, 96, 40)))
    A = -jnp.exp(jax.random.normal(keys[4], (40, 8)))
    D = jax.random.normal(keys[5], (40,))
    want = ref.recurrence(x, delta, B, C, A, D)
    got = selective_scan(x, delta, B, C, A, D)
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())


def test_program_config_refuses_what_it_does_not_pass_on():
    tiny = config("tiny-jamba")
    for key, other in (
            ("num_experts", 4), ("hidden_act", "gelu"),
            ("mamba_proj_bias", True), ("mamba_conv_bias", False),
            ("tie_word_embeddings", False),
            ("sliding_window", 64)):
        with pytest.raises(ValueError, match=key):
            worker.program_config({**tiny, key: other}, TRAFFIC)
    with pytest.raises(ValueError, match="attn_layer_offset"):
        worker.program_config({**tiny, "attn_layer_offset": 20}, TRAFFIC)
    moved = worker.program_config({**tiny, "attn_layer_offset": 3}, TRAFFIC)
    assert moved.layer_types.index("full_attention") == 3
    two = worker.program_config({**tiny, "num_hidden_layers": 28}, TRAFFIC)
    assert [i for i, t in enumerate(two.layer_types)
            if t == "full_attention"] == [7, 21]
    assert len(two.layer_plan()[1]) == 14  # the period, scanned twice


# -- the counts --------------------------------------------------------------

def test_jamba_counts_by_hand():
    c = config(NAME)
    s = jamba.shape(c)
    assert (s["layers"], s["mamba_layers"], s["attention_layers"]) == (
        14, 13, 1)
    assert (s["heads"], s["kv_heads"], s["head_dim"]) == (20, 1, 128)
    assert (s["channels"], s["states"], s["dt_rank"], s["taps"]) == (
        5120, 16, 160, 4)
    assert (s["hidden"], s["ffn"], s["vocab"], s["ffn_matrices"]) == (
        2560, 8192, 65536, 3)
    # in millions of weights met a token: a mixer's four matrices
    # 41.1, an MLP 62.9, the attention layer's 13.8, the head 167.8
    mixer = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    mlp, attention, head = 3 * 2560 * 8192, 2 * 2560 * 128 * 21, 2560 * 65536
    assert (mixer, mlp, attention, head) == (
        41_123_840, 62_914_560, 13_762_560, 167_772_160)
    want = 13 * mixer + 14 * mlp + attention + head
    assert jamba.matmul_params(c) == counts.matmul_params(c) == want
    assert want == 1_596_948_480
    attn = counts.attention_forward_flops_per_token(c, 8192)
    assert attn == 2 * 20 * 128 * 8192 == 41_943_040
    flops = counts.train_flops_per_token(c, 8192)
    assert flops == 3 * (2 * want + attn) == 9_707_520_000
    assert 8192 * flops == pytest.approx(79.5e12, rel=1e-3)
    assert 14 * mlp / want == pytest.approx(0.552, abs=1e-3)
    assert 13 * mixer / want == pytest.approx(0.335, abs=1e-3)
    assert head / want == pytest.approx(0.105, abs=1e-3)
    # in the whole model's 28 layers the head is 5.5%
    assert head / (2 * (want - head) + head) == pytest.approx(0.055, abs=1e-3)
    # the attention kernels: seven causal products, one layer, 20
    # heads on one
    kernel_flops, nbytes = counts.attention_kernel_step(c, 1, 8192)
    assert kernel_flops == 7 * 20 * 8192 * 8192 * 128
    assert nbytes == 6 * 8192 * (20 + 1) * 128 * 2
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(kernel_flops, nbytes, peak)
    assert bound == "compute"
    # the recurrence: 18 operations a token, channel and state; x,
    # Delta and o at two bytes forward with B and C once; backward
    # those and the cotangent read, four gradients written
    flops, nbytes = jamba.selective_scan_step(c, 8192)
    assert flops == 13 * 18 * 8192 * 5120 * 16
    x_like, bc_like = 2 * 8192 * 5120, 2 * 8192 * 16
    assert nbytes == 13 * (8 * x_like + 6 * bc_like)
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "memory"
    assert seconds == pytest.approx(0.010677, rel=1e-3)
    # by operations the scan is nothing: 1.5 MFLOP a token and layer,
    # 0.2% of what the matrices take
    assert flops / (8192 * counts.train_flops_per_token(c, 8192)) < 0.003


def test_the_share_of_a_roofline_stays_under_100_at_the_kernels_least():
    """``selective_scan_roofline_pct`` with the kernels at the least
    time they could take: what the kernels move is more than the
    count's least bytes (``Delta`` in float32 both ways, the backward's
    entry states written and read, the forward run twice under
    ``minimal``), so the share is under 100 there, and a reading above
    it is a wrong count."""
    c = config(NAME)
    cell, _, traffic = cells.load_cell(CELL)
    peak = cells.peak_of("TPU v5 lite")
    _, nbytes = jamba.selective_scan_step(c, 8192)
    x_like = 2 * 8192 * 5120
    wider = 13 * 3 * x_like  # Delta twice and its gradient, 2 bytes more
    forward = 13 * 4 * x_like  # a second forward: x, o and Delta's four
    states = 13 * 2 * 128 * 16 * 5120 * 4
    least = (nbytes + wider + forward + states) / peak["hbm_bytes_per_s"]
    run = {"trace": {"steps": 4, "ops": [
        ["selective_scan.7", 3 * least, 12], ["selective_scan", least, 4]]},
           "peak": peak, "config": c, "traffic": traffic, "cell": cell}
    got = share.read(run)
    assert 40 < got < 100, got
    assert selective_scan_ms.read(run) == pytest.approx(1e3 * least)
    assert share.read({**run, "trace": None}) is None
    no_kernel = {"steps": 4, "ops": [["fusion.1", 1.0, 3],
                                     ["selective_scan_like.3", 1.0, 3],
                                     ["ssd.3", 1.0, 3]]}
    assert share.read({**run, "trace": no_kernel}) is None
    assert selective_scan_ms.read({**run, "trace": no_kernel}) is None
    # a family without the operator has nothing to read, and the other
    # scan's reader does not take these kernels for its own
    other = config("nemotron-3-super-120b-a12b-ep64")
    assert share.read({**run, "config": other}) is None
    from yardstick.layer_metrics import ssd_ms

    assert ssd_ms.read(run) is None


# -- the files ---------------------------------------------------------------

def test_every_published_number_is_run_but_the_cut():
    c = config(NAME)
    if os.path.exists(CATALOG):  # the guide's, outside the repository
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "AI21-Jamba2-3B"]
        assert c["published"] == row["config"]
        assert c["source"] == row["source_url"]
    assert c["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/"
        "config.json")
    assert c["family"] == "jamba" and c["dtype"] == "bfloat16"
    differs = [k for k, v in c["published"].items() if c[k] != v]
    assert differs == c["reduced"] == ["num_hidden_layers"]
    assert (c["num_hidden_layers"], c["published"]["num_hidden_layers"]) == (
        14, 28)
    assert c["num_hidden_layers"] == c["attn_layer_period"]  # one period
    for key, value in (
            ("hidden_size", 2560), ("intermediate_size", 8192),
            ("num_attention_heads", 20), ("num_key_value_heads", 1),
            ("mamba_expand", 2), ("mamba_d_state", 16),
            ("mamba_dt_rank", 160), ("mamba_d_conv", 4),
            ("mamba_conv_bias", True), ("mamba_proj_bias", False),
            ("attn_layer_period", 14), ("attn_layer_offset", 7),
            ("num_experts", 1), ("vocab_size", 65536),
            ("tie_word_embeddings", True), ("rms_norm_eps", 1e-6),
            ("max_position_embeddings", 262144)):
        assert c[key] == c["published"][key] == value, key
    # the published 26 : 2, exactly
    types = jamba.layer_types(c)
    assert (types.count("mamba"), types.count("full_attention")) == (13, 1)
    whole = jamba.layer_types({**c, "num_hidden_layers": 28})
    assert (whole.count("mamba"), whole.count("full_attention")) == (26, 2)
    for key in ("layers", "mixer", "decay_draws", "positions", "max_seq_len",
                "embed_init_std", "embed_init_std_origin", "optimizer_state"):
        assert c["assumed"][key], key
    depth = c["depth"]
    assert depth["found"] == 14
    assert depth["accepted_peak_memory_in_bytes"] and depth["refused"]
    assert "two periods" in " ".join(depth["refused"])
    assert "Two pipeline stages" in c["deployment"]
    tiny = config("tiny-jamba")
    assert tiny["rehearsal"] == {"global_batch": 2, "seq": 128}
    assert (tiny["mamba_d_state"], tiny["num_hidden_layers"]) == (16, 14)
    group = tiny["num_attention_heads"] // tiny["num_key_value_heads"]
    assert group & (group - 1)  # no power of two, as the cell's 20
    assert set(tiny) - {"rehearsal"} <= set(c)


def test_the_benchmark_names_the_cell_and_its_metrics():
    bench = cells.benchmark()
    made = entry(bench["configs"], NAME)
    assert made["file"] == f"yardstick/configs/{NAME}.json"
    assert made["source"] == config(NAME)["source"]
    assert made["reduced"] == config(NAME)["reduced"]
    cell = entry(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "steady-1x8192", 1)
    assert len(cell["why"]) <= 200 and len(made["why"]) <= 200
    reported = {m["name"] for m in cells.metrics_of(CELL, bench["per_layer"])}
    assert {"mfu_pct", "device_idle_pct", "attn_kernel_ms",
            "attn_roofline_pct", "selective_scan_ms",
            "selective_scan_roofline_pct"} <= reported
    assert not {"ssd_ms", "ssd_roofline_pct", "delta_rule_ms",
                "moe_expert_ms", "short_conv_ms",
                "collective_exposed_ms"} & reported
    for name in ("selective_scan_ms", "selective_scan_roofline_pct"):
        metric = entry(bench["per_layer"], name)
        assert metric["workloads"] == [CELL]
        assert (metric["layer"], metric["moves"], metric["source"]) == (
            "selective scan", "tokens_per_s", "device_trace")
        module = cells.metric_module(name)
        assert (module.NAME, module.LAYER, module.MOVES, module.SOURCE) == (
            name, "selective scan", "tokens_per_s", "device_trace")
    _, _, traffic = cells.load_cell(CELL)
    assert (traffic["seq"], traffic["global_batch"], traffic["remat"],
            traffic["loss_chunk"]) == (8192, 1, "minimal", 0)
