"""The ``sala`` family: its program (models/llama.py with attention
over the key blocks each query selects in the first layer of four and
lightning linear attention on the state-space scan in the three
others, a norm on each head's q and k, a gate on each operator's
result, and the three scalar factors) against ``references/sala.py``
at the tiny size, in the loss, in the selection itself and in every
leaf's gradient, each term of the block showing when it is changed;
its counts against integers worked by hand; what the configuration's
file states."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.ops import sparse_attention
from yardstick import cells, counts, reference, worker
from yardstick.families import sala
from yardstick.layer_metrics import ssd_roofline_pct

SEQ, SEQUENCES = 128, 4
NAME = "minicpm-sala-9b-vp8"
CELL = NAME + ".steady"
REFERENCE = os.path.join(cells.HERE, "references", "sala.py")
TRAFFIC = {"seq": SEQ, "remat": "off", "loss_chunk": 0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _case(dtype, draw=True, sequences=SEQUENCES, seed=7):
    cfg_file = dict(config("tiny-sala"), dtype=dtype)
    cfg = worker.program_config(cfg_file, TRAFFIC)
    params = llama.init_params(jax.random.key(2), cfg)
    if draw:
        params = drawn(params)
    tokens, targets = worker.SeededTokens(
        seed, SEQ, cfg_file["vocab_size"])(0, sequences)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    return cfg_file, cfg, params, batch


def drawn(params):
    """``params`` with the heads' norms' scales drawn at 1 +/- 0.5
    (the program starts them at one, where a scale that is left out
    changes nothing) and the head at three times its deviation: over
    random targets a changed trunk moves the mean loss by a sum of
    mean zero over the positions, whose size goes with the logits'."""
    keys = iter(jax.random.split(jax.random.key(3), 64))

    def draw(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else None
        if name in ("q_norm", "k_norm", "o_norm"):
            return leaf * jax.random.uniform(
                next(keys), leaf.shape, leaf.dtype, 0.5, 1.5)
        return leaf * 3.0 if name == "lm_head" else leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def entry(entries, name):
    """The entry of a list of ``BENCHMARK.json`` by its name, wherever
    it stands."""
    (found,) = [e for e in entries if e["name"] == name]
    return found


def test_program_config_takes_the_sources_keys():
    cfg = worker.program_config(
        config(NAME), {"seq": 16384, "remat": "minimal", "loss_chunk": 0})
    assert (cfg.hidden_size, cfg.intermediate_size) == (4096, 16384)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.lightning_num_heads, cfg.lightning_head_dim) == (32, 128)
    assert cfg.norm_eps == 1e-6 and cfg.rope_theta == 10000.0
    assert (cfg.scale_emb, cfg.scale_depth, cfg.scale_depth_layers,
            cfg.dim_model_base) == (12.0, 1.4, 32, 256)
    assert (cfg.sparse_block_size, cfg.sparse_kernel_size,
            cfg.sparse_kernel_stride, cfg.sparse_topk,
            cfg.sparse_window_size, cfg.sparse_init_blocks,
            cfg.sparse_dense_len) == (64, 32, 16, 64, 2048, 1, 8192)
    assert cfg.qk_head_norm and cfg.attn_out_gate and not cfg.qk_norm
    assert not cfg.tie_word_embeddings and cfg.num_experts == 0
    assert cfg.layer_types == ("sparse_attention",) + (
        "lightning_attention",) * 3
    assert cfg.rope_layout == (0, 1, 1, 1)
    assert (cfg.vocab_size, cfg.num_layers) == (9181, 4)
    assert (cfg.embed_init_std, cfg.head_init_std) == (
        config(NAME)["assumed"]["embed_init_std"],
        config(NAME)["assumed"]["head_init_std"])
    assert llama.operator_layers(cfg) == {
        "sparse_attention": 1, "lightning_attention": 3}
    # the selected-attention layer: q, the gate and o 16.78 M each, k
    # and v 1.05 M each; a lightning layer five of 16.78 M; the MLP
    # 201.33 M; the norms' scales
    mlp = 3 * 4096 * 16384
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + mlp + 2 * 4096 + 2 * 128
    lightning = 5 * 4096 * 4096 + mlp + 2 * 4096 + 3 * 128
    assert (sparse, lightning) == (253_763_840, 285_221_248)
    assert llama.param_count(cfg) == (
        sparse + 3 * lightning + 2 * 9181 * 4096 + 4096)
    assert llama.param_count(cfg) == 1_184_642_432  # 7.11 GB at 6 bytes
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 1_184_642_432
    assert shapes["period"][0]["wk"].shape == (1, 4096, 256)
    assert shapes["period"][0]["wg"].shape == (1, 4096, 4096)
    assert shapes["period"][2]["wk"].shape == (1, 4096, 4096)
    assert shapes["period"][3]["o_norm"].shape == (1, 128)
    assert "o_norm" not in shapes["period"][0]
    for key, value in (("qk_norm", False), ("use_output_norm", False),
                       ("lightning_nkv", 8), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key):
            worker.program_config({**config(NAME), key: value}, {
                "seq": 16384, "remat": "minimal", "loss_chunk": 0})
    with pytest.raises(ValueError, match="mixer_types"):
        sala.layer_types({**config(NAME), "mixer_types": ["minicpm4"]})


def test_float32_program_agrees_with_the_reference():
    cfg_file, cfg, params, batch = _case("float32")
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < 2e-5, (program, ref)


@pytest.mark.parametrize("seed", [3, 5, 6])
def test_bf16_program_is_inside_the_chip_tolerance(seed):
    """As the program starts, the step jitted, as the worker's is."""
    cfg_file, cfg, params, batch = _case("bfloat16", False, 8, seed=seed)
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(jax.jit(
        lambda p, b: llama.next_token_loss(p, b, cfg))(params, batch))
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


def _qk(seed=4, b=2, heads=4, kv_heads=2, d=16):
    keys = jax.random.split(jax.random.key(seed), 2)
    return (jax.random.normal(keys[0], (b, SEQ, heads, d)),
            jax.random.normal(keys[1], (b, SEQ, kv_heads, d)))


def test_the_programs_selection_is_the_references():
    """Float32, the tiny configuration's sizes: the same sets of
    blocks for every query and kv head, free picks among them."""
    sizes = config("tiny-sala")["assumed"]["sparse_config"]
    q, k = _qk()
    want = edited("selection").selection(q, k, sizes, rows=32)
    got = sparse_attention.select_blocks(
        q, sparse_attention.compress_keys(
            k, sizes["kernel_size"], sizes["kernel_stride"]),
        block=sizes["block_size"], kernel=sizes["kernel_size"],
        stride=sizes["kernel_stride"], topk=sizes["topk"],
        window=sizes["window_size"], init_blocks=sizes["init_blocks"],
        rows=64)
    assert got.shape == want.shape == (2, 2, SEQ, 16)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    count = np.asarray(want).sum(-1)
    assert (count == np.minimum(np.arange(SEQ) // 8 + 1, 6)).all()
    # past the forced three (block 0 and the two nearest) the picks
    # are free: queries of one block do not all take the same
    late = np.asarray(want)[0, 0, 120:128]
    assert late[:, 0].all() and late[:, 14:].all()
    assert late[:, 1:14].sum(-1).tolist() == [3] * 8
    assert (late != late[0]).any()


def test_the_references_attention_is_exact_for_its_selection():
    """The reference's walk in blocks of rows against plain attention
    under the selection spread over the keys, whatever the block of
    rows; dense on a sequence within ``dense_len``."""
    from dlrover_tpu.ops.attention import mha_reference

    ref = edited("attention")
    sizes = config("tiny-sala")["assumed"]["sparse_config"]
    q, k = _qk()
    v = jax.random.normal(jax.random.key(6), k.shape)
    mask = jnp.repeat(ref.selection(q, k, sizes), 8, axis=-1)
    want = mha_reference(q, k, v, mask=mask).reshape(2, SEQ, -1)
    for rows in (128, 16):
        got = ref.selected_attention(q, k, v, sizes, rows=rows)
        assert float(jnp.abs(got - want).max()) < 1e-5
    dense = ref.selected_attention(q, k, v, {**sizes, "dense_len": SEQ})
    assert float(jnp.abs(
        dense - mha_reference(q, k, v).reshape(2, SEQ, -1)).max()) < 1e-5
    assert float(jnp.abs(dense - want).max()) > 1e-3


def test_every_leafs_gradient_is_the_references():
    """Float32, remat ``minimal`` as the cell runs it: every leaf of
    both operators against ``jax.grad`` of the reference, whose
    selection is as little differentiable as the program's."""
    cfg_file, _, params, batch = _case("float32", sequences=2)
    want = jax.grad(lambda p: reference.loss(cfg_file, p, *batch))(params)
    cfg = worker.program_config(cfg_file, {**TRAFFIC, "remat": "minimal"})
    got = jax.jit(jax.grad(
        lambda p: llama.next_token_loss(p, batch, cfg)))(params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    seen = set()
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        seen.add(path[-1].key if hasattr(path[-1], "key") else str(path[-1]))
        scale = float(jnp.abs(w).max())
        assert scale > 0, path
        assert float(jnp.abs(g - w).max()) < 2e-4 * scale, path
    assert {"wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm", "o_norm",
            "w_gate", "embed", "lm_head", "final_norm"} <= seen


#: ISSUE 64's controls, and the block's other terms, as edits to the
#: reference
CONTROLS = {
    "selection off: every earlier key": ((
        '    select = s > sizes["dense_len"]\n', "    select = False\n"),),
    "the initial block not forced": ((
        "    return (at < init_blocks) | ((at > own - local) & (at <= own))\n",
        "    return (at > own - local) & (at <= own)\n"),),
    "the nearest blocks not forced but the own": ((
        "    return (at < init_blocks) | ((at > own - local) & (at <= own))\n",
        "    return (at < init_blocks) | (at == own)\n"),),
    "top-k one fewer": ((
        '    _, chosen = jax.lax.top_k(score, min(sizes["topk"], blocks))\n',
        '    _, chosen = jax.lax.top_k(\n'
        '        score, min(sizes["topk"] - 1, blocks))\n'),),
    "a compressed key visible from its first key on": ((
        "    return first + kernel - 1 <= t\n", "    return first <= t\n"),),
    "the group's score its first head's": ((
        "    return jnp.sum(p, axis=2)\n", "    return p[:, :, 0]\n"),),
    "decay left out": ((
        '        state = keep * state + jnp.einsum("bhk,bhv->bhkv", k_t, v_t)\n',
        '        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, v_t)\n'),),
    "no norm on the heads' result": ((
        '    if config["use_output_norm"]:\n', "    if False:\n"),),
    "no gate on a lightning layer": ((
        '    if config["use_output_gate"]:\n', "    if False:\n"),),
    "no gate on attention": ((
        '    if config["attn_use_output_gate"]:\n', "    if False:\n"),),
    "scale_emb at 1": ((
        ' * F32(config["scale_emb"])\n', "\n"),),
    "scale_depth over the layers that are run": ((
        '            F32(config["scale_depth_layers"]))\n',
        "            F32(4))\n"),),
    "the head's input not divided": ((
        '    x = x / F32(config["hidden_size"] / config["dim_model_base"])\n',
        ""),),
    "rotation on the attention layer": ((
        '    if config["attn_use_rope"]:\n', "    if True:\n"),),
    "no rotation on a lightning layer": ((
        '    if config["lightning_use_rope"]:\n', "    if False:\n"),),
    "no norm on attention's q and k": ((
        '    v = (y @ p["wv"]).reshape(b, s, kv_heads, -1)\n'
        '    if config["qk_norm"]:\n',
        '    v = (y @ p["wv"]).reshape(b, s, kv_heads, -1)\n'
        '    if False:\n'),),
    "no norm on a lightning layer's q and k": ((
        '               for w in ("wq", "wk", "wv"))\n'
        '    if config["qk_norm"]:\n',
        '               for w in ("wq", "wk", "wv"))\n'
        '    if False:\n'),),
}
#: the reference in the nearest precision below the program's
#: bfloat16: every matrix and the two normed streams a layer rounded
#: to float8 (e4m3, a scale a tensor), the sums in float32
FLOAT8 = (
    ('#: query rows whose scores against every key are held at once\n',
     'def q8(a):\n'
     '    s = jnp.max(jnp.abs(a)) / 448.0\n'
     '    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s\n\n\n'
     '#: query rows whose scores against every key are held at once\n'),
    ('        y = rms_norm(x, p["attn_norm"], eps)\n',
     '        p = {k: q8(v) if v.ndim > 1 else v for k, v in p.items()}\n'
     '        y = q8(rms_norm(x, p["attn_norm"], eps))\n'),
    ('        y = rms_norm(x, p["mlp_norm"], eps)\n',
     '        y = q8(rms_norm(x, p["mlp_norm"], eps))\n'),
)


@pytest.fixture(scope="module")
def float32_cases():
    """Two batches on the same weights, each with the program's loss:
    a changed term's reading is a sum of mean zero over the positions,
    and on a batch in ten it lands inside the tolerance."""
    cases = [_case("float32", seed=seed) for seed in (7, 8)]
    return [
        (case, float(llama.next_token_loss(case[2], case[3], case[1])))
        for case in cases
    ]


def most_off(changed, cases):
    """The larger |program - changed reference| of the batches."""
    return max(
        abs(program - float(changed.loss(cfg_file, params, *batch)))
        for (cfg_file, _, params, batch), program in cases
    )


@pytest.mark.parametrize("term", list(CONTROLS))
def test_a_changed_term_shows(term, float32_cases):
    """A reference with one term altered is off by more than twenty
    times what the unchanged pair agrees to in float32 (2e-5; the
    selection's controls move a few blocks of a few queries at this
    size, and read 0.0005 and more)."""
    difference = most_off(
        edited(term.split()[0], *CONTROLS[term]), float32_cases)
    assert difference > 4e-4, (term, difference)


def test_the_reference_in_float8_shows(float32_cases):
    assert most_off(
        edited("float8", *FLOAT8), float32_cases
    ) > worker.REFERENCE_TOLERANCE


def test_reference_refuses_more_positions_than_the_source_declares():
    cfg_file, _, params, batch = _case("float32")
    with pytest.raises(ValueError):
        reference.loss(
            {**cfg_file, "max_position_embeddings": 64}, params, *batch)


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
    assert "dlrover_tpu" not in body and "ssd" not in body
    assert "lax.scan" in body and "cumsum" not in body  # token by token
    assert "lax.map" in body and "HIGHEST" in body  # rows in blocks
    with open(os.path.join(cells.HERE, "families", "sala.py")) as f:
        top = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top == []  # no JAX, nothing of the program, at import


def test_counts_worked_by_hand():
    c = config(NAME)
    s = counts.shape(c)
    assert (s["hidden"], s["ffn"], s["layers"], s["heads"], s["kv_heads"],
            s["head_dim"], s["vocab"]) == (4096, 16384, 4, 32, 2, 128, 9181)
    assert (s["attention_layers"], s["lightning_layers"]) == (1, 3)
    mlp = 3 * 4096 * 16384
    assert counts.matmul_params(c) == (
        3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 5 * 4096 * 4096 + 4 * mlp
        + 4096 * 9181)
    # a query's keys at 16,384: t + 1 before position 4,096, then 63
    # whole blocks and its own up to itself
    early = 4096 * 4097 // 2
    late = (16384 - 4096) * 63 * 64 + (16384 - 4096) // 64 * (64 * 65 // 2)
    assert sala.selected_keys(c, 16384) == early + late == 58_335_232
    assert sala.selected_keys(c, 8192) == 8192 * 8193 // 2  # dense
    # compressed key j from query 16 j + 31 on
    assert sala.visible_compressed_keys(c, 16384) == sum(
        16384 - (16 * j + 31) for j in range(1023))
    assert sala.visible_compressed_keys(c, 8192) == 0
    keys = 58_335_232 / 16384
    assert counts.attention_forward_flops_per_token(c, 16384) == (
        4.0 * 32 * 128 * keys)
    select = 2.0 * 32 * 128 * sala.visible_compressed_keys(c, 16384) / 16384
    assert counts.train_flops_per_token(c, 16384) == 3.0 * (
        2.0 * counts.matmul_params(c) + 4.0 * 32 * 128 * keys) + select
    flops, nbytes = counts.attention_kernel_step(c, 1, 16384)
    assert flops == 7.0 * 2 * 128 * 32 * 58_335_232
    q_like, kv_like = 16384 * 32 * 128 * 2, 16384 * 2 * 128 * 2
    assert nbytes == 6 * q_like + 6 * kv_like + 2 * (16384 * 2 * 256 // 8)
    flops, nbytes = sala.ssd_step(c, 16384)
    assert flops == 3 * 15.0 * 16384 * 32 * 128 * 128
    assert nbytes == 3 * 11 * (2 * 16384 * 32 * 128)


def test_the_scans_reader_finds_the_familys_count():
    run = {"config": config(NAME), "cell": {"chips": 1},
           "traffic": {"global_batch": 1, "seq": 16384},
           "peak": cells.peak_of("TPU v5 lite")}
    seconds, bound = ssd_roofline_pct.least_seconds(run)
    assert bound == "memory"
    assert seconds == pytest.approx(
        3 * 11 * 2 * 16384 * 4096 / run["peak"]["hbm_bytes_per_s"])


def test_what_the_configuration_states():
    c = config(NAME)
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA"]
    assert c["published"] == row["config"] and c["source"] == row["source_url"]
    assert c["family"] == "sala" and c["dtype"] == "bfloat16"
    assert sorted(c["reduced"]) == [
        "mixer_types", "num_hidden_layers", "vocab_size"]
    changed = {k for k, v in row["config"].items() if c[k] != v}
    assert changed == set(c["reduced"])
    assert c["mixer_types"] == row["config"]["mixer_types"][:4] == [
        "minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"]
    assert (c["num_hidden_layers"], c["vocab_size"]) == (4, 9181)
    assert 9181 == 73448 // 8
    # every published width as it is
    assert (c["hidden_size"], c["intermediate_size"], c["head_dim"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["lightning_nh"], c["lightning_nkv"], c["lightning_head_dim"],
            c["rms_norm_eps"], c["scale_emb"], c["scale_depth"],
            c["dim_model_base"]) == (
                4096, 16384, 128, 32, 2, 32, 32, 128, 1e-6, 12, 1.4, 256)
    assumed = c["assumed"]
    assert assumed["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "window_size": 2048, "init_blocks": 1,
        "dense_len": 8192}
    assert assumed["scale_depth_layers"] == row["config"]["num_hidden_layers"]
    for key in ("sparse_config_origin", "selection", "attention",
                "lightning", "lightning_decay", "scale_depth_layers_origin",
                "factors", "norms", "mlp", "max_seq_len", "optimizer_state",
                "embed_init_std", "head_init_std", "draws_origin"):
        assert assumed[key], key
    assert c["share"]["stages"] == 8 and "0-9,180" in c["share"]["vocab_held"]
    assert "eight stages" in c["deployment"]
    depth = c["depth"]
    assert depth["accepted_peak_memory_in_bytes"] and depth["refused"]
    # the tiny size scales the selection's sizes down together
    tiny = config("tiny-sala")
    assert tiny["assumed"]["sparse_config"] == {
        "kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 6,
        "window_size": 16, "init_blocks": 1, "dense_len": 64}
    assert tiny["rehearsal"] == {"global_batch": 2, "seq": 128}
    assert set(tiny) - {"rehearsal"} <= set(c)


def test_the_benchmark_names_the_cell_and_its_metrics():
    bench = cells.benchmark()
    made = entry(bench["configs"], NAME)
    assert made["file"] == f"yardstick/configs/{NAME}.json"
    assert made["source"] == config(NAME)["source"]
    assert sorted(made["reduced"]) == sorted(config(NAME)["reduced"])
    cell = entry(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "steady-1x16384", 1)
    assert len(cell["why"]) <= 200 and len(made["why"]) <= 200
    reported = {m["name"] for m in cells.metrics_of(CELL, bench["per_layer"])}
    assert {"mfu_pct", "device_idle_pct", "attn_kernel_ms",
            "attn_roofline_pct", "ssd_ms", "ssd_roofline_pct"} <= reported
    assert not {"delta_rule_ms", "moe_expert_ms", "short_conv_ms",
                "collective_exposed_ms"} & reported
    for name in ("ssd_ms", "ssd_roofline_pct"):
        assert entry(bench["per_layer"], name)["workloads"][-1] == CELL
    _, _, traffic = cells.load_cell(CELL)
    assert (traffic["seq"], traffic["global_batch"], traffic["remat"],
            traffic["loss_chunk"]) == (16384, 1, "minimal", 0)
    assert traffic["seq"] > config(NAME)["assumed"]["sparse_config"][
        "dense_len"]
