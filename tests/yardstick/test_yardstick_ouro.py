"""The ``ouro`` family: its program (models/llama.py as a looped stack:
four norms a block, the whole stack walked ``total_ut_steps`` times
with one set of weights and the final norm inside the loop, an exit
gate a position, every pass's cross entropy weighted by the exit
distribution less an entropy term) against ``references/ouro.py`` at
the tiny size, in the loss and in every leaf's gradient; each term of
the loop showing when it is changed; its counts against integers
worked by hand; what the configuration's file states."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import llama
from yardstick import cells, counts, reference, worker
from yardstick.families import ouro

SEQ, SEQUENCES = 128, 4
CELL = "ouro-2.6b-1chip.steady"
REFERENCE = os.path.join(cells.HERE, "references", "ouro.py")
TRAFFIC = {"seq": SEQ, "remat": "off", "loss_chunk": 0}


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _case(dtype, draw=True, sequences=SEQUENCES, seed=7):
    cfg_file = dict(config("tiny-ouro"), dtype=dtype)
    cfg = worker.program_config(cfg_file, TRAFFIC)
    params = llama.init_params(jax.random.key(2), cfg)
    if draw:
        params = drawn(params)
    tokens, targets = worker.SeededTokens(
        seed, SEQ, cfg_file["vocab_size"])(0, sequences)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    return cfg_file, cfg, params, batch


def drawn(params):
    """``params`` with the gate's bias at 0.7 and every norm's scale
    at 1 +/- 0.5 (the program starts them at zero and one, where the
    bias changes nothing and a norm on a normed branch next to
    nothing), and the head at three times its fan-in deviation: over
    random targets a changed trunk moves the mean loss by a sum of
    mean zero over the positions, whose size goes with the logits'."""
    keys = iter(jax.random.split(jax.random.key(3), 16))

    def draw(path, leaf):
        name = path[-1].key
        if name == "b":
            return leaf + 0.7
        if name.endswith("_norm"):
            return leaf * jax.random.uniform(
                next(keys), leaf.shape, leaf.dtype, 0.5, 1.5)
        return leaf * 3.0 if name == "lm_head" else leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def test_program_config_takes_the_sources_keys():
    c = config("ouro-2.6b-1chip")
    cfg = worker.program_config(
        c, {"seq": 8192, "remat": "minimal", "loss_chunk": 0})
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size) == (
        2048, 5632, 49152)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (16, 16, 128)
    assert cfg.num_layers == c["num_hidden_layers"] == 16
    assert (cfg.total_ut_steps, cfg.exit_entropy_weight) == (4, 0.1)
    assert (cfg.rope_theta, cfg.norm_eps) == (1e6, 1e-6)
    assert cfg.post_norms and not cfg.tie_word_embeddings
    assert not cfg.by_position and cfg.num_experts == 0
    assert cfg.embed_init_std == c["assumed"]["embed_init_std"]
    assert cfg.head_init_std == c["assumed"].get("head_init_std")
    # a layer: four 2048 x 2048 projections, three 2048 x 5632, four
    # norms; the embedding and the untied head; the final norm; the gate
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert llama.param_count(cfg) == (
        16 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1)
    assert llama.param_count(cfg) == 1_023_545_345  # 6.14 GB at 6 bytes
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 1_023_545_345
    assert shapes["blocks"]["w_gate"].shape == (16, 2048, 5632)
    assert shapes["blocks"]["post_mlp_norm"].shape == (16, 2048)
    assert shapes["exit_gate"]["w"].shape == (2048,)


def test_float32_program_agrees_with_the_reference():
    cfg_file, cfg, params, batch = _case("float32")
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < 2e-5, (program, ref)


def test_bf16_program_is_inside_the_chip_tolerance():
    """On the fresh parameters, as the cell's check has them, over
    three seeds of tokens."""
    for seed in (7, 8, 9):
        cfg_file, cfg, params, batch = _case(
            "bfloat16", draw=False, seed=seed)
        ref = float(reference.loss(cfg_file, params, *batch))
        program = float(llama.next_token_loss(params, batch, cfg))
        assert abs(program - ref) < worker.REFERENCE_TOLERANCE, seed


@pytest.fixture(scope="module")
def reference_gradients():
    """``jax.grad`` of the reference in float32, on the drawn
    parameters: what the sum over the passes is held against."""
    cfg_file, cfg, params, batch = _case("float32", sequences=2)
    grads = jax.grad(
        lambda p: reference.loss(cfg_file, p, *batch))(params)
    return cfg_file, cfg, params, batch, grads


def test_every_leafs_gradient_is_the_references(reference_gradients):
    """Float32, remat ``minimal`` as the cell runs it: every leaf, the
    gate's two among them. A weight's gradient is the sum over the
    four passes that met it; a pass dropped from the sum is a quarter
    of it gone."""
    _, _, params, batch, want = reference_gradients
    cfg = worker.program_config(
        {**config("tiny-ouro"), "dtype": "float32"},
        {**TRAFFIC, "remat": "minimal"})
    got = jax.jit(jax.grad(
        lambda p: llama.next_token_loss(p, batch, cfg)))(params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path
        assert float(jnp.abs(g - w).max()) < 2e-4 * scale, path


def test_the_bf16_gradients_sum_over_the_passes_is_held_in_bf16(
        reference_gradients):
    """The cell's dtype: the scan's backward adds a weight's four
    gradients in bfloat16 (``models/llama.py _run_loop`` says why).
    Against the float32 reference's, every leaf's gradient lies within
    a few roundings of 2 ** -8 of its size, the stacked weights' and
    the head's (each a sum over four passes) no further than the
    embedding's, which one pass meets."""
    cfg_file, _, params, batch, want = reference_gradients
    cfg = worker.program_config(
        {**cfg_file, "dtype": "bfloat16"}, {**TRAFFIC, "remat": "minimal"})
    half = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, params)
    got = jax.jit(jax.grad(
        lambda p: llama.next_token_loss(p, batch, cfg)))(half)
    off = {}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        name = path[-1].key
        assert g.dtype == (jnp.bfloat16 if w.ndim > 1 else w.dtype), path
        off[name] = float(
            jnp.linalg.norm((g.astype(jnp.float32) - w).ravel())
            / jnp.linalg.norm(w.ravel()))
    # the gate's bias, one number summed over every position, reads
    # 0.06; every matrix 0.024-0.027, the embedding 0.026: what the
    # bfloat16 activations cost, and nothing on top for the sum
    assert max(off.values()) < 0.1, off
    summed = max(off[n] for n in ("wq", "wo", "w_up", "w_down", "lm_head"))
    assert summed < 1.5 * off["embed"], off


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


LOSS = "        jnp.where(keep, expected - beta * entropy, 0.0)\n"
#: the controls of ISSUE 58, as edits to the reference
CONTROLS = {
    "three passes for four": ((
        '    for _ in range(config["total_ut_steps"]):\n',
        '    for _ in range(config["total_ut_steps"] - 1):\n'),),
    "last pass's cross entropy alone": ((
        LOSS, "        jnp.where(keep, nll[-1], 0.0)\n"),),
    "no entropy term": ((
        LOSS, "        jnp.where(keep, expected, 0.0)\n"),),
    "final norm outside the loop": ((
        '        x = final_rms(x, params["final_norm"], eps)\n'
        "        out.append(x)\n",
        '        out.append(final_rms(x, params["final_norm"], eps))\n'),),
    "no post-norms": (
        ('        x = x + rms_norm(a @ p["wo"], p["post_attn_norm"], eps)\n',
         '        x = x + a @ p["wo"]\n'),
        ('        return x + rms_norm(m, p["post_mlp_norm"], eps)\n',
         "        return x + m\n")),
    "gate's bias left out": ((
        ' + gate["b"].astype(F32)\n', "\n"),),
}
#: the reference in the nearest precision below the program's
#: bfloat16: whatever the program keeps in bfloat16 rounded to float8
#: (e4m3, a scale a tensor), the sums in float32. That is every
#: matrix, the embedding's rows, the stream after each residual sum,
#: the two normed streams a layer, each pass's normed state, the
#: gate's weight and the head
FLOAT8 = (
    ("#: query rows whose scores",
     "def q8(a):\n"
     "    s = jnp.max(jnp.abs(a)) / 448.0\n"
     "    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s\n\n\n"
     "#: query rows whose scores"),
    ('        u = rms_norm(x, p["attn_norm"], eps)\n',
     '        p = {k: q8(v) if v.ndim > 1 else v for k, v in p.items()}\n'
     '        u = q8(rms_norm(x, p["attn_norm"], eps))\n'),
    ('        x = x + rms_norm(a @ p["wo"], p["post_attn_norm"], eps)\n',
     '        x = q8(x + rms_norm(a @ p["wo"], p["post_attn_norm"], eps))\n'),
    ('        u = rms_norm(x, p["mlp_norm"], eps)\n',
     '        u = q8(rms_norm(x, p["mlp_norm"], eps))\n'),
    ('        return x + rms_norm(m, p["post_mlp_norm"], eps)\n',
     '        return q8(x + rms_norm(m, p["post_mlp_norm"], eps))\n'),
    ('    x = embed(params["embed"], tokens)\n',
     '    x = q8(embed(params["embed"], tokens))\n'),
    ('        x = final_rms(x, params["final_norm"], eps)\n',
     '        x = q8(final_rms(x, params["final_norm"], eps))\n'),
    ('        logits = x @ head.astype(F32)\n',
     '        logits = x @ q8(head.astype(F32))\n'),
    ('        return x @ gate["w"].astype(F32)',
     '        return x @ q8(gate["w"].astype(F32))'),
)


@pytest.fixture(scope="module")
def float32_cases():
    """Two batches on the same weights, each with the program's
    loss: a changed term's reading is a sum of mean zero over the
    positions, and on one batch in ten it lands inside the
    tolerance."""
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
    """A reference with one term of the loop altered is off by more
    than the chip's tolerance, in float32, where the unchanged pair
    agrees to 2e-5 (the gate's bias and the norms' scales drawn:
    ``drawn``)."""
    difference = most_off(
        edited(term.split()[0], *CONTROLS[term]), float32_cases)
    assert difference > worker.REFERENCE_TOLERANCE, (term, difference)


def test_the_unchanged_copy_is_the_reference(float32_cases):
    assert most_off(edited("same"), float32_cases) < 2e-5


def test_the_reference_in_float8_shows(float32_cases):
    assert most_off(
        edited("float8", *FLOAT8), float32_cases
    ) > worker.REFERENCE_TOLERANCE


def test_the_reference_of_one_pass_is_the_sandwich_blocks_plain_loss():
    """``total_ut_steps`` 1: ``p_1`` is the empty product, the entropy
    0, no gate is read, and the reference is the mean cross entropy of
    a stack of four-norm blocks, which the program's
    ``llama_tiny``-sized config with ``post_norms`` computes."""
    cfg_file = dict(config("tiny-ouro"), dtype="float32", total_ut_steps=1)
    cfg = worker.program_config(cfg_file, TRAFFIC)
    assert cfg.total_ut_steps == 1
    params = llama.init_params(jax.random.key(2), cfg)
    assert "exit_gate" not in params
    tokens, targets = worker.SeededTokens(7, SEQ, 256)(0, 2)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    ref = float(reference.loss(cfg_file, params, *batch))
    assert abs(float(llama.next_token_loss(params, batch, cfg)) - ref) < 2e-5


def test_reference_refuses_what_it_does_not_compute():
    cfg_file, _, params, batch = _case("float32", sequences=1)
    for change in ({"max_position_embeddings": 64}, {"sliding_window": 64},
                   {"num_key_value_heads": 2}):
        with pytest.raises(ValueError):
            reference.loss({**cfg_file, **change}, params, *batch)


def test_reference_imports_no_line_of_the_program():
    with open(REFERENCE) as f:
        src = f.read()
    imports = [ln for ln in src.splitlines(True)
               if ln.startswith(("import ", "from "))]
    assert imports == [
        "import functools\n", "import jax\n", "import jax.numpy as jnp\n",
        "from yardstick.reference import (\n",
    ]
    assert "dlrover_tpu" not in src.split('"""', 2)[2]
    with open(os.path.join(cells.HERE, "families", "ouro.py")) as f:
        top = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top == ["from yardstick import counts\n"]  # no JAX at import


def test_reference_attention_walks_rows_in_blocks():
    ref = edited("rows")
    keys = jax.random.split(jax.random.key(3), 3)
    q, k, v = (jax.random.normal(key, (1, 64, 4, 8)) for key in keys)
    whole = ref.causal_attention(q, k, v, rows=64)
    blocks = ref.causal_attention(q, k, v, rows=8)
    assert float(jnp.abs(whole - blocks).max()) < 1e-5
    want = reference.causal_attention(q, k, v)
    assert float(jnp.abs(blocks - want).max()) < 1e-5


def test_program_config_refuses_what_it_does_not_pass_on():
    tiny = config("tiny-ouro")
    for key, other in (
            ("sliding_window", 64), ("use_sliding_window", True),
            ("rope_scaling", {"type": "yarn", "factor": 4.0}),
            ("hidden_act", "gelu"), ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            worker.program_config({**tiny, key: other}, TRAFFIC)
    with pytest.raises(ValueError, match="layer_types"):
        worker.program_config({**tiny, "num_hidden_layers": 4}, TRAFFIC)
    with pytest.raises(ValueError, match="layer_types"):
        worker.program_config(
            {**tiny, "layer_types": ["sliding_attention"] * 3}, TRAFFIC)
    without = {k: v for k, v in tiny.items() if k != "total_ut_steps"}
    with pytest.raises(ValueError, match="total_ut_steps is not given"):
        worker.program_config(without, TRAFFIC)
    with pytest.raises(ValueError, match="total_ut_steps is not given"):
        counts.matmul_params(without)
    with pytest.raises(ValueError, match="a whole number"):
        counts.matmul_params({**tiny, "total_ut_steps": 0})


# -- the counts --------------------------------------------------------------

def test_ouro_counts_by_hand():
    c = config("ouro-2.6b-1chip")
    s = ouro.shape(c)
    assert (s["layers"], s["heads"], s["kv_heads"], s["head_dim"]) == (
        16, 16, 16, 128)
    # weights a token meets in one pass: a layer's four 2048 x 2048 and
    # three 2048 x 5632 (51.38 M; the norms are no matmul), the head
    layer, head = 4 * 2048 * 2048 + 3 * 2048 * 5632, 2048 * 49152
    assert (layer, head) == (51_380_224, 100_663_296)
    once = 16 * layer + head
    assert counts.dense_matmul_params(s) == once
    # every layer four times and the head four times
    assert ouro.matmul_params(c) == counts.matmul_params(c) == 4 * once
    assert 4 * once == 3_690_987_520
    # and the program's own parameters, less the embedding, the norms
    # and the gate, are one pass's
    cfg = worker.program_config(
        c, {"seq": 8192, "remat": "minimal", "loss_chunk": 0})
    small = 16 * 4 * 2048 + 2048 + 2048 + 1
    assert llama.param_count(cfg) - 49152 * 2048 - small == once
    # scores and weighted values: 33.55 MFLOP a token, layer and pass
    a_layer = 2 * 16 * 128 * 8192
    assert a_layer == 33_554_432
    attn = counts.attention_forward_flops_per_token(c, 8192)
    assert attn == 4 * 16 * a_layer == 2_147_483_648
    flops = counts.train_flops_per_token(c, 8192)
    assert flops == 3 * (2 * 4 * once + attn) == 28_588_376_064
    assert flops * 8192 == pytest.approx(234.2e12, rel=1e-3)  # a step
    # of the counted operations: the projections and SwiGLU 69%, the
    # attention kernels' products 22.5%, the four head passes 8.4%
    forward = flops / 3
    assert 2 * 4 * 16 * layer / forward == pytest.approx(0.690, abs=2e-3)
    assert attn / forward == pytest.approx(0.225, abs=2e-3)
    assert 2 * 4 * head / forward == pytest.approx(0.084, abs=2e-3)
    # in the whole model (48 layers) the head's four passes are 3%
    whole = 2 * 4 * (48 * layer + head) + 4 * 48 * a_layer
    assert 2 * 4 * head / whole == pytest.approx(0.031, abs=2e-3)
    # the kernels: seven causal products a layer and pass, 61.6 TFLOP
    # a step, 313 ms at 197 TFLOP/s
    kernel_flops, nbytes = counts.attention_kernel_step(c, 1, 8192)
    assert kernel_flops == 4 * 16 * 7 * 16 * 8192 * 8192 * 128
    assert kernel_flops == 61_572_651_155_456
    q_like = 8192 * 16 * 128 * 2
    assert nbytes == 4 * 16 * 12 * q_like
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(kernel_flops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(0.31255, rel=1e-3)
    # T times the dense count, whatever T
    for steps in (1, 2):
        other = {**c, "total_ut_steps": steps}
        assert counts.train_flops_per_token(other, 8192) * 4 == (
            flops * steps)
        assert counts.attention_kernel_step(other, 1, 8192)[0] * 4 == (
            kernel_flops * steps)


def test_every_published_number_is_run_but_the_cut():
    c = config("ouro-2.6b-1chip")
    differs = [k for k, v in c["published"].items() if c[k] != v]
    assert differs == c["reduced"] == ["num_hidden_layers"]
    assert (c["published"]["num_hidden_layers"],
            c["num_hidden_layers"]) == (48, 16)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert c["published"] == row["config"]
    assert c["source"] == row["source_url"]
    for key, value in (
            ("hidden_size", 2048), ("intermediate_size", 5632),
            ("num_attention_heads", 16), ("num_key_value_heads", 16),
            ("head_dim", 128), ("vocab_size", 49152),
            ("total_ut_steps", 4), ("rope_theta", 1000000),
            ("rms_norm_eps", 1e-6), ("max_position_embeddings", 65536)):
        assert c[key] == c["published"][key] == value, key
    assert c["depth"]["found"] == 16
    assert set(c["depth"]["refused"]) >= {"24 layers", "20 layers"}
    assert max(
        c["depth"]["accepted_peak_memory_in_bytes"].values()) < 16.91e9
    for key in ("norms", "loop", "gate", "loss", "entropy_weight",
                "attention", "gradient_sum", "embed_init_std",
                "init_origin", "max_seq_len", "optimizer_state"):
        assert key in c["assumed"], key
    assert "ring" in c["deployment"]
    # by name, not by place: the next configuration stands after it
    bench = cells.benchmark()
    (entry,) = [e for e in bench["configs"]
                if e["name"] == "ouro-2.6b-1chip"]
    assert entry["source"] == c["source"]
    assert entry["reduced"] == c["reduced"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b-1chip", "steady-1x8192", 1)
    # the cell is in no metric's list: it reports those without one
    assert not any(
        CELL in m.get("workloads", ()) for m in bench["per_layer"])
