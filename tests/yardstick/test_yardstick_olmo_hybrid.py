"""The ``olmo_hybrid`` family: its program (models/llama.py with the
gated delta rule with one decay a head on heads of two widths in three
layers of four, full attention of ungrouped heads without positions in
the fourth, q and k normed over their whole projections, and every
block normed on its branches' results alone) against
``references/olmo_hybrid.py`` at the tiny size, in the loss and in
every leaf's gradient, each term of the block showing when it is
changed; its counts against integers worked by hand; what the
configuration's file states."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import llama
from yardstick import cells, counts, reference, worker
from yardstick.families import olmo_hybrid
from yardstick.layer_metrics import delta_rule_roofline_pct

SEQ, SEQUENCES = 128, 4
NAME = "olmo-hybrid-7b-vp8"
CELL = NAME + ".steady"
REFERENCE = os.path.join(cells.HERE, "references", "olmo_hybrid.py")
TRAFFIC = {"seq": SEQ, "remat": "off", "loss_chunk": 0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _case(dtype, draw=True, sequences=SEQUENCES, seed=7):
    cfg_file = dict(config("tiny-olmo_hybrid"), dtype=dtype)
    cfg = worker.program_config(cfg_file, TRAFFIC)
    params = llama.init_params(jax.random.key(2), cfg)
    if draw:
        params = drawn(params)
    tokens, targets = worker.SeededTokens(
        seed, SEQ, cfg_file["vocab_size"])(0, sequences)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    return cfg_file, cfg, params, batch


def drawn(params):
    """``params`` with every norm's scale drawn at 1 +/- 0.5 (the
    program starts them at one, where a scale in another place changes
    less) and the head at three times its fan-in deviation: over
    random targets a changed trunk moves the mean loss by a sum of
    mean zero over the positions, whose size goes with the logits'."""
    keys = iter(jax.random.split(jax.random.key(3), 64))

    def draw(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else None
        if name in ("q_norm", "k_norm", "o_norm", "post_attn_norm",
                    "post_mlp_norm"):
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
    assert (cfg.hidden_size, cfg.intermediate_size) == (3840, 11008)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (30, 30, 128)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim) == (30, 30, 96, 192, 4)
    assert cfg.linear_allow_neg_eigval and cfg.norm_eps == 1e-6
    assert cfg.qk_norm and not cfg.qk_head_norm and not cfg.attn_out_gate
    assert cfg.post_norms == "alone"
    assert not cfg.tie_word_embeddings and cfg.num_experts == 0
    assert cfg.layer_types == ("gated_delta_net",) * 3 + ("full_attention",)
    assert cfg.rope_layout == (0, 0, 0, 0)
    assert (cfg.vocab_size, cfg.num_layers) == (12544, 4)
    assert (cfg.embed_init_std, cfg.head_init_std) == (
        config(NAME)["assumed"]["embed_init_std"],
        config(NAME)["assumed"]["head_init_std"])
    assert llama.operator_layers(cfg) == {
        "gated_delta_net": 3, "full_attention": 1}
    # a linear layer: q and k 11.06 M each, v, the gate and o 22.12 M
    # each, the two a head, the taps, the decay's two and the norms;
    # the attention layer four of 14.75 M and q's and k's scales
    mlp = 3 * 3840 * 11008
    linear = (2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30
              + 4 * (2 * 2880 + 5760) + 2 * 30 + 192 + mlp + 2 * 3840)
    full = 4 * 3840 * 3840 + 2 * 3840 + mlp + 2 * 3840
    assert (linear, full) == (215_570_172, 185_809_920)
    assert llama.param_count(cfg) == (
        3 * linear + full + 2 * 12544 * 3840 + 3840)
    assert llama.param_count(cfg) == 928_862_196  # 5.57 GB at 6 bytes
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 928_862_196
    # no leaf is padded: every weight keeps its published shape
    assert shapes["period"][0]["wq"].shape == (1, 3840, 2880)
    assert shapes["period"][0]["wv"].shape == (1, 3840, 5760)
    assert shapes["period"][0]["wo"].shape == (1, 5760, 3840)
    assert shapes["period"][0]["w_a"].shape == (1, 3840, 30)
    assert shapes["period"][0]["conv_k"].shape == (1, 2880, 4)
    assert shapes["period"][0]["o_norm"].shape == (1, 192)
    assert shapes["period"][3]["q_norm"].shape == (1, 3840)
    assert "attn_norm" not in shapes["period"][0]
    assert "o_norm" not in shapes["period"][3]
    for key, value in (("attention_bias", True), ("hidden_act", "gelu"),
                       ("tie_word_embeddings", True),
                       ("rope_parameters", {"rope_theta": 10000.0})):
        with pytest.raises(ValueError, match=key):
            worker.program_config({**config(NAME), key: value}, {
                "seq": 16384, "remat": "minimal", "loss_chunk": 0})
    with pytest.raises(ValueError, match="linear_num_key_heads 15"):
        worker.program_config({**config(NAME), "linear_num_key_heads": 15}, {
            "seq": 16384, "remat": "minimal", "loss_chunk": 0})
    with pytest.raises(ValueError, match="layer_types"):
        olmo_hybrid.layer_types(
            {**config(NAME), "layer_types": ["full_attention"]})


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
    for remat, chunk in (("minimal", 0), ("dots", 64)):
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


def test_every_leafs_gradient_is_the_references():
    """Float32, remat ``minimal`` as the cell runs it: every leaf of
    both operators against ``jax.grad`` of the reference."""
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
    assert {"wq", "wk", "wv", "wg", "wo", "w_a", "w_beta", "A_log",
            "dt_bias", "conv_q", "conv_k", "conv_v", "o_norm", "q_norm",
            "k_norm", "post_attn_norm", "post_mlp_norm", "w_gate", "embed",
            "lm_head", "final_norm"} <= seen


LAYER = ('            x, params["period"][l % period], l // period,\n'
         '            operator=operators[l],\n')
#: ISSUE 70's controls, as edits to the reference
CONTROLS = {
    "the decay left out": ((
        "        state = jnp.exp(g_t)[..., None, None] * state\n", ""),),
    "a decay a channel in the scalar's place": ((
        "        state = jnp.exp(g_t)[..., None, None] * state\n",
        "        ramp = 2.0 * (jnp.arange(dk, dtype=F32) + 0.5) / dk\n"
        "        state = jnp.exp(\n"
        "            g_t[..., None, None] * ramp[:, None]) * state\n"),),
    "beta without its factor 2": ((
        "        beta = 2.0 * beta\n", "        pass\n"),),
    "the l2 norm off q and k": (
        ('    q = l2norm(by_head(conv_silu(x @ p["wq"], p["conv_q"])))\n',
         '    q = by_head(conv_silu(x @ p["wq"], p["conv_q"]))\n'),
        ('    k = l2norm(by_head(conv_silu(x @ p["wk"], p["conv_k"])))\n',
         '    k = by_head(conv_silu(x @ p["wk"], p["conv_k"]))\n')),
    "the scale at dv ** -0.5": ((
        "    return jnp.moveaxis(o, 0, 1) / jnp.sqrt(F32(dk))\n",
        "    return jnp.moveaxis(o, 0, 1) / jnp.sqrt(F32(v.shape[-1]))\n"),),
    "a sigmoid for the gate's silu": ((
        '    o = o * jax.nn.silu(x @ p["wg"])\n',
        '    o = o * jax.nn.sigmoid(x @ p["wg"])\n'),),
    "the gate ahead of the norm": ((
        '    o = rms_norm(o, p["o_norm"], eps).reshape(b, s, -1)\n'
        '    o = o * jax.nn.silu(x @ p["wg"])\n',
        '    o = o * jax.nn.silu(x @ p["wg"]).reshape(o.shape)\n'
        '    o = rms_norm(o, p["o_norm"], eps).reshape(b, s, -1)\n'),),
    "silu off the convolutions": ((
        "    return jax.nn.silu(c)\n", "    return c\n"),),
    "the block's norms ahead of the branches": (
        ('            out = full_attention(x, p, heads, kv_heads, eps)\n',
         '            out = full_attention(\n'
         '                rms_norm(x, p["post_attn_norm"], eps), p, heads,\n'
         '                kv_heads, eps)\n'),
        ('            out = linear_attention(x, p, linear_heads, eps, '
         'neg_eigval)\n',
         '            out = linear_attention(\n'
         '                rms_norm(x, p["post_attn_norm"], eps), p,\n'
         '                linear_heads, eps, neg_eigval)\n'),
        ('        x = x + rms_norm(out, p["post_attn_norm"], eps)\n'
         '        out = (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) '
         '@ p["w_down"]\n'
         '        return x + rms_norm(out, p["post_mlp_norm"], eps)\n',
         '        x = x + out\n'
         '        y = rms_norm(x, p["post_mlp_norm"], eps)\n'
         '        out = (jax.nn.silu(y @ p["w_gate"]) * (y @ p["w_up"])) '
         '@ p["w_down"]\n'
         '        return x + out\n')),
    "q's and k's norm a head": (
        ('    q = rms_norm(x @ p["wq"], p["q_norm"], eps).reshape('
         'b, s, heads, -1)\n',
         '    q = (x @ p["wq"]).reshape(b, s, heads, -1)\n'
         '    q = rms_norm(q, p["q_norm"].reshape(heads, -1), eps)\n'),
        ('    k = rms_norm(x @ p["wk"], p["k_norm"], eps).reshape('
         'b, s, kv_heads, -1)\n',
         '    k = (x @ p["wk"]).reshape(b, s, kv_heads, -1)\n'
         '    k = rms_norm(k, p["k_norm"].reshape(kv_heads, -1), eps)\n')),
    "a rotation on the attention layer": ((
        "    return x\n\n\ndef full_attention",
        "    from yardstick.reference import rotate\n"
        "    return rotate(x, 10000.0)\n\n\ndef full_attention"),),
    "the full layer first in the period": ((
        LAYER,
        '            x, params["period"][(l - 1) % period], l // period,\n'
        '            operator=operators[(l - 1) % period],\n'),),
    "a tied head": ((
        '    return params["lm_head"]\n',
        '    return params["embed"].T\n'),),
}
#: the reference in the nearest precision below the program's
#: bfloat16: every matrix and what each branch reads rounded to float8
#: (e4m3, a scale a tensor), the sums in float32
FLOAT8 = (
    ('#: query rows whose scores against every key are held at once\n',
     'def q8(a):\n'
     '    s = jnp.max(jnp.abs(a)) / 448.0\n'
     '    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s\n\n\n'
     '#: query rows whose scores against every key are held at once\n'),
    ('        p = layer(blocks, i)\n'
     '        if operator == "full_attention":\n',
     '        p = layer(blocks, i)\n'
     '        p = {k: q8(v) if v.ndim > 1 else v for k, v in p.items()}\n'
     '        wide, x = x, q8(x)\n'
     '        if operator == "full_attention":\n'),
    ('        x = x + rms_norm(out, p["post_attn_norm"], eps)\n',
     '        wide = wide + rms_norm(out, p["post_attn_norm"], eps)\n'
     '        x = q8(wide)\n'),
    ('        return x + rms_norm(out, p["post_mlp_norm"], eps)\n',
     '        return wide + rms_norm(out, p["post_mlp_norm"], eps)\n'),
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
    times what the unchanged pair agrees to in float32 (2e-5), or is
    no number at all."""
    difference = most_off(
        edited(term.split()[0], *CONTROLS[term]), float32_cases)
    # without the l2 norm the rule's ``I - beta k k^T`` is no
    # contraction and the loss is not a number: that shows too
    assert not difference <= 4e-4, (term, difference)


def test_the_reference_in_float8_shows(float32_cases):
    assert most_off(
        edited("float8", *FLOAT8), float32_cases
    ) > worker.REFERENCE_TOLERANCE


def test_reference_refuses_more_positions_than_the_source_declares():
    cfg_file, _, params, batch = _case("float32")
    with pytest.raises(ValueError, match="64 positions"):
        reference.loss(
            {**cfg_file, "max_position_embeddings": 64}, params, *batch)
    with pytest.raises(ValueError, match="linear_num_key_heads 1"):
        reference.loss(
            {**cfg_file, "linear_num_key_heads": 1}, params, *batch)


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
    assert "dlrover_tpu" not in body and "delta_rule" not in body
    assert "lax.scan" in body and "cumsum" not in body  # token by token
    assert "lax.map" in body and "HIGHEST" in body  # rows in blocks
    with open(os.path.join(cells.HERE, "families", "olmo_hybrid.py")) as f:
        top = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top == []  # no JAX, nothing of the program, at import


def test_reference_recurrence_is_the_programs_scan():
    """The reference's walk a position at a time, its state ``[dk,
    dv]``, against the program's chunked plain path, at widths that
    differ and a decay no floor would leave alone."""
    from dlrover_tpu.ops import delta_rule

    keys = jax.random.split(jax.random.key(4), 5)
    q, k = (jax.random.normal(key, (2, SEQ, 3, 24)) for key in keys[:2])
    q, k = (a / jnp.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    v = jax.random.normal(keys[2], (2, SEQ, 3, 40))
    g = -20.0 * jax.random.uniform(keys[3], (2, SEQ, 3))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (2, SEQ, 3)))
    want = edited("recurrence").recurrence(q, k, v, g, beta)
    got = delta_rule.gated_delta_rule(q, k, v, g, beta)
    assert want.shape == got.shape == (2, SEQ, 3, 40)
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_reference_attention_walks_rows_in_blocks():
    from dlrover_tpu.ops.attention import mha_reference

    ref = edited("attention")
    keys = jax.random.split(jax.random.key(5), 3)
    q, k, v = (jax.random.normal(key, (2, SEQ, 3, 16)) for key in keys)
    want = mha_reference(q, k, v, causal=True).reshape(2, SEQ, -1)
    for rows in (SEQ, 16):
        got = ref.attention(q, k, v, rows=rows)
        assert float(jnp.abs(got - want).max()) < 1e-5
    with pytest.raises(ValueError, match="blocks of 48"):
        ref.attention(q, k, v, rows=48)


def test_counts_worked_by_hand():
    c = config(NAME)
    s = counts.shape(c)
    assert (s["hidden"], s["ffn"], s["layers"], s["heads"], s["kv_heads"],
            s["head_dim"], s["vocab"]) == (3840, 11008, 4, 30, 30, 128, 12544)
    assert (s["attention_layers"], s["linear_layers"], s["linear_heads"],
            s["linear_key_dim"], s["linear_value_dim"], s["taps"]) == (
                1, 3, 30, 96, 192, 4)
    mlp = 3 * 3840 * 11008
    linear = 2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30
    assert counts.matmul_params(c) == (
        4 * 3840 * 3840 + 3 * linear + 4 * mlp + 3840 * 12544)
    assert counts.matmul_params(c) == 880_512_000
    assert counts.attention_forward_flops_per_token(c, 16384) == (
        2.0 * 30 * 128 * 16384)
    assert counts.train_flops_per_token(c, 16384) == 3.0 * (
        2.0 * 880_512_000 + 2.0 * 30 * 128 * 16384)
    # 5.66 GFLOP a token, 92.7 TFLOP a step
    assert counts.train_flops_per_token(c, 16384) == 5_660_559_360.0
    flops, nbytes = counts.attention_kernel_step(c, 1, 16384)
    assert flops == 7.0 * 30 * 16384 * 16384 * 128
    assert nbytes == 12 * (16384 * 30 * 128 * 2)
    flops, nbytes = olmo_hybrid.delta_rule_step(c, 16384)
    assert flops == 3 * 21.0 * 16384 * 30 * 96 * 192
    keys, values, a_head = 2 * 30 * 96, 2 * 30 * 192, 4 * 30
    # q, k, v, o and g, beta; then q, k, v, do, g, beta and the five
    # gradients: 92,880 bytes a token and layer
    a_token = (2 * keys + 2 * values + 2 * a_head) + (
        2 * keys + 2 * values + 2 * a_head + 2 * keys + values + 2 * a_head)
    assert a_token == 92_880 and nbytes == 3 * 16384 * a_token


def test_the_share_of_a_roofline_stays_under_100_at_the_kernels_least():
    """``delta_rule_roofline_pct`` in this cell: the least time is the
    bytes' (5.6 ms a step at 819 GB/s), so a run whose kernels took
    that long reads 100 and any real one less."""
    run = {"config": config(NAME), "cell": {"chips": 1},
           "traffic": {"global_batch": 1, "seq": 16384},
           "peak": cells.peak_of("TPU v5 lite")}
    seconds, bound = delta_rule_roofline_pct.least_seconds(run)
    assert bound == "memory"
    assert seconds == pytest.approx(
        3 * 16384 * 92_880 / run["peak"]["hbm_bytes_per_s"])
    assert 0.0055 < seconds < 0.0057
    run["trace"] = {"steps": 4, "ops": [
        ("delta_rule.3", 4 * seconds / 2, 8),
        ("delta_rule.7", 4 * seconds / 2, 4), ("fusion.1", 1.0, 4)]}
    assert delta_rule_roofline_pct.read(run) == pytest.approx(100.0)


def test_what_the_configuration_states():
    c = config(NAME)
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Olmo-Hybrid-7B"]
    assert c["published"] == row["config"] and c["source"] == row["source_url"]
    assert c["family"] == "olmo_hybrid" and c["dtype"] == "bfloat16"
    assert sorted(c["reduced"]) == [
        "layer_types", "num_hidden_layers", "vocab_size"]
    # every published number is run but the cut
    changed = {k for k, v in row["config"].items() if c[k] != v}
    assert changed == set(c["reduced"])
    assert c["layer_types"] == row["config"]["layer_types"][:4] == [
        "linear_attention"] * 3 + ["full_attention"]
    assert row["config"]["layer_types"] == c["layer_types"] * 8
    assert (c["num_hidden_layers"], c["vocab_size"]) == (4, 12544)
    assert 12544 == 100352 // 8
    # every published width as it is
    assert (c["hidden_size"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["linear_num_key_heads"], c["linear_num_value_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"],
            c["linear_conv_kernel_dim"], c["rms_norm_eps"]) == (
                3840, 11008, 30, 30, 30, 30, 96, 192, 4, 1e-6)
    assert c["linear_allow_neg_eigval"] is True
    assert c["rope_parameters"] == {"rope_theta": None}
    assumed = c["assumed"]
    for key in ("block", "block_origin", "attention", "linear", "mlp",
                "max_seq_len", "optimizer_state", "embed_init_std", "draws",
                "draws_origin"):
        assert assumed[key], key
    assert "head_init_std" in assumed
    assert c["share"]["stages"] == 8 and "0-12,543" in c["share"]["vocab_held"]
    assert "eight stages" in c["deployment"]
    depth = c["depth"]
    assert depth["accepted_peak_memory_in_bytes"]
    tiny = config("tiny-olmo_hybrid")
    assert tiny["rehearsal"] == {"global_batch": 2, "seq": 128}
    assert set(tiny) - {"rehearsal"} <= set(c)
    # one whole period at toy widths: key and value widths unequal and
    # neither a power of two, a head count that is no power of two
    dk, dv, heads = (tiny["linear_key_head_dim"],
                     tiny["linear_value_head_dim"],
                     tiny["linear_num_value_heads"])
    assert dk != dv and all(n & (n - 1) for n in (dk, dv, heads))
    assert tiny["layer_types"] == c["layer_types"]


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
            "attn_roofline_pct", "delta_rule_ms",
            "delta_rule_roofline_pct"} <= reported
    assert not {"ssd_ms", "moe_expert_ms", "short_conv_ms",
                "selective_scan_ms", "collective_exposed_ms"} & reported
    for name in ("delta_rule_ms", "delta_rule_roofline_pct"):
        assert CELL in entry(bench["per_layer"], name)["workloads"]
    _, _, traffic = cells.load_cell(CELL)
    assert (traffic["seq"], traffic["global_batch"], traffic["remat"],
            traffic["loss_chunk"]) == (16384, 1, "minimal", 0)
