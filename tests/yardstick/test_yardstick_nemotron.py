"""The ``nemotron`` family: its program (models/llama.py as a stack of
one-branch blocks: Mamba-2 mixers whose scan is ops/ssd.py's chunked
dual, attention without positions, experts without a gate in a latent
beside a shared expert on the stream, a share of them held, and a
prediction module of two sublayers) against ``references/nemotron.py``
(the recurrence a position at a time) at the tiny size, each term of
the blocks showing when it is changed; the shares adding up to the
uncut layer; its counts against integers worked by hand; what the
configuration's file states."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.parallel import moe
from yardstick import cells, counts, reference, worker
from yardstick.families import nemotron

SEQ, SEQUENCES = 128, 4
NAME = "nemotron-3-super-120b-a12b-ep64"
CELL = NAME + ".steady"
REFERENCE = os.path.join(cells.HERE, "references", "nemotron.py")
TRAFFIC = {"seq": SEQ, "remat": "off", "loss_chunk": 0}


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _case(dtype, draw=True, sequences=SEQUENCES, seed=7):
    cfg_file = dict(config("tiny-nemotron"), dtype=dtype)
    cfg = worker.program_config(cfg_file, TRAFFIC)
    params = llama.init_params(jax.random.key(2), cfg)
    if draw:
        params = drawn(params)
    tokens, targets = worker.SeededTokens(
        seed, SEQ, cfg_file["vocab_size"])(0, sequences)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    return cfg_file, cfg, params, batch


def drawn(params):
    """``params`` with every selection bias and convolution bias
    drawn at 0.3, the grouped norm's scale and ``D`` at 1 +/- 0.5 (the
    program starts them at zero and one, where they change nothing),
    and the head at six times the tiny file's (three times its fan-in
    deviation): over random targets a changed trunk moves the mean
    loss by a sum of mean zero over the positions, whose size goes
    with the logits'."""
    keys = iter(jax.random.split(jax.random.key(3), 64))

    def draw(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else None
        if name in ("expert_bias", "ssm_conv_b"):
            return 0.3 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if name in ("ssm_norm", "D"):
            return leaf * jax.random.uniform(
                next(keys), leaf.shape, leaf.dtype, 0.5, 1.5)
        return leaf * 6.0 if name == "lm_head" else leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def test_program_config_takes_the_sources_keys():
    cfg = worker.program_config(
        config(NAME), {"seq": 8192, "remat": "minimal", "loss_chunk": 0})
    assert (cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.moe_latent_size,
            cfg.moe_shared_expert_intermediate_size) == (
                4096, 2688, 1024, 5376)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
            cfg.ssm_state_size, cfg.conv_kernel, cfg.chunk_size) == (
                128, 64, 8, 128, 4, 128)
    assert cfg.use_conv_bias is True
    assert (cfg.num_experts, cfg.moe_top_k) == (512, 22)  # the router's
    assert (cfg.moe_first_expert_held, cfg.moe_experts_held) == (0, 8)
    assert cfg.moe_gate == "sigmoid" and cfg.use_expert_bias is True
    assert (cfg.moe_expert_act, cfg.moe_expert_gated) == ("relu2", False)
    assert (cfg.moe_routed_scaling, cfg.moe_topk_norm_eps,
            cfg.moe_shared_experts) == (5.0, 1e-20, 1)
    assert cfg.norm_topk_prob is True
    assert cfg.moe_capacity_factor == 0.0  # dropless, stated
    assert cfg.moe_bias_update_rate == 0.0  # the bias a frozen buffer
    assert (cfg.mtp_layers, cfg.mtp_loss_weight,
            cfg.mtp_hybrid_override_pattern) == (1, 0.3, "*E")
    assert cfg.norm_eps == 1e-5 and not cfg.tie_word_embeddings
    assert cfg.rope_layout == (0,) * 11  # no rotary embedding
    lead, period = cfg.layer_plan()
    assert lead == () and len(period) == 11
    assert "".join(
        {"state_space": "M", "full_attention": "*", "none": "E"}[k.operator]
        for k in period) == "MEMEMEM*EME"
    assert all((k.operator == "none") != (k.ffn == "none") for k in period)
    # ISSUE 54's arithmetic, by the program's own count: a mixer's
    # two projections, its taps and bias, the grouped norm's scale,
    # three vectors a head and the block's norm; attention's four
    # matrices; an expert layer outside its routed experts (two latent
    # projections, router and bias, the shared expert) and one expert
    mixer = (4096 * 18560 + 8192 * 4096 + 10240 * 5 + 8192 + 3 * 128
             + 4096)
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    outside = (2 * 4096 * 1024 + 4096 * 512 + 512 + 2 * 4096 * 5376
               + 4096)
    expert = 2 * 1024 * 2688
    assert (mixer, attention, outside, expert) == (
        109_640_064, 35_655_680, 54_530_560, 5_505_024)
    module = 2 * 4096 * 4096 + 3 * 4096 + attention + outside + 8 * expert
    assert module == 167_793_152
    layers = 5 * mixer + attention + 5 * (outside + 8 * expert)
    assert llama.param_count(cfg) == (
        layers + 2 * 16384 * 4096 + 4096 + module)
    assert llama.param_count(cfg) == 1_378_724_736  # 8.27 GB at 6 bytes
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 1_378_724_736
    assert shapes["period"][0]["ssm_in"].shape == (1, 4096, 18560)
    assert shapes["period"][1]["w_up"].shape == (1, 8, 1024, 2688)
    assert shapes["period"][7]["wk"].shape == (1, 4096, 256)
    assert shapes["mtp"][0]["block"][1]["ws_up"].shape == (4096, 5376)


def test_float32_program_agrees_with_the_reference():
    cfg_file, cfg, params, batch = _case("float32")
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < 2e-5, (program, ref)


def test_bf16_program_is_inside_the_chip_tolerance():
    """At the tiny size the bf16 reading is the noise of flipped top-4
    choices behind 64-wide streams, times the weights' factor of 5 and
    the logits' deviation: six seeds read 0.0008-0.0024 at the tiny
    file's head (half its fan-in deviation, a quarter of the 16
    experts held), and 0.005-0.014 with half of them held under a head
    at its fan-in deviation. As the program starts, the biases at
    zero."""
    cfg_file, cfg, params, batch = _case("bfloat16", False, 8, seed=5)
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


GATE = "    o = o.reshape(b, s, inner) * jax.nn.silu(z)\n"
NORM = (
    "    by_group = o.reshape(b, s, groups, -1)\n"
    "    by_group = by_group * jax.lax.rsqrt(\n"
    "        jnp.mean(by_group * by_group, axis=-1, keepdims=True) + eps)\n"
)
SQUARE = "    return jnp.square(jax.nn.relu(y @ w_up)) @ w_down\n"
#: the controls of ISSUE 54, as edits to the reference
CONTROLS = {
    "a = 1": ((
        "        state = jnp.exp(A * dt_t)[..., None, None] * state + "
        "jnp.einsum(\n", "        state = state + jnp.einsum(\n"),),
    "no D": ((
        "        return state, jnp.einsum(\"bhdn,bhn->bhd\", state, c_t) "
        "+ (\n            D[:, None] * x_t)\n",
        "        return state, jnp.einsum(\"bhdn,bhn->bhd\", state, c_t)\n"),),
    "no gate silu(z)": ((GATE, "    o = o.reshape(b, s, inner)\n"),),
    "the norm before the gate": (
        (GATE, "    o = o.reshape(b, s, inner)\n"),
        ("    return (by_group.reshape(b, s, inner) * p[\"ssm_norm\"]) "
         "@ p[\"ssm_out\"]\n",
         "    return (by_group.reshape(b, s, inner) * p[\"ssm_norm\"]\n"
         "            * jax.nn.silu(z)) @ p[\"ssm_out\"]\n")),
    "one norm group for all": ((
        "    by_group = o.reshape(b, s, groups, -1)\n",
        "    by_group = o.reshape(b, s, 1, -1)\n"),),
    "no conv bias": ((
        "    return jax.nn.silu(c + bias)\n", "    return jax.nn.silu(c)\n"),),
    "three taps for four": ((
        "    for j in range(taps):\n", "    for j in range(1, taps):\n"),),
    "relu for relu2": ((
        SQUARE, "    return jax.nn.relu(y @ w_up) @ w_down\n"),),
    "a gated expert": ((
        SQUARE,
        "    return (jax.nn.silu(y @ w_up) * (y @ w_up)) @ w_down\n"),),
    # the routed experts on the stream's first columns, not the latent
    "experts fed the stream": ((
        '    u = y @ p["w_latent_down"]\n',
        '    u = y[..., :p["w_latent_down"].shape[1]]\n'),),
    "factor 1 for 5": (("    picked = picked * scaling\n", ""),),
    "top-k of s without the bias": ((
        'jax.lax.top_k(score + p["expert_bias"], per_token)',
        "jax.lax.top_k(score, per_token)"),),
    "weights not renormalised": (("    if norm_topk:\n",
                                  "    if False:\n"),),
    "no shared expert": ((
        ' + ungated(y, p["ws_up"], p["ws_down"])\n', "\n"),),
    "no prediction module": ((
        '        main + assumed["mtp_loss_weight"] * mtp\n',
        "        main + 0.0 * mtp\n"),),
    "the module's expert sublayer left out": ((
        '            config["mtp_hybrid_override_pattern"], '
        'module["block"]):\n',
        '            config["mtp_hybrid_override_pattern"][:1], '
        'module["block"]):\n'),),
    "q and k rotated": (
        ("    return attention(q, k, v) @ p[\"wo\"]\n",
         "    from yardstick.reference import rotate\n"
         "    return attention(rotate(q, 10000.0), rotate(k, 10000.0), v) "
         "@ p[\"wo\"]\n"),),
}
#: the reference in the nearest precision below the program's
#: bfloat16, throughout: every matrix, the embedding's rows, a block's
#: normed stream, the final normed streams and the head rounded to
#: float8 (e4m3, a scale a tensor), as the program keeps each in
#: bfloat16; the sums in float32
FLOAT8 = (
    ('    x = embed(params["embed"], tokens)\n',
     '    x = q8(embed(params["embed"], tokens))\n'),
    ('    head = params["lm_head"]\n',
     '    head = q8(params["lm_head"].astype(F32))\n'),
    ('    main = mean_nll(final_rms(x, params["final_norm"], eps), head, '
     'targets)\n',
     '    main = mean_nll(q8(final_rms(x, params["final_norm"], eps)), '
     'head, targets)\n'),
    ('    mtp = mean_nll(final_rms(y, module["final_norm"], eps), head, '
     'further)\n',
     '    mtp = mean_nll(q8(final_rms(y, module["final_norm"], eps)), '
     'head, further)\n'),
    ('EXPERTS = ("w_up", "w_down")\n',
     'EXPERTS = ("w_up", "w_down")\n\n\n'
     'def q8(a):\n'
     '    s = jnp.max(jnp.abs(a)) / 448.0\n'
     '    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s\n'),
    ('        if branch == "M":\n',
     '        p = {k: q8(v) if v.ndim > 1 else v for k, v in p.items()}\n'
     '        if branch == "M":\n'),
    ('            y = rms_norm(x, p["attn_norm"], eps)\n'
     '            return x + mamba(',
     '            y = q8(rms_norm(x, p["attn_norm"], eps))\n'
     '            return x + mamba('),
    ('            y = rms_norm(x, p["attn_norm"], eps)\n'
     '            return x + full_attention(',
     '            y = q8(rms_norm(x, p["attn_norm"], eps))\n'
     '            return x + full_attention('),
    ('        y = rms_norm(x, p["mlp_norm"], eps)\n',
     '        y = q8(rms_norm(x, p["mlp_norm"], eps))\n'),
    ('    return one_layer[e].astype(F32)\n',
     '    return q8(one_layer[e].astype(F32))\n'),
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
    """A reference with one term of a block altered is off by more
    than the chip's tolerance, in float32, where the unchanged pair
    agrees to 2e-5 (the biases, the grouped norm's scale, ``D`` and
    the head drawn: ``drawn``)."""
    difference = most_off(
        edited(term.split()[0], *CONTROLS[term]), float32_cases)
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
    assert "dlrover_tpu" not in body and "ssd" not in body
    # a position at a time: no chunk, no cumulated decay, no mask
    assert "lax.scan" in body and "cumsum" not in body
    assert "tril" not in body and "chunk" not in body
    with open(os.path.join(cells.HERE, "families", "nemotron.py")) as f:
        top = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top == []  # no JAX, nothing of the program, at import


def test_reference_recurrence_is_the_programs_scan():
    """The reference's position-by-position walk against the
    program's chunked entry (ops/ssd.py) on operands of their own,
    two heads a group."""
    from dlrover_tpu.ops.ssd import ssd_scan

    ref = edited("recurrence")
    keys = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(keys[0], (2, 96, 4, 16))
    B, C = (jax.random.normal(key, (2, 96, 2, 8)) for key in keys[1:3])
    dt = jax.nn.softplus(jax.random.normal(keys[3], (2, 96, 4)))
    A = -jnp.exp(jax.random.normal(keys[4], (4,)))
    D = jax.random.normal(keys[5], (4,))
    want = ref.recurrence(
        x, jnp.repeat(B, 2, axis=2), jnp.repeat(C, 2, axis=2), dt, A, D)
    got = ssd_scan(
        x.reshape(2, 96, -1), B.reshape(2, 96, -1), C.reshape(2, 96, -1),
        dt, A, D, 4, 2, chunk=32).reshape(x.shape)
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())


def test_program_config_refuses_what_it_does_not_pass_on():
    tiny = config("tiny-nemotron")
    for key, other in (
            ("n_group", 2), ("topk_group", 2), ("mlp_hidden_act", "silu"),
            ("mamba_hidden_act", "relu"), ("attention_bias", True),
            ("mamba_proj_bias", True), ("mlp_bias", True), ("use_bias", True),
            ("tie_word_embeddings", True), ("num_nextn_predict_layers", 2),
            ("sliding_window", 64), ("num_hidden_layers", 12),
            ("expand", 4)):
        with pytest.raises(ValueError, match=key):
            worker.program_config({**tiny, key: other}, TRAFFIC)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        worker.program_config(
            {**tiny, "hybrid_override_pattern": "ME-EMEM*EME"}, TRAFFIC)
    raw = worker.program_config({**tiny, "norm_topk_prob": False}, TRAFFIC)
    assert raw.norm_topk_prob is False
    bare = worker.program_config({**tiny, "use_conv_bias": False}, TRAFFIC)
    assert bare.use_conv_bias is False


# -- the share ---------------------------------------------------------------

def test_the_64_shares_add_up_to_the_uncut_layer():
    """The guide's share test at the deployment's number: 64 shares of
    one expert each, of a router 64 wide, top-22. The routed parts that
    the shares give (each through the way up: it is linear, so partial
    latent sums add past it), with the shared expert's term (which
    every share computes alike, for its own tokens) counted once, add
    up to what the layer that holds all 64 gives: in the reference,
    and in the program's layer."""
    ref = edited("share")
    h, latent, m, ms, width, k = 32, 16, 12, 24, 64, 22
    keys = jax.random.split(jax.random.key(11), 8)
    y = jax.random.normal(keys[0], (2, 24, h))
    p = {
        "router": jax.random.normal(keys[1], (h, width)) * h ** -0.5,
        "expert_bias": 0.3 * jax.random.normal(keys[2], (width,)),
        "w_latent_down": jax.random.normal(keys[3], (h, latent)) * h ** -0.5,
        "w_latent_up": jax.random.normal(keys[4], (latent, h))
        * latent ** -0.5,
        "ws_up": jax.random.normal(keys[5], (h, ms)) * h ** -0.5,
        "ws_down": jax.random.normal(keys[6], (ms, h)) * ms ** -0.5,
    }
    whole = {
        "w_up": jax.random.normal(keys[7], (1, width, latent, m))
        * latent ** -0.5,
        "w_down": jax.random.normal(keys[0], (1, width, m, latent))
        * m ** -0.5,
    }
    shared = ref.ungated(y, p["ws_up"], p["ws_down"])
    with reference.HIGHEST():
        uncut, balance = ref.experts(y, whole, p, 0, k, 0, True, 1e-20, 5.0)
        parts = []
        for rank in range(64):
            one = {n: w[:, rank:rank + 1] for n, w in whole.items()}
            part, same = ref.experts(
                y, one, p, 0, k, rank, True, 1e-20, 5.0)
            assert float(same) == float(balance)  # over all 64, held or not
            parts.append(part - shared)
    assert float(jnp.abs(sum(parts) + shared - uncut).max()) < 2e-5
    assert float(jnp.abs(sum(parts)).max()) > 0.1
    # a token's 22 experts are on 22 of the 64 shares
    live = sum(float(jnp.abs(part[0, 0]).max()) > 0 for part in parts)
    assert live == k

    def program(first, held):
        out, _ = moe.dropless_moe_mlp(
            y, p["router"], None, *(whole[n][0, first:first + held]
                                    for n in ("w_up", "w_down")),
            k=k, norm_topk_prob=True, z_coef=0.0, first_held=first,
            shared=(None, p["ws_up"], p["ws_down"]), act="relu2",
            latent=(p["w_latent_down"], p["w_latent_up"]),
            gate="sigmoid", bias=p["expert_bias"], norm_eps=1e-20,
            scaling=5.0)
        return out

    mine = sum(program(rank, 1) - shared for rank in range(64)) + shared
    assert float(jnp.abs(mine - uncut).max()) < 1e-4
    assert float(jnp.abs(program(0, 64) - uncut).max()) < 1e-4


# -- the counts --------------------------------------------------------------

def test_nemotron_counts_by_hand():
    c = config(NAME)
    s = nemotron.shape(c)
    assert (s["layers"], s["ssm_layers"], s["attention_layers"],
            s["expert_layers"]) == (11, 5, 1, 5)
    assert (s["mtp_layers"], s["mtp_attention_layers"],
            s["mtp_expert_layers"]) == (1, 1, 1)
    assert (s["experts"], s["experts_held"], s["experts_per_token"],
            s["shared_experts"], s["ffn"], s["latent"], s["shared_ffn"],
            s["ffn_matrices"]) == (512, 8, 22, 1, 2688, 1024, 5376, 2)
    assert (s["heads"], s["kv_heads"], s["head_dim"]) == (32, 2, 128)
    assert (s["ssm_heads"], s["ssm_head_dim"], s["ssm_groups"],
            s["ssm_state"], s["taps"]) == (128, 64, 8, 128, 4)
    # in millions of weights met a token: a mixer's projections 109.6,
    # the attention layer's 35.7, an expert layer's router 2.1, latent
    # projections 8.4, shared expert 44.0 and 0.34 of a held expert in
    # expectation (22 x 8 / 512), the head 67.1
    mixer = 4096 * 18560 + 8192 * 4096
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256
    router, latent, shared = 4096 * 512, 2 * 4096 * 1024, 2 * 4096 * 5376
    expert, head = 2 * 1024 * 2688, 4096 * 16384
    assert (mixer, attention, router, latent, shared, expert, head) == (
        109_576_192, 35_651_584, 2_097_152, 8_388_608, 44_040_192,
        5_505_024, 67_108_864)
    met = 22 * 8 / 512
    assert met == 0.34375
    experts = router + latent + shared + met * expert
    module = 2 * 4096 * 4096 + attention + experts + head
    want = 5 * mixer + attention + 5 * experts + head + module
    assert nemotron.matmul_params(c) == counts.matmul_params(c) == want
    assert want == 1_125_466_112
    # scores and weighted values at 8,192 in the stack's attention
    # layer and the module's
    attn = counts.attention_forward_flops_per_token(c, 8192)
    assert attn == 2 * 2 * 32 * 128 * 8192 == 134_217_728
    flops = counts.train_flops_per_token(c, 8192)
    assert flops == 3 * (2 * want + attn) == 7_155_449_856
    assert 8192 * flops == pytest.approx(58.6e12, rel=2e-3)
    assert 5 * mixer / want == pytest.approx(0.487, abs=2e-3)
    assert head / want == pytest.approx(0.060, abs=1e-3)
    # the attention kernels: seven causal products, two layers
    kernel_flops, nbytes = counts.attention_kernel_step(c, 1, 8192)
    assert kernel_flops == 7 * 2 * 32 * 8192 * 8192 * 128
    assert nbytes == 2 * 6 * 8192 * (32 + 2) * 128 * 2
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(kernel_flops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(0.019534, rel=1e-3)
    # the recurrence: 15 x 64 x 128 operations a token and head; x and
    # o in bf16, a group's B and C once, Delta in float32 forward;
    # those and the cotangent read and four gradients written backward
    flops, nbytes = nemotron.ssd_step(c, 8192)
    assert flops == 5 * 15 * 8192 * 128 * 64 * 128
    x_like, bc_like, dt_like = (
        2 * 8192 * 8192, 2 * 8192 * 1024, 4 * 8192 * 128)
    assert nbytes == 5 * (5 * x_like + 6 * bc_like + 3 * dt_like)
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "memory"
    assert seconds == pytest.approx(0.004788, rel=1e-3)


def test_the_share_of_a_roofline_stays_under_100_at_the_kernels_least():
    """``ssd_roofline_pct`` with the kernels at the least time they
    could take: what the kernels move is more than the count's least
    bytes (the backward's entry states, 32 KB a chunk and head written
    and read, and the forward run twice under ``minimal`` are the
    implementation's), so the share is under 100 there, and a reading
    above it is a wrong count."""
    from yardstick.layer_metrics import ssd_ms, ssd_roofline_pct as share

    c = config(NAME)
    cell, _, traffic = cells.load_cell(CELL)
    peak = cells.peak_of("TPU v5 lite")
    _, nbytes = nemotron.ssd_step(c, 8192)
    forward = 5 * (2 * 2 * 8192 * 8192 + 2 * 2 * 8192 * 1024)
    states = 5 * 2 * 64 * 128 * 64 * 128 * 4
    moved = nbytes + forward + states  # a second forward, the states
    least = moved / peak["hbm_bytes_per_s"]
    run = {"trace": {"steps": 4, "ops": [["ssd.7", 4 * least, 12]]},
           "peak": peak, "config": c, "traffic": traffic, "cell": cell}
    got = share.read(run)
    assert 40 < got < 100, got
    assert ssd_ms.read(run) == pytest.approx(1e3 * least)
    assert share.read({**run, "trace": None}) is None
    no_kernel = {"steps": 4, "ops": [["fusion.1", 1.0, 3],
                                     ["ssd_like.3", 1.0, 3]]}
    assert share.read({**run, "trace": no_kernel}) is None
    assert ssd_ms.read({**run, "trace": no_kernel}) is None
    # a family without the operator has nothing to read
    other = config("solar-open2-250b-ep32")
    assert share.read({**run, "config": other}) is None


def test_every_published_number_is_run_but_the_cut():
    c = config(NAME)
    differs = [k for k, v in c["published"].items() if c[k] != v]
    assert sorted(differs) == sorted(c["reduced"]) == [
        "hybrid_override_pattern", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (11, 8, 16384)
    published = c["published"]["hybrid_override_pattern"]
    assert c["hybrid_override_pattern"] == published[:11] == "MEMEMEM*EME"
    # the published 40 : 40 : 8, exactly
    assert [published.count(x) for x in "ME*"] == [40, 40, 8]
    assert [c["hybrid_override_pattern"].count(x) for x in "ME*"] == [5, 5, 1]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["source_url"] == c["source"]]
    assert c["published"] == row["config"]
    for key, value in (
            ("hidden_size", 4096), ("num_attention_heads", 32),
            ("num_key_value_heads", 2), ("head_dim", 128),
            ("mamba_num_heads", 128), ("mamba_head_dim", 64),
            ("n_groups", 8), ("ssm_state_size", 128), ("conv_kernel", 4),
            ("chunk_size", 128), ("moe_intermediate_size", 2688),
            ("moe_latent_size", 1024),
            ("moe_shared_expert_intermediate_size", 5376),
            ("num_experts_per_tok", 22), ("n_shared_experts", 1),
            ("routed_scaling_factor", 5), ("expand", 2)):
        assert c[key] == c["published"][key] == value, key
    share = c["share"]
    assert share["router_width"] == c["published"]["n_routed_experts"] == 512
    assert (share["chips_sharing_a_layer"], share["rank"],
            share["first_expert_held"]) == (64, 0, 0)
    assert 8 * c["vocab_size"] == c["published"]["vocab_size"]
    assert 64 * c["n_routed_experts"] == share["router_width"]
    assert c["depth"]["found"] == 11
    for key in ("mixer", "positions", "routing", "expert_bias", "latent",
                "mtp", "mtp_loss_weight", "router_aux_loss_coef",
                "decay_draws", "rescale_prenorm_residual", "max_seq_len",
                "embed_init_std", "topk_norm_eps", "optimizer_state"):
        assert key in c["assumed"], key
    bench = cells.benchmark()
    assert bench["configs"][-1]["name"] == NAME
    assert bench["workloads"][-1]["name"] == CELL
    entry = bench["configs"][-1]
    assert entry["source"] == c["source"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "ssd_ms", "ssd_roofline_pct"]
    for m in bench["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["layer"] == "state-space scan"
    # not the experts' readers: grouped matmuls of 352 rows an expert
    # fall under the 200 operation names a reduced trace keeps
    # (PERF.md section 7), as solar's do; nor the other operators'
    for metric in ("moe_expert_ms", "moe_expert_roofline_pct",
                   "short_conv_ms", "short_conv_roofline_pct",
                   "delta_rule_ms", "delta_rule_roofline_pct"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == metric]
        assert CELL not in m["workloads"], metric
