"""Test bootstrap: force an 8-device virtual CPU mesh.

Mirrors the reference's multi-node-without-a-cluster approach
(dlrover/python/tests/test_utils.py) — sharding/mesh tests run on a virtual
8-device CPU topology; no real TPU needed. Tests and drills run on the
CPU; the chip is reached only through ``chip_smoke.py``.
"""

import os

os.environ.setdefault("DLROVER_TPU_LOG_LEVEL", "WARNING")
# hang-detector tests trip on purpose; flight-recorder dumps to the
# shared temp dir would be side effects — tests that assert on dumps
# opt back in with monkeypatch
os.environ.setdefault("DLROVER_TPU_FLIGHT_RECORDER", "0")
# subprocesses spawned by tests (agents, probes) must also land on CPU
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_NUM_CPU_DEVICES"] = "8"
# XLA CPU kills a collective when participants arrive >40s apart;
# causal ring attention at 16k trips it (see common/xla_flags.py)
from dlrover_tpu.common.xla_flags import ensure_cpu_collective_timeout

ensure_cpu_collective_timeout()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


# -- CI shard policy (pyproject [tool.pytest.ini_options] markers) --------
# Timed drills assert wall-clock SLAs (failover <60s, heartbeat windows)
# and flake when sharing cores with XLA compiles; compile-heavy modules
# dominate runtime. CI runs the three groups on separate shards.

DRILL_MODULES = {
    "test_master_failover",
    "test_two_node_failover",
    "test_e2e_elastic_run",
    "test_operator",
    "test_four_node_drill",
    "test_goodput_drill",
    "test_serving_drill",
    "test_preemption_drill",
    "test_sentinel_drill",
    "test_slice_soak_drill",
    "test_scale_up_drill",
    "test_streaming_e2e",
}
HEAVY_MODULES = {
    "test_auto",
    "test_brain_algorithms",
    "test_context_parallel",
    "test_elastic_shm_data",
    "test_flash_attention",
    "test_gpt",
    "test_moe",
    "test_parallel",
    "test_pipeline",
    "test_planner",
    "test_pp_memory",
    "test_trainer",
    "test_zero2_hlo",
}


def pytest_collection_modifyitems(config, items):
    import pytest

    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        if mod in DRILL_MODULES:
            item.add_marker(pytest.mark.drill)
        elif mod in HEAVY_MODULES:
            item.add_marker(pytest.mark.heavy)


# -- tier-1 wall-clock budget guard (ISSUE 9) -----------------------------
# Tier-1 stays fast because every module stays fast: a module that
# creeps past its budget fails the run HERE with the measured time, not
# three PRs later when the whole suite hits the CI timeout. Timed
# drills and compile-heavy modules carry explicit measured budgets;
# everything else gets the default. DLROVER_TPU_TEST_MODULE_BUDGET
# overrides the default (seconds) or disables the guard ("off").

DEFAULT_MODULE_BUDGET_S = 60.0
#: measured ceilings + headroom for the known-expensive modules; a new
#: module does NOT belong here unless its cost is inherent (wall-clock
#: SLA drills, XLA compiles), not accidental
MODULE_BUDGET_OVERRIDES = {
    "test_four_node_drill": 240.0,
    "test_goodput_drill": 180.0,
    # four real-agent-subprocess drills (chaos, fallback, spare
    # promotion, join/shrink/join oscillation) — measured 113s
    "test_reshard_drill": 180.0,
    "test_serving_drill": 120.0,
    "test_preemption_drill": 120.0,
    "test_sentinel_drill": 120.0,
    "test_master_failover": 180.0,
    "test_two_node_failover": 180.0,
    "test_e2e_elastic_run": 180.0,
    "test_slice_soak_drill": 180.0,
    "test_scale_up_drill": 120.0,
    "test_streaming_e2e": 120.0,
    "test_auto": 120.0,
    # compiles for a described v5e: the 22-layer one-chip step, the
    # four-chip fsdp step, twelve kernels — held to two cores so as
    # not to starve the drills: measured 64s alone, 72s beside five
    # other workers on a quiet machine (PR 31; 189s on a loaded one
    # when it was 88s alone); since PR 35 also smallthinker's whole
    # step at the default effort, 100s of its own on the two cores;
    # since PR 36 lfm2's thirteen-layer step too, 150s of its own;
    # since PR 39 OLMoE's step traced and lowered, 20s of its own;
    # since PR 43 joyai's step at the least effort, 70s of its own,
    # and latent attention's kernels on its parts: 394s alone; since
    # PR 44 solar's four-layer step at the least effort, 55-75s of
    # its own, which is all the budget gains: 562s beside five other
    # workers; since PR 49 trinity's nine-layer step at the least
    # effort, 70s of its own; since PR 52 the windowed kernels at
    # trinity's shape as well, two compiles of 12s: 965s beside five
    # other workers; since PR 54 nemotron's eleven-layer step at the
    # least effort, 50-60s of its own, and the scan's kernels alone;
    # since PR 64 sala's four-layer step at the least effort, 30s of
    # its own: 1,265s beside five other workers on a loaded machine;
    # since PR 67 the gate-and-norm frame's kernels at three more
    # cells' shapes, 5s: 1,508s in a whole run of 1,394s
    "test_chip_compile": 1600.0,
    # the gate-and-norm frame's two bodies (the heads' with a bias and
    # without) in interpret mode, at widths of 4,096 and 8,192 too (PR
    # 67): 78s alone; since PR 70 a third body (the heads' with
    # ``silu``) through the same cases, 119 tests for 87: 105s alone
    "test_gated_norm": 300.0,
    # the sala family's program against its reference, whose
    # selection walks whole score arrays and whose recurrence a
    # position at a time, with seventeen edited references jitted
    # anew (PR 64): 44s alone, 76s beside five other workers
    "test_yardstick_sala": 120.0,
    # a four-layer stack of the selection and the lightning layers
    # jitted forward and backward, each factor, norm, gate and
    # rotation changed in turn (PR 64): 73s beside five other workers
    "test_llama_sala": 120.0,
    # the state-space scan's Pallas kernels in interpret mode, forward
    # and backward on seven shapes (PR 54): 40s alone, 85s beside
    # three other workers
    "test_ssd": 150.0,
    # the convolution's kernels in interpret mode, since PR 54 with a
    # bias as well (nine more cases): 63s beside three other workers;
    # since PR 70 a head of 96 in rows of 2,880 on the plain path, six
    # more: 107s beside five other workers
    "test_kda_conv": 180.0,
    # the walk's written-out backward jitted for experts without a
    # gate, in a latent and not, against a dense loop (PR 54): 69s
    # beside three other workers
    "test_moe_ungated": 120.0,
    # eleven-layer one-branch models jitted forward and backward, and
    # a three-layer one under each remat policy (PR 54): 110s beside
    # three other workers
    "test_llama_state_space": 200.0,
    # seventeen edited references on two batches of an eleven-layer
    # model with a position-by-position scan (PR 54): 150s beside
    # three other workers
    "test_yardstick_nemotron": 240.0,
    # a two-layer stack walked four times, jitted forward and backward
    # under each remat policy and chunking (PR 58): 41s alone, up to
    # 87s beside three other workers
    "test_llama_loop": 150.0,
    # the same loop against the float32 reference and its gradients,
    # eight edited references on two batches (PR 58): 35s alone, 62s
    # beside three other workers
    "test_yardstick_ouro": 120.0,
    # a five-layer stack of two operators (four token-by-token scans
    # in the reference) against the float32 reference and its
    # gradients, seventeen edited references on two batches, sixteen
    # shares (PR 60): 110 s alone
    "test_yardstick_kimi": 300.0,
    # five- and nine-layer stacks of the delta rule and latent
    # attention jitted forward and backward under each remat policy,
    # and a trainer stepped on eight CPU devices (PR 60): 71 s alone
    "test_llama_latent_pattern": 180.0,
    # two whole rehearsals of the new cell, launcher to last line: 69s
    "test_yardstick_nemotron_rehearsal": 150.0,
    # one whole rehearsal of a fourteen-layer period, every layer a
    # position of its own in the step's program (PR 68): 45s alone,
    # 82s beside three other workers
    "test_yardstick_jamba_rehearsal": 150.0,
    # the same period against the float32 reference and its gradients,
    # eleven edited references on two batches (PR 68): 57s alone, 79s
    # beside three other workers, 184s beside five that compile too
    "test_yardstick_jamba": 300.0,
    # the same period of four layers, three of them the delta rule with
    # one decay a head, against the float32 reference and its
    # gradients, thirteen edited references and the float8 one on two
    # batches (PR 70): 42s alone on four workers, 125s of tests
    "test_yardstick_olmo_hybrid": 300.0,
    # one whole rehearsal of the new cell, launcher to last line (PR
    # 70): 35s alone
    "test_yardstick_olmo_hybrid_rehearsal": 150.0,
    # a four-layer stack of the one-decay delta rule jitted forward
    # and backward under each remat policy, once through the kernels
    # in interpret mode (PR 70): 37s alone on four workers, 82s of tests
    "test_llama_gdn": 200.0,
    # the selective scan's Pallas kernels in interpret mode, forward
    # and backward on four shapes (PR 68): 50s alone, 76s beside three
    # other workers
    "test_selective_scan": 150.0,
    # a four-layer stack of mixers jitted forward and backward under
    # each remat policy (PR 68): 40s alone
    "test_llama_mamba": 120.0,
    # Pallas kernels in interpret mode, since PR 39 the in-place sum
    # against megablox's on seven pieces: 47s alone, 71s beside five
    # other workers
    "test_grouped_matmul": 100.0,
    # Pallas kernels in interpret mode at groups 1, 4 and 7 (45 s
    # alone), and eight-layer patterned models jitted forward and
    # backward under each remat policy (75 s alone): PR 34
    # the dropless layer jitted forward and backward under each remat
    # policy: 55 s alone, 64 s beside five other workers (PR 34)
    "test_llama_experts": 90.0,
    # a share's walk jitted forward and backward for six routings at
    # two chunk sizes: 36 s alone, 60 s beside five other workers
    # (PR 35)
    "test_moe_share_walk": 90.0,
    # Pallas kernels in interpret mode; since PR 52 thirty-four cases
    # more (the band's column tiles at the cells' geometry in small,
    # the pair's kernels): 45 s alone at four workers, 110 s in one
    # process beside another run
    "test_attention_window": 300.0,
    # since PR 42 the share test with a shared expert too, six cases
    # more: 158 s alone, 270 s beside five other workers
    "test_llama_pattern": 340.0,
    # nine-layer hybrid models jitted forward and backward under each
    # remat policy, the convolution's kernels in interpret mode: 70 s
    # alone (PR 36), 175 s beside five other workers (PR 42)
    "test_llama_hybrid": 220.0,
    # the latent model and its prediction module jitted forward and
    # backward under each remat policy, a trainer over eight CPU
    # devices: 85 s alone (PR 42); since PR 43 the parts beside the
    # whole q and k too, the model jitted in both forms under two
    # remat policies: 115 s alone
    "test_llama_latent": 300.0,
    # the dropless layer at every remat policy: 71 s beside five other
    # workers (PR 42)
    "test_moe_dropless": 100.0,
    # eleven changed references and a changed program jitted at the
    # tiny size, nine layers each: 75 s alone (PR 36)
    "test_yardstick_lfm2": 150.0,
    # changed references jitted at the tiny size: 68 s beside
    # five other workers in PR 43's whole run (the default's 60 s
    # failed a run in which every test passed)
    "test_yardstick_smallthinker": 120.0,
    # launcher, agent, worker and coworkers at thirteen tiny layers:
    # 45 s alone, 64 s beside three other workers (PR 36)
    "test_yardstick_lfm2_rehearsal": 120.0,
    # eighteen changed references jitted at the tiny size on two
    # batches, the program under three remat policies: 80 s alone,
    # 312 s beside five other workers, whose cores the compiler of
    # each edited module wants too (PR 42)
    "test_yardstick_joyai": 400.0,
    # launcher, agent, worker and coworkers at three tiny layers and
    # the prediction module: 40 s alone (PR 42)
    "test_yardstick_joyai_rehearsal": 120.0,
    # two traced rehearsals (launcher, agent, worker, coworkers), one
    # of them holding a step for a second: 35 s alone, 84 s beside five
    # other workers (PR 38)
    "test_yardstick_host_stall": 150.0,
    # the delta rule's kernels in interpret mode and the recurrence
    # walked token by token, each jitted forward and backward for
    # nine sets of operands: 85 s alone, 98 s beside five other
    # workers (PR 44); since PR 45 the rows entry against the 4-D one
    # on both paths in two precisions, 35 tests for 26: 82 s alone,
    # 234 s as the only module of six workers, which all compile at
    # once; since PR 46 the kernels at one, two and four heads a grid
    # step, 53 tests for 35: 481 s as the only module of six workers;
    # since PR 70 one decay a head on heads of two widths, 94 tests for
    # 53, the new ones at 24 x 40 and three at 96 x 192: 120 s on four
    # workers
    "test_delta_rule": 900.0,
    # eight-layer delta-rule hybrids jitted forward and backward under
    # each remat policy, the kernels in interpret mode inside a model,
    # a trainer over eight CPU devices: 145 s alone, 199 s beside five
    # other workers (PR 44)
    "test_llama_linear": 400.0,
    # fourteen changed references jitted at the tiny size on two
    # batches, each walking the recurrence token by token, the program
    # under three remat policies: 75 s alone, 206 s beside five other
    # workers (PR 44)
    "test_yardstick_solar": 400.0,
    # fourteen changed references jitted at the tiny size on two
    # batches, nine layers each, the program under three remat
    # policies: 75 s alone (PR 49)
    "test_yardstick_trinity": 300.0,
    # nine-layer trainers jitted and stepped, the reference's counts
    # beside each step: 60 s alone (PR 49)
    "test_moe_bias_rule": 200.0,
    # five tiny families' training steps lowered, three of them run
    # once: 50 s alone (PR 49)
    "test_llama_static_path": 150.0,
    "test_context_parallel": 180.0,
    # since PR 42 the kernels at latent attention's (192, 128) too,
    # one backward kernel and the pair: 195 s alone, 272 s beside five
    # other workers; since PR 43 the kernels on latent attention's
    # parts in seven forms, jitted: 203 s alone
    "test_flash_attention": 400.0,
    "test_gpt": 120.0,
    "test_moe": 120.0,
    "test_parallel": 120.0,
    "test_pipeline": 120.0,
    "test_pp_memory": 120.0,
    "test_trainer": 120.0,
    "test_zero2_hlo": 120.0,
}

_module_spent = {}


def _module_budget_default():
    raw = os.environ.get("DLROVER_TPU_TEST_MODULE_BUDGET", "")
    if raw.lower() in ("off", "no", "false", "0"):
        return None
    try:
        return float(raw) if raw else DEFAULT_MODULE_BUDGET_S
    except ValueError:
        return DEFAULT_MODULE_BUDGET_S


def pytest_runtest_logreport(report):
    mod = os.path.basename(report.nodeid.split("::", 1)[0])
    if mod.endswith(".py"):
        mod = mod[:-3]
    _module_spent[mod] = (
        _module_spent.get(mod, 0.0) + getattr(report, "duration", 0.0)
    )


def _budget_violations():
    default = _module_budget_default()
    if default is None:
        return []
    out = []
    for mod, spent in sorted(_module_spent.items()):
        budget = MODULE_BUDGET_OVERRIDES.get(mod, default)
        if spent > budget:
            out.append((mod, spent, budget))
    return out


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    violations = _budget_violations()
    if not violations:
        return
    terminalreporter.section("module wall-clock budget exceeded")
    for mod, spent, budget in violations:
        terminalreporter.line(
            f"{mod}: {spent:.1f}s > {budget:.0f}s budget — split the "
            "module, mark the culprits slow, or (if the cost is "
            "inherent) add a measured override in tests/conftest.py"
        )


def pytest_sessionfinish(session, exitstatus):
    if exitstatus == 0 and _budget_violations():
        session.exitstatus = 1
