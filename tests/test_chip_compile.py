"""Compile the main path for a described TPU v5e, without the chip.

The chip's compiler is installed in the sandbox and compiles for a
topology that is described, not attached. Kept here: what interpret
mode and the CPU mesh cannot see — the Pallas kernels at ``llama_1b``
widths, the one-chip step at the edge of 16 GB, and the four-chip
``fsdp`` step (a bare kernel call inside a GSPMD-partitioned jit does
not lower at all). Nothing runs; a compile that passes is not a chip
run.

Everything that loads the TPU library happens inside the module-scoped
fixture below, in this process, after a test of this file has started:
only one process may hold the library, and every xdist worker imports
every test file.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, SingleDeviceSharding

from dlrover_tpu.models import llama
from dlrover_tpu.ops import attention, tuning
from dlrover_tpu.ops.pallas import flash_attention as fa
from dlrover_tpu.trainer.sharded import make_trainer_for_llama

BATCH, SEQ = 3, 2048  # bench.py's one-chip size for llama_1b
#: the whole-step compiles ask the compiler for the least optimization:
#: a quarter of the CPU time (18 s, not 67 s, each), and what they
#: guard does not depend on it — whether the step lowers, and whether
#: it fits (at batch 3 the least-effort program needs 0.1 GB more
#: temporaries than the default one, and batch 4 is refused by both)
LEAST_EFFORT = {"exec_time_optimization_effort": -1.0}


@pytest.fixture(scope="module")
def topo(two_cores):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry written for a described chip cannot be read back
    # without one: keep these compiles out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def two_cores():
    """The chip's compiler takes every core it finds, and the timed
    drills that run beside this file under xdist (5 s heartbeat
    windows) then miss their deadlines. Its threads inherit the
    affinity of the thread that starts them."""
    jax.devices()  # the CPU backend's own threads start unpinned
    allowed = os.sched_getaffinity(0)
    os.sched_setaffinity(0, sorted(allowed)[-2:])
    yield
    os.sched_setaffinity(0, allowed)


@pytest.fixture
def on_tpu_path(monkeypatch):
    """Take the branches a TPU process takes: both ask
    ``jax.default_backend()``, which is the CPU here."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(attention, "_use_pallas", lambda q, k: True)
    # the tuner would now try to time kernels on a chip that is not
    # there; the heuristic pair is what an untuned chip run starts from
    monkeypatch.setattr(tuning, "_measurement_enabled", lambda: False)


def _abstract_step_args(trainer, batch, seq):
    tok = jax.ShapeDtypeStruct(
        (1, batch, seq), jnp.int32,
        sharding=trainer.microbatch_sharding,
    )
    return (*trainer.abstract_state(), (tok, tok))


def _kernel_args(one_chip):
    cfg = llama.llama_1b()
    q = jax.ShapeDtypeStruct(
        (BATCH, SEQ, cfg.num_heads, cfg.head_dim), jnp.bfloat16,
        sharding=one_chip,
    )
    kv = jax.ShapeDtypeStruct(
        (BATCH, SEQ, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16,
        sharding=one_chip,
    )
    return q, kv, kv


@pytest.mark.parametrize("pair", range(4))
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_kernel_compiles_at_llama_1b_shape(
    topo, on_tpu_path, pair, grad
):
    cfg = llama.llama_1b()
    grid = tuning.candidate_grid(
        SEQ, cfg.num_heads // cfg.num_kv_heads
    )
    assert len(grid) == 4, grid
    bq, bk = grid[pair]

    def attn(q, k, v):
        return fa.flash_attention_tpu(
            q, k, v, causal=True, block_q=bq, block_k=bk
        )

    fn = attn
    if grad:
        fn = jax.grad(
            lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )
    args = _kernel_args(SingleDeviceSharding(topo.devices[0]))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_llama_1b_step_fits_one_chip_at_batch_3(topo, on_tpu_path):
    """The memory edge: the compiler refuses what does not fit 16 GB
    (batch 4 is refused: "Used 16.64G of 15.75G hbm")."""
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    trainer = make_trainer_for_llama(
        llama.llama_1b(remat="dots_attn_out"), mesh, strategy="ddp",
        optimizer=optax.adamw(1e-4, b1=0.9, b2=0.95),
    )
    compiled = trainer.train_step.lower(
        *_abstract_step_args(trainer, BATCH, SEQ)
    ).compile(compiler_options=LEAST_EFFORT)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 6e9  # params + adam, bf16/f32


def test_fsdp_step_lowers_over_four_chips(topo, on_tpu_path):
    """Full widths, depth cut to two layers, the mesh
    examples/llama_train.py builds on a four-chip host."""
    mesh = Mesh(
        np.array(topo.devices).reshape(1, 4), ("data", "fsdp")
    )
    trainer = make_trainer_for_llama(
        dataclasses.replace(
            llama.llama_1b(remat="dots_attn_out"), num_layers=2
        ),
        mesh, strategy="fsdp", optimizer=optax.adamw(1e-4),
    )
    compiled = trainer.train_step.lower(
        *_abstract_step_args(trainer, 8, SEQ)
    ).compile(compiler_options=LEAST_EFFORT)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text and "reduce-scatter" in text
