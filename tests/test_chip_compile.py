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

import base64
import dataclasses
import hashlib
import json
import math
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, SingleDeviceSharding

from dlrover_tpu.models import llama
from dlrover_tpu.ops import attention, tuning
from dlrover_tpu.ops.pallas import grouped_sum
from dlrover_tpu.ops.pallas import flash_attention as fa
from dlrover_tpu.parallel import moe
from dlrover_tpu.trainer.sharded import make_trainer_for_llama

BATCH, SEQ = 3, 2048  # chip_smoke.py's one-chip size for llama_1b
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


def _abstract_step_args(trainer, batch, seq):
    tok = jax.ShapeDtypeStruct(
        (1, batch, seq), jnp.int32,
        sharding=trainer.microbatch_sharding,
    )
    return (*trainer.abstract_state(), (tok, tok))


# ---------------------------------------------------------------------------
# a census of the collectives in a compiled step's text

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2,
             "s32": 4, "u32": 4, "f32": 4, "s64": 8, "f64": 8}
_COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
                "collective-permute", "reduce-scatter")
#: instructions that move or cut a value and compute nothing: what an
#: all-gather's operand may pass through on its way from a parameter
_MOVES = ("copy", "copy-start", "copy-done", "bitcast", "reshape",
          "transpose", "slice", "slice-start", "slice-done",
          "dynamic-slice")


def _parse_hlo(text):
    """``{computation: {instruction: (result, op, operands, attrs)}}``,
    the instruction each fusion or loop body is called from, each
    computation's root, and the entry computation's name."""
    comps, called_from, roots, entry, cur = {}, {}, {}, None, None
    for line in text.splitlines():
        m = re.match(r"(ENTRY )?%([\w.\-]+) \(.*\) -> .*\{$", line)
        if m:
            cur = m.group(2)
            comps[cur] = {}
            entry = cur if m.group(1) else entry
            continue
        m = re.match(
            r"\s+(ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$", line
        )
        if not (m and cur):
            continue
        root, name, result, op, rest = m.groups()
        args, _, attrs = rest.partition(")")
        operands = re.findall(r"%([\w.\-]+)", args)
        if op == "parameter":
            operands = [args.strip()]  # its number
        comps[cur][name] = (result, op, operands, attrs)
        if root:
            roots[cur] = name
        for callee in re.findall(r"(?:calls|body)=%([\w.\-]+)", attrs):
            called_from[callee] = (cur, name)
    return comps, called_from, roots, entry


def _origin(hlo, comp, name):
    """``"parameter"`` if the value is an argument of the step (or a
    cut of one: a layer of a scan-stacked weight) that nothing has
    computed on yet, else ``"computed"``."""
    comps, called_from, roots, entry = hlo
    for _ in range(64):
        _, op, operands, attrs = comps[comp][name]
        if op == "parameter":
            if comp == entry:
                return "parameter"
            if comp not in called_from:
                return "computed"
            comp, site = called_from[comp]
            name = comps[comp][site][2][int(operands[0])]
        elif op == "get-tuple-element":
            index = int(re.search(r"index=(\d+)", attrs).group(1))
            source = comps[comp][operands[0]]
            if source[1] == "parameter" and comp in called_from:
                # an element of a loop body's carry that the body
                # hands on as it came (a weight): follow what the loop
                # was started with
                if comps[comp][roots[comp]][2][index] != name:
                    return "computed"
                comp, site = called_from[comp]
                init = comps[comp][comps[comp][site][2][0]]
                if init[1] != "tuple":
                    return "computed"
                name = init[2][index]
            elif source[1] in ("copy-start", "slice-start"):
                name = operands[0]
            else:
                return "computed"
        elif op in _MOVES or (
            op == "custom-call" and "ConcatBitcast" in attrs
        ):
            name = operands[0]
        elif op == "fusion":
            callee = re.search(r"calls=%([\w.\-]+)", attrs).group(1)
            if any(
                o not in _MOVES + ("parameter", "constant")
                for _, o, _, _ in comps[callee].values()
            ):
                return "computed"
            name = operands[0]  # a cut: the value is its first operand
        else:
            return "computed"
    return "computed"


def collective_census(text):
    """One entry for each collective of a compiled step's text:
    ``kind``, ``dtype``, ``shape`` and ``bytes`` of its (largest)
    result, ``in_loop``, ``origin`` of its operand (``_origin``) and
    ``scatter`` (inside an ``all-reduce-scatter`` fusion, which is how
    this compiler prints a reduce-scatter). An all-gather the compiler
    has split into the three stages of an asynchronous fusion counts
    once, at its start."""
    hlo = _parse_hlo(text)
    comps, called_from = hlo[:2]

    def in_loop(comp):
        while comp in called_from:
            comp, name = called_from[comp]
            if "body=" in comps[comp][name][3]:
                return True
        return False

    census = []
    for comp, instructions in comps.items():
        for name, (result, op, operands, _) in instructions.items():
            kind = op[:-6] if op.endswith("-start") else op
            if kind not in _COLLECTIVES:
                continue
            caller = called_from.get(comp, ("", ""))[1]
            if comp.startswith("async_collective_fusion") or (
                caller.startswith("async-collective-done")
            ):
                continue  # a later stage of a gather counted at its start
            sized = [
                (math.prod(int(d) for d in dims.split(",") if d)
                 * _ITEMSIZE[dtype], dtype, dims)
                for dtype, dims in re.findall(
                    r"\b([a-z]+\d+|pred)\[([\d,]*)\]", result
                )
            ]
            size, dtype, dims = max(sized)
            census.append({
                "kind": kind, "dtype": dtype, "bytes": size,
                "shape": tuple(int(d) for d in dims.split(",") if d),
                "in_loop": in_loop(comp),
                "origin": _origin(hlo, comp, operands[0]),
                "scatter": comp.startswith("all-reduce-scatter"),
            })
    return census


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


#: the rule's pair at a group of 8 and every narrower block_k
LLAMA_1B_BLOCKS = [(128, 1024), (128, 512), (128, 256), (128, 128)]


@pytest.mark.parametrize("pair", range(4))
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_kernel_compiles_at_llama_1b_shape(
    topo, on_tpu_path, pair, grad
):
    cfg = llama.llama_1b()
    assert LLAMA_1B_BLOCKS[0] == tuning.heuristic_blocks(
        SEQ, cfg.num_heads // cfg.num_kv_heads
    )
    bq, bk = LLAMA_1B_BLOCKS[pair]

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


#: [batch, seq, heads, kv_heads, head_dim] of the attention calls the
#: yardstick's cells make: gpt2-xl at 12 x 1024, OLMoE and Mistral at
#: 3 x 4096 (the fsdp4 cell runs Mistral's with one sequence a chip),
#: lfm2 at 4 x 8192, smallthinker at 1 x 16384
CELL_ATTENTION = {
    "gpt2-xl": (12, 1024, 25, 25, 64),
    "olmoe": (3, 4096, 16, 16, 128),
    "mistral": (3, 4096, 32, 8, 128),
    "lfm2": (4, 8192, 32, 8, 64),
    "smallthinker": (1, 16384, 28, 4, 128),
    # latent attention handed whole, as until PR 43 (v is 128 wide:
    # ``CELL_V_WIDTH``); the cell hands parts
    "joyai": (4, 8192, 32, 32, 192),
    # the looped stack's: 16 ungrouped heads of 128 at 8,192 whole
    "ouro": (1, 8192, 16, 16, 128),
}
CELL_V_WIDTH = {"joyai": 128}
#: the backward is one kernel at each: a head's float32 dQ stays in
#: VMEM without a group (in whole lanes 512 KB, 2 MB; 4 MiB of
#: ``RESIDENT_BYTES``' 16 at ``ouro``'s 8,192 x 128, 8 at ``joyai``'s
#: 8,192 x 192), a kv head's float32 dK and dV with one (4, 8 and 16
#: MB)
CELL_BACKWARD_FORM = {
    "gpt2-xl": "dq_resident", "olmoe": "dq_resident",
    "mistral": "dkv_resident", "lfm2": "dkv_resident",
    "smallthinker": "dkv_resident", "joyai": "dq_resident",
    "ouro": "dq_resident",
}
#: what the grouped backward call asks of a v5e core's 128 MiB of VMEM
#: (``jax/_src/pallas/mosaic/tpu_info.py``): the kv head's float32 dK
#: and dV and two buffers of each one's output block, 8, 16 and 32
#: MiB, and ``OTHER_VMEM_BYTES``, 28, for everything else
CELL_BACKWARD_VMEM = {"mistral": 36 * 2 ** 20, "lfm2": 44 * 2 ** 20,
                      "smallthinker": 60 * 2 ** 20,
                      # without a group, a head's float32 dQ at 8,192
                      # x 128 (4 MiB) and two buffers of its output
                      # block (2 MiB each) pass the default too
                      "ouro": 36 * 2 ** 20}


def _asked_of_vmem(text):
    """What each kernel call of a compiled step states of VMEM, in
    MiB, the default 16 (written out beside a call that asks) left
    out."""
    asked = [int(n) for n in re.findall(
        r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', text)]
    assert all(n % 2 ** 10 == 0 for n in asked)
    return [n / 2 ** 20 for n in asked if n != 16 * 2 ** 20]


def _sum_grad(attn):
    return jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )


#: forward alone only where the forward kernel is walked: the other two
#: shapes' forward is the whole-block body, which the forward-and-
#: backward compile holds as well
@pytest.mark.parametrize("cell,grad", [
    ("gpt2-xl", False), ("gpt2-xl", True), ("olmoe", True), ("mistral", True),
    ("lfm2", True), ("smallthinker", True), ("ouro", False), ("ouro", True),
], ids=lambda v: v if isinstance(v, str) else ("fwd", "fwd_bwd")[v])
def test_sub_tiled_kernels_compile_at_the_cells_shapes(
    topo, on_tpu_path, cell, grad
):
    """At the rule's blocks (what a cell runs). Without a group the
    block on the diagonal is walked in rows of sub-tiles: a switch over
    the widths a row can have, slices of the scratch rows and, in the
    backward kernels, of the lanes of the logsumexp."""
    batch, seq, heads, kv_heads, d = CELL_ATTENTION[cell]
    bq, bk = tuning.heuristic_blocks(seq, heads // kv_heads)
    group = heads // kv_heads
    # a group is not sub-tiled: its kernels are the whole-block ones
    assert (group > 1) != any(
        fa._sub_tiles(kernel, bq, bk, group, d)
        for kernel in ("fwd", "dq", "dkv", "dq_dkv")
    )

    def attn(q, k, v):
        return fa.flash_attention_tpu(
            q, k, v, causal=True, block_q=bq, block_k=bk
        )

    one_chip = SingleDeviceSharding(topo.devices[0])
    q, kv = (
        jax.ShapeDtypeStruct(
            (batch, seq, h, d), jnp.bfloat16, sharding=one_chip
        ) for h in (heads, kv_heads)
    )
    fn = _sum_grad(attn) if grad else attn
    text = jax.jit(fn).lower(q, kv, kv).compile().as_text()
    # the one backward kernel holds OLMoE's whole dQ (2 MB of float32
    # beside a [4096, 128] output block) under the default VMEM limit;
    # a kv head's dK and dV with their whole-head output blocks take
    # more, and the call says how much
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == (
        2 if grad else 1
    )
    assert _asked_of_vmem(text) == (
        [CELL_BACKWARD_VMEM[cell] / 2 ** 20]
        if grad and cell in CELL_BACKWARD_VMEM else [])


#: the windowed cells' attention: 28 and 32 query heads on 4 of 128
#: at 16,384 positions, windows of 4096 and 2048
WINDOWED_CELLS = {"smallthinker": (28, 4096), "trinity-mini": (32, 2048)}


@pytest.mark.parametrize("window", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("cell", WINDOWED_CELLS)
def test_windowed_kernels_compile_at_the_cells_shapes(
    topo, on_tpu_path, cell, window
):
    """One 16,384-token sequence, 28 or 32 query heads on 4 of 128:
    the rule's (128, 1024) blocks fold a group of 7 into 896 rows, no
    power of two, one of 8 into 1,024; the forward kernel and the one
    backward kernel (a kv head's float32 dK and dV, 16 MB, resident).
    With the window the grid counts the band's key blocks, and the
    backward kernel holds a body for every run of 512-wide column
    tiles of a block that an edge of the band crosses (the forward
    takes such a block whole: its step is bound by its rows); without
    it the kernels are the whole-block ones."""
    heads, width = WINDOWED_CELLS[cell]
    seq, kv_heads, d = 16384, 4, 128
    assert CELL_ATTENTION.get(cell, (1, seq, heads, kv_heads, d)) == (
        1, seq, heads, kv_heads, d)
    assert tuning.heuristic_blocks(seq, heads // kv_heads) == (128, 1024)

    def attn(q, k, v):
        return fa.flash_attention_tpu(
            q, k, v, causal=True, block_q=128, block_k=1024,
            window=width if window else None,
        )

    one_chip = SingleDeviceSharding(topo.devices[0])
    q, kv = (
        jax.ShapeDtypeStruct(
            (1, seq, h, d), jnp.bfloat16, sharding=one_chip
        ) for h in (heads, kv_heads)
    )
    fn = _sum_grad(attn)
    text = jax.jit(fn).lower(q, kv, kv).compile().as_text()
    assert len(
        re.findall(r'custom_call_target="tpu_custom_call"', text)) == 2
    # what the band's grid and the tiles show in the traced call: the
    # grid's minor dimension, and a body (a ``cond``) for the unmasked
    # block and for the masked one, in the backward for each of the
    # three runs of tiles, where the whole-block kernel has the one
    # test of whether a block is live
    grids, conds = [], []

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
                conds.append(sum(
                    e.primitive.name == "cond"
                    for e in eqn.params["jaxpr"].eqns))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                find(sub)

    find(jax.make_jaxpr(fn)(q, kv, kv).jaxpr)
    steps = {"smallthinker": 5, "trinity-mini": 3}[cell] if window else 16
    assert grids == [(4, 128, steps)] * 2, grids
    # beside the bodies: init and finalize, the backward's resident
    # dK and dV their own
    assert conds == ([2 + 1 + 1, 4 + 1 + 3] if window else [2 + 1, 4 + 1]), conds


@pytest.mark.parametrize("operands", ["parts", "whole"])
def test_latent_kernels_compile_at_joyais_shape(topo, on_tpu_path, operands):
    """``joyai-llm-flash-ep8.steady``'s attention: four sequences of
    8,192, 32 heads, q and k 192 wide (a lane and a half), v 128: the
    rule's (1024, 1024) blocks, the forward kernel and the one
    backward kernel with a head's float32 dQ resident (6 MiB, 8 in
    whole lanes), which states what it takes of VMEM, through the
    dispatch a TPU process takes. In the parts the cell hands over (q
    and k 128 wide, their rotated 64 columns apart, the key's one for
    every head) a grid step puts the 192-wide tiles together in VMEM
    and the gradients leave in the same parts; whole, as before."""
    def shaped(heads, d):
        return jax.ShapeDtypeStruct(
            (4, 8192, heads, d), jnp.bfloat16,
            sharding=SingleDeviceSharding(topo.devices[0]),
        )

    assert tuning.heuristic_blocks(8192, 1) == (1024, 1024)
    assert fa._one_backward_kernel(1, 8192, 192)
    if operands == "parts":
        compiled = jax.jit(jax.grad(
            lambda q, k, v, q_rope, k_rope: attention.flash_attention(
                q, k, v, q_rope=q_rope, k_rope=k_rope,
            ).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4),
        )).lower(shaped(32, 128), shaped(32, 128), shaped(32, 128),
                 shaped(32, 64), shaped(1, 64)).compile()
    else:
        compiled = jax.jit(_sum_grad(attention.flash_attention)).lower(
            shaped(32, 192), shaped(32, 192), shaped(32, 128)).compile()
    kernels = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", compiled.as_text())
    assert len(kernels) == 2
    assert _asked_of_vmem(compiled.as_text()) == [
        16 + fa.OTHER_VMEM_BYTES / 2 ** 20]
    # o and dv 128 wide; dq and dk 192, or each in parts of 128 and
    # 64 (the rotated key's a head's own, summed outside the kernel)
    assert sorted(re.findall(r"8192,(\d+)\]", " ".join(
        result for _, result in kernels))) == (
            ["128", "128", "128", "128", "64", "64"]
            if operands == "parts" else ["128", "128", "192", "192"])
    selection = tuning.last_selection()
    assert (selection["head_dim"], selection["v_head_dim"]) == (192, 128)
    assert selection.get("rope_head_dim") == (
        64 if operands == "parts" else None)


@pytest.mark.parametrize("cell,seq,selection", [
    ("nemotron", 8192, None), ("minicpm-sala", 16384, 64),
])
def test_a_group_of_16_forward_states_its_wider_key_block(
    topo, on_tpu_path, cell, seq, selection
):
    """Nemotron's and ``minicpm-sala``'s attention, 32 query heads on
    2 of 128 (the second with the selection's words), through the
    dispatch a TPU process takes: the pair is (128, 512), which the
    one backward kernel runs with its kv head's dK and dV resident;
    the forward takes 1,024 columns and states what its [2048, 1024]
    scores, ``p``, streamed blocks and state take, 22 MiB of a v5e
    core's 128, and the chip's compiler takes it."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [shaped(1, seq, 32, 128), shaped(1, seq, 2, 128),
            shaped(1, seq, 2, 128)]
    if selection:
        args.append(shaped(1, 2, seq, seq // selection, dtype=jnp.bool_))
    assert tuning.heuristic_blocks(seq, 16) == (128, 512)
    assert tuning.forward_key_block(
        seq, 16, (128, 512), selection_block=selection) == 1024
    stated = fa._fwd_vmem_bytes(16 * 128, 1024, 128, 128, 2)
    assert stated == 22 * 2 ** 20
    assert fa._fwd_vmem_bytes(16 * 128, 512, 128, 128, 2) is None
    compiled = jax.jit(jax.grad(
        lambda q, k, v, *selected: attention.flash_attention.__wrapped__(
            q, k, v, selected=selected[0] if selected else None,
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2),
    )).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"', text)) == 2
    # the forward's, then the backward's resident dK and dV
    assert _asked_of_vmem(text) == [
        22, fa._dkv_resident_vmem_bytes(seq, 128, 2) / 2 ** 20]
    assert max(_asked_of_vmem(text)) < 128
    selection = tuning.last_selection()
    assert (selection["block_q"], selection["block_k"],
            selection["fwd_block_k"], selection["backward"]) == (
                128, 512, 1024, "dkv_resident")


def test_kimis_backward_is_one_kernel_with_the_heads_dq_resident(
    topo, on_tpu_path
):
    """``kimi-linear-48b-a3b-ep16.steady``'s latent attention: 32
    heads at 16,384 positions, q and k in parts of 128 and 64 columns
    (the rotated key one for every head), v 128: a head's float32 dQ
    is 16 MiB in rows of two lanes, the one budget's edge, so the
    backward is ``_dqkv_kernel`` as ``joyai``'s is at 8,192, and the
    call states 60 MiB, what the grouped backward states at 16,384
    positions of 128."""
    def shaped(heads, d):
        return jax.ShapeDtypeStruct(
            (1, 16384, heads, d), jnp.bfloat16,
            sharding=SingleDeviceSharding(topo.devices[0]),
        )

    assert fa.backward_form(1, 16384, 192) == "dq_resident"
    lowered = jax.jit(jax.grad(
        lambda q, k, v, q_rope, k_rope: attention.flash_attention.__wrapped__(
            q, k, v, q_rope=q_rope, k_rope=k_rope,
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4),
    )).lower(shaped(32, 128), shaped(32, 128), shaped(32, 128),
             shaped(32, 64), shaped(1, 64))
    assert re.findall(r'kernel_name = "(\w+)"', lowered.as_text()) == [
        "_fwd_kernel", "_dqkv_kernel"]
    assert _asked_of_vmem(lowered.compile().as_text()) == [60]
    assert tuning.last_selection()["backward"] == "dq_resident"
    assert "fwd_block_k" not in tuning.last_selection()


#: sha256 (first 16 digits) of the forward and the backward kernel's
#: Mosaic module, without source locations, that a call with whole q,
#: k and v lowers to at each cell's shape: what commit 6e20e70 (PR 42)
#: lowers. A PR that changes what such a call runs reads the cells
#: again and then writes its own here (the test prints them)
WHOLE_KERNELS = {
    "gpt2-xl": ("fc758e9c4fb635a1", "163ae216a736d229"),
    "olmoe": ("1c7c62ea288cfef3", "19453fc863f47018"),
    "mistral": ("7f3bc98ec0816dc5", "fffbbf467e2e1ea4"),
    "lfm2": ("3b21acb8a143e85c", "761086b50c01458f"),
    "smallthinker": ("f2c890076821e395", "515ba026a486cd3e"),
    "joyai": ("ffb0b59e8f1d4260", "a29cfc45da995f91"),
    "ouro": ("e4726d9dbf8af8e7", "6be7937b54eaa797"),
}


def _lowered_kernels(fn, *args):
    """A lowering's text with each ``tpu_custom_call``'s payload taken
    out, and the payloads' Mosaic modules printed without source
    locations (those name the call stack, and so the caller)."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    payload = re.compile(r'backend_config = "(.*?)"(?=[,}])')
    text = jax.jit(fn).lower(*args).as_text()
    modules = []
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        for escaped in payload.findall(text):
            config = json.loads(re.sub(
                r"\\([0-9A-Fa-f]{2})",
                lambda m: chr(int(m.group(1), 16)), escaped,
            ))
            body = base64.b64decode(config["custom_call_config"]["body"])
            modules.append(ir.Module.parse(body).operation.get_asm(
                enable_debug_info=False
            ))
    return payload.sub("", text), modules


@pytest.mark.parametrize("variable", [None, "off"], ids=["unset", "off"])
@pytest.mark.parametrize("cell", list(CELL_ATTENTION))
def test_dispatch_lowers_the_rules_kernels_whatever_the_environment(
    topo, on_tpu_path, monkeypatch, cell, variable
):
    """What a process on the chip lowers for a cell's attention and
    its gradients is ``flash_attention_tpu`` at the rule's pair, with
    the variable every mix of ``yardstick/traffic/`` still sets and
    without it; nothing is timed on the way (a sweep ran on a thread
    of its own and ended in ``jax.clear_caches()``)."""
    batch, seq, heads, kv_heads, d = CELL_ATTENTION[cell]
    bq, bk = tuning.heuristic_blocks(seq, heads // kv_heads)
    if variable is None:
        monkeypatch.delenv("DLROVER_TPU_ATTN_TUNING", raising=False)
    else:
        monkeypatch.setenv("DLROVER_TPU_ATTN_TUNING", variable)
    # as a chip's process sees it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])
    q, k, v = (
        jax.ShapeDtypeStruct(
            (batch, seq, h, width), jnp.bfloat16, sharding=one_chip
        ) for h, width in ((heads, d), (kv_heads, d),
                           (kv_heads, CELL_V_WIDTH.get(cell, d)))
    )
    want = _lowered_kernels(_sum_grad(
        lambda q, k, v: fa.flash_attention_tpu(
            q, k, v, causal=True, block_q=bq, block_k=bk)
    ), q, k, v)
    # a Mosaic module for the forward and one for the backward kernel
    backward = {"dq_resident": "_dqkv_kernel",
                "dkv_resident": "_dq_dkv_kernel"}
    assert re.findall(r'kernel_name = "(\w+)"', want[0]) == [
        "_fwd_kernel", backward[CELL_BACKWARD_FORM[cell]]]
    assert len(want[1]) == 2, len(want[1])
    digests = tuple(
        hashlib.sha256(module.encode()).hexdigest()[:16]
        for module in want[1])
    assert digests == WHOLE_KERNELS[cell], digests

    def refuse(*args, **kwargs):
        raise AssertionError("a thread or jax.clear_caches()")

    monkeypatch.setattr(threading.Thread, "start", refuse)
    monkeypatch.setattr(jax, "clear_caches", refuse)
    # the undecorated function: inlined like the kernel's own wrapper,
    # and traced whatever this process has traced before
    got = _lowered_kernels(
        _sum_grad(attention.flash_attention.__wrapped__), q, k, v
    )
    assert got == want
    assert tuning.last_selection()["source"] == "static"


#: the same digests for the calls ``WHOLE_KERNELS`` does not make,
#: a window and q and k in parts, each at a cell's shape ([batch, seq,
#: heads, kv_heads, head_dim], then the window or the rotated part's
#: width): what commit 2ce30bb (PR 63) lowers, read from a copy of it
#: beside PR 64's tree, which hands every kernel one more operand
#: where a caller has a selection and only there. ``kimi.parts``: the
#: forward's as then; the backward since PR 66 one kernel, where the
#: dq and dk/dv pair was ("ee597869148835bc", "37307a7630892ff4")
UNSELECTED_KERNELS = {
    "smallthinker.window": (
        (1, 16384, 28, 4, 128), {"window": 4096},
        ("b44903697fa71161", "8c0685c0e24217c2")),
    "trinity-mini.window": (
        (1, 16384, 32, 4, 128), {"window": 2048},
        ("44e4aa29da87ae3c", "920bd8b32af8dc47")),
    # one backward kernel, a head's dQ resident
    "joyai.parts": (
        (4, 8192, 32, 32, 128), {"rope": 64},
        ("4f74afc876050aca", "c830cd5e967c2955")),
    # one backward kernel, a head's dQ resident, since PR 66
    "kimi.parts": (
        (1, 16384, 32, 32, 128), {"rope": 64},
        ("1fbf08f00fce77f2", "a8c707f7762425e0")),
}


@pytest.mark.parametrize("call", list(UNSELECTED_KERNELS))
def test_a_call_without_a_selection_lowers_the_kernels_it_did(
    topo, on_tpu_path, call
):
    """A windowed call and one with q and k in parts, ``selected``
    None: the forward's and the backward's Mosaic modules are the
    parent's, so the selection's operand costs the cells that have
    none nothing."""
    (batch, seq, heads, kv_heads, d), kind, want = UNSELECTED_KERNELS[call]
    bq, bk = tuning.heuristic_blocks(seq, heads // kv_heads)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shaped(h, width):
        return jax.ShapeDtypeStruct(
            (batch, seq, h, width), jnp.bfloat16, sharding=one_chip)

    args = [shaped(heads, d), shaped(kv_heads, d), shaped(kv_heads, d)]
    if "rope" in kind:
        args += [shaped(heads, kind["rope"]), shaped(1, kind["rope"])]

        def attn(q, k, v, q_rope, k_rope):
            return fa.flash_attention_tpu(
                q, k, v, causal=True, block_q=bq, block_k=bk,
                q_rope=q_rope, k_rope=k_rope,
                scale=(d + kind["rope"]) ** -0.5)
    else:
        def attn(q, k, v):
            return fa.flash_attention_tpu(
                q, k, v, causal=True, block_q=bq, block_k=bk,
                window=kind["window"])

    _, modules = _lowered_kernels(jax.grad(
        lambda *operands: attn(*operands).astype(jnp.float32).sum(),
        argnums=tuple(range(len(args)))), *args)
    digests = tuple(
        hashlib.sha256(module.encode()).hexdigest()[:16]
        for module in modules)
    assert digests == want, digests


def _count_equations(jaxpr):
    """Equations of a jaxpr and of every jaxpr inside it (loop bodies,
    branches)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    count = 0
    for eqn in jaxpr.eqns:
        count += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    count += _count_equations(sub)
    return count


#: the backward's kernels: the one the rule gives this shape, and the
#: dq and dk/dv pair that longer heads keep
BACKWARDS = pytest.mark.parametrize("backward", [1, 2], ids=["dqkv", "dq_dkv"])


def _one_head(causal, edge, backward, monkeypatch, sharding=None):
    """Attention and its gradients at gpt2-xl's shape, one (1024, 1024)
    block a head, the diagonal block walked in sub-tiles of ``edge``
    (None: whole) by every kernel, the backward in ``backward``
    kernels; and one head's argument."""
    monkeypatch.setattr(
        fa, "_sub_tiles", lambda kernel, bq, bk, g, d: fa._fits(edge, g, bq, bk)
    )
    monkeypatch.setattr(
        fa, "_one_backward_kernel", lambda g, seq, d: backward == 1
    )
    return _sum_grad(
        lambda q, k, v: fa.flash_attention_tpu(
            q, k, v, causal=causal, block_q=1024, block_k=1024)
    ), jax.ShapeDtypeStruct((1, 1024, 1, 64), jnp.bfloat16, sharding=sharding)


def _kernel_sizes(causal, edge, backward, monkeypatch):
    """Equation counts of the forward and the backward kernels'
    jaxprs."""
    fn, q = _one_head(causal, edge, backward, monkeypatch)
    sizes = _pallas_call_sizes(fn, q, q, q)
    assert len(sizes) == 1 + backward, sizes
    return sizes


def _pallas_call_sizes(fn, *args):
    """Equation counts of the kernels in ``fn``'s jaxpr, in order."""
    sizes = []

    def find(jaxpr):
        for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
            if eqn.primitive.name == "pallas_call":
                sizes.append(_count_equations(eqn.params["jaxpr"]))
                continue
            for value in eqn.params.values():
                if hasattr(getattr(value, "jaxpr", value), "eqns"):
                    find(value)

    find(jax.make_jaxpr(fn)(*args))
    return sizes


def _lowered_sizes(edge, backward, monkeypatch, chip):
    """Bytes of the causal kernels as a step's lowering for ``chip``
    holds them (a ``tpu_custom_call`` line each, the Mosaic module
    inside it)."""
    fn, q = _one_head(
        True, edge, backward, monkeypatch, SingleDeviceSharding(chip)
    )
    text = jax.jit(fn).lower(q, q, q).as_text()
    sizes = [len(line) for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(sizes) == 1 + backward, sizes
    return sizes


EDGES = (128, 256, 512, None)


@BACKWARDS
def test_kernel_jaxpr_grows_with_the_edge_not_the_sub_tiles(
    monkeypatch, backward
):
    """What is traced (a kernel's Python runs once a ``pallas_call``,
    four or five times a process, in every process's ``setup_s``): a
    kernel's jaxpr holds one body for each width a row of sub-tiles can
    have, one a column tile of the block, inside one loop over the
    rows. 64 sub-tiles of 128 add to the whole-block kernel twice what
    16 of 256 add, and those twice what 4 of 512 add; a body a sub-tile
    would add four times as much at each step. The one backward kernel
    holds the dk/dv kernel's bodies with a product more in each, not a
    second set."""
    at = {edge: _kernel_sizes(True, edge, backward, monkeypatch)
          for edge in EDGES}
    plain = _kernel_sizes(False, None, backward, monkeypatch)
    if backward == 1:
        pair = _kernel_sizes(True, 256, 2, monkeypatch)
        assert pair[2] < at[256][1] < pair[2] + 0.25 * pair[1], (at, pair)
    for k in range(1 + backward):
        a, b, c, whole = (at[edge][k] for edge in EDGES)
        assert plain[k] < whole < c < b < a, (at, plain)
        assert a - b == 2 * (b - c), at
        # the whole-block causal kernel is the non-causal body, the
        # mask and the test of whether the block is live
        assert whole < 1.5 * plain[k], (at, plain)
        assert a < 10 * plain[k], (at, plain)


def _grouped_kernel_sizes(g, window):
    """Equation counts of the forward and the backward kernels' jaxprs
    at a group's whole blocks, two (256, 1024) blocks across."""
    fn = _sum_grad(
        lambda q, k, v: fa.flash_attention_tpu(
            q, k, v, causal=True, block_q=256, block_k=1024, window=window)
    )
    q, kv = (jax.ShapeDtypeStruct((1, 2048, h, 128), jnp.bfloat16)
             for h in (g, 1))
    return _pallas_call_sizes(fn, q, kv, kv)


@pytest.mark.parametrize("window", [None, 512], ids=["full", "window"])
@pytest.mark.parametrize("g", [4, 7])
def test_grouped_backward_traces_the_dq_body_and_two_products(
    monkeypatch, g, window
):
    """What a group's one backward kernel adds to every process's
    set-up: the dq kernel's body with two products and two sums more
    in it (with a window, in each of its two bodies), not the dk/dv
    kernel's slices and scores a second time: a third fewer equations
    than the pair it stands in for (113 against 79 + 81 at Mistral's
    group, 229 against 184 + 184 at smallthinker's with the window)."""
    _, one = _grouped_kernel_sizes(g, window)
    monkeypatch.setattr(fa, "_one_backward_kernel", lambda g, seq, d: False)
    _, dq, dkv = _grouped_kernel_sizes(g, window)
    assert dq < one < dq + 0.5 * dkv, (one, dq, dkv)
    assert one < 0.75 * (dq + dkv), (one, dq, dkv)


@BACKWARDS
def test_lowered_kernel_holds_a_body_a_row(
    topo, on_tpu_path, monkeypatch, backward
):
    """What is lowered: the loop over rows is unrolled there and each
    row's switch has a constant index, so the Mosaic program grows by
    one body a row of sub-tiles (2, 4, 8 at 512, 256, 128), not by one
    a sub-tile (3, 10, 36), and not by every width in every row (4,
    16, 64)."""
    at = {edge: _lowered_sizes(edge, backward, monkeypatch, topo.devices[0])
          for edge in EDGES}
    for k in range(1 + backward):
        a, b, c, whole = (at[edge][k] for edge in EDGES)
        assert whole < c < b < a, at
        # rows double at each step: 2.0-2.2 read; a body a sub-tile
        # would read 3.7, every width in every row 4
        assert a - b < 2.6 * (b - c), at
        assert a < 3 * whole, at


def _not_here(*args, **kwargs):
    raise AssertionError("the in-place grouped sum, where nothing is summed")


@pytest.mark.parametrize("rows,experts,k,n", [
    (3 * 4096 * 8, 64, 2048, 1024), (3 * 4096 * 8, 64, 1024, 2048),
    (98304, 16, 2560, 768), (98304, 16, 768, 2560),
], ids=["olmoe_gate_up", "olmoe_down", "smallthinker_gate_up",
        "smallthinker_down"])
def test_grouped_matmul_kernels_compile_at_the_cells_shapes(
    topo, monkeypatch, rows, experts, k, n
):
    """The expert projections of OLMoE-1B-7B at 3 x 4096 tokens, top-8
    of 64, and of SmallThinker's 16 held experts in the 98,304 rows
    of 16,384 tokens x 6: the forward product and both backward ones are
    Pallas kernels at the tiles ``grouped_matmul.tiles`` gives each
    ((512, 1024, 1024) at OLMoE's; (512, 640, 768) and, for the rows'
    gradient, (512, 768, 640) at 2560 x 768; a tile larger in any
    dimension is refused for its VMEM)."""
    from dlrover_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_use_pallas", lambda lhs, rhs: True)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    # one product over all rows has no sum to add to: every group is
    # written once, by megablox's ``tgmm``, not by the in-place kernel
    monkeypatch.setattr(grouped_sum, "add_grouped_product", _not_here)
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = (
        jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((experts, k, n), jnp.bfloat16,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((experts,), jnp.int32, sharding=one_chip),
    )

    def loss(lhs, rhs, sizes):
        out = gm.grouped_matmul(lhs, rhs, sizes, filled=experts == 64)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text
    calls = re.findall(r"%([\w.]*gmm[\w.]*) = [^\n]*custom-call", text)
    # the rows' gradient (gmm against the transposed matrices) and
    # the matrices' (tgmm); the forward product is not needed for them
    assert len([c for c in calls if "tgmm" in c]) == 1, calls
    assert len([c for c in calls if "tgmm" not in c]) == 1, calls
    assert "ragged-dot" not in text


def _compiled_route(one_chip, tokens, experts, k, biased, **routing):
    """``route_logits`` and its gradient at a cell's shape, compiled
    for the described chip under the expert layer's scope: the
    weights against a cotangent plus ``aux``, as a layer's loss
    reaches them."""
    def reached(logits, bias, cot):
        with jax.named_scope("moe.route"):
            weights, chosen, aux = moe.route_logits(
                logits, k, True, bias=bias if biased else None, **routing)
        return jnp.sum(weights * cot) + aux, chosen

    shapes = [
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
        for shape in ((tokens, experts), (experts,), (tokens, k))
    ]
    return jax.jit(jax.value_and_grad(reached, has_aux=True)).lower(
        *shapes).compile().as_text()


def _dims(result):
    """The shapes in an instruction's result, as lists of ints."""
    return [[int(d) for d in dims.split(",") if d]
            for dims in re.findall(r"\w\[([\d,]*)\]", result)]


def _scalar_moves(text, tokens, k, scope=""):
    """What a compiled program holds of the instructions the chip
    runs an element at a time (PERF.md, PR 55: 10 ns an element):
    every ``gather``, ``scatter`` and packing of their indices, and
    every sort of the ``tokens x k`` assignments; with a ``scope``,
    those whose ``op_name`` holds it (in a whole step the transpose
    of a gather carries none)."""
    found = []
    for comp, instructions in _parse_hlo(text)[0].items():
        for name, (result, op, _, attrs) in instructions.items():
            if scope not in attrs:
                continue
            if op in ("gather", "scatter") or (
                op == "custom-call" and "GatherScatter" in attrs
            ) or (
                op == "sort" and [tokens * k] in _dims(result)
            ):
                found.append((comp, name, op, result))
    return found


def _routers_compare(text, traffic, cfg):
    """In a cell's whole step the routers read their k scores by
    comparison (PERF.md, PR 55): nothing under ``moe.route`` moves a
    scalar at a time."""
    assert _scalar_moves(
        text, traffic["global_batch"] * traffic["seq"], cfg.moe_top_k,
        scope="moe.route") == []


@pytest.mark.parametrize("tokens,experts,k", [
    (8192, 512, 22), (16384, 128, 8),
], ids=["nemotron", "trinity"])
def test_biased_router_compiles_to_no_gather(topo, tokens, experts, k):
    """A router with a selection bias at Nemotron's and
    ``trinity-mini``'s shapes, with its gradient: the k scores are
    read by a select and a sum over the experts, so the program holds
    no gather or scatter, no sort but ``top_k``'s over the experts,
    and nothing of shape [tokens, k, experts] between its fusions."""
    text = _compiled_route(
        SingleDeviceSharding(topo.devices[0]), tokens, experts, k, True,
        gate="sigmoid")
    assert _scalar_moves(text, tokens, k) == []
    comps, _, _, entry = _parse_hlo(text)
    sorts = [result for result, op, _, _ in comps[entry].values()
             if op == "sort"]
    assert len(sorts) == 1 and _dims(sorts[0])[0] == [tokens, experts]
    held = [name for name, (result, _, _, _) in comps[entry].items()
            if any(sorted(d) == sorted([tokens, k, experts])
                   for d in _dims(result))]
    assert held == []


#: sha256 (first 16 digits) of ``_compiled_route``'s instructions,
#: without their metadata, for a router that has no bias, at OLMoE's
#: and SmallThinker's shapes: what commit e8a7988 (PR 54) compiles.
#: A PR that changes what such a router runs reads those cells again
#: and then writes its own here (the test prints them)
ROUTE_WITHOUT_BIAS = {
    "olmoe": "53bcedc15f5242cb",
    "smallthinker": "2978ee9ab3f86c39",
}


@pytest.mark.parametrize("cell,tokens,experts,k", [
    ("olmoe", 3 * 4096, 64, 8), ("smallthinker", 16384, 64, 6),
])
def test_router_without_a_bias_compiles_as_it_did(
    topo, cell, tokens, experts, k
):
    """``top_k``'s own values: the branch a biased router's change
    leaves alone, so the cells that take it compile the program they
    did."""
    text = _compiled_route(
        SingleDeviceSharding(topo.devices[0]), tokens, experts, k, False)
    instructions = [
        line for line in re.sub(
            r", metadata=\{[^}]*\}", "", text).splitlines()
        if re.match(r"\s+(ROOT )?%|%|ENTRY ", line)
    ]
    digest = hashlib.sha256(
        "\n".join(instructions).encode()).hexdigest()[:16]
    print("route without a bias", cell, digest, len(instructions))
    assert digest == ROUTE_WITHOUT_BIAS[cell], digest


def test_olmoe_step_keeps_megabloxs_tgmm(topo, on_tpu_path, monkeypatch):
    """``olmoe-1b-7b-1chip.steady``'s step, traced and lowered for
    the chip (not compiled): with every expert held the layer is one
    pass, the matrices' gradients are megablox's ``tgmm`` writing
    every group once (no ``existing_out``), three a scanned layer,
    and the in-place kernel of a share's walk is not on its path; nor
    is a line of a delta-rule layer's convolutions (PERF.md, PR 51):
    the lowering loads no module of theirs and counts no call."""
    import importlib

    from dlrover_tpu.ops import grouped_matmul as gm
    from yardstick import cells, worker

    monkeypatch.delitem(sys.modules, KDA_CONV_KERNELS, raising=False)
    convs = _kda_conv_calls()

    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    whole, sums = megablox.tgmm, []

    def tgmm(*args, existing_out=None, **kwargs):
        sums.append(existing_out)
        return whole(*args, existing_out=existing_out, **kwargs)

    monkeypatch.setattr(megablox, "tgmm", tgmm)
    monkeypatch.setattr(grouped_sum, "add_grouped_product", _not_here)
    monkeypatch.setattr(gm, "_use_pallas", lambda lhs, rhs: True)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    _, config, traffic = cells.load_cell("olmoe-1b-7b-1chip.steady")
    cfg = worker.program_config(config, traffic)
    assert cfg.moe_experts_held in (0, cfg.num_experts)
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "fsdp"))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    text = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])).as_text()
    assert "tpu_custom_call" in text
    assert len(sums) == 3 and all(s is None for s in sums), sums
    assert KDA_CONV_KERNELS not in sys.modules
    assert _kda_conv_calls() == convs


#: the module that holds a delta-rule layer's convolutions' kernels,
#: which only the branch that takes them imports
#: (``ops/kda_conv.py conv_silu_norm``)
KDA_CONV_KERNELS = "dlrover_tpu.ops.pallas.kda_conv"


def _kda_conv_calls():
    from dlrover_tpu.telemetry.registry import counter

    return [counter(f"kda_conv_{path}_calls", "").value
            for path in ("kernel", "plain")]


#: a fresh process, as a cell's worker is: the tiny configuration's
#: step built and lowered through the trainer, then what was loaded
#: and counted
_LOWER_A_TINY_STEP = """
import json, os, sys
import jax, optax
from dlrover_tpu.parallel.mesh import create_mesh
from dlrover_tpu.telemetry.registry import counter
from dlrover_tpu.trainer.sharded import make_trainer_for_llama
from yardstick import cells, worker
name, traffic = sys.argv[1], json.loads(sys.argv[2])
with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
    cfg = worker.program_config(json.load(f), traffic)
trainer = make_trainer_for_llama(
    cfg, create_mesh([("data", 1), ("fsdp", 1)], devices=jax.devices()[:1]),
    optimizer=optax.adamw(1e-3))
state = trainer.init(jax.random.key(0))
tokens = worker.SeededTokens(3, traffic["seq"], cfg.vocab_size)(0, 2)
text = trainer.train_step.lower(*state, trainer.microbatch(tokens)).as_text()
print(json.dumps({
    "linear": "linear_attention" in (cfg.layer_types or ()),
    "loaded": sorted(m for m in sys.modules if m.endswith("kda_conv")),
    "calls": [counter(f"kda_conv_{path}_calls", "").value
              for path in ("kernel", "plain")],
    "text": len(text)}))
"""


@pytest.mark.parametrize("name,linear", [
    ("tiny-olmoe", False), ("tiny-llama", False), ("tiny-lfm2", False),
    ("tiny-solar", True),
])
def test_only_a_delta_rule_layer_reaches_its_convolutions(name, linear):
    """A configuration without a linear-attention layer builds and
    lowers its training step, in a process of its own, without
    loading the convolutions' kernels' module and without moving
    either of the entry's counters: no cell but ``solar``'s runs a
    line of what PR 51 adds beside ``ops/kda_conv.py``'s import, which
    is three plain functions (``lfm2``'s gated convolution is another
    module's). ``tiny-solar`` is the control: it counts its calls, on
    the plain path off the TPU, and still loads no kernel."""
    from .test_moe_bias_rule import TRAFFIC

    out = subprocess.run(
        [sys.executable, "-c", _LOWER_A_TINY_STEP, name,
         json.dumps(TRAFFIC)],
        capture_output=True, text=True, timeout=600, check=False,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    said = json.loads(out.stdout.splitlines()[-1])
    assert said["linear"] is linear and said["text"] > 0
    assert said["loaded"] == ["dlrover_tpu.ops.kda_conv"]
    assert (said["calls"][1] > 0) is linear and said["calls"][0] == 0


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


#: ``peak_memory_in_bytes`` of ``smallthinker-21b-a3b-ep4.steady``'s
#: step as this file compiles it: at the commit before a share's walk
#: (6d61769), all ``tokens x k`` rows in one pass (PERF.md, PR 34),
#: and with the walk (PR 35). Of both 6.7 GB are the state, and the
#: peak is the head's: 4.7 GB of logits live at once in a heap that
#: is half fragmentation. The walk's buffers are a chunk's rows (the
#: layer alone plans 1.15 GB where the one pass planned 2.17), but
#: its backward loop carries the three matrices' gradient sums of a
#: layer in float32, 0.38 GB that live through the loop, and the
#: step plans 4.6% more than it did. Since PR 37 the walk's backward
#: gathers the cotangent's rows once both products of the tokens' rows
#: are made (``parallel/moe.py _walk_bwd``), and the step plans
#: 16,244,227,584 whichever form the attention backward takes; left
#: the choice, the chip's scheduler put that gather between the two
#: products in the last layer it walks once the attention backward
#: was one call (its tie is broken by instruction names, and the
#: walk's five grouped matmuls were then ``tpu_custom_call.98`` to
#: ``.102``), and planned 16,298,763,776. Since the head's cross
#: entropy keeps its logits once, in bfloat16 (PR 63,
#: ``models/llama.py _head_nll``), the step plans 15,213,382,656:
#: 1,030,838,272 under the 16,244,220,928 it planned the commit
#: before, and the ceiling came down by 1,043,438,080; the compiler's
#: ``.remat`` twins of the head's fusions (fourteen names with
#: ``fusion.1734.remat``) are no longer in the step's text
SMALLTHINKER_STEP_BYTES = {"one pass": 15_542_064_128,
                           "walk": 15_213_382_656}


def test_smallthinker_step_walks_its_share_in_chunks(
    topo, on_tpu_path, monkeypatch
):
    """``smallthinker-21b-a3b-ep4.steady``'s step (1 x 16,384, 8
    layers, 16 of 64 experts held, remat ``minimal``): it fits and
    plans no more than was read when the walk was built (ISSUE 35
    asked for no more than the one pass's: not met, see above); no
    operation of the expert layer has a row of the hidden or the
    experts' width for each of the 98,304 assignments (their keys and
    weights, a number each, are sorted whole); and the kernels that
    add a chunk's rows to their tokens are not named as the experts'
    matmuls are, which the benchmark's ``moe_expert_ms`` tells by
    name (a compiled step's instruction names are a device
    trace's)."""
    from dlrover_tpu.ops import grouped_matmul as gm
    from yardstick import cells, worker
    from yardstick.layer_metrics.moe_expert_ms import KERNEL

    monkeypatch.setattr(gm, "_use_pallas", lambda lhs, rhs: True)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_add_on_mxu", lambda out, rows: True)
    _, config, traffic = cells.load_cell(
        "smallthinker-21b-a3b-ep4.steady")
    cfg = worker.program_config(config, traffic)
    assert (cfg.moe_experts_held, cfg.num_experts) == (16, 64)
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "fsdp"))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    compiled = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])).compile()
    planned = compiled.memory_analysis().peak_memory_in_bytes
    print("smallthinker step plans", planned)
    assert planned <= SMALLTHINKER_STEP_BYTES["walk"]
    rows = traffic["seq"] * cfg.moe_top_k
    wide = re.compile(
        rf"= \w+\[(?:{rows}|{traffic['seq']},{cfg.moe_top_k}),\d{{3,}}\]")
    text = compiled.as_text()
    assert not [
        line[:200] for line in text.splitlines()
        if "moe." in line and wide.search(line)
    ]
    # Pallas calls by name and result: of [rows of a chunk, width] and
    # [experts held, ., .] the experts' matmuls; of [blocks of 512
    # tokens, 512, hidden] the rows' sums into their tokens
    kernels = re.findall(
        r"%([\w.\-]+) = (\w+\[[\d,]+\])[^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", text)
    chunk, held = (moe.walk_chunks(rows, cfg.hidden_size)[0],
                   cfg.moe_experts_held)
    matmuls = [name for name, result in kernels
               if re.match(rf"\w+\[({chunk}|{held}),", result)]
    sums = [name for name, result in kernels if result.startswith(
        f"f32[{traffic['seq'] // gm.ROW_BLOCK},{gm.ROW_BLOCK},")]
    assert len(matmuls) >= 9 and len(sums) >= 2
    assert all(KERNEL.search(name) for name in matmuls), matmuls
    assert not any(KERNEL.search(name) for name in sums), sums
    _in_place_sums_keep_their_names(text, held, sums)


def _in_place_sums_keep_their_names(text, held, token_sums):
    """The calls of ``ops/pallas/grouped_sum.py`` in a compiled step
    (a Pallas call whose result is an operand's buffer): into
    ``f32[held, ., .]`` the experts' gradient sums, named ``tgmm.<n>``
    after the jitted function that holds them, three a walked layer,
    which ``moe_expert_ms`` counts; into the tokens' blocks
    ``_rows_by_place.<n>``, which it does not."""
    from yardstick.layer_metrics.moe_expert_ms import KERNEL

    in_place = re.findall(
        r"%([\w.\-]+) = (\w+\[[\d,]+\])[^\n]*"
        r"custom_call_target=\"tpu_custom_call\"[^\n]*"
        r"output_to_operand_aliasing", text)
    gradient_sums = [name for name, result in in_place
                     if result.startswith(f"f32[{held},")]
    assert len(gradient_sums) >= 3 and len(gradient_sums) % 3 == 0
    assert all(re.fullmatch(r"tgmm(\.\d+)?", name) and KERNEL.search(name)
               for name in gradient_sums), gradient_sums
    assert token_sums and set(token_sums) <= {n for n, _ in in_place}
    assert all(re.fullmatch(r"_rows_by_place(\.\d+)?", name)
               for name in token_sums), token_sums


#: ``peak_memory_in_bytes`` of ``lfm2-8b-a1b-ep4.steady``'s step as
#: this file compiles it (4 x 8,192, thirteen layers, remat
#: ``minimal``, the loss unchunked; PERF.md, PR 36): 8.0 GB of it the
#: state. At nine layers it plans 11.64 GB, which is the room the
#: third period took; ``loss_chunk`` 2048 is refused at thirteen.
#: The ceiling came down by 8,669,184 in PR 63 to what the step plans
#: with the head's own backward rule and planned the commit before it
#: alike: the step's peak is not the head's
LFM2_STEP_BYTES = 15_459_463_680


def test_lfm2_step_holds_the_convolutions_kernels(
    topo, on_tpu_path, monkeypatch
):
    """``lfm2-8b-a1b-ep4.steady``'s step: it fits and plans no more
    than was read when the cell was built; a conv layer's two Pallas
    calls (the forward, twice under ``minimal``, and the backward
    with the taps' gradient) are named as the benchmark's
    ``short_conv_ms`` tells them, and as neither the attention's nor
    the experts' readers do (a compiled step's instruction names are
    a device trace's); the in-place float32 sums of the 2048 x 1792
    experts' gradients take the tiles that fit (1024 x 896 of them
    are refused); and every op of the convolution carries a
    ``conv.*`` scope."""
    from dlrover_tpu.ops import grouped_matmul as gm, short_conv
    from dlrover_tpu.ops.pallas import short_conv as conv_kernels
    from yardstick import cells, worker
    from yardstick.layer_metrics import (
        attn_kernel_ms, moe_expert_ms, short_conv_ms,
    )

    monkeypatch.setattr(gm, "_use_pallas", lambda lhs, rhs: True)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_add_on_mxu", lambda out, rows: True)
    monkeypatch.setattr(short_conv, "_use_pallas", lambda bcu, w: True)
    monkeypatch.setattr(conv_kernels, "_interpret", lambda: False)
    _, config, traffic = cells.load_cell("lfm2-8b-a1b-ep4.steady")
    cfg = worker.program_config(config, traffic)
    assert (cfg.remat, cfg.loss_chunk) == ("minimal", 0)
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "fsdp"))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    compiled = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])).compile()
    planned = compiled.memory_analysis().peak_memory_in_bytes
    print("lfm2 step plans", planned)
    assert planned <= LFM2_STEP_BYTES
    text = compiled.as_text()
    _routers_compare(text, traffic, cfg)
    kernels = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"[^\n]*"
        r"op_name=\"([^\"]*)\"", text)
    conv = [(name, op) for name, _, op in kernels
            if short_conv_ms.KERNEL.search(name)]
    # three conv positions of the period and the leading layer, each
    # the forward, the forward again and the backward
    assert len(conv) == 4 * 3, [name for name, _ in conv]
    assert all("conv.mix" in op for _, op in conv)
    others = [name for name, _, op in kernels if "conv.mix" not in op]
    assert others and not any(
        short_conv_ms.KERNEL.search(name) for name in others)
    assert not any(
        attn_kernel_ms.KERNEL.search(name)
        or moe_expert_ms.KERNEL.search(name) for name, _ in conv)
    # a period's attention layer: the forward, the forward again and
    # the one backward kernel
    assert sum(bool(attn_kernel_ms.KERNEL.search(n)) for n in others) == 3
    assert sum(bool(moe_expert_ms.KERNEL.search(n)) for n in others) >= 9
    _in_place_sums_keep_their_names(
        text, cfg.moe_experts_held,
        [name for name, result, _ in kernels if result.startswith(
            f"f32[{traffic['global_batch'] * traffic['seq'] // gm.ROW_BLOCK}"
            f",{gm.ROW_BLOCK},")])
    # [batch, seq, 3 x hidden] and [batch, seq, hidden] of the
    # convolution: whatever computes on them says whose op it is
    wide = re.compile(r"= \w+\[4,8192,6144\]")
    unscoped = [
        line[:160] for line in text.splitlines()
        if wide.search(line) and "op_name=" in line
        and "conv." not in line and "fusion(" in line
    ]
    assert not unscoped


#: ``peak_memory_in_bytes`` of ``joyai-llm-flash-ep8.steady``'s step as
#: this file compiles it (4 x 8,192, six layers and the module, remat
#: ``minimal``, the least effort; PERF.md, PR 43): 7.4 GB of it the
#: state. The step with q and k built whole outside the kernels (PR
#: 42) read 15,958,852,096 here: by this statistic the parts plan 55
#: MB more (at the default effort 46), by the buffer assignment's
#: total 119 MB less, and on the chip the same (PERF.md section 6).
#: 16,013,520,896 until the routers read their k scores by comparison
#: (PR 55): a quarter of a megabyte more at this effort, 16,013,783,040.
#: Down by 1,592,082,432 since the two heads keep their logits once,
#: in bfloat16, and nothing float32 of [32768, 16160] (PR 63,
#: ``models/llama.py _head_nll``)
JOYAI_STEP_BYTES = 14_421_700_608


def test_joyai_step_holds_no_whole_q_or_k(topo, on_tpu_path, monkeypatch):
    """``joyai-llm-flash-ep8.steady``'s step: it fits and plans no
    more than was read when the kernels took the parts; no
    instruction's result, in a fusion or out of one, is a head's
    whole 192-wide q or k in the model's or the kernels' order (the
    parts reach the kernels as their products make them: a compiled
    step's instruction names and shapes are a device trace's); the
    three blocks' kernels (the forward, the forward again under
    ``minimal``, the one backward kernel) are named as the
    benchmark's ``attn_kernel_ms`` tells them and carry
    ``attn.latent``; and the record says which form ran."""
    from dlrover_tpu.ops import grouped_matmul as gm
    from yardstick import cells, worker
    from yardstick.layer_metrics import attn_kernel_ms

    monkeypatch.setattr(gm, "_use_pallas", lambda lhs, rhs: True)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_add_on_mxu", lambda out, rows: True)
    _, config, traffic = cells.load_cell("joyai-llm-flash-ep8.steady")
    cfg = worker.program_config(config, traffic)
    assert (cfg.remat, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim) == (
        "minimal", 128, 64)
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "fsdp"))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    compiled = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])
    ).compile(LEAST_EFFORT)
    planned = compiled.memory_analysis().peak_memory_in_bytes
    print("joyai step plans", planned)
    assert planned <= JOYAI_STEP_BYTES
    text = compiled.as_text()
    _routers_compare(text, traffic, cfg)
    whole = re.findall(
        r"bf16\[(?:4,8192,32|4,32,8192|128,1,8192|128,8192),192\]", text)
    assert not whole, len(whole)
    assert "bf16[4,8192,32,128]" in text and "bf16[4,8192,1,64]" in text
    kernels = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"[^\n]*"
        r"op_name=\"([^\"]*)\"", text)
    latent = [name for name, _, op in kernels if "attn.latent" in op]
    # the leading layer, the scanned layers' body and the module's
    # block, each the forward, the forward again and the backward
    assert len(latent) == 3 * 3, latent
    assert all(attn_kernel_ms.KERNEL.search(name) for name in latent)
    assert not any(
        attn_kernel_ms.KERNEL.search(name)
        for name, _, op in kernels if "attn.latent" not in op)
    # a backward kernel's results: dq and dk in their parts, dv
    backward = [result for name, result, _ in kernels
                if name in latent and result.count("bf16[") == 5]
    assert len(backward) == 3
    assert all(sorted(re.findall(r"8192,(\d+)\]", result)) == [
        "128", "128", "128", "64", "64"] for result in backward)
    assert tuning.last_selection()["rope_head_dim"] == 64


#: ``peak_memory_in_bytes`` of ``solar-open2-250b-ep32.steady``'s step
#: as this file compiles it (1 x 8,192, four layers, remat ``minimal``,
#: the least effort; PERF.md, PR 51): 8.5 GB of it the state. With the
#: delta-rule layers' heads an axis of their own between the
#: projections and the kernels (PR 44) it read 16,240,236,032: the
#: norms' factors at full width and the relayouts' copies; in rows with
#: the convolutions as plain float32 ops (PR 45) 15,315,505,664.
#: 14,462,113,280 until the scan's forward kept a layer's inverses and
#: ``w`` for its backward (PR 61): 268 MB each, one layer's at a time,
#: 14,998,671,872. UP by 113,664 bytes with the head's own backward
#: rule (PR 63), at this effort and at the default alike, and the one
#: ceiling that rose: the parent's live peak was the head's (two
#: float32 [8192, 24576], 805 MB each, in the buffer assignment of
#: the compiler's dump), the rule's is a delta-rule layer's backward
#: (``delta_rule.15``'s results and three ``tgmm``s), and the heap,
#: 6,478,791,168 and 6,478,922,240 bytes of temporaries around live
#: peaks of 2.7 and 4.8 GB, is packed 128 KiB apart. 14,998,785,536
#: until the heads' norm and gate became Pallas calls (PR 67), and UP
#: by 202,244,096: the plain passes' result was never an array (XLA
#: made it inside the operand fusions of ``W_o``'s three products, a
#: ``kOutput`` fusion that read the scan's ``o`` and the gate's
#: logits), and the kernels' ``y`` is one, 134 MB of bf16 a layer from
#: its forward-again to ``W_o``'s weight gradient; at this effort and
#: at the default alike, 1.7 GB under the compiler's 16.91
SOLAR_STEP_BYTES = 15_201_029_632
#: what a delta-rule layer's q, k, v, g or o is as rows, as heads, and
#: as the tiles of rows that the compiler names ``[s / 8, 8, heads, d]``
SOLAR_ROWS = re.compile(
    r"\[(?:1,8192,8192|8192,8192|1,8192,64,128|8192,64,128"
    r"|1024,8,64,128)\]")


def _outside_fusions(text, ops):
    """``(op, result, operands' results, op_name)`` of every
    instruction of one of ``ops`` that is a device op of its own: in
    no fusion's computation."""
    comps, called_from, _, _ = _parse_hlo(text)
    fused = {
        callee for callee, (comp, site) in called_from.items()
        if comps[comp][site][1] == "fusion"
    }
    for comp, instructions in comps.items():
        if comp in fused:
            continue
        for result, op, operands, attrs in instructions.values():
            if op in ops:
                name = re.search(r'op_name="([^"]*)"', attrs)
                yield (op, result, [
                    instructions[o][0] for o in operands
                    if o in instructions
                ], name.group(1) if name else "")


def _shapes_of_the_keeping_calls(kernels, named):
    """The results' shapes of each custom call ``named`` whose results
    are one bfloat16 array and float32 ones behind it: the scan's
    forward that keeps what its backward reads (the plain forward has
    ``o`` alone, the backward three bfloat16 gradients)."""
    results = [re.findall(r"(\w+)(\[[\d,]+\])", result)
               for name, result, _ in kernels if named.search(name)]
    return [
        [dtype + shape for dtype, shape in shapes] for shapes in results
        if [dtype for dtype, _ in shapes] == ["bf16"] + ["f32"] * (
            len(shapes) - 1) and len(shapes) > 1
    ]


def _heads_norms_calls(kernels, text, scope, layers, readers):
    """The heads' norm and gate of ``layers`` linear-attention layers
    under ``scope``: each the forward, the forward again under
    ``minimal`` and the backward, Pallas calls by the jitted name
    ``gated_norm``, which no reader's pattern matches, and none of the
    scope's instructions a ``.remat`` twin of another (a second copy of
    a fusion that the compiler makes to rematerialize it: PERF.md, PR
    63)."""
    calls = [name for name, *_, op in kernels if scope in op]
    assert len(calls) == 3 * layers, calls
    assert all(name.startswith("gated_norm") for name in calls)
    assert not any(
        reader.KERNEL.search(name) for name in calls for reader in readers)
    twins = [
        line.split(" = ")[0].strip() for line in text.splitlines()
        if scope in line and ".remat" in line.split(" = ")[0]
    ]
    assert not twins, twins


def test_solar_step_holds_the_delta_rules_kernels(
    topo, on_tpu_path, monkeypatch
):
    """``solar-open2-250b-ep32.steady``'s step: it fits and plans no
    more than was read when the cell was built; a linear-attention
    layer's Pallas calls (the forward, the forward again under
    ``minimal``, and the backward over the entry states it kept) are
    named as the benchmark's ``delta_rule_ms`` tells them, and as
    neither the attention's, the experts' nor the convolution's
    readers do (a compiled step's instruction names are a device
    trace's), and carry ``kda.scan``; the convolutions, ``silu`` and
    l2 norms of q, k and v are twenty-seven Pallas calls under
    ``kda.conv`` by a jitted name of their own, ``kda_conv``, which no
    reader's pattern matches, every call of the entry took the kernels,
    and no fusion on a ``[1, 8192, 8192]`` array is left under that
    scope; the 4096 x 1280 experts' products
    and in-place float32 sums take the tiles the rule gives them;
    every fusion on a ``[1, 8192, 8192]`` array says whose op it is;
    and a delta-rule layer stays in rows from its projections to
    ``W_o``: no reshape, copy, transpose or broadcast of a ``[1, 8192,
    8192]`` array, in any of its shapes, is a device op of its own
    under a ``kda.`` scope, no float32 one under no scope (the copies
    a trace shows without an ``op_name``), and the nine calls were
    handed rows and built with the most heads a grid step that the
    kernels' rule has, which divides the cell's 64; and the heads'
    norm and gate between the scan and ``W_o`` are nine Pallas calls
    under ``kda.out`` by the jitted name ``gated_norm`` (PR 67), every
    call of their entry on the kernels' path."""
    from dlrover_tpu.ops import (
        delta_rule, gated_norm, grouped_matmul as gm, kda_conv,
    )
    from dlrover_tpu.telemetry.registry import counter, gauge
    from dlrover_tpu.ops.pallas import delta_rule as scan_kernels
    from dlrover_tpu.ops.pallas import gated_norm as norm_kernels
    from dlrover_tpu.ops.pallas import kda_conv as conv_kernels
    from yardstick import cells, worker
    from yardstick.layer_metrics import (
        attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms,
    )

    monkeypatch.setattr(gm, "_use_pallas", lambda lhs, rhs: True)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_add_on_mxu", lambda out, rows: True)
    monkeypatch.setattr(delta_rule, "_use_pallas", lambda q, heads: True)
    monkeypatch.setattr(scan_kernels, "_interpret", lambda: False)
    monkeypatch.setattr(
        kda_conv, "_use_pallas", lambda x, w, l2_heads: (
            conv_kernels.tiles_the_kernel(x.shape, w.shape, l2_heads)))
    monkeypatch.setattr(conv_kernels, "_interpret", lambda: False)
    monkeypatch.setattr(
        gated_norm, "_use_pallas", lambda o, groups: (
            norm_kernels.tiles_the_kernel(o.shape, groups)))
    monkeypatch.setattr(norm_kernels, "_interpret", lambda: False)
    calls = [counter(
        f"delta_rule_{handed}_calls", "", scan_kernels.CALL_LABELS,
    ).labels(decay="channel", head="128x128")
        for handed in ("rows", "folded")]
    calls += [counter(f"{entry}_{path}_calls", "")
              for entry in ("kda_conv", "head_norm_gate")
              for path in ("kernel", "plain")]
    before = [c.value for c in calls]
    gauge("delta_rule_heads_per_step", "").set(0)
    _, config, traffic = cells.load_cell("solar-open2-250b-ep32.steady")
    cfg = worker.program_config(config, traffic)
    assert (cfg.remat, cfg.loss_chunk) == ("minimal", 0)
    assert gm.tiles(5120, 4096, 1280) == (512, 1024, 640)
    assert gm.tiles(5120, 1280, 4096) == (512, 640, 1024)
    assert gm.tiles(5120, 4096, 1280, most=gm.IN_PLACE_TILE) == (
        512, 512, 640)
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "fsdp"))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    compiled = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])
    ).compile(LEAST_EFFORT)
    planned = compiled.memory_analysis().peak_memory_in_bytes
    print("solar step plans", planned)
    assert planned <= SOLAR_STEP_BYTES < 15.75 * 2 ** 30
    text = compiled.as_text()
    _routers_compare(text, traffic, cfg)
    kernels = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"[^\n]*"
        r"op_name=\"([^\"]*)\"", text)
    scan = [(name, op) for name, _, op in kernels
            if delta_rule_ms.KERNEL.search(name)]
    # three linear positions of the period, each the forward, the
    # forward again and the backward
    assert len(scan) == 3 * 3, [name for name, _ in scan]
    # either forward keeps, beside ``o``, the chunks' entry states
    # [batch, heads, chunks, 128, 128], the pairs' inverses and ``w``
    assert _shapes_of_the_keeping_calls(kernels, delta_rule_ms.KERNEL) == [[
        "bf16[1,8192,8192]", "f32[1,64,128,128,128]",
        "f32[1,32,128,128,128]", "f32[1,8192,8192]"]] * 6
    assert all("kda.scan" in op for _, op in scan)
    others = [name for name, _, op in kernels if "kda.scan" not in op]
    assert others and not any(
        delta_rule_ms.KERNEL.search(name) for name in others)
    assert not any(
        attn_kernel_ms.KERNEL.search(name)
        or moe_expert_ms.KERNEL.search(name)
        or short_conv_ms.KERNEL.search(name) for name, _ in scan)
    # the period's attention layer: the forward, the forward again and
    # the one backward kernel (a group of 8: a kv head's dK and dV
    # resident)
    assert sum(bool(attn_kernel_ms.KERNEL.search(n)) for n in others) == 3
    assert fa._one_backward_kernel(8, 8192, 128)
    assert sum(bool(moe_expert_ms.KERNEL.search(n)) for n in others) >= 9
    assert not any(short_conv_ms.KERNEL.search(n) for n in others)
    # q, k and v at three linear positions of the period, each the
    # forward, the forward again and the backward: under ``kda.conv``,
    # by the jitted name no reader goes by
    conv = [name for name, _, op in kernels if "kda.conv" in op]
    assert len(conv) == 3 * 3 * 3, conv
    assert all(name.startswith("kda_conv") for name in conv)
    assert not any(
        reader.KERNEL.search(name) for name in conv for reader in (
            attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms))
    wide = re.compile(r"= \w+\[1,8192,8192\]")
    unscoped = [
        line[:160] for line in text.splitlines()
        if wide.search(line) and "op_name=" in line and "fusion(" in line
        and "kda." not in line and "attn." not in line
    ]
    assert not unscoped
    # nothing is left at full width under ``kda.conv`` beside the kernels
    # (36 fusions when the convolutions were plain ops; the eighteen still
    # there turn the taps and their gradients, ``f32[4, 8192]``)
    left = [
        line.split(" = ")[0].strip() for line in text.splitlines()
        if SOLAR_ROWS.search(line.split("fusion(")[0])
        and "fusion(" in line and "kda.conv" in line
    ]
    assert not left, left
    _heads_norms_calls(kernels, text, "kda.out", 3, (
        attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms))
    # nine scans on rows and none folded; nine calls of the
    # convolutions' entry (q, k, v a linear position), none plain;
    # three of the heads' norm and gate, none plain
    assert [c.value - was for c, was in zip(calls, before)] == [
        9, 0, 9, 0, 3, 0]
    together = gauge("delta_rule_heads_per_step", "").value
    assert together == max(scan_kernels.HEADS_A_STEP) > 1
    assert gauge(
        "delta_rule_state_bytes", "").value == together * 128 * 128 * 4
    assert gauge("delta_rule_backward_inverses", "").value == 0
    # a head's chunk: its state, half a pair's inverse, its w
    assert gauge("delta_rule_kept_bytes", "").value == 4 * (
        128 * 128 + 64 * 128 + 64 * 128)
    moved = [
        (op, result[:40], name[-60:])
        for op, result, operands, name in _outside_fusions(
            text, ("reshape", "copy", "transpose", "broadcast"))
        if any(SOLAR_ROWS.search(r) for r in [result] + operands)
        and ("kda." in name or (not name and result.startswith("f32")))
    ]
    assert not moved, moved
    assert tuning.last_selection()["gqa_group"] == 8


#: ``peak_memory_in_bytes`` of ``kimi-linear-48b-a3b-ep16.steady``'s
#: step as this file compiles it (1 x 16,384, five layers, remat
#: ``minimal``, the least effort; PERF.md, PR 60): 4.97 GB of it the
#: state. At the default effort it read 9,291,786,240, and with 32 of
#: the 256 experts held 12,337,465,344 (the file's ``depth``).
#: 9,407,342,592 until the scan's forward kept a layer's inverses and
#: ``w`` for its backward (PR 61): 268 MB each a layer, 215 MB of it
#: over what the step's peak held beside them, 9,622,743,040; 1,024
#: bytes less with the head's own backward rule (PR 63): the peak is
#: not the head's, 9,622,742,016. UP by 100,597,760 with the heads'
#: norm and gate as Pallas calls (PR 67): their result an array of its
#: own, as ``SOLAR_STEP_BYTES`` says
KIMI_STEP_BYTES = 9_723_339_776


def test_kimi_step_holds_the_one_backward_and_both_operators_kernels(
    topo, on_tpu_path, monkeypatch
):
    """``kimi-linear-48b-a3b-ep16.steady``'s step under
    ``steady-1x16384``: it fits under 15.75 GiB and plans no more than
    was read when the cell was built; the one latent layer's attention
    reaches the kernels in parts (no instruction's result is a head's
    whole 192-wide q or k) and, a head's float32 dQ at 16,384
    positions of 192 columns being the 16 MiB of ``RESIDENT_BYTES``
    in rows of two lanes, its backward is the one kernel that
    ``joyai``'s is (the dq and dk/dv pair until PR 66): three kernels
    named as ``attn_kernel_ms`` tells them under ``attn.latent``, and
    the gauges say 2 parts and a head's dQ resident; the four
    delta-rule layers' scans (the leading layer's outside the loop,
    three in the period's body) are named as ``delta_rule_ms`` tells
    them under ``kda.scan``, 32 heads in grid groups of the most the
    rule has, the backward over ``[1, 32, 256, 128, 128]`` entry
    states; their convolutions are ``kda_conv`` calls under
    ``kda.conv``; q's one matrix stands under ``mla.q`` and nothing
    under ``mla.q_down``; the 2304 x 1024 experts take the tiles the
    rule gives them and a share's walk its chunk; the heads' norm and
    gate of the four delta-rule layers are twelve ``gated_norm`` calls
    under ``kda.out`` (PR 67), every call of their entry on the
    kernels' path."""
    from dlrover_tpu.ops import (
        delta_rule, gated_norm, grouped_matmul as gm, kda_conv,
    )
    from dlrover_tpu.telemetry.registry import counter, gauge
    from dlrover_tpu.ops.pallas import delta_rule as scan_kernels
    from dlrover_tpu.ops.pallas import gated_norm as norm_kernels
    from dlrover_tpu.ops.pallas import kda_conv as conv_kernels
    from yardstick import cells, worker
    from yardstick.layer_metrics import (
        attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms,
    )

    monkeypatch.setattr(gm, "_use_pallas", lambda lhs, rhs: True)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_add_on_mxu", lambda out, rows: True)
    monkeypatch.setattr(delta_rule, "_use_pallas", lambda q, heads: True)
    monkeypatch.setattr(scan_kernels, "_interpret", lambda: False)
    monkeypatch.setattr(
        kda_conv, "_use_pallas", lambda x, w, l2_heads: (
            conv_kernels.tiles_the_kernel(x.shape, w.shape, l2_heads)))
    monkeypatch.setattr(conv_kernels, "_interpret", lambda: False)
    monkeypatch.setattr(
        gated_norm, "_use_pallas", lambda o, groups: (
            norm_kernels.tiles_the_kernel(o.shape, groups)))
    monkeypatch.setattr(norm_kernels, "_interpret", lambda: False)
    calls = [counter(
        f"delta_rule_{handed}_calls", "", scan_kernels.CALL_LABELS,
    ).labels(decay="channel", head="128x128")
        for handed in ("rows", "folded")]
    calls += [counter(f"{entry}_{path}_calls", "")
              for entry in ("kda_conv", "head_norm_gate")
              for path in ("kernel", "plain")]
    before = [c.value for c in calls]
    gauge("delta_rule_heads_per_step", "").set(0)
    for kernel in ("fwd", "dqkv"):
        gauge("attn_operand_parts", "", ("kernel",)).labels(
            kernel=kernel).set(0)
    gauge("attn_backward_kernels", "", ("form",)).labels(
        form="dq_resident").set(0)
    _, config, traffic = cells.load_cell("kimi-linear-48b-a3b-ep16.steady")
    assert (traffic["global_batch"], traffic["seq"]) == (1, 16384)
    cfg = worker.program_config(config, traffic)
    assert (cfg.remat, cfg.loss_chunk, cfg.q_lora_rank) == (
        "minimal", 0, None)
    # 2304 = 18 x 128: the largest 128-multiple divisor within a tile
    assert gm.tiles(8192, 2304, 1024) == (512, 768, 1024)
    assert gm.tiles(8192, 1024, 2304) == (512, 1024, 768)
    assert gm.tiles(8192, 2304, 1024, most=gm.IN_PLACE_TILE) == (
        512, 768, 512)
    # a chunk of the walk from 2304-wide rows: 8,192 x 2560 / 2304 in
    # whole tiles of 512; even routing's 8,192 held rows are one chunk
    assert moe.walk_chunks(16384 * 8, 2304) == (8704, 16)
    # 16 MiB in rows of two lanes: on the one budget's limit
    assert fa._one_backward_kernel(1, 16384, 192)
    assert not fa._one_backward_kernel(1, 32768, 192)
    assert fa._one_backward_kernel(1, 8192, 192)  # joyai's: 8 MiB
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "fsdp"))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    layers = gauge("dlrover_model_operator_layers", "", ("operator",))
    assert [layers.labels(operator=o).value for o in (
        "linear_attention", "latent_attention", "full_attention")] == [
            4, 1, 0]
    compiled = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])
    ).compile(LEAST_EFFORT)
    planned = compiled.memory_analysis().peak_memory_in_bytes
    print("kimi step plans", planned)
    assert planned <= KIMI_STEP_BYTES < 15.75 * 2 ** 30
    text = compiled.as_text()
    _routers_compare(text, traffic, cfg)
    whole = re.findall(
        r"bf16\[(?:1,16384,32|1,32,16384|32,1,16384|32,16384),192\]", text)
    assert not whole, len(whole)
    # the parts as the kernels take them, a head's 128 and 64 columns
    assert "bf16[32,1,16384,128]" in text and "bf16[32,1,16384,64]" in text
    kernels = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"[^\n]*"
        r"op_name=\"([^\"]*)\"", text)
    latent = [(name, result) for name, result, op in kernels
              if "attn.latent" in op]
    # the forward, the forward again under ``minimal``, then the one
    # backward kernel, as at joyai's 8,192 positions
    assert len(latent) == 3, latent
    assert all(attn_kernel_ms.KERNEL.search(name) for name, _ in latent)
    assert not any(
        attn_kernel_ms.KERNEL.search(name)
        for name, _, op in kernels if "attn.latent" not in op)
    # dq in its two parts, dk in its two and dv, of one kernel
    widths = sorted(
        sorted(re.findall(r"16384,(\d+)\]", result))
        for _, result in latent if "f32[" not in result)
    assert widths == [["128", "128", "128", "64", "64"]], widths
    parts = gauge("attn_operand_parts", "", ("kernel",))
    assert [parts.labels(kernel=k).value for k in ("fwd", "dqkv")] == [2, 2]
    assert gauge("attn_backward_kernels", "", ("form",)).labels(
        form="dq_resident").value == 1
    # which states the VMEM the grouped backward states at 16,384
    assert 60 in _asked_of_vmem(text)
    assert tuning.last_selection()["backward"] == "dq_resident"
    assert tuning.last_selection()["rope_head_dim"] == 64
    assert tuning.last_selection()["seq"] == 16384
    scan = [(name, op) for name, _, op in kernels
            if delta_rule_ms.KERNEL.search(name)]
    # the leading layer and three positions of the period, each the
    # forward, the forward again and the backward
    assert len(scan) == 4 * 3, [name for name, _ in scan]
    assert all("kda.scan" in op for _, op in scan)
    assert sum("while" not in op for _, op in scan) == 3  # the lead's
    # the chunks' entry states, the pairs' inverses and ``w``
    assert _shapes_of_the_keeping_calls(kernels, delta_rule_ms.KERNEL) == [[
        "bf16[1,16384,4096]", "f32[1,32,256,128,128]",
        "f32[1,16,256,128,128]", "f32[1,16384,4096]"]] * 8
    others = [name for name, _, op in kernels if "kda.scan" not in op]
    assert not any(delta_rule_ms.KERNEL.search(name) for name in others)
    assert not any(short_conv_ms.KERNEL.search(name) for name in others)
    assert sum(bool(moe_expert_ms.KERNEL.search(n)) for n in others) >= 9
    conv = [name for name, _, op in kernels if "kda.conv" in op]
    assert len(conv) == 4 * 3 * 3, conv
    assert all(name.startswith("kda_conv") for name in conv)
    assert not any(
        reader.KERNEL.search(name) for name in conv for reader in (
            attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms))
    _heads_norms_calls(kernels, text, "kda.out", 4, (
        attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms))
    # twelve scans on rows and none folded; twelve calls of the
    # convolutions' entry (q, k, v a delta-rule layer), none plain;
    # four of the heads' norm and gate, none plain
    assert [c.value - was for c, was in zip(calls, before)] == [
        12, 0, 12, 0, 4, 0]
    together = gauge("delta_rule_heads_per_step", "").value
    assert together == max(scan_kernels.HEADS_A_STEP) and 32 % together == 0
    assert gauge("delta_rule_backward_inverses", "").value == 0
    assert gauge("delta_rule_kept_bytes", "").value == 4 * (
        128 * 128 + 64 * 128 + 64 * 128)
    # q's product is named, and no q latent's is
    assert "mla.q/" in text and "mla.q_down" not in text
    assert "mla.kv_down" in text and "mla.up" in text


#: ``peak_memory_in_bytes`` of ``nemotron-3-super-120b-a12b-ep64
#: .steady``'s step as this file compiles it (1 x 8,192, eleven layers
#: and the module, remat ``minimal``, the least effort; PERF.md, PR
#: 54): 8.27 GB of it the state. At the default effort it read
#: 11,458,404,352. PR 54 read 11,462,598,656 here; the figure below is
#: what the tree plans since PR 56, with a mixer's gate and norm as
#: plain passes and as the kernels alike (PR 57): the step's peak is
#: not in a mixer, 11,461,876,736. It was the heads': down by
#: 473,532,416 since the model's and the module's keep their logits
#: once, in bfloat16 (PR 63, ``models/llama.py _head_nll``)
NEMOTRON_STEP_BYTES = 10_988_344_320


def test_ssd_kernels_compile_at_the_cells_shape(topo, monkeypatch):
    """The state-space scan's forward and backward kernels at the
    cell's shape (one sequence of 8,192, 128 heads of 64 in 8 groups of
    128 states): a grid step a group's sixteen heads, 1,024 lanes, and
    the entry states kept for the backward pass."""
    from dlrover_tpu.ops import ssd
    from dlrover_tpu.ops.pallas import ssd as kernels

    monkeypatch.setattr(
        ssd, "_use_pallas", lambda x, B, heads, groups: True)
    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (
        of((1, 8192, 8192), jnp.bfloat16), of((1, 8192, 1024), jnp.bfloat16),
        of((1, 8192, 1024), jnp.bfloat16), of((1, 8192, 128), jnp.float32),
        of((128,), jnp.float32), of((128,), jnp.float32),
    )

    def loss(*operands):
        return ssd.ssd_scan(*operands, 128, 8).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "f32[1,8,64,128,1024]" in text  # the chunks' entry states


#: the gate-and-norm frame's callers: ``[batch, seq, width]``, the
#: groups (a mixer's) or heads, the body, and whether the gate has a
#: bias
GATED_NORM_CELLS = {
    "nemotron": ((1, 8192, 8192), 8, "gate, norm", False),
    "solar": ((1, 8192, 8192), 64, "norm, gate", True),
    "kimi": ((1, 16384, 4096), 32, "norm, gate", True),
    "minicpm-sala": ((1, 16384, 4096), 32, "norm, gate", False),
}
#: the mixer's forward and backward Mosaic modules as commit 7cf0d04
#: (PR 66) lowers them, from the file that held that body alone: the
#: frame the two bodies share since PR 67 hands it the same text
NEMOTRON_GATED_NORM = ("0a8b711df9b7df4e", "adca66b2998e6cbe")


@pytest.mark.parametrize("cell", list(GATED_NORM_CELLS))
def test_gated_norm_kernels_compile_at_the_cells_shape(
    topo, monkeypatch, cell
):
    """A mixer's gate and grouped norm at Nemotron's shape (one
    sequence of 8,192, 8 groups of 1,024 columns) and the heads' norm
    and gate at the three linear-attention cells' (64 heads of 128 at
    8,192 positions; 32 at 16,384, with the gate's bias and without):
    a forward and a backward Pallas call, the cotangent read in bf16
    and the vectors' gradients summed in float32, the one scale's over
    the heads too. The mixer's two modules are the parent's."""
    from dlrover_tpu.ops.pallas import gated_norm as kernels

    shape, groups, body, biased = GATED_NORM_CELLS[cell]
    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    assert kernels.tiles_the_kernel(shape, groups)
    one_chip = SingleDeviceSharding(topo.devices[0])
    width = shape[-1]
    rows = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    vectors = tuple(
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
        for n in ((width,) if body == "gate, norm"
                  else (width // groups, width)[:1 + biased]))

    def gradients(o, z, vectors, dy):
        y, back = jax.vjp(
            lambda *a: kernels.gated_norm_tpu(*a, body, groups, 1e-5),
            o, z, vectors)
        return (y, *back(dy))

    compiled = jax.jit(gradients).lower(rows, rows, vectors, rows).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert [(o.dtype, o.shape) for o in jax.tree.leaves(
        compiled.out_info)] == 3 * [(jnp.bfloat16, shape)] + [
            (jnp.float32, v.shape) for v in vectors]
    # nothing of the operator's is left to XLA at full width
    assert not re.search(r"= f32\[%d,%d,%d\]" % shape, text)
    if cell == "nemotron":
        _, modules = _lowered_kernels(gradients, rows, rows, vectors, rows)
        assert tuple(
            hashlib.sha256(module.encode()).hexdigest()[:16]
            for module in modules) == NEMOTRON_GATED_NORM


def test_nemotron_step_holds_the_scans_kernels(topo, on_tpu_path, monkeypatch):
    """``nemotron-3-super-120b-a12b-ep64.steady``'s step: it fits and
    plans no more than was read when the cell was built; a mixer's
    Pallas calls (the forward, the forward again under ``minimal``,
    and the backward over the entry states it kept) are named as the
    benchmark's ``ssd_ms`` tells them, and as no other reader does,
    and carry ``ssm.scan``; the convolution with its bias is fifteen
    Pallas calls under ``ssm.conv`` by the jitted name ``kda_conv``,
    and the gate with the grouped norm fifteen under ``ssm.gate_norm``
    by the jitted name ``gated_norm``, which no reader's pattern
    matches, so nothing of them is left to XLA: no norm's factor
    written out at a group's width, no float32 cotangent from
    ``ssm_out``'s input-gradient product (PERF.md, PR 57);
    every call of the three entries took the kernels; the two attention
    layers (the stack's and the module's) run the one backward kernel
    at a group of 16; and the expert layers' 1024 x 2688 products take
    the tiles the rule gives them."""
    from dlrover_tpu.ops import gated_norm, grouped_matmul as gm, kda_conv, ssd
    from dlrover_tpu.ops.pallas import gated_norm as norm_kernels
    from dlrover_tpu.ops.pallas import kda_conv as conv_kernels
    from dlrover_tpu.ops.pallas import ssd as scan_kernels
    from dlrover_tpu.telemetry.registry import counter, gauge
    from yardstick import cells, worker
    from yardstick.layer_metrics import (
        attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms, ssd_ms,
    )

    monkeypatch.setattr(gm, "_use_pallas", lambda lhs, rhs: True)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_add_on_mxu", lambda out, rows: True)
    monkeypatch.setattr(
        ssd, "_use_pallas", lambda x, B, heads, groups: (
            scan_kernels.tiles_the_kernel(x.shape, B.shape, heads, groups)))
    monkeypatch.setattr(scan_kernels, "_interpret", lambda: False)
    monkeypatch.setattr(
        kda_conv, "_use_pallas", lambda x, w, l2_heads: (
            conv_kernels.tiles_the_kernel(x.shape, w.shape, l2_heads)))
    monkeypatch.setattr(conv_kernels, "_interpret", lambda: False)
    monkeypatch.setattr(
        gated_norm, "_use_pallas", lambda o, groups: (
            norm_kernels.tiles_the_kernel(o.shape, groups)))
    monkeypatch.setattr(norm_kernels, "_interpret", lambda: False)
    calls = [counter(f"{entry}_{path}_calls", "")
             for entry in ("ssd", "kda_conv", "gated_norm")
             for path in ("kernel", "plain")]
    before = [c.value for c in calls]
    gauge("ssd_heads_per_step", "").set(0)
    _, config, traffic = cells.load_cell(
        "nemotron-3-super-120b-a12b-ep64.steady")
    cfg = worker.program_config(config, traffic)
    assert (cfg.remat, cfg.loss_chunk) == ("minimal", 0)
    assert gm.tiles(20480, 1024, 2688) == (512, 1024, 896)
    assert gm.tiles(20480, 2688, 1024) == (512, 896, 1024)
    assert moe.walk_chunks(8192 * 22, 1024) == (20480, 9)
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "fsdp"))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    compiled = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])
    ).compile(LEAST_EFFORT)
    planned = compiled.memory_analysis().peak_memory_in_bytes
    print("nemotron step plans", planned)
    assert planned <= NEMOTRON_STEP_BYTES < 15.75 * 2 ** 30
    text = compiled.as_text()
    _routers_compare(text, traffic, cfg)
    kernels = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"[^\n]*"
        r"op_name=\"([^\"]*)\"", text)
    scan = [(name, op) for name, _, op in kernels
            if ssd_ms.KERNEL.search(name)]
    # five mixers, each the forward, the forward again and the backward
    assert len(scan) == 5 * 3, [name for name, _ in scan]
    assert all("ssm.scan" in op for _, op in scan)
    others = [name for name, _, op in kernels if "ssm.scan" not in op]
    assert others and not any(ssd_ms.KERNEL.search(n) for n in others)
    assert not any(
        reader.KERNEL.search(name) for name, _ in scan for reader in (
            attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms))
    # the chunks' entry states: [batch, groups, chunks, 128, 16 x 64]
    assert "f32[1,8,64,128,1024]" in text
    # beside the scan, a mixer's convolution and its gate with the
    # grouped norm: each the forward, the forward again and the
    # backward, by a jitted name that no reader goes by
    for scope, jitted in (("ssm.conv", "kda_conv"),
                          ("ssm.gate_norm", "gated_norm")):
        beside = [name for name, _, op in kernels if scope in op]
        assert len(beside) == 5 * 3, beside
        assert all(name.startswith(jitted) for name in beside)
        assert not any(
            reader.KERNEL.search(name) for name in beside for reader in (
                attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms,
                ssd_ms))
    # what the plain passes wrote out is gone: the norm's factor at a
    # group's width (two a layer, 268 MB each) and a float32 result of
    # ``ssm_out``'s input-gradient product (268 MB where bf16 is 134)
    assert not re.search(r"= f32\[1024,8,8,1024\]\S* broadcast\(", text)
    assert not re.search(r"= f32\[8192,8192\]", text)
    # the stack's attention layer and the module's: the forward, the
    # forward again and the one backward kernel each (a group of 16: a
    # kv head's dK and dV resident)
    assert sum(bool(attn_kernel_ms.KERNEL.search(n)) for n in others) == 6
    assert fa._one_backward_kernel(16, 8192, 128)
    assert tuning.last_selection()["gqa_group"] == 16
    # the pair is the backward's; the forward states its own key block
    assert (tuning.last_selection()["block_k"],
            tuning.last_selection()["fwd_block_k"]) == (512, 1024)
    assert sorted(set(_asked_of_vmem(text))) == [22, 44]
    # six expert layers' walks: grouped products, none of them read as
    # another operator's
    assert sum(bool(moe_expert_ms.KERNEL.search(n)) for n in others) >= 18
    # every mixer's call of each of the three entries took the kernels
    # (a call a layer: the remat's second forward reuses its trace)
    assert [c.value - was for c, was in zip(calls, before)] == 3 * [5, 0]
    assert gauge("ssd_heads_per_step", "").value == 16
    assert gauge("ssd_state_bytes", "").value == 16 * 64 * 128 * 4
    for scope in ("ssm.in_proj", "ssm.dt", "ssm.gate_norm", "ssm.out_proj",
                  "moe.latent_down", "moe.latent_up", "moe.shared",
                  "mtp.block", "attn.full"):
        assert scope in text, scope


#: ``peak_memory_in_bytes`` of ``minicpm-sala-9b-vp8.steady``'s step as
#: this file compiles it (1 x 16,384, four layers, an eighth of the
#: vocabulary, remat ``minimal``, the least effort; PERF.md, PR 64):
#: 7.11 GB of it the state. 12,682,685,440 at the default effort,
#: which the chip compiles at (the file's ``depth``); 12,989,689,856
#: while the selection's ``top_k`` was the compiler's sort;
#: 12,686,945,280 until the lightning layers' heads' norm and gate
#: became Pallas calls (PR 67): 404,585,472 less, where ``solar``'s and
#: ``kimi``'s plans rose by the kernels' result
SALA_STEP_BYTES = 12_282_359_808


def test_sala_step_holds_the_selections_and_the_scans_kernels(
    topo, on_tpu_path, monkeypatch
):
    """``minicpm-sala-9b-vp8.steady``'s step: it fits under 15.75 GiB
    and plans no more than was read when the cell was built; the one
    selected-attention layer's kernels (the forward, the forward again
    under ``minimal`` and the one backward kernel, a kv head's dK and
    dV resident at a group of 16) are named as ``attn_kernel_ms``
    tells them and carry ``sparse.attn``, with the selection's words
    among their operands, each kernel's from its own key block
    (``s32[2, 16, 1, 16384]`` the forwards', ``s32[2, 32, 1, 16384]``
    the backward's); the three lightning
    layers' scans (each the forward, the forward again and the
    backward over ``[1, 32, 128, 128, 128]`` entry states) are named
    as ``ssd_ms`` tells them, and as no other reader does, and carry
    ``lightning.scan``; the heads' norm and gate behind each scan are
    nine ``gated_norm`` calls under ``lightning.out`` (PR 67), which no
    reader's pattern matches; every call of the three entries took the
    kernels; no array of the compressed scores is whole in the step
    (``[32 or 16 heads, 16384, 1023]``), nor the sixteen chunks'
    masks; and every scope of the two operators and the three factors
    is in the text."""
    from dlrover_tpu.ops import gated_norm, ssd
    from dlrover_tpu.ops.pallas import gated_norm as norm_kernels
    from dlrover_tpu.ops.pallas import ssd as scan_kernels
    from dlrover_tpu.telemetry.registry import counter, gauge
    from yardstick import cells, worker
    from yardstick.layer_metrics import (
        attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms, ssd_ms,
    )

    monkeypatch.setattr(
        ssd, "_use_pallas", lambda x, B, heads, groups: (
            scan_kernels.tiles_the_kernel(x.shape, B.shape, heads, groups)))
    monkeypatch.setattr(scan_kernels, "_interpret", lambda: False)
    monkeypatch.setattr(
        gated_norm, "_use_pallas", lambda o, groups: (
            norm_kernels.tiles_the_kernel(o.shape, groups)))
    monkeypatch.setattr(norm_kernels, "_interpret", lambda: False)
    calls = [counter(f"{entry}_calls", "") for entry in (
        "sparse_attention_kernel", "sparse_attention_plain",
        "ssd_kernel", "ssd_plain",
        "head_norm_gate_kernel", "head_norm_gate_plain")]
    before = [c.value for c in calls]
    _, config, traffic = cells.load_cell("minicpm-sala-9b-vp8.steady")
    cfg = worker.program_config(config, traffic)
    assert (cfg.remat, cfg.loss_chunk) == ("minimal", 0)
    # the backward's pair; the forward's key block is as wide as the
    # chip reads fastest, half of what a selection's word could hold
    assert tuning.heuristic_blocks(16384, 16) == (128, 512)
    assert tuning.forward_key_block(
        16384, 16, (128, 512), selection_block=64) == 1024
    assert fa._one_backward_kernel(16, 16384, 128)
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "fsdp"))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    compiled = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])
    ).compile(LEAST_EFFORT)
    planned = compiled.memory_analysis().peak_memory_in_bytes
    print("sala step plans", planned)
    assert planned <= SALA_STEP_BYTES < 15.75 * 2 ** 30
    text = compiled.as_text()
    kernels = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\("
        r"([^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*)"
        r"op_name=\"([^\"]*)\"", text)
    attend = [(name, operands, op) for name, _, operands, op in kernels
              if attn_kernel_ms.KERNEL.search(name)]
    assert len(attend) == 3, [name for name, _, _ in attend]
    assert all("sparse.attn" in op for _, _, op in attend)
    # each kernel makes its words from its own key block: the two
    # forwards' 16 blocks of 1,024 keys, the backward's 32 of 512
    assert sorted(
        re.search(r"s32\[2,(\d+),1,16384\]", operands).group(1)
        for _, operands, _ in attend) == ["16", "16", "32"]
    assert sorted(set(_asked_of_vmem(text))) == [22, 60]
    scan = [(name, op) for name, _, _, op in kernels
            if ssd_ms.KERNEL.search(name)]
    assert len(scan) == 3 * 3, [name for name, _ in scan]
    assert all("lightning.scan" in op for _, op in scan)
    all_readers = (
        attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms, ssd_ms)
    _heads_norms_calls(kernels, text, "lightning.out", 3, all_readers)
    assert len(kernels) == 12 + 9  # and no other kernel
    for name, _, _, op in kernels:
        readers = [r for r in all_readers if r.KERNEL.search(name)]
        assert len(readers) == ("lightning.out" not in op), name
    # the chunks' entry states: [batch, heads, chunks, 128, 128]
    assert "f32[1,32,128,128,128]" in text
    # nothing of the compressed scores whole, in any layout
    assert not re.search(r"\[(\d+,)*(32|16),16384,1023\]", text)
    assert not re.search(r"\[(\d+,)*16384,1023(,\d+)*\]", text)
    assert not re.search(r"\[16,1,2,16,1024,1023\]", text)
    assert re.search(r"f32\[1,2,16,1024,\d+\]", text)  # a chunk's are
    assert [c.value - was for c, was in zip(calls, before)] == [
        1, 0, 3, 0, 3, 0]
    assert gauge("ssd_heads_per_step", "").value == 1
    assert tuning.last_selection()["gqa_group"] == 16
    assert tuning.last_selection()["fwd_block_k"] == 1024
    for scope in ("sparse.compress", "sparse.select", "sparse.attn",
                  "lightning.proj", "lightning.scan", "lightning.out",
                  "embed.scale", "branch.scale", "head.scale", "attn.gate"):
        assert scope in text, scope


#: ``peak_memory_in_bytes`` of ``olmo-hybrid-7b-vp8.steady``'s step as
#: this file compiles it (1 x 16,384, four layers, an eighth of the
#: vocabulary, remat ``minimal``, the least effort; PERF.md, PR 70):
#: 5.57 GB of it the state; 12,887,379,968 at the default effort, which
#: the chip compiles at (the file's ``depth``)
OLMO_HYBRID_STEP_BYTES = 12_887_527_424


def test_olmo_hybrid_step_holds_the_scalar_scans_kernels(
    topo, on_tpu_path, monkeypatch
):
    """``olmo-hybrid-7b-vp8.steady``'s step: it fits under 15.75 GiB
    and plans no more than was read when the cell was built; the three
    Gated DeltaNet layers' scans (each the forward, the forward again
    under ``minimal`` and the one backward over ``[1, 30, 256, 256,
    128]`` entry states, a head's 96 keys by 192 values padded to
    whole lane tiles inside the operator) are named as
    ``delta_rule_ms`` tells them, and as no other reader does, and
    carry ``gdn.scan``; the one attention layer's kernels, 30
    ungrouped heads of 128, are named as ``attn_kernel_ms`` tells them
    under ``attn.full``; v's convolution is a ``kda_conv`` call under
    ``gdn.conv`` while q's and k's, a head of 96 in rows of 2,880, and
    the heads' norm and ``silu`` gate at a head of 192 are plain
    fusions (their frames take whole lane tiles); the log decay is a
    number a head: no float32 ``[16384, 2880]`` array and no ``[.., 64,
    64, 96]`` decay is in the step, and ``g`` reaches the kernels as
    ``[1, 30, 16384, 1]``; no leaf is padded; the counters say the
    form of decay and the head's two widths; every scope of the layer
    is in the text."""
    from dlrover_tpu.ops import delta_rule, gated_norm, kda_conv
    from dlrover_tpu.ops.pallas import delta_rule as scan_kernels
    from dlrover_tpu.ops.pallas import gated_norm as norm_kernels
    from dlrover_tpu.ops.pallas import kda_conv as conv_kernels
    from dlrover_tpu.telemetry.registry import counter, gauge
    from yardstick import cells, worker
    from yardstick.layer_metrics import (
        attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms, ssd_ms,
    )

    monkeypatch.setattr(
        delta_rule, "_use_pallas_a_head", lambda q, v, heads: (
            scan_kernels.tiles_the_kernel(q.shape, heads, v.shape)))
    monkeypatch.setattr(scan_kernels, "_interpret", lambda: False)
    monkeypatch.setattr(
        kda_conv, "_use_pallas", lambda x, w, l2_heads: (
            conv_kernels.tiles_the_kernel(x.shape, w.shape, l2_heads)))
    monkeypatch.setattr(conv_kernels, "_interpret", lambda: False)
    monkeypatch.setattr(
        gated_norm, "_use_pallas", lambda o, groups: (
            norm_kernels.tiles_the_kernel(o.shape, groups)))
    monkeypatch.setattr(norm_kernels, "_interpret", lambda: False)
    # the plain paths as a TPU process traces them: the heads' sums of
    # a head of 96 or 192 columns by two thin products, not a view
    monkeypatch.setattr(kda_conv, "_sums_by_product", lambda d: d % 128 != 0)
    form = dict(decay="head", head="96x192")
    calls = [counter(
        f"delta_rule_{handed}_calls", "", scan_kernels.CALL_LABELS,
    ).labels(**form) for handed in ("rows", "folded")]
    calls += [counter(f"{entry}_{path}_calls", "")
              for entry in ("kda_conv", "head_norm_silu")
              for path in ("kernel", "plain")]
    before = [c.value for c in calls]
    gauge("delta_rule_heads_per_step", "").set(0)
    _, config, traffic = cells.load_cell("olmo-hybrid-7b-vp8.steady")
    assert (traffic["global_batch"], traffic["seq"]) == (1, 16384)
    cfg = worker.program_config(config, traffic)
    assert (cfg.remat, cfg.loss_chunk, cfg.post_norms) == (
        "minimal", 0, "alone")
    # what decides each path, from the shapes alone
    assert scan_kernels.tiles_the_kernel(
        (1, 16384, 2880), 30, (1, 16384, 5760))
    assert not scan_kernels.tiles_the_kernel((1, 16384, 2880), 30)
    assert conv_kernels.tiles_the_kernel((1, 16384, 5760), (5760, 4))
    assert not conv_kernels.tiles_the_kernel(
        (1, 16384, 2880), (2880, 4), 30)
    assert not norm_kernels.tiles_the_kernel((1, 16384, 5760), 30)
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "fsdp"))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    layers = gauge("dlrover_model_operator_layers", "", ("operator",))
    assert [layers.labels(operator=o).value for o in (
        "gated_delta_net", "linear_attention", "full_attention")] == [
            3, 0, 1]
    compiled = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])
    ).compile(LEAST_EFFORT)
    planned = compiled.memory_analysis().peak_memory_in_bytes
    print("olmo_hybrid step plans", planned)
    assert planned <= OLMO_HYBRID_STEP_BYTES < 15.75 * 2 ** 30
    text = compiled.as_text()
    kernels = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"[^\n]*"
        r"op_name=\"([^\"]*)\"", text)
    scan = [(name, op) for name, _, op in kernels
            if delta_rule_ms.KERNEL.search(name)]
    assert len(scan) == 3 * 3, [name for name, _ in scan]
    assert all("gdn.scan" in op for _, op in scan)
    # the chunks' entry states [values, keys], the pairs' inverses
    # and ``w``, at the padded widths
    assert _shapes_of_the_keeping_calls(kernels, delta_rule_ms.KERNEL) == [[
        "bf16[1,16384,7680]", "f32[1,30,256,256,128]",
        "f32[1,15,256,128,128]", "f32[1,16384,7680]"]] * 6
    attend = [(name, op) for name, _, op in kernels
              if attn_kernel_ms.KERNEL.search(name)]
    assert len(attend) == 3, [name for name, _ in attend]
    assert all("attn.full" in op for _, op in attend)
    conv = [name for name, _, op in kernels if "gdn.conv" in op]
    assert len(conv) == 3 * 3, conv  # v's, of each layer, three times
    assert all(name.startswith("kda_conv") for name in conv)
    assert len(kernels) == 9 + 3 + 9  # and no other kernel
    all_readers = (
        attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms, ssd_ms)
    for name, _, op in kernels:
        readers = [r for r in all_readers if r.KERNEL.search(name)]
        assert len(readers) == ("gdn.conv" not in op), name
    # one decay a head: nothing of it a key column wide. The float32
    # arrays of that width are q and k on their way through the plain
    # convolutions; none is the decay's or the scan's
    wide = re.findall(
        r"= f32\[(?:1,)?16384,2880\][^\n]*op_name=\"([^\"]*)\"", text)
    assert any("gdn.conv" in op for op in wide)
    assert not [op for op in wide if "gdn.decay" in op or "gdn.scan" in op]
    assert not re.search(r"\[(\d+,)*64,64,96\]", text)
    assert "f32[1,30,16384,1]" in text
    # nine scans on rows and none folded; nine calls of the
    # convolutions' entry, v's three on the kernels; three of the
    # heads' norm and gate, plain
    assert [c.value - was for c, was in zip(calls, before)] == [
        9, 0, 3, 6, 0, 3]
    assert gauge("delta_rule_heads_per_step", "").value == 2
    assert gauge("delta_rule_state_bytes", "").value == 2 * 256 * 128 * 4
    assert gauge("delta_rule_kept_bytes", "").value == 4 * (
        256 * 128 + 64 * 128 + 64 * 256)
    assert tuning.last_selection()["gqa_group"] == 1
    assert tuning.last_selection()["seq"] == 16384
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    assert shapes["period"][0]["wq"].shape == (1, 3840, 2880)
    assert shapes["period"][0]["wo"].shape == (1, 5760, 3840)
    assert llama.param_count(cfg) == 928_862_196
    for scope in ("gdn.proj", "gdn.conv", "gdn.decay", "gdn.scan",
                  "gdn.out", "norm.post_attn", "norm.post_mlp",
                  "attn.full"):
        assert scope in text, scope


#: ``peak_memory_in_bytes`` of ``jamba2-3b-l14.steady``'s step as this
#: file compiles it (1 x 8,192, fourteen layers, the whole vocabulary
#: tied, remat ``minimal``, the least effort; PERF.md, PR 68): 9.59 GB
#: of it the state. 12,325,139,456 at the default effort, which the
#: chip compiles at (the file's ``depth``)
JAMBA_STEP_BYTES = 11_957_564_928


def _selective_scan_on_tpu_path(monkeypatch):
    from dlrover_tpu.ops import selective_scan
    from dlrover_tpu.ops.pallas import selective_scan as kernels

    monkeypatch.setattr(
        selective_scan, "_use_pallas", lambda x, B: (
            kernels.tiles_the_kernel(x.shape, B.shape)))
    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    return selective_scan


def test_selective_scan_kernels_compile_at_the_cells_shape(topo, monkeypatch):
    """The selective scan's forward and backward kernels at the cell's
    shape (one sequence of 8,192, 5,120 channels of 16 states, rows in
    bf16, the step and the rates in float32): five tiles of 1,024
    lanes, 128 chunks of 64, and the entry states kept for the backward
    pass."""
    selective_scan = _selective_scan_on_tpu_path(monkeypatch)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (
        of((1, 8192, 5120), jnp.bfloat16), of((1, 8192, 5120), jnp.float32),
        of((1, 8192, 16), jnp.bfloat16), of((1, 8192, 16), jnp.bfloat16),
        of((5120, 16), jnp.float32), of((5120,), jnp.float32),
    )

    def loss(*operands):
        return selective_scan.selective_scan(
            *operands).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "f32[1,128,16,5120]" in text  # the chunks' entry states
    # B and C by groups of eight positions, and their gradients
    assert text.count("f32[1,1024,16,128]") >= 4
    # no array of a state a position, in any layout
    assert not re.search(r"\[(\d+,)*8192,(5120,16|16,5120)\]", text)


def test_jamba_step_holds_the_scans_kernels(topo, on_tpu_path, monkeypatch):
    """``jamba2-3b-l14.steady``'s step: it fits under 15.75 GiB and
    plans no more than was read when the cell was built; the thirteen
    mixers' scans (each the forward, the forward again under
    ``minimal`` and the backward over ``[1, 128, 16, 5120]`` entry
    states) are named as ``selective_scan_ms`` tells them, and as no
    other reader does, and carry ``mamba.scan``; a mixer's convolution
    with its bias is three Pallas calls under ``mamba.conv`` by the
    jitted name ``kda_conv``, which no reader's pattern matches; the
    one attention layer runs the flash kernels at a group of 20 on one
    key head (the forward, the forward again, the one backward kernel),
    so no ``[20, 8192, 8192]`` scores are in the step; every call of
    the two entries took the kernels; no array of ``[8192, 5120, 16]``
    is in the step in any layout; no table of angles is built; and
    every scope of the mixer is in the text."""
    from dlrover_tpu.ops import kda_conv
    from dlrover_tpu.ops.pallas import kda_conv as conv_kernels
    from dlrover_tpu.telemetry.registry import counter
    from yardstick import cells, worker
    from yardstick.layer_metrics import (
        attn_kernel_ms, delta_rule_ms, moe_expert_ms, selective_scan_ms,
        short_conv_ms, ssd_ms,
    )

    _selective_scan_on_tpu_path(monkeypatch)
    monkeypatch.setattr(
        kda_conv, "_use_pallas", lambda x, w, l2_heads: (
            conv_kernels.tiles_the_kernel(x.shape, w.shape, l2_heads)))
    monkeypatch.setattr(conv_kernels, "_interpret", lambda: False)
    calls = [counter(f"{entry}_{path}_calls", "")
             for entry in ("selective_scan", "kda_conv")
             for path in ("kernel", "plain")]
    before = [c.value for c in calls]
    _, config, traffic = cells.load_cell("jamba2-3b-l14.steady")
    cfg = worker.program_config(config, traffic)
    assert (cfg.remat, cfg.loss_chunk) == ("minimal", 0)
    # PR 66's rules at a group of 20, which is no power of two
    assert tuning.heuristic_blocks(8192, 20) == (128, 256)
    assert tuning.forward_key_block(8192, 20, (128, 256)) == 512
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "fsdp"))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    compiled = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])
    ).compile(LEAST_EFFORT)
    planned = compiled.memory_analysis().peak_memory_in_bytes
    print("jamba step plans", planned)
    assert planned <= JAMBA_STEP_BYTES < 15.75 * 2 ** 30
    text = compiled.as_text()
    kernels = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"[^\n]*"
        r"op_name=\"([^\"]*)\"", text)
    readers = (attn_kernel_ms, delta_rule_ms, moe_expert_ms, short_conv_ms,
               ssd_ms, selective_scan_ms)
    scan = [(name, op) for name, _, op in kernels
            if selective_scan_ms.KERNEL.search(name)]
    assert len(scan) == 13 * 3, [name for name, _ in scan]
    assert all("mamba.scan" in op for _, op in scan)
    conv = [name for name, _, op in kernels if "mamba.conv" in op]
    assert len(conv) == 13 * 3 and all(
        name.startswith("kda_conv") for name in conv)
    attend = [(name, op) for name, _, op in kernels
              if attn_kernel_ms.KERNEL.search(name)]
    assert len(attend) == 3 and all("attn.full" in op for _, op in attend)
    assert len(kernels) == 39 + 39 + 3  # and no other kernel
    for name, _, op in kernels:
        found = [r for r in readers if r.KERNEL.search(name)]
        assert len(found) == ("mamba.conv" not in op), name
    assert "f32[1,128,16,5120]" in text  # the chunks' entry states
    assert not re.search(r"\[(\d+,)*8192,(5120,16|16,5120)\]", text)
    assert not re.search(r"\[(\d+,)*20,8192,8192\]", text)
    assert fa._one_backward_kernel(20, 8192, 128)
    selection = tuning.last_selection()
    assert (selection["gqa_group"], selection["block_q"],
            selection["block_k"], selection["fwd_block_k"]) == (
                20, 128, 256, 512)
    # a call a mixer: the remat's second forward reuses its trace
    assert [c.value - was for c, was in zip(calls, before)] == 2 * [13, 0]
    assert "cosine" not in text and "sine" not in text  # no rotary table
    for scope in ("mamba.in_proj", "mamba.conv", "mamba.x_proj", "mamba.dt",
                  "mamba.scan", "mamba.gate", "mamba.out_proj", "attn.full"):
        assert scope in text, scope


#: ``peak_memory_in_bytes`` of ``ouro-2.6b-1chip.steady``'s step as
#: this file compiles it (1 x 8,192, 16 layers walked four times,
#: remat ``minimal``, the least effort; PERF.md, PR 58): 6.14 GB of it
#: the state. 14,867,043,328 at either effort while a pass's logits
#: were widened to float32 and their gradient scattered into zeros;
#: down by 467,533,312 since a pass keeps them once, in bfloat16 (PR
#: 63, ``models/llama.py _head_nll``; 14,391,121,408 at the default
#: effort, which the chip compiles at)
OURO_STEP_BYTES = 14_399_510_016


def test_ouro_step_fits_and_holds_a_layer_body_a_pass(topo, on_tpu_path):
    """``ouro-2.6b-1chip.steady``'s step (1 x 8,192, 16 layers walked
    four times, remat ``minimal``, the loss unchunked): it fits the
    chip's 16.91 GB and plans no more than was read when the cell was
    built, the four passes' float32 logits (6.4 GB) not among it; the
    passes are unrolled, so the program holds a layer body a pass, and
    in each the Pallas kernels as the benchmark's ``attn_kernel_ms``
    tells them by name: the forward, the forward again under
    ``minimal`` and ONE backward kernel with a head's dQ resident, not
    the dq and dk/dv pair; the three scopes the loop brought are in
    the compiled step's ``op_name``s beside the block's two."""
    from yardstick import cells, worker
    from yardstick.layer_metrics import attn_kernel_ms

    _, config, traffic = cells.load_cell("ouro-2.6b-1chip.steady")
    cfg = worker.program_config(config, traffic)
    assert (cfg.remat, cfg.loss_chunk, cfg.total_ut_steps) == (
        "minimal", 0, 4)
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "fsdp"))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    compiled = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])
    ).compile(LEAST_EFFORT)
    planned = compiled.memory_analysis().peak_memory_in_bytes
    print("ouro step plans", planned)
    assert planned <= OURO_STEP_BYTES < 16.91e9
    text = compiled.as_text()
    kernels = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"[^\n]*"
        r"op_name=\"([^\"]*)\"", text)
    attn = [(name, op) for name, _, op in kernels
            if attn_kernel_ms.KERNEL.search(name)]
    assert fa._one_backward_kernel(1, 8192, 128)
    assert len(kernels) == len(attn) == 4 * 3, [name for name, _ in attn]
    assert all("loop.pass" in op for _, op in attn)
    again = [op for _, op in attn if "rematted_computation" in op]
    backward = [op for _, op in attn
                if "transpose(jvp" in op and op not in again]
    assert (len(again), len(backward)) == (4, 4), attn
    for scope in ("loop.pass", "loop.exit_gate", "loop.exit_loss",
                  "norm.post_attn", "norm.post_mlp"):
        assert scope in text, scope
    assert tuning.last_selection()["gqa_group"] == 1


#: ``peak_memory_in_bytes`` of ``trinity-mini-ep8.steady``'s step as
#: this file compiles it (1 x 16,384, nine layers, remat ``minimal``,
#: the least effort; PERF.md, PR 49): 7.46 GB of it the state. It
#: read 15,488,046,080 here and 15,546,647,040 at the default effort
#: while the routers gathered their k scores; since they compare (PR
#: 55) the default effort, which the chip compiles at, plans
#: 15,524,612,608, and the least effort 108 MB more than it did: it
#: alone lifts the select's zeros, a float32 [16384, 8, 128], out of
#: the layer loop and carries them through it,
#: 15,596,441,600. Down by 616,621,056 since the head keeps its
#: logits once, in bfloat16 (PR 63, ``models/llama.py _head_nll``)
TRINITY_STEP_BYTES = 14_979_820_544


def test_trinity_step_fits_and_moves_the_bias(
    topo, on_tpu_path, monkeypatch
):
    """``trinity-mini-ep8.steady``'s step (1 x 16,384, a leading dense
    layer and two periods of [window, full, window, window] with 16 of
    128 experts held, remat ``minimal``): it fits the chip's 16.91 GB
    and plans no more than was read when the cell was built; every
    layer's attention is the Pallas kernels, as the benchmark's
    ``attn_kernel_ms`` tells them by name, a period's four positions
    the forward, the forward again and the one backward kernel of a
    group of eight each, under ``attn.window`` or ``attn.full``; the
    held share is walked in chunks of 10,240 rows by the kernels
    ``moe_expert_ms`` knows; and the four scopes this configuration
    brought are in the compiled step's ``op_name``s, the bias rule
    inside the one program."""
    from dlrover_tpu.ops import grouped_matmul as gm
    from yardstick import cells, worker
    from yardstick.layer_metrics import attn_kernel_ms, moe_expert_ms

    monkeypatch.setattr(gm, "_use_pallas", lambda lhs, rhs: True)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_add_on_mxu", lambda out, rows: True)
    _, config, traffic = cells.load_cell("trinity-mini-ep8.steady")
    cfg = worker.program_config(config, traffic)
    assert (cfg.remat, cfg.loss_chunk) == ("minimal", 0)
    assert (cfg.moe_experts_held, cfg.num_experts) == (16, 128)
    assert cfg.moe_bias_update_rate == 0.001
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "fsdp"))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    compiled = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])
    ).compile(LEAST_EFFORT)
    planned = compiled.memory_analysis().peak_memory_in_bytes
    print("trinity step plans", planned)
    assert planned <= TRINITY_STEP_BYTES < 16.91e9
    text = compiled.as_text()
    _routers_compare(text, traffic, cfg)
    kernels = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"[^\n]*"
        r"op_name=\"([^\"]*)\"", text)
    attn = [(name, op) for name, _, op in kernels
            if attn_kernel_ms.KERNEL.search(name)]
    # the leading layer and the period's four positions, each the
    # forward, the forward again and the one backward kernel
    assert fa._one_backward_kernel(8, 16384, 128)
    assert len(attn) == 5 * 3, [name for name, _ in attn]
    windowed = [op for _, op in attn if "attn.window" in op]
    full = [op for _, op in attn if "attn.full" in op]
    assert (len(windowed), len(full)) == (4 * 3, 3)
    experts = [name for name, result, _ in kernels
               if moe_expert_ms.KERNEL.search(name)]
    assert len(experts) >= 4 * 9
    rows, _ = moe.walk_chunks(
        traffic["seq"] * cfg.moe_top_k, cfg.hidden_size)
    assert rows == 10240 and f"bf16[{rows},1024]" in text
    for scope in ("embed.mup", "norm.post_attn", "norm.post_mlp",
                  "moe.bias_update", "attn.gate", "moe.shared"):
        assert f"{scope}" in text, scope
    assert tuning.last_selection()["gqa_group"] == 8


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


def test_fsdp_step_gathers_weights_not_activations(topo, on_tpu_path):
    """Mistral-7B widths, two layers, 4 x 4096 over the four chips
    (the yardstick's ``fsdp4-4x4096`` at a depth that compiles in
    seconds). With the activations pinned to their batch shards the
    only traffic left is ZeRO-3's: each layer's seven weights gathered
    once for the forward and once for the backward, their gradients
    reduce-scattered. Left free, the partitioner all-to-alls the
    activations onto the weights' shards instead (seven a layer) and
    gathers the ``[4, 4096, 14336]`` MLP hidden back whole."""
    cfg = llama.LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=2, num_heads=32, num_kv_heads=8, max_seq_len=4096,
        remat="dots_attn_out",
    )
    batch, seq = 4, 4096
    mesh = Mesh(
        np.array(topo.devices).reshape(1, 4), ("data", "fsdp")
    )
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy="fsdp", optimizer=optax.adamw(1e-4),
    )
    compiled = trainer.train_step.lower(
        *_abstract_step_args(trainer, batch, seq)
    ).compile(compiler_options=LEAST_EFFORT)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    census = collective_census(text)
    kinds = {c["kind"] for c in census}
    assert "all-to-all" not in kinds, [
        c for c in census if c["kind"] == "all-to-all"
    ]
    gathers = [c for c in census if c["kind"] == "all-gather"]
    assert not [
        c for c in gathers
        if batch in c["shape"] and cfg.intermediate_size in c["shape"]
    ]
    # inside the two loops: the layer's weights, each exactly twice
    in_loop = [c for c in gathers if c["in_loop"]]
    assert len(in_loop) == 2 * 7, in_loop
    assert all(c["origin"] == "parameter" for c in in_loop), in_loop
    # above 64 MB nothing but weights is gathered, with one exception
    # outside the loops: the embedding's cotangent, which the scatter-
    # add into a vocab-sharded table needs whole (134 MB once a step;
    # reducing a whole table's gradient instead would move 262 MB)
    large = [
        c for c in gathers
        if c["bytes"] > 64e6 and c["origin"] != "parameter"
    ]
    assert [(c["shape"], c["in_loop"]) for c in large] in (
        [], [((batch, seq, cfg.hidden_size), False)],
    ), large
    assert sum(c["scatter"] for c in census) >= 7, census


FSDP4_STEP_BYTES = 15_047_397_376


def test_fsdp4_step_fits_and_ties_its_gradients(topo, on_tpu_path):
    """``mistral-7b-l16.fsdp4``'s step (16 layers, 4 x 4096 over four
    chips, remat ``dots_attn_out``): it fits a chip's 16.91 GB and
    plans no more than was read when the gradients' reductions got
    their deadlines (``models/llama.py _tie``; 15,018,036,224 without
    them; the real chips plan 15,463,208,960 and 15,412,876,800,
    PERF.md section 6, PR 59); each of its two loops holds one layer's
    seven weight gathers (the barriers are gone from a scheduled
    program's text: tests/test_gradient_deadlines.py holds them in
    the jaxpr). Two layers in a body (``lax.scan``'s ``unroll=2``)
    planned 15,610,513,920 here and 15,832,355,840 on the chips, where
    the step then took 1,005 ms for 811."""
    from yardstick import cells, worker

    _, config, traffic = cells.load_cell("mistral-7b-l16.fsdp4")
    cfg = worker.program_config(config, traffic)
    assert (cfg.num_layers, cfg.remat) == (16, "dots_attn_out")
    mesh = Mesh(
        np.array(topo.devices).reshape(1, 4), tuple(traffic["mesh"]))
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    compiled = trainer.train_step.lower(*_abstract_step_args(
        trainer, traffic["global_batch"], traffic["seq"])
    ).compile()
    planned = compiled.memory_analysis().peak_memory_in_bytes
    print("fsdp4 step plans", planned)
    assert planned <= FSDP4_STEP_BYTES < 16.91e9
    in_loop = [
        c for c in collective_census(compiled.as_text())
        if c["kind"] == "all-gather" and c["in_loop"]
    ]
    assert len(in_loop) == 2 * 7, in_loop
