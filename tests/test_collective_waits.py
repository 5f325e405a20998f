"""benchmarks/collective_waits.py on a hand-made trace and step text:
the exposed part of each collective adds up to the yardstick's own
``collective_exposed_s``, and the text says where each one stands."""

import importlib.util
import json
import os

import pytest

from yardstick import reduce

_SPEC = importlib.util.spec_from_file_location(
    "collective_waits", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "collective_waits.py"))
waits = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(waits)

US = 1e-6
DONE_1 = "collective-permute-done.1 bf16[1024,4096]"
DONE_2 = "collective-permute-done.2 bf16[1024,4096]"
#: one chip, one pass through a loop body (the line of a chip's
#: operations is serial): a gather's first hop that waits 30 us, a
#: product, then the last hop of a gradient's ring, 50 us with nothing
#: behind it, and a reduction past the loop
EVENTS = [
    ("while.1 (bf16[4,4096]", 0.0, 300 * US),
    ("collective-permute-start.1 (bf16[1024,4096]", 0.0, 1 * US),
    ("fusion.7 bf16[4,4096]", 1 * US, 9 * US),
    (DONE_1, 10 * US, 30 * US),
    ("fusion.8 bf16[4,4096]", 40 * US, 100 * US),
    ("collective-permute-start.2 (bf16[1024,4096]", 140 * US, 1 * US),
    (DONE_2, 141 * US, 50 * US),
    ("all-reduce.5 (f32[4096]", 200 * US, 100 * US),
]
TEXT = """HloModule jit_step, is_scheduled=true

FileNames
1 "/root/repo/dlrover_tpu/models/llama.py"
2 "/root/repo/dlrover_tpu/trainer/sharded.py"

FunctionNames
1 "body"

FileLocations
1 {file_name_id=2 function_name_id=1 line=198 end_line=198 column=4 end_column=9}
2 {file_name_id=1 function_name_id=1 line=1404 end_line=1404 column=4 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}


%fused_computation.7 (param_0: bf16[4,4096]) -> bf16[4,4096] {
  %param_0 = bf16[4,4096]{1,0} parameter(0)
  ROOT %add.1 = bf16[4,4096]{1,0} add(%param_0, %param_0)
}

%body.1 (p: (bf16[4,4096], bf16[1024,4096])) -> (bf16[4,4096], bf16[1024,4096]) {
  %p = (bf16[4,4096]{1,0}, bf16[1024,4096]{1,0}) parameter(0)
  %gte.1 = bf16[1024,4096]{1,0} get-tuple-element(%p), index=1
  %collective-permute-start.1 = (bf16[1024,4096]{1,0}, bf16[1024,4096]{1,0}) collective-permute-start(%gte.1), channel_id=1, source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(step)/loss/while/body/dot_general" stack_frame_id=2}
  %fusion.7 = bf16[4,4096]{1,0} fusion(%gte.0), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(step)/loss/while/body/add" stack_frame_id=2}
  %collective-permute-done.1 = bf16[1024,4096]{1,0} collective-permute-done(%collective-permute-start.1), metadata={op_name="jit(step)/loss/while/body/dot_general" stack_frame_id=2}
  %fusion.8 = bf16[4,4096]{1,0} fusion(%fusion.7, %collective-permute-done.1), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(step)/loss/while/body/dot_general" stack_frame_id=2}
  %collective-permute-start.2 = (bf16[1024,4096]{1,0}, bf16[1024,4096]{1,0}) collective-permute-start(%fusion.8), channel_id=2, source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(step)/loss/transpose(jvp())/while/body/dot_general" stack_frame_id=2}
  %collective-permute-done.2 = bf16[1024,4096]{1,0} collective-permute-done(%collective-permute-start.2), metadata={op_name="jit(step)/loss/transpose(jvp())/while/body/dot_general" stack_frame_id=2}
  ROOT %tuple.1 = (bf16[4,4096]{1,0}, bf16[1024,4096]{1,0}) tuple(%fusion.8, %collective-permute-done.2)
}

ENTRY %main.1 (a: bf16[4,4096], b: bf16[1024,4096]) -> f32[4096] {
  %a = bf16[4,4096]{1,0} parameter(0)
  %b = bf16[1024,4096]{1,0} parameter(1)
  %tuple.0 = (bf16[4,4096]{1,0}, bf16[1024,4096]{1,0}) tuple(%a, %b)
  %while.1 = (bf16[4,4096]{1,0}, bf16[1024,4096]{1,0}) while(%tuple.0), condition=%cond.1, body=%body.1
  ROOT %all-reduce.5 = f32[4096]{0} all-reduce(%while.1), channel_id=3, replica_groups={{0,1}}, to_apply=%sum, metadata={op_name="jit(step)/loss/reduce_sum" stack_frame_id=1}
}
"""


@pytest.fixture
def files(tmp_path):
    planes = tmp_path / "trace.planes.json"
    planes.write_text(json.dumps(
        {"devices": {"/device:TPU:0": EVENTS}, "host": []}))
    text = tmp_path / "step.txt"
    text.write_text(TEXT)
    return str(planes), str(text)


def test_the_parts_add_up_to_the_yardsticks_number():
    exposed, every = waits.device_times({"/device:TPU:0": EVENTS})
    assert exposed[DONE_1] == [1, pytest.approx(30 * US)]
    assert exposed[DONE_2] == [1, pytest.approx(50 * US)]
    assert exposed["all-reduce.5 (f32[4096]"] == [
        1, pytest.approx(100 * US)]
    assert sum(row[1] for row in exposed.values()) == pytest.approx(
        reduce.device_numbers(EVENTS)["collective_exposed_s"])
    # leaves only: the while holds its body's operations
    assert "while.1" not in every
    assert every["fusion.8"] == [1, pytest.approx(100 * US)]


def test_the_text_says_where_a_collective_stands(files):
    computations, bodies = waits.read_text(files[1])
    assert bodies == {"body.1"}
    assert set(computations) == {"fused_computation.7", "body.1", "main.1"}
    done = {op.name: op for op in computations["body.1"]}
    assert done["collective-permute-done.2"].source == "llama.py:1404"
    assert done["collective-permute-done.2"].operand == (
        "collective-permute-start.2")
    _, every = waits.device_times({"/device:TPU:0": EVENTS})
    placed = waits.places(computations, every)
    comp, op, end, whole, between = placed["collective-permute-done.1"]
    assert comp == "body.1" and [o.name for o in between] == ["fusion.7"]
    # the first hop ends 40 us into a pass of 191, the last hop ends it
    assert (end, whole) == (pytest.approx(0.040), pytest.approx(0.191))
    assert placed["collective-permute-done.2"][2] == pytest.approx(0.191)
    assert not placed["collective-permute-done.2"][4]
    comp, op, *_ = placed["all-reduce.5"]
    assert (comp, op.source) == ("main.1", "sharded.py:198")


@pytest.mark.parametrize("with_text", [False, True], ids=["trace", "text"])
def test_what_it_prints(files, capsys, with_text):
    planes, text = files
    argv = [planes, "--steps", "1"] + (["--text", text] if with_text else [])
    assert waits.main(argv) == 0
    out = capsys.readouterr().out
    assert "1 chips, 1 steps; collectives exposed 0.18 ms a step" in out
    # two hops of one shape, 30 and 50 us
    assert "0.08     2.0  collective-permute-done bf16[1024,4096]" in out
    assert ("by operation" in out) == with_text
    if with_text:
        assert "*body.1 llama.py:1404 bwd; at 0.19 of 0.19 | 0 ops" in out
        assert "*body.1 llama.py:1404 fwd; at 0.04 of 0.19 | 1 ops" in out
        assert " main.1 sharded.py:198 fwd; at 0.10 of 0.10" in out


def test_a_body_as_scheduled(files, capsys):
    planes, text = files
    assert waits.main(
        [planes, "--text", text, "--body", "body.1", "--least-ms", "0.05"]
    ) == 0
    lines = capsys.readouterr().out.splitlines()
    # the collectives all, of the compute what takes 50 us or more
    assert [line.split()[2] for line in lines[:-1]] == [
        "collective-permute-start.1", "collective-permute-done.1",
        "fusion.8", "collective-permute-start.2",
        "collective-permute-done.2"]
    assert lines[-1] == "a pass through body.1: 0.19 ms"
