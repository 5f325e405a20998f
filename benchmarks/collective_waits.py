"""A traced cell's exposed collective time by operation (dev tool).

``collective_exposed_ms`` (yardstick/layer_metrics) is one number: the
time a step in which a collective runs on a chip and no compute does.
This script splits it the way PERF.md section 5's
``mistral-7b-l16.fsdp4`` paragraph does: by the collective's kind and
result shape (on a v5e host ZeRO-3's weight gathers and gradient
reduce-scatters are rings of ``collective-permute``s, a quarter of a
weight a hop, so the shape names the weight) and, given the compiled
step's text, by operation: the loop body it stands in, the model's
source line, where in a pass through that body it waits, and what the
schedule put between an asynchronous collective's ``-start`` and its
``-done``. ``--body <computation>`` prints that body's operations in
their scheduled order with their device time, the collectives among
them: what stands before a ring's first hop and behind its last.

On the chips, in one call (a compile elsewhere numbers the
instructions otherwise)::

    YARDSTICK_DESCRIBE_TRACE=chiprun_out/x/trace python3 yardstick/run.py \\
        --workload mistral-7b-l16.fsdp4 --seed <n> --seconds 10 --trace 1
    python3 benchmarks/trace_by_scope.py step mistral-7b-l16.fsdp4 \\
        chiprun_out/x/step.txt

and anywhere afterwards::

    python3 benchmarks/collective_waits.py chiprun_out/x/trace.planes.json \\
        [--text chiprun_out/x/step.txt [--body <computation>]] [--steps 4]

The first argument may also be a run's ``.xplane.pb``. Means over the
chips, ms a step; the total is the yardstick's own arithmetic
(``yardstick/reduce.py``), so it reads what ``collective_exposed_ms``
read in that run. Under a remat policy a backward operation's source
line is the ``jax.checkpoint`` call's, not the matmul's.
"""

import argparse
import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from yardstick import reduce  # noqa: E402

INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(")
COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")

Op = collections.namedtuple("Op", "name opcode source op_name operand")


def read_events(path):
    """``{device: [(name, start, seconds)]}`` of a ``.planes.json``
    (``YARDSTICK_DESCRIBE_TRACE``) or an ``.xplane.pb``."""
    if path.endswith(".pb"):
        return reduce.read_planes(path)["devices"]
    with open(path) as f:
        return json.load(f)["devices"]


def kind_of(instruction):
    """``collective-permute-done.41`` -> ``collective-permute-done``."""
    return re.sub(r"[.\d]+$", "", instruction)


def device_times(devices):
    """Summed over the chips: ``{collective's event name: [calls,
    exposed seconds]}`` and every leaf operation's ``{instruction:
    [calls, seconds]}``. A collective's exposed part is what of it
    lies outside every compute operation of its chip
    (``reduce.device_numbers``' arithmetic, kept by operation)."""
    waits = collections.defaultdict(lambda: [0, 0.0])
    every = collections.defaultdict(lambda: [0, 0.0])
    for events in devices.values():
        ops = reduce.leaves([tuple(e) for e in events])
        compute = reduce.union(
            (s, s + d) for n, s, d in ops if not reduce.is_collective(n)
        )
        for name, start, dur in ops:
            row = every[name.partition(" ")[0]]
            row[0] += 1
            row[1] += dur
            if reduce.is_collective(name):
                row = waits[name]
                row[0] += 1
                row[1] += reduce.total(
                    reduce.subtract([(start, start + dur)], compute))
    return waits, every


def read_frames(lines):
    """``{stack_frame_id: "file.py:line"}`` from the tables at the
    head of a compiled module's text: a frame's own location, the
    innermost."""
    tables, at = {}, None
    for line in lines:
        word = line.strip()
        if line.startswith(("ENTRY", "%")):
            break
        if word in TABLES:
            at = tables.setdefault(word, {})
        elif at is not None and word[:1].isdigit():
            number, _, rest = word.partition(" ")
            at[int(number)] = rest
    files = {
        n: os.path.basename(name.strip('"'))
        for n, name in tables.get("FileNames", {}).items()
    }
    places = {}
    for n, text in tables.get("FileLocations", {}).items():
        m = re.search(r"file_name_id=(\d+).*? line=(\d+)", text)
        places[n] = f"{files.get(int(m.group(1)), '?')}:{m.group(2)}"
    return {
        n: places.get(int(re.search(
            r"file_location_id=(\d+)", text).group(1)), "")
        for n, text in tables.get("StackFrames", {}).items()
    }


def read_text(path):
    """``({computation: [Op]} in the printed, which is the scheduled,
    order; the names of the ``while`` bodies)`` of a compiled step's
    text."""
    with open(path) as f:
        lines = f.readlines()
    frames = read_frames(lines)
    computations, bodies, at = {}, set(), None
    for line in lines:
        m = COMPUTATION.match(line)
        if m:
            at = computations.setdefault(m.group(1), [])
            continue
        m = INSTRUCTION.match(line)
        if m is None or at is None:
            continue
        name, opcode = m.groups()
        frame = re.search(r"stack_frame_id=(\d+)", line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        operand = re.search(re.escape(opcode) + r"\(%?([\w.\-]+)", line)
        at.append(Op(
            name, opcode,
            frames.get(int(frame.group(1)), "") if frame else "",
            op_name.group(1) if op_name else "",
            operand.group(1) if operand else "",
        ))
        body = re.search(r"body=%?([\w.\-]+)", line)
        if opcode == "while" and body:
            bodies.add(body.group(1))
    return computations, bodies


def ms_a_call(every, instruction):
    calls, seconds = every.get(instruction, (0, 0.0))
    return 1e3 * seconds / calls if calls else 0.0


def places(computations, every):
    """``{instruction: (computation, Op, ms into a pass through the
    computation at which it ends, the pass's ms, the Ops between its
    -start and it)}``: the last only for a ``-done``."""
    out = {}
    for comp, ops in computations.items():
        where, ends, at = {}, [], 0.0
        for i, op in enumerate(ops):
            where[op.name] = i
            at += ms_a_call(every, op.name)
            ends.append(at)
        for i, op in enumerate(ops):
            between = ()
            if op.opcode.endswith("-done") and op.operand in where:
                between = ops[where[op.operand] + 1:i]
            out[op.name] = (comp, op, ends[i], at, between)
    return out


def print_by_shape(waits, per, top):
    by_shape = collections.defaultdict(lambda: [0, 0.0])
    for name, (n, exposed) in waits.items():
        instruction, _, shape = name.partition(" ")
        row = by_shape[kind_of(instruction) + " " + shape]
        row[0] += n
        row[1] += exposed
    print("\nby kind and result shape: ms a step, calls a step and chip")
    for key, (n, exposed) in sorted(
            by_shape.items(), key=lambda kv: -kv[1][1])[:top]:
        print(f"  {per * exposed:8.2f} {per * n / 1e3:7.1f}  {key}")


def print_by_operation(waits, every, computations, bodies, per, top):
    placed = places(computations, every)
    print("\nby operation: exposed ms a step, calls a step and chip, us a "
          "call, the operation\n    its computation (* a while's body), "
          "source line, pass; ms into a pass through the computation at "
          "which it ends, of the pass's ms | between its -start and it: "
          "operations, their ms, the commonest")
    by_line = collections.defaultdict(float)
    for name, (n, exposed) in sorted(
            waits.items(), key=lambda kv: -kv[1][1])[:top]:
        instruction = name.partition(" ")[0]
        if instruction not in placed:
            print(f"  {per * exposed:7.2f}  {name}  <not in the text>")
            continue
        comp, op, end, whole, between = placed[instruction]
        by_line[(comp, op.source)] += exposed
        kinds = collections.Counter(kind_of(o.name) for o in between)
        beside = " ".join(
            f"{c}x{kind}" for kind, c in kinds.most_common(3))
        ms = sum(ms_a_call(every, o.name) for o in between)
        print(f"  {per * exposed:7.2f} {per * n / 1e3:5.1f} "
              f"{1e6 * exposed / max(n, 1):6.0f}  {name}\n"
              f"      {'*' if comp in bodies else ' '}{comp} {op.source} "
              f"{'bwd' if 'transpose' in op.op_name else 'fwd'}; at "
              f"{end:.2f} of {whole:.2f} | {len(between)} ops {ms:.2f} ms "
              f"{beside}")
    print("\nby computation and source line (of the operations above): "
          "ms a step")
    for (comp, src), exposed in sorted(
            by_line.items(), key=lambda kv: -kv[1]):
        print(f"  {per * exposed:8.2f}  {comp} {src}")


def print_body(body, computations, every, least_ms):
    """A computation's operations as scheduled: ms into a pass at
    which each ends, its ms a call, name, opcode, source line, first
    operand. Collectives all, the others from ``least_ms`` up."""
    at = 0.0
    for op in computations[body]:
        ms = ms_a_call(every, op.name)
        at += ms
        if ms >= least_ms or reduce.is_collective(op.opcode):
            print(f"{at:8.2f} {ms:7.3f}  {op.name:42s} {op.opcode:26s} "
                  f"{op.source:18s} {op.operand[:32]}")
    print(f"a pass through {body}: {at:.2f} ms")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("trace", help="<stem>.planes.json or an .xplane.pb")
    ap.add_argument("--text", help="the compiled step's text")
    ap.add_argument("--body", help="print this computation as scheduled")
    ap.add_argument("--least-ms", type=float, default=0.08,
                    help="with --body: leave out shorter compute")
    ap.add_argument("--steps", type=int, default=4,
                    help="the mix's traced_steps")
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args(argv)
    devices = read_events(args.trace)
    waits, every = device_times(devices)
    # seconds summed over chips and steps -> ms a step and chip
    per = 1e3 / (args.steps * len(devices))
    if args.body:
        print_body(args.body, read_text(args.text)[0], every, args.least_ms)
        return 0
    print(f"{len(devices)} chips, {args.steps} steps; collectives exposed "
          f"{per * sum(row[1] for row in waits.values()):.2f} ms a step")
    print_by_shape(waits, per, args.top)
    if args.text:
        print_by_operation(
            waits, every, *read_text(args.text), per, args.top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
