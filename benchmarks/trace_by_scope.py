"""A traced cell's device time by ``jax.named_scope`` (dev tool).

A device trace names every op after its instruction in the compiled
step (``fusion.1827``, ``delta_rule.33``) and holds no ``op_name``; the
compiled step's text holds each instruction's ``op_name``, scopes and
all. This script joins the two: PERF.md section 5's by-scope
paragraphs (PRs 29, 42-45) are its output.

On the chip, in one call (a compile elsewhere numbers the
instructions otherwise)::

    YARDSTICK_DESCRIBE_TRACE=chiprun_out/x/trace python3 yardstick/run.py \\
        --workload <cell> --seed <n> --seconds 40 --trace 1
    python3 benchmarks/trace_by_scope.py step <cell> chiprun_out/x/step.txt

and anywhere afterwards::

    python3 benchmarks/trace_by_scope.py classes \\
        chiprun_out/x/trace.planes.json chiprun_out/x/step.txt

Leaf device ops only (a ``while`` holds its body's ops). An op that
overlaps an asynchronous copy is taken for its holder and dropped: in
``solar-open2-250b-ep32.steady`` one of three attention calls, 4.6 ms.
On no cell's path.
"""

import argparse
import collections
import json
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the scopes of models/llama.py and parallel/moe.py, the first that an
#: ``op_name`` holds wins; ``loss`` and ``optimizer`` (trainer/sharded.py)
#: hold every op of theirs that no inner scope names, as ``loop.pass``
#: (a looped stack's walk) holds a pass's. ``loss.head`` (the head's
#: products and its cross entropy, forward and backward, since PR 63)
#: stands after ``mtp.head``, so a prediction module's head keeps its
#: own name, and ahead of ``loop.exit_loss``: it is ``loss.head`` that
#: names ``ouro``'s four passes through the head, and ``loop.exit_loss``
#: holds what is left of it, the exit distribution and the weighted sum
SCOPES = (
    "ssm.scan", "ssm.in_proj", "ssm.conv", "ssm.dt", "ssm.gate_norm",
    "ssm.out_proj", "moe.latent_down", "moe.latent_up", "kda.scan", "kda.proj", "kda.conv", "kda.out", "kda.decay",
    "gdn.scan", "gdn.proj", "gdn.conv", "gdn.out", "gdn.decay",
    "sparse.compress", "sparse.select", "sparse.attn", "lightning.proj",
    "lightning.scan", "lightning.out", "mamba.scan", "mamba.in_proj",
    "mamba.conv", "mamba.x_proj", "mamba.dt", "mamba.gate",
    "mamba.out_proj", "embed.scale", "branch.scale", "head.scale",
    # "mla.q" (q by one matrix) after "mla.q_down", which holds it
    "mla.q_down", "mla.q", "mla.kv_down", "mla.up", "attn.latent",
    "attn.gate",
    "attn.full", "attn.window", "conv.in_proj", "conv.mix",
    "conv.out_proj", "moe.shared", "moe.route", "moe.dispatch",
    "moe.combine", "moe.experts", "moe.bias_update", "mtp.merge",
    "mtp.block", "mtp.head", "norm.post_attn", "norm.post_mlp",
    "embed.mup", "loop.exit_gate", "loss.head", "loop.exit_loss",
    "loop.pass", "optimizer", "loss",
)


def write_step(cell, out):
    """The cell's step as this process's backend compiles it, and
    how often each operator's entry took its kernels."""
    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models import make_trainer_for
    from dlrover_tpu.parallel.mesh import create_mesh
    from yardstick import cells, worker

    _, config, traffic = cells.load_cell(cell)
    mesh = create_mesh(
        list(traffic["mesh"].items()),
        devices=jax.devices()[:math.prod(traffic["mesh"].values())])
    trainer = make_trainer_for(
        worker.program_config(config, traffic), mesh,
        strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    tokens = jax.ShapeDtypeStruct(
        (1, traffic["global_batch"], traffic["seq"]), jnp.int32,
        sharding=trainer.microbatch_sharding,
    )
    with mesh:
        compiled = trainer.train_step.lower(
            *trainer.abstract_state(), (tokens, tokens)).compile()
    with open(out, "w") as f:
        f.write(compiled.as_text())
    print("planned", compiled.memory_analysis().peak_memory_in_bytes)
    # which path each operator's entry took while the step was traced
    # (``<operator>_<path>_calls``, docs/TELEMETRY.md)
    from dlrover_tpu.telemetry.registry import default_registry

    print("calls", {
        name: sum(family["series"].values())
        for name, family in default_registry().to_dict().items()
        if name.endswith("_calls")
    })


def leaves(events):
    """The events ``[name, start, seconds]`` that hold no other."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    leaf, open_ = [], []
    for at, (_, start, _) in enumerate(events):
        while open_ and sum(events[open_[-1]][1:]) <= start + 1e-12:
            open_.pop()
        if open_:
            leaf[open_[-1]] = False
        leaf.append(True)
        open_.append(at)
    return [e for e, is_leaf in zip(events, leaf) if is_leaf]


def classes(planes, text, steps, top):
    with open(planes) as f:
        events = next(iter(json.load(f)["devices"].values()))
    op_names = {}
    with open(text) as f:
        for line in f:
            m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = ", line)
            if m:
                name = re.search(r'op_name="([^"]*)"', line)
                op_names[m.group(1)] = name.group(1) if name else ""
    total = collections.Counter()
    kinds = collections.defaultdict(collections.Counter)
    for name, _, seconds in leaves(events):
        instruction, _, result = name.partition(" ")
        op_name = op_names.get(instruction)
        if op_name is None:
            scope = "<not in the text>"
        else:
            scope = next(
                (s for s in SCOPES if s in op_name),
                "<other scope>" if op_name else "<no op_name>")
        total[scope] += seconds
        kinds[scope][
            re.sub(r"[.\d]+$", "", instruction) + " " + result[:36]
        ] += seconds
    print(f"leaf device time a step "
          f"{1e3 * sum(total.values()) / steps:.1f} ms")
    for scope, seconds in total.most_common():
        print(f"  {scope:18s}{1e3 * seconds / steps:8.1f} ms")
        for kind, part in kinds[scope].most_common(top):
            print(f"      {1e3 * part / steps:8.2f}  {kind}")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    sub = ap.add_subparsers(dest="what", required=True)
    step = sub.add_parser("step")
    step.add_argument("cell")
    step.add_argument("out")
    by = sub.add_parser("classes")
    by.add_argument("planes")
    by.add_argument("text")
    by.add_argument("--steps", type=int, default=4,
                    help="the mix's traced_steps")
    by.add_argument("--top", type=int, default=6)
    args = ap.parse_args(argv)
    if args.what == "step":
        write_step(args.cell, args.out)
    else:
        classes(args.planes, args.text, args.steps, args.top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
