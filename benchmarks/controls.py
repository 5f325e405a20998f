"""On the chip, at a cell's sizes: how far a changed reference's loss
lies from the program's, for each control of the family's
``tests/yardstick/test_yardstick_<family>.py`` (``CONTROLS``: edits to
``yardstick/references/<family>.py``) and for the reference in float8
(``FLOAT8``), beside the unchanged pair, on one sequence of the cell's
length a seed as the cell's check compares them. The family is the
``family`` of the cell's configuration. One JSON line a control on
stdout and in ``chiprun_out/<family>_controls.jsonl``.

    python benchmarks/controls.py --cell solar-open2-250b-ep32.steady \
        --seeds 4400000101 4400000102 \
        [--embed-std 0.02] [--head-std 0.25] \
        [--only "no shared expert" ...]

With ``--probe N`` instead, for a family with experts: the trainer's
own step on the cell's weights for N steps, ``routing_stats`` before
each step (a layer's rows on the held experts, the most loaded expert
over the mean), with delta-rule layers the least ``alpha`` a layer
(``kda_decay_min``) and with state-space layers the least ``a`` a
head takes (``ssm_decay_min``), with a selection bias its largest magnitude a
layer (what ``moe_bias_abs_max`` is the most of: 0 unless a rule moves
it), each step's seconds and loss: whether the routers keep their
balance while they train.

A number from here is a chip's or it is nothing: the program's loss
runs the Pallas kernels, and off the TPU the script refuses.
"""

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def key_of(seed):
    """As ``yardstick/worker.py`` draws the cell's weights."""
    import jax

    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def write(args, line):
    print(line[:6000], flush=True)
    if not args.rehearse:
        with open(args.out, "a") as f:
            f.write(line + "\n")


def probe(args, config, traffic, cfg, platform):
    """The step's trajectory on each seed's weights."""
    import jax
    import numpy as np
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.mesh import create_mesh
    from dlrover_tpu.trainer.sharded import make_trainer_for_llama
    from yardstick import worker

    if not cfg.num_experts:
        sys.exit(f"{config['family']}: no experts, no router to probe")
    decays = "linear_attention" in (cfg.layer_types or ()) or (
        "M" in (cfg.hybrid_override_pattern or ""))
    mesh = create_mesh(
        list(traffic["mesh"].items()), devices=jax.devices()[:1])
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]))
    stats = jax.jit(lambda p, t: (
        llama.routing_stats(p, t, cfg),
        llama.decay_min(p, t, cfg) if decays else None,
        llama.expert_bias_abs_max(p, cfg) if cfg.use_expert_bias
        else None))
    held = slice(cfg.moe_first_expert_held,
                 cfg.moe_first_expert_held + cfg.moe_experts_held)
    for seed in args.seeds:
        batch_fn = worker.SeededTokens(
            seed, traffic["seq"], config["vocab_size"])
        # in the layout the step returns: one compile, not two
        params, opt_state = trainer.init(key_of(seed))
        rows = []
        for step in range(args.probe):
            n = traffic["global_batch"]
            tokens, targets = batch_fn(step * n, (step + 1) * n)
            with mesh:
                counts, least, bias = stats(
                    params, jax.device_put(tokens))
                counts = np.asarray(counts)
                mb = trainer.microbatch((tokens, targets))
                t0 = time.perf_counter()
                params, opt_state, loss = trainer.train_step(
                    params, opt_state, mb)
                loss = float(loss)
            row = {
                "step": step, "seconds": time.perf_counter() - t0,
                "loss": loss,
                "held_rows": counts[:, held].sum(axis=1).tolist(),
                "max_over_mean": (
                    counts.max(axis=1) / counts.mean(axis=1)).tolist(),
            }
            if decays:
                row["decay_min"] = np.asarray(least).tolist()
            if bias is not None:
                row["bias_abs_max"] = np.asarray(bias).tolist()
            rows.append(row)
        write(args, json.dumps({
            "probe": seed, "platform": platform,
            "rehearse": args.rehearse,
            "embed_std": cfg.embed_init_std,
            "head_std": cfg.head_init_std,
            "rows": rows}))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--embed-std", type=float, default=None)
    ap.add_argument("--head-std", type=float, default=None)
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--probe", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="chiprun_out/<family>_controls.jsonl")
    ap.add_argument("--rehearse", default=None,
                    help="a tiny configuration: the control flow on "
                    "the CPU, its rows marked and written nowhere")
    args = ap.parse_args()

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        sys.exit("no TPU: the controls are read on the chip")

    from dlrover_tpu.models import llama
    from yardstick import cells, worker

    _, config, traffic = cells.load_cell(args.cell, rehearse=args.rehearse)
    family = config["family"]
    if args.embed_std is not None:
        config["assumed"]["embed_init_std"] = args.embed_std
    if args.head_std is not None:
        config["assumed"]["head_init_std"] = args.head_std
    cfg = worker.program_config(config, traffic)
    if not args.rehearse:
        args.out = args.out or f"chiprun_out/{family}_controls.jsonl"
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    if args.probe:
        return probe(args, config, traffic, cfg, platform)

    t = importlib.import_module(f"tests.yardstick.test_yardstick_{family}")
    if not hasattr(t, "edited"):
        sys.exit(f"{t.__name__} has no `edited`: no control to run")
    variants = {"unchanged": (), **t.CONTROLS}
    if hasattr(t, "FLOAT8"):
        variants["the reference in float8"] = t.FLOAT8
    else:
        print(f"{t.__name__} has no `FLOAT8`: no float8 row",
              file=sys.stderr)
    # a family whose controls put one operator in another's place
    # hands the reference the other's leaves, as its own test does
    exchanged = getattr(t, "exchanged", None)

    program_loss = jax.jit(
        lambda p, b: llama.next_token_loss(p, b, cfg))
    fresh = jax.jit(lambda k: llama.init_params(k, cfg))
    cases = []
    for seed in args.seeds:
        params = fresh(key_of(seed))
        start = 2 ** 40
        batch = jax.device_put(worker.SeededTokens(
            seed, traffic["seq"], config["vocab_size"])(start, start + 1))
        cases.append((seed, params, batch,
                      float(program_loss(params, batch))))

    for name, edits in variants.items():
        if args.only is not None and name not in args.only:
            continue
        if name.startswith("attention in"):
            edits = edits + getattr(t, "KV_OF_THEIR_OWN", ())
        module = t.edited(name.split()[0], *edits)
        row = {"control": name, "platform": platform,
               "rehearse": args.rehearse,
               "embed_std": cfg.embed_init_std,
               "head_std": cfg.head_init_std,
               "tolerance": worker.REFERENCE_TOLERANCE, "readings": {}}
        for seed, params, batch, program in cases:
            if exchanged and "place" in name:
                params = exchanged(params, name)
            changed = float(module.loss(config, params, *batch))
            row["readings"][str(seed)] = {
                "program": program, "reference": changed,
                "difference": abs(program - changed)}
        write(args, json.dumps(row))


if __name__ == "__main__":
    main()
