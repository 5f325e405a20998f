"""On the chip, at the cell's sizes: how far a changed reference's
loss lies from the program's, for each control of
``tests/yardstick/test_yardstick_joyai.py`` (edits to
``yardstick/references/joyai.py``) and for the reference in float8,
beside the unchanged pair, on one 8,192-token sequence a seed as the
cell's check compares them. One JSON line a control on stdout and in
``chiprun_out/joyai_controls.jsonl``.

    python benchmarks/joyai_controls.py --seeds 4200000101 4200000102 \
        [--embed-std 0.02] [--only "no shared expert" ...]

A number from here is a chip's or it is nothing: the program's loss
runs the Pallas kernels, and off the TPU the script refuses.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

CELL = "joyai-llm-flash-ep8.steady"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--embed-std", type=float, default=None)
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--out", default="chiprun_out/joyai_controls.jsonl")
    ap.add_argument("--rehearse", default=None,
                    help="a tiny configuration: the control flow on "
                    "the CPU, its rows marked and written nowhere")
    args = ap.parse_args()

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        sys.exit("no TPU: the controls are read on the chip")

    from dlrover_tpu.models import llama
    from tests.yardstick import test_yardstick_joyai as t
    from yardstick import cells, worker

    _, config, traffic = cells.load_cell(CELL, rehearse=args.rehearse)
    if args.embed_std is not None:
        config["assumed"]["embed_init_std"] = args.embed_std
    cfg = worker.program_config(config, traffic)
    program_loss = jax.jit(
        lambda p, b: llama.next_token_loss(p, b, cfg))

    cases = []
    for seed in args.seeds:
        key = jax.random.fold_in(
            jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
        params = jax.jit(lambda k: llama.init_params(k, cfg))(key)
        start = 2 ** 40
        batch = jax.device_put(worker.SeededTokens(
            seed, traffic["seq"], config["vocab_size"])(start, start + 1))
        cases.append((seed, params, batch,
                      float(program_loss(params, batch))))

    variants = {"unchanged": ()}
    variants.update(t.CONTROLS)
    variants["the reference in float8"] = t.FLOAT8
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    for name, edits in variants.items():
        if args.only is not None and name not in args.only:
            continue
        module = t.edited(name.split()[0], *edits)
        row = {"control": name, "platform": platform,
               "rehearse": args.rehearse,
               "embed_std": cfg.embed_init_std,
               "tolerance": worker.REFERENCE_TOLERANCE, "readings": {}}
        for seed, params, batch, program in cases:
            changed = float(module.loss(config, params, *batch))
            row["readings"][str(seed)] = {
                "program": program, "reference": changed,
                "difference": abs(program - changed)}
        line = json.dumps(row)
        print(line, flush=True)
        if not args.rehearse:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
