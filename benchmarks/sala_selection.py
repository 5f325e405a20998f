"""On the chip, at a ``sala`` cell's sizes: how far the selection that
the bfloat16 program makes in its selected-attention layer lies from
the float32 reference's on the same weights and tokens, and what the
difference does to the loss. A seed a JSON line on stdout and in
``chiprun_out/sala_selection.jsonl``:

- ``pairs_differing``: the share of (query, kv head) pairs whose set
  of selected blocks is not the reference's; ``blocks_differing``: of
  all selected (query, kv head, block) triples, the share that the
  other side did not select;
- ``loss_own`` and ``loss_with_the_references``: the program's loss
  with its own selection and with the reference's handed to its
  attention in its place; ``reference``: the reference's loss.

    python benchmarks/sala_selection.py \
        --cell minicpm-sala-9b-vp8.steady --seeds 6400000201 6400000202

The selected-attention layer is the stack's first, so its input is the
embedding's rows: the program's q and k are ``models/llama.py
_pre_attn``'s, the reference's its own lines in float32. Off the TPU
the script refuses unless ``--rehearse tiny-sala`` names a size the
CPU holds.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default="chiprun_out/sala_selection.jsonl")
    ap.add_argument("--rehearse", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        sys.exit("no TPU: the selection is compared on the chip")

    from benchmarks.controls import key_of
    from dlrover_tpu.models import llama
    from dlrover_tpu.ops import sparse_attention
    from yardstick import cells, reference, worker
    from yardstick.reference import F32, HIGHEST, rms_norm
    from yardstick.references import sala as ref

    _, config, traffic = cells.load_cell(args.cell, rehearse=args.rehearse)
    cfg = worker.program_config(config, traffic)
    kind = cfg.layer_plan()[1][0]
    assert kind.operator == "sparse_attention", kind
    sizes = config["assumed"]["sparse_config"]
    eps = float(config["rms_norm_eps"])

    @jax.jit
    def programs(params, tokens):
        p = jax.tree.map(lambda a: a[0], params["period"][0])
        x = llama._embed(params, tokens, cfg)
        (q, k, _, _), _ = llama._pre_attn(cfg, x, p, None, None, kind=kind)
        return sparse_attention.select_blocks(
            q, sparse_attention.compress_keys(
                k, cfg.sparse_kernel_size, cfg.sparse_kernel_stride),
            block=cfg.sparse_block_size, kernel=cfg.sparse_kernel_size,
            stride=cfg.sparse_kernel_stride, topk=cfg.sparse_topk,
            window=cfg.sparse_window_size,
            init_blocks=cfg.sparse_init_blocks)

    @jax.jit
    def references(params, tokens):
        with HIGHEST():
            p = ref.layer(params["period"][0], 0)
            x = params["embed"][tokens].astype(F32) * F32(config["scale_emb"])
            y = rms_norm(x, p["attn_norm"], eps)
            b, s, _ = y.shape
            q = rms_norm((y @ p["wq"]).reshape(
                b, s, config["num_attention_heads"], -1), p["q_norm"], eps)
            k = rms_norm((y @ p["wk"]).reshape(
                b, s, config["num_key_value_heads"], -1), p["k_norm"], eps)
            return ref.selection(q, k, sizes)

    def loss(params, batch, selection=None):
        """The program's loss, its attention handed ``selection`` in
        its own's place (None: its own); traced while it stands in."""
        was = llama.select_blocks
        if selection is not None:
            llama.select_blocks = lambda *a, **kw: selection
        try:
            return float(jax.jit(
                lambda p, b: llama.next_token_loss(p, b, cfg))(params, batch))
        finally:
            llama.select_blocks = was

    fresh = jax.jit(lambda k: llama.init_params(k, cfg))
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    for seed in args.seeds:
        params = fresh(key_of(seed))
        start = 2 ** 40
        batch = jax.device_put(worker.SeededTokens(
            seed, traffic["seq"], config["vocab_size"])(start, start + 1))
        own, other = programs(params, batch[0]), references(params, batch[0])
        differs = own != other
        row = {
            "seed": seed, "platform": platform, "rehearse": args.rehearse,
            "seq": traffic["seq"],
            "pairs": int(differs.shape[1] * differs.shape[2]),
            "pairs_differing": float(jnp.mean(jnp.any(differs, axis=-1))),
            "blocks_selected": int(jnp.sum(other)),
            "blocks_differing": float(
                jnp.sum(differs) / (jnp.sum(own) + jnp.sum(other))),
            "loss_own": loss(params, batch),
            "loss_with_the_references": loss(params, batch, other),
            "reference": float(reference.loss(config, params, *batch)),
        }
        line = json.dumps(row)
        print(line, flush=True)
        if not args.rehearse:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
