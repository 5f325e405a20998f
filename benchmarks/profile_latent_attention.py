"""On the chip: one layer's latent attention path of
``joyai-llm-flash-ep8.steady`` alone ([4, 8192] tokens, 32 heads, q
and k 128 un-rotated and 64 rotated columns, v 128, causal), from the
normed latents ``c_q`` [.., 1536] and ``c_kv`` [.., 512] and the one
un-rotated key [.., 64] through the up-projections, the rotation and
the attention kernels to ``o``, and back to the gradients of the three
and of ``wq_b`` and ``wkv_b``. Two forms of the same mathematics:

  whole  q and k of 192 built outside the kernels, as the model did
         until PR 43: two products, the activations split, rotated in
         neighbouring pairs, the one key copied to every head,
         concatenated; ``flash_attention(q, k, v)``
  parts  ``models/llama.py _latent_up``: four products on columns of
         the weights, the rotation in halves on 64 columns,
         ``flash_attention(q, k, v, q_rope=, k_rope=)``

One JSON line a form: ms a call (forward alone; forward and the
gradients) and how far the parts' results lie from the whole's. The
caches are cleared between the forms, or the first form's trace would
answer for the second (PERF.md section 6, PR 42).

    python benchmarks/profile_latent_attention.py [--n 10]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.models import llama  # noqa: E402
from dlrover_tpu.ops import tuning  # noqa: E402
from dlrover_tpu.ops.attention import flash_attention  # noqa: E402

SHAPE = dict(batch=4, seq=8192, heads=32, nope=128, rope=64, v=128,
             q_rank=1536, kv_rank=512)


def timeit(fn, *args, n=10, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def _config():
    s = SHAPE
    return llama.llama_latent_tiny(
        num_heads=s["heads"], num_kv_heads=s["heads"],
        q_lora_rank=s["q_rank"], kv_lora_rank=s["kv_rank"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
        v_head_dim=s["v"], max_seq_len=s["seq"], rope_theta=32000000.0,
    )


def whole(cfg, c_q, c_kv, k_rope, p, cos, sin):
    """A head's q and k whole, built outside the kernels."""
    b, s, _ = c_q.shape
    nh, nope, rope = (cfg.num_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim)
    q = (c_q @ p["wq_b"]).reshape(b, s, nh, -1)
    kv = (c_kv @ p["wkv_b"]).reshape(b, s, nh, -1)
    q_nope, q_rope = jnp.split(q, [nope], axis=-1)
    k_nope, v = jnp.split(kv, [nope], axis=-1)
    q_rope = llama.apply_rope(q_rope, cos, sin, cfg.rope_interleave)
    k_rope = llama.apply_rope(
        k_rope[:, :, None, :], cos, sin, cfg.rope_interleave)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, nh, rope))], axis=-1)
    return flash_attention(q, k, v)


def parts(cfg, c_q, c_kv, k_rope, p, cos, sin):
    """The parts as their products make them. The one key comes in the
    source's column order here, as ``whole`` takes it: the model takes
    that order on ``wkv_a``, this on 4 MB of activations."""
    q, k, v, q_rope, k_rope = llama._latent_up(
        cfg, c_q, c_kv, llama._evens_then_odds(k_rope), p, cos, sin)
    return flash_attention(q, k, v, q_rope=q_rope, k_rope=k_rope)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10)
    ap.add_argument("--out", default="chiprun_out/latent_attention.jsonl")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: a kernel's time is read on the chip")
    s, cfg = SHAPE, _config()
    keys = jax.random.split(jax.random.key(0), 6)
    tokens = (s["batch"], s["seq"])

    def draw(key, *shape, scale=1.0):
        return (scale * jax.random.normal(key, shape)).astype(jnp.bfloat16)

    c_q = draw(keys[0], *tokens, s["q_rank"])
    c_kv = draw(keys[1], *tokens, s["kv_rank"])
    k_rope = draw(keys[2], *tokens, s["rope"])
    do = draw(keys[3], *tokens, s["heads"], s["v"])
    p = {
        "wq_b": draw(keys[4], s["q_rank"], s["heads"] * (s["nope"] + s["rope"]),
                     scale=s["q_rank"] ** -0.5),
        "wkv_b": draw(keys[5], s["kv_rank"], s["heads"] * (s["nope"] + s["v"]),
                      scale=s["kv_rank"] ** -0.5),
    }
    cos, sin = llama.rope_tables(s["seq"], s["rope"], cfg.rope_theta)

    got = {}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    for form in (whole, parts):
        jax.clear_caches()  # or the first form's trace answers again

        def attend(c_q, c_kv, k_rope, p, form=form):
            return form(cfg, c_q, c_kv, k_rope, p, cos, sin)

        def grads(c_q, c_kv, k_rope, p, attend=attend):
            return jax.grad(
                lambda *operands: jnp.sum(
                    attend(*operands).astype(jnp.float32)
                    * do.astype(jnp.float32)),
                argnums=(0, 1, 2, 3))(c_q, c_kv, k_rope, p)

        forward, backward = jax.jit(attend), jax.jit(grads)
        operands = (c_q, c_kv, k_rope, p)
        row = {
            "form": form.__name__, **s,
            "forward_ms": 1e3 * timeit(forward, *operands, n=args.n),
            "forward_backward_ms": 1e3 * timeit(
                backward, *operands, n=args.n),
            "selection": tuning.last_selection(),
        }
        got[form.__name__] = jax.tree.leaves(
            (forward(*operands), backward(*operands)))
        if form is parts:
            # o, d c_q, d c_kv, d k_rope, d wkv_b, d wq_b (the tree's
            # order), each beside the largest magnitude of the whole's
            row["max_abs_difference_from_the_whole"] = [
                [float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32)))),
                 float(jnp.max(jnp.abs(b.astype(jnp.float32))))]
                for a, b in zip(got["parts"], got["whole"])]
        line = json.dumps(row)
        print(line, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
