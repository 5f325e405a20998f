"""On the chip: the attention call of ``joyai-llm-flash-ep8.steady``
([4, 8192, 32 heads], q and k 192 wide, v 128, causal, no group),
forward and backward, with the backward as the dq and dk/dv pair and
as the one kernel that keeps the head's float32 dQ (6.3 MB) in VMEM
and states what it takes. One JSON line a form: ms a call (forward
alone; forward and the gradients), and how far the one kernel's
gradients lie from the pair's.

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

from dlrover_tpu.ops import tuning  # noqa: E402
from dlrover_tpu.ops.pallas import flash_attention as fa  # noqa: E402

SHAPE = dict(batch=4, seq=8192, heads=32, qk=192, v=128)


def timeit(fn, *args, n=10, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10)
    ap.add_argument("--out", default="chiprun_out/latent_attention.jsonl")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: a kernel's time is read on the chip")
    s = SHAPE
    keys = jax.random.split(jax.random.key(0), 4)
    q, k = (
        jax.random.normal(
            key, (s["batch"], s["seq"], s["heads"], s["qk"]), jnp.bfloat16)
        for key in keys[:2])
    v, do = (
        jax.random.normal(
            key, (s["batch"], s["seq"], s["heads"], s["v"]), jnp.bfloat16)
        for key in keys[2:])
    bq, bk = tuning.heuristic_blocks(s["seq"], 1)

    def attn(q, k, v):
        return fa.flash_attention_tpu(
            q, k, v, causal=True, block_q=bq, block_k=bk)

    def grads(q, k, v):
        return jax.grad(
            lambda q, k, v: jnp.sum(
                attn(q, k, v).astype(jnp.float32) * do.astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    rule, got = fa._one_backward_kernel, {}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    for form, one in (("pair", False), ("dq_resident", True)):
        fa._one_backward_kernel = lambda g, seq, d: one
        jax.clear_caches()  # or the first form's trace answers again
        try:
            forward, backward = jax.jit(attn), jax.jit(grads)
            row = {
                "form": form, **s, "block_q": bq, "block_k": bk,
                "forward_ms": 1e3 * timeit(forward, q, k, v, n=args.n),
                "forward_backward_ms": 1e3 * timeit(
                    backward, q, k, v, n=args.n),
            }
            got[form] = backward(q, k, v)
        finally:
            fa._one_backward_kernel = rule
        if form != "pair":
            row["max_abs_difference_from_the_pair"] = [
                float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(got[form], got["pair"])]
        line = json.dumps(row)
        print(line, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
