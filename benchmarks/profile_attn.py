"""Micro-bench flash attention block sizes on model shapes (dev tool).

The measurement loop itself now lives in the library
(dlrover_tpu/ops/tuning.py — the persistent autotuner uses it on the
hot path); this script remains the offline driver: sweep a block grid
on a real shape, print the table, and with ``--write-cache`` persist
each swept shape's winner into the host-local tuning cache so workers
starting later on this host skip tuning entirely
(docs/TUNING_CACHE.md).
"""

import argparse
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.ops import tuning
from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.ops.pallas.flash_attention import (
    flash_attention_tpu as flash_attention,
)

timeit = tuning.timeit


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument(
        "--write-cache", action="store_true",
        help="persist the measured winner for this shape into the "
        "tuning cache (ops/tuning.py), pre-populating it for every "
        "later worker on this host",
    )
    ap.add_argument(
        "--cache-dir", default=None,
        help="tuning cache dir (default: tuning/ below the compile "
        "cache dir, common/cachedir.py)",
    )
    args = ap.parse_args(argv)

    batch, seq = args.batch, args.seq
    nh, nkv, d = args.heads, args.kv_heads, args.head_dim
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((batch, seq, nh, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((batch, seq, nkv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((batch, seq, nkv, d)), jnp.bfloat16)

    group = nh // nkv
    grid = tuning.candidate_grid(seq, group)
    # causal attention flops (fwd): 2 matmuls, half the blocks
    fwd_flops = 4 * batch * nh * seq * seq * d / 2
    best = None  # (t_b, bq, bk)
    for bq, bk in grid:
        fn_f = jax.jit(partial(
            flash_attention, causal=True, block_q=bq, block_k=bk))
        t_f = timeit(fn_f, q, k, v, n=20, warmup=3)
        fn_b = jax.jit(jax.value_and_grad(
            lambda q, k, v: partial(
                flash_attention, causal=True, block_q=bq, block_k=bk
            )(q, k, v).astype(jnp.float32).mean(), argnums=(0, 1, 2)))
        t_b = timeit(fn_b, q, k, v, n=20, warmup=3)
        if best is None or t_b < best[0]:
            best = (t_b, bq, bk)
        print(f"bq={bq:5d} bk={bk:5d}: fwd {t_f*1e3:6.2f} ms "
              f"({fwd_flops/t_f/1e12:5.1f} TF/s)  fwd+bwd {t_b*1e3:6.2f} ms"
              f"  (x22: fwd {t_f*22*1e3:5.1f} / fb {t_b*22*1e3:6.1f})")

    fn_f = jax.jit(partial(mha_reference, causal=True))
    t_f = timeit(fn_f, q, k, v, n=20, warmup=3)
    fn_b = jax.jit(jax.value_and_grad(
        lambda q, k, v: mha_reference(q, k, v, causal=True)
        .astype(jnp.float32).mean(), argnums=(0, 1, 2)))
    t_b = timeit(fn_b, q, k, v, n=20, warmup=3)
    print(f"mha_reference : fwd {t_f*1e3:6.2f} ms  fwd+bwd {t_b*1e3:6.2f} "
          f"ms  (x22: fwd {t_f*22*1e3:5.1f} / fb {t_b*22*1e3:6.1f})")

    if args.write_cache and best is not None:
        t_best, bq, bk = best
        dev = jax.devices()[0]
        key = tuning.TuningKey(
            kernel="flash_attention",
            seq=seq,
            head_dim=d,
            gqa_group=group,
            dtype=jnp.dtype(q.dtype).name,
            causal=True,
            device_kind=getattr(
                dev, "device_kind", dev.platform
            ),
        )
        cache = tuning.get_cache(args.cache_dir)
        if cache.path is None:
            print("tuning cache persistence disabled; nothing written",
                  file=sys.stderr)
            return 1
        cache.store(key, (bq, bk), measured_ms=t_best * 1e3)
        print(f"wrote {key.filename()} -> bq={bq} bk={bk} "
              f"({cache.path})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
