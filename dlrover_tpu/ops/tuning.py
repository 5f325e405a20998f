"""Persistent on-device autotuner for Pallas kernel block sizes.

The flash-attention kernel's throughput swings with ``(block_q,
block_k)`` per shape (benchmarks/profile_attn.py measures the spread),
but the hot path used to pick blocks with a static largest-power-of-two
heuristic. This module closes the loop: on the first call for a key
``(kernel, seq, head_dim, gqa_group, dtype, causal, device_kind)`` it
times a small candidate grid ON THE DEVICE, picks the winner, and
persists it as JSON in a host-local tuning cache co-located with the
persistent XLA compile cache (trainer/compile_cache.py) — so a
restarted worker, the common elastic-failover case, reads its blocks
from disk and never re-tunes. Same warm-restart economics as the
compile cache: pay once per host, not once per incarnation.

Selection ladder:
 - non-TPU backend, tuning disabled, or no valid candidates: the
   static heuristic answer, ZERO timing runs;
 - cache hit (memory, then disk): the persisted winner, zero timing;
 - cache miss on TPU: measure, persist best-effort, return winner; a
   sweep in which no candidate could be timed is an error, and the
   heuristic is never stored under a key that says "measured".

``get_blocks`` is reached while the caller's jit traces the Python
body of ``flash_attention``. The sweep therefore runs on a thread of
its own: a trace belongs to the thread that started it, so there the
inputs are concrete arrays and the jitted calls execute on the device.
(``jax.ensure_compile_time_eval`` does not do: it also folds the
kernel body's ``program_id``, which has no value outside a kernel.)

Layout: one JSON file per key under ``<compile cache dir>/tuning``
(common/cachedir.py: ``JAX_COMPILATION_CACHE_DIR`` where set, else the
fixed directory in the checkout). ``benchmarks/profile_attn.py
--write-cache`` pre-populates it offline.
"""

import dataclasses
import hashlib
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

from dlrover_tpu.common.cachedir import ensure_private_dir, resolve_cache_dir
from dlrover_tpu.common.log import default_logger as logger

#: "off" disables on-device measurement (heuristic-only, e.g. CI)
ENV_TUNING = "DLROVER_TPU_ATTN_TUNING"

_SCHEMA_VERSION = 1

# s/p are [group*block_q, block_k] fp32 in VMEM; cap rows x block_k so
# the block pair stays inside the ~16MB VMEM budget alongside the rest
# of a fused train step (1024 rows x 1024 cols measured fastest
# in-model on v5e: 50.2% MFU vs 48.5% for the best
# per-query-head-grid config)
ROWS_CAP = 1024
_POW2 = (128, 256, 512, 1024)


# --------------------------------------------------------------------------
# keys and records


@dataclasses.dataclass(frozen=True)
class TuningKey:
    """Identity of one tuning decision. Everything that changes the
    kernel's performance landscape is in the key; batch size is NOT
    (the TPU grid runs blocks sequentially, so block ranking is
    batch-stable and one entry serves every batch of the shape)."""

    kernel: str
    seq: int
    head_dim: int
    gqa_group: int
    dtype: str
    causal: bool
    device_kind: str

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict) -> "TuningKey":
        return cls(
            kernel=str(d["kernel"]),
            seq=int(d["seq"]),
            head_dim=int(d["head_dim"]),
            gqa_group=int(d["gqa_group"]),
            dtype=str(d["dtype"]),
            causal=bool(d["causal"]),
            device_kind=str(d["device_kind"]),
        )

    def filename(self) -> str:
        """Stable, filesystem-safe name: readable prefix + hash of the
        exact key (device_kind strings contain spaces/slashes)."""
        tag = (
            f"{self.kernel}-s{self.seq}-d{self.head_dim}"
            f"-g{self.gqa_group}-{self.dtype}"
            f"-{'c' if self.causal else 'nc'}"
        )
        h = hashlib.sha256(
            json.dumps(self.to_dict(), sort_keys=True).encode()
        ).hexdigest()[:16]
        return f"{tag}-{h}.json"


# --------------------------------------------------------------------------
# the static heuristic (the prior, and the no-measure fallback)


def block_caps(
    group: int,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> Tuple[int, int]:
    """VMEM-safety caps on (block_q, block_k) for a GQA group size,
    honoring the caller's explicit caps. For high GQA ratios (g > 8,
    where even the 128-row-minimum q block overshoots ROWS_CAP)
    block_k shrinks to keep the fp32 s/p blocks' rows*cols footprint
    constant."""
    rows_min = 128 * group
    bq_cap = min(block_q or ROWS_CAP, max(ROWS_CAP // group, 128))
    bk_cap = min(
        block_k or 1024,
        max(128, ROWS_CAP * 1024 // max(rows_min, ROWS_CAP)),
    )
    return bq_cap, bk_cap


def candidate_blocks(
    seq: int,
    group: int,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> Tuple[List[int], List[int]]:
    """Power-of-two blocks that tile ``seq`` within the VMEM caps
    (the kernel's causal mask requires power-of-two block_q)."""
    bq_cap, bk_cap = block_caps(group, block_q, block_k)
    bq = [b for b in _POW2 if seq % b == 0 and b <= bq_cap]
    bk = [b for b in _POW2 if seq % b == 0 and b <= bk_cap]
    return bq, bk


def heuristic_blocks(
    seq: int,
    group: int,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> Optional[Tuple[int, int]]:
    """The pre-autotuner static choice: largest valid block pair.
    None when nothing tiles ``seq`` under the caps (the caller falls
    back to the XLA path)."""
    bqs, bks = candidate_blocks(seq, group, block_q, block_k)
    if not bqs or not bks:
        return None
    return max(bqs), max(bks)


def candidate_grid(
    seq: int,
    group: int,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> List[Tuple[int, int]]:
    """The measured candidate set: the cross product of valid blocks,
    heuristic-first (so a truncated/failed sweep still contains the
    prior)."""
    bqs, bks = candidate_blocks(seq, group, block_q, block_k)
    prior = heuristic_blocks(seq, group, block_q, block_k)
    grid = [
        (q, k) for q in sorted(bqs, reverse=True)
        for k in sorted(bks, reverse=True)
    ]
    if prior is not None and prior in grid:
        grid.remove(prior)
        grid.insert(0, prior)
    return grid


# --------------------------------------------------------------------------
# measurement (promoted from benchmarks/profile_attn.py)


def timeit(fn: Callable, *args, n: int = 10, warmup: int = 2) -> float:
    """Mean wall-clock seconds per call."""
    import jax

    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def measure_candidates(
    key: TuningKey,
    candidates: List[Tuple[int, int]],
    n: int = 10,
    warmup: int = 2,
) -> List[Tuple[int, int, float]]:
    """Time each (block_q, block_k) pair on the device with the
    training-shaped work (fwd+bwd — selection must optimize the step,
    not just inference). Returns (bq, bk, seconds) per surviving
    candidate; a candidate that fails to compile (e.g. VMEM overflow
    on an untried device generation) is skipped, and when none
    survives the last failure is raised.

    Safe to call from inside a trace: the sweep runs on a thread of
    its own, and a trace belongs to the thread that started it."""
    import jax

    try:
        with ThreadPoolExecutor(1, "attn-tuning") as pool:
            return pool.submit(
                _measure_candidates, key, candidates, n, warmup
            ).result()
    finally:
        # the sweep traced the kernel from ITS call stack, and jax
        # keeps that trace, source locations included, for the step
        # that follows; the locations end up in the kernel's payload
        # and so in the compile cache's key. Dropped, the step of the
        # worker that tuned is the program every later worker (which
        # reads its blocks from the cache) asks the compile cache for.
        jax.clear_caches()


def _measure_candidates(key, candidates, n, warmup):
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.ops.pallas.flash_attention import (
        flash_attention_tpu,
    )

    rng = np.random.default_rng(0)
    dtype = jnp.dtype(key.dtype)
    # one KV head with the key's group folded in reproduces the
    # kernel's per-block work exactly; the grid's batch dim only
    # repeats it
    q = jnp.asarray(
        rng.standard_normal((1, key.seq, key.gqa_group, key.head_dim)),
        dtype,
    )
    k = jnp.asarray(
        rng.standard_normal((1, key.seq, 1, key.head_dim)), dtype
    )
    v = jnp.asarray(
        rng.standard_normal((1, key.seq, 1, key.head_dim)), dtype
    )

    results, last_error = [], None
    for bq, bk in candidates:
        attn = partial(
            flash_attention_tpu, causal=key.causal, block_q=bq,
            block_k=bk,
        )
        fn = jax.jit(jax.value_and_grad(
            lambda q, k, v: attn(q, k, v)
            .astype(jnp.float32).mean(), argnums=(0, 1, 2),
        ))
        try:
            t = timeit(fn, q, k, v, n=n, warmup=warmup)
        except Exception as e:
            logger.warning(
                "tuning candidate bq=%d bk=%d failed (%s); skipped",
                bq, bk, e,
            )
            last_error = e
            continue
        results.append((bq, bk, t))
    if not results:
        raise RuntimeError(
            f"tuning sweep for {key} timed none of {candidates}"
        ) from last_error
    return results


# --------------------------------------------------------------------------
# persistence


class TuningCache:
    """One JSON file per key under a uid-private dir; an in-memory map
    in front so a key is read (or measured) at most once per process.
    ``path=None`` = memory-only (persistence disabled or dir
    untrusted)."""

    def __init__(self, path: Optional[str]):
        self.path = path
        self._mem: Dict[TuningKey, Tuple[int, int]] = {}

    def _file(self, key: TuningKey) -> Optional[str]:
        if self.path is None:
            return None
        return os.path.join(self.path, key.filename())

    def lookup(self, key: TuningKey) -> Optional[Tuple[int, int]]:
        if key in self._mem:
            return self._mem[key]
        f = self._file(key)
        if f is None or not os.path.exists(f):
            return None
        try:
            with open(f, "r") as fh:
                rec = json.load(fh)
            if rec.get("version") != _SCHEMA_VERSION:
                raise ValueError(f"schema {rec.get('version')}")
            if TuningKey.from_dict(rec["key"]) != key:
                raise ValueError("key mismatch (stale entry)")
            bq, bk = int(rec["block_q"]), int(rec["block_k"])
            if key.seq % bq or key.seq % bk or bq & (bq - 1):
                raise ValueError(f"invalid blocks ({bq}, {bk})")
        except Exception as e:
            # corrupt/stale entries are a MISS, never an error: the
            # caller falls back to heuristic or re-measures
            logger.warning("ignoring bad tuning entry %s: %s", f, e)
            return None
        self._mem[key] = (bq, bk)
        return bq, bk

    def store(
        self,
        key: TuningKey,
        blocks: Tuple[int, int],
        measured_ms: Optional[float] = None,
    ) -> None:
        self._mem[key] = tuple(blocks)
        f = self._file(key)
        if f is None:
            return
        rec = {
            "version": _SCHEMA_VERSION,
            "key": key.to_dict(),
            "block_q": int(blocks[0]),
            "block_k": int(blocks[1]),
            "measured_ms": measured_ms,
            "timestamp": time.time(),
        }
        try:
            tmp = f + f".tmp.{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(rec, fh, indent=1)
            os.replace(tmp, f)  # atomic vs concurrent workers
        except OSError as e:
            logger.warning("tuning cache write failed (%s); in-memory "
                           "only", e)

    def entries(self) -> int:
        """Persisted entry count (observability helper)."""
        if self.path is None:
            return 0
        try:
            return sum(
                1 for n in os.listdir(self.path)
                if n.endswith(".json")
            )
        except FileNotFoundError:
            return 0


_caches: Dict[str, TuningCache] = {}


def get_cache(cache_dir: Optional[str] = None) -> TuningCache:
    """Resolve (and memoize per-dir) the tuning cache: explicit arg,
    else ``tuning/`` inside the compile cache directory
    (common/cachedir.py). An untrusted dir degrades to memory-only."""
    if cache_dir is None:
        root = resolve_cache_dir()
        cache_dir = os.path.join(root, "tuning") if root else ""
    if cache_dir not in _caches:
        path = ensure_private_dir(cache_dir) if cache_dir else None
        _caches[cache_dir] = TuningCache(path)
    return _caches[cache_dir]


def reset_cache_memo() -> None:
    """Drop per-process cache handles (tests; env changes)."""
    _caches.clear()


# --------------------------------------------------------------------------
# selection


_last_selection: Optional[Dict] = None


def last_selection() -> Optional[Dict]:
    """The most recent block decision (bench/observability): dict with
    kernel/seq/head_dim/gqa_group/dtype/causal/block_q/block_k/source,
    or None if no Pallas dispatch has happened."""
    return _last_selection


def _measurement_enabled() -> bool:
    import jax

    if os.getenv(ENV_TUNING, "").strip().lower() in ("off", "none", "0"):
        return False
    # interpret mode / CPU / GPU: timings are meaningless (and the
    # contract is ZERO timing runs off-TPU)
    return jax.default_backend() == "tpu"


def _record(key: TuningKey, blocks: Tuple[int, int], source: str,
            elapsed_s: float = 0.0) -> None:
    global _last_selection
    sel = dict(key.to_dict(), block_q=blocks[0], block_k=blocks[1],
               source=source)
    _last_selection = sel
    try:  # tuning telemetry must never take the hot path down
        from dlrover_tpu.telemetry import counter, histogram
        from dlrover_tpu.trainer import profiler

        profiler.record_tuning_event(
            **sel, tuning_seconds=round(elapsed_s, 3)
        )
        counter(
            "dlrover_tuning_decisions_total",
            "Kernel block-size decisions by provenance", ["source"],
        ).labels(source=source).inc()
        if source == "measured":
            histogram(
                "dlrover_tuning_sweep_seconds",
                "On-device candidate-sweep wall time",
                buckets=(0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0),
            ).observe(elapsed_s)
    except Exception:
        pass


def get_blocks(
    seq: int,
    head_dim: int,
    group: int,
    dtype: str,
    causal: bool,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    kernel: str = "flash_attention",
    cache_dir: Optional[str] = None,
) -> Optional[Tuple[int, int]]:
    """The (block_q, block_k) to run ``kernel`` with: persisted winner
    if known, measured winner on first TPU encounter, static heuristic
    off the TPU. None = no valid blocks. Raises when the sweep times
    nothing. ``block_q``/``block_k`` are the caller's caps and join the
    candidate filter, not the key (an explicit cap is a debugging
    override, not a new shape)."""
    prior = heuristic_blocks(seq, group, block_q, block_k)
    if prior is None:
        return None
    if not _measurement_enabled():
        # no key lookup either: off-TPU the heuristic IS the contract
        # (bitwise-identical to the pre-tuning path, zero timing runs)
        return prior

    import jax

    key = TuningKey(
        kernel=kernel,
        seq=seq,
        head_dim=head_dim,
        gqa_group=group,
        dtype=str(dtype),
        causal=causal,
        device_kind=getattr(
            jax.devices()[0], "device_kind", jax.default_backend()
        ),
    )
    cache = get_cache(cache_dir)
    hit = cache.lookup(key)
    if hit is not None:
        _record(key, hit, "cache")
        return hit

    t0 = time.perf_counter()
    results = measure_candidates(
        key, candidate_grid(seq, group, block_q, block_k)
    )
    elapsed = time.perf_counter() - t0
    bq, bk, t = min(results, key=lambda r: r[2])
    logger.info(
        "tuned %s -> block_q=%d block_k=%d (%.2f ms; %d candidates in "
        "%.1fs)", key, bq, bk, t * 1e3, len(results), elapsed,
    )
    cache.store(key, (bq, bk), measured_ms=t * 1e3)
    _record(key, (bq, bk), "measured", elapsed)
    return bq, bk
