"""Layout-free restore: assemble any topology's save onto any mesh.

The v2 loader never assumes the restoring world matches the saving
world. It reads a :class:`StepCatalog` (global domain maps + member
locations, built from a step manifest or any host archive's manifest),
computes the index domains the CURRENT process's target shardings
need, and fills each one from whichever saved domains overlap it —
fetched through a tiered source chain:

    local archive  ->  peer /ckpt/shard (survivors' RAM tier)  ->  store

Every fetched member is sha256-verified against the catalog before it
is trusted; a mismatch journals ``checkpoint.restore_fallback{reason=
digest_mismatch}`` + ``ckpt.shard_refetch`` and tries the NEXT tier
for that one shard — the candidate step only fails (and the caller
walks down) when no tier can produce a clean copy
(:class:`ShardUnavailableError`). Assembled domains land on devices
via ``jax.device_put`` + ``jax.make_array_from_single_device_arrays``
onto the target ``NamedSharding`` — the SNIPPETS.md [2] pattern — so a
pp×tp save restores under dp, and across a world resize, unchanged.
"""

import hashlib
import io
import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from dlrover_tpu.checkpoint import manifest as mf
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry import counter, record, tracing
from dlrover_tpu.trainer import ckpt_store

__all__ = [
    "StepCatalog",
    "ShardUnavailableError",
    "LocalArchiveSource",
    "PeerSource",
    "StoreSource",
    "restore_from_catalog",
]


class ShardUnavailableError(ckpt_store.ArchiveError):
    """No tier could produce a clean copy of a needed shard: the
    candidate step is not restorable and the caller walks down."""


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # noqa: F401  (registers extension dtypes)

        return np.dtype(name)


def _nbytes(raw) -> int:
    """Size of what a source handed back: member bytes, or an array
    from an in-process source."""
    if isinstance(raw, (bytes, bytearray, memoryview)):
        return len(raw)
    return int(getattr(raw, "nbytes", 0))


def _decode_member(raw: bytes, enc: Optional[Dict[str, Any]]) -> np.ndarray:
    """Member bytes -> array (same decode the whole-archive reader
    applies: extension dtypes travel as uint8 + a recorded dtype/shape)."""
    try:
        arr = np.lib.format.read_array(
            io.BytesIO(raw), allow_pickle=False
        )
    except Exception as e:
        raise ckpt_store.ArchiveError(f"undecodable shard member: {e}")
    if enc:
        try:
            arr = np.frombuffer(
                arr.tobytes(), dtype=_np_dtype(enc["dtype"])
            ).reshape(enc["shape"])
        except (TypeError, ValueError, ImportError) as e:
            raise ckpt_store.ArchiveError(
                f"shard member inconsistent with its encoding: {e}"
            )
    return arr


# ------------------------------------------------------------------ catalog


class StepCatalog:
    """Everything restore planning needs about one saved step: the
    logical leaves with their GLOBAL domain maps, plus per-shard
    digests/encodings and (when known) which process file + member
    holds each shard."""

    def __init__(self, step: int, leaves: List[Dict[str, Any]],
                 topology: Optional[Dict[str, Any]] = None,
                 last_good: Optional[bool] = None):
        self.step = int(step)
        self.leaves = leaves
        self.topology = topology
        self.last_good = last_good
        self.digests: Dict[str, str] = {}
        self.encodings: Dict[str, Dict[str, Any]] = {}
        self.locations: Dict[str, Tuple[int, str]] = {}

    @classmethod
    def from_archive_manifest(cls, man: Dict[str, Any]) -> "StepCatalog":
        """Catalog from ONE host archive's manifest (RAM-tier file or
        a peer's served manifest). The global domain maps in a v2
        manifest are complete on every host; member locations cover
        only what that host's file holds — merge more manifests with
        :meth:`absorb` to widen them."""
        leaves = [mf._leaf_meta(e) for e in man.get("leaves", [])]
        cat = cls(
            man.get("step", 0), leaves,
            topology=man.get("topology"),
            last_good=man.get("last_good"),
        )
        cat.absorb(man)
        return cat

    def absorb(self, man: Dict[str, Any]) -> None:
        """Fold another host's archive manifest into the location /
        digest tables (first writer wins; replicas are bit-identical
        so any recorded sha verifies any copy)."""
        for key, loc in mf._piece_locations(man).items():
            self.locations.setdefault(key, (loc["proc"], loc["m"]))
            if loc.get("sha256"):
                self.digests.setdefault(key, loc["sha256"])
            if loc.get("enc"):
                self.encodings.setdefault(key, loc["enc"])

    @classmethod
    def from_step_manifest(cls, doc: Dict[str, Any]) -> "StepCatalog":
        """Catalog from the merged step manifest rank 0 published next
        to the COMMIT marker (ckpt_store.step_manifest)."""
        cat = cls(
            doc.get("step", 0), list(doc.get("leaves", [])),
            topology=doc.get("topology"),
            last_good=doc.get("last_good"),
        )
        for key, loc in (doc.get("locations") or {}).items():
            cat.locations[key] = (int(loc["proc"]), loc["m"])
            if loc.get("sha256"):
                cat.digests[key] = loc["sha256"]
            if loc.get("enc"):
                cat.encodings[key] = loc["enc"]
        return cat

    def attempt(self) -> str:
        return "0"


# ------------------------------------------------------------------ sources


class LocalArchiveSource:
    """This host's own archive (the RAM-tier file): serves every
    member it physically contains, addressed by shard key."""

    tier = "local"

    def __init__(self, fileobj):
        self._zf = None
        self._members: Dict[str, Tuple[str, Optional[dict]]] = {}
        import zipfile

        try:
            self._zf = zipfile.ZipFile(fileobj)
            man = json.loads(
                self._zf.read("manifest.json").decode("utf-8")
            )
            for key, loc in mf._piece_locations(man).items():
                self._members[key] = (loc["m"], loc.get("enc"))
        except Exception as e:
            raise ckpt_store.ArchiveError(f"unreadable local archive: {e}")

    def fetch(self, pkey: str, ikey: str, procs) -> Optional[bytes]:
        ref = self._members.get(mf.joined_key(pkey, ikey))
        if ref is None:
            return None
        try:
            return self._zf.read(ref[0])
        except Exception:
            return None

    def enc_for(self, key: str) -> Optional[dict]:
        ref = self._members.get(key)
        return ref[1] if ref else None

    def close(self) -> None:
        if self._zf is not None:
            self._zf.close()


class PeerSource:
    """Survivors' RAM tier over HTTP: tries each replica process that
    advertised this step (master KV) until one serves the shard."""

    tier = "peer"

    def __init__(self, peers: Dict[int, str], step: int,
                 process_index: Optional[int] = None,
                 timeout: float = 10.0):
        self._peers = dict(peers)
        self._step = int(step)
        self._me = process_index
        self._timeout = timeout

    def fetch(self, pkey: str, ikey: str, procs) -> Optional[bytes]:
        from dlrover_tpu.checkpoint import peer as peer_mod

        candidates = [
            p for p in (procs or sorted(self._peers))
            if p in self._peers and p != self._me
        ]
        # replicas first, then any advertised survivor — a resized
        # world's proc numbering must not hide a peer that holds it
        for p in sorted(self._peers):
            if p not in candidates and p != self._me:
                candidates.append(p)
        for p in candidates:
            try:
                raw = peer_mod.fetch_shard(
                    self._peers[p], self._step, pkey, ikey,
                    timeout=self._timeout,
                )
            except Exception as e:
                _count_peer_fetch("error")
                logger.warning(
                    "peer shard fetch from proc %s failed: %s", p, e
                )
                continue
            if raw is None:
                _count_peer_fetch("miss")
                continue
            return raw
        return None


class StoreSource:
    """The object store's process files for a committed step, read
    member-at-a-time through the location table (never a whole-archive
    download per shard)."""

    tier = "store"

    def __init__(self, store, step: int, attempt: str,
                 locations: Dict[str, Tuple[int, str]]):
        self._store = store
        self._step = int(step)
        self._attempt = attempt
        self._locations = locations
        self._files: Dict[int, Any] = {}

    def _zip_for(self, proc: int):
        import zipfile

        if proc not in self._files:
            f = self._store.open_read(
                ckpt_store.step_key(self._step, proc, self._attempt)
            )
            self._files[proc] = zipfile.ZipFile(f)
        return self._files[proc]

    def fetch(self, pkey: str, ikey: str, procs) -> Optional[bytes]:
        loc = self._locations.get(mf.joined_key(pkey, ikey))
        if loc is None:
            return None
        proc, member = loc
        try:
            return self._zip_for(proc).read(member)
        except KeyError:
            return None

    def close(self) -> None:
        for zf in self._files.values():
            try:
                zf.close()
            except Exception:
                pass


def _count_peer_fetch(result: str) -> None:
    counter(
        "dlrover_ckpt_peer_fetches_total",
        "Peer-tier shard fetches by outcome", ["result"],
    ).labels(result=result).inc()


# ------------------------------------------------------------------ restore


class _Fetcher:
    """One restore's shard access: tiered fetch + digest verify +
    per-shard memo (overlapping needed domains reuse a fetched
    member instead of re-pulling it)."""

    def __init__(self, catalog: StepCatalog, sources: List[Any]):
        self.catalog = catalog
        self.sources = [s for s in sources if s is not None]
        self.cache: Dict[str, np.ndarray] = {}
        self.stats = {
            "local": 0, "peer": 0, "store": 0, "live": 0,
            "digest_mismatch": 0, "bytes": 0,
        }

    def get(self, pkey: str, ikey: str, procs) -> np.ndarray:
        key = mf.joined_key(pkey, ikey)
        if key in self.cache:
            return self.cache[key]
        want = self.catalog.digests.get(key)
        enc = self.catalog.encodings.get(key)
        tried: List[str] = []
        for i, src in enumerate(self.sources):
            size = {"tier": src.tier}
            try:
                with tracing.span("ckpt.restore.fetch", size):
                    raw = src.fetch(pkey, ikey, procs)
                    size["bytes"] = _nbytes(raw)
            except Exception as e:
                logger.warning(
                    "%s-tier shard fetch failed: %s", src.tier, e
                )
                raw = None
            if raw is None:
                tried.append(src.tier)
                continue
            if not isinstance(raw, (bytes, bytearray, memoryview)):
                # an in-process source (the live tier) handed back the
                # array itself: the bytes never left this trust domain
                # and never round-tripped through npz, so there is
                # nothing to decode or digest-verify — downstream
                # device_put moves it device-to-device
                self.stats[src.tier] = self.stats.get(src.tier, 0) + 1
                self.stats["bytes"] += size["bytes"]
                self.cache[key] = raw
                return raw
            got = want
            if want is not None:
                with tracing.span("ckpt.restore.digest", size):
                    got = hashlib.sha256(raw).hexdigest()
            if got != want:
                # the PR 9 walk-down contract, extended per shard:
                # journal the mismatch, then RE-FETCH this one shard
                # from the next tier before giving up on the step
                self.stats["digest_mismatch"] += 1
                if src.tier == "peer":
                    _count_peer_fetch("digest_mismatch")
                record(
                    "checkpoint.restore_fallback",
                    step=self.catalog.step,
                    requested_step=self.catalog.step,
                    reason="digest_mismatch", tier=src.tier,
                    shard=key[:160],
                )
                record(
                    "ckpt.shard_refetch", step=self.catalog.step,
                    shard=key[:160], failed_tier=src.tier,
                    next_tiers=[s.tier for s in self.sources[i + 1:]],
                )
                tried.append(src.tier)
                continue
            if enc is None and hasattr(src, "enc_for"):
                enc = src.enc_for(key)
            with tracing.span("ckpt.restore.decode", size):
                arr = _decode_member(raw, enc)
            self.stats[src.tier] += 1
            self.stats["bytes"] += len(raw)
            if src.tier == "peer":
                _count_peer_fetch("ok")
                record(
                    "ckpt.peer_fetch", step=self.catalog.step,
                    shard=key[:160], result="ok", bytes=len(raw),
                )
            self.cache[key] = arr
            return arr
        raise ShardUnavailableError(
            f"step {self.catalog.step}: shard {key[:160]!r} "
            f"unavailable from every tier (tried {tried})"
        )


def _gather_domain(fetcher: _Fetcher, leaf: Dict[str, Any],
                   pkey: str, nidx: List[List[int]]) -> np.ndarray:
    """One needed domain of one logical array, from whatever saved
    domains cover it (exact hit = a single member fetch; otherwise
    assembled from every overlapping saved shard)."""
    ikey = mf.index_key(nidx)
    domains = leaf.get("domains") or []
    by_key = {mf.index_key(d["idx"]): d for d in domains}
    if ikey in by_key:
        arr = fetcher.get(pkey, ikey, by_key[ikey].get("replicas"))
        return arr.reshape(mf.domain_shape(nidx))
    dtype = _np_dtype(leaf["dtype"])
    out = np.empty(mf.domain_shape(nidx), dtype=dtype)
    covered = 0
    # the saved layout is not the needed one: the fetches are this
    # span's children, the copies into place its own time
    with tracing.span("ckpt.restore.assemble", {"bytes": out.nbytes}):
        for d in domains:
            ov = mf.overlap(d["idx"], nidx)
            if ov is None:
                continue
            src = fetcher.get(
                pkey, mf.index_key(d["idx"]), d.get("replicas")
            ).reshape(mf.domain_shape(d["idx"]))
            dst_sl = tuple(
                slice(s - n[0], e - n[0]) for (s, e), n in zip(ov, nidx)
            )
            src_sl = tuple(
                slice(s - o[0], e - o[0])
                for (s, e), o in zip(ov, d["idx"])
            )
            out[dst_sl] = src[src_sl]
            covered += mf.domain_volume(ov)
    if covered != mf.domain_volume(nidx):
        raise ShardUnavailableError(
            f"step {fetcher.catalog.step}: domain {nidx} of "
            f"{pkey[:120]} only {covered}/{mf.domain_volume(nidx)} "
            "covered by the saved domains"
        )
    return out


def _full_domain(shape) -> List[List[int]]:
    return [[0, int(n)] for n in shape]


def _leaf_value(fetcher: _Fetcher, leaf: Dict[str, Any],
                target=None):
    """Restore one logical leaf onto its target (or to host values
    when no target): py leaves come from the manifest, 'array' leaves
    from their owner's member, 'shards' leaves are planned per needed
    domain and landed onto the target sharding."""
    import jax

    from dlrover_tpu.trainer.checkpoint import target_sharding

    sharding = target_sharding(target)
    pkey = mf.path_key(leaf["path"])
    kind = leaf.get("kind")

    def put(arr, where):
        # the enqueue: the copy itself completes later
        with tracing.span(
            "ckpt.restore.device_put", {"bytes": _nbytes(arr)}
        ):
            return jax.device_put(arr, where)

    if kind == "py":
        return leaf.get("v")
    if kind == "array":
        arr = fetcher.get(pkey, "full", leaf.get("replicas"))
        if sharding is not None:
            return put(arr, sharding)
        return arr
    if kind != "shards":
        raise ckpt_store.ArchiveError(f"unknown leaf kind {kind!r}")
    shape = tuple(int(n) for n in leaf["shape"])
    if sharding is not None:
        needed = sharding.addressable_devices_indices_map(shape)
        assembled: Dict[str, np.ndarray] = {}
        arrays = []
        for dev, idx in needed.items():
            nidx = mf.normalize_index(idx, shape)
            ikey = mf.index_key(nidx)
            if ikey not in assembled:
                assembled[ikey] = _gather_domain(
                    fetcher, leaf, pkey, nidx
                )
            arrays.append(put(assembled[ikey], dev))
        return jax.make_array_from_single_device_arrays(
            shape, sharding, arrays
        )
    return _gather_domain(fetcher, leaf, pkey, _full_domain(shape))


def restore_from_catalog(catalog: StepCatalog, target: Any,
                         sources: List[Any]):
    """Assemble the step onto ``target``'s shardings (or, without a
    target, into nested dicts of full host arrays — the evaluator
    contract). Returns ``(state, step, stats)``; raises
    :class:`ShardUnavailableError` /
    :class:`~dlrover_tpu.trainer.ckpt_store.ArchiveError` when the
    step cannot be fully and verifiably assembled."""
    import jax

    fetcher = _Fetcher(catalog, sources)
    by_path = {mf.path_key(e["path"]): e for e in catalog.leaves}
    if target is not None:
        paths_and_leaves = jax.tree_util.tree_flatten_with_path(
            target, is_leaf=None
        )
        tpaths = [
            mf.path_key(ckpt_store._path_components(p))
            for p, _ in paths_and_leaves[0]
        ]
        if set(tpaths) != set(by_path):
            missing = sorted(set(tpaths) - set(by_path))[:3]
            extra = sorted(set(by_path) - set(tpaths))[:3]
            raise ckpt_store.ArchiveError(
                f"checkpoint/target structure mismatch "
                f"(missing={missing}, extra={extra})"
            )
        leaves = [
            _leaf_value(fetcher, by_path[p], tgt)
            for p, (_, tgt) in zip(tpaths, paths_and_leaves[0])
        ]
        state = jax.tree_util.tree_unflatten(
            paths_and_leaves[1], leaves
        )
    else:
        root: Dict[str, Any] = {}
        for e in catalog.leaves:
            node = root
            comps = e["path"]
            for i, c in enumerate(comps):
                key = c.get("k", c.get("i"))
                if i == len(comps) - 1:
                    node[key] = _leaf_value(fetcher, e, None)
                else:
                    node = node.setdefault(key, {})
        state = root if catalog.leaves else None
    return state, catalog.step, fetcher.stats
