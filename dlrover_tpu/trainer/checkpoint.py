"""Flash checkpoint: async two-tier save for sub-minute failover restore.

Design intent from the reference's north star (the snapshot predates
DLRover's Flash Checkpoint — see SURVEY.md): training state is staged to
host RAM first (a tmpfs such as /dev/shm on each TPU-VM) so a process
restart after preemption/failure restores in seconds, while a background
thread persists to durable storage at a lower cadence.

TPU-native shape:
  * RAM tier — per-process: each JAX process snapshots its *addressable*
    shards (``jax.device_get`` of local shards only, no cross-host traffic)
    plus the sharding metadata; restore re-assembles global arrays with
    ``jax.make_array_from_single_device_arrays`` on the re-formed mesh.
  * Persistent tier — Orbax CheckpointManager (async), the JAX-standard
    distributed checkpoint layout, usable across topology changes. When
    Orbax is unavailable the fallback writes the SAME local-shard
    archives through an :class:`~dlrover_tpu.trainer.ckpt_store.ObjectStore`
    (``gs://`` bucket, or a directory shim for shared mounts/tests) —
    a spare host restoring a dead host's state needs the persist tier
    to be durable shared storage, never local disk. ``persist_dir``
    accepts a URL (``gs://...``/``file://...``) or a plain path.

Atomicity: RAM tier via tmp+``os.rename`` (local tmpfs); persist tier
via a COMMIT marker written after the data objects (object stores have
no rename — see ckpt_store.py for the layout). Archives are the npz+
manifest format from ckpt_store (``numpy.load(allow_pickle=False)``) —
no pickle on any tier, a corrupt or foreign file is rejected, not run.

Zero-stall save pipeline (ISSUE 3; the decomposition Orbax async and
Universal Checkpointing both converge on — a fast snapshot barrier on
the critical path, transfer/serialize/commit pipelined behind it):

    train thread          serializer lane           persist worker
    ------------          ---------------           --------------
    stage (dispatch   ->  materialize D2H       ->  stream archive to
    copy_to_host_async    stream npz to tmpfs       the store / Orbax,
    on all shards,        (snapshot_to_file),       COMMIT barrier, gc
    ~free)                gc RAM tier

``save()`` costs the train thread only the copy *dispatch*; the next
step's compute overlaps the D2H DMA. The serializer lane is depth-1
(one running + one pending — a third concurrent save blocks, honest
back-pressure instead of unbounded staged handles), and the persist
worker sits behind a bounded queue with an explicit overflow policy:
oldest skippable entry dropped + counted (newest data wins), forced
saves never skipped (their submitters block for room). See
docs/CHECKPOINT.md for the stall budget, knobs, and the
donation-safety contract (``wait_staged``).
"""

import atexit
import io
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from dlrover_tpu.checkpoint import manifest as ckpt_manifest
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry import counter, gauge, histogram, record, tracing
from dlrover_tpu.trainer import ckpt_store

#: max persist archives in flight (queued + running)
QUEUE_DEPTH = 2
#: "async": background D2H materialization; "sync": Orbax-style
#: blocking D2H on the train thread (serialization/persist still async)
STAGE = "async"

#: RAM-tier saves are milliseconds; persist commits can run minutes
_CKPT_BUCKETS = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
    30.0, 60.0, 300.0,
)

#: the staging budget: dispatch is expected in the lowest buckets (12
#: ms for 6.8 GB in 38 shards on a v5e chip); far above ~25ms means
#: back-pressure. The wait for the copies is wait_staged's histogram
_STALL_BUCKETS = (
    0.0002, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 5.0, 30.0,
)


def _observe_ckpt(op: str, tier: str, step: int, seconds: float,
                  ok: bool = True, **extra) -> None:
    """One checkpoint save/restore outcome -> metrics + journal."""
    counter(
        "dlrover_checkpoint_ops_total",
        "Checkpoint saves/restores by tier and outcome",
        ["op", "tier", "outcome"],
    ).labels(op=op, tier=tier, outcome="ok" if ok else "error").inc()
    histogram(
        "dlrover_checkpoint_seconds",
        "Checkpoint save/restore wall time", ["op", "tier"],
        buckets=_CKPT_BUCKETS,
    ).labels(op=op, tier=tier).observe(seconds)
    record(
        f"checkpoint.{op}", tier=tier, step=step,
        duration_s=round(seconds, 4), ok=ok, **extra,
    )


def default_ram_dir(job_name: str = "job") -> str:
    base = "/dev/shm" if os.path.isdir("/dev/shm") else "/tmp"
    return os.path.join(base, f"dlrover_tpu_ckpt_{job_name}")


def _is_snap_leaf(x) -> bool:
    return isinstance(x, dict) and x.get("__jax_shards__") is True


def _global_domain_map(x, proc_of_device) -> List[Dict[str, Any]]:
    """The logical array's GLOBAL domain map: every distinct index
    domain the sharding produces, with its replica process set.
    ``devices_indices_map`` is a global view every process holds, so
    each host computes the identical map with no collective — the
    foundation of format-v2 owner election (docs/CHECKPOINT.md)."""
    groups: Dict[str, Dict[str, Any]] = {}
    for dev, idx in x.sharding.devices_indices_map(
        tuple(x.shape)
    ).items():
        nidx = ckpt_manifest.normalize_index(idx, x.shape)
        key = ckpt_manifest.index_key(nidx)
        g = groups.setdefault(key, {"idx": nidx, "replicas": set()})
        g["replicas"].add(int(proc_of_device(dev)))
    return [
        {"idx": g["idx"], "replicas": sorted(g["replicas"])}
        for g in groups.values()
    ]


def _stage_local_shards(pytree, sync: bool = False, topology=None):
    """Start the device->host snapshot of a pytree's *addressable*
    shards and return a staged pytree (shard-snap dicts whose shard
    data are device handles, or host arrays when ``sync=True``).

    Async mode dispatches ``copy_to_host_async()`` on EVERY shard up
    front — the train thread pays only copy dispatch and all shards'
    DMA overlaps the next step's compute — then hands the handles to
    :func:`_materialize_staged` on the serializer thread. Sync mode
    blocks for each shard's transfer here (the Orbax-async model: the
    D2H is the only train-thread cost; use it when donated buffers
    can't be guaranteed to outlive staging — see docs/CHECKPOINT.md).

    ``topology`` (``{"process_index", "n_processes",
    "proc_of_device"}``) turns on format-v2 staging: each snap dict
    additionally carries the global ``domains`` map (replica sets for
    owner election). A non-None ``proc_of_device`` also FILTERS the
    staged shards to the virtual process's own devices — how the
    drill suite runs a multi-host topology inside one real process.
    """
    proc_of = None
    me = None
    if topology is not None:
        proc_of = topology.get("proc_of_device")
        me = int(topology["process_index"])

    def snap(x):
        if isinstance(x, jax.Array):
            shards = []
            for s in x.addressable_shards:
                if proc_of is not None and int(proc_of(s.device)) != me:
                    continue
                d = s.data
                if sync:
                    d = _owned_host_array(d)
                else:
                    try:
                        d.copy_to_host_async()
                    except (AttributeError, RuntimeError):
                        pass  # backend without async D2H: asarray later
                shards.append((s.index, d))
            out = {
                "__jax_shards__": True,
                "shape": tuple(x.shape),
                "dtype": str(x.dtype),
                "shards": shards,
            }
            if topology is not None:
                out["domains"] = _global_domain_map(
                    x,
                    proc_of or (
                        lambda dev: getattr(dev, "process_index", 0)
                    ),
                )
            return out
        return x

    return jax.tree.map(snap, pytree)


def _owned_host_array(d) -> np.ndarray:
    """Host copy of one shard that OWNS its memory. On the CPU backend
    ``np.asarray`` returns a zero-copy view of the device buffer —
    donation/deletion of the source array would leave the snapshot
    pointing at freed memory, so a view is copied out; on TPU the host
    transfer already produced an owned buffer and no extra copy runs."""
    arr = np.asarray(d)
    if arr.base is not None and isinstance(d, jax.Array):
        try:
            platform = next(iter(d.devices())).platform
        except Exception:
            platform = None
        if platform == "cpu":
            arr = np.array(arr)
    return arr


def _materialize_staged(staged):
    """Complete a staged snapshot: wait out the async copies and turn
    every shard handle into an owned host array (the layout
    ``snapshot_to_file`` serializes). Runs on the serializer thread."""

    def mat(x):
        if _is_snap_leaf(x):
            return {
                **x,
                "shards": [
                    (idx, _owned_host_array(d)) for idx, d in x["shards"]
                ],
            }
        return x

    return jax.tree.map(mat, staged, is_leaf=_is_snap_leaf)


def _shard_sizes(tree) -> Tuple[int, int]:
    """``(bytes, shards)`` of a staged or materialized snapshot: what
    the spans say of a save's size. Called under ``tracing.enabled()``
    only."""
    sizes = [
        d.nbytes
        for leaf in jax.tree.leaves(tree, is_leaf=_is_snap_leaf)
        if _is_snap_leaf(leaf)
        for _, d in leaf["shards"]
    ]
    return sum(sizes), len(sizes)


def _local_shards(pytree):
    """Blocking snapshot of process-local shard data + index metadata
    (stage + materialize in one call; the synchronous baseline and the
    restore-side test helper)."""
    return _materialize_staged(_stage_local_shards(pytree))


def target_sharding(target):
    """The sharding a restore lands a leaf on: a concrete array's, or
    an abstract ``ShapeDtypeStruct``'s (a target that holds no second
    state on the device). None = leave the leaf on the host."""
    if isinstance(target, (jax.Array, jax.ShapeDtypeStruct)):
        return target.sharding
    return None


def _restore_shards(snapshot, target=None):
    """Rebuild arrays from local-shard snapshots. With a ``target`` pytree of
    sharded arrays (same treedef), restores onto the target's shardings;
    otherwise returns plain host arrays."""
    import numpy as np

    def rebuild(snap, tgt=None):
        if isinstance(snap, dict) and snap.get("__jax_shards__"):
            shards = snap["shards"]
            sharding = target_sharding(tgt)
            if sharding is not None:
                # index is a tuple of slices; key by repr for hashability
                per_index = {repr(i): d for i, d in shards}
                full = None
                arrays = []
                for d, idx in sharding.addressable_devices_indices_map(
                    snap["shape"]
                ).items():
                    data = per_index.get(repr(idx))
                    if data is None:
                        # world changed: reslice from assembled host array
                        if full is None:
                            full = _assemble(snap)
                        data = full[idx]
                    data = np.asarray(data)
                    # the enqueue: the copy itself completes later
                    with tracing.span(
                        "ckpt.restore.device_put", {"bytes": data.nbytes}
                    ):
                        arrays.append(jax.device_put(data, d))
                return jax.make_array_from_single_device_arrays(
                    snap["shape"], sharding, arrays
                )
            return _assemble(snap)
        return snap

    def _assemble(snap):
        full = np.zeros(snap["shape"], dtype=snap["dtype"])
        with tracing.span(
            "ckpt.restore.assemble", {"bytes": full.nbytes}
        ):
            for idx, data in snap["shards"]:
                full[idx] = np.asarray(data)
        return full

    def is_snap(x):
        return isinstance(x, dict) and x.get("__jax_shards__") is True

    if target is None:
        return jax.tree.map(rebuild, snapshot, is_leaf=is_snap)
    return jax.tree.map(rebuild, snapshot, target, is_leaf=is_snap)


@dataclass
class CheckpointRecord:
    step: int
    path: str
    tier: str  # "ram" | "persistent"


@dataclass
class _SaveJob:
    """One save() handed to the serializer lane."""

    step: int
    staged: Any
    persist_due: bool
    force: bool
    #: sentinel verdict at save() time — True means no anomaly window
    #: was open, False taints the step against rollback restores, None
    #: means no sentinel is armed (legacy archives stay untagged)
    last_good: Optional[bool] = None
    #: set once the staged snapshot is fully materialized on the host —
    #: after this, the source device buffers may be donated/deleted
    staged_evt: threading.Event = field(default_factory=threading.Event)


@dataclass
class _PersistJob:
    """One persist handed to the bounded persist queue.

    ``payload`` is ``("store", ram_file_path)`` — the worker streams
    the already-serialized tmpfs archive into the object store (never
    a full in-memory copy) — or ``("orbax", snapshot)`` /
    ``("snapshot", snapshot)`` holding the materialized host snapshot
    captured at save() time (NEVER re-read from device state on the
    background thread: with donation the train loop may have
    invalidated those buffers long ago). The ``"snapshot"`` kind is
    the store branch's RAM-write-failure fallback: the worker builds
    the archive in memory so a due persist is never silently lost."""

    step: int
    payload: Tuple[str, Any]
    force: bool
    last_good: Optional[bool] = None
    abandon: Callable[[], None] = lambda: None


class _SerializerLane:
    """Depth-1 background serializer: at most one snapshot being
    serialized plus one staged save pending. A third concurrent save()
    BLOCKS in submit — honest back-pressure instead of staged
    device-handle pytrees piling up when serialization can't keep up."""

    def __init__(self, run_fn: Callable[[Any], None], name: str):
        self._run = run_fn
        self._cond = threading.Condition()
        self._pending: Optional[Any] = None
        self._busy = False
        self._closed = False
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=name
        )
        self._thread.start()

    def submit(self, job) -> None:
        with self._cond:
            if self._pending is not None and not self._closed:
                # back-pressure, apart from dispatch in ``ckpt.stage``
                with tracing.span("ckpt.submit_wait", {
                    "step": job.step, "behind": self._pending.step,
                }):
                    while self._pending is not None and not self._closed:
                        self._cond.wait()
            if self._closed:
                raise RuntimeError("checkpointer is closed")
            self._pending = job
            self._cond.notify_all()

    def drain(self) -> None:
        if threading.current_thread() is self._thread:
            return
        with self._cond:
            while self._pending is not None or self._busy:
                if self._closed:
                    return
                self._cond.wait(timeout=0.2)

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=10.0)

    def _loop(self) -> None:
        while True:
            with self._cond:
                while self._pending is None and not self._closed:
                    self._cond.wait()
                if self._pending is None and self._closed:
                    return
                job, self._pending = self._pending, None
                self._busy = True
                self._cond.notify_all()
            try:
                self._run(job)
            except Exception as e:  # never kill the lane
                logger.error("checkpoint serializer failed: %s", e)
            with self._cond:
                self._busy = False
                self._cond.notify_all()


class _PersistQueue:
    """Single persist worker behind a bounded queue.

    In-flight persists (queued + running) never exceed ``depth`` — a
    slow store can pin at most ``depth`` archives, not one per save.
    Overflow policy: a same-step entry is superseded in place; else the
    oldest NON-forced queued entry is dropped and counted
    (``dlrover_checkpoint_persist_skipped_total`` — newest data wins);
    if nothing is skippable the incoming non-forced save is the one
    skipped. Forced saves are never dropped: their submitter blocks
    until there is room (back-pressure on ``force_persist``)."""

    def __init__(self, run_fn: Callable[[_PersistJob], None],
                 depth: int, on_skip: Callable[[_PersistJob, str], None]):
        self._run = run_fn
        self._depth = max(1, int(depth))
        self._on_skip = on_skip
        self._cond = threading.Condition()
        self._q: List[_PersistJob] = []
        self._busy = False
        self._closed = False
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="ckpt-persist"
        )
        self._thread.start()

    @property
    def depth(self) -> int:
        return self._depth

    def _inflight_locked(self) -> int:
        return len(self._q) + (1 if self._busy else 0)

    def inflight(self) -> int:
        with self._cond:
            return self._inflight_locked()

    def _gauge_locked(self) -> None:
        gauge(
            "dlrover_checkpoint_persist_queue_depth",
            "Persist archives in flight (queued + running)",
        ).set(self._inflight_locked())

    def submit(self, job: _PersistJob) -> bool:
        """Returns True when the job was accepted (queued or
        superseded a queued same-step entry), False when skipped."""
        with self._cond:
            if self._closed:
                job.abandon()
                return False
            for i, queued in enumerate(self._q):
                if queued.step == job.step:
                    self._q[i] = job
                    self._cond.notify_all()
                    self._on_skip(queued, "superseded")
                    return True
            if job.force:
                while (
                    self._inflight_locked() >= self._depth
                    and not self._closed
                ):
                    self._cond.wait(timeout=0.5)
                if self._closed:
                    job.abandon()
                    return False
            elif self._inflight_locked() >= self._depth:
                idx = next(
                    (i for i, e in enumerate(self._q) if not e.force),
                    None,
                )
                if idx is None:
                    self._on_skip(job, "queue_full")
                    return False
                self._on_skip(self._q.pop(idx), "overflow")
            self._q.append(job)
            self._gauge_locked()
            self._cond.notify_all()
            return True

    def drain(self) -> None:
        if threading.current_thread() is self._thread:
            return
        with self._cond:
            while self._q or self._busy:
                if self._closed:
                    return
                self._cond.wait(timeout=0.2)

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=10.0)

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._q and not self._closed:
                    self._cond.wait()
                if not self._q and self._closed:
                    return
                job = self._q.pop(0)
                self._busy = True
                self._gauge_locked()
                self._cond.notify_all()
            try:
                self._run(job)
            except Exception as e:  # worker survives any one failure
                logger.error(
                    "persist worker failed for step %d: %s", job.step, e
                )
            with self._cond:
                self._busy = False
                self._gauge_locked()
                self._cond.notify_all()


class FlashCheckpointer:
    """Two-tier async checkpointer.

    save(step, state): stages the device->host snapshot (copy dispatch
    only, independent of serialization and near-independent of state
    size; a loop whose step donates the state then waits for the
    copies in :meth:`wait_staged`, docs/CHECKPOINT.md "Stall budget")
    and returns; the serializer lane
    materializes the staged shards and streams the archive to the RAM
    tier (tmpfs), then hands the persistent save to a bounded persist
    worker when ``step % persist_interval == 0`` (or force_persist).

    ``queue_depth`` bounds in-flight persist archives (default 2, env
    ENV_QUEUE_DEPTH); ``stage`` picks async (default) or sync D2H
    staging (env ENV_STAGE; see the donation-safety contract in
    docs/CHECKPOINT.md and :meth:`wait_staged`).
    """

    def __init__(
        self,
        persist_dir: str,
        ram_dir: Optional[str] = None,
        persist_interval: int = 100,
        max_ram_keep: int = 2,
        max_persist_keep: int = 3,
        use_orbax: bool = True,
        commit_timeout: float = 300.0,
        queue_depth: int = QUEUE_DEPTH,
        stage: str = STAGE,
        process_index: Optional[int] = None,
        n_processes: Optional[int] = None,
        proc_of_device: Optional[Callable[[Any], int]] = None,
        peer_registry=None,
    ):
        self.persist_dir = (
            persist_dir if ckpt_store.is_url(persist_dir)
            else os.path.abspath(persist_dir)
        )
        self.ram_dir = ram_dir or default_ram_dir(
            os.path.basename(persist_dir.rstrip("/")) or "job"
        )
        self.persist_interval = persist_interval
        self.max_ram_keep = max_ram_keep
        self.max_persist_keep = max_persist_keep
        self.commit_timeout = commit_timeout
        # overridable for virtual-host drills (several logical
        # processes sharing one real jax process) and spare-host tools
        self._process_index = (
            jax.process_index() if process_index is None
            else int(process_index)
        )
        self._n_processes = (
            jax.process_count() if n_processes is None
            else int(n_processes)
        )
        #: device -> owning (possibly virtual) process index; None
        #: means the real topology (device.process_index)
        self._proc_of_device = proc_of_device
        #: checkpoint.peer.PeerRegistry advertising this host's
        #: RAM-tier steps and resolving peers at restore (optional)
        self._peer_registry = peer_registry
        # the save-attempt id scoping the COMMIT barrier (see
        # ckpt_store.write_step): the rendezvous round is globally
        # consistent across hosts of one world incarnation. Outside the
        # elastic agent the fallback is the CONSTANT "0" — never a
        # per-host value like RESTART_COUNT, which diverges after a
        # single-host restart and would starve the barrier forever
        # (processes writing different-attempt shards never commit)
        from dlrover_tpu.common.constants import NodeEnv

        self._attempt = os.getenv(NodeEnv.RDZV_ROUND, "0")
        os.makedirs(self.ram_dir, exist_ok=True)
        self.queue_depth = max(1, queue_depth)
        if stage not in ("async", "sync"):
            raise ValueError(f"stage must be async|sync, got {stage!r}")
        self._stage_sync = stage == "sync"
        # workers start lazily on the first save(): restore-only
        # instances (evaluator, spare hosts) never spawn threads
        self._workers_lock = threading.Lock()
        self._serializer: Optional[_SerializerLane] = None
        self._persistq: Optional[_PersistQueue] = None
        self._last_save: Optional[_SaveJob] = None
        self._closed = False
        # sentinel hook: () -> bool, True while no anomaly window is
        # open; archives saved under an open window are tagged
        # last_good=False and skipped by the restore walk-down
        self._clean_fn: Optional[Callable[[], bool]] = None
        # RAM-tier files referenced by queued/running persist jobs must
        # survive _gc_ram until the upload finished
        self._pin_lock = threading.Lock()
        self._pinned: Dict[str, int] = {}
        self._use_orbax = use_orbax
        self._manager = None
        self._store: Optional[ckpt_store.ObjectStore] = None
        if use_orbax:
            try:
                import orbax.checkpoint as ocp

                self._manager = ocp.CheckpointManager(
                    self.persist_dir,
                    options=ocp.CheckpointManagerOptions(
                        max_to_keep=max_persist_keep,
                        enable_async_checkpointing=True,
                    ),
                )
            except Exception as e:  # pragma: no cover
                logger.warning(
                    "Orbax unavailable (%s); persistent tier uses the "
                    "object-store shard-archive format", e,
                )
                self._use_orbax = False
        if self._manager is None:
            self._store = ckpt_store.get_store(self.persist_dir)

    def _stage_topology(self) -> Optional[Dict[str, Any]]:
        """Staging-time topology for format-v2 domain maps: engaged on
        any multi-process world or when a virtual-host device mapping
        is installed; single-process saves skip the bookkeeping (their
        archives are complete and self-contained either way)."""
        if self._n_processes <= 1 and self._proc_of_device is None:
            return None
        return {
            "process_index": self._process_index,
            "n_processes": self._n_processes,
            "proc_of_device": self._proc_of_device,
        }

    def _save_topology(self) -> Dict[str, int]:
        return {
            "n_processes": self._n_processes,
            "process_index": self._process_index,
        }

    def shard_provider(self) -> Callable[[int], Optional[str]]:
        """The ``/ckpt/shard`` backing for this host: step -> RAM-tier
        archive path when held. Wire it with
        ``telemetry.http.set_shard_provider(ckpt.shard_provider())``
        (or the MetricsServer ``shard_provider`` arg)."""

        def provide(step: int) -> Optional[str]:
            path = self._ram_path(int(step))
            return path if os.path.exists(path) else None

        return provide

    def set_clean_fn(self, fn: Optional[Callable[[], bool]]) -> None:
        """Install the sentinel's clean-verdict callback. Called on the
        train thread at save() time; its answer tags the archive
        (``last_good``) so a coordinated rollback never restores a step
        saved while an anomaly window was open."""
        self._clean_fn = fn

    # ------------------------------------------------------------------ save

    def save(self, step: int, state: Any,
             force_persist: bool = False,
             durable: bool = False) -> float:
        """Stage the snapshot and return; serialization + both tier
        writes happen behind the step loop. Returns the train-thread
        stall in milliseconds.

        ``durable=True`` additionally blocks until the RAM-tier
        archive is on tmpfs (surviving an immediate HARD kill of this
        process — ``os._exit``, SIGKILL). That is the pre-pipeline
        cost profile: use it only where a drill/caller needs
        crash-durability at a specific step; a normal step loop keeps
        the async default and accepts a serialize-window of
        durability lag (docs/CHECKPOINT.md). The returned stall covers
        the full durable drain, but the stall histogram keeps
        recording staging dispatch only — durable saves must not skew
        the staging budget it alerts on."""
        t0 = time.perf_counter()
        ts_wall = time.time()
        staged = _stage_local_shards(
            state, sync=self._stage_sync,
            topology=self._stage_topology(),
        )
        # verdict captured on the train thread, at save() time: the
        # background lanes must tag the archive with what the sentinel
        # knew when the state was snapshotted, not when it lands
        last_good = None
        if self._clean_fn is not None:
            try:
                last_good = bool(self._clean_fn())
            except Exception:
                last_good = None
        job = _SaveJob(
            step=step,
            staged=staged,
            persist_due=force_persist or (
                self.persist_interval > 0
                and step % self.persist_interval == 0
            ),
            force=force_persist,
            last_good=last_good,
        )
        if self._stage_sync:
            job.staged_evt.set()  # host copies already owned
        self._ensure_workers()
        self._last_save = job
        self._serializer.submit(job)  # blocks only when the lane is full
        stall_s = time.perf_counter() - t0
        histogram(
            "dlrover_checkpoint_save_stall_seconds",
            "Train-thread stall per checkpoint save (staging only)",
            buckets=_STALL_BUCKETS,
        ).observe(stall_s)
        # the train-thread slice of the save on the trace timeline;
        # serialize/persist appear as their own lanes' spans
        if tracing.enabled():
            nbytes, shards = _shard_sizes(staged)
            tracing.add_span("ckpt.stage", ts_wall, stall_s, attrs={
                "step": step, "bytes": nbytes, "shards": shards,
            })
        if durable:
            self._serializer.drain()
            total_s = time.perf_counter() - t0
            logger.info(
                "Flash save step %d: staged in %.2f ms, durable on "
                "tmpfs in %.0f ms", step, stall_s * 1e3, total_s * 1e3,
            )
            return total_s * 1e3
        logger.info(
            "Flash save step %d: staged in %.2f ms (train-thread stall)",
            step, stall_s * 1e3,
        )
        return stall_s * 1e3

    def wait_staged(self, timeout: Optional[float] = None) -> bool:
        """Block until the most recent save()'s snapshot is fully
        materialized on the host. THE DONATION SYNC POINT: a train
        loop whose step donates the state buffers must call this
        before dispatching the step that invalidates them (or
        construct the checkpointer with ``stage="sync"``). Where it
        waits, the wait is the span ``ckpt.wait_staged`` on the
        caller's thread and one observation of
        ``dlrover_checkpoint_wait_staged_seconds``; with nothing in
        flight it reads one attribute and an event's flag."""
        job = self._last_save
        if job is None or job.staged_evt.is_set():
            return True  # nothing in flight: nothing written
        t0 = time.perf_counter()
        with tracing.span("ckpt.wait_staged", {"step": job.step}):
            staged = job.staged_evt.wait(timeout)
        histogram(
            "dlrover_checkpoint_wait_staged_seconds",
            "Train-thread wait for a save's device-to-host copies "
            "(the stall save_stall_seconds leaves out)",
            buckets=_CKPT_BUCKETS,
        ).observe(time.perf_counter() - t0)
        return staged

    def _ensure_workers(self) -> None:
        if self._serializer is not None:
            return
        with self._workers_lock:
            if self._serializer is not None:
                return
            if self._closed:
                raise RuntimeError("checkpointer is closed")
            self._persistq = _PersistQueue(
                self._run_persist, self.queue_depth, self._skip_persist
            )
            self._serializer = _SerializerLane(
                self._serialize_job, "ckpt-serialize"
            )
            atexit.register(self._atexit_flush)

    def _atexit_flush(self) -> None:
        # daemon workers die with the interpreter; a clean exit right
        # after a save must still land it (examples/drills exit the
        # step loop and return without close())
        try:
            self.wait()
        except Exception:
            pass

    def _serialize_job(self, job: _SaveJob) -> None:
        """Serializer lane: materialize the staged D2H copies, stream
        the archive to the RAM tier, then hand off persistence. A
        RAM-tier write failure must NOT drop a due persist — the
        materialized snapshot is still good, so the persist proceeds
        from it (forced persists are guaranteed never skipped); only a
        staging failure truly loses the save, and that loss is counted
        (``persist_skipped{reason="stage_failed"}``) so failover
        drills can detect it."""
        with tracing.span("ckpt.serialize", {"step": job.step},
                          cpu=True):
            self._serialize_job_inner(job)

    def _serialize_job_inner(self, job: _SaveJob) -> None:
        t0 = time.perf_counter()
        try:
            size = {}
            with tracing.span("ckpt.write.materialize", size, cpu=True):
                snapshot = _materialize_staged(job.staged)
                if tracing.enabled():
                    size["bytes"] = _shard_sizes(snapshot)[0]
            job.staged = None  # drop device handles promptly
            job.staged_evt.set()
        except Exception as e:
            job.staged_evt.set()
            logger.error(
                "staging snapshot for step %d failed: %s", job.step, e
            )
            _observe_ckpt(
                "save", "ram", job.step, time.perf_counter() - t0,
                ok=False, reason=str(e)[:200],
            )
            if job.persist_due:
                self._skip_persist(
                    _PersistJob(job.step, ("none", None), job.force),
                    "stage_failed",
                )
            return
        ram_ok = True
        try:
            nbytes = self._write_ram(job.step, snapshot, job.last_good)
            dt = time.perf_counter() - t0
            logger.info(
                "Flash save step %d: RAM tier in %.0f ms (pipelined)",
                job.step, dt * 1e3,
            )
            _observe_ckpt(
                "save", "ram", job.step, dt, bytes=nbytes,
            )
            if self._peer_registry is not None:
                # the RAM archive is now servable over /ckpt/shard:
                # tell the master KV so restoring peers can find it
                self._peer_registry.advertise(job.step)
            self._gc_ram()
        except Exception as e:
            ram_ok = False
            logger.error(
                "RAM-tier save step %d failed: %s", job.step, e
            )
            _observe_ckpt(
                "save", "ram", job.step, time.perf_counter() - t0,
                ok=False, reason=str(e)[:200],
            )
        if job.persist_due:
            self._enqueue_persist(
                job.step, snapshot, job.force, ram_ok=ram_ok,
                last_good=job.last_good,
            )

    def _ram_path(self, step: int) -> str:
        return os.path.join(
            self.ram_dir, f"step-{step}-proc-{self._process_index}"
        )

    def _write_ram(self, step: int, snapshot: Any,
                   last_good: Optional[bool] = None) -> int:
        path = self._ram_path(step)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            nbytes = ckpt_store.snapshot_to_file(
                snapshot, step, f, last_good=last_good,
                topology=self._save_topology(),
            )
        os.replace(tmp, path)
        counter(
            "dlrover_ckpt_shard_bytes_total",
            "Checkpoint shard bytes moved, by tier", ["tier"],
        ).labels(tier="ram").inc(max(0, nbytes))
        return nbytes

    def _pin(self, path: str) -> None:
        with self._pin_lock:
            self._pinned[path] = self._pinned.get(path, 0) + 1

    def _unpin(self, path: str) -> None:
        with self._pin_lock:
            n = self._pinned.get(path, 0) - 1
            if n <= 0:
                self._pinned.pop(path, None)
            else:
                self._pinned[path] = n

    def _gc_ram(self):
        records = self._list_ram()
        with self._pin_lock:
            pinned = set(self._pinned)
        for step, path in records[: -self.max_ram_keep]:
            if path in pinned:
                continue  # a persist upload still streams from it
            try:
                os.remove(path)
            except OSError:
                continue
            if self._peer_registry is not None:
                # stop advertising what we no longer hold
                self._peer_registry.withdraw(step)

    def _list_ram(self):
        # let queued saves land first so listings (and the gc/consensus
        # decisions built on them) see every save already issued;
        # no-op when called from the serializer lane itself (gc)
        self._drain_saves()
        records = []
        suffix = f"-proc-{self._process_index}"
        try:
            for name in os.listdir(self.ram_dir):
                if name.startswith("step-") and name.endswith(suffix):
                    try:
                        step = int(name.split("-")[1])
                    except ValueError:
                        continue
                    records.append(
                        (step, os.path.join(self.ram_dir, name))
                    )
        except FileNotFoundError:
            pass
        return sorted(records)

    def _enqueue_persist(self, step: int, snapshot: Any,
                         force: bool, ram_ok: bool = True,
                         last_good: Optional[bool] = None) -> None:
        """Serializer lane -> persist queue handoff. The store branch
        references the RAM-tier file (pinned against gc) so a queued
        persist costs a tmpfs path, not an in-memory archive; the
        Orbax branch carries the host snapshot captured at save() time
        — the background worker must NEVER touch the live device state
        (donation may have invalidated it by then). When the RAM write
        failed (``ram_ok=False``) the store branch falls back to
        carrying the snapshot itself and the worker builds the archive
        in memory — the only persist path paying a full in-memory
        copy, and still bounded by the queue like any other job."""
        if self._manager is not None:
            job = _PersistJob(
                step, ("orbax", snapshot), force, last_good=last_good
            )
        elif ram_ok:
            path = self._ram_path(step)
            self._pin(path)
            job = _PersistJob(
                step, ("store", path), force, last_good=last_good,
                abandon=lambda: self._unpin(path),
            )
        else:
            logger.warning(
                "RAM tier for step %d unavailable; persisting from "
                "the in-memory snapshot", step,
            )
            job = _PersistJob(
                step, ("snapshot", snapshot), force, last_good=last_good
            )
        self._persistq.submit(job)

    def _put_owned_subset(self, step: int, src) -> None:
        """Persist-tier upload for a format-v2 multi-process save: the
        OWNED subset of the full archive (dedup — replicated shards go
        up exactly once, from their elected owner) plus this host's
        index piece (the subset manifest) for rank 0's merge."""
        import json

        from dlrover_tpu.checkpoint import saver as ckpt_saver

        if isinstance(src, str):
            with open(src, "rb") as f:
                sub_bytes, sub_man, stats = ckpt_saver.subset_archive(
                    f, self._process_index
                )
        else:
            sub_bytes, sub_man, stats = ckpt_saver.subset_archive(
                src, self._process_index
            )
        ckpt_store.put_shard_stream(
            self._store, step, self._process_index,
            io.BytesIO(sub_bytes), attempt=self._attempt,
            size=len(sub_bytes),
        )
        self._store.put(
            ckpt_store.index_key(
                step, self._process_index, self._attempt
            ),
            json.dumps(sub_man, separators=(",", ":")).encode("utf-8"),
        )
        counter(
            "dlrover_ckpt_shard_bytes_total",
            "Checkpoint shard bytes moved, by tier", ["tier"],
        ).labels(tier="persistent").inc(len(sub_bytes))
        record(
            "ckpt.dedup", step=step,
            process_index=self._process_index, **stats,
        )

    def _skip_persist(self, job: _PersistJob, reason: str) -> None:
        job.abandon()
        counter(
            "dlrover_checkpoint_persist_skipped_total",
            "Persistent saves dropped by the bounded queue",
            ["reason"],
        ).labels(reason=reason).inc()
        record(
            "checkpoint.persist_skipped", step=job.step, reason=reason,
            queue_depth=self.queue_depth,
        )
        logger.warning(
            "Persistent save step %d skipped (%s; queue depth %d)",
            job.step, reason, self.queue_depth,
        )

    def _run_persist(self, job: _PersistJob) -> None:
        with tracing.span(
            "ckpt.persist", {"step": job.step, "kind": job.payload[0]},
            cpu=True,
        ):
            self._run_persist_inner(job)

    def _run_persist_inner(self, job: _PersistJob) -> None:
        t0 = time.time()
        step = job.step
        kind, payload = job.payload
        try:
            if kind == "orbax":
                # single-host assembly of the staged snapshot; parity
                # with the old jax.device_get(state) tree, minus the
                # background-thread device access
                host_state = _restore_shards(payload)
                self._manager.save(
                    step,
                    args=__import__(
                        "orbax.checkpoint", fromlist=["args"]
                    ).args.StandardSave(host_state),
                )
                logger.info("Persistent save step %d done", step)
                _observe_ckpt(
                    "save", "persistent", step, time.time() - t0,
                    backend="orbax",
                )
                return
            extra = {}
            sharded = self._n_processes > 1
            if kind == "store":
                try:
                    if sharded:
                        self._put_owned_subset(step, payload)
                    else:
                        with open(payload, "rb") as f:
                            size = os.fstat(f.fileno()).st_size
                            ckpt_store.put_shard_stream(
                                self._store, step,
                                self._process_index, f,
                                attempt=self._attempt, size=size,
                            )
                finally:
                    job.abandon()  # upload done/failed: unpin RAM file
            else:  # "snapshot": RAM tier failed — archive from memory
                buf = io.BytesIO()
                size = ckpt_store.snapshot_to_file(
                    payload, step, buf, last_good=job.last_good,
                    topology=self._save_topology(),
                )
                if sharded:
                    buf.seek(0)
                    self._put_owned_subset(step, buf)
                else:
                    buf.seek(0)
                    ckpt_store.put_shard_stream(
                        self._store, step, self._process_index, buf,
                        attempt=self._attempt, size=size,
                    )
                extra = {"source": "memory"}
            if self._process_index != 0:
                # only rank 0 knows whether the step COMMITs;
                # claiming "done" here misleads incident triage
                # when the commit barrier later times out
                logger.info(
                    "Persistent save step %d: shard uploaded "
                    "(awaiting rank-0 commit)", step,
                )
                return
            if sharded:
                committed = ckpt_store.commit_step_sharded(
                    self._store, step, self._n_processes,
                    attempt=self._attempt,
                    timeout=self.commit_timeout,
                    last_good=job.last_good,
                )
                if committed:
                    record(
                        "ckpt.manifest_committed", step=step,
                        n_processes=self._n_processes,
                        attempt=self._attempt,
                    )
            else:
                committed = ckpt_store.commit_step(
                    self._store, step, self._n_processes,
                    attempt=self._attempt,
                    timeout=self.commit_timeout,
                    last_good=job.last_good,
                )
            if committed:
                ckpt_store.gc_steps(self._store, self.max_persist_keep)
                logger.info("Persistent save step %d done", step)
                _observe_ckpt(
                    "save", "persistent", step, time.time() - t0,
                    backend="store", **extra,
                )
            else:
                logger.error(
                    "Persistent save step %d NOT committed: peer "
                    "shards missing after %.0fs", step,
                    self.commit_timeout,
                )
                _observe_ckpt(
                    "save", "persistent", step, time.time() - t0,
                    ok=False, reason="commit_timeout",
                )
        except Exception as e:
            logger.error("Persistent save step %d failed: %s", step, e)
            _observe_ckpt(
                "save", "persistent", step, time.time() - t0,
                ok=False, reason=str(e)[:200],
            )

    def wait(self):
        """Block until EVERY in-flight save — staged, serializing, and
        queued/running persists — has finished (not just the last one:
        close() must never orphan an uncommitted save)."""
        if self._serializer is not None:
            self._serializer.drain()
        if self._persistq is not None:
            self._persistq.drain()
        if self._manager is not None:
            self._manager.wait_until_finished()

    # --------------------------------------------------------------- restore

    def _drain_saves(self) -> None:
        """Make queued-but-unserialized saves visible to readers: the
        RAM tier is written by the serializer lane, so listings and
        restores first let in-flight saves land (no-op from the
        pipeline's own threads)."""
        if self._serializer is not None:
            self._serializer.drain()

    def latest_step(self) -> Optional[int]:
        self._drain_saves()
        ram = self._list_ram()
        ram_step = ram[-1][0] if ram else None
        persist_step = None
        if self._manager is not None:
            persist_step = self._manager.latest_step()
        else:
            # per-process availability, not just global COMMITs: a step
            # that lost this process's shard object must not be chosen
            # over an older fully-restorable one
            steps = ckpt_store.available_steps(
                self._store, self._process_index
            )
            persist_step = steps[-1] if steps else None
        candidates = [s for s in (ram_step, persist_step) if s is not None]
        return max(candidates) if candidates else None

    def _consensus_step(self, local_steps) -> Optional[int]:
        """The newest step EVERY process can restore.

        After elastic world changes, hosts can hold different RAM-tier
        histories (a returning host's tmpfs still has files from an
        older incarnation). Each process restoring its own latest step
        would silently mix training states — the collectives still
        shape-match, so nothing crashes, the run is just wrong. With a
        multi-process world, allgather the per-process candidate sets
        and take the max step present EVERYWHERE."""
        if not local_steps:
            local_steps = set()
        if self._n_processes <= 1:
            return max(local_steps) if local_steps else None
        try:
            import numpy as np
            from jax.experimental import multihost_utils

            k = 16
            mine = sorted(local_steps)[-k:]
            arr = np.full((k,), -1, dtype=np.int64)
            arr[: len(mine)] = mine
            gathered = multihost_utils.process_allgather(arr)
            # a single-controller world gathers to the same 1-D shape
            # (no leading process axis) — normalize before iterating
            gathered = np.asarray(gathered).reshape(-1, k)
            sets = [
                {int(s) for s in row if s >= 0} for row in gathered
            ]
            common = set.intersection(*sets) if sets else set()
            if common:
                return max(common)
            return None
        except Exception as e:
            # A consensus-collective failure must vote FRESH, never
            # fall back to the host-local latest: if the allgather
            # failed on only a subset of hosts, per-host "local
            # latest" answers can differ while every host still votes
            # success in the agreement gather — exactly the silent
            # mixed-step restore this path exists to prevent. A
            # recoverable checkpoint lost to a transient collective
            # error costs a cold start; a mixed world corrupts the
            # run.
            logger.error(
                "cross-process checkpoint consensus failed (%s); "
                "voting for a fresh start — a partial collective "
                "failure must not produce a mixed-step restore", e,
            )
            return None

    def restore(self, target: Any = None, step: Optional[int] = None,
                extra_sources: Optional[List[Any]] = None):
        """Restore (state, step), preferring the RAM tier.

        ``target``: pytree of arrays with desired shardings (abstract or
        concrete); restored values take the target's shardings so restore
        works after mesh re-formation. In auto mode (``step=None``) on a
        multi-process world, the outcome is AGREED across processes:
        either every process restores the consensus step or every
        process starts fresh — never a mix.

        ``extra_sources``: shard sources consulted BEFORE every
        checkpoint tier by the v2 planner (reshard/migrate.py's live
        tier, a hot spare's pre-warmed cache). A source carrying a
        ``step`` attribute is only consulted when the candidate step
        matches it — a walk-down to an older step must never be
        served another step's bytes.
        """
        attrs: Dict[str, Any] = {}
        with tracing.span("ckpt.restore", attrs):
            state, got = self._restore(target, step, extra_sources)
            if state is not None and tracing.enabled():
                leaves = jax.tree.leaves(state)
                attrs.update(
                    step=got, tier=self.last_restore_tier,
                    leaves=len(leaves),
                    bytes=sum(getattr(x, "nbytes", 0) for x in leaves),
                )
        return state, got

    def _restore(self, target, step, extra_sources):
        self._drain_saves()
        # per-tier shard-move stats of the newest v2 assembly (consumed
        # by reshard/migrate.py to attribute where shards came from);
        # None until a topology restore runs
        self.last_restore_stats = None
        #: the tier the newest restore was served from
        self.last_restore_tier = None
        auto_mode = step is None
        if not (auto_mode and self._n_processes > 1):
            # no agreement collective on this path: let failures
            # SURFACE — downgrading a single-host restore error to a
            # fresh start would silently bury a recoverable checkpoint
            return self._restore_once(target, step, extra_sources)
        # Multi-process auto mode runs a FIXED collective sequence —
        # consensus allgather, then agreement allgather — on every
        # host, no matter what fails locally:
        #   1. candidate listing (never raises: store/Orbax errors
        #      contribute an empty set, so a host with a broken store
        #      still reaches the consensus collective; an exception
        #      here would make its agreement gather pair against
        #      peers' consensus gather — mismatched collectives)
        #   2. consensus step selection (collective #1)
        #   3. the fallible restore attempt; failure = a failed vote
        #   4. outcome agreement (collective #2)
        with tracing.span("ckpt.restore.select"):
            step = self._consensus_step(self._local_candidate_steps())
        state, got = None, None
        if step is not None:
            try:
                state, got = self._restore_once(target, step,
                                                extra_sources)
            except Exception as e:
                logger.warning("restore attempt failed: %s", e)
                state, got = None, None
        if not self._agree_restored(state is not None):
            if state is not None:
                logger.warning(
                    "A peer failed to restore step %s; starting "
                    "fresh everywhere for a consistent world", got,
                )
            return None, None
        return state, got

    def _local_candidate_steps(self) -> set:
        """This host's restorable-step candidates; errors yield an
        empty contribution instead of raising (see ``restore``: every
        host must reach the consensus collective)."""
        steps: set = set()
        try:
            steps |= set(dict(self._list_ram()))
        except Exception as e:
            logger.warning("RAM-tier listing failed: %s", e)
        if self._manager is not None:
            try:
                steps |= set(self._manager.all_steps() or [])
            except Exception as e:
                logger.warning("Orbax step listing failed: %s", e)
        else:
            try:
                steps |= set(
                    ckpt_store.available_steps(
                        self._store, self._process_index
                    )
                )
            except Exception as e:
                logger.warning("persist-tier listing failed: %s", e)
        if self._peer_registry is not None:
            # steps survivors still hold in RAM are candidates too:
            # the v2 loader can assemble them over /ckpt/shard even
            # when this host lost its tmpfs and the store is down
            try:
                steps |= set(self._peer_registry.advertised_steps())
            except Exception as e:
                logger.warning("peer step listing failed: %s", e)
        return steps

    def _restore_once(self, target: Any = None,
                      step: Optional[int] = None,
                      extra_sources: Optional[List[Any]] = None):
        t0 = time.time()
        # tier listing and consensus (the manifest read below too)
        with tracing.span("ckpt.restore.select"):
            ram = dict(self._list_ram())
            auto_step = step is None
            # one store scan serves both step selection and the fallback
            # candidate list (each available_steps call lists the bucket
            # and HEADs every committed step — don't do it twice); both
            # consumers are auto-mode only (an explicit step never walks
            # down), so explicit-step restores skip the scan entirely
            avail: Optional[list] = None
            if self._manager is None and auto_step:
                # an unreachable store must not kill the whole attempt:
                # the RAM and peer tiers can still restore the step
                try:
                    avail = ckpt_store.available_steps(
                        self._store, self._process_index
                    )
                except Exception as e:
                    logger.warning("persist-tier listing failed: %s", e)
                    avail = []
            if step is None:
                if self._manager is not None:
                    # the Orbax path needs the same cross-process agreement
                    # as the store path: a returning host's stale RAM tier
                    # must not out-vote the shared persistent steps
                    try:
                        orbax_steps = set(self._manager.all_steps() or [])
                    except Exception:
                        orbax_steps = set()
                    step = self._consensus_step(set(ram) | orbax_steps)
                else:
                    local_steps = set(ram) | set(avail or [])
                    step = self._consensus_step(local_steps)
        if step is None:
            return None, None
        if step in ram:
            tainted = False
            try:
                with open(ram[step], "rb") as f:
                    with tracing.span("ckpt.restore.select"):
                        man = ckpt_store.read_manifest(f)
                    # an auto-selected step saved inside an anomaly
                    # window must not be restored — the corruption the
                    # sentinel tripped on may already be in it. An
                    # explicit step is the caller's (master's) choice.
                    if auto_step and man.get("last_good") is False:
                        tainted = True
                    else:
                        state = self._restore_local_archive(
                            f, man, step, target, extra_sources
                        )
                        logger.info(
                            "Restored step %d from RAM tier", step
                        )
                        self._restored("ram", step, t0)
                        return state, step
            except Exception as e:
                logger.warning("RAM restore failed (%s); trying persistent",
                               e)
            if tainted:
                self._note_tainted(step, step, tier="ram")
        if self._manager is not None:
            import orbax.checkpoint as ocp

            if target is not None:
                ref = jax.tree.map(
                    lambda x: jax.device_get(x)
                    if isinstance(x, jax.Array) else x,
                    target,
                )
                restored = self._manager.restore(
                    step, args=ocp.args.StandardRestore(ref)
                )
                restored = jax.tree.map(
                    lambda r, t: jax.device_put(r, t.sharding)
                    if isinstance(t, jax.Array) else r,
                    restored, target,
                )
            else:
                restored = self._manager.restore(step)
            logger.info("Restored step %d from persistent tier", step)
            self._restored("persistent", step, t0, backend="orbax")
            return restored, step
        # auto-selection may land on a step whose persist shard is gone
        # (e.g. a RAM-tier step never persisted): fall back down the
        # restorable persist steps rather than restarting from scratch.
        # An EXPLICITLY requested step never falls back — the caller
        # asked for that step, not "the best available". In a
        # MULTI-PROCESS world the solo walk is disabled: one host
        # quietly restoring an older step than its peers is the mixed
        # state the consensus exists to prevent — all processes agree
        # on the outcome instead (``_agree_restored``).
        candidates = [step]
        if auto_step and self._n_processes <= 1:
            candidates += [
                s for s in reversed(avail or []) if s < step
            ]
        for cand in candidates:
            if (auto_step and
                    ckpt_store.step_last_good(self._store, cand)
                    is False):
                self._note_tainted(cand, step, tier="persistent")
                continue
            # format-v2 first: catalog from the store's step manifest
            # and/or surviving peers, shards assembled from any tier —
            # works across any topology change and with the store off
            # the critical path when peers still hold the step
            try:
                state, stats = self._restore_v2(
                    cand, target, extra_sources=extra_sources
                )
            except Exception as e:
                state, stats = None, None
                logger.info(
                    "step %d not v2-restorable (%s); trying the "
                    "monolithic path", cand, e,
                )
            if state is not None:
                tier = (
                    "peer"
                    if stats.get("peer")
                    and not stats.get("store") and not stats.get("local")
                    else "persistent"
                )
                if cand != step:
                    logger.warning(
                        "Step %d not restorable; restored older "
                        "step %d", step, cand,
                    )
                self._restored(
                    tier, cand, t0, backend="store", requested_step=step,
                )
                return state, cand
            # monolithic path: a single-proc archive readable whole
            try:
                with ckpt_store.open_step(
                    self._store, cand, self._process_index
                ) as f:
                    snapshot, _ = ckpt_store.snapshot_from_file(
                        f, target
                    )
            except (KeyError, ckpt_store.ArchiveError) as e:
                # missing OR corrupt: keep walking down — an unreadable
                # newest step must not abort the promised fallback
                if isinstance(e, ckpt_store.DigestMismatchError):
                    reason = "digest_mismatch"
                elif isinstance(e, ckpt_store.ArchiveError):
                    reason = "archive_error"
                else:
                    reason = "missing"
                record(
                    "checkpoint.restore_fallback", step=cand,
                    requested_step=step, reason=reason,
                    error=str(e)[:200],
                )
                counter(
                    "dlrover_ckpt_restore_fallbacks_total",
                    "Persist-tier restore candidates rejected during "
                    "the walk-down", ["reason"],
                ).labels(reason=reason).inc()
                logger.warning(
                    "Persist step %d unusable (%s); trying older", cand, e,
                )
                continue
            if cand != step:
                logger.warning(
                    "Step %d not restorable from persist tier; "
                    "restored older step %d", step, cand,
                )
            self._restored(
                "persistent", cand, t0, backend="store",
                requested_step=step,
            )
            return _restore_shards(snapshot, target), cand
        return None, None

    def _restored(self, tier: str, step: int, t0: float, **extra):
        """A restore served from ``tier``: remembered for the
        ``ckpt.restore`` span, observed like every checkpoint op."""
        self.last_restore_tier = tier
        _observe_ckpt("restore", tier, step, time.time() - t0, **extra)

    def _restore_local_archive(self, f, man, step: int, target,
                               extra_sources=None):
        """RAM-tier restore dispatch on the archive's topology. A
        complete single-process archive goes through the monolithic
        reader; a multi-process archive holds only this host's
        addressable shards, so the v2 planner assembles the rest from
        peers / the store."""
        topo_n = int((man.get("topology") or {}).get("n_processes", 1))
        if topo_n <= 1 and not man.get("subset"):
            snapshot, _ = ckpt_store.snapshot_from_file(f, target)
            return _restore_shards(snapshot, target)
        state, _ = self._restore_v2(
            step, target, local_file=f, extra_sources=extra_sources
        )
        return state

    def _restore_v2(self, step: int, target, local_file=None,
                    extra_sources=None):
        """Format-v2 catalog restore across the tier chain: build the
        widest catalog the surviving metadata allows (this host's
        archive manifest, peers' manifests, the store's merged step
        manifest), then assemble the CURRENT topology's needed domains
        through local -> peer -> store sources with per-shard digest
        verification. Returns ``(state, stats)``; raises when the step
        has no v2 metadata or cannot be fully assembled."""
        from dlrover_tpu.checkpoint import loader as ckpt_loader
        from dlrover_tpu.checkpoint import peer as ckpt_peer

        catalog = None
        sources: List[Any] = []
        for src in extra_sources or []:
            # the live/pre-warmed tiers outrank every checkpoint tier
            # (their bytes never left the process trust domain), but a
            # source that declares its step serves ONLY that step — a
            # walk-down candidate older than the live state must be
            # assembled from the checkpoint tiers instead
            if src is None:
                continue
            src_step = getattr(src, "step", None)
            if src_step is not None and int(src_step) != int(step):
                continue
            sources.append(src)
        if local_file is not None:
            man = ckpt_store.read_manifest(local_file)
            catalog = ckpt_loader.StepCatalog.from_archive_manifest(man)
            sources.append(ckpt_loader.LocalArchiveSource(local_file))
        peers: Dict[int, str] = {}
        if self._peer_registry is not None:
            try:
                peers = {
                    p: u
                    for p, u in self._peer_registry.peers(step).items()
                    if p != self._process_index
                }
            except Exception as e:
                logger.warning("peer lookup failed: %s", e)
                peers = {}
            for p in sorted(peers):
                try:
                    man = ckpt_peer.fetch_manifest(peers[p], step)
                except Exception as e:
                    logger.warning(
                        "peer manifest from proc %d failed: %s", p, e
                    )
                    continue
                if man is None:
                    continue
                if catalog is None:
                    catalog = ckpt_loader.StepCatalog.from_archive_manifest(
                        man
                    )
                else:
                    catalog.absorb(man)
            if peers:
                sources.append(
                    ckpt_loader.PeerSource(
                        peers, step,
                        process_index=self._process_index,
                    )
                )
        if self._store is not None:
            man2 = None
            try:
                man2 = ckpt_store.step_manifest(self._store, step)
            except Exception as e:
                logger.warning(
                    "step manifest unavailable from store: %s", e
                )
            if man2 is not None:
                store_cat = ckpt_loader.StepCatalog.from_step_manifest(
                    man2
                )
                if catalog is None:
                    catalog = store_cat
                else:
                    for k, loc in store_cat.locations.items():
                        catalog.locations.setdefault(k, loc)
                    for k, v in store_cat.digests.items():
                        catalog.digests.setdefault(k, v)
                    for k, v in store_cat.encodings.items():
                        catalog.encodings.setdefault(k, v)
                sources.append(
                    ckpt_loader.StoreSource(
                        self._store, step,
                        str(man2.get("attempt", "0")),
                        store_cat.locations,
                    )
                )
        if catalog is None:
            raise KeyError(
                f"step {step}: no format-v2 metadata reachable"
            )
        try:
            state, _, stats = ckpt_loader.restore_from_catalog(
                catalog, target, sources
            )
        finally:
            for s in sources:
                close = getattr(s, "close", None)
                if close is not None:
                    try:
                        close()
                    except Exception:
                        pass
        self.last_restore_stats = dict(stats)
        record(
            "ckpt.topology_restore", step=step,
            saved_processes=int(
                (catalog.topology or {}).get("n_processes", 1)
            ),
            restore_processes=self._n_processes,
            local=stats.get("local", 0), peer=stats.get("peer", 0),
            store=stats.get("store", 0), live=stats.get("live", 0),
            digest_mismatch=stats.get("digest_mismatch", 0),
            bytes=stats.get("bytes", 0),
        )
        return state, stats

    def _note_tainted(self, cand: int, requested: int,
                      tier: str) -> None:
        """Journal an auto-restore candidate rejected for carrying the
        ``last_good=False`` tag (saved inside a sentinel anomaly
        window) — same vocabulary as every other walk-down rejection."""
        record(
            "checkpoint.restore_fallback", step=cand,
            requested_step=requested, reason="anomaly_window",
            tier=tier,
        )
        counter(
            "dlrover_ckpt_restore_fallbacks_total",
            "Persist-tier restore candidates rejected during "
            "the walk-down", ["reason"],
        ).labels(reason="anomaly_window").inc()
        logger.warning(
            "Step %d (%s tier) was saved inside an anomaly window; "
            "skipping it for restore", cand, tier,
        )

    def _agree_restored(self, ok: bool) -> bool:
        """All-process agreement on a restore outcome (auto mode): True
        only when EVERY process succeeded — one host silently dropping
        to scratch (or an older step) while peers restore is a mixed
        world."""
        if self._n_processes <= 1:
            return ok
        try:
            import numpy as np
            from jax.experimental import multihost_utils

            flags = multihost_utils.process_allgather(
                np.asarray([1 if ok else 0], dtype=np.int32)
            )
            return bool(np.all(flags))
        except Exception as e:
            logger.warning("restore agreement check failed: %s", e)
            return ok

    def close(self):
        """Flush every in-flight save, then stop the pipeline threads.
        Idempotent; the instance refuses new saves afterwards."""
        self.wait()
        with self._workers_lock:
            if self._closed:
                return
            self._closed = True
            serializer, self._serializer = self._serializer, None
            persistq, self._persistq = self._persistq, None
        if serializer is not None:
            serializer.close()
        if persistq is not None:
            persistq.close()
        if serializer is not None or persistq is not None:
            try:
                atexit.unregister(self._atexit_flush)
            except Exception:
                pass
        if self._manager is not None:
            self._manager.close()
