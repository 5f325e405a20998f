"""Coworker data loading over the native shm ring + device prefetch.

Parity reference: atorch/atorch/data/shm_dataloader.py:138
(ShmDataloader), shm_context.py:527 (create_coworker_shm_context), and
preloader.py:8 (GpuPreLoader — async H2D with a CUDA stream).

TPU shape: coworker PROCESSES (CPU pods / extra host processes) produce
batches into the C++ shm ring; the trainer iterates them; DevicePrefetch
keeps N batches in flight to the TPU with ``jax.device_put`` (dispatch is
async in JAX — overlap comes free; the buffer bounds host memory).
"""

import contextlib
import multiprocessing as mp
import os
import threading
from queue import Queue
from typing import Any, Callable, Iterable, Iterator, Optional

import jax

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.data.shm_ring import RingClosed, ShmRing


@contextlib.contextmanager
def _spawn_env_pinned_to_cpu():
    """A spawned coworker re-imports the training script as
    ``__mp_main__``, jax and all. Its parent holds the chip, and a
    chip belongs to one process: whatever the child does with jax, it
    does on the CPU. (jax read the parent's own value at import.)"""
    was = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if was is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = was


def _producer_main(ring_name: str, dataset_fn, worker_id: int,
                   num_workers: int, pre_sharded: bool):
    """Runs in a coworker process: iterate dataset_fn(), push batches.

    With ``pre_sharded`` each worker's dataset_fn already yields a
    disjoint stream (e.g. master-coordinated shards via ShardingClient)
    and the round-robin filter is skipped."""
    ring = ShmRing.attach(ring_name)
    # a full ring is a consumer busy elsewhere (its step program
    # compiles for longer than a push waits): wait on for as long as
    # the consumer, this process's parent, is there
    parent = os.getppid()
    try:
        for i, batch in enumerate(dataset_fn()):
            if not pre_sharded and i % num_workers != worker_id:
                continue
            ring.push(
                batch, keep_waiting=lambda: os.getppid() == parent
            )
    except RingClosed:
        pass
    except Exception as e:  # pragma: no cover - crash path
        logger.error("shm producer %d failed: %s", worker_id, e)


class ShmDataLoader:
    """Iterate batches produced by coworker processes over the shm ring.

    ``dataset_fn`` must be a picklable zero-arg callable returning an
    iterable of batches (numpy arrays / tuples / pytrees).
    """

    def __init__(
        self,
        dataset_fn: Callable[[], Iterable],
        num_workers: int = 1,
        slot_bytes: int = 64 << 20,
        num_slots: int = 8,
        name: Optional[str] = None,
        pre_sharded: bool = False,
    ):
        # pid + random suffix: id(self) repeats across processes, and
        # create() unlinks same-named stale segments — two jobs on one
        # host must never collide on the default name
        default_name = (
            f"/dlrover_shm_{os.getpid():x}_{os.urandom(4).hex()}"
        )
        self._ring = ShmRing(
            name or default_name,
            slot_bytes=slot_bytes, num_slots=num_slots, create=True,
        )
        ctx = mp.get_context("spawn")
        self._procs = [
            ctx.Process(
                target=_producer_main,
                args=(self._ring.name, dataset_fn, w, num_workers,
                      pre_sharded),
                daemon=True,
            )
            for w in range(num_workers)
        ]
        with _spawn_env_pinned_to_cpu():
            for p in self._procs:
                p.start()
        self._watcher = threading.Thread(
            target=self._close_when_done, daemon=True,
            name="shm-ring-watcher",
        )
        self._watcher.start()

    def _close_when_done(self):
        for p in self._procs:
            p.join()
        self._ring.close()  # EOF after every producer finished

    def __iter__(self) -> Iterator[Any]:
        while True:
            try:
                yield self._ring.pop()
            except RingClosed:
                return

    def close(self):
        """EOF the ring: blocked consumers drain and see RingClosed."""
        self._ring.close()

    def shutdown(self, destroy: bool = True):
        self._ring.close()
        for p in self._procs:
            if p.is_alive():
                p.terminate()
        for p in self._procs:
            p.join(timeout=5.0)
        # the watcher thread calls ring.close() after the producers
        # exit; let it finish before unmapping the ring under it
        self._watcher.join(timeout=10.0)
        if destroy:
            if self._watcher.is_alive():
                logger.error(
                    "shm watcher still alive; leaking ring %s instead "
                    "of unmapping under a live thread", self._ring.name,
                )
                return
            self._ring.destroy()


class DevicePrefetch:
    """Wrap a batch iterator, keeping ``depth`` batches in flight on
    device (parity: GpuPreLoader preloader.py:8 — the CUDA-stream H2D
    overlap maps to JAX's async device_put dispatch).

    ``transform`` (e.g. the trainer's microbatch reshape) runs on the
    fill thread, between fetching a batch from the source and staging
    it to device — the train loop only ever dequeues device-ready
    batches. A producer exception (failed transform/device_put, or the
    source iterator raising) is re-raised in the CONSUMING iterator
    instead of truncating the epoch into a silent EOF."""

    def __init__(self, it: Iterable, depth: int = 2, sharding=None,
                 transform: Optional[Callable[[Any], Any]] = None):
        self._it = iter(it)
        self._depth = depth
        self._sharding = sharding
        self._transform = transform
        self._queue: "Queue" = Queue(maxsize=depth)
        self._done = object()
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._fill, daemon=True, name="prefetch-fill"
        )
        self._thread.start()

    def _put_device(self, batch):
        if self._sharding is not None:
            return jax.tree.map(
                lambda x: jax.device_put(x, self._sharding), batch
            )
        return jax.tree.map(jax.device_put, batch)

    def _fill(self):
        from dlrover_tpu.telemetry import tracing

        try:
            while True:
                with tracing.span("data.fetch"):
                    try:
                        batch = next(self._it)
                    except StopIteration:
                        break
                with tracing.span("data.stage"):
                    if self._transform is not None:
                        batch = self._transform(batch)
                    staged = self._put_device(batch)
                self._queue.put(staged)
        except BaseException as e:
            self._error = e
        finally:
            self._queue.put(self._done)

    def _check_error(self):
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def __iter__(self):
        from queue import Empty

        while True:
            try:
                item = self._queue.get(timeout=0.5)
            except Empty:
                # resilient to a swallowed _done sentinel (join()'s
                # drain) — a dead fill thread means the stream is over
                if not self._thread.is_alive():
                    self._check_error()
                    return
                continue
            if item is self._done:
                self._check_error()
                return
            yield item

    def join(self, timeout: float = 10.0) -> bool:
        """Wait for the fill thread to exit (it does once the source
        iterator ends, e.g. after the shm ring is closed). MUST be
        called before destroying a ring this prefetcher reads: pop()
        runs in this thread against the ring's mapping, and unmapping
        under it is a native crash, not an exception. Drains the queue
        while waiting so a fill thread blocked in put() (consumer
        stopped early) can reach the source's EOF. Returns False if the
        thread is still alive at the deadline — the caller must then
        NOT unmap the source."""
        import time as _time

        deadline = _time.monotonic() + timeout
        while self._thread.is_alive():
            if _time.monotonic() > deadline:
                return False
            try:
                self._queue.get_nowait()
            except Exception:
                pass
            self._thread.join(timeout=0.05)
        return True
