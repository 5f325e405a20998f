"""Elastic shm data path: master-sharded coworker producers -> ring ->
device prefetch, feeding the flagship trainer.

Parity reference: atorch/atorch/data/shm_context.py:527
(create_coworker_shm_context — coworker pods preprocess and publish
batches over shared memory) combined with the dynamic-sharding client
(dlrover/python/elastic_agent/sharding/client.py).

TPU shape: each coworker PROCESS owns a gRPC ShardingClient and pulls
disjoint sample-range shards from the master's TaskManager (elastic: a
dead coworker's unacked shards are recycled to the others), materializes
batches with a user ``batch_fn``, and pushes them into the C++ shm ring.
The trainer pops ready batches and ``DevicePrefetch`` keeps transfers in
flight — the host never blocks the TPU step on IO or preprocessing.
"""

import os
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Iterator, Optional

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.data.shm_dataloader import DevicePrefetch, ShmDataLoader
from dlrover_tpu.telemetry import tracing


@dataclass
class _ShardedProducer:
    """Picklable zero-arg callable run inside each coworker process:
    fetch shards from the master, yield ``batch_fn(start, end)``."""

    batch_fn: Callable[[int, int], Any]
    dataset_name: str
    batch_size: int
    dataset_size: int
    num_epochs: int
    shuffle: bool
    num_minibatches_per_shard: int
    master_addr: Optional[str]
    fetch_batch: Optional[int] = None
    lookahead: Optional[int] = None

    def __call__(self) -> Iterable[Any]:
        # built here (not in the trainer) so every producer has its own
        # channel; the master hands out disjoint shards
        from dlrover_tpu.agent.master_client import build_master_client
        from dlrover_tpu.agent.sharding.client import ShardingClient

        client = build_master_client(self.master_addr)
        sharding = ShardingClient(
            dataset_name=self.dataset_name,
            batch_size=self.batch_size,
            num_epochs=self.num_epochs,
            dataset_size=self.dataset_size,
            shuffle=self.shuffle,
            num_minibatches_per_shard=self.num_minibatches_per_shard,
            master_client=client,
            fetch_batch=self.fetch_batch,
            lookahead=self.lookahead,
        )
        while True:
            shard = sharding.fetch_shard()
            if shard is None:
                return
            yield self.batch_fn(shard.start, shard.end)
            sharding.report_batch_done()


class ElasticShmDataLoader:
    """Master-coordinated elastic data loading over the shm ring.

    Args:
      batch_fn: ``batch_fn(start, end) -> batch pytree`` materializing
        the samples of one shard (read from disk / tokenize / augment) —
        runs in the coworker processes.
      dataset_name/batch_size/dataset_size/num_epochs: registered with
        the master's dataset manager (shards of ``batch_size`` samples).
      num_workers: coworker producer processes.
      sharding (optional): jax sharding for DevicePrefetch placement.
      transform (optional): per-batch reshape (e.g. the trainer's
        microbatch split) run on the prefetch thread, off the train
        loop.
      fetch_batch/lookahead (optional): per-producer shard dispatch
        batching and lookahead window (see ShardingClient; None takes
        its FETCH_BATCH / LOOKAHEAD).
    """

    def __init__(
        self,
        batch_fn: Callable[[int, int], Any],
        dataset_name: str,
        batch_size: int,
        dataset_size: int,
        num_epochs: int = 1,
        shuffle: bool = False,
        num_workers: int = 2,
        num_minibatches_per_shard: int = 1,
        master_addr: Optional[str] = None,
        slot_bytes: int = 64 << 20,
        num_slots: int = 8,
        prefetch_depth: int = 2,
        sharding=None,
        transform: Optional[Callable[[Any], Any]] = None,
        fetch_batch: Optional[int] = None,
        lookahead: Optional[int] = None,
    ):
        from dlrover_tpu.common.constants import NodeEnv

        master_addr = (master_addr
                       or os.environ.get(NodeEnv.MASTER_ADDR, "")
                       or None)
        producer = _ShardedProducer(
            batch_fn=batch_fn,
            dataset_name=dataset_name,
            batch_size=batch_size,
            dataset_size=dataset_size,
            num_epochs=num_epochs,
            shuffle=shuffle,
            num_minibatches_per_shard=num_minibatches_per_shard,
            master_addr=master_addr,
            fetch_batch=fetch_batch,
            lookahead=lookahead,
        )
        with tracing.span("boot.data_plane", {
            "coworkers": num_workers, "slots": num_slots,
        }):
            self._loader = ShmDataLoader(
                producer,
                num_workers=num_workers,
                slot_bytes=slot_bytes,
                num_slots=num_slots,
                pre_sharded=True,  # disjointness comes from the master
            )
            self._prefetch = DevicePrefetch(
                self._loader, depth=prefetch_depth, sharding=sharding,
                transform=transform,
            )
        logger.info(
            "ElasticShmDataLoader: %d coworkers, dataset=%s size=%d "
            "batch=%d", num_workers, dataset_name, dataset_size,
            batch_size,
        )

    def __iter__(self) -> Iterator[Any]:
        return iter(self._prefetch)

    def shutdown(self):
        # order matters: EOF the ring so the prefetch thread's pop()
        # returns, JOIN it, and only then unmap/destroy the ring — the
        # thread shares this process's mapping and unmapping under a
        # live pop() is a SIGSEGV (observed in the llama system e2e
        # with never-ending producers). Idempotent; if the fill thread
        # won't die in time, leak the segment rather than crash.
        if getattr(self, "_shut", False):
            return
        self._shut = True
        self._loader.close()
        joined = self._prefetch.join()
        if not joined:
            logger.error(
                "prefetch thread still alive at shutdown; leaking the "
                "shm ring instead of unmapping under it"
            )
        self._loader.shutdown(destroy=joined)
