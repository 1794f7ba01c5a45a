"""DataLoader (reference: python/paddle/io/reader.py:216 DataLoader).

Host-side loading with a thread-pool prefetcher: workers run `dataset[i]` +
collate concurrently while the accelerator computes, the TPU-idiomatic
replacement for the reference's multiprocess shared-memory loader (device
transfer is XLA's job; `jnp.asarray` in collate is async).
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..observability import (enabled as _obs_enabled,
                             histogram as _obs_histogram)
from ..observability import tracing as _tracing
from .dataset import Dataset, IterableDataset
from .sampler import BatchSampler

__all__ = ["DataLoader", "default_collate_fn", "get_worker_info"]

_worker_info = threading.local()

# Input-pipeline telemetry (paddle_tpu.observability): per-batch WAIT time
# (the training loop blocked on the loader — a hot wait histogram means the
# input pipeline, not the device, bounds step time) vs the consumer's
# COMPUTE time between batches. Finer low-end buckets than the default
# latency ladder: a healthy prefetched loader waits microseconds.
_IO_BUCKETS = (1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 0.01, 0.025,
               0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0)
_OBS_WAIT = _obs_histogram(
    "paddle_tpu_io_batch_wait_seconds",
    "time the consumer blocked waiting for the next batch",
    buckets=_IO_BUCKETS)
_OBS_COMPUTE = _obs_histogram(
    "paddle_tpu_io_compute_seconds",
    "consumer time between batches (compute the loader must hide under)",
    buckets=_IO_BUCKETS)


def get_worker_info():
    """WorkerInfo inside a loader worker (process or thread), else None."""
    from .worker import get_worker_info as _mp_info
    info = _mp_info()
    if info is not None:
        return info
    return getattr(_worker_info, "info", None)


def default_collate_fn(batch):
    """Stack samples into batched Tensors (reference:
    python/paddle/io/dataloader/collate.py)."""
    from ..core.tensor import Tensor, to_tensor
    sample = batch[0]
    if isinstance(sample, Tensor):
        import jax.numpy as jnp
        return Tensor(jnp.stack([s._data for s in batch]))
    if isinstance(sample, np.ndarray):
        return to_tensor(np.stack(batch))
    if isinstance(sample, (int, float, np.number)):
        return to_tensor(np.array(batch))
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, dict):
        return {k: default_collate_fn([s[k] for s in batch]) for k in sample}
    if isinstance(sample, (tuple, list)):
        return type(sample)(default_collate_fn(list(items))
                            for items in zip(*batch))
    raise TypeError(f"unsupported sample type {type(sample)}")


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None, return_list=True,
                 batch_sampler=None, batch_size=1, shuffle=False, drop_last=False,
                 collate_fn=None, num_workers=0, use_buffer_reader=True,
                 prefetch_factor=2, use_shared_memory=True, timeout=0,
                 worker_init_fn=None, persistent_workers=False, seed=None):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = max(prefetch_factor, 1)
        self.worker_init_fn = worker_init_fn
        self.timeout = timeout
        # num_workers>0 defaults to forked worker processes (reference
        # semantics); use_buffer_reader=False keeps the in-process thread
        # pool instead (e.g. datasets holding device arrays, which must not
        # cross a fork)
        self.use_multiprocess = use_buffer_reader
        self.use_shared_memory = use_shared_memory
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.seed = seed
        self._iterable = isinstance(dataset, IterableDataset)
        self._custom_batch_sampler = batch_sampler is not None
        if self._iterable:
            self.batch_sampler = None
            self.batch_size = batch_size
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = getattr(batch_sampler, "batch_size", None)
        elif batch_size is None:
            self.batch_sampler = None
            self.batch_size = None
        else:
            self.batch_size = batch_size
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)
        # checkpointable iteration (opt-in via seed=): a single monotone
        # consumed-batch counter is the whole cursor; epoch and within-epoch
        # position are derived by divmod against the fixed per-epoch batch
        # count, and every epoch's order is a pure function of (seed, epoch)
        self._checkpointable = seed is not None and not self._iterable
        self._consumed_total = 0
        self._replay_budget = 0
        self._live = None
        if self._checkpointable:
            self._epoch_batches = self._count_epoch_batches()
            from . import state as _state
            _state.register(self)

    def _count_epoch_batches(self) -> int:
        if self._custom_batch_sampler:
            return len(self.batch_sampler)
        n = len(self.dataset)
        bs = self.batch_size or 1
        nb = n // bs if self.drop_last else (n + bs - 1) // bs
        if nb < 1:
            raise ValueError("dataset yields zero batches per epoch")
        return nb

    def __len__(self):
        if self._iterable:
            raise TypeError("IterableDataset has no length")
        if self.batch_sampler is None:
            return len(self.dataset)
        return len(self.batch_sampler)

    # -- checkpointable-iterator state ---------------------------------------

    @property
    def consumed(self) -> int:
        """Batches delivered to the consumer, monotone across epochs."""
        return self._consumed_total

    def in_flight(self) -> int:
        """Batches materialized by the active backend (worker processes or
        thread pool) but not yet delivered to the consumer."""
        live = self._live
        if live is None:
            return 0
        try:
            return int(live["inflight"]())
        except Exception:
            return 0

    def set_epoch(self, epoch: int) -> None:
        """Jump the cursor to the start of ``epoch`` (checkpointable mode);
        also forwarded to a custom batch sampler that supports it."""
        if self._checkpointable:
            self._consumed_total = int(epoch) * self._epoch_batches
            self._replay_budget = 0
        if self.batch_sampler is not None and \
                hasattr(self.batch_sampler, "set_epoch"):
            self.batch_sampler.set_epoch(epoch)

    def state_dict(self) -> dict:
        """Resumable iterator state. Requires checkpointable mode (map-style
        dataset + ``seed=``): the cursor is only meaningful when every
        epoch's order is reproducible from (seed, epoch)."""
        from . import state as _state
        if self._iterable:
            raise _state.IteratorStateError(
                "IterableDataset streams have no replayable cursor; wrap a "
                "map-style source (e.g. ShardedDataset) for checkpointable "
                "input")
        if not self._checkpointable:
            raise _state.IteratorStateError(
                "pass seed= to DataLoader to enable checkpointable "
                "iteration (deterministic epoch order is required for "
                "exactly-once resume)")
        from .sharding import ShardedDataset
        shard = self.dataset.state() \
            if isinstance(self.dataset, ShardedDataset) else None
        eb = self._epoch_batches
        c = self._consumed_total
        return {"version": _state.STATE_VERSION, "consumed": c,
                "epoch": c // eb, "cursor": c % eb,
                "seed": self.seed, "shuffle": self.shuffle,
                "batch_size": self.batch_size, "drop_last": self.drop_last,
                "dataset_len": len(self.dataset), "epoch_batches": eb,
                "shard": shard, "inflight": self.in_flight()}

    def load_state_dict(self, sd: dict) -> None:
        """Restore the cursor from :meth:`state_dict`.

        Exactly-once semantics: ``consumed`` counts only batches the
        training loop actually received, so restoring replays precisely the
        batches that were speculative (in worker queues) at save time —
        their count is taken from the saved ``inflight`` and reported via
        ``paddle_tpu_data_resume_replayed_total``. If a live iterator
        exists, its in-flight batches are abandoned (they belong to the
        abandoned timeline) and counted as
        ``paddle_tpu_data_resume_discarded_total``; the active ``for`` loop
        over this loader ends, and the next ``iter()`` resumes at the
        restored cursor.
        """
        from . import state as _state
        if not self._checkpointable:
            raise _state.IteratorStateError(
                "load_state_dict requires checkpointable mode "
                "(map-style dataset + seed=)")
        if sd.get("version") != _state.STATE_VERSION:
            raise _state.IteratorStateError(
                f"unsupported iterator state version {sd.get('version')!r} "
                f"(this build reads {_state.STATE_VERSION})")
        if int(sd.get("dataset_len", -1)) != len(self.dataset) or \
                int(sd.get("epoch_batches", -1)) != self._epoch_batches:
            raise _state.IteratorStateError(
                f"iterator geometry changed: saved "
                f"{sd.get('dataset_len')} samples / "
                f"{sd.get('epoch_batches')} batches per epoch, loader has "
                f"{len(self.dataset)} / {self._epoch_batches}")
        if sd.get("seed") != self.seed or \
                bool(sd.get("shuffle")) != bool(self.shuffle):
            raise _state.IteratorStateError(
                f"shuffle/seed mismatch: saved seed={sd.get('seed')} "
                f"shuffle={sd.get('shuffle')}, loader has seed={self.seed} "
                f"shuffle={self.shuffle} — resumed order would diverge")
        from .sharding import ShardedDataset
        shard = self.dataset.state() \
            if isinstance(self.dataset, ShardedDataset) else None
        if sd.get("shard") != shard:
            raise _state.IteratorStateError(
                f"shard assignment changed: saved {sd.get('shard')}, "
                f"loader has {shard} — rescaling requires re-dealing the "
                f"stream from an epoch boundary (set_epoch), not a cursor "
                f"restore")
        live, self._live = self._live, None
        if live is not None:
            # invalidate only — the stale generator discards its next pull
            # and tears its backend down on close (bounded); shutting the
            # backend down here could strand a pull already blocked on it
            try:
                discarded = int(live["inflight"]())
            except Exception:
                discarded = 0
            if discarded:
                _state.OBS_RESUME_DISCARDED.inc(discarded)
        self._consumed_total = int(sd["consumed"])
        self._replay_budget = max(int(sd.get("inflight") or 0), 0)

    def _epoch_index_batches(self, epoch: int):
        """Index batches for one epoch, a pure function of (seed, epoch)."""
        if self._custom_batch_sampler:
            if hasattr(self.batch_sampler, "set_epoch"):
                self.batch_sampler.set_epoch(epoch)
            yield from self.batch_sampler
            return
        n = len(self.dataset)
        if self.shuffle:
            order = np.random.default_rng([self.seed, epoch]).permutation(n)
        else:
            order = np.arange(n)
        bs = self.batch_size or 1
        for s in range(0, n, bs):
            chunk = order[s:s + bs]
            if len(chunk) < bs and self.drop_last:
                return
            yield chunk.tolist()

    def _index_batches(self):
        if self._iterable:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                yield batch  # already samples, not indices
        elif self.batch_sampler is None:
            for i in range(len(self.dataset)):
                yield [i]
        else:
            yield from self.batch_sampler

    def _fetch(self, batch):
        if self._iterable:
            samples = batch
        else:
            samples = [self.dataset[i] for i in batch]
        return self.collate_fn(samples)

    def _np_tree_to_tensors(self, data):
        """Numpy tree from a worker process -> Tensor tree on device."""
        from ..core.tensor import to_tensor
        if isinstance(data, np.ndarray):
            return to_tensor(data)
        if isinstance(data, dict):
            return {k: self._np_tree_to_tensors(v) for k, v in data.items()}
        if isinstance(data, (tuple, list)):
            return type(data)(self._np_tree_to_tensors(v) for v in data)
        return data

    def __iter__(self):
        it = self._checkpointable_iter() if self._checkpointable \
            else self._plain_iter()
        if not _obs_enabled() and not _tracing.tracing_enabled():
            yield from it
            return
        # wait/compute split: time blocked in next() is loader wait; time
        # between our yield returning and the consumer asking again is the
        # consumer's compute the prefetcher must hide under
        prev_yield = None
        while True:
            t0 = time.perf_counter()
            with _tracing.span("io.next"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            now = time.perf_counter()
            _OBS_WAIT.observe(now - t0)
            if prev_yield is not None:
                _OBS_COMPUTE.observe(t0 - prev_yield)
            yield batch
            prev_yield = time.perf_counter()

    def _plain_iter(self):
        from ..resilience import faults as _faults
        from . import state as _state
        for batch in self._iter_batches():
            _faults.on_loader_next()
            _state.OBS_BATCHES.inc()
            yield batch

    def _checkpointable_iter(self):
        """One epoch's worth of batches, resuming at the saved cursor.

        Each ``iter()`` covers the REMAINDER of the current epoch (a fresh
        loop after a mid-epoch restore finishes that epoch, then the next
        loop starts the following one). The consumed counter advances only
        when a batch is actually handed to the consumer — speculative
        batches sitting in worker queues are never counted, which is what
        makes the cursor exact under multi-worker prefetch. A
        load_state_dict while this iterator is live invalidates it: the
        next pull ends the loop instead of yielding a stale-timeline batch.
        """
        from ..resilience import faults as _faults
        from . import state as _state
        eb = self._epoch_batches
        epoch = self._consumed_total // eb
        cursor = self._consumed_total % eb
        live = {"inflight": lambda: 0}
        self._live = live
        batches = itertools.islice(self._epoch_index_batches(epoch),
                                   cursor, None)
        try:
            for batch in self._iter_batches(batches, live):
                if self._live is not live:
                    return  # invalidated by load_state_dict mid-iteration
                _faults.on_loader_next()
                self._consumed_total += 1
                _state.OBS_BATCHES.inc()
                if self._replay_budget > 0:
                    self._replay_budget -= 1
                    _state.OBS_RESUME_REPLAYED.inc()
                yield batch
            if self._live is live:
                _state.OBS_EPOCHS.inc()
        finally:
            if self._live is live:
                self._live = None

    def _iter_batches(self, batches=None, live=None):
        if batches is None:
            batches = self._index_batches()
        if self.num_workers == 0:
            for batch in batches:
                yield self._fetch(batch)
            return
        if self.use_multiprocess:
            # reference io/reader.py:216 semantics: num_workers>0 = forked
            # worker processes, numpy collate in-worker, shm transport for
            # large arrays, ordered reassembly in the parent
            from .worker import MultiprocessLoaderIter, np_collate
            collate = np_collate if self.collate_fn is default_collate_fn \
                else self.collate_fn
            mp_iter = MultiprocessLoaderIter(
                self.dataset,
                [] if self._iterable else batches,
                self.num_workers, collate, self._np_tree_to_tensors,
                prefetch_factor=self.prefetch_factor,
                worker_init_fn=self.worker_init_fn,
                timeout=self.timeout, iterable=self._iterable,
                batch_size=self.batch_size,
                use_shm=self.use_shared_memory)
            if live is not None:
                live["inflight"] = mp_iter.in_flight
            yield from mp_iter
            return
        # thread-pool prefetch pipeline
        with ThreadPoolExecutor(max_workers=self.num_workers) as pool:
            if self.worker_init_fn is not None:
                for w in range(self.num_workers):
                    pool.submit(self.worker_init_fn, w)
            depth = self.num_workers * self.prefetch_factor
            batches = iter(batches)
            pending = queue.Queue()
            if live is not None:
                live["inflight"] = pending.qsize
            for batch in itertools.islice(batches, depth):
                pending.put(pool.submit(self._fetch, batch))
            while not pending.empty():
                fut = pending.get()
                for batch in itertools.islice(batches, 1):
                    pending.put(pool.submit(self._fetch, batch))
                yield fut.result()
