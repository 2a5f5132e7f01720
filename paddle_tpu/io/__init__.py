# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""paddle.io: Dataset / DataLoader (reference: python/paddle/fluid/reader.py:273
DataLoader, fluid/dataloader/ worker.py + batch_sampler.py + dataset.py).

Multiprocess workers feed batches through queues; a background prefetch
thread keeps a buffer ahead of the consumer — the host-side half of the
infeed pipeline (the reference's buffered_reader.cc double-buffering is the
device half; on TPU, jax device_put overlap covers it).
"""
from __future__ import annotations

import itertools
import math
import os
import queue as queue_mod
import time
import threading
from typing import Iterable, List, Optional

import numpy as np

from ..core.tensor import Tensor, to_tensor

__all__ = [
    "Dataset", "IterableDataset", "TensorDataset", "ComposeDataset",
    "ConcatDataset", "ChainDataset", "Subset", "random_split", "Sampler",
    "SequenceSampler", "RandomSampler", "WeightedRandomSampler",
    "BatchSampler", "DistributedBatchSampler", "DataLoader",
    "DeviceLoader", "get_worker_info", "default_collate_fn",
]


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset does not support indexing")

    def __len__(self):
        raise RuntimeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __getitem__(self, idx):
        out = []
        for ds in self.datasets:
            sample = ds[idx]
            out.extend(sample if isinstance(sample, (list, tuple)) else [sample])
        return tuple(out)

    def __len__(self):
        return min(len(ds) for ds in self.datasets)


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cumulative_sizes = list(itertools.accumulate(
            len(d) for d in self.datasets))

    def __getitem__(self, idx):
        if idx < 0:
            idx += len(self)
        ds_idx = np.searchsorted(self.cumulative_sizes, idx, side="right")
        prev = self.cumulative_sizes[ds_idx - 1] if ds_idx else 0
        return self.datasets[ds_idx][idx - prev]

    def __len__(self):
        return self.cumulative_sizes[-1]


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        for ds in self.datasets:
            yield from ds


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    if all(isinstance(l, float) for l in lengths):
        total = len(dataset)
        lengths = [int(math.floor(total * l)) for l in lengths]
        lengths[-1] = total - sum(lengths[:-1])
    if sum(lengths) != len(dataset):
        raise ValueError("lengths must sum to dataset size")
    perm = np.random.permutation(len(dataset))
    out, offset = [], 0
    for n in lengths:
        out.append(Subset(dataset, perm[offset:offset + n].tolist()))
        offset += n
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(self.weights), self.num_samples,
                               replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False, batch_size=1,
                 drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Shards the index space across data-parallel ranks (reference:
    python/paddle/fluid/dataloader/batch_sampler.py:168)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        from ..distributed import get_rank, get_world_size

        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas if num_replicas is not None \
            else get_world_size()
        self.local_rank = rank if rank is not None else get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(math.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
        else:
            indices = list(range(n))
        indices += indices[: self.total_size - n]
        indices = indices[self.local_rank::self.nranks]
        batch = []
        for idx in indices:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


class WorkerInfo:
    def __init__(self, id, num_workers, dataset, seed):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = seed


_worker_info = None


def get_worker_info():
    return _worker_info


def default_collate_fn(batch):
    """Stack a list of samples into batched Tensors."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return to_tensor(np.stack([s.numpy() for s in batch]))
    if isinstance(sample, np.ndarray):
        return to_tensor(np.stack(batch))
    if isinstance(sample, (int, np.integer)):
        return to_tensor(np.asarray(batch, np.int64))
    if isinstance(sample, (float, np.floating)):
        return to_tensor(np.asarray(batch, np.float32))
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return [default_collate_fn(list(s)) for s in transposed]
    if isinstance(sample, dict):
        return {k: default_collate_fn([s[k] for s in batch]) for k in sample}
    return batch


def _worker_loop(dataset, index_queue, data_queue, collate_fn, worker_id,
                 num_workers, seed, arena=None):
    global _worker_info
    _worker_info = WorkerInfo(worker_id, num_workers, dataset, seed)
    np.random.seed(seed)
    while True:
        item = index_queue.get()
        if item is None:
            break
        task_id, indices = item
        try:
            samples = [dataset[i] for i in indices]
            batch = collate_fn(samples)
            batch = _to_numpy_tree(batch)
            if arena is not None:
                from .shm import pack_tree

                batch = pack_tree(batch, arena)
            data_queue.put((task_id, batch, None))
        except Exception as e:  # propagate worker errors
            data_queue.put((task_id, None, e))


def _to_numpy_tree(obj):
    if isinstance(obj, Tensor):
        return obj.numpy()
    if isinstance(obj, (list, tuple)):
        return [_to_numpy_tree(v) for v in obj]
    if isinstance(obj, dict):
        return {k: _to_numpy_tree(v) for k, v in obj.items()}
    return obj


def _to_tensor_tree(obj):
    if isinstance(obj, np.ndarray):
        return to_tensor(obj)
    if isinstance(obj, (list, tuple)):
        return [_to_tensor_tree(v) for v in obj]
    if isinstance(obj, dict):
        return {k: _to_tensor_tree(v) for k, v in obj.items()}
    return obj


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.use_shared_memory = use_shared_memory
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle,
                batch_size=batch_size if batch_size is not None else 1,
                drop_last=drop_last)
            if batch_size is None:
                self.batch_sampler = None  # no auto-batching

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no len()")
        if self.batch_sampler is None:
            return len(self.dataset)
        return len(self.batch_sampler)

    def __iter__(self):
        if self._iterable_mode:
            return self._iter_iterable()
        if self.num_workers == 0:
            return self._iter_single()
        return self._iter_multiprocess()

    def _iter_iterable(self):
        batch = []
        for sample in self.dataset:
            batch.append(sample)
            if len(batch) == self.batch_size:
                yield self.collate_fn(batch)
                batch = []
        if batch and not self.drop_last:
            yield self.collate_fn(batch)

    def _iter_single(self):
        if self.batch_sampler is None:
            for i in range(len(self.dataset)):
                yield self.dataset[i]
            return
        for indices in self.batch_sampler:
            yield self.collate_fn([self.dataset[i] for i in indices])

    def _iter_multiprocess(self):
        import multiprocessing as mp

        # fork is only safe while JAX has no live non-CPU backend: the TPU
        # client owns threads+locks that deadlock a forked child (the
        # reference hits the same with CUDA contexts and also switches to
        # spawn-style workers).  spawn children are exec-fresh and read the
        # parent env at start() time; worker payloads (dataset, collate_fn)
        # must then be picklable.
        method = os.environ.get("PT_DATALOADER_START_METHOD")
        if method is None:
            # private, but the only way to ask without initializing a
            # backend; on jax 0.9.0 a dict keyed by live platform names
            from jax._src import xla_bridge as _xb

            unsafe = any(k != "cpu" for k in _xb._backends)
            method = "spawn" if unsafe else "fork"
        ctx = mp.get_context(method)
        index_queues = [ctx.SimpleQueue() for _ in range(self.num_workers)]
        data_queue = ctx.Queue()
        arena = None
        workers = []
        # Keep worker processes off the accelerator: they produce host
        # batches only, and a fresh child dialing the TPU client would race
        # the parent for the chip.  (fork children never re-init JAX, so the
        # env is only mutated for exec-fresh start methods.)
        saved_platforms = os.environ.get("JAX_PLATFORMS")
        try:
            if method != "fork":
                os.environ["JAX_PLATFORMS"] = "cpu"
            # Shared-memory transport (reference: use_shared_memory + the
            # mmap allocator): fork workers inherit the arena mapping;
            # spawn workers re-attach by name when unpickling it.
            if self.use_shared_memory:
                from . import shm

                if shm.shm_available():
                    try:
                        arena = shm.ShmArena()
                    except Exception:
                        arena = None
            for wid in range(self.num_workers):
                w = ctx.Process(
                    target=_worker_loop,
                    args=(self.dataset, index_queues[wid], data_queue,
                          self.collate_fn, wid, self.num_workers,
                          np.random.randint(0, 2 ** 31), arena),
                    daemon=True)
                w.start()
                workers.append(w)
        except BaseException:
            for w in workers:
                w.terminate()
            if arena is not None:
                arena.destroy()
            raise
        finally:
            if method != "fork":
                if saved_platforms is not None:
                    os.environ["JAX_PLATFORMS"] = saved_platforms
                else:
                    os.environ.pop("JAX_PLATFORMS", None)

        try:
            batches = list(self.batch_sampler)
            n_tasks = len(batches)
            # dispatch up to prefetch_factor batches per worker ahead
            next_task = 0
            inflight = 0
            results = {}
            want = 0
            max_inflight = self.num_workers * self.prefetch_factor
            while next_task < n_tasks and inflight < max_inflight:
                index_queues[next_task % self.num_workers].put(
                    (next_task, batches[next_task]))
                next_task += 1
                inflight += 1
            while want < n_tasks:
                while want not in results:
                    # Liveness-aware get: a worker that dies before putting
                    # (unpicklable payload, failed arena attach, OOM-kill)
                    # must raise here, not hang the training loop.
                    # timeout in (None, 0) = no deadline (reference
                    # convention); the dead-worker liveness check still
                    # runs every second either way.
                    deadline = (time.monotonic() + self.timeout
                                if self.timeout else None)
                    while True:
                        try:
                            task_id, data, err = data_queue.get(timeout=1)
                            break
                        except queue_mod.Empty:
                            dead = [w for w in workers if not w.is_alive()]
                            if dead:
                                raise RuntimeError(
                                    "DataLoader worker (pid "
                                    f"{dead[0].pid}) exited unexpectedly "
                                    f"with code {dead[0].exitcode}")
                            if (deadline is not None
                                    and time.monotonic() > deadline):
                                raise RuntimeError(
                                    f"DataLoader timed out after "
                                    f"{self.timeout}s waiting for a batch")
                    if err is not None:
                        raise err
                    results[task_id] = data
                    inflight -= 1
                    if next_task < n_tasks:
                        index_queues[next_task % self.num_workers].put(
                            (next_task, batches[next_task]))
                        next_task += 1
                        inflight += 1
                data = results.pop(want)
                if arena is not None:
                    from .shm import unpack_tree

                    data = unpack_tree(data, arena)
                yield _to_tensor_tree(data)
                want += 1
        finally:
            for q in index_queues:
                q.put(None)
            for w in workers:
                w.join(timeout=1)
                if w.is_alive():
                    w.terminate()
            if arena is not None:
                arena.destroy()


class DeviceLoader:
    """Device-prefetching wrapper: the host->HBM infeed half of the
    reference's double-buffered reader (buffered_reader.cc keeps N batches
    resident on device ahead of compute).  Wrap any iterable of batches;
    each batch is jax.device_put'd (optionally with a sharding) while the
    previous one is being consumed, so transfers overlap the step.

        for x, y in DeviceLoader(loader, buffer_size=2):
            loss = train_step(x, y)
    """

    def __init__(self, loader, buffer_size=2, sharding=None, device=None):
        self.loader = loader
        self.buffer_size = max(1, int(buffer_size))
        self.sharding = sharding
        self.device = device

    def _place(self, batch):
        import jax

        from ..core.tensor import Tensor

        target = self.sharding or self.device

        def put(v):
            raw = v._value if isinstance(v, Tensor) else v
            arr = jax.device_put(raw, target) if target is not None \
                else jax.device_put(raw)
            return Tensor(arr)

        if isinstance(batch, tuple) and hasattr(batch, "_fields"):
            return type(batch)(*map(put, batch))  # namedtuple
        if isinstance(batch, (list, tuple)):
            return type(batch)(put(v) for v in batch)
        if isinstance(batch, dict):
            return {k: put(v) for k, v in batch.items()}
        return put(batch)

    def __iter__(self):
        from collections import deque

        buf = deque()
        it = iter(self.loader)
        try:
            for _ in range(self.buffer_size):
                buf.append(self._place(next(it)))
        except StopIteration:
            pass
        while buf:
            out = buf.popleft()
            try:
                # enqueue the next transfer BEFORE yielding: device_put is
                # async, so it overlaps the consumer's compute
                buf.append(self._place(next(it)))
            except StopIteration:
                pass
            yield out

    def __len__(self):
        try:
            return len(self.loader)
        except TypeError:
            raise TypeError(
                "DeviceLoader wraps a len-less iterable; iterate instead")
