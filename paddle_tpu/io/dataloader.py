"""DataLoader (reference: python/paddle/fluid/reader.py:149 DataLoader,
fluid/dataloader/dataloader_iter.py:265 single-process iter, :469
multi-process iter with shared-memory workers + watchdog).

TPU-first design: workers fetch+collate ahead of the consumer through a
bounded prefetch queue, and the device transfer is one `jax.device_put`
per batch — the double-buffer H2D prefetch of the reference's
buffered_reader. With num_workers > 0, a spawned PROCESS pool is used
when use_shared_memory=True and the dataset/collate pickle cleanly
(dataset ships once via the worker initializer); otherwise a thread pool
(numpy releases the GIL for the copies that matter).
"""
from __future__ import annotations

import itertools
import pickle
import queue
import threading
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from typing import Callable, Optional

import numpy as np

from ..core.device import CpuSpawnContext
from ..core.tensor import Tensor
from .dataset import Dataset, IterableDataset
from .sampler import BatchSampler


_PROC_STATE = {}


def _proc_worker_init(dataset, collate_fn):
    """Runs once per spawned worker: bind the dataset/collate globally
    (the mmap-shared-dataset analog — spawn ships them exactly once).
    The pool's `CpuSpawnContext` has already pinned the worker to the
    CPU backend in its environment — a child touching jnp (e.g. a
    dataset returning Tensors) must never grab the parent's TPU, and by
    the time this runs unpickling has imported jax."""
    _PROC_STATE["dataset"] = dataset
    _PROC_STATE["collate"] = collate_fn


def _proc_worker_fetch(indices):
    ds = _PROC_STATE["dataset"]
    return _PROC_STATE["collate"]([ds[i] for i in indices])


# Shared-memory return transport (reference: the use_shared_memory path of
# fluid/dataloader/dataloader_iter.py — workers place batch arrays in
# /dev/shm segments and send only metadata through the result pipe,
# instead of pickling megabytes of batch data through it).
_SHM_MIN_BYTES = 1 << 16  # small arrays pickle cheaper than a shm segment


def _shm_encode(obj):
    import numpy as _np

    if isinstance(obj, _np.ndarray) and obj.nbytes >= _SHM_MIN_BYTES:
        from multiprocessing import resource_tracker, shared_memory

        arr = _np.ascontiguousarray(obj)
        shm = shared_memory.SharedMemory(create=True, size=arr.nbytes)
        _np.ndarray(arr.shape, arr.dtype, buffer=shm.buf)[...] = arr
        name = shm.name
        shm.close()
        # the PARENT owns the segment's lifetime (it unlinks after the
        # device transfer); stop this process's resource tracker from
        # unlinking it again at worker exit
        try:
            resource_tracker.unregister("/" + name, "shared_memory")
        except Exception:
            pass
        return ("__shm__", name, arr.shape, str(arr.dtype))
    if isinstance(obj, tuple):
        return tuple(_shm_encode(o) for o in obj)
    if isinstance(obj, list):
        return [_shm_encode(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _shm_encode(v) for k, v in obj.items()}
    return obj


def _shm_decode(obj):
    import numpy as _np

    if isinstance(obj, tuple) and len(obj) == 4 and obj[0] == "__shm__":
        from multiprocessing import shared_memory

        _, name, shape, dtype = obj
        shm = shared_memory.SharedMemory(name=name)
        try:
            view = _np.ndarray(shape, _np.dtype(dtype), buffer=shm.buf)
            out = _np.array(view)  # own the data before freeing the block
        finally:
            shm.close()
            try:
                shm.unlink()
            except FileNotFoundError:
                pass
        return out
    if isinstance(obj, tuple):
        return tuple(_shm_decode(o) for o in obj)
    if isinstance(obj, list):
        return [_shm_decode(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _shm_decode(v) for k, v in obj.items()}
    return obj


def _proc_worker_fetch_shm(indices):
    return _shm_encode(_proc_worker_fetch(indices))


def default_collate_fn(batch):
    """Stack samples into batch arrays (reference:
    fluid/dataloader/collate.py default_collate_fn)."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        import jax.numpy as jnp

        return Tensor._wrap(jnp.stack([s._data for s in batch]))
    if isinstance(sample, np.ndarray):
        if (len(batch) > 1 and sample.ndim > 0
                and not sample.dtype.hasobject
                and all(s.shape == sample.shape
                        and s.dtype == sample.dtype
                        and s.flags.c_contiguous for s in batch)):
            # native GIL-free collation (staging.cpp pt_stack; numpy
            # fallback inside when no toolchain built the library)
            from .. import native

            return native.stack_samples(batch)
        return np.stack(batch)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, np.float32)
    if isinstance(sample, (list, tuple)):
        return tuple(default_collate_fn(list(col)) for col in zip(*batch))
    if isinstance(sample, dict):
        return {k: default_collate_fn([s[k] for s in batch]) for k in sample}
    return np.asarray(batch)


def vision_collate_fn(batch):
    """Collate for (uint8 image, label) vision samples with the native
    FUSED stack + uint8->float32 /255 normalize (staging.cpp
    pt_stack_u8_to_f32) — use as DataLoader(collate_fn=vision_collate_fn)
    with datasets that keep images uint8 and skip transforms.ToTensor's
    per-sample division. Non-(img, label) batches defer to the default."""
    sample = batch[0]
    if (isinstance(sample, (tuple, list)) and len(sample) == 2
            and isinstance(sample[0], np.ndarray)
            and sample[0].dtype == np.uint8
            and all(s[0].shape == sample[0].shape
                    and s[0].flags.c_contiguous for s in batch)):
        from .. import native

        imgs = native.stack_u8_to_f32([s[0] for s in batch])
        labels = default_collate_fn([s[1] for s in batch])
        return imgs, labels
    return default_collate_fn(batch)


def _to_tensor_tree(obj):
    if isinstance(obj, np.ndarray):
        return Tensor(obj)
    if isinstance(obj, Tensor):
        return obj
    if isinstance(obj, tuple):
        return tuple(_to_tensor_tree(o) for o in obj)
    if isinstance(obj, list):
        return [_to_tensor_tree(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _to_tensor_tree(v) for k, v in obj.items()}
    return Tensor(np.asarray(obj))


class DataLoader:
    def __init__(
        self,
        dataset: Dataset,
        feed_list=None,
        places=None,
        return_list=True,
        batch_sampler: Optional[BatchSampler] = None,
        batch_size=1,
        shuffle=False,
        drop_last=False,
        collate_fn: Optional[Callable] = None,
        num_workers=0,
        use_buffer_reader=True,
        use_shared_memory=True,
        prefetch_factor=2,
        timeout=0,
        worker_init_fn=None,
        persistent_workers=False,
    ):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = int(num_workers)
        self.prefetch_factor = max(int(prefetch_factor), 1)
        self.use_buffer_reader = use_buffer_reader
        self.use_shared_memory = use_shared_memory
        self.persistent_workers = persistent_workers
        self._pool = None
        self._pool_is_proc = False
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last,
            )

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no len()")
        return len(self.batch_sampler)

    def __call__(self):
        return self.__iter__()

    def __iter__(self):
        if self._iterable_mode:
            yield from self._iter_iterable()
        elif self.num_workers == 0 or not self.use_buffer_reader:
            yield from self._iter_sync()
        else:
            yield from self._iter_prefetch()

    # -- paths ---------------------------------------------------------------
    def _fetch(self, indices):
        batch = [self.dataset[i] for i in indices]
        return self.collate_fn(batch)

    def _iter_sync(self):
        for indices in self.batch_sampler:
            yield _to_tensor_tree(self._fetch(indices))

    def _iter_iterable(self):
        it = iter(self.dataset)
        while True:
            batch = list(itertools.islice(it, self.batch_size))
            if not batch:
                return
            if len(batch) < self.batch_size and self.drop_last:
                return
            yield _to_tensor_tree(self.collate_fn(batch))

    def _make_pool(self):
        """Worker pool choice (dataloader_iter.py:469 multiprocess path):
        process workers when shared memory is requested and the dataset/
        collate pickle cleanly (children are spawned, so the dataset
        travels once via the initializer); thread pool otherwise. The
        pool persists across epochs when persistent_workers=True."""
        if self._pool is not None:
            return self._pool
        pool = None
        if self.use_shared_memory:
            try:
                # probe picklability WITHOUT materializing the bytes (a
                # large in-RAM dataset must not be copied just to probe)
                class _Null:
                    def write(self, b):
                        return len(b)

                pickle.Pickler(_Null(), protocol=4).dump(self.dataset)
                pickle.Pickler(_Null(), protocol=4).dump(self.collate_fn)
            except Exception:
                pool = ThreadPoolExecutor(max_workers=self.num_workers)
                self._pool_is_proc = False
            else:
                pool = ProcessPoolExecutor(
                    max_workers=self.num_workers,
                    mp_context=CpuSpawnContext(),
                    initializer=_proc_worker_init,
                    initargs=(self.dataset, self.collate_fn),
                )
                self._pool_is_proc = True
        else:
            pool = ThreadPoolExecutor(max_workers=self.num_workers)
            self._pool_is_proc = False
        if self.persistent_workers:
            self._pool = pool
        return pool

    def _iter_prefetch(self):
        """Worker-pool fetch + bounded queue — the buffered_reader analog
        (one device transfer per batch on the consumer side)."""
        depth = self.num_workers * self.prefetch_factor
        pool = self._make_pool()
        is_proc = self._pool_is_proc
        q: "queue.Queue" = queue.Queue(maxsize=depth)
        sentinel = object()

        def submit(indices):
            if is_proc:
                return pool.submit(_proc_worker_fetch_shm, list(indices))
            return pool.submit(self._fetch, indices)

        stop = threading.Event()

        def reap(fut):
            """Cancel a pending fetch; if it already completed, decode its
            shm descriptors so the segments are unlinked, not leaked."""
            if not fut.cancel() and is_proc:
                try:
                    _shm_decode(fut.result(timeout=5))
                except Exception:
                    pass

        def put_or_cancel(item):
            """Blocking put that aborts when the consumer is gone — the
            producer must never deadlock on a full queue nobody drains."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            if item is not sentinel and hasattr(item, "cancel"):
                reap(item)
            return False

        def producer():
            try:
                futures = []
                for indices in self.batch_sampler:
                    if stop.is_set():
                        break
                    futures.append(submit(indices))
                    while len(futures) >= depth:
                        if not put_or_cancel(futures.pop(0)):
                            break
                for f in futures:
                    if stop.is_set():
                        reap(f)
                    else:
                        put_or_cancel(f)
            finally:
                put_or_cancel(sentinel)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is sentinel:
                    break
                out = item.result()
                if is_proc:
                    out = _shm_decode(out)
                yield _to_tensor_tree(out)
        finally:
            # early break: stop the producer and cancel queued fetches so
            # a persistent pool is clean for the next epoch; q is drained
            # so the producer can never deadlock on q.put
            stop.set()
            while True:
                try:
                    item = q.get_nowait()
                except queue.Empty:
                    break
                if item is not sentinel:
                    reap(item)
            if pool is not self._pool:
                pool.shutdown(wait=False, cancel_futures=True)

    # -- legacy constructors (fluid reader API shims) ------------------------
    @staticmethod
    def from_generator(feed_list=None, capacity=None, use_double_buffer=True,
                       iterable=True, return_list=False, use_multiprocess=False,
                       drop_last=True):
        raise NotImplementedError(
            "Legacy fluid DataLoader.from_generator: build a paddle_tpu.io."
            "Dataset and use DataLoader(dataset=...) instead"
        )

    @staticmethod
    def from_dataset(dataset, places=None, drop_last=True):
        return DataLoader(dataset, drop_last=drop_last)
