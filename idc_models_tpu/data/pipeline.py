"""Host→HBM input pipeline: batching, shuffling, and prefetch.

The TPU-native replacement for the reference's `prepare_for_training`
(cache → shuffle(1000) → batch → prefetch(AUTOTUNE), e.g.
dist_model_tf_vgg.py:47-65). Data lives in host RAM as numpy (the cache);
per-epoch order is a fresh seeded permutation (the shuffle); batches are
cut to a multiple of the mesh's data-axis size; and a background thread
keeps `prefetch` batches already transferred to device HBM with the right
NamedSharding (the prefetch) so the chips never wait on PCIe/host.
Where that thread is handed a `Loader`'s epoch it assembles the batches
in the loader's ring of recycled host buffers (`_StagingRing`): writing a
batch into pages that are already mapped, instead of into a fresh
allocation every time, is what lets the host keep up with the chips.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from collections.abc import Iterator
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
from jax.sharding import Mesh

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.data.idc import ArrayDataset
from idc_models_tpu.observe import trace


class _EpochSchedule:
    """The shared batching/shuffle/repeat schedule — the seeding contract
    ((seed, epoch) for pass 0, (seed, epoch, rep) for extra passes) lives
    only here, so `Loader` and `FileStream` stay bit-identical.

    - `shuffle`: new seeded permutation each epoch (epoch mixed into seed)
    - `drop_remainder`: required under data parallelism so every step's
      global batch divides the mesh; the reference gets this implicitly
      from fixed-size take/skip splits
    - `repeat`: passes over the dataset per epoch — the reference's
      CIFAR pipeline appends `.repeat(2)` after batching
      (dist_model_tf_dense.py:122-123), so each fit "epoch" sees the
      train set twice; with shuffle on, every pass gets a fresh
      permutation (tf.data reshuffles each iteration)

    Subclasses define `_num_examples()` and `_gather(idx) -> batch`.
    """

    def __init__(self, batch_size: int, *, shuffle: bool = True,
                 seed: int = 0, drop_remainder: bool = True,
                 repeat: int = 1):
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder
        self.repeat = repeat
        self._validate()

    def _validate(self) -> None:
        if self.batch_size <= 0:
            raise ValueError("batch_size must be positive")
        if self.repeat < 1:
            raise ValueError(f"repeat must be >= 1, got {self.repeat}")
        n = self._num_examples()
        if n < self.batch_size and self.drop_remainder:
            raise ValueError(
                f"dataset of {n} examples yields zero batches of "
                f"size {self.batch_size} with drop_remainder")

    def _num_examples(self) -> int:
        raise NotImplementedError

    def _gather(self, idx: np.ndarray):
        raise NotImplementedError

    def replace(self, **kw) -> "_EpochSchedule":
        """A copy with schedule knobs replaced (seed/repeat/...); used by
        `fit` to impose its per-phase schedule on caller-built loaders.
        Re-runs the constructor validation, so a bad knob fails as loudly
        here as at construction."""
        import copy

        new = copy.copy(self)
        for k, v in kw.items():
            if not hasattr(new, k):
                raise AttributeError(f"{type(self).__name__} has no {k!r}")
            setattr(new, k, v)
        new._validate()
        return new

    def __len__(self) -> int:
        n = self._num_examples()
        per_pass = (n // self.batch_size if self.drop_remainder
                    else -(-n // self.batch_size))
        return per_pass * self.repeat

    def _index_batches(self, epoch: int) -> Iterator[np.ndarray]:
        """The schedule itself: per-batch index arrays, deterministic in
        (seed, epoch) — the one place batching/shuffle/repeat order is
        defined (FileStream's multi-process decode re-walks it)."""
        n = self._num_examples()
        stop = (n // self.batch_size * self.batch_size
                if self.drop_remainder else n)
        for rep in range(self.repeat):
            if self.shuffle:
                # rep folded into the seed only for the extra passes keeps
                # the repeat=1 stream identical to what it always was
                key = (self.seed, epoch) if rep == 0 else (self.seed, epoch, rep)
                order = np.random.default_rng(key).permutation(n)
            else:
                order = np.arange(n)
            for i in range(0, stop, self.batch_size):
                yield order[i:i + self.batch_size]

    def epoch(self, epoch: int = 0) -> Iterator:
        for idx in self._index_batches(epoch):
            yield self._gather(idx)

    def __iter__(self):
        return self.epoch(0)


class Loader(_EpochSchedule):
    """Iterates (images, labels) numpy batches of a materialized
    ArrayDataset over epochs (see _EpochSchedule for the knobs).

    Iterated directly, every batch is a fresh pair of arrays the caller
    owns. Handed to `prefetch_to_mesh`, which owns the hand-over to the
    device, the same batches are assembled in a ring of host buffers
    that lives as long as the loader (`_StagingRing`)."""

    def __init__(self, ds: ArrayDataset, batch_size: int, **kw):
        self.ds = ds
        self._ring: _StagingRing | None = None
        super().__init__(batch_size, **kw)

    def _num_examples(self) -> int:
        return len(self.ds)

    def _gather(self, idx):
        return self.ds.images[idx], self.ds.labels[idx]

    def epoch(self, epoch: int = 0) -> "_LoaderEpoch":
        return _LoaderEpoch(self, epoch)

    def replace(self, **kw) -> "Loader":
        new = super().replace(**kw)
        new._ring = None    # sized by the copy's own batch and dataset
        return new

    def _staging(self, depth: int) -> "_StagingRing":
        """The loader's ring, built at the first hand-over (a loader that
        is only iterated directly never has one) and again only if a
        later hand-over asks for another depth."""
        if self._ring is None or len(self._ring.slots) != depth:
            self._ring = _StagingRing(depth, self.batch_size, self.ds)
        return self._ring


class _LoaderEpoch:
    """One epoch of a `Loader`'s batches. As an iterator it yields what
    the generator it replaces yielded: `_gather`'s fresh arrays.
    `prefetch_to_mesh` recognises it and calls `next_into` instead, which
    writes the same rows into a slot of the loader's staging ring; which
    of the two runs follows from who iterates, nothing else."""

    def __init__(self, loader: Loader, epoch: int):
        self.loader = loader
        self._indices = loader._index_batches(epoch)
        self.left = len(loader)         # batches not yet drawn

    def __iter__(self):
        return self

    def _next_indices(self) -> np.ndarray:
        idx = next(self._indices)
        self.left -= 1
        return idx

    def __next__(self):
        return self.loader._gather(self._next_indices())

    def next_into(self, ring: "_StagingRing", slot: "_Slot"):
        return ring.fill(slot, self.loader.ds, self._next_indices())


# A batch is cut into row slices of about this size for the ring's pool
# (see _StagingRing): one thread writes mapped pages at 11 GB/s on the
# chip's host, so a smaller batch is done before a pool would have
# started on it.
_FILL_SLICE_BYTES = 64 << 20


class _Slot:
    """One staging buffer pair, and the device arrays last placed from
    it: while they are not ready the transfer may still read the host
    buffers, so `_StagingRing.acquire` waits for them before a refill."""

    __slots__ = ("images", "labels", "placed")

    def __init__(self, rows: int, ds: ArrayDataset):
        self.images = np.empty((rows,) + ds.images.shape[1:], ds.images.dtype)
        self.labels = np.empty((rows,) + ds.labels.shape[1:], ds.labels.dtype)
        self.placed = None


class _StagingRing:
    """A `Loader`'s recycled host batches, used round-robin by the
    prefetch thread across epochs.

    `images[idx]` allocates a fresh batch every time, and writing fresh
    pages is what it costs (0.94 GB/s on the chip's host, PERF.md §5);
    `np.take(..., out=)` into pages mapped by an earlier batch moves the
    same rows several times faster, and releases the interpreter lock, so
    a large batch is filled in row slices by a few threads. Depth and
    pool width are derived: the depth from the prefetch queue's (by
    `prefetch_to_mesh`), the width from the batch's bytes and the host's
    cores. The buffers are mapped by their first fill, not before.
    """

    def __init__(self, depth: int, rows: int, ds: ArrayDataset):
        self.slots = [_Slot(rows, ds) for _ in range(depth)]
        self._next = 0
        self._lock = threading.Lock()
        self._owner_stop: threading.Event | None = None
        nbytes = self.slots[0].images.nbytes
        # a quarter of the cores: the copy rate levels off at four
        # threads (PERF.md §5), and the transfer runs on the others
        self.width = max(1, min(nbytes // _FILL_SLICE_BYTES,
                                (os.cpu_count() or 1) // 4))
        self._pool = (ThreadPoolExecutor(self.width,
                                         thread_name_prefix="idc-stage")
                      if self.width > 1 else None)

    def checkout(self, stop: threading.Event) -> bool:
        """Claim the ring for one producer thread until `release`. An
        abandoned epoch's producer may still be writing a slot: it is on
        its way out (its `stop` is set), so wait for it. A live epoch of
        the same loader keeps the ring, and this one gathers the plain
        way (False)."""
        while not self._lock.acquire(blocking=False):
            owner = self._owner_stop
            if owner is None or not owner.is_set():
                return False
            time.sleep(0.005)
        self._owner_stop = stop
        return True

    def release(self) -> None:
        self._lock.release()

    def acquire(self) -> _Slot:
        """The next slot, once nothing can still read it: the arrays
        placed from it last time round (by this epoch or by one that was
        abandoned with transfers in flight) are ready on the device."""
        slot = self.slots[self._next]
        self._next = (self._next + 1) % len(self.slots)
        if slot.placed is not None:
            jax.block_until_ready(slot.placed)
            slot.placed = None
        return slot

    def fill(self, slot: _Slot, ds: ArrayDataset, idx: np.ndarray):
        """Rows `idx` of `ds` written into the head of `slot`; returns
        the views that hold them (all of the slot, but for a final
        partial batch). `idx` comes from the schedule, so it is in range
        and `mode="clip"` changes nothing but numpy's buffering of
        `out`."""
        n = len(idx)
        x, y = slot.images[:n], slot.labels[:n]
        np.take(ds.labels, idx, axis=0, out=y, mode="clip")
        if self._pool is None:
            np.take(ds.images, idx, axis=0, out=x, mode="clip")
        else:
            cuts = [n * i // self.width for i in range(self.width + 1)]
            parts = [self._pool.submit(np.take, ds.images, idx[lo:hi], 0,
                                       x[lo:hi], "clip")
                     for lo, hi in zip(cuts, cuts[1:])]
            for part in parts:
                part.result()
        return x, y


class FileStream(_EpochSchedule):
    """Loader-shaped iterator that decodes image files per batch instead
    of materializing the dataset in host RAM.

    The scale path for C1/C2: `ArrayDataset` + `Loader` is the
    reference's `cache()` (entire dataset resident, fastest for the
    preset-sized subsets); `FileStream` is its streaming tf.data shape
    for directories that do not fit in memory — per-epoch seeded
    permutation of the FILE list, batches decoded on demand (native
    C++/libpng decoder when available, one persistent thread pool on the
    PIL fallback). Under `prefetch_to_mesh` the decode runs in the
    producer thread, overlapping device compute.

    Shares `Loader`'s schedule (`_EpochSchedule`) bit-for-bit: streaming
    a directory and training on its materialized ArrayDataset (same pair
    order) produce identical batch streams.
    """

    def __init__(self, pairs: list[tuple[str, int]], image_size: int,
                 batch_size: int, *, workers: int = 16,
                 backend: str = "auto", decode_workers: int = 0, **kw):
        if not pairs:
            raise ValueError("FileStream needs a non-empty file list")
        self.pairs = list(pairs)
        self.image_size = image_size
        self.workers = workers
        self.backend = backend
        self.decode_workers = decode_workers
        # lazy persistent pools, boxed so replace()'s shallow copies
        # share ONE pool instead of each leaking their own
        self._pool_box: list = [None]       # PIL thread pool
        self._proc_box: list = [None]       # decode worker processes
        super().__init__(batch_size, **kw)

    def _num_examples(self) -> int:
        return len(self.pairs)

    def _gather(self, idx):
        from idc_models_tpu.data.idc import decode_pairs

        batch = [self.pairs[j] for j in idx]
        labels = np.asarray([l for _, l in batch], np.int32)
        return decode_pairs(batch, self.image_size, workers=self.workers,
                            backend=self.backend,
                            pool=self._pil_pool), labels

    def _pil_pool(self):
        if self._pool_box[0] is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool_box[0] = ThreadPoolExecutor(max_workers=self.workers)
        return self._pool_box[0]

    def epoch(self, epoch: int = 0) -> Iterator:
        """With ``decode_workers`` > 0, whole batches fan out round-robin
        to N persistent worker PROCESSES (the tf.data C++ parallel-
        pipeline role at process granularity: each worker independently
        decodes full batches with the native/PIL path while the parent
        consumes earlier ones in order). The schedule is the shared
        `_index_batches`, and each batch is decoded by the SAME
        `decode_pairs` call a single-process stream would make, so the
        two streams are bit-identical — pinned by test. Workers hold no
        jax state (idc.py is numpy-only) and scale with host cores;
        BASELINE.md's decode-rate record (32.8k img/s/core) combines
        with this fan-out to cover the chip's ~88k img/s appetite at
        >=3 cores."""
        if not self.decode_workers:
            yield from super().epoch(epoch)
            return
        import itertools
        from collections import deque

        from idc_models_tpu.data import idc

        pool = self._proc_pool()
        # Bounded in-flight submission (submit-one/consume-one over a
        # 2N-deep window), NOT Pool.imap: imap's feeder drains the whole
        # epoch's task generator up front and buffers every decoded
        # batch until consumed — on a host where N workers outpace the
        # device that re-materializes the dataset --stream exists to
        # avoid. With the window, at most 2N decoded batches exist at
        # once, and an abandoned epoch leaves at most 2N stray tasks on
        # the shared pool.
        it = self._index_batches(epoch)
        inflight: deque = deque()

        def submit(n):
            for idx in itertools.islice(it, n):
                task = ([self.pairs[j] for j in idx], self.image_size,
                        self.backend, self.workers)
                inflight.append(
                    (idx, pool.apply_async(idc.decode_task, (task,))))

        submit(2 * self.decode_workers)
        while inflight:
            idx, fut = inflight.popleft()
            images = fut.get()
            labels = np.asarray([self.pairs[j][1] for j in idx], np.int32)
            yield images, labels
            submit(1)

    def _proc_pool(self):
        if self._proc_box[0] is None:
            import multiprocessing as mp

            # spawn, not fork: the parent holds live TPU-runtime and
            # prefetch threads that must not be duplicated into workers
            ctx = mp.get_context("spawn")
            self._proc_box[0] = ctx.Pool(
                self.decode_workers,
                initializer=_decode_worker_init)
        return self._proc_box[0]

    def close(self) -> None:
        """Shut the decode pools down (no-op if never created). Copies
        made by replace() share the same pools, so close the stream only
        when no copy is iterating; without close() the shared pools
        simply live until process exit."""
        pool, self._pool_box[0] = self._pool_box[0], None
        if pool is not None:
            pool.shutdown(wait=False)
        procs, self._proc_box[0] = self._proc_box[0], None
        if procs is not None:
            procs.terminate()
            procs.join()


def _decode_worker_init():
    """Decode workers never touch an accelerator, and a chip belongs to
    one process: the parent's. Spawning a worker imports this module —
    and with it jax, which reads JAX_PLATFORMS at import — BEFORE this
    runs, so the environment alone is too late. Pin the platform
    through jax.config as well (a fresh worker has no backend yet), so
    nothing a worker touches can claim the chip."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"     # for anything it starts
    jax.config.update("jax_platforms", "cpu")


def prefetch_to_mesh(batches: Iterator, mesh: Mesh, *, axis=meshlib.DATA_AXIS,
                     prefetch: int = 2) -> Iterator:
    """Background-thread device_put: yields batches already resident in HBM.

    Each incoming (images, labels) batch is placed with its leading axis
    sharded over `axis`. A bounded queue of `prefetch` in-flight transfers
    overlaps host decode/transfer with device compute — the AUTOTUNE
    prefetch of the reference, made explicit.

    When `batches` is a `Loader`'s epoch (`loader.epoch(e)`,
    `iter(loader)`), the hand-over to the device is this function's, so
    no caller can keep a host batch, and the producer assembles each
    batch in the loader's staging ring (`_StagingRing`) instead of in a
    fresh allocation. The ring is as deep as the batches that can be
    live at once: the one being filled, the `prefetch` queued, and the
    one whose transfer the consumer's step may still wait for. A slot is
    refilled only after the arrays placed from it are ready on the
    device, and they are placed with `may_alias=False`. Any other
    source (`FileStream`, a generator) keeps the arrays it yields.

    Traced (observe/trace.py; every site is the shared no-op handle
    unless a tracer is active), all under the span open on the
    consumer's thread when iteration starts (`train.epoch`,
    `train.eval`): `data.wait` around each `q.get()` on the consumer;
    on the producer thread (`idc-prefetch`) `data.recycle` around each
    acquisition of a ring slot (the wait for the slot's previous
    transfer; none without a ring), `data.load` around each
    `next(batches)`, `data.put` around one batch's `put_with_sharding`
    calls and `data.full` around the bounded put; and the detached
    `data.transfer`, from the put call to the placed arrays being ready
    on the device. `device_put` returns before the bytes land, so that
    last span is closed by a watcher thread that waits for them off both
    paths; it exists only while a tracer is active.
    """
    sh = meshlib.sharding(mesh, axis)
    q: queue.Queue = queue.Queue(maxsize=prefetch)
    stop = threading.Event()
    _END = object()
    parent = trace.current_span_id()
    placed_q = _start_transfer_watcher(stop)
    traced = placed_q is not None       # attributes are computed only then
    # multi-process placement reads the host batch through a callback of
    # its own (put_with_sharding): those batches stay fresh allocations
    staged = isinstance(batches, _LoaderEpoch) and sh.is_fully_addressable

    def put(item) -> bool:
        # Bounded put that gives up when the consumer is gone — otherwise
        # an abandoned iterator would leave this thread blocked forever,
        # pinning `prefetch` HBM-resident batches.
        with trace.span("data.full", parent=parent):
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
        return False

    def produce(ring):
        it = iter(batches)
        index = 0
        while True:
            slot = None
            if ring is not None and batches.left:
                with trace.span("data.recycle", parent=parent):
                    slot = ring.acquire()
            with trace.span("data.load", parent=parent) as sp:
                batch = (next(it, _END) if slot is None
                         else batches.next_into(ring, slot))
                if traced:
                    sp.set(index=index)
            if batch is _END:
                return True
            if traced:
                nbytes = sum(int(getattr(a, "nbytes", 0))
                             for a in jax.tree.leaves(batch))
                moving = trace.start_span("data.transfer", parent=parent,
                                          bytes=nbytes, index=index)
            with trace.span("data.put", parent=parent) as sp:
                # a slot is written again: nothing may keep its memory
                may_alias = None if slot is None else False
                placed = jax.tree.map(
                    lambda a: meshlib.put_with_sharding(
                        a, sh, may_alias=may_alias), batch)
                if slot is not None:
                    slot.placed = placed
                if traced:
                    sp.set(bytes=nbytes)
                    placed_q.put((moving, placed))
            if not put(placed):
                return False
            index += 1

    def producer():
        ring = None
        try:
            if staged:
                ring = batches.loader._staging(prefetch + 2)
                if not ring.checkout(stop):
                    ring = None
            if not produce(ring):
                return
        except BaseException as e:  # surface errors to the consumer
            put(e)
            return
        finally:
            if ring is not None:
                ring.release()
        put(_END)

    t = threading.Thread(target=producer, name="idc-prefetch", daemon=True)
    t.start()
    try:
        while True:
            with trace.span("data.wait") as sp:
                if traced:
                    sp.set(depth=q.qsize())
                item = q.get()
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()


def _start_transfer_watcher(stop: threading.Event):
    """With a tracer active, start the thread that closes `data.transfer`
    spans and return the queue that feeds it `(span, placed batch)`
    pairs; None, and no thread, otherwise. It waits for each placed
    batch off the producer's and the consumer's path, so neither is
    paced by being measured, and ends once `stop` is set and the queue is
    drained (what is queued was already dispatched, so each wait is
    finite)."""
    if trace.get_tracer() is None:
        return None
    placed_q: queue.SimpleQueue = queue.SimpleQueue()

    def watcher():
        while True:
            try:
                moving, placed = placed_q.get(timeout=0.1)
            except queue.Empty:
                if stop.is_set():
                    return
                continue
            try:
                jax.block_until_ready(placed)
            except Exception as e:   # the consumer meets the same error
                moving.close(error=type(e).__name__)
            else:
                moving.close()
            del placed      # or the idle wait below pins a batch in HBM

    threading.Thread(target=watcher, name="idc-prefetch-watch",
                     daemon=True).start()
    return placed_q


def prefetch_eval_batches(ds: ArrayDataset, mesh: Mesh, batch_size: int, *,
                          steps: int | None = None) -> Iterator:
    """The deterministic full-coverage eval pipeline, shared by the
    Evaluator and the feature cache: batches of `ds` in order, final
    batch padded to divide the mesh, transfers overlapped with compute
    via `prefetch_to_mesh`. Yields (images_dev, labels_dev, size) where
    `size` is the batch's true row count — padding rows sit at the tail,
    so `out[:size]` drops them exactly."""
    axis = meshlib.batch_axis(mesh)
    # pad to the BATCH axis size — on a 2-D ("data", "model") mesh the
    # model axis replicates the batch, so padding to devices.size would
    # compute model-factor more dummy rows than sharding needs
    n_shards = mesh.shape[axis]
    loader = Loader(ds, batch_size, shuffle=False, drop_remainder=False)

    def padded():
        for i, (x, y) in enumerate(loader.epoch(0)):
            if steps is not None and i >= steps:
                break
            x, y, _ = pad_to_multiple(x, y, n_shards)
            yield x, y

    n_total = (len(ds) if steps is None
               else min(len(ds), steps * batch_size))
    for j, (x, y) in enumerate(prefetch_to_mesh(padded(), mesh, axis=axis)):
        yield x, y, min(batch_size, n_total - j * batch_size)


def pad_to_multiple(images: np.ndarray, labels: np.ndarray,
                    multiple: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad a final partial batch up to `multiple`, returning a validity mask.

    Used by eval loops that must see every example exactly once while still
    dividing the mesh (training uses drop_remainder instead).
    """
    n = len(images)
    pad = (-n) % multiple
    if pad == 0:
        return images, labels, np.ones(n, bool)
    images = np.concatenate([images, np.zeros((pad,) + images.shape[1:],
                                              images.dtype)])
    labels = np.concatenate([labels, np.zeros((pad,) + labels.shape[1:],
                                              labels.dtype)])
    mask = np.concatenate([np.ones(n, bool), np.zeros(pad, bool)])
    return images, labels, mask
