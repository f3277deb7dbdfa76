"""Data iterators (parity: python/mxnet/io.py — DataIter:182, NDArrayIter:546,
PrefetchingIter:349, ResizeIter; plus the registered C++ iterators of
src/io/ (SURVEY.md N14): MNISTIter, CSVIter, ImageRecordIter).

TPU-native design: the reference's C++ decode/augment thread pool +
``dmlc::ThreadedIter`` double buffering maps onto the host dependency engine
(``mxnet_tpu.engine``): PrefetchingIter pushes batch production as engine ops
so host IO overlaps device compute; device transfer happens once per batch
(``device_put``) feeding the XLA pipeline.
"""
from __future__ import annotations

import os
import gzip
import queue
import struct
import threading
import time
from collections import deque, namedtuple
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .base import MXNetError
from . import ndarray as nd
from . import telemetry as _telemetry
from . import health as _health
from . import profiler as _profiler
from .ndarray.ndarray import NDArray

_IO_BATCHES = _telemetry.counter(
    "io_batches_total", "Batches produced by data iterators", ("iter",))
_IO_WAIT = _telemetry.histogram(
    "io_prefetch_wait_seconds",
    "Consumer-side wait on the prefetch queue (0 when a batch was ready)",
    ("iter",))
_IO_WS = _telemetry.gauge(
    "io_workspace_bytes",
    "Pooled staging-workspace bytes held by the iterator", ("iter",))
_IO_PUT = _telemetry.histogram(
    "io_device_put_seconds",
    "Producer-side device placement (host->device upload) per batch",
    ("iter",))
_IO_DEPTH = _telemetry.gauge(
    "io_pipeline_depth",
    "Configured in-flight batch depth of the producer pipeline", ("iter",))
_IO_WORKERS = _telemetry.gauge(
    "io_pipeline_workers",
    "Worker threads producing batches for the pipeline", ("iter",))

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter",
           "SyntheticLMIter", "BlockDiffusionIter", "block_diffusion_noise",
           "CSVIter", "MNISTIter", "PrefetchingIter",
           "ResizeIter", "ImageRecordIter", "LibSVMIter",
           "ImageDetRecordIter"]


class DataDesc(namedtuple("DataDesc", ["name", "shape", "dtype", "layout"])):
    def __new__(cls, name, shape, dtype=np.float32, layout="NCHW"):
        return super().__new__(cls, name, tuple(shape), np.dtype(dtype), layout)

    @staticmethod
    def get_batch_axis(layout):
        return 0 if layout is None else layout.find("N")


class DataBatch:
    def __init__(self, data, label=None, pad=0, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter:
    """Base iterator (ref io.py:DataIter).

    ``sharding`` is the mesh-training hook: a ``jax.sharding.Sharding``
    for the produced batch (typically ``NamedSharding(mesh, P('dp'))``).
    Iterators that honor it land batches on device pre-sharded, so the
    train step never pays a host→device placement on its critical path;
    see ``parallel.mesh.host_shard_hint`` for the multi-host
    ``(rank, nranks)`` counterpart.
    """

    def __init__(self, batch_size=0, sharding=None):
        self.batch_size = batch_size
        self.sharding = sharding

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self) -> DataBatch:
        if self.iter_next():
            batch = DataBatch(self.getdata(), self.getlabel(),
                              pad=self.getpad(), index=self.getindex())
            if _telemetry.enabled:
                _IO_BATCHES.labels(iter=type(self).__name__).inc()
            return batch
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError

    def getdata(self):
        raise NotImplementedError

    def getlabel(self):
        raise NotImplementedError

    def getindex(self):
        return None

    def getpad(self):
        return 0


def _part_rows(v, rank, nranks):
    """Contiguous row block of `v` for one of `nranks` loading hosts."""
    n = v.shape[0]
    return v[n * rank // nranks: n * (rank + 1) // nranks]


def _init_data(data, allow_empty, default_name):
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, (list, tuple)):
        if not allow_empty and len(data) == 0:
            raise MXNetError("empty data")
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {default_name + "_%d" % i: d for i, d in enumerate(data)}
    out = {}
    for k, v in dict(data).items():
        if isinstance(v, NDArray):
            v = v.asnumpy()
        out[k] = np.asarray(v)
    return list(out.items())


class NDArrayIter(DataIter):
    """In-memory iterator (ref io.py:NDArrayIter): shuffle, pad/discard/
    roll_over last-batch handling."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label", num_parts=1, part_index=0,
                 sharding=None):
        super().__init__(batch_size, sharding=sharding)
        self.data = _init_data(data, False, data_name)
        self.label = _init_data(label, True, label_name)
        # per-host sharded loading (parallel.mesh.host_shard_hint): this
        # process keeps only its contiguous 1/num_parts row block, so a
        # multi-host mesh never decodes the full global batch per host
        if not 0 <= part_index < num_parts:
            raise MXNetError("part_index %d out of range for num_parts %d"
                             % (part_index, num_parts))
        self.num_parts = num_parts
        self.part_index = part_index
        if num_parts > 1:
            self.data = [(k, _part_rows(v, part_index, num_parts))
                         for k, v in self.data]
            self.label = [(k, _part_rows(v, part_index, num_parts))
                          for k, v in self.label]
        self.num_data = self.data[0][1].shape[0]
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle
        self.idx = np.arange(self.num_data)
        self.cursor = -batch_size
        self._cache = None
        if last_batch_handle == "discard":
            self.num_batches = self.num_data // batch_size
        else:
            self.num_batches = (self.num_data + batch_size - 1) // batch_size
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.label]

    def reset(self):
        if self.shuffle:
            np.random.shuffle(self.idx)
        if self.last_batch_handle == "roll_over" and \
                0 < self.cursor < self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) \
                % self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        if self.last_batch_handle == "discard":
            return self.cursor + self.batch_size <= self.num_data
        return self.cursor < self.num_data

    def _slice(self, arrays):
        out = []
        for _, v in arrays:
            start = self.cursor
            end = self.cursor + self.batch_size
            if end <= self.num_data:
                sel = self.idx[start:end]
            else:  # pad by wrapping
                sel = np.concatenate([self.idx[start:],
                                      self.idx[:end - self.num_data]])
            out.append(nd.array(v[sel], dtype=v.dtype))
        return out

    def getdata(self):
        return self._slice(self.data)

    def getlabel(self):
        return self._slice(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


class SyntheticLMIter(DataIter):
    """Deterministic synthetic next-token-prediction stream for LM
    workloads (models.transformer): data is ``(B, T)`` token ids, label
    is the same stream shifted one position (a REAL next-token target,
    not independent noise, so eval losses below ln(vocab) are
    achievable).  The full corpus is generated once from ``seed`` —
    identical across processes and runs, which is what makes bench
    rounds and multi-host parity tests reproducible without shipping a
    dataset.  ``num_parts``/``part_index`` follow the
    ``parallel.mesh.host_shard_hint`` contract (each host keeps its
    contiguous batch-row block)."""

    def __init__(self, vocab_size, seq_len, batch_size=1, num_batches=16,
                 seed=0, data_name="data", label_name="softmax_label",
                 dtype="float32", num_parts=1, part_index=0, sharding=None):
        super().__init__(batch_size, sharding=sharding)
        if not 0 <= part_index < num_parts:
            raise MXNetError("part_index %d out of range for num_parts %d"
                             % (part_index, num_parts))
        self.vocab_size = int(vocab_size)
        self.seq_len = int(seq_len)
        self.num_batches = int(num_batches)
        self.seed = int(seed)
        self.data_name = data_name
        self.label_name = label_name
        self.dtype = np.dtype(dtype)
        rng = np.random.RandomState(self.seed)
        # one extra token so every position has a next-token label
        corpus = rng.randint(0, self.vocab_size,
                             size=self.num_batches * batch_size
                             * self.seq_len + 1)
        n = self.num_batches * batch_size * self.seq_len
        self._data = corpus[:n].reshape(
            self.num_batches, batch_size, self.seq_len).astype(self.dtype)
        self._label = corpus[1:n + 1].reshape(
            self.num_batches, batch_size, self.seq_len).astype(self.dtype)
        if num_parts > 1:
            if batch_size % num_parts:
                raise MXNetError("batch_size %d not divisible by "
                                 "num_parts %d" % (batch_size, num_parts))
            self.batch_size = batch_size // num_parts
            self._data = self._data[:, _part_slice(batch_size, part_index,
                                                   num_parts)]
            self._label = self._label[:, _part_slice(batch_size, part_index,
                                                     num_parts)]
        self.cursor = -1

    @property
    def provide_data(self):
        return [DataDesc(self.data_name, (self.batch_size, self.seq_len),
                         self.dtype, layout="NT")]

    @property
    def provide_label(self):
        return [DataDesc(self.label_name, (self.batch_size, self.seq_len),
                         self.dtype, layout="NT")]

    def reset(self):
        self.cursor = -1

    def iter_next(self):
        self.cursor += 1
        return self.cursor < self.num_batches

    def _batch_array(self, v):
        arr = nd.array(v[self.cursor], dtype=self.dtype)
        if self.sharding is not None:
            import jax
            arr._data = jax.device_put(arr._data, self.sharding)
        return arr

    def getdata(self):
        return [self._batch_array(self._data)]

    def getlabel(self):
        return [self._batch_array(self._label)]


def block_diffusion_noise(x0, block, mask_id, seed):
    """x_t of the clean documents ``x0`` (B, L), noised by blocks as block
    diffusion trains (BD3-LM, arXiv:2503.09573; SDAR): for every block of
    ``block`` positions a count m is drawn uniformly from 1 .. block, and m
    positions of the block, chosen uniformly, take ``mask_id`` (the block's
    rate t = m / block, exactly).  Deterministic under ``seed`` (anything
    ``numpy.random.default_rng`` takes: a whole number of any size, or a
    list of them).  ``x0`` keeps its dtype; ``mask_id`` must not be one of
    its tokens."""
    x0 = np.asarray(x0)
    if x0.ndim != 2 or block < 1 or x0.shape[1] % block:
        raise MXNetError("block_diffusion_noise: documents (B, L) of whole "
                         "blocks of %d, got %s" % (block, x0.shape))
    if np.any(x0 == mask_id):
        raise MXNetError("block_diffusion_noise: the mask id %r is a token "
                         "of the documents" % (mask_id,))
    B, L = x0.shape
    rng = np.random.default_rng(seed)
    m = rng.integers(1, block + 1, size=(B, L // block, 1))
    rank = np.argsort(np.argsort(rng.random((B, L // block, block)), -1), -1)
    masked = (rank < m).reshape(B, L)
    return np.where(masked, np.asarray(mask_id, x0.dtype), x0)


class BlockDiffusionIter(DataIter):
    """Block-diffusion training batches from a token iterator: each batch of
    ``source`` gives its first data array as the clean documents x_0 (B, L),
    and this iterator yields ``data`` = x_t (``block_diffusion_noise``, under
    the seed ``[seed, batch number]``) and ``label`` = x_0, as
    ``models.transformer_lm`` takes them under ``diffusion_block``.  The
    noising is the host span ``IO::block_diffusion_noise``."""

    def __init__(self, source, block, mask_id, seed=0,
                 label_name="softmax_label"):
        super().__init__(source.batch_size)
        self.source, self.block, self.mask_id = source, int(block), mask_id
        self.seed, self.label_name = int(seed), label_name
        self._count = 0
        self._batch = None

    @property
    def provide_data(self):
        return self.source.provide_data[:1]

    @property
    def provide_label(self):
        desc = self.source.provide_data[0]
        return [DataDesc(self.label_name, desc.shape, desc.dtype,
                         layout=desc.layout)]

    def reset(self):
        self.source.reset()
        self._count = 0

    def iter_next(self):
        try:
            batch = self.source.next()
        except StopIteration:
            return False
        x0 = batch.data[0]
        with _profiler.span("IO::block_diffusion_noise", "io"):
            xt = block_diffusion_noise(x0.asnumpy(), self.block, self.mask_id,
                                       [self.seed, self._count])
        self._count += 1
        self._batch = ([nd.array(xt, dtype=xt.dtype)], [x0])
        return True

    def getdata(self):
        return self._batch[0]

    def getlabel(self):
        return self._batch[1]


def _part_slice(batch, rank, nranks):
    return slice(batch * rank // nranks, batch * (rank + 1) // nranks)


class CSVIter(DataIter):
    """CSV file iterator (ref src/io/iter_csv.cc:218)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, dtype="float32", **kwargs):
        super().__init__(batch_size)
        data = np.loadtxt(data_csv, delimiter=",", dtype=np.dtype(dtype),
                          ndmin=2)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", dtype=np.float32,
                               ndmin=2).reshape((-1,) + tuple(label_shape))
            if label.shape[-1] == 1:
                label = label.reshape(label.shape[:-1])
        else:
            label = np.zeros((data.shape[0],), np.float32)
        self._inner = NDArrayIter(data, label, batch_size,
                                  last_batch_handle="pad"
                                  if round_batch else "discard",
                                  label_name="label")

    def __getattr__(self, item):
        return getattr(self._inner, item)

    def __next__(self):
        return self._inner.__next__()

    next = __next__

    def reset(self):
        self._inner.reset()


class MNISTIter(DataIter):
    """MNIST idx-format iterator (ref src/io/iter_mnist.cc:260).  Reads the
    standard (optionally gzipped) idx files."""

    def __init__(self, image="train-images-idx3-ubyte",
                 label="train-labels-idx1-ubyte", batch_size=128,
                 shuffle=True, flat=False, silent=False, seed=0,
                 input_shape=None, **kwargs):
        super().__init__(batch_size)
        imgs = _read_idx(image)
        labels = _read_idx(label)
        imgs = imgs.astype(np.float32) / 255.0
        if flat:
            imgs = imgs.reshape(imgs.shape[0], -1)
        else:
            imgs = imgs.reshape(imgs.shape[0], 1, imgs.shape[1], imgs.shape[2])
        if input_shape is not None:
            imgs = imgs.reshape((imgs.shape[0],) + tuple(input_shape))
        self._inner = NDArrayIter(imgs, labels.astype(np.float32), batch_size,
                                  shuffle=shuffle, last_batch_handle="discard")

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def __next__(self):
        return next(self._inner)

    next = __next__


def _read_idx(path):
    if not os.path.exists(path):
        for alt in (path + ".gz",):
            if os.path.exists(alt):
                path = alt
                break
        else:
            raise MXNetError("MNIST file not found: %s" % path)
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rb") as f:
        buf = f.read()
    magic = struct.unpack(">I", buf[:4])[0]
    ndim = magic & 0xFF
    dims = struct.unpack(">" + "I" * ndim, buf[4:4 + 4 * ndim])
    data = np.frombuffer(buf, dtype=np.uint8, offset=4 + 4 * ndim)
    return data.reshape(dims)


class PrefetchingIter(DataIter):
    """Multi-worker background prefetch with device-side double buffering
    (ref io.py:349 + iter_prefetcher.h + the iter_image_recordio_2.cc
    worker pool).

    ``num_workers`` threads produce batches concurrently.  The underlying
    ``next(it)`` calls stay serialized under a fetch lock — inner
    iterators are not thread-safe and batch ORDER must match the
    unpipelined iterator exactly — while the expensive per-batch work
    (flattening plus, when ``sharding``/``device`` is set, the
    host->device ``jax.device_put``) runs outside the lock in parallel
    and is reassembled in sequence order before entering the bounded
    prefetch queue.  With a placement target the producer lands batch
    N+1 on device (pre-sharded against the cached ``NamedSharding`` for
    the mesh step, plain device placement otherwise) while the consumer
    computes step N, so the train step never pays the H2D copy on its
    critical path.

    ``prefetch_depth`` bounds in-flight batches (0 -> env
    ``MXNET_IO_PREFETCH_DEPTH``, default 2); ``num_workers`` defaults
    from ``MXNET_IO_PIPELINE_WORKERS`` falling back to
    ``MXNET_CPU_WORKER_NTHREADS``.
    """

    def __init__(self, iters, rename_data=None, rename_label=None,
                 prefetch_depth=0, sharding=None, device=None,
                 num_workers=0):
        if not isinstance(iters, list):
            iters = [iters]
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        super().__init__(iters[0].batch_size, sharding=sharding)
        if prefetch_depth <= 0:
            prefetch_depth = int(os.environ.get(
                "MXNET_IO_PREFETCH_DEPTH", "2"))
        self.prefetch_depth = max(1, prefetch_depth)
        if num_workers <= 0:
            num_workers = int(os.environ.get(
                "MXNET_IO_PIPELINE_WORKERS",
                os.environ.get("MXNET_CPU_WORKER_NTHREADS", "2")))
        self.num_workers = max(1, num_workers)
        self.device = device
        self._target = self._placement()
        self.current_batch = None
        self._queue: "queue.Queue" = queue.Queue(
            maxsize=self.prefetch_depth)
        self._stop = threading.Event()
        self._fetch_lock = threading.Lock()
        self._emit_cv = threading.Condition()
        self._threads: List[threading.Thread] = []
        self._seq = 0
        self._next_emit = 0
        self._eof = False
        self._done = False
        self._start()

    def _placement(self):
        """Sharding the producer lands batches on (None = host batches)."""
        if self.sharding is not None:
            return self.sharding
        if self.device is None:
            return None
        from jax.sharding import SingleDeviceSharding
        dev = getattr(self.device, "jax_device", self.device)
        return SingleDeviceSharding(dev)

    @property
    def _label(self):
        return "PrefetchingIter.mesh" if self.sharding is not None \
            else "PrefetchingIter"

    @property
    def provide_data(self):
        out = []
        for i, it in enumerate(self.iters):
            descs = it.provide_data
            if self.rename_data:
                # keep ALL four DataDesc fields: dropping layout here
                # broke get_batch_axis for renamed non-NCHW inputs
                descs = [DataDesc(self.rename_data[i].get(d.name, d.name),
                                  d.shape, d.dtype, d.layout)
                         for d in descs]
            out.extend(descs)
        return out

    @property
    def provide_label(self):
        out = []
        for i, it in enumerate(self.iters):
            descs = it.provide_label
            if self.rename_label:
                descs = [DataDesc(self.rename_label[i].get(d.name, d.name),
                                  d.shape, d.dtype, d.layout)
                         for d in descs]
            out.extend(descs)
        return out

    def _put(self, item) -> bool:
        """Stop-aware put; returns False if reset() interrupted us."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _place(self, arr):
        """Land a batch array on the placement target (producer side), so
        the consumer-side step finds it already on device/pre-sharded."""
        import jax
        data = getattr(arr, "_data", None)
        if data is None:
            return arr
        if getattr(data, "sharding", None) != self._target:
            arr._data = jax.device_put(data, self._target)
        from . import memwatch as _memwatch
        if _memwatch.enabled:
            # producer-side staging buffers: double-buffered batches live
            # on device before the consumer step adopts them
            _memwatch.tag("io", arr._data, detail=self._label)
        return arr

    def _assemble(self, batches):
        data = sum((b.data for b in batches), [])
        label = sum((b.label for b in batches), [])
        if self._target is not None:
            t0 = time.perf_counter()
            data = [self._place(a) for a in data]
            label = [self._place(a) for a in label]
            if _telemetry.enabled:
                _IO_PUT.labels(iter=self._label).observe(
                    time.perf_counter() - t0)
        return DataBatch(data, label, pad=batches[0].pad,
                         index=getattr(batches[0], "index", None))

    def _emit(self, seq, item) -> bool:
        """Ordered reassembly: deliver `item` as the seq-th queue entry."""
        with self._emit_cv:
            while self._next_emit != seq:
                if self._stop.is_set():
                    return False
                self._emit_cv.wait(timeout=0.05)
            ok = self._put(item)
            self._next_emit = seq + 1
            self._emit_cv.notify_all()
        return ok

    def _worker(self):
        while not self._stop.is_set():
            with self._fetch_lock:
                if self._eof:
                    return
                seq = self._seq
                self._seq += 1
                try:
                    batches = [next(it) for it in self.iters]
                except StopIteration:
                    self._eof = True
                    batches = None
                except Exception as e:  # surfaced on the consumer side
                    self._eof = True
                    batches = e
            if batches is None:
                self._emit(seq, None)
                return
            if isinstance(batches, Exception):
                self._emit(seq, batches)
                return
            try:
                item = self._assemble(batches)
            except Exception as e:  # surfaced on the consumer side
                with self._fetch_lock:
                    self._eof = True
                item = e
            if not self._emit(seq, item):
                return

    def _start(self):
        self._seq = 0
        self._next_emit = 0
        self._eof = False
        self._done = False
        if _telemetry.enabled:
            _IO_DEPTH.labels(iter=self._label).set(self.prefetch_depth)
            _IO_WORKERS.labels(iter=self._label).set(self.num_workers)
        self._threads = [
            threading.Thread(target=self._worker, daemon=True,
                             name="prefetch-worker-%d" % i)
            for i in range(self.num_workers)]
        for t in self._threads:
            t.start()

    def reset(self):
        # stop the producers FIRST, then drain — otherwise an in-flight
        # batch lands after the drain and leaks into the next epoch
        self._stop.set()
        with self._emit_cv:
            self._emit_cv.notify_all()
        for t in self._threads:
            while t.is_alive():
                try:  # unblock a producer stuck in put on a full queue
                    self._queue.get_nowait()
                except queue.Empty:
                    pass
                t.join(timeout=0.05)
        while True:  # final drain after every producer has exited
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        for it in self.iters:
            it.reset()
        self.current_batch = None
        self._stop.clear()
        self._start()

    def _get_timed(self):
        """Queue get, measuring how long the consumer sat starved."""
        if not _telemetry.enabled:
            return self._queue.get()
        t0 = time.perf_counter()
        batch = self._queue.get()
        wait = time.perf_counter() - t0
        _IO_WAIT.labels(iter=self._label).observe(wait)
        if _health.enabled:
            _health.monitor.note_phase("input", wait)
        return batch

    def _consume(self):
        batch = self._get_timed()
        if batch is None:
            self._done = True
            return None
        if isinstance(batch, Exception):
            self._done = True
            raise batch
        if _telemetry.enabled:
            _IO_BATCHES.labels(iter="PrefetchingIter").inc()
        return batch

    def __next__(self):
        # honor a batch already fetched by iter_next() (reference
        # PrefetchingIter: iter_next fills current_batch, next returns it)
        if self.current_batch is not None:
            batch, self.current_batch = self.current_batch, None
            return batch
        if self._done:
            # post-EOF next() must re-raise, not block on an idle queue
            raise StopIteration
        batch = self._consume()
        if batch is None:
            raise StopIteration
        return batch

    next = __next__

    def iter_next(self):
        if self.current_batch is not None:
            return True
        if self._done:
            return False
        batch = self._consume()
        if batch is None:
            return False
        self.current_batch = batch
        return True

    def getdata(self):
        assert self.current_batch is not None, \
            "call iter_next() before getdata()"
        return self.current_batch.data

    def getlabel(self):
        assert self.current_batch is not None, \
            "call iter_next() before getlabel()"
        return self.current_batch.label

    def getindex(self):
        return getattr(self.current_batch, "index", None)

    def getpad(self):
        return getattr(self.current_batch, "pad", 0)


class ResizeIter(DataIter):
    """Resize an iterator to a fixed number of batches (ref io.ResizeIter)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__(data_iter.batch_size)
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None

    @property
    def provide_data(self):
        return self.data_iter.provide_data

    @property
    def provide_label(self):
        return self.data_iter.provide_label

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = next(self.data_iter)
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = next(self.data_iter)
        self.cur += 1
        return True

    def __next__(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    next = __next__

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getpad(self):
        return self.current_batch.pad


class ImageRecordIter(DataIter):
    """RecordIO image iterator (ref src/io/iter_image_recordio_2.cc:727):
    multithreaded JPEG decode + augmentation feeding batches.

    Same pipeline shape as the reference's ImageRecordIOParser2: a reader
    walks the record file sequentially (cheap), ``preprocess_threads``
    workers JPEG-decode + augment concurrently (cv2/PIL release the GIL),
    and assembled batches wait in a bounded prefetch queue so decode
    overlaps the training step.  Up to ``prefetch_buffer`` BATCHES decode
    concurrently: workers write straight into a ring of reusable staging
    buffers and the producer reassembles them strictly in order, so the
    pool is never drained batch-by-batch.  Thread count honors the
    ``MXNET_CPU_WORKER_NTHREADS`` env (the reference's engine worker knob,
    docs/faq/env_var.md) with ``preprocess_threads`` as the per-iterator
    override.  ``num_parts``/``part_index`` shard the stream per mesh
    host (defaulting from ``parallel.mesh.host_shard_hint``) so
    multi-host training never decodes the full dataset on every host.
    The augmentation params mirror image_aug_default.cc (resize,
    rand_crop, rand_mirror, mean/std normalization)."""

    def __init__(self, path_imgrec, data_shape, batch_size, label_width=1,
                 shuffle=False, rand_crop=False, rand_mirror=False,
                 resize=-1, mean_r=0.0, mean_g=0.0, mean_b=0.0,
                 std_r=1.0, std_g=1.0, std_b=1.0, scale=1.0,
                 preprocess_threads=0, prefetch_buffer=2, path_imgidx=None,
                 round_batch=True, data_name="data",
                 label_name="softmax_label", num_parts=None,
                 part_index=None, **kwargs):
        super().__init__(batch_size)
        from . import recordio
        self.data_shape = tuple(data_shape)
        self.rand_crop = rand_crop
        self.rand_mirror = rand_mirror
        self.resize = resize
        self.mean = np.array([mean_r, mean_g, mean_b], np.float32)
        self.std = np.array([std_r, std_g, std_b], np.float32)
        self.scale = scale
        self.shuffle = shuffle
        self.data_name = data_name
        self.label_name = label_name
        if preprocess_threads <= 0:
            preprocess_threads = int(os.environ.get(
                "MXNET_CPU_WORKER_NTHREADS", "4"))
        self._nthreads = max(1, preprocess_threads)
        self._prefetch = max(1, prefetch_buffer)
        self._pool = None
        self._queue = None
        self._producer_thread = None
        self._stop = threading.Event()
        self._mem = None
        # pipelined-producer state: up to `prefetch_buffer` batches decode
        # concurrently, each into its own slot of a reusable staging ring
        self._inflight = deque()
        self._bufs = None
        self._reader_done = False
        self._seq_read = 0
        # batch staging buffers come from the per-context temp-space pool
        # (resource.cc kTempSpace semantics: one rotating slot per user,
        # reused across batches instead of a fresh malloc per batch)
        from . import resource as _resource
        from . import context as _ctx
        self._workspace_res = _resource.ResourceManager.get().request(
            _ctx.cpu(0),
            _resource.ResourceRequest(_resource.ResourceRequest.kTempSpace))
        if path_imgidx and os.path.exists(path_imgidx):
            self.rec = recordio.MXIndexedRecordIO(path_imgidx, path_imgrec, "r")
            self.keys = list(self.rec.keys)
        else:
            self.rec = recordio.MXRecordIO(path_imgrec, "r")
            self.keys = None
            if shuffle:
                # no index for random access: load raw records into memory
                # so shuffling is real (the reference C++ iterator shuffles
                # chunk-wise; silent sequential order would be wrong)
                import warnings
                warnings.warn(
                    "ImageRecordIter: shuffle=True without path_imgidx "
                    "loads the whole .rec into memory; provide an .idx "
                    "file for large datasets")
                self._mem = []
                while True:
                    raw = self.rec.read()
                    if raw is None:
                        break
                    self._mem.append(raw)
        # per-host sharded loading: each mesh host keeps 1/num_parts of the
        # stream (defaults from parallel.mesh.host_shard_hint), so multi-
        # host training never re-decodes the full dataset on every host
        if num_parts is None and part_index is None:
            from .parallel.mesh import host_shard_hint
            part_index, num_parts = host_shard_hint()
        num_parts = 1 if num_parts is None else int(num_parts)
        part_index = 0 if part_index is None else int(part_index)
        if not 0 <= part_index < num_parts:
            raise MXNetError(
                "ImageRecordIter: part_index %d out of range for "
                "num_parts %d" % (part_index, num_parts))
        self.num_parts, self.part_index = num_parts, part_index
        if num_parts > 1:
            if self.keys is not None:
                n = len(self.keys)
                self.keys = self.keys[n * part_index // num_parts:
                                      n * (part_index + 1) // num_parts]
            elif self._mem is not None:
                n = len(self._mem)
                self._mem = self._mem[n * part_index // num_parts:
                                      n * (part_index + 1) // num_parts]
        self._order = None
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(self.data_name,
                         (self.batch_size,) + self.data_shape)]

    @property
    def provide_label(self):
        return [DataDesc(self.label_name, (self.batch_size,))]

    def reset(self):
        self._stop_producer()
        if getattr(self, "_workspace_res", None) is None:
            # resuming after close(): reset() is the one sanctioned way to
            # bring the iterator back, so re-acquire the temp-space slot
            # (the pool is likewise rebuilt by _start_producer below)
            from . import resource as _resource
            from . import context as _ctx
            self._workspace_res = _resource.ResourceManager.get().request(
                _ctx.cpu(0), _resource.ResourceRequest(
                    _resource.ResourceRequest.kTempSpace))
        self.rec.reset()
        if self.keys is not None:
            self._order = list(self.keys)
            if self.shuffle:
                np.random.shuffle(self._order)
            self._pos = 0
        elif self._mem is not None:
            self._order = np.random.permutation(len(self._mem)).tolist()
            self._pos = 0
        self._seq_read = 0
        self._done = False
        self._start_producer()

    def close(self):
        self._stop_producer()
        if getattr(self, "_pool", None) is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
        # release the staging ring and temp-space slot with the iterator,
        # not at GC time
        self._bufs = None
        self._workspace_res = None

    __del__ = close

    @property
    def _workspace(self):
        # close() releases the temp-space slot for good; only an explicit
        # reset() re-acquires it.  Lazily re-acquiring here would silently
        # resurrect a half-closed iterator (dead pool, no producer) the
        # first time anything touched the workspace.
        ws = self._workspace_res
        if ws is None:
            raise MXNetError(
                "ImageRecordIter: used after close(); call reset() to "
                "restart the iterator")
        return ws

    def _read_raw(self):
        """Sequential record read (reader stage of the pipeline)."""
        if self.keys is not None:
            if self._pos >= len(self._order):
                return None
            raw = self.rec.read_idx(self._order[self._pos])
            self._pos += 1
            return raw
        if self._mem is not None:
            if self._pos >= len(self._order):
                return None
            raw = self._mem[self._order[self._pos]]
            self._pos += 1
            return raw
        if self.num_parts > 1:
            # sequential .rec without an index: stride-skip other hosts'
            # records — skipped bytes are read but never hit the decode
            # pool, so each host only pays decode for its own 1/num_parts
            while True:
                raw = self.rec.read()
                if raw is None:
                    return None
                i = self._seq_read
                self._seq_read += 1
                if i % self.num_parts == self.part_index:
                    return raw
        return self.rec.read()

    def _decode_into(self, raw, buf, i):
        """Worker stage: JPEG decode + augment straight into row ``i`` of
        the staging slot (GIL released in cv2/PIL; the row write is the
        worker's own memcpy, off the assembly thread)."""
        from . import recordio
        header, img = recordio.unpack_img(raw, iscolor=1)
        buf[0][i] = self._augment(img)
        buf[1][i] = float(np.asarray(header.label).ravel()[0])

    # --- producer/prefetch machinery (dmlc::ThreadedIter analog) ---------
    def _start_producer(self):
        import concurrent.futures
        import weakref
        if self._pool is None:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                self._nthreads, thread_name_prefix="imgrec-decode")
        if self._bufs is None:
            # reusable staging ring: one HWC+label slot per in-flight
            # batch (a single workspace carve can't back several batches
            # decoding concurrently), allocated once and recycled
            c, h, w = self.data_shape
            self._bufs = queue.Queue()
            for _ in range(self._prefetch + 1):
                self._bufs.put(
                    (np.empty((self.batch_size, h, w, c), np.float32),
                     np.empty((self.batch_size,), np.float32)))
        self._reader_done = False
        self._queue = queue.Queue(self._prefetch)
        self._stop.clear()
        if _telemetry.enabled:
            _IO_DEPTH.labels(iter="ImageRecordIter").set(self._prefetch)
            _IO_WORKERS.labels(iter="ImageRecordIter").set(self._nthreads)
        # the thread holds only a WEAK reference between batches, so an
        # abandoned iterator stays collectable and its loop exits instead
        # of leaking the thread + pool
        self._producer_thread = threading.Thread(
            target=_imgrec_produce_loop,
            args=(weakref.ref(self), self._stop, self._queue), daemon=True)
        self._producer_thread.start()

    def _stop_producer(self):
        if getattr(self, "_producer_thread", None) is None:
            return
        self._stop.set()
        try:
            cur = threading.current_thread()
        except Exception:   # interpreter teardown: module globals cleared
            self._producer_thread = None
            return
        if self._producer_thread is cur:
            # GC collected the abandoned iterator ON the producer thread
            # (it holds the last transient strong ref) — can't self-join
            self._producer_thread = None
            return
        while self._producer_thread.is_alive():
            try:  # unblock a producer stuck on a full queue
                self._queue.get_nowait()
            except queue.Empty:
                pass
            self._producer_thread.join(timeout=0.05)
        self._producer_thread = None
        # settle in-flight decodes before the ring is recycled: a worker
        # still writing into a slot would corrupt the next epoch's batches
        if self._inflight:
            for futs, _n, buf in self._inflight:
                for f in futs:
                    if not f.cancel():
                        try:
                            f.result()
                        except Exception:  # noqa: BLE001 — epoch abandoned
                            pass
                if self._bufs is not None:
                    self._bufs.put(buf)
            self._inflight.clear()

    def _pump(self):
        """One producer turn (pipelined): keep up to ``prefetch_buffer``
        batches decoding in the pool, then finish + assemble the OLDEST
        one — batch order is exactly the reader order even though several
        batches' decodes overlap.  Returns (items_to_enqueue, done)."""
        while (not self._reader_done
               and len(self._inflight) < self._prefetch):
            raws = []
            while len(raws) < self.batch_size:
                raw = self._read_raw()
                if raw is None:
                    self._reader_done = True
                    break
                raws.append(raw)
            if not raws:
                break
            try:
                buf = self._bufs.get_nowait()
            except queue.Empty:  # can't happen by sizing; stay deadlock-free
                c, h, w = self.data_shape
                buf = (np.empty((self.batch_size, h, w, c), np.float32),
                       np.empty((self.batch_size,), np.float32))
            futs = [self._pool.submit(self._decode_into, r, buf, i)
                    for i, r in enumerate(raws)]
            self._inflight.append((futs, len(raws), buf))
        if not self._inflight:
            return [None], True
        futs, n, buf = self._inflight.popleft()
        for f in futs:
            f.result()
        batch = self._assemble_batch(buf, n)
        self._bufs.put(buf)
        pad = self.batch_size - n
        done = bool(pad) or (self._reader_done and not self._inflight)
        return ([batch, None], True) if done else ([batch], False)

    def _assemble_batch(self, buf, n):
        """Pad + transpose one decoded staging slot into a DataBatch."""
        data, label = buf
        c, h, w = self.data_shape
        # CHW output still comes from the pooled temp space (one rotating
        # slot; only the producer thread touches it).  Reuse of both the
        # carve and the ring slot is safe because nd.array's astype copy
        # (guaranteed, never aliasing) materializes the batch first.
        n_img = self.batch_size * h * w * c
        ws = self._workspace.get_space((n_img,), np.float32)
        if _telemetry.enabled:
            _IO_WS.labels(iter="ImageRecordIter").set(
                ws.nbytes + (self._prefetch + 1)
                * (data.nbytes + label.nbytes))
        chw = ws[:n_img].reshape((self.batch_size, c, h, w))
        pad = self.batch_size - n
        if pad:
            data[n:] = data[:1]
            label[n:] = label[:1]
        # one vectorized HWC->CHW for the whole batch (cheaper than 128
        # per-image strided copies, and outside the decode workers),
        # written into the pooled CHW carve instead of a fresh allocation
        np.copyto(chw, data.transpose(0, 3, 1, 2))
        return DataBatch([nd.array(chw)], [nd.array(label)], pad=pad)

    def _augment(self, img):
        c, h, w = self.data_shape
        if self.resize > 0:
            img = _resize_short(img, self.resize)
        ih, iw = img.shape[:2]
        if self.rand_crop and ih > h and iw > w:
            y = np.random.randint(0, ih - h + 1)
            x = np.random.randint(0, iw - w + 1)
        else:
            y, x = max(0, (ih - h) // 2), max(0, (iw - w) // 2)
        img = img[y:y + h, x:x + w]
        if img.shape[0] != h or img.shape[1] != w:
            img = _resize_exact(img, (w, h))
        if self.rand_mirror and np.random.rand() < 0.5:
            img = img[:, ::-1]
        # BGR->RGB + (x - mean)/std*scale as x*a + b.  cv2 releases the GIL
        # (numpy ufuncs don't), which is what lets preprocess_threads scale
        # (the reference's N decode threads, iter_image_recordio_2.cc:727).
        a = self.scale / self.std
        b = -self.mean * a
        try:
            import cv2
            rgb = cv2.cvtColor(np.ascontiguousarray(img),
                               cv2.COLOR_BGR2RGB)
            mul = tuple(float(x) for x in a) + (0.0,)
            add = tuple(float(x) for x in b) + (0.0,)
            out = cv2.multiply(rgb, mul, dtype=cv2.CV_32F)
            out = cv2.add(out, add)
        except ImportError:
            out = img[..., ::-1].astype(np.float32) * a + b
        return out                               # HWC; batch-transposed once

    def __next__(self):
        if self._done:
            raise StopIteration
        tel = _telemetry.enabled
        if tel:
            t0 = time.perf_counter()
            batch = self._queue.get()
            wait = time.perf_counter() - t0
            _IO_WAIT.labels(iter="ImageRecordIter").observe(wait)
            if _health.enabled:
                _health.monitor.note_phase("input", wait)
        else:
            batch = self._queue.get()
        if batch is None:
            self._done = True
            raise StopIteration
        if isinstance(batch, Exception):
            self._done = True
            raise batch
        if tel:
            _IO_BATCHES.labels(iter="ImageRecordIter").inc()
        return batch

    next = __next__


def _imgrec_produce_loop(ref, stop, q):
    """ImageRecordIter producer body (module-level: must not pin the
    iterator alive — see _start_producer).  Any reader/decoder exception is
    forwarded to the consumer via the queue instead of dying silently."""
    while not stop.is_set():
        it = ref()
        if it is None:
            return
        try:
            items, done = it._pump()
        except Exception as e:               # noqa: BLE001 — surfaced below
            items, done = [e, None], True
        del it
        for item in items:
            placed = False
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    placed = True
                    break
                except queue.Full:
                    if ref() is None:        # consumer abandoned us
                        return
            if not placed:
                return
        if done:
            return


def _resize_short(img, size):
    h, w = img.shape[:2]
    if h < w:
        nh, nw = size, int(w * size / h)
    else:
        nh, nw = int(h * size / w), size
    return _resize_exact(img, (nw, nh))


def _resize_exact(img, wh):
    try:
        import cv2
        return cv2.resize(img, wh)
    except ImportError:
        from PIL import Image
        mode = "RGB" if img.ndim == 3 else "L"
        return np.asarray(Image.fromarray(img, mode).resize(wh))


class LibSVMIter(DataIter):
    """libsvm sparse text format (ref src/io/iter_libsvm.cc:200); yields
    dense batches (device compute is dense on TPU — SURVEY.md §7.3 sparse)."""

    def __init__(self, data_libsvm, data_shape, batch_size, label_shape=(1,),
                 **kwargs):
        super().__init__(batch_size)
        dim = int(np.prod(data_shape))
        rows, labels = [], []
        with open(data_libsvm) as f:
            for line in f:
                parts = line.strip().split()
                if not parts:
                    continue
                labels.append(float(parts[0]))
                row = np.zeros(dim, np.float32)
                for kv in parts[1:]:
                    k, v = kv.split(":")
                    row[int(k)] = float(v)
                rows.append(row)
        data = np.stack(rows).reshape((-1,) + tuple(data_shape))
        self._inner = NDArrayIter(data, np.asarray(labels, np.float32),
                                  batch_size, label_name="label")

    def __getattr__(self, item):
        return getattr(self._inner, item)

    def reset(self):
        self._inner.reset()

    def __next__(self):
        return next(self._inner)

    next = __next__


def ImageDetRecordIter(path_imgrec, data_shape, batch_size,
                       label_pad_width=-1, label_pad_value=-1.0,
                       path_imgidx=None, shuffle=False, mean_r=0.0,
                       mean_g=0.0, mean_b=0.0, std_r=1.0, std_g=1.0,
                       std_b=1.0, part_index=0, num_parts=1, **kwargs):
    """Detection record iterator (ref src/io/iter_image_det_recordio.cc:582).

    Deviation from the reference C++ iterator: labels are emitted directly
    in the padded ``(batch, max_objects, obj_width)`` format (padded with
    ``label_pad_value``) rather than the flat header-prefixed rows the
    reference emits and every consumer immediately reshapes
    (example/ssd/dataset/iterator.py:101-124).  ``label_pad_width`` counts
    objects (rows) here; -1 estimates the maximum over the dataset.

    Augmentation kwargs are forwarded to ``CreateDetAugmenter``
    (rand_crop/rand_pad/rand_mirror/brightness/...).
    """
    from .image_detection import ImageDetIter

    mean = None
    if mean_r or mean_g or mean_b:
        mean = np.array([mean_r, mean_g, mean_b], np.float32)
    std = None
    if (std_r, std_g, std_b) != (1.0, 1.0, 1.0):
        std = np.array([std_r, std_g, std_b], np.float32)
    it = ImageDetIter(batch_size=batch_size, data_shape=tuple(data_shape),
                      path_imgrec=path_imgrec, path_imgidx=path_imgidx,
                      shuffle=shuffle, part_index=part_index,
                      num_parts=num_parts, mean=mean, std=std, **kwargs)
    if label_pad_width > 0:
        if label_pad_width < it.label_shape[0]:
            raise MXNetError(
                "label_pad_width %d smaller than max object count %d"
                % (label_pad_width, it.label_shape[0]))
        it.reshape(label_shape=(label_pad_width, it.label_shape[1]))
    it.label_pad_value = label_pad_value
    return it
