"""Data loading: host numpy -> sharded device batches.

Reference: python/flexflow_dataloader.cc (574 LoC) — the full dataset is pinned
in zero-copy memory and an index task copies each batch slice to framebuffer
per iteration (load_entire_dataset_from_numpy:324, next_batch:208). TPU-native:
the dataset stays in host RAM; each batch is ``jax.device_put`` with the batch
NamedSharding (each chip receives exactly its shard — the same
one-copy-per-iteration pattern), with lookahead prefetch to overlap host->HBM
transfer with the previous step (replacing zero-copy staging).
"""
from __future__ import annotations

import threading
import time
from queue import Queue
from typing import Any, Dict, Iterator, List, Optional, Sequence

import numpy as np

from ..obs.trace import span, timed_span


class SingleDataLoader:
    """API-parity loader for one tensor (reference: flexflow_cffi.py:2447)."""

    def __init__(self, ffmodel, batch_tensor, full_array: np.ndarray,
                 num_samples: Optional[int] = None):
        self.ffmodel = ffmodel
        self.batch_tensor = batch_tensor
        self.full_array = np.asarray(full_array)
        self.num_samples = num_samples or self.full_array.shape[0]
        self.batch_size = batch_tensor.dims[0]
        self._idx = 0

    def reset(self) -> None:
        self._idx = 0

    def next_batch(self, ffmodel=None) -> np.ndarray:
        lo = self._idx
        hi = lo + self.batch_size
        if hi > self.num_samples:
            self.reset()
            lo, hi = 0, self.batch_size
        self._idx = hi
        return self.full_array[lo:hi]

    @property
    def num_batches(self) -> int:
        return self.num_samples // self.batch_size


def batch_iterator(arrays: Sequence[np.ndarray], batch_size: int,
                   shuffle: bool = False, seed: int = 0,
                   drop_remainder: bool = True,
                   start_batch: int = 0) -> Iterator[List[np.ndarray]]:
    """``start_batch`` skips the first k batches of the (seed-determined)
    stream without materializing them — the exact-resume path: a run
    restored mid-epoch replays the same shuffle and continues at the batch
    cursor the checkpoint recorded (resilience/session.py).

    Unshuffled batches are views of ``arrays`` (basic slices, nothing is
    copied on the host); shuffled batches are gathered copies."""
    n = arrays[0].shape[0]
    if not shuffle:
        # consecutive rows: the basic slice a[lo:hi] is the rows of
        # a[idx[lo:hi]] as a view — an index array makes numpy copy the
        # batch on this one thread, about 0.9 GB/s, slower than four chips
        # train on it (PERF.md §6, PR 30). The batch aliases its source
        # until its transfer ends; no consumer writes into a batch.
        lo0 = start_batch * batch_size
        m = max(n - lo0, 0)
        for b in range(m // batch_size if drop_remainder
                       else -(-m // batch_size)):
            lo = lo0 + b * batch_size
            yield [a[lo:min(lo + batch_size, n)] for a in arrays]
        return
    idx = np.arange(n)
    np.random.default_rng(seed).shuffle(idx)
    if start_batch > 0:
        # trim AFTER the shuffle: the remaining stream is identical to the
        # tail of an uninterrupted epoch at the same seed
        idx = idx[start_batch * batch_size:]
    m = len(idx)
    # native double-buffered staging: C++ gathers batch b+1 while batch b
    # ships to the device (flexflow_tpu/native BatchPipeline; falls back
    # to synchronous gather without the library)
    from ..native import BatchPipeline

    if drop_remainder or m % batch_size == 0:
        yield from BatchPipeline(arrays, idx, batch_size)
        return
    from ..native import gather_rows

    arrays = [np.ascontiguousarray(a) for a in arrays]
    for b in range(-(-m // batch_size)):
        sl = idx[b * batch_size:(b + 1) * batch_size]
        yield [gather_rows(a, sl) for a in arrays]


def device_put_batch(arrays: List[np.ndarray], shardings: List[Any]):
    import jax

    if shardings and shardings[0] is not None:
        return [jax.device_put(a, s) for a, s in zip(arrays, shardings)]
    return [jax.device_put(a) for a in arrays]


def new_input_stats() -> Dict[str, Any]:
    """The input pipeline's always-on counters (``FFModel.input_stats``):
    seconds the consumer waited for a batch, batches handed over, seconds
    the producer gathered and shipped, and the bytes it received as host
    copies (batch arrays that own their data; a view of the set counts 0).
    Each key has one writing thread."""
    return {"wait_s": 0.0, "batches": 0, "gather_s": 0.0, "put_s": 0.0,
            "copied_bytes": 0}


def prefetch_iterator(it: Iterator, shardings: List[Any], depth: int = 2,
                      stats: Optional[Dict[str, Any]] = None):
    """Background-thread prefetch of device batches (double buffering).

    Every region is a span on the profiler's clock (obs/trace.SPANS):
    ``batch_gather`` / ``batch_put`` / ``prefetch_backpressure`` on the
    producer's thread, ``dataloader_wait`` around each ``q.get()`` of the
    consumer; the three that ``stats`` (``new_input_stats``) sums are
    ``timed_span``s: plain adds, no profiler needed, the clock read inside
    the span's own edges so that a sum never exceeds its spans'.

    Abandoning the generator early (e.g. fit breaking out on a dynamic
    recompile) stops the producer promptly and JOINS it — without the stop
    flag it would stay blocked on ``q.put`` for the rest of the process,
    pinning its in-flight device batches; and without the join, a producer
    mid-``device_put`` could still race one more item into a queue nobody
    will drain. Producer errors (a raising source iterator, a failed device
    transfer) propagate to the consumer via the same stop-aware queue path
    instead of dying silently in the thread — every ``put``, the terminal
    sentinel and the error included, gives up once the consumer is gone."""
    from queue import Empty, Full

    if stats is None:
        stats = new_input_stats()
    q: Queue = Queue(maxsize=depth)
    stop = threading.Event()
    _END = object()

    def put_or_stop(item) -> bool:
        """Blocking put that abandons ship when the consumer left; True if
        the item landed."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except Full:
                continue
        return False

    def producer():
        try:
            source = iter(it)
            while True:
                with timed_span("batch_gather", stats, "gather_s"):
                    batch = next(source, _END)
                if batch is _END:
                    break
                stats["copied_bytes"] += sum(
                    a.nbytes for a in batch
                    if isinstance(a, np.ndarray) and a.flags.owndata)
                with timed_span("batch_put", stats, "put_s",
                                bytes=sum(getattr(a, "nbytes", 0)
                                          for a in batch)):
                    staged = device_put_batch(batch, shardings)
                with span("prefetch_backpressure"):
                    landed = put_or_stop(staged)
                if not landed:
                    return
            put_or_stop(_END)
        except BaseException as e:  # propagate to the consumer, don't swallow
            put_or_stop(e)

    with span("fit_epoch_setup"):
        t = threading.Thread(target=producer, daemon=True)
        t.start()
    try:
        while True:
            with timed_span("dataloader_wait", stats, "wait_s",
                            batch=stats["batches"]):
                item = q.get()
            if item is _END:
                break
            if isinstance(item, BaseException):
                raise item
            stats["batches"] += 1
            yield item
    finally:
        stop.set()
        # drain-and-join loop: draining unblocks a producer mid-put, and
        # every put path above is stop-aware, so the thread exits promptly
        # — unless it is blocked inside the SOURCE iterator or a device
        # transfer, which cannot observe the stop flag; bound the wait
        # (short: this sits on fit's recompile path) and fall back to
        # leaking the daemon thread (the pre-fix behavior) rather than
        # stalling the training process in generator close
        deadline = time.monotonic() + 1.0
        while t.is_alive() and time.monotonic() < deadline:
            try:
                while True:
                    q.get_nowait()
            except Empty:
                pass
            t.join(timeout=0.1)
        # final drain drops any last raced-in item's device buffers
        try:
            while True:
                q.get_nowait()
        except Empty:
            pass
