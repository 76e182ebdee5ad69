"""Pipelined host->device batch prefetch for the async train loop.

The synchronous loop serializes three phases per iteration: host data
fetch (``next(data_iter)``), host->device transfer (``_put_batch``), and
the device step — so the TPU idles while the host tokenizes/collates/
transfers. The reference hides this with DataLoader workers + pinned-
memory prefetch (megatron/data/data_samplers.py); the JAX equivalent is
this module: a background thread that pulls host batches IN SAMPLER
ORDER, places them on device with the loop's own put function, and
double-buffers the landed arrays in a bounded queue. Step N+1's data is
on device while step N computes; the loop's queue pop is the only data
cost left on the critical path (journaled as ``data_wait_ms``).

Rollback/resume contract (the part that keeps crash-safe training
bitwise-reproducible): the prefetcher NEVER owns data-order state. The
sampler order is a pure function of ``consumed_samples``, which only the
train loop advances — one batch per pop. Batches pulled ahead of the
loop are in-flight work with no side effects; on divergence rollback,
epoch boundary, or batch-size rampup the loop ``close()``s the
prefetcher (discarding everything in flight) and rebuilds it from a
fresh ``train_iter_factory(consumed_samples, gbs)`` iterator at the
exact watermark. No sample is ever lost or duplicated because nothing
but the loop's own counter defines position (tests/test_prefetch.py
asserts loss-curve bitwise identity against the synchronous loop,
including across a rollback rebuild).

Fault injection rides along deterministically: ``transform(batch,
iteration)`` is applied on the HOST copy before placement, with the
iteration number the batch will be consumed at (``first_iteration + i``
— pops map 1:1 to loop iterations, skipped ones included), so
``nan_loss`` poisoning hits the same batches the synchronous loop would
poison.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation


class DevicePrefetcher:
    """Bounded background host->device prefetcher over one iterator.

    Iterator protocol: ``next(pf, None)`` yields device batches in strict
    source order and ``None`` once the source iterator is exhausted (same
    shape as the plain host iterator, so the train loop's epoch-boundary
    rebuild logic is path-independent). Exceptions raised by the source
    iterator or the put function surface on the consuming thread.
    """

    def __init__(
        self,
        iterator: Iterator[Dict[str, np.ndarray]],
        put_fn: Callable[[Dict[str, np.ndarray]], Dict[str, Any]],
        depth: int = 2,
        first_iteration: int = 1,
        transform: Optional[Callable[[Dict[str, np.ndarray], int],
                                     Dict[str, np.ndarray]]] = None,
        land: bool = True,
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._iterator = iterator
        self._put_fn = put_fn
        self._transform = transform
        self._first_iteration = int(first_iteration)
        self._land = land
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._end = object()
        self._done = False
        # stats read by the consumer (single-writer on the worker side;
        # torn reads of floats are harmless for telemetry)
        self.batches_put = 0
        self.put_s = 0.0        # device_put dispatch seconds (worker-side)
        self.land_s = 0.0       # block_until_ready seconds (worker-side)
        self._thread = threading.Thread(
            target=self._worker, name="batch-prefetcher", daemon=True)
        self._thread.start()

    # -- worker side ---------------------------------------------------------

    def _enqueue(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        try:
            for i, batch in enumerate(self._iterator):
                if self._stop.is_set():
                    return
                if self._transform is not None:
                    batch = self._transform(batch, self._first_iteration + i)
                # the spans the loop credits from put_s/land_s after the
                # fact (Timers.record), annotated here where the work runs:
                # under a trace capture they sit on this thread's line
                with TraceAnnotation("batch-transfer"):
                    t0 = time.perf_counter()
                    with TraceAnnotation("batch-transfer-dispatch"):
                        device_batch = self._put_fn(batch)
                    t1 = time.perf_counter()
                    if self._land:
                        # land the copy in the worker so a queue pop hands
                        # the loop a device-resident batch, not an
                        # in-flight one
                        jax.block_until_ready(device_batch)
                    t2 = time.perf_counter()
                self.put_s += t1 - t0
                self.land_s += t2 - t1
                self.batches_put += 1
                if not self._enqueue(device_batch):
                    return
            self._enqueue(self._end)
        except BaseException as e:  # noqa: BLE001 - worker thread: every
            # failure (incl. KeyboardInterrupt) must surface on the
            # consuming thread, not die silently here
            self._enqueue(e)

    # -- consumer side -------------------------------------------------------

    def __iter__(self) -> "DevicePrefetcher":
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        item = self._q.get()
        if item is self._end:
            self._done = True
            raise StopIteration
        if isinstance(item, BaseException):
            self._done = True
            raise item
        return item

    def close(self) -> None:
        """Stop the worker and discard everything in flight (idempotent).

        The loop calls this on rollback / epoch / rampup boundaries and
        rebuilds from a fresh iterator at its consumed_samples watermark;
        queued batches are dropped, never consumed."""
        self._stop.set()
        # unblock a worker parked on a full queue
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)
