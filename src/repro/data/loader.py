"""Host data pipeline: sharded batching with background prefetch.

The device never waits on the host: batches are produced by a worker thread
into a small queue and transferred while the previous step computes (the
WorkSchedule2 overlap idea — C2 — applied to input data).  The LDA corpus
is static today (resident, WorkSchedule1), so no training path uses it yet.
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Iterator

import jax


class PrefetchLoader:
    """Wraps a host-side batch generator with N-deep device prefetch."""

    def __init__(self, make_batch: Callable[[int], dict], depth: int = 2,
                 sharding=None):
        self._make = make_batch
        self._depth = depth
        self._sharding = sharding
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._step = 0
        self._thread.start()

    def _worker(self):
        i = 0
        while not self._stop.is_set():
            batch = self._make(i)
            if self._sharding is not None:
                batch = jax.device_put(batch, self._sharding)
            else:
                batch = jax.device_put(batch)
            try:
                self._q.put(batch, timeout=1.0)
                i += 1
            except queue.Full:
                continue

    def __iter__(self) -> Iterator[dict]:
        return self

    def __next__(self) -> dict:
        return self._q.get()

    def close(self):
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

