"""Async double-buffered batch pipeline.

The host-side analogue of GVEL's madvise read-ahead: while the device
runs step n, a background thread builds (and device_puts) batch n+1, so
input never serializes with compute.  Step-indexed sources keep restart
deterministic.

``graph_walk_source`` is the bridge from the loading front door
(:func:`repro.core.source.open_graph`) into this pipeline: graph file
-> ``GraphSource`` -> CSR through a named engine -> step-indexed
walk-batch source for :class:`Prefetcher`.
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Optional

import jax


def graph_walk_source(path: str, cfg, batch: int, seq: int, *,
                      engine: str = "device", seed: int = 99,
                      **load_kw) -> Callable[[int], dict]:
    """Load a graph through ``open_graph(path)`` and return a
    deterministic step-indexed source of random-walk LM batches
    (a :class:`repro.data.corpus.WalkCorpus` bound to the handle).

    The returned callable feeds :class:`Prefetcher` directly, completing
    the streamed path: file -> packed device edges -> CSR -> walk batches,
    with the loader and the batch pipeline double-buffering at both ends.
    """
    from ..core.source import open_graph
    from .corpus import CorpusConfig, WalkCorpus

    src = open_graph(path, engine=engine, **load_kw)
    corpus = WalkCorpus(src, CorpusConfig(
        batch=batch, seq=seq, vocab_size=cfg.vocab_size, seed=seed))
    return corpus.batch_at


class _Failure:
    """Sentinel carrying a worker exception through the batch queue —
    how a dead lookahead thread reaches its consumer instead of
    leaving it blocked on an empty queue forever."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class Prefetcher:
    """Wraps source(step)->batch with a lookahead thread.

    Failure semantics: an exception in the worker (a corrupt graph, an
    injected fault) is queued behind any batches already built and
    re-raised from :meth:`get` — never swallowed.  ``get`` also bounds
    its wait by the watchdog budget (``timeout`` here, else
    ``faults.WATCHDOG_S``), raising :class:`~repro.core.faults.
    StageTimeout` when the source is stuck rather than hanging the
    training/serving loop.
    """

    def __init__(self, source: Callable[[int], dict], start_step: int = 0,
                 lookahead: int = 2, sharding=None,
                 timeout: Optional[float] = None):
        self.source = source
        self.sharding = sharding
        self._timeout = timeout
        self._q: queue.Queue = queue.Queue(maxsize=lookahead)
        self._stop = threading.Event()
        self._next = start_step
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def _work(self):
        step = self._next
        try:
            while not self._stop.is_set():
                batch = self.source(step)
                if self.sharding is not None:
                    batch = jax.device_put(batch, self.sharding)
                try:
                    self._q.put((step, batch), timeout=0.2)
                    step += 1
                except queue.Full:
                    continue
        except BaseException as exc:   # propagate through the queue
            while not self._stop.is_set():
                try:
                    self._q.put((step, _Failure(exc)), timeout=0.2)
                    return
                except queue.Full:
                    continue

    def get(self, expect_step: Optional[int] = None):
        from ..core import faults

        budget = faults.WATCHDOG_S if self._timeout is None else self._timeout
        try:
            step, batch = self._q.get(timeout=budget)
        except queue.Empty:
            raise faults.StageTimeout(
                f"batch pipeline: no batch produced within {budget:.1f}s "
                f"(REPRO_WATCHDOG_S); the source is stuck") from None
        if isinstance(batch, _Failure):
            self._stop.set()
            raise batch.exc
        if expect_step is not None and step != expect_step:
            raise RuntimeError(f"pipeline desync: got {step}, want {expect_step}")
        return batch

    def close(self):
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=2.0)
