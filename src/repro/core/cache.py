"""Process-level hot-graph cache: a bounded, thread-safe LRU of open
:class:`~repro.core.source.GraphSource` handles, and the serve-facing
``query(path, op)`` entry built on it.

A graph-query service (ParaGrapher's serving scenario: thousands of
point/range reads per second against a snapshot corpus) must not pay
open-and-validate per request, must notice when a snapshot is swapped
under it, and must bound how many mmaps / decoded sections it pins.
This module is that layer:

    from repro.core.cache import query

    nbrs = query("web.gvel", "neighbors", vertex=42)
    rows = query("web.gvel", "rows", rows=range(100, 200))
    csr  = query("web.gvel", "csr")

* **Keyed by content, not path**: entries are validated against
  ``(mtime_ns, size)`` on every hit — overwriting a snapshot (the
  swap-under-the-server scenario) invalidates its entry on the next
  request, which reopens the new file.  No TTLs, no staleness window
  beyond the filesystem's mtime granularity.
* **Bounded LRU**: at most ``capacity`` open handles; the least
  recently used is evicted (dropping its mmap and decoded-section
  memos with it).
* **Thread-safe, single-open**: concurrent requests for the same path
  coordinate through a pending slot so a cold file is opened and
  validated exactly once, not once per waiting thread; every wait-er
  gets the same handle.  Product access on a shared handle is safe:
  section decodes are lock-guarded per section
  (:mod:`repro.core.snapshot`) and memoized products are immutable.

The default process cache (capacity from ``$REPRO_CACHE_CAPACITY``,
else 16) serves the module-level :func:`query`; build explicit
:class:`SourceCache` instances for isolation (tests, per-tenant
caches).  Cache semantics and invalidation rules: ``docs/query.md``.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

from . import faults as faults_mod
from .faults import CorruptGraphError, StageTimeout
from .snapshot import SnapshotError
from .source import GraphSource, open_graph

_DEFAULT_CAPACITY = int(os.environ.get("REPRO_CACHE_CAPACITY", "16"))

# sections each query op may touch — the quarantine scope of the op.
# A quarantined section only blocks ops that would read it; "info" is
# header-only and keeps serving (the health probe must outlive the
# corruption it reports).
_OP_SECTIONS: Dict[str, Tuple[str, ...]] = {
    "info": (),
    "csr": ("csr_offsets", "csr_indices", "csr_weights"),
    "full": ("csr_offsets", "csr_indices", "csr_weights"),
    "rows": ("csr_offsets", "csr_indices", "csr_weights"),
    "csr_rows": ("csr_offsets", "csr_indices", "csr_weights"),
    "range": ("csr_offsets", "csr_indices", "csr_weights"),
    "neighbors": ("csr_offsets", "csr_indices", "csr_weights"),
    "point": ("csr_offsets", "csr_indices", "csr_weights"),
    "degree": ("csr_offsets",),
    "edgelist": ("src", "dst", "edge_weights"),
}


class _Pending:
    """One in-flight open: waiters block on ``event``; the opener
    publishes ``source`` or ``error`` before setting it."""

    __slots__ = ("event", "source", "error")

    def __init__(self):
        self.event = threading.Event()
        self.source: Optional[GraphSource] = None
        self.error: Optional[BaseException] = None


class _Entry:
    __slots__ = ("key", "source")

    def __init__(self, key, source):
        self.key = key
        self.source = source


def _stat_key(path: str) -> Tuple[int, int]:
    st = os.stat(path)
    return st.st_mtime_ns, st.st_size


class SourceCache:
    """Bounded, thread-safe LRU of open :class:`GraphSource` handles,
    keyed by ``(path, mtime_ns, size, open-kwargs)``.

    ``get`` returns the cached handle when the file on disk still
    matches the entry's stat key, else drops the stale entry and
    reopens.  ``capacity`` bounds simultaneously-open handles (mmaps +
    decoded sections); eviction is strict LRU.  All open keyword
    arguments participate in the key, so ``get(p)`` and
    ``get(p, weighted=False)`` are distinct entries (kwarg values must
    be hashable).
    """

    def __init__(self, capacity: int = _DEFAULT_CAPACITY, *, open_fn=None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._open_fn = open_graph if open_fn is None else open_fn
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, _Entry]" = OrderedDict()
        self._pending: Dict[tuple, _Pending] = {}
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        self._invalidations = 0
        # (path, section) -> {"stat": (mtime_ns, size) | None,
        #                     "error": str, "count": int}
        self._quarantined: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._faults = {"open_retries": 0, "open_faults": 0,
                        "corrupt_errors": 0, "quarantines": 0,
                        "recovered": 0, "wait_timeouts": 0}

    # -- core ----------------------------------------------------------------

    def get(self, path: str, **open_kw) -> GraphSource:
        """The cached handle for ``path`` (opened with ``open_kw``),
        opening at most once per (path, stat, kwargs) across threads.
        A changed file (mtime or size) invalidates the old entry and
        reopens; raising opens are not cached (the next request
        retries)."""
        path = str(path)
        slot = (path, tuple(sorted(open_kw.items())))
        while True:
            key = _stat_key(path)       # raises for missing paths — uncached
            with self._lock:
                ent = self._entries.get(slot)
                if ent is not None:
                    if ent.key == key:
                        self._hits += 1
                        self._entries.move_to_end(slot)
                        return ent.source
                    # snapshot swapped under us: drop and reopen (the
                    # swap also lifts any quarantine on the path)
                    del self._entries[slot]
                    self._invalidations += 1
                    self._clear_quarantine_locked(path, key)
                pending = self._pending.get(slot)
                if pending is None:
                    pending = self._pending[slot] = _Pending()
                    opener = True
                else:
                    opener = False
            if not opener:
                # watchdogged wait: a wedged opener (stuck IO inside
                # open) must not strand every other request forever
                if not pending.event.wait(faults_mod.WATCHDOG_S):
                    with self._lock:
                        self._faults["wait_timeouts"] += 1
                    raise StageTimeout(
                        f"SourceCache: open of {path} still pending after "
                        f"{faults_mod.WATCHDOG_S:.1f}s (REPRO_WATCHDOG_S); "
                        f"the opening thread is stuck")
                if pending.source is not None:
                    # served the opener's handle: a hit, like any other
                    # request answered without opening the file
                    with self._lock:
                        self._hits += 1
                    return pending.source
                # the opener failed; retry (surfacing our own error)
                continue
            # the pending event MUST be set on every exit from this
            # opener block — an exception anywhere (the open itself, or
            # bookkeeping after it) that skipped the set would leave
            # every waiter blocked forever on a slot nobody owns
            try:
                source = faults_mod.call_with_retries(
                    lambda: self._open_once(path, open_kw),
                    describe=f"SourceCache open {path}",
                    on_retry=self._note_open_retry)
                pending.source = source
                with self._lock:
                    self._misses += 1
                    self._entries[slot] = _Entry(key, source)
                    self._entries.move_to_end(slot)
                    while len(self._entries) > self.capacity:
                        self._entries.popitem(last=False)
                        self._evictions += 1
                return source
            except BaseException as exc:
                pending.error = exc
                raise
            finally:
                with self._lock:
                    self._pending.pop(slot, None)
                pending.event.set()

    def _open_once(self, path: str, open_kw: Dict[str, Any]) -> GraphSource:
        if faults_mod._ACTIVE is not None:      # chaos hook (open site)
            faults_mod.inject("open", 0, where=path)
        return self._open_fn(path, **open_kw)

    def _note_open_retry(self, exc: BaseException) -> None:
        with self._lock:
            self._faults["open_retries"] += 1

    def query(self, path: str, op: str, *, rows=None, vertex=None,
              with_weights: bool = False, **open_kw) -> Any:
        """One request against the cache.  ``op`` selects the product:

        ==============  ==================================================
        op              result
        ==============  ==================================================
        ``info``        :class:`~repro.core.source.SourceInfo`
        ``csr``         the full :class:`~repro.core.types.CSR`
        ``rows``        ``.csr(rows=rows)`` — row-local CSR slice
        ``neighbors``   ``.neighbors(vertex)`` point lookup
        ``degree``      ``.degree(vertex)``
        ``edgelist``    the full :class:`~repro.core.types.EdgeList`
        ==============  ==================================================

        A corrupt section (CRC/decode failure, surfaced as
        :class:`~repro.core.snapshot.SnapshotError`) quarantines
        ``(path, section)``: this and subsequent requests touching that
        section get a structured :class:`CorruptGraphError` while other
        sections and other graphs keep serving; swapping the file on
        disk lifts the quarantine (see docs/robustness.md).
        """
        self.check_quarantine(path, _OP_SECTIONS.get(op))
        src = self.get(path, **open_kw)
        try:
            if op == "info":
                return src.info()
            if op in ("csr", "full"):
                return src.csr()
            if op in ("rows", "csr_rows", "range"):
                if rows is None:
                    raise ValueError("op 'rows' needs rows=")
                return src.csr(rows=rows)
            if op in ("neighbors", "point"):
                if vertex is None:
                    raise ValueError("op 'neighbors' needs vertex=")
                return src.neighbors(vertex, with_weights=with_weights)
            if op == "degree":
                if vertex is None:
                    raise ValueError("op 'degree' needs vertex=")
                return src.degree(vertex)
            if op == "edgelist":
                return src.edgelist()
        except SnapshotError as exc:
            raise self.report_corrupt(path, exc, op=op) from exc
        raise ValueError(
            f"unknown query op {op!r}; one of: info, csr, rows, neighbors, "
            f"degree, edgelist")

    # -- corruption quarantine -----------------------------------------------

    def check_quarantine(self, path: str,
                         sections: Optional[Tuple[str, ...]] = None) -> None:
        """Raise :class:`CorruptGraphError` when a live quarantine entry
        for ``path`` covers one of ``sections`` (any section when
        ``None``).  Entries whose file changed on disk since the
        corrupt read (stat key differs) are *cleared* instead — the
        swap-recovery contract."""
        path = str(path)
        with self._lock:
            entries = [(k, rec) for k, rec in self._quarantined.items()
                       if k[0] == path]
        if not entries:
            return
        try:
            key = _stat_key(path)
        except OSError:
            key = None                  # vanished file: treat as swapped
        hit = None
        with self._lock:
            for (p, sec), rec in entries:
                if rec["stat"] != key:
                    if self._quarantined.pop((p, sec), None) is not None:
                        self._faults["recovered"] += 1
                    continue
                # an op with an empty section tuple ("info") reads no
                # payload and is never blocked, even by an "unknown"
                # quarantine — health probes must outlive the corruption
                if sections is None or (len(sections) > 0 and
                                        (sec in sections or sec == "unknown")):
                    hit = (sec, rec)
            if hit is not None:
                self._faults["corrupt_errors"] += 1
                hit[1]["count"] += 1
        if hit is not None:
            sec, rec = hit
            raise CorruptGraphError(
                f"{path}: section {sec!r} is quarantined after a corrupt "
                f"read ({rec['error']}); serving resumes when the file is "
                f"replaced on disk",
                path=path, section=sec)

    def report_corrupt(self, path: str, exc: BaseException, *,
                       op: Optional[str] = None) -> CorruptGraphError:
        """Record a corrupt read of ``path`` (quarantining the section
        named by ``exc.section``, or ``"unknown"``) and return the
        structured error for the caller to raise.  Idempotent per
        section; counts every report."""
        path = str(path)
        section = getattr(exc, "section", None) or "unknown"
        try:
            key = _stat_key(path)
        except OSError:
            key = None
        with self._lock:
            rec = self._quarantined.get((path, section))
            if rec is None:
                rec = self._quarantined[(path, section)] = {
                    "stat": key, "error": str(exc), "count": 0}
                self._faults["quarantines"] += 1
            rec["count"] += 1
            rec["stat"] = key
            rec["error"] = str(exc)
            self._faults["corrupt_errors"] += 1
        return CorruptGraphError(
            f"{path}: corrupt read of section {section!r}"
            f"{f' during op {op!r}' if op else ''}: {exc}",
            path=path, section=section, op=op)

    def quarantined(self) -> List[Dict[str, Any]]:
        """Live quarantine entries (path, section, error, count)."""
        with self._lock:
            return [{"path": p, "section": s, "error": rec["error"],
                     "count": rec["count"]}
                    for (p, s), rec in self._quarantined.items()]

    def _clear_quarantine_locked(self, path: str, new_key) -> None:
        """Drop quarantine entries for ``path`` whose recorded stat no
        longer matches ``new_key`` (the file was swapped).  Caller holds
        the lock."""
        for k in [k for k in self._quarantined if k[0] == path]:
            if self._quarantined[k]["stat"] != new_key:
                del self._quarantined[k]
                self._faults["recovered"] += 1

    # -- management ----------------------------------------------------------

    def invalidate(self, path: Optional[str] = None) -> int:
        """Drop entries for ``path`` (all its kwarg variants), or every
        entry with ``path=None``.  Returns the number dropped.  In-use
        handles stay valid for their holders — only the cache forgets
        them."""
        with self._lock:
            if path is None:
                n = len(self._entries)
                self._entries.clear()
            else:
                path = str(path)
                stale = [s for s in self._entries if s[0] == path]
                for s in stale:
                    del self._entries[s]
                n = len(stale)
            self._invalidations += n
            return n

    def clear(self) -> None:
        self.invalidate(None)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, path: str) -> bool:
        with self._lock:
            return any(s[0] == str(path) for s in self._entries)

    def stats(self) -> Dict[str, Any]:
        """Counters since construction: ``hits``/``misses`` (misses ==
        opens that were cached), ``evictions`` (capacity),
        ``invalidations`` (stat-key changes + explicit), ``size``, and
        ``frame_cache`` — the decoded-frame memo counters summed over
        the hot handles' pinned snapshots (bytes held, hits, LRU
        evictions past ``snapshot.FRAME_CACHE_BYTES``), the memory the
        selective-read path pins on this cache's behalf.

        ``faults`` is the robustness health block: per-cache counters
        (open retries, corrupt reads, quarantines entered/recovered,
        watchdogged waits), the live quarantine list, the process-wide
        recovery counters from :mod:`repro.core.faults` (IO retries,
        stage timeouts, shard re-executions), and — when a fault plan
        is active — the injected-fault counts by ``site:kind``."""
        plan = faults_mod.active_plan()
        with self._lock:
            frame = {"frames": 0, "bytes": 0, "hits": 0, "evictions": 0}
            for ent in self._entries.values():
                fc = getattr(ent.source, "frame_cache_stats", None)
                fc = fc() if callable(fc) else None
                if fc:
                    for k in frame:
                        frame[k] += fc.get(k, 0)
            faults = dict(self._faults)
            faults["quarantined"] = [
                {"path": p, "section": s, "count": rec["count"]}
                for (p, s), rec in self._quarantined.items()]
            faults.update(faults_mod.counters())
            faults["injected"] = {} if plan is None else plan.injected()
            return {"hits": self._hits, "misses": self._misses,
                    "evictions": self._evictions,
                    "invalidations": self._invalidations,
                    "size": len(self._entries),
                    "capacity": self.capacity,
                    "frame_cache": frame,
                    "faults": faults}


_default: Optional[SourceCache] = None
_default_lock = threading.Lock()


def default_cache() -> SourceCache:
    """The process-wide cache behind the module-level :func:`query`."""
    global _default
    with _default_lock:
        if _default is None:
            _default = SourceCache()
        return _default


def query(path: str, op: str, **kw) -> Any:
    """Serve one graph query through the process-wide hot-graph cache —
    the front door for the query service (see :meth:`SourceCache.query`
    for ops).  ``repro.serve`` / benchmark drivers call this."""
    return default_cache().query(path, op, **kw)
