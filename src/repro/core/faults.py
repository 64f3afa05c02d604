"""Deterministic fault injection + the self-healing IO machinery it tests.

The IO stack (streaming loader -> sharded mesh load -> snapshot mmap ->
SourceCache -> ServeRuntime) is the hot path this repo exists to make
fast; this module is what keeps it *alive* when the bytes misbehave.
Two halves, deliberately in one file so the recovery code and the chaos
harness that exercises it can never drift apart:

* **Injection** — a seeded :class:`FaultPlan` of :class:`FaultSpec`
  entries, activated process-wide via :func:`set_fault_plan`, the
  :func:`fault_plan` context manager, or the ``REPRO_FAULTS`` env var
  (``"seed=7;block:oserror@3*2;frame:bitflip@0"``).  Hooks at four
  sites — ``block`` (staged block batches, via
  :class:`FaultyBlockSource`), ``frame`` (compressed-frame decodes in
  :mod:`repro.core.codecs`), ``open`` (:class:`~repro.core.cache.
  SourceCache` cold opens) and ``mmap`` (:func:`repro.core.blocks.
  mmap_bytes`) — inject transient ``OSError`` s, latency spikes,
  stuck-reader stalls, truncations and bit-flips at chosen indices.
  With no active plan every hook is a single ``is None`` test: the
  disabled path adds no measurable overhead (the perf gates in
  scripts/verify.sh run with this layer compiled in).

* **Recovery** — :func:`call_with_retries` (bounded exponential
  backoff over the *transient* ``OSError`` class; ``REPRO_IO_RETRIES``),
  the :data:`WATCHDOG_S` budget every prefetch/staging wait honours
  (``REPRO_WATCHDOG_S``), and the structured errors the rest of the
  stack raises: :class:`StageTimeout` (a stuck reader, naming the byte
  span), :class:`ShardLoadError` (a shard's retry budget exhausted,
  carrying the per-attempt fault log) and :class:`CorruptGraphError`
  (a quarantined ``(path, section)`` in the serving path).

Injection raises/stalls *before* delegating to the wrapped reader, so
a retried call observes exactly the state the failed call did —
bitwise-identical re-execution is what the chaos matrix asserts.
Semantics and knobs: docs/robustness.md.
"""
from __future__ import annotations

import dataclasses
import errno
import os
import threading
import time
from contextlib import contextmanager
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "FaultSpec", "FaultPlan", "FaultyBlockSource",
    "StageTimeout", "ShardLoadError", "CorruptGraphError",
    "set_fault_plan", "active_plan", "fault_plan", "plan_from_env",
    "inject", "corrupt_bytes", "wrap_block_source",
    "call_with_retries", "is_transient",
    "counters", "reset_counters",
]

SITES = ("block", "frame", "open", "mmap")
KINDS = ("oserror", "latency", "stall", "truncate", "bitflip")

# -- knobs (module globals so tests monkeypatch them; env sets defaults) ------

#: attempts per IO call (1 = no retry); $REPRO_IO_RETRIES
DEFAULT_ATTEMPTS = max(1, int(os.environ.get("REPRO_IO_RETRIES", "3")))
#: first-retry sleep; doubles per attempt; $REPRO_IO_BACKOFF_S
DEFAULT_BACKOFF_S = float(os.environ.get("REPRO_IO_BACKOFF_S", "0.005"))
#: seconds a staging/prefetch wait may block before StageTimeout;
#: $REPRO_WATCHDOG_S
WATCHDOG_S = float(os.environ.get("REPRO_WATCHDOG_S", "120"))
#: extra re-executions of a whole shard span after its in-span retries
#: are exhausted; $REPRO_SHARD_RETRIES
SHARD_RETRIES = max(0, int(os.environ.get("REPRO_SHARD_RETRIES", "2")))

#: OSError errnos retried as transient.  Deliberately narrow: missing
#: files, permissions, and directory mistakes are programming errors
#: and fail immediately.
TRANSIENT_ERRNOS = frozenset({
    errno.EIO, errno.EAGAIN, errno.EINTR, errno.EBUSY,
    errno.ETIMEDOUT, errno.ESTALE, errno.ECONNRESET,
})


# -- structured errors --------------------------------------------------------


class StageTimeout(TimeoutError):
    """A staging/prefetch worker produced nothing within the watchdog
    budget.  The message names the file and byte span so a stuck NFS
    mount or wedged decompressor is diagnosable from the error alone;
    the stuck thread is abandoned (never joined) so the caller's
    control flow continues."""


class ShardLoadError(RuntimeError):
    """One shard of a sharded streaming load exhausted its re-execution
    budget.  ``fault_log`` holds one line per failed attempt."""

    def __init__(self, message: str, *, shard: int = -1,
                 fault_log: Sequence[str] = ()):
        super().__init__(message)
        self.shard = int(shard)
        self.fault_log = list(fault_log)


class CorruptGraphError(RuntimeError):
    """Structured corruption error for the serving path: the graph at
    ``path`` has a quarantined ``section`` (CRC/decode failure).  Other
    sections and other graphs in the same cache keep serving; the
    quarantine lifts when the file is swapped on disk."""

    def __init__(self, message: str, *, path: str = "",
                 section: str = "unknown", op: Optional[str] = None):
        super().__init__(message)
        self.path = str(path)
        self.section = str(section)
        self.op = op


# -- fault plans --------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One planned fault.

    ``site``   -- where it fires: ``block`` (block id), ``frame``
                  (frame index), ``open`` / ``mmap`` (index is always 0;
                  use ``path`` to choose the file).
    ``kind``   -- ``oserror`` (transient EIO), ``latency`` (short
                  sleep), ``stall`` (sleep ``delay_s`` — set it past the
                  watchdog to simulate a stuck reader), ``truncate``
                  (drop trailing bytes), ``bitflip`` (flip one seeded
                  bit).
    ``index``  -- site-local index the fault targets.
    ``times``  -- injections before the spec is spent (< 0: unlimited).
    ``path``   -- substring filter on the target's description.
    """
    site: str
    kind: str
    index: int = 0
    times: int = 1
    path: str = ""
    delay_s: float = 0.05

    def __post_init__(self):
        if self.site not in SITES:
            raise ValueError(f"FaultSpec: unknown site {self.site!r}; "
                             f"sites: {SITES}")
        if self.kind not in KINDS:
            raise ValueError(f"FaultSpec: unknown kind {self.kind!r}; "
                             f"kinds: {KINDS}")


class FaultPlan:
    """A seeded, thread-safe schedule of :class:`FaultSpec` s.

    ``match`` consumes spec budgets atomically, so concurrent staging
    threads injecting from one plan see a deterministic total count;
    data corruption (:meth:`corrupt`) is a pure function of
    ``(seed, spec, salt)`` so chaos runs reproduce bit-for-bit.
    """

    def __init__(self, faults: Iterable[FaultSpec], *, seed: int = 0):
        self.faults: Tuple[FaultSpec, ...] = tuple(faults)
        self.seed = int(seed)
        self._lock = threading.Lock()
        self._fired = [0] * len(self.faults)
        self._counts: Dict[str, int] = {}

    def has_site(self, site: str) -> bool:
        return any(f.site == site for f in self.faults)

    def match(self, site: str, index: int, where: str = "") -> List[FaultSpec]:
        """Specs firing for this event; consumes their budgets."""
        out: List[FaultSpec] = []
        with self._lock:
            for i, f in enumerate(self.faults):
                if f.site != site or f.index != int(index):
                    continue
                if f.path and f.path not in where:
                    continue
                if f.times >= 0 and self._fired[i] >= f.times:
                    continue
                self._fired[i] += 1
                key = f"{f.site}:{f.kind}"
                self._counts[key] = self._counts.get(key, 0) + 1
                out.append(f)
        return out

    def injected(self) -> Dict[str, int]:
        """``{"site:kind": count}`` of faults actually fired."""
        with self._lock:
            return dict(self._counts)

    def total_injected(self) -> int:
        with self._lock:
            return sum(self._counts.values())

    def corrupt(self, data: bytes, spec: FaultSpec, salt: int = 0) -> bytes:
        """Deterministically damaged copy of ``data`` per ``spec``."""
        if not data:
            return data
        rng = np.random.default_rng((self.seed, spec.index, salt))
        if spec.kind == "truncate":
            keep = max(1, len(data) - max(1, len(data) // 4))
            return data[:keep]
        if spec.kind == "bitflip":
            buf = bytearray(data)
            buf[int(rng.integers(len(buf)))] ^= 1 << int(rng.integers(8))
            return bytes(buf)
        return data


# -- activation ---------------------------------------------------------------

_ACTIVE: Optional[FaultPlan] = None
_ACTIVE_LOCK = threading.Lock()


def set_fault_plan(plan: Optional[FaultPlan]) -> Optional[FaultPlan]:
    """Install ``plan`` process-wide (``None`` disables injection)."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        _ACTIVE = plan
    return plan


def active_plan() -> Optional[FaultPlan]:
    return _ACTIVE


@contextmanager
def fault_plan(plan: Optional[FaultPlan]):
    """Activate ``plan`` for the dynamic extent.  ``None`` is a no-op
    (the surrounding plan, if any, stays active) so callers can thread
    an optional ``LoadOptions.faults`` through unconditionally."""
    global _ACTIVE
    if plan is None:
        yield None
        return
    with _ACTIVE_LOCK:
        prev, _ACTIVE = _ACTIVE, plan
    try:
        yield plan
    finally:
        with _ACTIVE_LOCK:
            _ACTIVE = prev


def plan_from_env(spec: Optional[str] = None) -> Optional[FaultPlan]:
    """Parse a ``REPRO_FAULTS`` spec into a plan (``None`` if empty).

    Grammar (``;``-separated entries)::

        seed=<int>
        <site>:<kind>[@<index>][*<times>][~<path-substring>]

    e.g. ``"seed=7;block:oserror@3*2;frame:bitflip@0~web.gvel"``.
    """
    if spec is None:
        spec = os.environ.get("REPRO_FAULTS", "")
    spec = spec.strip()
    if not spec:
        return None
    seed, faults = 0, []
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        if part.startswith("seed="):
            seed = int(part[len("seed="):])
            continue
        site, sep, rest = part.partition(":")
        if not sep:
            raise ValueError(f"REPRO_FAULTS: bad entry {part!r} "
                             f"(want site:kind[@index][*times][~path])")
        path, times, index = "", 1, 0
        if "~" in rest:
            rest, path = rest.split("~", 1)
        if "*" in rest:
            rest, times_s = rest.split("*", 1)
            times = int(times_s)
        kind, _, tail = rest.partition("@")
        if tail:
            index = int(tail)
        faults.append(FaultSpec(site=site, kind=kind, index=index,
                                times=times, path=path))
    return FaultPlan(faults, seed=seed)


# a REPRO_FAULTS env plan is live from import (how the chaos lane arms
# subprocesses without touching their code)
set_fault_plan(plan_from_env())


# -- injection hooks ----------------------------------------------------------


def inject(site: str, index: int, *, where: str = "") -> List[FaultSpec]:
    """Fire the active plan's faults for one event.

    Raising kinds (``oserror``) raise here; sleeping kinds
    (``latency``/``stall``) sleep here — both *before* the caller
    touches its underlying reader, which is what makes a retry safe.
    Data kinds (``truncate``/``bitflip``) are returned for the caller
    to apply to the bytes it is about to produce.
    """
    plan = _ACTIVE
    if plan is None:
        return []
    mutators: List[FaultSpec] = []
    for f in plan.match(site, index, where):
        if f.kind in ("latency", "stall"):
            time.sleep(f.delay_s)
        elif f.kind == "oserror":
            raise OSError(
                errno.EIO,
                f"injected transient IO error at {where or site} "
                f"(index {index})")
        else:
            mutators.append(f)
    return mutators


def corrupt_bytes(data: bytes, spec: FaultSpec, salt: int = 0) -> bytes:
    plan = _ACTIVE
    return data if plan is None else plan.corrupt(data, spec, salt)


class FaultyBlockSource:
    """A ``BlockSource`` wrapper injecting ``block``-site faults.

    Raising/sleeping faults fire *before* delegation, so the inner
    source's cursor (``SequentialBlockSource`` advances ``_next_block``
    at entry) is untouched by an injected failure and the retried
    ``stage`` call is exact.  Data faults corrupt a copy of the staged
    bytes (the staged buffer itself is never damaged).
    """

    def __init__(self, inner, where: str):
        self._inner = inner
        self._where = str(where)
        self._describe = getattr(inner, "_describe", self._where)

    @property
    def length(self):
        return self._inner.length

    def stage(self, plan, block_ids, check_lines: bool = False):
        ids = np.asarray(block_ids, dtype=np.int64)
        mutators: List[Tuple[FaultSpec, int]] = []
        for b in ids:
            for f in inject("block", int(b), where=self._where):
                mutators.append((f, int(b)))
        out = self._inner.stage(plan, block_ids, check_lines=check_lines)
        if mutators:
            out = np.array(out, copy=True)   # never damage the staged bytes
            for f, b in mutators:
                row = int(np.nonzero(ids == b)[0][0])
                raw = out[row].tobytes()
                bad = corrupt_bytes(raw, f, salt=b)
                out[row] = np.frombuffer(           # truncation keeps the
                    bad.ljust(len(raw), b"\n"),     # staged shape: pad \n
                    np.uint8)
        return out

    def finish(self) -> None:
        self._inner.finish()


def wrap_block_source(source, where: str):
    """Wrap ``source`` when the active plan has block-site faults;
    otherwise return it untouched (the zero-fault path has no wrapper
    in the stack at all)."""
    plan = _ACTIVE
    if plan is None or not plan.has_site("block"):
        return source
    return FaultyBlockSource(source, where)


# -- retries + counters -------------------------------------------------------

_COUNT_LOCK = threading.Lock()
_COUNTERS = {"io_retries": 0, "stage_timeouts": 0, "shard_retries": 0}


def _count(key: str, n: int = 1) -> None:
    with _COUNT_LOCK:
        _COUNTERS[key] = _COUNTERS.get(key, 0) + n


def counters() -> Dict[str, int]:
    """Process-wide recovery counters (retries, timeouts, shard
    re-executions) — surfaced via ``SourceCache.stats()["faults"]``."""
    with _COUNT_LOCK:
        return dict(_COUNTERS)


def reset_counters() -> None:
    with _COUNT_LOCK:
        for k in _COUNTERS:
            _COUNTERS[k] = 0


def is_transient(exc: BaseException) -> bool:
    """True for the OSError class worth retrying: EIO/EAGAIN/... but
    never missing files or permission errors."""
    return (isinstance(exc, OSError)
            and exc.errno in TRANSIENT_ERRNOS)


def call_with_retries(fn: Callable[[], "object"], *,
                      describe: str = "io operation",
                      attempts: Optional[int] = None,
                      backoff_s: Optional[float] = None,
                      on_retry: Optional[Callable[[BaseException], None]]
                      = None):
    """``fn()`` with bounded retry of *transient* failures.

    Exponential backoff starting at ``backoff_s`` (defaults are the
    module knobs, resolved at call time so tests can monkeypatch).
    Non-transient exceptions, and the last transient one, propagate
    unchanged.
    """
    attempts = DEFAULT_ATTEMPTS if attempts is None else max(1, int(attempts))
    backoff_s = DEFAULT_BACKOFF_S if backoff_s is None else float(backoff_s)
    for attempt in range(attempts):
        try:
            return fn()
        except BaseException as exc:
            if not is_transient(exc) or attempt + 1 >= attempts:
                raise
            _count("io_retries")
            if on_retry is not None:
                on_retry(exc)
            time.sleep(backoff_s * (2 ** attempt))
