"""Streaming walk-corpus: a snapshot-backed GraphSource -> step-indexed
LM batch pipeline.

This is the bridge the ROADMAP's end-to-end scenario needs: the fast
loader (:func:`repro.core.source.open_graph`, or a hot
:class:`~repro.core.cache.SourceCache` handle) on one side, the
training/serving substrate on the other.

    corpus = WalkCorpus(open_graph("web.gvel"), CorpusConfig(batch=8))
    with corpus.batches(start_step=0) as stream:
        for step, batch in stream:
            ...

Contract (tests/test_corpus.py, docs/serving.md):

* **Step-indexed and pure**: ``batch_at(step)`` is a pure function of
  ``(CSR, cfg, step)`` — same snapshot + same config => bitwise-equal
  batch, forever.  ``batches(start_step=n)`` therefore resumes a
  killed stream mid-corpus with a bitwise-identical continuation; no
  replay, no drift.  The cursor (``save_cursor``/``load_cursor``) is
  just the next step index, written atomically so a preemption
  mid-save never corrupts it.
* **Prefetch-threaded, double-buffered**: ``batches()`` builds walk
  batch ``n+1`` (and stages it host->device) in a background thread
  while the consumer runs step ``n`` — the serving-side mirror of the
  loader's prefetch thread, reusing
  :class:`repro.data.pipeline.Prefetcher`.
* **Degradable**: per-walk keying in :mod:`repro.data.walks` means a
  batch-size cut keeps the surviving walks bitwise identical
  (``batch_at(step, batch=b)`` rows are a prefix of the full batch) —
  the straggler-degrade path in :mod:`repro.serve.runtime` leans on
  this.

The CSR is resolved once through the source's memo (``source.csr()``)
and pinned on the corpus as device arrays, so after the first batch no
host->device transfer of the graph ever repeats.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .pipeline import Prefetcher
from .walks import I32, random_walks


@dataclasses.dataclass(frozen=True)
class CorpusConfig:
    """Walk-corpus geometry and keying.  Every field participates in
    the determinism contract: same config + same snapshot => same
    batch stream."""

    batch: int = 8                    # walks (rows) per batch
    seq: int = 32                     # tokens per row (walk length - 1)
    vocab_size: int = 256             # token ids = vertex ids mod vocab
    seed: int = 99                    # corpus-level PRNG root
    lookahead: int = 2                # prefetch queue depth


class WalkCorpus:
    """A deterministic, prefetch-threaded walk-batch stream over one
    :class:`~repro.core.source.GraphSource`."""

    def __init__(self, source, cfg: CorpusConfig = CorpusConfig()):
        self.source = source
        self.cfg = cfg
        self._offsets = None          # device-pinned CSR, built lazily
        self._targets = None
        self._num_vertices = 0

    # -- graph resolution ----------------------------------------------------

    def _csr_arrays(self):
        """The source's CSR as device int32 arrays, pinned on the
        corpus (one transfer per corpus, not per batch)."""
        if self._offsets is None:
            csr = self.source.csr()
            self._offsets = jnp.asarray(np.asarray(csr.offsets), I32)
            self._targets = jnp.asarray(np.asarray(csr.targets), I32)
            self._num_vertices = int(csr.num_vertices)
        return self._offsets, self._targets, self._num_vertices

    # -- batches -------------------------------------------------------------

    def batch_at(self, step: int, *, batch: Optional[int] = None) -> dict:
        """The walk-LM batch for ``step`` — pure and memoless.  A
        smaller ``batch`` override returns the bitwise prefix of the
        full batch's rows (per-walk keying; see ``data/walks.py``)."""
        offsets, targets, v = self._csr_arrays()
        cfg = self.cfg
        b = cfg.batch if batch is None else int(batch)
        key = jax.random.fold_in(jax.random.key(cfg.seed), step)
        walks = random_walks(offsets, targets, key, num_walks=b,
                             length=cfg.seq + 1, num_vertices=v)
        toks = (walks % cfg.vocab_size).astype(I32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def batches(self, start_step: int = 0, *, sharding=None) -> "BatchStream":
        """Iterate ``(step, batch)`` from ``start_step`` with a
        lookahead thread building (and, with ``sharding``, staging
        host->device) the next batch while the caller consumes the
        current one.  Close the stream (or use ``with``) to stop the
        thread."""
        return BatchStream(self, start_step, sharding=sharding)


class BatchStream:
    """Iterator over ``(step, batch)`` backed by a prefetch thread.
    ``next_step`` is the resume cursor: checkpoint it after consuming a
    batch and ``batches(start_step=next_step)`` continues the stream
    bitwise-identically."""

    def __init__(self, corpus: WalkCorpus, start_step: int, *, sharding=None):
        corpus._csr_arrays()          # resolve the CSR before threading
        self.next_step = int(start_step)
        self._pf = Prefetcher(corpus.batch_at, start_step=self.next_step,
                              lookahead=corpus.cfg.lookahead,
                              sharding=sharding)

    def __iter__(self):
        return self

    def __next__(self):
        step = self.next_step
        batch = self._pf.get(expect_step=step)
        self.next_step = step + 1
        return step, batch

    def close(self):
        self._pf.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# -- resume cursor -----------------------------------------------------------

def save_cursor(path: str, step: int) -> None:
    """Durably persist the next step index (tmp + fsync + rename +
    directory fsync, same discipline as checkpoint/io.py: a preemption
    mid-write leaves the previous cursor intact).  The directory fsync
    is what makes the *rename* itself survive a host crash — without
    it the journal may replay the directory to the pre-rename state
    and lose the cursor the resume contract depends on."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"step": int(step)}, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    dfd = os.open(os.path.dirname(os.path.abspath(path)) or ".", os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)


def load_cursor(path: str) -> Optional[int]:
    """The persisted next step index, or ``None`` when no cursor
    exists yet (cold start)."""
    try:
        with open(path) as f:
            return int(json.load(f)["step"])
    except FileNotFoundError:
        return None
