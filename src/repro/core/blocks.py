"""Host-side block planning and staging (GVEL getBlock, TPU-adapted).

The file is cut into uniform beta-byte blocks.  Each block's device buffer
is `overlap + beta` bytes: `overlap` bytes of left context plus the owned
range.  Buffers are newline-padded at both file edges so the very first
byte of the file starts a line and the final line is always terminated —
the branch-free replacement for GVEL's newline repositioning.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

NEWLINE = 10


def mmap_bytes(path: str, offset: int = 0) -> np.ndarray:
    """Memory-map a file as uint8, optionally skipping a header prefix.

    GVEL maps the file and advises WILLNEED; np.memmap is the same
    mmap(2) under the hood, and the staging loops touch pages
    sequentially, which triggers kernel readahead (the madvise effect).
    Shared by the text staging pipeline, the host parsers, and the
    binary snapshot reader.
    """
    from . import faults
    if faults._ACTIVE is not None:          # chaos hook; no-op otherwise
        faults.inject("mmap", 0, where=path)
    size = os.path.getsize(path)
    if size <= offset:
        return np.zeros(0, np.uint8)
    data = np.memmap(path, dtype=np.uint8, mode="r")
    return data[offset:] if offset else data


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    file_len: int
    beta: int          # owned bytes per block (GVEL: 256 KiB)
    overlap: int       # left context >= max line length
    num_blocks: int
    buf_len: int       # overlap + beta

    @property
    def edge_cap(self) -> int:
        # min parsable line is 4 bytes ("1 2\n"); +2 slack
        return self.buf_len // 4 + 2


def plan_blocks(file_len: int, beta: int = 256 * 1024, overlap: int = 64) -> BlockPlan:
    if beta <= overlap:
        raise ValueError(f"beta ({beta}) must exceed overlap ({overlap})")
    num_blocks = max(1, -(-file_len // beta))
    return BlockPlan(file_len, beta, overlap, num_blocks, overlap + beta)


def flat_len(nb: int, plan: BlockPlan) -> int:
    """Bytes of flat staging needed for ``nb`` consecutive blocks (one
    block's owned bytes per stride step, plus the final overlap)."""
    return (nb - 1) * plan.beta + plan.buf_len


def _new_flat(nb: int, plan: BlockPlan) -> np.ndarray:
    """A fresh newline-filled flat staging buffer for ``nb`` blocks.

    Fresh on every call: the streaming loader hands each staged batch to
    a host-to-device transfer that may read (or, on the CPU backend,
    alias) the bytes after it returns, so a buffer is never reused."""
    return np.full(flat_len(nb, plan), NEWLINE, np.uint8)


def _strided_block_view(flat: np.ndarray, nb: int, plan: BlockPlan) -> np.ndarray:
    """Zero-copy per-block windows over a flat span.  Rows alias (row
    r's overlap tail IS row r+1's head), so the view is read-only;
    consumers copy into device buffers anyway."""
    return np.lib.stride_tricks.as_strided(
        flat, shape=(nb, plan.buf_len), strides=(plan.beta, 1),
        writeable=False)


def check_line_overlap(view: np.ndarray, plan: BlockPlan,
                       ids: np.ndarray, data_len: int,
                       describe: str = "staged blocks") -> None:
    """Detect lines longer than ``plan.overlap`` crossing a block's owned
    start — the one staging geometry the parser cannot recover from.

    The parse contract says no line may exceed ``overlap`` bytes; when a
    longer line spans a block boundary its head lies before the owning
    block's buffer and the parser would silently mis-parse the truncated
    tail (a too-long comment whose tail looks like digits becomes a
    phantom edge).  For in-contract inputs every ``overlap``-wide window
    of file bytes contains a newline, so this check never fires on them:
    a block whose left-context window ``[b*beta - overlap, b*beta)`` has
    *no* newline proves a violating line and raises, naming the byte
    offset.  Block 0 is exempt (its left context is synthetic padding),
    as are windows past EOF (newline-padded).
    """
    ids = np.asarray(ids, np.int64)
    if len(ids) == 0:
        return
    need = (ids > 0) & (ids * plan.beta < data_len)
    if not need.any():
        return
    ok = (view[:, :plan.overlap] == NEWLINE).any(axis=1)
    bad = need & ~ok
    if bad.any():
        b = int(ids[int(np.argmax(bad))])
        off = b * plan.beta
        raise ValueError(
            f"{describe}: no newline within overlap={plan.overlap} bytes "
            f"before byte offset {off} (block {b}'s owned start) — a line "
            f"longer than {plan.overlap} bytes crosses the block boundary "
            f"there and would be mis-parsed.  Re-run with a larger "
            f"overlap= (it must exceed the longest line, including "
            f"comments), or strip overlong lines; offsets are relative to "
            f"any header offset skipped at open.")


def stage_blocks(data: np.ndarray, plan: BlockPlan, block_ids: np.ndarray,
                 check_lines: bool = False) -> np.ndarray:
    """Gather block buffers (with left overlap) into an (nb, buf_len) array.

    ``data`` is the memory-mapped file bytes (uint8).  Out-of-file regions
    (before byte 0, after EOF) are filled with newlines.

    Consecutive block ids (the streaming loader's batches) take a fast
    path: one contiguous memcpy of the spanned byte range into a
    newline-padded flat buffer, then a zero-copy strided window per
    block — the per-block Python loop this replaces copied the overlap
    bytes twice and paid a numpy slice round-trip per block.

    ``check_lines=True`` (the text-parse pipelines set it; raw byte
    staging does not) raises ``ValueError`` when a line longer than
    ``plan.overlap`` bytes crosses a block's owned start
    (:func:`check_line_overlap`).
    """
    ids = np.asarray(block_ids, np.int64)
    nb = len(ids)
    n = plan.file_len
    if nb == 0:
        return np.zeros((0, plan.buf_len), np.uint8)
    if nb == 1 or np.all(np.diff(ids) == 1):
        lo = int(ids[0]) * plan.beta - plan.overlap        # may be < 0
        s = max(lo, 0)
        e = min(lo + flat_len(nb, plan), n)
        flat = _new_flat(nb, plan)
        if e > s:
            flat[s - lo : e - lo] = data[s:e]
        view = _strided_block_view(flat, nb, plan)
    else:
        # general (non-contiguous) case: per-block slice copies
        view = np.full((nb, plan.buf_len), NEWLINE, np.uint8)
        for row, b in enumerate(ids):
            lo = int(b) * plan.beta - plan.overlap
            hi = int(b) * plan.beta + plan.beta
            s, e = max(lo, 0), min(hi, n)
            if e > s:
                view[row, s - lo : e - lo] = data[s:e]
    if check_lines:
        check_line_overlap(view, plan, ids, n)
    return view


def owned_range(plan: BlockPlan) -> tuple[int, int]:
    """Buffer-local [start, end) of the owned byte range (uniform per block)."""
    return plan.overlap, plan.overlap + plan.beta


@dataclasses.dataclass(frozen=True)
class ShardSpan:
    """Shard ``shard``-of-``num_shards``'s contiguous slice of a BlockPlan.

    The split is **block-aligned**, which is what makes it safe: a line
    is owned by the block containing its terminating newline, and a
    block's left context comes from its own staged ``overlap`` bytes —
    so any contiguous block range parses exactly the lines it owns, with
    no coordination with neighbouring shards.  For framed codecs the
    plan's beta is already forced to ``frame_beta``, so a block-aligned
    split is frame-aligned for free.
    """

    plan: BlockPlan
    shard: int
    num_shards: int
    block_lo: int      # first owned block (inclusive)
    block_hi: int      # past-the-end block; == block_lo for an empty span

    @property
    def num_blocks(self) -> int:
        return self.block_hi - self.block_lo

    @property
    def byte_lo(self) -> int:
        """First owned file byte (post-header coordinates)."""
        return min(self.block_lo * self.plan.beta, self.plan.file_len)

    @property
    def byte_hi(self) -> int:
        """Past-the-end owned file byte."""
        return min(self.block_hi * self.plan.beta, self.plan.file_len)

    @property
    def edge_cap(self) -> int:
        """Accumulator slots this span needs (over-allocation bound)."""
        return self.num_blocks * self.plan.edge_cap


def shard_plan(plan: BlockPlan, k: int, d: int) -> ShardSpan:
    """Partition ``plan``'s blocks into ``d`` contiguous byte-range spans
    and return shard ``k``'s.

    Spans are balanced to within one block, ordered (shard k's bytes all
    precede shard k+1's — the exchange stage relies on this to keep
    received edges in global file order), disjoint, and jointly cover
    every block.  When the mesh is wider than the plan (``d`` >
    ``num_blocks``) the excess shards get empty spans, which the sharded
    loader must — and does — handle: their accumulators simply stay
    empty.
    """
    if d < 1:
        raise ValueError(f"num_shards must be >= 1, got {d}")
    if not 0 <= k < d:
        raise ValueError(f"shard index {k} outside [0, {d})")
    nb = plan.num_blocks
    return ShardSpan(plan, k, d, (k * nb) // d, ((k + 1) * nb) // d)


# ---------------------------------------------------------------------------
# block sources: where staged block bytes come from
# ---------------------------------------------------------------------------
#
# The streaming loader used to stage straight off an mmap; compressed
# inputs (core.codecs) need the same staging over bytes that only exist
# after decompression.  A block source answers "give me the staged
# buffers for these block ids" — random-access over memory, or
# sequentially over a stream of decompressed chunks.  The loader's
# prefetch thread drives `stage`, so for stream sources decompression
# runs in that thread and overlaps the device parse.

class MemoryBlockSource:
    """Random-access staging over in-memory (usually mmap'd) bytes."""

    def __init__(self, data: np.ndarray):
        self.data = data
        self.length = len(data)

    def stage(self, plan: BlockPlan, block_ids: np.ndarray,
              check_lines: bool = False) -> np.ndarray:
        return stage_blocks(self.data, plan, block_ids, check_lines)

    def finish(self) -> None:
        pass


class SequentialBlockSource:
    """Staging over a forward-only stream of byte chunks.

    ``chunks`` yields successive spans of the uncompressed byte stream
    (any sizes, including empty); ``length`` is the total expected after
    dropping the first ``skip`` bytes (an embedded-header offset, in
    uncompressed coordinates).  Batches must be consumed in order with
    contiguous ascending block ids — exactly how the streaming loader
    iterates.

    Pending bytes are held as a queue of zero-copy chunk views with a
    running stream offset: staging copies each overlapping chunk span
    straight into the flat batch buffer (one memcpy per chunk) and
    retains only the unconsumed tail views for the next batch's overlap
    — memory stays O(batch), and there is no per-batch compaction of a
    growing buffer (the old ``bytearray`` design paid an O(buffered)
    memmove per batch to delete its consumed prefix).

    A source may cover only a *span* of the logical stream — the sharded
    loader gives each mesh shard its own source over its byte range:
    ``start`` is the post-skip stream position of the first chunk byte
    (the chunks iterator must begin there — e.g. a frame-sliced framed
    reader), ``end`` is the past-the-end position this source must cover,
    and ``first_block`` is the first block id ``stage`` will be asked
    for.  ``start`` must not exceed ``first_block * beta - overlap`` (the
    leftmost byte the first staged batch needs); block-aligned spans with
    a one-block (or one-frame) left margin satisfy this because
    ``beta > overlap``.

    ``finish`` verifies coverage: a source whose span reaches the stream
    end (``end == length``) drains the remainder and demands the exact
    declared total (truncated file, lying gzip trailer); a mid-stream
    span only demands that the stream reached ``end`` — either way a
    short stream raises ``ValueError`` instead of returning a silently
    partial graph.
    """

    def __init__(self, chunks, length: int, *, skip: int = 0,
                 start: int = 0, end: int | None = None,
                 first_block: int = 0,
                 describe: str = "byte stream", mismatch_hint: str = ""):
        self._chunks = iter(chunks)
        self.length = max(int(length), 0)
        self._to_skip = skip
        self._start = min(max(int(start), 0), self.length)
        self._end = self.length if end is None else \
            min(max(int(end), self._start), self.length)
        self._describe = describe
        self._hint = mismatch_hint
        self._q: list[np.ndarray] = []     # pending chunk views, in order
        self._q_start = self._start    # stream offset of _q[0][0] (post-skip)
        self._q_len = 0                # total bytes queued
        self._produced = 0             # post-skip bytes pulled so far
        self._next_block = int(first_block)

    def _pull(self) -> bool:
        chunk = next(self._chunks, None)
        if chunk is None:
            return False
        if self._to_skip:
            drop = min(self._to_skip, len(chunk))
            self._to_skip -= drop
            chunk = chunk[drop:]
        self._produced += len(chunk)
        if len(chunk):
            view = np.frombuffer(chunk, np.uint8)
            self._q.append(view)
            self._q_len += len(view)
        return True

    def stage(self, plan: BlockPlan, block_ids: np.ndarray,
              check_lines: bool = False) -> np.ndarray:
        ids = np.asarray(block_ids, np.int64)
        nb = len(ids)
        if nb == 0:
            return np.zeros((0, plan.buf_len), np.uint8)
        if (nb > 1 and not np.all(np.diff(ids) == 1)) or \
                int(ids[0]) != self._next_block:
            raise ValueError(
                f"{self._describe}: sequential source staged out of order "
                f"(got blocks {ids[0]}..{ids[-1]}, expected "
                f"{self._next_block}..)")
        self._next_block = int(ids[-1]) + 1
        lo = int(ids[0]) * plan.beta - plan.overlap          # may be < 0
        hi = min((int(ids[-1]) + 1) * plan.beta, self.length)
        while self._q_start + self._q_len < hi:
            if not self._pull():
                break                 # short stream: pad now, finish() raises
        s = max(lo, 0)
        e = min(hi, self._q_start + self._q_len)
        flat = _new_flat(nb, plan)
        pos = self._q_start           # walk the queue once, copying spans
        for view in self._q:
            if pos >= e:
                break
            c0, c1 = max(s - pos, 0), min(e - pos, len(view))
            if c1 > c0:
                flat[pos + c0 - lo : pos + c1 - lo] = view[c0:c1]
            pos += len(view)
        # retain only the tail the next batch's overlap needs (views,
        # not copies); whole chunks before it are dropped
        keep_from = max((int(ids[-1]) + 1) * plan.beta - plan.overlap,
                        self._q_start)
        while self._q and self._q_start + len(self._q[0]) <= keep_from:
            dropped = self._q.pop(0)
            self._q_start += len(dropped)
            self._q_len -= len(dropped)
        if self._q and keep_from > self._q_start:
            cut = keep_from - self._q_start
            self._q[0] = self._q[0][cut:]
            self._q_start = keep_from
            self._q_len -= cut
        out = _strided_block_view(flat, nb, plan)
        if check_lines:
            check_line_overlap(out, plan, ids, self.length, self._describe)
        return out

    def finish(self) -> None:
        need = self._end - self._start
        if self._end >= self.length:
            # span reaches the stream end: drain and demand the exact total
            while self._pull():
                self._q.clear()       # drained bytes are only counted
                self._q_len = 0
            if self._produced != need:
                raise ValueError(
                    f"{self._describe}: stream decompressed to "
                    f"{self._start + self._produced} bytes after the header "
                    f"offset, expected {self.length}{self._hint}")
        else:
            # mid-stream span: only demand that the stream covered it
            while self._produced < need and self._pull():
                self._q.clear()
                self._q_len = 0
            if self._produced < need:
                raise ValueError(
                    f"{self._describe}: stream ended at byte "
                    f"{self._start + self._produced} (after the header "
                    f"offset), before this shard span's end at "
                    f"{self._end}{self._hint}")
