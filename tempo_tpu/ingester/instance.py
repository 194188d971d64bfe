"""Per-tenant ingester instance: live traces → head block → local blocks.

Mirrors `modules/ingester/instance.go`: push with limit enforcement
(`push` `instance.go:199-228` → `PushErrorReason`), complete-trace cutting,
head-block lifecycle, WAL→columnar completion, and recent-data reads
(find/search) across live traces + head + completing + complete blocks.

TPU-first twist: completed blocks are columnar from birth (the parquet
writing path shared with the storage engine), and search over the
in-memory span dicts goes through the same vectorized `ColumnView`
evaluation as block scans — there is no separate row-at-a-time read path.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import uuid
from typing import Callable, Sequence

from tempo_tpu.backend.local import LocalBackend
from tempo_tpu.backend.meta import BlockMeta, read_block_meta
from tempo_tpu.block.live_columns import ColumnSource, cut_table
from tempo_tpu.block.reader import BackendBlock
from tempo_tpu.block.wal import WALBlock, rescan_blocks
from tempo_tpu.block.writer import write_block
from tempo_tpu.model.combine import combine_spans, sort_spans
from tempo_tpu.overrides.limits import Limits
from tempo_tpu.utils import tracing
from tempo_tpu.utils.livetraces import (
    CHUNK_SPANS,
    ERR_LIVE_TRACES_EXCEEDED,
    ERR_TRACE_TOO_LARGE,
    LIVE_SPANS,
    LiveTraceStore,
    TraceSet,
    key_trace_id,
)

PUSH_ERRORS = (ERR_LIVE_TRACES_EXCEEDED, ERR_TRACE_TOO_LARGE)


@dataclasses.dataclass
class InstanceConfig:
    max_block_duration_s: float = 1800.0   # ingester default 30m
    max_block_bytes: int = 500_000_000
    trace_idle_s: float = 5.0              # trace_idle_period
    trace_live_s: float = 30.0             # max live time before forced cut
    dedicated_columns: tuple = ()
    row_group_rows: int = 50_000
    replication_factor: int = 3            # 1 for generator localblocks


@dataclasses.dataclass
class LocalBlockEntry:
    """A completed, locally owned block (`modules/ingester/local_block.go`):
    flushed_ts tracks backend flush for replay-safe deletion."""
    meta: BlockMeta
    block: BackendBlock
    flushed_ts: float = 0.0


class TenantInstance:
    """One tenant's live traces, head WAL block and local blocks.

    Two locks, taken in this order and never the other way round:

    - `sweep_lock`, held by `cut_complete_traces` from the hand-over of
      the cut traces to the end of the segment's write, and by
      `cut_block_if_ready`: two sweeps of one instance never interleave,
      and a head block is never sealed while a segment is on its way
      into it. A push never takes it.
    - `lock`, the one a push takes: it covers the live store and the
      bookkeeping (`head`, `cutting`, `completing`, `complete`) and is
      never held through a table build or a write.

    `cutting` is the traces a sweep has taken out of `live` and whose
    segment is being built and written: in neither `live` nor (yet) any
    segment, so the reads look there too. None when no sweep is between
    its two holds of `lock`. Nobody appends to a trace once it is cut
    (spans that arrive for its id start a new live trace), so readers
    use what they take under `lock` (`TraceSet`s) after releasing it."""

    def __init__(self, tenant: str, wal_dir: str, local_dir: str,
                 cfg: InstanceConfig | None = None,
                 limits: Limits | None = None,
                 now: Callable[[], float] = time.time) -> None:
        self.tenant = tenant
        self.cfg = cfg or InstanceConfig()
        self.now = now
        lim = limits or Limits()
        self.live = LiveTraceStore(
            max_live_traces=lim.ingestion.max_traces_per_user,
            max_trace_bytes=lim.read.max_bytes_per_trace,
            now=now)
        self.wal_dir = wal_dir
        self.local_dir = local_dir
        os.makedirs(wal_dir, exist_ok=True)
        self.local_backend = LocalBackend(local_dir)
        self.head: WALBlock | None = None
        self.head_created = 0.0
        self.completing: list[WALBlock] = []     # cut, awaiting completion
        self.complete: dict[str, LocalBlockEntry] = {}
        self.cutting: TraceSet | None = None
        self.lock = threading.RLock()
        self.sweep_lock = threading.Lock()
        self.discarded: dict[str, int] = {}

    # -- write path --------------------------------------------------------

    def push_trace(self, trace_id: bytes, spans: Sequence[dict],
                   size_bytes: int | None = None) -> str | None:
        """Append one trace's spans; returns a PushErrorReason or None."""
        with self.lock:
            err = self.live.push(trace_id, spans, size_bytes)
            if err:
                self.discarded[err] = self.discarded.get(err, 0) + 1
            return err

    def push_columns(self, source: ColumnSource,
                     rows) -> dict[bytes, str]:
        """Append `rows` of a staged push (None: all of them) as ONE chunk
        (grouped by trace outside the lock); returns {trace_id:
        PushErrorReason} for the traces that were refused."""
        chunk = source.chunk(rows)
        if chunk is None:
            return {}
        spans = int(chunk.spans.sum())
        CHUNK_SPANS.inc(spans, (chunk.grouping,))
        with self.lock:
            refused = self.live.push_chunk(chunk)
            for err in refused.values():
                self.discarded[err] = self.discarded.get(err, 0) + 1
        LIVE_SPANS.inc(spans - int(chunk.spans[list(refused)].sum()),
                       ("columns",))
        return {key_trace_id(chunk.keys[i]): err
                for i, err in refused.items()}

    def cut_complete_traces(self, immediate: bool = False) -> int:
        """Idle/aged live traces → head WAL block (`CutCompleteTraces`).
        `lock` is held while the traces are taken and published as
        `cutting`, and again to withdraw them; the table build and the
        write run with only `sweep_lock` held, so pushes go on."""
        with self.sweep_lock:
            with self.lock, tracing.span("instance.cut_locked"):
                cut = self.live.cut(idle_s=self.cfg.trace_idle_s,
                                    max_age_s=self.cfg.trace_live_s,
                                    immediate=immediate)
                if not cut:
                    return 0
                if self.head is None:
                    self.head = WALBlock(self.wal_dir, self.tenant)
                    self.head_created = self.now()
                head = self.head
                self.cutting = cut
            try:
                # ONE segment (one parquet file, one fsync pair) per sweep,
                # as the reference appends every cut trace and flushes the
                # head block once: a segment per trace costs milliseconds
                # each, which no real trace rate survives
                table = cut_table(cut)
                if table is not None:
                    head.append_table(table)
            finally:
                # a failed write loses what it lost under the lock; it
                # must not leave the traces published for ever
                self.live.forget(cut)
                spent = cut.spent_chunks()
                with self.lock, tracing.span("instance.cut_locked"):
                    self.cutting = None
                    self.live.drop_chunks(spent)
            return len(cut)

    def head_bytes(self) -> int:
        if self.head is None:
            return 0
        return sum(os.path.getsize(os.path.join(self.head.dir, s))
                   for s in self.head.segments())

    def cut_block_if_ready(self, immediate: bool = False) -> WALBlock | None:
        """Seal the head block when over age/size (`CutBlockIfReady`);
        returns the sealed WAL block to enqueue for completion. Waits for
        a sweep in flight: its segment belongs in the block it seals."""
        with self.sweep_lock, self.lock:
            if self.head is None:
                return None
            age = self.now() - self.head_created
            if not (immediate
                    or age >= self.cfg.max_block_duration_s
                    or self.head_bytes() >= self.cfg.max_block_bytes):
                return None
            sealed = self.head
            self.head = None
            if not sealed.segments():
                sealed.clear()
                return None
            self.completing.append(sealed)
            return sealed

    def complete_block(self, wal_block: WALBlock) -> BlockMeta:
        """WAL → columnar complete block on local disk (`CompleteBlock`
        `instance.go:316`): read back every trace, dedupe/sort, write the
        same block format the storage engine serves."""
        traces = wal_block.complete()
        meta = write_block(
            self.local_backend, self.tenant,
            [(tid, sort_spans(combine_spans(spans))) for tid, spans in traces],
            block_id=wal_block.block_id,
            dedicated_columns=self.cfg.dedicated_columns,
            row_group_rows=self.cfg.row_group_rows,
            replication_factor=self.cfg.replication_factor)
        with self.lock:
            self.complete[meta.block_id] = LocalBlockEntry(
                meta, BackendBlock(self.local_backend, meta))
            if wal_block in self.completing:
                self.completing.remove(wal_block)
        wal_block.clear()
        return meta

    def mark_flushed(self, block_id: str) -> None:
        with self.lock:
            e = self.complete.get(block_id)
            if e:
                e.flushed_ts = self.now()

    def delete_old_flushed(self, after_s: float) -> list[str]:
        """Drop local complete blocks flushed more than after_s ago
        (complete_block_timeout semantics)."""
        out = []
        with self.lock:
            for bid in list(self.complete):
                e = self.complete[bid]
                if e.flushed_ts and self.now() - e.flushed_ts >= after_s:
                    del self.complete[bid]
                    out.append(bid)
        for bid in out:
            try:
                self.local_backend.delete("", _kp(bid, self.tenant), recursive=True)
            except Exception:
                pass
        return out

    # -- replay ------------------------------------------------------------

    def replay(self) -> tuple[int, int]:
        """Restart recovery: re-adopt WAL blocks and local complete blocks
        (`instance.go:601` + `ingester.go:159`). Returns (wal, complete)."""
        n_wal = 0
        for wb in rescan_blocks(self.wal_dir):
            if wb.tenant != self.tenant:
                continue
            with self.lock:
                if wb.block_id in {b.block_id for b in self.completing}:
                    continue
                self.completing.append(wb)
            n_wal += 1
        n_complete = 0
        blocks_root = os.path.join(self.local_dir, self.tenant)
        if os.path.isdir(blocks_root):
            for bid in os.listdir(blocks_root):
                try:
                    meta = read_block_meta(self.local_backend, bid, self.tenant)
                except Exception:
                    continue
                with self.lock:
                    self.complete[bid] = LocalBlockEntry(
                        meta, BackendBlock(self.local_backend, meta))
                n_complete += 1
        return n_wal, n_complete

    # -- read path ---------------------------------------------------------

    def find_trace_by_id(self, trace_id: bytes) -> list[dict] | None:
        """Combine across live + cutting + head + completing + complete
        blocks (the recent-data side of `Querier.FindTraceByID`). All of
        them are snapshot under ONE hold of `lock` and read after it, so
        a trace a sweep is writing is found in `cutting`, in the new
        segment, or (between the write's end and the sweep's second hold)
        in both: `combine_spans` keeps one span a span id."""
        parts: list[list[dict]] = []
        with self.lock:
            live = self.live.view(trace_id)
            cutting = self.cutting
            heads = self._wal_blocks()
            complete = list(self.complete.values())
        for held in (live, cutting):
            spans = held.spans_of(trace_id) if held is not None else None
            if spans:
                parts.append(spans)
        for wb in heads:
            spans = wb.find_trace_by_id(trace_id)
            if spans:
                parts.append(spans)
        for e in complete:
            spans = e.block.find_trace_by_id(trace_id)
            if spans:
                parts.append(spans)
        if not parts:
            return None
        return sort_spans(combine_spans(*parts))

    def all_recent_traces(self) -> list[tuple[bytes, list[dict]]]:
        """Snapshot of live + cutting + WAL data as (trace_id, spans)
        groups, for vectorized search over an in-memory ColumnView."""
        with self.lock:
            live = self.live.view()
            cutting = self.cutting
            heads = self._wal_blocks()
        # columns turn into dicts here, outside the lock
        by_id: dict[bytes, list[dict]] = dict(live.groups())
        for tid, spans in cutting.groups() if cutting is not None else ():
            by_id.setdefault(tid, []).extend(spans)
        for wb in heads:
            for s in wb.iter_spans():
                by_id.setdefault(s["trace_id"], []).append(s)
        return [(tid, sort_spans(combine_spans(spans)))
                for tid, spans in by_id.items()]

    def _wal_blocks(self) -> list[WALBlock]:
        """The head block and those awaiting completion (under `lock`)."""
        return ([self.head] if self.head else []) + list(self.completing)

    def complete_blocks(self) -> list[BackendBlock]:
        with self.lock:
            return [e.block for e in self.complete.values()]


def _kp(block_id: str, tenant: str):
    from tempo_tpu.backend.raw import block_keypath
    return block_keypath(block_id, tenant)
