"""Live-trace accumulation with size/count limits and idle cutting.

Analog of `pkg/livetraces/livetraces.go:23-120` (used by the ingester
instance, generator localblocks, and blockbuilder): spans group per trace id
in memory; traces are "cut" (emitted for WAL append) once idle longer than
`idle_s`, older than `max_age_s`, or immediately on demand. Per-trace byte
and global count limits guard memory, mirroring the push error reasons of
`modules/ingester/instance.go:199-228` (`PushErrorReason`).

A staged push enters the store as ONE chunk (`block.live_columns.
ColumnChunk`: the push's rows grouped by trace, each trace's slot). A
trace held by chunks alone has a SLOT: its bytes, first and last append,
state and first and last chunk are arrays indexed by slot, and
`TraceIndex` maps its exact id to it, so a staged push and a cut are array
operations and never a Python step a trace. A trace that a dict route
(Jaeger, Zipkin, gRPC, replay, blockbuilder, the tests) pushed to is a
`LiveTrace` of segments in a dict by id, as it always was, and a push to
it touches no array; spans of a staged push for such a trace join it as a
column segment. Slot numbers count the traces of both kinds in first-seen
order: a LiveTrace keeps its number, and only a column trace's number
indexes the arrays.
"""

from __future__ import annotations

import bisect
import dataclasses
import hashlib
import time
from operator import attrgetter
from typing import Callable, Iterable, Iterator

import numpy as np

from tempo_tpu.native import TraceIndex
from tempo_tpu.obs.jaxruntime import RUNTIME

ERR_LIVE_TRACES_EXCEEDED = "live_traces_exceeded"
ERR_TRACE_TOO_LARGE = "trace_too_large"

LIVE_SPANS = RUNTIME.counter(
    "tempo_ingester_live_spans_total",
    "Spans appended to live traces (the ingester's and the local-blocks "
    "processor's stores), by the form the store keeps them in: columns = "
    "a row slice of a staged push's columns; dicts = span dicts",
    labels=("form",))
CUT_SPANS = RUNTIME.counter(
    "tempo_ingester_cut_spans_total",
    "Spans a cut put into a WAL segment (the ingester's and the "
    "local-blocks processor's), by the route that built them: columns = "
    "taken from the chunks of staged pushes; dicts = through span dicts",
    labels=("route",))
CHUNK_SPANS = RUNTIME.counter(
    "tempo_ingester_chunk_spans_total",
    "Spans a live store (the ingester's and the local-blocks processor's) "
    "took in as one chunk of a staged push, by where the chunk's trace "
    "grouping came from: staged = the staging's native pass, shared by "
    "every store and the distributor; own = the store grouped the rows "
    "itself",
    labels=("grouping",))

# a slot's state
DEAD, COLUMNS = 0, 1
_LENGTH = [bytes((n,)) for n in range(17)]


def key_of(trace_id: bytes) -> bytes:
    """The 17-byte index key of an exact trace id: the id zero-padded to
    16 bytes, then its length; an id over 16 bytes (no staged push has
    one) by its 16-byte digest, length byte 255."""
    if len(trace_id) <= 16:
        return bytes(trace_id).ljust(16, b"\0") + _LENGTH[len(trace_id)]
    return hashlib.blake2b(trace_id, digest_size=16).digest() + b"\xff"


def trace_key(trace_id: bytes) -> np.ndarray:
    """`key_of` as a [1, 17] uint8 row."""
    return np.frombuffer(key_of(trace_id), np.uint8).reshape(1, 17)


def key_trace_id(key: np.ndarray) -> bytes:
    """The exact id of a key (of a staged push's trace)."""
    return key[:key[16]].tobytes()


@dataclasses.dataclass
class LiveTrace:
    """A trace a dict route pushed to. `segments` in arrival order: a
    list of span dicts (consecutive dict pushes share one), or a column
    slice of a staged push, an object with `to_span_dicts()`
    (`block.live_columns.ColumnSegment`). `slot` is its number in the
    store's first-seen order."""
    trace_id: bytes
    segments: list = dataclasses.field(default_factory=list)
    slot: int = -1
    bytes: int = 0
    first_append: float = 0.0
    last_append: float = 0.0

    @property
    def spans(self) -> list[dict]:
        """Every span as a dict; column segments convert on demand."""
        return segment_spans(self.segments)

    def snapshot(self) -> list:
        """The segments as they stand (take it under the store's lock:
        a later dict push extends the last list), for `segment_spans`
        once the lock is released."""
        return [list(seg) if isinstance(seg, list) else seg
                for seg in self.segments]


def segment_spans(segments: Iterable) -> list[dict]:
    """A live trace's segments as span dicts, in arrival order."""
    out: list[dict] = []
    for seg in segments:
        out.extend(seg if isinstance(seg, list) else seg.to_span_dicts())
    return out


class TraceSet:
    """Traces taken out of a store by a cut, or read from it, at one
    moment, and read after its lock is released. The traces held by
    chunks alone are `pos` (slot positions from `base`, ascending: first
    seen first) with their `keys`, their rows in `chunks` (arrival
    order); the others are `dicts`, LiveTraces in slot order. Nothing in
    it changes after it is made: a cut trace's id that is pushed again
    starts a new trace."""

    def __init__(self, chunks: list, base: int, pos: np.ndarray,
                 key_rows: np.ndarray, dicts: list[LiveTrace],
                 state: np.ndarray | None = None,
                 gone: np.ndarray | None = None) -> None:
        self.chunks = chunks
        self.base = base
        self.pos = pos
        self.dicts = dicts
        # the store's key array as it was: a push never writes a position
        # a set holds, so the keys are gathered after the lock
        self._key_rows = key_rows
        self._state = state      # the store's slot states after a cut
        self._gone = gone        # the positions a cut took
        self._keys = None
        self._rows = None

    @property
    def keys(self) -> np.ndarray:
        """[len(pos), 17] index keys of the column traces."""
        if self._keys is None:
            self._keys = _gather_keys(self._key_rows, self.pos)
        return self._keys

    def gone(self) -> tuple[np.ndarray, np.ndarray] | None:
        """(keys, slots) of every trace a cut took, for the index."""
        if self._gone is None:
            return None
        return _gather_keys(self._key_rows, self._gone), self._gone + self.base

    def __len__(self) -> int:
        return len(self.pos) + len(self.dicts)

    def column_rows(self) -> tuple[list, np.ndarray]:
        """(`parts`, `trace`): `parts` is (chunk, rows of its source) for
        every chunk that holds rows of the set's column traces, arrival
        order; `trace` the index into `pos` of each of those rows, the
        parts' rows concatenated. A chunk's rows of one trace stay in push
        order."""
        if self._rows is None:
            self._rows = self._gather()
        return self._rows

    def _gather(self) -> tuple[list, np.ndarray]:
        if not len(self.pos) or not self.chunks:
            return [], np.zeros(0, np.int64)
        if len(self.pos) == 1:          # a read of one trace
            at = np.concatenate([c.row_slot for c in self.chunks]) - self.base
            trace = np.where(at == self.pos[0], 0, -1)
        else:
            where = np.full(int(self.pos[-1]) + 1, -1, np.int64)
            where[self.pos] = np.arange(len(self.pos))
            at = _positions(self.chunks, self.base, len(where))
            trace = np.full(len(at), -1, np.int64)
            inside = at >= 0
            trace[inside] = where[at[inside]]
        hit = np.flatnonzero(trace >= 0)
        rows = np.concatenate([c.rows for c in self.chunks])[hit]
        ends = np.searchsorted(hit, np.cumsum(
            [len(c.rows) for c in self.chunks])).tolist()
        parts, lo = [], 0
        for c, hi in zip(self.chunks, ends):
            if hi > lo:
                parts.append((c, rows[lo:hi]))
            lo = hi
        return parts, trace[hit]

    def spent_chunks(self) -> list:
        """The chunks of a cut that hold no live column trace after it
        (for `LiveTraceStore.drop_chunks`). A slot never comes back to life,
        so a chunk spent here stays spent."""
        if self._state is None or not self.chunks:
            return []
        at = _positions(self.chunks, self.base, len(self._state))
        alive = np.zeros(len(at), bool)
        inside = at >= 0
        alive[inside] = self._state[at[inside]] == COLUMNS
        starts = np.cumsum([0] + [len(c.rows) for c in self.chunks[:-1]])
        kept = np.logical_or.reduceat(alive, starts)
        return [c for c, k in zip(self.chunks, kept.tolist()) if not k]

    def __iter__(self) -> Iterator[LiveTrace]:
        """Every trace as a LiveTrace, slot order; a column trace gets a
        column segment a chunk that holds it (the read path's form)."""
        segs: list[list] = [[] for _ in range(len(self.pos))]
        parts, trace = self.column_rows()
        at = 0
        for c, rows in parts:
            t = trace[at:at + len(rows)]
            at += len(rows)
            o = np.argsort(t, kind="stable")
            ts, rows = t[o], rows[o]
            cuts = np.flatnonzero(np.diff(ts)) + 1
            for i, r in zip(ts[np.r_[0, cuts]].tolist(),
                            np.split(rows, cuts)):
                segs[i].append(c.segment(r))
        cols = [LiveTrace(key_trace_id(k), s, self.base + p)
                for k, s, p in zip(self.keys, segs, self.pos.tolist())]
        return iter(sorted(cols + self.dicts, key=attrgetter("slot")))

    def groups(self) -> list[tuple[bytes, list[dict]]]:
        """(trace id, its spans as dicts in arrival order), slot order."""
        return [(lt.trace_id, lt.spans) for lt in self]

    def spans_of(self, trace_id: bytes) -> list[dict]:
        """The spans the set holds under `trace_id`, arrival order."""
        out: list[dict] = []
        for lt in self.dicts:
            if lt.trace_id == trace_id:
                out.extend(lt.spans)
        hit = np.flatnonzero((self.keys == trace_key(trace_id)).all(axis=1))
        if len(hit):
            parts, trace = self.column_rows()
            at = 0
            for c, rows in parts:
                mine = rows[trace[at:at + len(rows)] == hit[0]]
                at += len(rows)
                if len(mine):
                    out.extend(c.segment(mine).to_span_dicts())
        return out


def _gather_keys(key_rows: np.ndarray, pos: np.ndarray) -> np.ndarray:
    """Rows `pos` of a [n, 17] key array (through a 17-byte void view: a
    row gather of uint8 is twice as slow)."""
    return key_rows.view("V17").ravel()[pos].view(np.uint8).reshape(-1, 17)


def _positions(chunks: list, base: int, n: int) -> np.ndarray:
    """Every chunk row's slot position from `base` (chunks concatenated),
    -1 where it lies outside [0, n)."""
    at = np.concatenate([c.row_slot for c in chunks]) - base
    at[(at < 0) | (at >= n)] = -1
    return at


_EMPTY = np.zeros(0, np.int64)
_MIN_SLOTS = 1024


class LiveTraceStore:
    def __init__(self, max_live_traces: int = 0, max_trace_bytes: int = 0,
                 now: Callable[[], float] = time.time):
        self.max_live_traces = max_live_traces  # 0 = unlimited
        self.max_trace_bytes = max_trace_bytes
        self.now = now
        self.total_bytes = 0
        self.pushes_rejected: dict[str, int] = {}
        self.index = TraceIndex()
        self.chunks: list = []                  # arrival order
        self.dict_traces: dict[bytes, LiveTrace] = {}   # by `key_of`
        self._seq = 0         # the next chunk's arrival number
        self._base = 0        # the slot at position 0 of the arrays
        self._top = 0         # the next new slot
        self._live = 0        # column traces
        self._key = np.zeros((0, 17), np.uint8)
        self._state = np.zeros(0, np.int8)
        self._bytes = np.zeros(0, np.int64)
        self._first = np.zeros(0, np.float64)
        self._last = np.zeros(0, np.float64)
        self._c0 = np.zeros(0, np.int64)      # its first chunk's number
        self._c1 = np.zeros(0, np.int64)      # its last chunk's number

    def __len__(self) -> int:
        return self._live + len(self.dict_traces)

    # -- slots --------------------------------------------------------------

    def _n(self) -> int:
        """The positions the arrays hold (LiveTraces' numbers past the last
        column trace have none)."""
        return min(self._top - self._base, len(self._state))

    def _find(self, keys: np.ndarray) -> np.ndarray:
        """The live column slot of each key, -1 where it has none (the
        index may still name a slot a cut took, until `forget`)."""
        slot = self.index.lookup(keys)
        at = slot - self._base
        ok = at >= 0
        ok[ok] = self._state[at[ok]] != DEAD
        return np.where(ok, slot, -1)

    def _new_slots(self, keys: np.ndarray, seq: int,
                   now: float) -> np.ndarray:
        k = len(keys)
        n = self._top - self._base
        if n + k > len(self._state):
            # drop the dead positions in front, then make room for twice
            # what is left (slots keep their numbers, positions move).
            # New arrays, never a move in place: a TraceSet reads its keys
            # from the array it was made with, after the lock
            live = np.flatnonzero(self._state[:n])
            lo = int(live[0]) if len(live) else n
            cap = max(2 * (n - lo + k), _MIN_SLOTS)
            held = max(min(n, len(self._state)) - lo, 0)
            for name in ("_key", "_state", "_bytes", "_first", "_last",
                         "_c0", "_c1"):
                old = getattr(self, name)
                new = np.zeros((cap,) + old.shape[1:], old.dtype)
                new[:held] = old[lo:lo + held]
                setattr(self, name, new)
            self._base += lo
            n -= lo
        new = slice(n, n + k)
        self._key[new] = keys
        self._state[new] = COLUMNS
        self._first[new] = now
        self._last[new] = now
        self._c0[new] = seq
        self._c1[new] = seq
        slots = np.arange(self._top, self._top + k, dtype=np.int64)
        self._top += k
        self._live += k
        self.index.upsert(keys, slots)
        return slots

    def _slot_of(self, key: bytes) -> int:
        """The live column slot of one `key_of` key, or -1."""
        if not self._live:
            return -1
        return int(self._find(np.frombuffer(key, np.uint8).reshape(1, 17))[0])

    def _chunks_of(self, slot: int) -> list:
        """The chunks that may hold rows of a live column slot: those from
        its first chunk to its last."""
        at = slot - self._base
        seq = attrgetter("seq")
        lo = bisect.bisect_left(self.chunks, int(self._c0[at]), key=seq)
        hi = bisect.bisect_right(self.chunks, int(self._c1[at]), key=seq)
        return self.chunks[lo:hi]

    def _to_dicts(self, trace_id: bytes, key: bytes, slot: int) -> LiveTrace:
        """Move a live column trace to the dict side, its rows as column
        segments (a dict route pushes to it)."""
        at = slot - self._base
        lt = LiveTrace(trace_id, [], slot, int(self._bytes[at]),
                       float(self._first[at]), float(self._last[at]))
        for c in self._chunks_of(slot):
            rows = c.rows[c.row_slot == slot]
            if len(rows):
                lt.segments.append(c.segment(rows))
        self._state[at] = DEAD
        self._live -= 1
        self.index.discard(np.frombuffer(key, np.uint8).reshape(1, 17),
                           np.array([slot], np.int64))
        self.dict_traces[key] = lt
        return lt

    def _refused(self, reason: str, n: int) -> None:
        if n:
            self.pushes_rejected[reason] = (
                self.pushes_rejected.get(reason, 0) + n)

    def bytes_of(self, trace_id: bytes) -> int:
        """The approximate bytes a live trace holds (0: not live)."""
        key = key_of(trace_id)
        lt = self.dict_traces.get(key)
        if lt is not None:
            return lt.bytes
        slot = self._slot_of(key)
        return int(self._bytes[slot - self._base]) if slot >= 0 else 0

    # -- pushes -------------------------------------------------------------

    def push(self, trace_id: bytes, spans: Iterable[dict],
             size_bytes: int | None = None) -> str | None:
        """Append spans to a live trace. Returns an error reason or None.
        A dict get a trace; the arrays only where the id is a live column
        trace."""
        spans = list(spans)
        sz = size_bytes if size_bytes is not None else _approx_size(spans)
        key = key_of(trace_id)
        lt = self.dict_traces.get(key)
        slot = -1 if lt is not None else self._slot_of(key)
        have = (lt.bytes if lt is not None else
                int(self._bytes[slot - self._base]) if slot >= 0 else 0)
        # both limit checks run before any store mutation
        if self.max_trace_bytes and have + sz > self.max_trace_bytes:
            self._refused(ERR_TRACE_TOO_LARGE, 1)
            return ERR_TRACE_TOO_LARGE
        now = self.now()
        if lt is None:
            if slot >= 0:
                lt = self._to_dicts(trace_id, key, slot)
            elif self.max_live_traces and len(self) >= self.max_live_traces:
                self._refused(ERR_LIVE_TRACES_EXCEEDED, 1)
                return ERR_LIVE_TRACES_EXCEEDED
            else:
                lt = self.dict_traces[key] = LiveTrace(
                    trace_id, slot=self._top, first_append=now)
                self._top += 1
        lt.bytes += sz
        lt.last_append = now
        self.total_bytes += sz
        if lt.segments and isinstance(lt.segments[-1], list):
            lt.segments[-1].extend(spans)
        else:
            lt.segments.append(spans)
        LIVE_SPANS.inc(len(spans), ("dicts",))
        return None

    def push_chunk(self, chunk) -> dict[int, str]:
        """Take a staged push as ONE chunk (`block.live_columns.
        ColumnChunk`) under the limits `push` enforces, trace by trace in
        first-seen order, with one clock read. Returns {trace index in the
        chunk: reason} for the traces refused; their rows leave the chunk.
        The caller counts the spans into `LIVE_SPANS`."""
        now = self.now()
        seq = chunk.seq = self._seq
        self._seq += 1
        sizes = chunk.sizes
        n = len(sizes)
        have = np.zeros(n, np.int64)
        # a trace a dict route holds takes its rows as a segment
        joins = np.zeros(n, bool)
        if self.dict_traces:
            kb = chunk.keys.view("V17").ravel().tolist()
            joins = np.fromiter(map(self.dict_traces.__contains__, kb),
                                bool, n)
            held = [self.dict_traces[kb[i]]
                    for i in np.flatnonzero(joins).tolist()]
            have[joins] = [lt.bytes for lt in held]
        slot = self._find(chunk.keys)
        found = slot >= 0
        have[found] = self._bytes[slot[found] - self._base]
        large = np.zeros(n, bool)
        if self.max_trace_bytes:
            large = have + sizes > self.max_trace_bytes
        over = np.zeros(n, bool)
        if self.max_live_traces:
            new = ~found & ~joins & ~large
            over = new & (np.cumsum(new) > self.max_live_traces - len(self))
        ok = ~(large | over)
        old = ok & found
        at = slot[old] - self._base
        self._bytes[at] += sizes[old]
        self._last[at] = now
        self._c1[at] = seq
        if joins.any():
            bounds = np.concatenate(([0], np.cumsum(chunk.spans)))
            for i, lt in zip(np.flatnonzero(joins).tolist(), held):
                if ok[i]:
                    lt.bytes += int(sizes[i])
                    lt.last_append = now
                    lt.segments.append(chunk.segment(
                        chunk.rows[bounds[i]:bounds[i + 1]]))
        fresh = ok & ~found & ~joins
        if fresh.any():
            slot[fresh] = self._new_slots(chunk.keys[fresh], seq, now)
            self._bytes[slot[fresh] - self._base] = sizes[fresh]
        self.total_bytes += int(sizes[ok].sum())
        keep = ok & ~joins
        row_slot = np.repeat(slot, chunk.spans)
        if not keep.all():
            rows = np.repeat(keep, chunk.spans)
            chunk.rows, row_slot = chunk.rows[rows], row_slot[rows]
        chunk.row_slot = row_slot
        if len(row_slot):
            self.chunks.append(chunk)
        self._refused(ERR_TRACE_TOO_LARGE, int(large.sum()))
        self._refused(ERR_LIVE_TRACES_EXCEEDED, int(over.sum()))
        return {i: ERR_TRACE_TOO_LARGE if large[i] else ERR_LIVE_TRACES_EXCEEDED
                for i in np.flatnonzero(~ok).tolist()}

    # -- cuts and reads -----------------------------------------------------

    def cut(self, idle_s: float = 0.0, max_age_s: float = 0.0,
            immediate: bool = False) -> TraceSet:
        """Remove and return traces idle > idle_s or older than max_age_s
        (`CutCompleteTraces` `instance.go:237`); immediate cuts everything.
        One array comparison picks the column traces; the dict routes'
        traces are walked as they always were. Once the cut is written,
        `forget` it and `drop_chunks` what it spent."""
        now = self.now()
        n = self._n()
        state = self._state[:n]
        take = state == COLUMNS
        if not immediate:
            due = np.zeros(n, bool)
            if idle_s:
                due |= now - self._last[:n] >= idle_s
            if max_age_s:
                due |= now - self._first[:n] >= max_age_s
            take &= due
        pos = np.flatnonzero(take)
        state[pos] = DEAD
        self._live -= len(pos)
        self.total_bytes -= int(self._bytes[pos].sum())
        dicts = []
        for key, lt in list(self.dict_traces.items()):
            if (immediate
                    or (idle_s and now - lt.last_append >= idle_s)
                    or (max_age_s and now - lt.first_append >= max_age_s)):
                dicts.append(self.dict_traces.pop(key))
                self.total_bytes -= lt.bytes
        dicts.sort(key=attrgetter("slot"))
        return TraceSet(list(self.chunks), self._base, pos, self._key,
                        dicts, state=state.copy(), gone=pos)

    def forget(self, cut: TraceSet) -> None:
        """Take the ids of a cut's traces out of the index. Needs no lock
        (`TraceIndex.discard` keeps an id a push has given a new slot
        since); until then `_find` reads their slots as dead."""
        gone = cut.gone()
        if gone is not None:
            self.index.discard(*gone)

    def drop_chunks(self, spent: list) -> None:
        """Let go of the chunks a cut spent (`TraceSet.spent_chunks`),
        under the lock pushes take."""
        if spent:
            gone = {id(c) for c in spent}
            self.chunks = [c for c in self.chunks if id(c) not in gone]

    def view(self, trace_id: bytes | None = None) -> TraceSet:
        """What the store holds (of `trace_id` alone, if given: one dict
        get, or one lookup and the chunks from its first to its last), to
        read after the lock is released."""
        if trace_id is None:
            cols = np.flatnonzero(self._state[:self._n()] == COLUMNS)
            dicts = sorted(self.dict_traces.values(), key=attrgetter("slot"))
            chunks = list(self.chunks)
        else:
            key = key_of(trace_id)
            lt = self.dict_traces.get(key)
            dicts = [lt] if lt is not None else []
            slot = -1 if lt is not None else self._slot_of(key)
            cols = (np.array([slot - self._base]) if slot >= 0 else _EMPTY)
            chunks = self._chunks_of(slot) if slot >= 0 else []
        dicts = [LiveTrace(lt.trace_id, lt.snapshot(), lt.slot)
                 for lt in dicts]
        return TraceSet(chunks, self._base, cols, self._key, dicts)


def _approx_size(spans: list[dict]) -> int:
    # cheap stand-in for proto size: span count * nominal span bytes + attrs
    return sum(200 + 32 * (len(s.get("attrs") or {}) + len(s.get("res_attrs") or {}))
               for s in spans)
