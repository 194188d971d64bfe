"""Live traces as the chunks of the pushed batches, and the WAL segment
cut from those columns.

A staged push reaches the live store as columns (`SpanBatch`, with the
staging's exact id lengths and its lazy events/links pass where the push
came through `StagedIngest`). The store keeps them as they are: the push
enters it as ONE `ColumnChunk`, its rows grouped by exact trace id, each
trace's slot beside them (`utils.livetraces.LiveTraceStore.push_chunk`).
Every other route (Jaeger, Zipkin, gRPC, replay, the tests) hands the
store span dicts, kept per trace as segments, where a staged push's rows
for the same trace join as a `ColumnSegment`. Both kinds meet in
`cut_table`, which builds the one arrow table a sweep appends to the WAL:
the reference ingester likewise keeps a live trace's pushed bytes
undecoded until the cut (`modules/ingester/instance.go` `PushBytes`).

`cut_table` equals, column for column and row for row, what
`schema.traces_to_table(spans_by_trace(sort_spans(combine_spans(...))))`
builds from the dicts the same pushes would have made
(`tests/test_live_columns.py` holds it to that).
"""

from __future__ import annotations

from typing import Any, Sequence

import numpy as np
import pyarrow as pa

from tempo_tpu import native
from tempo_tpu.block import schema as bs
from tempo_tpu.model.combine import combine_spans, sort_spans
from tempo_tpu.model.interner import INVALID_ID
from tempo_tpu.model.span_batch import (
    ATTR_BOOL, ATTR_DOUBLE, ATTR_INT, ATTR_STRING, SpanBatch)
from tempo_tpu.utils.livetraces import (
    CUT_SPANS, TraceSet, key_of, key_trace_id, segment_spans)


class ColumnSource:
    """One staged push as the live store keeps it, shared by its chunk and
    by the column segments cut from it. `staged` (a `StagedIngest`)
    carries what the SpanBatch pads away: id byte lengths, events and
    links."""

    __slots__ = ("batch", "staged")

    def __init__(self, batch: SpanBatch, staged=None) -> None:
        self.batch = batch
        self.staged = staged

    def span_dicts(self, rows: np.ndarray) -> list[dict]:
        """The span dicts the dict route would have stored for `rows`."""
        if self.staged is not None:
            return self.staged.view().to_span_dicts(rows)
        return self.batch.to_span_dicts(rows)

    def span_id_lens(self, rows: np.ndarray):
        """(span, parent) id byte lengths of `rows`; a bare SpanBatch has
        only the padded width."""
        if self.staged is None:
            return np.full(len(rows), 8, np.int32), np.full(len(rows), 8,
                                                            np.int32)
        recs = self.staged.spans
        return (np.minimum(recs["sid_len"][rows], 8),
                np.minimum(recs["pid_len"][rows], 8))

    def chunk(self, rows: "np.ndarray | None") -> "ColumnChunk | None":
        """`rows` (None: every row of the batch) grouped by exact trace id,
        first-seen order kept, as one chunk (None: no row). A trace's
        approximate bytes are `livetraces._approx_size` of the same spans
        as dicts, 200 + 32 x attrs a span, counted off the attr-key
        columns in one pass (a key a span repeats counts each time; a dict
        keeps it once).

        Every row of a batch that carries the staging's trace order
        (`SpanBatch.trace_order`) takes that order, where this source keys
        rows as it does: with the staging's id lengths, or where every id
        is 16 bytes long (a bare batch keys every row at 16). Else few
        numpy calls on purpose: under four request threads every call over
        500 elements hands the interpreter over, and the wait to get it
        back, not the arithmetic, is what a push pays."""
        sb = self.batch
        pick = slice(0, sb.n) if rows is None else rows
        n = sb.n if rows is None else len(rows)
        if not n:
            return None
        got = sb.trace_order
        if rows is None and got is not None and (
                self.staged is not None or got.same_length):
            return ColumnChunk(self, got.order, got.keys, got.trace_spans,
                               got.trace_sizes, "staged")
        # the exact id is (padded bytes, length): one 17-byte key a row
        keys = np.empty((n, 17), np.uint8)
        keys[:, :16] = sb.trace_id[pick]
        keys[:, 16] = (16 if self.staged is None else
                       np.minimum(self.staged.spans["tid_len"][pick], 16))
        first, inverse = native.group_keys(keys)     # first-seen order
        order = np.argsort(inverse, kind="stable")
        spans = np.bincount(inverse, minlength=len(first))
        attrs = np.bincount(inverse, weights=(np.concatenate(
            (sb.span_attr_key[pick], sb.res_attr_key[pick]), axis=1)
            != INVALID_ID).sum(axis=1))
        return ColumnChunk(self, order if rows is None else rows[order],
                           keys[first], spans,
                           200 * spans + 32 * attrs.astype(np.int64), "own")


class ColumnChunk:
    """One staged push in a live store: `rows` of `source` grouped by
    trace (a trace's rows contiguous, in push order; traces in first-seen
    order), each trace's index `keys` ([17] uint8), span count `spans` and
    approximate `sizes`; `grouping` says who grouped them (`staged`: the
    staging's native pass, `own`: `ColumnSource.chunk`). The store sets
    `row_slot` (each row's trace slot; the rows of refused traces leave)
    and `seq` (its arrival number, which orders a trace's chunks)."""

    __slots__ = ("source", "rows", "keys", "spans", "sizes", "grouping",
                 "row_slot", "seq")

    def __init__(self, source: ColumnSource, rows: np.ndarray,
                 keys: np.ndarray, spans: np.ndarray,
                 sizes: np.ndarray, grouping: str) -> None:
        self.source = source
        self.rows = rows
        self.keys = keys
        self.spans = spans
        self.sizes = sizes
        self.grouping = grouping
        self.row_slot = None
        self.seq = -1

    def segment(self, rows: np.ndarray) -> "ColumnSegment":
        return ColumnSegment(self.source, rows)


class ColumnSegment:
    """Rows of one staged push that belong to one trace: how a trace that
    also holds span dicts keeps them, and how a read sees a chunk."""

    __slots__ = ("source", "rows")

    def __init__(self, source: ColumnSource, rows: np.ndarray) -> None:
        self.source = source
        self.rows = rows

    def __len__(self) -> int:
        return len(self.rows)

    def to_span_dicts(self) -> list[dict]:
        return self.source.span_dicts(self.rows)


# ---------------------------------------------------------------------------
# the cut: live traces -> one arrow table
# ---------------------------------------------------------------------------

def cut_table(cut: TraceSet, dedicated: Sequence[Any] = ()) -> pa.Table | None:
    """The WAL segment of one sweep: every cut live trace, deduplicated
    by span id (first wins), spans ordered by start time, traces by id.
    None when the cut holds no span."""
    cols, flat, n_dicts = _split_routes(cut)
    groups = bs.spans_by_trace(flat)
    tables = []
    if cols is not None:
        parts, trank, arrival, tkeys = cols
        tables.append(_column_traces_table(parts, trank, arrival, dedicated))
        CUT_SPANS.inc(len(trank), ("columns",))
    CUT_SPANS.inc(n_dicts, ("dicts",))
    if groups:
        tables.append(bs.traces_to_table(groups, dedicated))
    if len(tables) < 2:
        return tables[0] if tables else None
    # both kinds in one sweep: whole traces interleave by trace id, and
    # only `trace_idx` knows about the other table
    keys = [key_trace_id(k) for k in tkeys]
    dkeys = [k for k, _ in groups]
    rank = {k: i for i, k in enumerate(sorted(keys + dkeys))}
    merged = pa.concat_tables([
        _with_trace_idx(tables[0], [rank[k] for k in keys]),
        _with_trace_idx(tables[1], [rank[k] for k in dkeys])])
    order = np.argsort(merged.column("trace_idx").to_numpy(), kind="stable")
    return merged.take(pa.array(order)).combine_chunks()


def _split_routes(cut: TraceSet):
    """(the column route, the flat span dicts of the rest in slot order,
    the number of spans the rest held before `combine_spans`). The column
    route is None, or (parts, trank, arrival, keys): `parts`
    [(source, rows)], each row's trace rank by trace id and arrival (its
    chunk's number), the traces' keys in rank order. A trace goes the dict
    route when any of its segments is dicts (a LiveTrace), when a dict
    span in the sweep claims its trace id (`spans_by_trace` would merge
    the two), or when a chunk of it was staged against another interner
    than the sweep's first chunk."""
    parts, trace = cut.column_rows()
    by_slot = []        # (slot, spans, spans before combine) of the dicts
    for lt in cut.dicts:
        spans = segment_spans(lt.segments)
        by_slot.append((lt.slot, sort_spans(combine_spans(spans)), len(spans)))
    demote = np.zeros(len(cut.pos), bool)
    if parts:
        interner = parts[0][0].source.batch.interner
        at = 0
        for c, rows in parts:
            if c.source.batch.interner is not interner:
                demote[trace[at:at + len(rows)]] = True
            at += len(rows)
    if by_slot and len(cut.pos):
        claimed = {key_of(bytes(s.get("trace_id", b"")))
                   for _, spans, _ in by_slot for s in spans}
        demote |= np.fromiter(
            (k.tobytes() in claimed for k in cut.keys), bool, len(cut.pos))
    if demote.any():
        # such traces are few: each takes its rows as dicts, arrival order
        mine: dict[int, list[dict]] = {}
        at = 0
        for c, rows in parts:
            t = trace[at:at + len(rows)]
            at += len(rows)
            hit = demote[t]
            for i, s in zip(t[hit].tolist(),
                            c.source.span_dicts(rows[hit])):
                mine.setdefault(i, []).append(s)
        for i, spans in mine.items():
            by_slot.append((cut.base + int(cut.pos[i]),
                            sort_spans(combine_spans(spans)), len(spans)))
        kept, at = [], 0
        for c, rows in parts:
            t = trace[at:at + len(rows)]
            at += len(rows)
            if (~demote[t]).any():
                kept.append((c, rows[~demote[t]]))
        parts, trace = kept, trace[~demote[trace]]
    by_slot.sort(key=lambda x: x[0])
    flat = [s for _, spans, _ in by_slot for s in spans]
    n_dicts = sum(n for _, _, n in by_slot)
    if not parts:
        return None, flat, n_dicts
    # trace ranks by exact id: padded bytes, then length, orders as the
    # ids do (a shorter id that is a prefix of a longer one comes first)
    keys = cut.keys
    live = np.flatnonzero(~demote)
    k = keys[live]
    order = live[np.lexsort((k[:, 16], _u64(k[:, 8:16]), _u64(k[:, :8])))]
    rank = np.full(len(keys), -1, np.int64)
    rank[order] = np.arange(len(order))
    arrival = np.concatenate([np.full(len(rows), c.seq, np.int64)
                              for c, rows in parts])
    return ([(c.source, rows) for c, rows in parts], rank[trace], arrival,
            keys[order]), flat, n_dicts


def _u64(cols: np.ndarray) -> np.ndarray:
    """[n, 8] uint8 as big-endian uint64: byte order is number order."""
    return np.ascontiguousarray(cols).view(">u8").ravel()


def _with_trace_idx(table: pa.Table, ranks: list[int]) -> pa.Table:
    idx = np.asarray(ranks, np.int32)[table.column("trace_idx").to_numpy()]
    return table.set_column(table.schema.get_field_index("trace_idx"),
                            table.schema.field("trace_idx"), pa.array(idx))


def _column_traces_table(parts: Sequence, trank: np.ndarray,
                         arrival: np.ndarray, dedicated: Sequence[Any]
                         ) -> pa.Table:
    """Arrow table of column traces over one interner: `parts` [(source,
    rows)], and for their rows concatenated the trace's rank by id and the
    arrival (its push's number; rows of one trace in one push are in push
    order)."""

    def col(name: str) -> np.ndarray:
        return np.concatenate([getattr(src.batch, name)[rows]
                               for src, rows in parts])
    sid, pid, start = (col("span_id"), col("parent_span_id"),
                       col("start_unix_nano"))
    lens = [src.span_id_lens(rows) for src, rows in parts]
    sl = np.concatenate([x[0] for x in lens])
    pl = np.concatenate([x[1] for x in lens])
    sid64 = np.ascontiguousarray(sid).view(np.uint64).ravel()

    # combine_spans: one span a (trace, span id), the first to arrive
    o = np.lexsort((arrival, sl, sid64, trank))
    dup = ((trank[o][1:] == trank[o][:-1]) & (sid64[o][1:] == sid64[o][:-1])
           & (sl[o][1:] == sl[o][:-1]))
    keep = np.ones(len(o), bool)
    keep[o[1:][dup]] = False
    # sort_spans + spans_by_trace: by trace id, then start, then arrival
    # (lexsort is stable, and rows of one segment are in arrival order)
    kept = np.flatnonzero(keep)
    perm = kept[np.lexsort((arrival[kept], start[kept], trank[kept]))]

    n = len(perm)
    trank, sid, pid, sl, pl, start = (a[perm] for a in
                                      (trank, sid, pid, sl, pl, start))
    it = parts[0][0].batch.interner
    cols: dict[str, pa.Array] = {}
    cols["trace_id"] = _fixed(col("trace_id")[perm], 16)
    cols["trace_idx"] = pa.array(trank.astype(np.int32))
    cols["span_id"] = _fixed(sid, 8)
    cols["parent_span_id"] = _fixed(pid, 8)
    parent_row, left, right = _nested_sets(trank, sid, sl, pid, pl)
    cols["parent_row"] = pa.array(parent_row)
    cols["nested_left"] = pa.array(left)
    cols["nested_right"] = pa.array(right)
    cols["is_root"] = pa.array(parent_row < 0)
    cols["kind"] = pa.array(col("kind")[perm].astype(np.int8))
    cols["status_code"] = pa.array(col("status_code")[perm].astype(np.int8))
    cols["start_unix_nano"] = pa.array(start)
    cols["duration_ns"] = pa.array(
        np.maximum(col("end_unix_nano")[perm] - start, 0))

    # string columns as interner ids, (offsets, ids) for a list column
    ids: dict[str, tuple] = {
        "name": (None, col("name_id")[perm]),
        "service": (None, col("service_id")[perm]),
        "status_message": (None, col("status_message_id")[perm])}
    for scope, pre in (("span", "sattr"), ("res", "rattr")):
        key, sval, fval, typ = _attr_matrices(parts, perm, scope)
        key = _dict_order(key, sval, fval, typ)
        for t, tag, conv in ((ATTR_STRING, "str", None),
                             (ATTR_INT, "int", np.int64),
                             (ATTR_DOUBLE, "f64", np.float64),
                             (ATTR_BOOL, "bool", np.bool_)):
            hit = (key != INVALID_ID) & (typ == t)
            offsets = np.zeros(n + 1, np.int32)
            np.cumsum(hit.sum(axis=1), out=offsets[1:])
            ids[f"{pre}_{tag}_keys"] = (offsets, key[hit])
            if conv is None:
                ids[f"{pre}_{tag}_vals"] = (offsets, sval[hit])
            else:
                cols[f"{pre}_{tag}_vals"] = pa.ListArray.from_arrays(
                    offsets, pa.array(fval[hit].astype(conv)))
        for i, dc in enumerate(dedicated):
            if (dc.scope == "span") == (scope == "span"):
                cols[bs.dedicated_field_name(dc.scope, i)] = _dedicated(
                    dc.name, key, sval, fval, typ, it)
    strings = _strings(it, [v for _, v in ids.values()])
    for (name, (offsets, _)), arr in zip(ids.items(), strings):
        cols[name] = (arr if offsets is None
                      else pa.ListArray.from_arrays(offsets, arr))
    cols.update(_events_links(parts, perm, n))
    schema = bs.block_schema(dedicated)
    return pa.Table.from_arrays([cols[f] for f in schema.names], schema=schema)


def _fixed(mat: np.ndarray, width: int) -> pa.Array:
    return pa.FixedSizeBinaryArray.from_buffers(
        pa.binary(width), len(mat),
        [None, pa.py_buffer(np.ascontiguousarray(mat).tobytes())])


def _strings(interner, id_arrays: list[np.ndarray]) -> list[pa.Array]:
    """Interner ids -> arrow strings, every array through ONE dictionary
    of the ids in use (INVALID_ID reads "")."""
    top = max((int(a.max()) for a in id_arrays if a.size), default=-1)
    used = np.zeros(top + 2, bool)        # last slot: INVALID_ID (-1)
    for a in id_arrays:
        used[a] = True
    used[-1] = True
    uniq = np.flatnonzero(used)
    uniq[-1] = INVALID_ID
    dictionary = pa.array(interner.lookup_many(uniq), pa.string())
    remap = np.cumsum(used, dtype=np.int32) - 1
    return [dictionary.take(pa.array(remap[a])) for a in id_arrays]


def _attr_matrices(parts, perm: np.ndarray, scope: str):
    """The [n, K] key / sval / fval / typ matrices of one scope over every
    source's rows (K: the widest source's), in table order."""
    names = [f"{scope}_attr_{f}" for f in ("key", "sval", "fval", "typ")]
    width = max(getattr(s.batch, names[0]).shape[1] for s, _ in parts)
    total = sum(len(r) for _, r in parts)
    out = []
    for name, fill in zip(names, (INVALID_ID, INVALID_ID, 0, 0)):
        dtype = getattr(parts[0][0].batch, name).dtype
        mat = np.full((total, width), fill, dtype)
        at = 0
        for src, rows in parts:
            m = getattr(src.batch, name)
            mat[at:at + len(rows), :m.shape[1]] = m[rows]
            at += len(rows)
        out.append(mat[perm])
    return out


def _dict_order(key, sval, fval, typ) -> np.ndarray:
    """Key ids as the decoded dict holds them: the slots `_decode_attrs`
    skips (no scalar type) blanked, and a key that repeats within a row
    kept once, where it first stood, with the value that came last (rows
    edited in place; such rows are rare)."""
    key = np.where((typ >= ATTR_STRING) & (typ <= ATTR_DOUBLE), key,
                   INVALID_ID)
    if key.shape[1] < 2:
        return key
    k = np.sort(key, axis=1)
    rep = ((k[:, 1:] == k[:, :-1]) & (k[:, 1:] != INVALID_ID)).any(axis=1)
    for r in np.flatnonzero(rep).tolist():
        last: dict[int, int] = {}
        for j, kid in enumerate(key[r].tolist()):
            if kid != INVALID_ID:
                last[kid] = j
        js = list(last.values())
        w = len(js)
        for m in (sval, fval, typ):
            m[r, :w] = m[r, js]
        key[r, :w] = list(last)
        key[r, w:] = INVALID_ID
    return key


def _dedicated(name: str, key, sval, fval, typ, interner) -> pa.Array:
    """A promoted attribute's column: `str()` of the row's value, null
    where the row has none."""
    out = np.full(len(key), None, object)
    kid = interner.get(name)
    if kid != INVALID_ID and key.shape[1]:
        hit = key == kid
        rows = np.flatnonzero(hit.any(axis=1))
        at = hit.argmax(axis=1)[rows]
        t = typ[rows, at]
        is_str = t == ATTR_STRING
        out[rows[is_str]] = np.array(
            interner.lookup_many(sval[rows[is_str], at[is_str]]), object)
        for r, j, tt in zip(rows[~is_str].tolist(), at[~is_str].tolist(),
                            t[~is_str].tolist()):
            v = fval[r, j]
            out[r] = str(bool(v) if tt == ATTR_BOOL
                         else int(v) if tt == ATTR_INT else float(v))
    return pa.array(out, pa.string())


def _events_links(parts, perm: np.ndarray, n: int) -> dict[str, pa.Array]:
    """The four event / link list columns. Only a `StagedIngest` source
    carries any, and only its rows that have some are walked."""
    ev_rows: dict[int, list] = {}
    ln_rows: dict[int, list] = {}
    at = 0
    for src, rows in parts:
        if src.staged is not None:
            ev_by, ln_by = src.staged.events_links()
            if ev_by or ln_by:
                for i, r in enumerate(rows.tolist()):
                    if r in ev_by:
                        ev_rows[at + i] = ev_by[r]
                    if r in ln_by:
                        ln_rows[at + i] = ln_by[r]
        at += len(rows)

    def lists(by_row: dict[int, list], fields) -> list[pa.Array]:
        counts = np.zeros(n + 1, np.int32)
        flat: list[list] = [[] for _ in fields]
        if by_row:
            where = np.full(at, -1, np.int64)   # -1: a dropped duplicate
            where[perm] = np.arange(n)
            for w, g in sorted((int(where[g]), g) for g in by_row
                               if where[g] >= 0):
                counts[w + 1] = len(by_row[g])
                for items, (conv, _) in zip(flat, fields):
                    items.extend(conv(e) for e in by_row[g])
        offsets = np.cumsum(counts, dtype=np.int32)
        return [pa.ListArray.from_arrays(offsets, pa.array(items, typ))
                for items, (_, typ) in zip(flat, fields)]

    ev = lists(ev_rows, (
        (lambda e: int(e.get("time_unix_nano", 0)), pa.int64()),
        (lambda e: str(e.get("name", "")), pa.string())))
    ln = lists(ln_rows, (
        (lambda l: bytes(l.get("trace_id", b"")).ljust(16, b"\0")[:16],
         pa.binary(16)),
        (lambda l: bytes(l.get("span_id", b"")).ljust(8, b"\0")[:8],
         pa.binary(8))))
    return {"event_times": ev[0], "event_names": ev[1],
            "link_trace_ids": ln[0], "link_span_ids": ln[1]}


_MAX_LEVELS = 64


def _nested_sets(trank, sid, sl, pid, pl):
    """(parent_row, nested_left, nested_right) int32 over rows ordered by
    trace: what `schema.nested_set` numbers a trace, on the exact
    (unpadded) ids. Parents are matched in one sorted join and the forest
    is numbered a LEVEL at a time; a trace with a parent cycle or deeper
    than `_MAX_LEVELS` goes through `schema.nested_set` itself."""
    n = len(trank)
    bounds = np.concatenate(([0], np.flatnonzero(np.diff(trank)) + 1, [n]))
    trace_no = np.repeat(np.arange(len(bounds) - 1), np.diff(bounds))
    first = bounds[:-1][trace_no]
    sid64 = np.ascontiguousarray(sid).view(np.uint64).ravel()
    pid64 = np.ascontiguousarray(pid).view(np.uint64).ravel()

    # parent: the row of the same trace whose span id IS the parent id
    # (same bytes, same length; an empty or all-zero parent id names none).
    # Spans and queries sort together, a span before the queries for it.
    q = np.flatnonzero((pl > 0) & ~((pl == 8) & (pid64 == 0)))
    k_trace = np.concatenate((trace_no, trace_no[q]))
    k_id = np.concatenate((sid64, pid64[q]))
    k_len = np.concatenate((sl, pl[q]))
    is_query = np.arange(n + len(q)) >= n
    o = np.lexsort((is_query, k_len, k_id, k_trace))
    at = np.arange(len(o))
    last_span = np.maximum.accumulate(np.where(is_query[o], -1, at))
    qpos = np.flatnonzero(is_query[o])
    cand = o[np.maximum(last_span[qpos], 0)]
    asked = o[qpos]
    match = ((last_span[qpos] >= 0) & (k_trace[cand] == k_trace[asked])
             & (k_id[cand] == k_id[asked]) & (k_len[cand] == k_len[asked])
             & (cand != q[asked - n]))            # its own parent: a root
    parent = np.full(n, -1, np.int64)
    parent[q[asked[match] - n]] = cand[match]

    depth = np.where(parent < 0, 0, -1).astype(np.int64)
    levels = [np.flatnonzero(parent < 0)]
    pending = np.flatnonzero(parent >= 0)
    while len(pending) and len(levels) <= _MAX_LEVELS:
        hit = depth[parent[pending]] == len(levels) - 1
        if not hit.any():
            break
        levels.append(pending[hit])
        depth[levels[-1]] = len(levels) - 1
        pending = pending[~hit]
    size = np.ones(n, np.int64)
    for nodes in reversed(levels[1:]):
        np.add.at(size, parent[nodes], size[nodes])
    # spans numbered before a node under the same parent (roots: in the
    # same trace), children in row order as `nested_set` visits them
    group = np.where(parent < 0, n + trace_no, parent)
    go = np.argsort(group, kind="stable")
    excl = np.cumsum(size[go]) - size[go]
    gstart = np.concatenate(([0], np.flatnonzero(np.diff(group[go])) + 1))
    before = np.empty(n, np.int64)
    before[go] = excl - np.repeat(excl[gstart],
                                  np.diff(np.concatenate((gstart, [n]))))
    pre = before.copy()
    for nodes in levels[1:]:
        pre[nodes] = pre[parent[nodes]] + 1 + before[nodes]
    left = (2 * pre - depth + 1).astype(np.int32)
    right = (left + 2 * size - 1).astype(np.int32)
    parent_row = np.where(parent < 0, -1, parent - first).astype(np.int32)

    if len(pending):
        sid_b, pid_b = sid.tobytes(), pid.tobytes()
        for t in np.unique(trace_no[pending]).tolist():
            a, b = int(bounds[t]), int(bounds[t + 1])
            sids = [sid_b[8 * i:8 * i + sl[i]] for i in range(a, b)]
            pids = [pid_b[8 * i:8 * i + pl[i]] for i in range(a, b)]
            left[a:b], right[a:b], parent_row[a:b] = bs.nested_set(sids, pids)
    return parent_row, left, right
