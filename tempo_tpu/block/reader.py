"""BackendBlock reader: trace-by-ID, columnar scan batches, tag scans.

Read side of the block encoding (`vparquet4/block_findtracebyid.go`,
`block_traceql.go`, `block_search_tags.go`). All object reads go through the
RawReader (so the role-keyed cache layer and, later, hedging apply); parquet
row groups are fetched with byte-range reads via a small file adapter.

The scan interface hands the query engines *column batches*: dicts of numpy
arrays per row group — the staging format the TraceQL mask-algebra engine
turns into device tensors (replacing the reference's pointer-chasing
`parquetquery` iterator tree, `pkg/parquetquery/iters.go`).
"""

from __future__ import annotations

import io
import json
from typing import Iterator, Sequence

import numpy as np
import pyarrow.parquet as pq

from tempo_tpu.backend.meta import BlockMeta
from tempo_tpu.backend.raw import DoesNotExist, RawReader, block_keypath
from tempo_tpu.obs import querystats
from tempo_tpu.block import schema as bs
from tempo_tpu.block.bloom import BloomFilter, shard_name
from tempo_tpu.block.writer import DATA_NAME, INDEX_NAME


class _RangeFile(io.RawIOBase):
    """File-like over RawReader byte-range reads (parquet footer/row groups)."""

    def __init__(self, r: RawReader, name: str, kp, size: int):
        self._r = r
        self._name = name
        self._kp = kp
        self._size = size
        self._pos = 0

    def seekable(self) -> bool:
        return True

    def readable(self) -> bool:
        return True

    def seek(self, off: int, whence: int = 0) -> int:
        self._pos = {0: off, 1: self._pos + off, 2: self._size + off}[whence]
        return self._pos

    def tell(self) -> int:
        return self._pos

    def read(self, n: int = -1) -> bytes:
        if n is None or n < 0:
            n = self._size - self._pos
        data = self._r.read_range(self._name, self._kp, self._pos, n)
        self._pos += len(data)
        return data

    def size(self) -> int:
        return self._size


class BackendBlock:
    """One immutable block in object storage."""

    def __init__(self, r: RawReader, meta: BlockMeta):
        self.r = r
        self.meta = meta
        self.kp = block_keypath(meta.block_id, meta.tenant_id)
        self._pf: pq.ParquetFile | None = None
        self._index: list[dict] | None = None

    # -- plumbing ----------------------------------------------------------

    def parquet_file(self) -> pq.ParquetFile:
        if self._pf is None:
            size = self.meta.size_bytes
            if size <= 0:
                size = self.r.size(DATA_NAME, self.kp)  # type: ignore[attr-defined]
            self._pf = pq.ParquetFile(
                _RangeFile(self.r, DATA_NAME, self.kp, size))
        return self._pf

    def row_group_index(self) -> list[dict]:
        if self._index is None:
            try:
                doc = json.loads(self.r.read(INDEX_NAME, self.kp))
                self._index = doc["row_groups"]
            except DoesNotExist:
                self._index = []
        return self._index

    # -- trace by id (`block_findtracebyid.go`) -----------------------------

    def _bloom_maybe(self, trace_id: bytes) -> bool:
        shard = (trace_id[0] if trace_id else 0) % max(self.meta.bloom_shard_count, 1)
        try:
            bf = BloomFilter.from_bytes(self.r.read(shard_name(shard), self.kp))
        except DoesNotExist:
            return True  # no bloom → must scan
        return trace_id in bf

    def find_trace_by_id(self, trace_id: bytes) -> list[dict] | None:
        """Spans of one trace as flat dicts, or None. Bloom probe → row-group
        binary search on the index bounds → single-group read."""
        tid = bytes(trace_id).ljust(16, b"\0")[:16]
        if not self._bloom_maybe(tid):
            querystats.add(blocks_skipped=1)      # bloom prune
            return None
        hexid = tid.hex()
        pf = self.parquet_file()
        index = self.row_group_index()
        if index:
            rgs = [i for i, g in enumerate(index)
                   if g["min_trace_id"] <= hexid <= g["max_trace_id"]]
        else:
            rgs = list(range(pf.num_row_groups))  # index lost: full scan
        if not rgs:
            querystats.add(blocks_skipped=1)      # row-group bounds prune
            return None
        querystats.add(blocks_scanned=1)
        out: list[dict] = []
        for rg in rgs:
            with querystats.stage("block_fetch"):
                tbl = pf.read_row_group(rg)
            querystats.add(inspected_bytes=tbl.nbytes,
                           inspected_spans=tbl.num_rows)
            rows = trace_id_rows(tbl, tid)
            if len(rows):
                out.extend(_rows_to_spans(tbl, rows))
        return out or None

    # -- columnar scan -----------------------------------------------------

    def column_batches(self, columns: Sequence[str] | None = None,
                       row_groups: Sequence[int] | None = None) -> Iterator[dict]:
        """Yield {column: numpy array} per row group (+ '_row_offset', '_rows').

        List-typed columns come back as arrow arrays (offsets+values);
        fixed-width columns as numpy. The caller picks only the columns its
        compiled conditions touch — the pushdown analog of `AllConditions`.
        """
        pf = self.parquet_file()
        index = self.row_group_index()
        rgs = range(pf.num_row_groups) if row_groups is None else row_groups
        for rg in rgs:
            with querystats.stage("block_fetch"):
                tbl = pf.read_row_group(rg, columns=list(columns) if columns else None)
            querystats.add(inspected_bytes=tbl.nbytes)
            out: dict = {"_rows": tbl.num_rows}
            out["_row_offset"] = index[rg]["row_offset"] if rg < len(index) else None
            for name in tbl.schema.names:
                col = tbl.column(name)
                if pa_is_fixed(col.type):
                    out[name] = col.to_numpy(zero_copy_only=False)
                else:
                    out[name] = col.combine_chunks()
            yield out

    def dedicated_column_name(self, scope: str, attr: str) -> str | None:
        for i, c in enumerate(self.meta.dedicated_columns):
            if c.scope == scope and c.name == attr:
                return bs.dedicated_field_name(scope, i)
        return None


def pa_is_fixed(t) -> bool:
    import pyarrow as pa

    return not (pa.types.is_list(t) or pa.types.is_large_list(t))


def trace_id_rows(tbl, trace_id: bytes) -> np.ndarray:
    """Rows of `tbl` whose 16-byte trace id is `trace_id` (zero-padded),
    matched on the column's buffer: a numpy compare of the column's bytes
    objects with a bytes value drops that value's trailing zero bytes, so
    an id ending in one was never found."""
    want = np.frombuffer(bytes(trace_id).ljust(16, b"\0")[:16], np.uint8)
    parts = [np.frombuffer(ch.buffers()[1], np.uint8)[
        16 * ch.offset:16 * (ch.offset + len(ch))]
        for ch in tbl.column("trace_id").chunks]
    ids = (np.concatenate(parts) if parts
           else np.zeros(0, np.uint8)).reshape(-1, 16)
    return np.flatnonzero((ids == want).all(axis=1))


def _rows_to_spans(tbl, rows: np.ndarray) -> list[dict]:
    """Materialize selected rows back into flat span dicts (find-by-id
    path, WAL completion). Each column converts ONCE (a per-row scalar
    `as_py()` costs tens of microseconds per span across ~30 columns)."""
    import pyarrow as pa

    whole = len(rows) == tbl.num_rows and \
        bool((rows == np.arange(tbl.num_rows)).all())
    picked = tbl if whole else tbl.take(pa.array(rows, pa.int64()))
    c = {n: picked.column(n).to_pylist() for n in tbl.schema.names}
    out = []
    for r in range(picked.num_rows):
        attrs: dict = {}
        for kcol, vcol in (("sattr_str_keys", "sattr_str_vals"),
                           ("sattr_int_keys", "sattr_int_vals"),
                           ("sattr_f64_keys", "sattr_f64_vals"),
                           ("sattr_bool_keys", "sattr_bool_vals")):
            attrs.update(zip(c[kcol][r] or [], c[vcol][r] or []))
        res_attrs: dict = {}
        for kcol, vcol in (("rattr_str_keys", "rattr_str_vals"),
                           ("rattr_int_keys", "rattr_int_vals"),
                           ("rattr_f64_keys", "rattr_f64_vals"),
                           ("rattr_bool_keys", "rattr_bool_vals")):
            res_attrs.update(zip(c[kcol][r] or [], c[vcol][r] or []))
        start = c["start_unix_nano"][r]
        out.append({
            "trace_id": c["trace_id"][r],
            "span_id": c["span_id"][r],
            "parent_span_id": c["parent_span_id"][r],
            "name": c["name"][r],
            "service": c["service"][r],
            "kind": c["kind"][r],
            "status_code": c["status_code"][r],
            "status_message": c["status_message"][r],
            "start_unix_nano": start,
            "end_unix_nano": start + c["duration_ns"][r],
            "attrs": attrs,
            "res_attrs": res_attrs,
            "events": [{"time_unix_nano": t, "name": n} for t, n in
                       zip(c["event_times"][r] or [],
                           c["event_names"][r] or [])],
            "links": [{"trace_id": t, "span_id": s} for t, s in
                      zip(c["link_trace_ids"][r] or [],
                          c["link_span_ids"][r] or [])],
        })
    return out
