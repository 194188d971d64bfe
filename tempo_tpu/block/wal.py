"""Write-ahead log: per-block append-only parquet segments + replay.

Analog of `tempodb/wal/wal.go:23-160` + `vparquet4/wal_block.go`: a WAL block
is a directory `<wal>/<block_id>+<tenant>+vtpu1/` of numbered parquet
segment files, one fsynced file per append (the reference appends flushed
parquet pages; one small file per flush is the same durability contract with
simpler recovery). Replay = `rescan_blocks`: re-read every segment of every
block dir, skipping torn files (`RescanBlocks` `wal/wal.go:80`).

`complete()` merges all segments into sorted (trace_id, spans) groups —
input to `writer.write_block` (WAL block → complete block,
`modules/ingester/instance.go:316` CompleteBlock).
"""

from __future__ import annotations

import io
import os
import uuid
from typing import Iterable, Iterator

import pyarrow.parquet as pq

from tempo_tpu.block import schema as bs
from tempo_tpu.block.reader import _rows_to_spans, trace_id_rows

import numpy as np


from tempo_tpu.utils import fsync_dir as _fsync_dir  # noqa: E402


class WALBlock:
    def __init__(self, path: str, tenant: str, block_id: str | None = None):
        self.tenant = tenant
        self.block_id = block_id or str(uuid.uuid4())
        self.dir = os.path.join(path, f"{self.block_id}+{tenant}+{bs.VERSION}")
        created = not os.path.isdir(self.dir)
        os.makedirs(self.dir, exist_ok=True)
        if created:
            # fsync the WAL ROOT so the block dir's own dirent survives a
            # crash: segment files fsync themselves and their parent (the
            # block dir, in append()), but a power loss right after the
            # first append could otherwise drop the block directory entry
            # from the root — a fully-fsynced segment nobody can rescan
            _fsync_dir(path)
        self._next_seg = self._scan_next_seg()
        self.spans_appended = 0

    def _scan_next_seg(self) -> int:
        segs = [int(f.split(".")[0]) for f in os.listdir(self.dir)
                if f.endswith(".parquet") and f.split(".")[0].isdigit()]
        return max(segs, default=-1) + 1

    def append(self, spans: Iterable[dict]) -> None:
        """Durably append a batch of flat span dicts as one segment file."""
        groups = bs.spans_by_trace(spans)
        if groups:
            self.append_table(bs.traces_to_table(groups))

    def append_table(self, table) -> None:
        """Durably append rows already in block order (`bs.block_schema()`)
        as one segment file."""
        tmp = os.path.join(self.dir, f".{self._next_seg:07d}.tmp")
        with open(tmp, "wb") as f:
            pq.write_table(table, f, compression="zstd")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(self.dir, f"{self._next_seg:07d}.parquet"))
        # fsync the directory so the rename itself survives power loss
        dfd = os.open(self.dir, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
        self._next_seg += 1
        self.spans_appended += table.num_rows

    def segments(self) -> list[str]:
        try:
            return sorted(f for f in os.listdir(self.dir) if f.endswith(".parquet"))
        except FileNotFoundError:
            return []  # cleared by a concurrent completion — read as empty

    def _tables(self) -> Iterator:
        for seg in self.segments():
            try:
                yield pq.read_table(os.path.join(self.dir, seg))
            except Exception:
                continue  # torn segment: skip, like RescanBlocks tolerates

    def iter_spans(self) -> Iterator[dict]:
        for tbl in self._tables():
            yield from _rows_to_spans(tbl, np.arange(tbl.num_rows))

    def complete(self) -> list[tuple[bytes, list[dict]]]:
        """All WAL contents as sorted trace groups (spans of a trace merged
        across segments)."""
        return bs.spans_by_trace(self.iter_spans())

    def find_trace_by_id(self, trace_id: bytes) -> list[dict] | None:
        """The trace's spans in segment order, or None. Each segment's
        trace-id column is matched first and only the trace's rows become
        dicts (a head block holds millions of spans under the write
        stress, a trace a handful)."""
        out: list[dict] = []
        for tbl in self._tables():
            rows = trace_id_rows(tbl, trace_id)
            if len(rows):
                out.extend(_rows_to_spans(tbl, rows))
        return out or None

    def clear(self) -> None:
        for f in os.listdir(self.dir):
            try:
                os.unlink(os.path.join(self.dir, f))
            except FileNotFoundError:
                pass
        os.rmdir(self.dir)


def rescan_blocks(path: str) -> list[WALBlock]:
    """Rebuild WALBlock handles for every block dir found under `path`."""
    out = []
    if not os.path.isdir(path):
        return out
    for d in sorted(os.listdir(path)):
        parts = d.split("+")
        if len(parts) != 3 or not os.path.isdir(os.path.join(path, d)):
            continue
        block_id, tenant, _version = parts
        out.append(WALBlock(path, tenant, block_id))
    return out
