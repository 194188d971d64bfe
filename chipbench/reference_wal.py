"""The plain reader of the generator's ingest log: the reference of the
configuration `k6-single-binary-wal`, numpy and the standard library only.

Written from the format's description and from nothing of the program's
(it imports nothing from `tempo_tpu`), so that what it reads back from a
run's disk is held against what the load generator sent by code that
shares no line with the writer:

    <wal.dir>/<quoted tenant>/<first seq, 12 digits>.wal     one segment
    segment  = frame*
    frame    = "TWR1" | seq u64 | length u32 | adler32 u32 | payload
    payload  = meta length u32 | meta JSON | array count u16 | array*
    array    = name length u16 | name | descr length u16 | descr JSON
             | ndim u8 | ndim x dim u64 | nbytes u64 | raw bytes

all little-endian. `descr` is numpy's own description of a dtype (a
string, or a list of [name, descr] / [name, descr, shape] fields, which
JSON made of tuples), rebuilt here field by field. A record's `meta` has
`kind`, `n` (its spans) and `ts`; a `staged` record's arrays are `spans`
(a row a span: ids of its strings, kind, status, start and end), `sattrs`,
`rattrs` and `res`. Strings travel as per-SEGMENT deltas: a record that
saw the tenant's vocabulary grow carries `smark` (the table's length
before it) and `new_strings`; an id is an index into the table the
segment's records have built so far, and every segment starts from an
empty table. A torn tail (a crash mid-write) ends a segment: reading
stops at the last whole frame and says where and why.

The reference for the STATE (what the collected metrics must read) is the
numpy oracle of `mixes/otlp_push.py` over `spans.draw_push`: its list of
what was sent is the plain log, the oracle over it the plain replay.
"""

from __future__ import annotations

import json
import os
import struct
import zlib

import numpy as np

MAGIC = b"TWR1"
HEADER = struct.Struct("<QII")          # seq, payload length, adler32
SUFFIX = ".wal"


class Torn(Exception):
    """The bytes at hand do not hold a whole record."""


def dtype_of(descr) -> np.dtype:
    """numpy's dtype description, after JSON, back to a dtype."""
    if isinstance(descr, str):
        return np.dtype(descr)
    names, formats, offsets, at = [], [], [], 0
    for field in descr:
        name, sub = field[0], dtype_of(field[1])
        if len(field) > 2:
            sub = np.dtype((sub, tuple(field[2])))
        if name:                         # "" is padding: bytes, no field
            names.append(name)
            formats.append(sub)
            offsets.append(at)
        at += sub.itemsize
    return np.dtype({"names": names, "formats": formats, "offsets": offsets,
                     "itemsize": at})


class _Cursor:
    def __init__(self, data: bytes) -> None:
        self.data, self.at = data, 0

    def take(self, n: int) -> bytes:
        if n < 0 or self.at + n > len(self.data):
            raise Torn(f"{n} bytes wanted at {self.at} of {len(self.data)}")
        out = self.data[self.at:self.at + n]
        self.at += n
        return out

    def unpack(self, fmt: str) -> tuple:
        return struct.unpack(fmt, self.take(struct.calcsize(fmt)))


def read_payload(payload: bytes) -> tuple[dict, dict]:
    """One record's (meta, arrays)."""
    cur = _Cursor(payload)
    meta = json.loads(cur.take(cur.unpack("<I")[0]))
    arrays = {}
    for _ in range(cur.unpack("<H")[0]):
        name = cur.take(cur.unpack("<H")[0]).decode()
        dt = dtype_of(json.loads(cur.take(cur.unpack("<H")[0])))
        shape = cur.unpack(f"<{cur.unpack('<B')[0]}Q")
        raw = cur.take(cur.unpack("<Q")[0])
        if int(np.prod(shape, dtype=np.int64)) * dt.itemsize != len(raw):
            raise Torn(f"array {name!r}: {len(raw)} bytes for {shape} "
                       f"of {dt.itemsize}")
        arrays[name] = np.frombuffer(raw, dt).reshape(shape).copy()
    if cur.at != len(payload):
        raise Torn(f"{len(payload) - cur.at} bytes after the last array")
    return meta, arrays


def read_segment(path: str) -> tuple[list, str | None]:
    """A segment's whole frames, in order, as [(seq, meta, arrays,
    strings)], where `strings` is the segment's table as it stood after
    that record; and why reading stopped before the file's end (None: it
    did not). A frame is whole when its magic, its length and its
    checksum hold."""
    with open(path, "rb") as f:
        data = f.read()
    out, table, at = [], [], 0
    head = len(MAGIC) + HEADER.size
    while at < len(data):
        if at + head > len(data):
            return out, f"{len(data) - at} bytes of a header at {at}"
        if data[at:at + 4] != MAGIC:
            return out, f"no frame magic at {at}"
        seq, length, checksum = HEADER.unpack_from(data, at + 4)
        payload = data[at + head:at + head + length]
        if len(payload) < length:
            return out, (f"frame {seq} at {at} is cut: {len(payload)} of "
                         f"{length} bytes")
        if zlib.adler32(payload) != checksum:
            return out, f"frame {seq} at {at}: the checksum does not hold"
        try:
            meta, arrays = read_payload(payload)
        except (Torn, ValueError, struct.error) as e:
            return out, f"frame {seq} at {at}: {e}"
        new = meta.get("new_strings")
        if new:
            if meta.get("smark") != len(table):
                return out, (f"frame {seq}: string delta from "
                             f"{meta.get('smark')}, the table has "
                             f"{len(table)}")
            table = table + list(new)    # a new list: earlier records
        out.append((seq, meta, arrays, table))   # keep the one they saw
        at += head + length
    return out, None


def segments(tenant_dir: str) -> list[str]:
    try:
        names = os.listdir(tenant_dir)
    except FileNotFoundError:
        return []
    return sorted(n for n in names if n.endswith(SUFFIX)
                  and n[:-len(SUFFIX)].isdigit())


def read_tenant(tenant_dir: str) -> tuple[list, list]:
    """Every segment of one tenant's log, oldest first: [(segment name,
    records)], and the faults found: a torn frame anywhere, a segment
    whose first record is not the one its name gives, a `seq` that does
    not follow its predecessor's."""
    out, faults, last = [], [], None
    names = segments(tenant_dir)
    for i, name in enumerate(names):
        records, torn = read_segment(os.path.join(tenant_dir, name))
        if torn is not None:
            faults.append(f"{name}: {torn}" + (
                "" if i == len(names) - 1 else " (not the last segment)"))
        if records and records[0][0] != int(name[:-len(SUFFIX)]):
            faults.append(f"{name}: first record is {records[0][0]}")
        for seq, *_ in records:
            if last is not None and seq != last + 1:
                faults.append(f"{name}: record {seq} follows {last}")
            last = seq
        out.append((name, records))
    return out, faults


def span_columns(arrays: dict, strings: list) -> dict:
    """A staged record's spans as plain columns, the ids resolved
    through the segment's table."""
    rows = arrays["spans"]
    table = np.asarray(strings + [""], dtype=object)

    def resolve(ids):
        ids = np.asarray(ids, np.int64)
        return table[np.where((ids >= 0) & (ids < len(strings)), ids, -1)]

    return {"service": resolve(rows["service_id"]),
            "name": resolve(rows["name_id"]),
            "kind": rows["kind"].astype(np.int64),
            "status": rows["status_code"].astype(np.int64),
            "start_ns": rows["start_ns"].astype(np.int64),
            "end_ns": rows["end_ns"].astype(np.int64),
            "span_id": np.ascontiguousarray(rows["span_id"]).view("<i8")
            .ravel()}
