"""Block encoding tests: round-trip, nested set, trace-by-id, bloom, WAL
(reference test models: vparquet4 create/fetch round-trip tests,
nested_set_model_test.go, wal_test.go)."""

import numpy as np
import pytest

from tempo_tpu.backend import MemBackend, read_block_meta
from tempo_tpu.block import (
    BackendBlock,
    BloomFilter,
    ShardedBloom,
    WALBlock,
    nested_set,
    rescan_blocks,
    spans_by_trace,
    write_block,
)
from tempo_tpu.backend.meta import DedicatedColumn
from tempo_tpu.utils.livetraces import (
    ERR_LIVE_TRACES_EXCEEDED,
    ERR_TRACE_TOO_LARGE,
    LiveTraceStore,
)


def mkspan(tid, sid, parent=b"", name="op", service="svc", start=1_000, dur=50,
           attrs=None, res_attrs=None, **kw):
    return {
        "trace_id": tid, "span_id": sid, "parent_span_id": parent,
        "name": name, "service": service, "kind": 2, "status_code": 0,
        "status_message": "", "start_unix_nano": start,
        "end_unix_nano": start + dur, "attrs": attrs or {},
        "res_attrs": res_attrs or {}, **kw,
    }


def trace(tid_byte: int, n_spans: int = 3, **kw):
    tid = bytes([tid_byte] * 16)
    spans = [mkspan(tid, bytes([tid_byte, j] + [0] * 6),
                    parent=b"" if j == 0 else bytes([tid_byte, 0] + [0] * 6),
                    start=1_000_000_000 * tid_byte + j, **kw)
             for j in range(n_spans)]
    return tid, spans


# -- nested set --------------------------------------------------------------

def test_nested_set_chain():
    # root -> a -> b
    sids = [b"r" * 8, b"a" * 8, b"b" * 8]
    pids = [b"", b"r" * 8, b"a" * 8]
    left, right, parent = nested_set(sids, pids)
    assert parent == [-1, 0, 1]
    # containment: descendant interval inside ancestor interval
    assert left[0] < left[1] < left[2] < right[2] < right[1] < right[0]


def test_nested_set_orphan_and_cycle():
    sids = [b"a" * 8, b"b" * 8, b"c" * 8, b"d" * 8]
    pids = [b"", b"x" * 8, b"d" * 8, b"c" * 8]  # b orphan; c<->d cycle
    left, right, parent = nested_set(sids, pids)
    assert parent[0] == -1 and parent[1] == -1
    assert all(l > 0 and r > l for l, r in zip(left, right))


# -- bloom -------------------------------------------------------------------

def test_bloom_membership():
    ids = [bytes([i] * 16) for i in range(100)]
    bf = BloomFilter(len(ids), fpp=0.01)
    bf.add_many(ids)
    assert all(i in bf for i in ids)
    other = [bytes([200, i] + [7] * 14) for i in range(100)]
    fp = sum(1 for o in other if o in bf)
    assert fp <= 5
    rt = BloomFilter.from_bytes(bf.to_bytes())
    assert all(i in rt for i in ids)


def test_sharded_bloom_routes_by_first_byte():
    sb = ShardedBloom(4, 100)
    tid = bytes([7] + [0] * 15)
    sb.add(tid)
    assert sb.shard_of(tid) == 3
    assert tid in sb


# -- block round trip --------------------------------------------------------

@pytest.fixture
def block():
    be = MemBackend()
    traces = [trace(i, n_spans=4, attrs={"http.status_code": 200 + i, "route": f"/r{i}"},
                    res_attrs={"cluster": "c1"}) for i in range(1, 20)]
    meta = write_block(be, "t1", traces, row_group_rows=24,
                       dedicated_columns=[DedicatedColumn("span", "route")])
    return be, meta, traces


def test_write_block_meta_stats(block):
    be, meta, traces = block
    assert meta.total_objects == 19
    assert meta.total_spans == 19 * 4
    assert meta.size_bytes > 0
    got = read_block_meta(be, meta.block_id, "t1")
    assert got.version == "vtpu1"
    assert [c.name for c in got.dedicated_columns] == ["route"]


def test_find_trace_by_id(block):
    be, meta, traces = block
    b = BackendBlock(be, meta)
    tid, spans = traces[7]
    got = b.find_trace_by_id(tid)
    assert got is not None and len(got) == 4
    assert {s["name"] for s in got} == {"op"}
    assert got[0]["attrs"]["http.status_code"] == 200 + 8
    assert got[0]["res_attrs"]["cluster"] == "c1"
    # absent trace: bloom or scan miss
    assert b.find_trace_by_id(bytes([99] * 16)) is None


def test_column_batches_scan(block):
    be, meta, traces = block
    b = BackendBlock(be, meta)
    rows = 0
    for cb in b.column_batches(columns=["trace_idx", "duration_ns", "service"]):
        rows += cb["_rows"]
        assert cb["duration_ns"].dtype == np.int64
        assert (cb["duration_ns"] == 50).all()
    assert rows == meta.total_spans
    # multiple row groups given row_group_rows=24 < 76 spans
    assert len(b.row_group_index()) > 1


def test_dedicated_column(block):
    be, meta, traces = block
    b = BackendBlock(be, meta)
    name = b.dedicated_column_name("span", "route")
    assert name == "ded_s_00"
    vals = set()
    for cb in b.column_batches(columns=[name]):
        vals.update(cb[name].tolist())
    assert "/r1" in vals


@pytest.mark.parametrize("where", ["complete", "wal"])
@pytest.mark.parametrize("tid", [bytes(range(1, 16)) + b"\0",
                                 b"\0" * 15 + b"\x07", bytes(range(1, 8))],
                         ids=["zero_last", "zeros_first", "short"])
def test_find_trace_by_id_takes_the_id_byte_for_byte(where, tid, tmp_path):
    """An id that ends in a zero byte (one random id in 256) or is shorter
    than 16 bytes is found, and the id one bit away is not taken for it."""
    other = tid[:-1] + bytes([tid[-1] ^ 1])
    spans = [mkspan(tid, b"\x01" * 8), mkspan(other, b"\x02" * 8)]
    if where == "wal":
        b = WALBlock(str(tmp_path), "t1")
        b.append(spans)
    else:
        be = MemBackend()
        b = BackendBlock(be, write_block(be, "t1", spans_by_trace(spans)))
    assert [s["span_id"] for s in b.find_trace_by_id(tid)] == [b"\x01" * 8]
    assert [s["span_id"] for s in b.find_trace_by_id(other)] == [b"\x02" * 8]


# -- WAL ---------------------------------------------------------------------

def test_wal_append_replay_complete(tmp_path):
    w = WALBlock(str(tmp_path), "t1")
    t1, s1 = trace(1)
    t2, s2 = trace(2)
    w.append(s1[:2])
    w.append(s1[2:] + s2)
    # replay from disk via fresh handle
    blocks = rescan_blocks(str(tmp_path))
    assert len(blocks) == 1 and blocks[0].block_id == w.block_id
    groups = blocks[0].complete()
    assert [tid for tid, _ in groups] == [t1, t2]
    assert len(groups[0][1]) == 3 and len(groups[1][1]) == 3
    assert blocks[0].find_trace_by_id(t2) is not None
    blocks[0].clear()
    assert rescan_blocks(str(tmp_path)) == []


def test_wal_to_complete_block(tmp_path):
    be = MemBackend()
    w = WALBlock(str(tmp_path), "t1")
    for i in range(1, 6):
        _, spans = trace(i)
        w.append(spans)
    meta = write_block(be, "t1", w.complete(), block_id=w.block_id)
    assert meta.total_objects == 5
    b = BackendBlock(be, meta)
    assert b.find_trace_by_id(bytes([3] * 16)) is not None


# -- live traces -------------------------------------------------------------

def test_livetraces_limits_and_cutting():
    now = [100.0]
    st = LiveTraceStore(max_live_traces=2, max_trace_bytes=500, now=lambda: now[0])
    assert st.push(b"t1", [mkspan(b"t1" * 8, b"s1")]) is None
    assert st.push(b"t2", [mkspan(b"t2" * 8, b"s2")]) is None
    assert st.push(b"t3", [mkspan(b"t3" * 8, b"s3")]) == ERR_LIVE_TRACES_EXCEEDED
    assert st.push(b"t1", [mkspan(b"t1" * 8, b"s4")], size_bytes=1000) == ERR_TRACE_TOO_LARGE
    now[0] = 110.0
    st.push(b"t2", [mkspan(b"t2" * 8, b"s5")])
    cut = st.cut(idle_s=5.0)  # t1 idle 10s, t2 just appended
    assert [c.trace_id for c in cut] == [b"t1"]
    assert [c.trace_id for c in st.cut(immediate=True)] == [b"t2"]
    assert len(st) == 0
