"""Live traces kept as column slices of the pushed batch
(`tempo_tpu.block.live_columns`): the cut's arrow table, the reads before
the cut, the limits and the WAL segment must all equal what the span-dict
route makes of the same pushes."""

from __future__ import annotations

import sys
import threading

import numpy as np
import pytest

from tempo_tpu.backend.meta import DedicatedColumn
from tempo_tpu.block import schema as bs
from tempo_tpu.block.live_columns import ColumnSource, cut_table
from tempo_tpu.block.wal import rescan_blocks
from tempo_tpu.ingester.instance import InstanceConfig, TenantInstance
from tempo_tpu.model import proto_wire as pw
from tempo_tpu.model.combine import combine_spans, sort_spans
from tempo_tpu.model.interner import StringInterner
from tempo_tpu.model.otlp import encode_spans_otlp
from tempo_tpu.model.otlp_batch import StagedIngest, StagedView, stage_otlp
from tempo_tpu.model.span_batch import SpanBatch
from tempo_tpu.overrides.limits import IngestionLimits, Limits, ReadLimits
from tempo_tpu.utils import livetraces
from tempo_tpu.utils.livetraces import CUT_SPANS, LIVE_SPANS, LiveTraceStore

T0 = 1_700_000_000_000_000_000


def _id(n: int, width: int) -> bytes:
    return n.to_bytes(width, "big")


def _k6_spans(seed: int, groups: int = 8, per: int = 125,
              trace_len: int = 5) -> list[dict]:
    """The k6 write stress shape: `groups` resources x `per` spans,
    `trace_len`-span traces chained parent to child, one span attr."""
    rng = np.random.default_rng(seed)
    out = []
    for g in range(groups):
        for i in range(per):
            t, j = divmod(i, trace_len)
            tid = _id(seed * 1_000_003 + g * 1000 + t + 1, 16)
            start = T0 + int(rng.integers(0, 10**9))
            out.append({
                "trace_id": tid,
                "span_id": _id((g * per + i + 1) * 7919 + seed, 8),
                "parent_span_id":
                    _id((g * per + i) * 7919 + seed, 8) if j else b"",
                "name": f"op-{int(rng.integers(0, 64)):04d}",
                "service": f"svc-{g:04d}",
                "kind": int(rng.integers(0, 2)),
                "status_code": 2 if rng.random() < 0.02 else 0,
                "start_unix_nano": start,
                "end_unix_nano": start + int(rng.lognormal(16, 1.0)),
                "attrs": {"k6.vu": f"vu-{int(rng.integers(0, 16)):02d}"},
                "res_attrs": {"service.name": f"svc-{g:04d}"},
            })
    return out


def _rich_spans() -> list[dict]:
    """Everything the k6 shape lacks: events, links, non-scalar and int /
    bool / double attrs, resource attrs of every type, ids shorter than
    16 / 8 bytes, an orphan, a parent cycle, a span that ends before it
    starts, a status message."""
    res = {"service.name": "checkout", "host.cpus": 64, "spot": True,
           "load": 0.75, "zones": ["a", "b"]}
    t1, t2, t3 = _id(0xA1, 16), _id(0xB2, 7), _id(0xC3, 16)
    return [
        {"trace_id": t1, "span_id": _id(1, 8), "parent_span_id": b"",
         "name": "root", "service": "checkout", "kind": 2,
         "start_unix_nano": T0 + 50, "end_unix_nano": T0 + 900,
         "attrs": {"http.method": "GET", "http.status_code": 200,
                   "retry": False, "ratio": 0.5, "big": 2**24 + 1,
                   "neg": -7, "tags": ["x", 1], "kv": {"a": 1},
                   "raw": b"\x00\x01"},
         "res_attrs": res,
         "events": [{"time_unix_nano": T0 + 60, "name": "accept"},
                    {"time_unix_nano": T0 + 70, "name": "flush"}],
         "links": [{"trace_id": t3, "span_id": _id(9, 8)},
                   {"trace_id": _id(5, 3), "span_id": _id(6, 2)}]},
        {"trace_id": t1, "span_id": _id(2, 8), "parent_span_id": _id(1, 8),
         "name": "child", "service": "checkout", "kind": 3,
         "status_code": 2, "status_message": "boom",
         "start_unix_nano": T0 + 10, "end_unix_nano": T0 + 5,
         "attrs": {}, "res_attrs": res,
         "events": [{"time_unix_nano": T0 + 11, "name": "late"}]},
        {"trace_id": t1, "span_id": _id(3, 8), "parent_span_id": _id(77, 8),
         "name": "orphan", "service": "checkout",
         "start_unix_nano": T0 + 10, "end_unix_nano": T0 + 20,
         "attrs": {"http.method": "PUT"}, "res_attrs": res},
        # short ids: a 7-byte trace id, 3- and 2-byte span ids; the child
        # names its parent by the parent's exact (short) bytes
        {"trace_id": t2, "span_id": _id(4, 3), "parent_span_id": b"",
         "name": "short-root", "service": "edge",
         "start_unix_nano": T0 + 1, "end_unix_nano": T0 + 2,
         "attrs": {"n": 1}, "res_attrs": {"service.name": "edge"}},
        {"trace_id": t2, "span_id": _id(5, 2), "parent_span_id": _id(4, 3),
         "name": "short-child", "service": "edge",
         "start_unix_nano": T0 + 3, "end_unix_nano": T0 + 4,
         "attrs": {"n": 2.5}, "res_attrs": {"service.name": "edge"}},
        # same padded bytes as the root's id, another length: no parent
        {"trace_id": t2, "span_id": _id(6, 8), "parent_span_id": _id(4, 4),
         "name": "short-stranger", "service": "edge",
         "start_unix_nano": T0 + 5, "end_unix_nano": T0 + 6,
         "attrs": {}, "res_attrs": {"service.name": "edge"}},
        # a parent cycle 10 -> 11 -> 10, a self parent, a leaf under the cycle
        {"trace_id": t3, "span_id": _id(10, 8), "parent_span_id": _id(11, 8),
         "name": "cyc-a", "service": "loop",
         "start_unix_nano": T0 + 1, "end_unix_nano": T0 + 2,
         "attrs": {}, "res_attrs": {"service.name": "loop"}},
        {"trace_id": t3, "span_id": _id(11, 8), "parent_span_id": _id(10, 8),
         "name": "cyc-b", "service": "loop",
         "start_unix_nano": T0 + 2, "end_unix_nano": T0 + 3,
         "attrs": {}, "res_attrs": {"service.name": "loop"}},
        {"trace_id": t3, "span_id": _id(12, 8), "parent_span_id": _id(12, 8),
         "name": "self", "service": "loop",
         "start_unix_nano": T0 + 3, "end_unix_nano": T0 + 4,
         "attrs": {}, "res_attrs": {"service.name": "loop"}},
        {"trace_id": t3, "span_id": _id(13, 8), "parent_span_id": _id(11, 8),
         "name": "leaf", "service": "loop",
         "start_unix_nano": T0 + 4, "end_unix_nano": T0 + 5,
         "attrs": {}, "res_attrs": {"service.name": "loop"}},
    ]


def _repeated_key_payload() -> bytes:
    """One span whose attributes name `k` three times (string, int,
    string) and `z` once between them: a dict keeps `k` where it first
    stood with the value that came last."""
    def kv(k: str, any_value: bytes) -> bytes:
        return pw.enc_field_msg(9, pw.enc_field_str(1, k)
                                + pw.enc_field_msg(2, any_value))
    span = (pw.enc_field_bytes(1, _id(0xD4, 16)) + pw.enc_field_bytes(2, _id(1, 8))
            + pw.enc_field_str(5, "dup")
            + pw.enc_field_fixed64(7, T0) + pw.enc_field_fixed64(8, T0 + 9)
            + kv("k", pw.enc_field_str(1, "first"))
            + kv("z", pw.enc_field_varint(2, 1))
            + kv("k", pw.enc_field_varint(3, 5))
            + kv("k", pw.enc_field_str(1, "last")))
    plain = (pw.enc_field_bytes(1, _id(0xD4, 16)) + pw.enc_field_bytes(2, _id(2, 8))
             + pw.enc_field_str(5, "plain")
             + pw.enc_field_fixed64(7, T0 + 1) + pw.enc_field_fixed64(8, T0 + 2)
             + kv("k", pw.enc_field_str(1, "only")))
    res = pw.enc_field_msg(1, pw.enc_field_msg(
        1, pw.enc_field_str(1, "service.name")
        + pw.enc_field_msg(2, pw.enc_field_str(1, "dups"))))
    return pw.enc_field_msg(1, res + pw.enc_field_msg(
        2, pw.enc_field_msg(2, span) + pw.enc_field_msg(2, plain)))


def _stage(payload, interner: StringInterner) -> StagedIngest:
    if not isinstance(payload, bytes):
        payload = encode_spans_otlp(payload)
    staged = stage_otlp(payload, interner)
    if staged is None:
        pytest.skip("native staging unavailable")
    return staged


# -- the two routes ----------------------------------------------------------
#
# A push is ("staged", StagedView) | ("batch", SpanBatch) | ("dicts",
# [(trace_id, spans)]). `_push_dicts` is the route the parent took:
# group row by row, `to_span_dicts`, `push_trace`.

def _trace_groups(view: StagedView) -> list[tuple[bytes, list[int]]]:
    """(exact trace-id bytes, row indices) in first-seen order, a row at
    a time: `StagedView.trace_groups` as the parent had it."""
    spans = view.staged.spans
    groups: dict[bytes, list[int]] = {}
    for i in view.row_indices().tolist():
        tid = bytes(spans["trace_id"][i])[:int(spans["tid_len"][i])]
        groups.setdefault(tid, []).append(i)
    return list(groups.items())


def _push_dicts(push_trace, kind, x) -> list:
    if kind == "staged":
        return [push_trace(tid, x.to_span_dicts(rows))
                for tid, rows in _trace_groups(x)]
    if kind == "batch":
        by_id: dict[bytes, list[dict]] = {}
        for s in x.to_span_dicts():
            by_id.setdefault(s["trace_id"], []).append(s)
        return [push_trace(tid, spans) for tid, spans in by_id.items()]
    return [push_trace(tid, spans) for tid, spans in x]


def _push_columns(inst: TenantInstance, kind, x) -> list:
    if kind == "staged":
        refused = inst.push_columns(
            ColumnSource(x.staged.batch()[0], x.staged), x.row_indices())
        return [refused.get(tid) for tid, _ in _trace_groups(x)]
    if kind == "batch":
        refused = inst.push_columns(ColumnSource(x),
                                    np.flatnonzero(x.valid[: x.n]))
        return [refused.get(tid) for tid in
                dict.fromkeys(x.trace_id[i].tobytes()
                              for i in np.flatnonzero(x.valid[: x.n]))]
    return [inst.push_trace(tid, spans) for tid, spans in x]


def _instance(tmp_path, name: str, limits: Limits | None = None,
              now=None, **cfg) -> TenantInstance:
    return TenantInstance("t", str(tmp_path / name / "wal"),
                          str(tmp_path / name / "blocks"),
                          cfg=InstanceConfig(**cfg), limits=limits,
                          **({"now": now} if now else {}))


def _reference_table(pushes, dedicated=()):
    store = LiveTraceStore()
    for kind, x in pushes:
        _push_dicts(store.push, kind, x)
    cut = store.cut(immediate=True)
    return bs.traces_to_table(bs.spans_by_trace(
        [s for lt in cut for s in sort_spans(combine_spans(lt.spans))]),
        dedicated)


def _column_table(tmp_path, pushes, dedicated=()):
    inst = _instance(tmp_path, "cols")
    for kind, x in pushes:
        _push_columns(inst, kind, x)
    return cut_table(inst.live.cut(immediate=True), dedicated)


def _assert_tables_equal(got, want) -> None:
    if want is None:        # every trace was refused: nothing to cut
        assert got is None
        return
    assert got.schema.equals(want.schema)
    assert got.num_rows == want.num_rows
    for name in want.schema.names:
        assert got.column(name).equals(want.column(name)), (
            name, got.column(name).to_pylist()[:8],
            want.column(name).to_pylist()[:8])
    assert got.equals(want)


# -- (a) the cut's table equals the dict route's ------------------------------

def _case_k6(it):
    return [("staged", _stage(_k6_spans(s), it).view()) for s in (1, 2, 3)]


def _case_rich(it):
    return [("staged", _stage(_rich_spans(), it).view())]


def _case_repeated_span_id(it):
    a = _k6_spans(4, groups=2, per=10)
    b = _k6_spans(5, groups=2, per=10)
    # the second push repeats three spans of the first (other names, other
    # start times: the first to arrive wins) and one span id twice itself
    for i in (0, 3, 7):
        b[i] = dict(a[i], name="again", start_unix_nano=T0 + i)
    b.append(dict(b[12], name="twice"))
    return [("staged", _stage(a, it).view()), ("staged", _stage(b, it).view())]


def _case_three_pushes(it):
    spans = _k6_spans(6, groups=3, per=25)
    # traces spread over the pushes, later pushes holding earlier starts
    parts = [spans[2::3], spans[1::3], spans[0::3]]
    return [("staged", _stage(p, it).view()) for p in parts]


def _case_sampled_view(it):
    staged = _stage(_k6_spans(7, groups=4, per=50), it)
    rng = np.random.default_rng(7)
    rows = np.sort(rng.choice(staged.n, staged.n // 3, replace=False))
    rich = _stage(_rich_spans(), it)
    return [("staged", staged.view(rows)),
            ("staged", rich.view(np.array([0, 2, 3, 5, 6, 9])))]


def _case_repeated_attr_key(it):
    return [("staged", _stage(_repeated_key_payload(), it).view())]


def _case_bare_batch(it):
    """The local-blocks processor's input: a SpanBatch with no staging
    behind it, some rows masked out (the slack filter does that)."""
    import dataclasses

    out = []
    for seed in (8, 9):
        sb = _stage(_k6_spans(seed, groups=4, per=30), it).batch()[0]
        valid = sb.valid.copy()
        valid[[1, 5, 40]] = False
        out.append(("batch", dataclasses.replace(sb, valid=valid)))
    out.append(("batch", _stage(_rich_spans(), it).batch()[0]))
    return out


def _case_dicts_beside_columns(it):
    """One sweep with traces of each kind, interleaving by trace id."""
    cols = _k6_spans(10, groups=2, per=20)
    dicts = _k6_spans(11, groups=2, per=20)
    return [("staged", _stage(cols, it).view()),
            ("dicts", bs.spans_by_trace(dicts)),
            ("staged", _stage(_rich_spans(), it).view())]


def _case_claimed_trace_id(it):
    """A dict push filed under one trace id whose spans name another, one
    that a staged push holds: `spans_by_trace` merges the two."""
    cols = _k6_spans(12, groups=1, per=10)
    stray = dict(_k6_spans(13, groups=1, per=5)[0],
                 trace_id=cols[0]["trace_id"])
    return [("staged", _stage(cols, it).view()),
            ("dicts", [(_id(0xEE, 16), [stray])])]


def _case_two_interners(it):
    other = StringInterner()
    other.intern("padding, so that ids differ")
    return [("staged", _stage(_k6_spans(14, groups=2, per=10), it).view()),
            ("staged", _stage(_k6_spans(15, groups=2, per=10), other).view())]


def _case_deep_and_wide(it):
    """A 150-span chain (deeper than the level-at-a-time numbering goes),
    a 40-child fan and a random forest with orphans, in one sweep."""
    rng = np.random.default_rng(16)
    spans = []

    def span(tid, i, parent, start):
        spans.append({
            "trace_id": tid, "span_id": _id(i, 8),
            "parent_span_id": _id(parent, 8) if parent else b"",
            "name": f"n{i % 7}", "service": "tree",
            "start_unix_nano": T0 + start, "end_unix_nano": T0 + start + 5,
            "attrs": {}, "res_attrs": {"service.name": "tree"}})
    for i in range(1, 151):
        span(_id(0x100, 16), i, i - 1, 1000 - i)     # children start first
    for i in range(1, 42):
        span(_id(0x101, 16), i, 0 if i == 1 else 1, int(rng.integers(0, 9)))
    for t in range(30):
        m = int(rng.integers(1, 25))
        for i in range(1, m + 1):
            span(_id(0x200 + t, 16), i, int(rng.integers(0, m + 3)),
                 int(rng.integers(0, 50)))
    order = rng.permutation(len(spans))
    return [("staged", _stage([spans[i] for i in order], it).view())]


def _case_trace_over_chunks(it):
    """Traces whose spans come in four pushes, each push holding the
    traces in another order, and one span sent again in the last push
    (the first copy wins)."""
    spans = _k6_spans(17, groups=2, per=40)
    order = np.random.default_rng(17).permutation(len(spans))
    parts = [[spans[i] for i in order[k::4]] for k in range(4)]
    parts[3].append(dict(parts[0][0], name="late-copy"))
    return [("staged", _stage(p, it).view()) for p in parts]


def _case_mixed_sizes(it):
    """Nine traces of one to five spans in a first push and the rest of
    their spans in a second: under a byte or a count limit one push holds
    traces refused beside traces admitted."""
    spans = _k6_spans(18, groups=1, per=45)
    first = [s for i, s in enumerate(spans) if i % 5 <= (i // 5) % 5]
    later = [s for i, s in enumerate(spans) if i % 5 > (i // 5) % 5]
    return [("staged", _stage(first, it).view()),
            ("staged", _stage(later, it).view())]


CASES = {
    "k6": _case_k6,
    "rich": _case_rich,
    "repeated_span_id": _case_repeated_span_id,
    "three_pushes": _case_three_pushes,
    "sampled_view": _case_sampled_view,
    "repeated_attr_key": _case_repeated_attr_key,
    "bare_batch": _case_bare_batch,
    "dicts_beside_columns": _case_dicts_beside_columns,
    "claimed_trace_id": _case_claimed_trace_id,
    "two_interners": _case_two_interners,
    "deep_and_wide": _case_deep_and_wide,
    "trace_over_chunks": _case_trace_over_chunks,
    "mixed_sizes": _case_mixed_sizes,
}

DEDICATED = (DedicatedColumn("span", "http.method"),
             DedicatedColumn("span", "http.status_code"),
             DedicatedColumn("resource", "service.name"),
             DedicatedColumn("span", "k6.vu"),
             DedicatedColumn("resource", "load"),
             DedicatedColumn("span", "retry"),
             DedicatedColumn("span", "never.seen"))


@pytest.mark.parametrize("dedicated", [(), DEDICATED],
                         ids=["plain", "dedicated"])
@pytest.mark.parametrize("case", list(CASES))
def test_cut_table_equals_the_dict_routes(case, dedicated, tmp_path):
    pushes = CASES[case](StringInterner())
    want = _reference_table(pushes, dedicated)
    got = _column_table(tmp_path, pushes, dedicated)
    assert want.num_rows > 0
    _assert_tables_equal(got, want)


@pytest.mark.parametrize("beside", ["columns", "dicts"])
def test_a_partial_idle_cut_and_the_rest_later_equal_the_dict_routes(
        beside, tmp_path):
    """Twenty traces of five spans. At t = 0 a push holds the first three
    spans of traces 0-9; at t = 10 one holds the last two of traces 0-4
    and 7, and all of traces 10-14. An idle cut at t = 12 takes traces
    5, 6, 8, 9 of the first chunk and leaves the rest of it; the immediate
    cut after it takes the rest. With `dicts`, traces 15-19 come as dict
    pushes at t = 0 and trace 7's last spans as one at t = 10 (its chunk
    rows join it). Each cut's table is the dict route's."""
    it = StringInterner()
    spans = _k6_spans(19, groups=2, per=50)
    trace = [(i // 50) * 10 + (i % 50) // 5 for i in range(len(spans))]
    place = [i % 5 for i in range(len(spans))]

    def pick(test) -> list[dict]:
        return [s for s, n, j in zip(spans, trace, place) if test(n, j)]
    pushes = [(0.0, "staged", _stage(pick(lambda n, j: n < 10 and j < 3),
                                     it).view())]
    tail = (lambda n: n < 5 or n == 7) if beside == "columns" else (
        lambda n: n < 5)
    pushes.append((10.0, "staged", _stage(pick(
        lambda n, j: (tail(n) and j >= 3) or 10 <= n < 15), it).view()))
    if beside == "dicts":
        pushes.insert(1, (0.0, "dicts", bs.spans_by_trace(
            pick(lambda n, j: n >= 15))))
        pushes.append((10.0, "dicts", bs.spans_by_trace(
            pick(lambda n, j: n == 7 and j >= 3))))
    clock = [0.0]
    ref = _instance(tmp_path, "ref", now=lambda: clock[0])
    col = _instance(tmp_path, "col", now=lambda: clock[0])
    for t, kind, x in pushes:
        clock[0] = t
        _push_dicts(ref.push_trace, kind, x)
        _push_columns(col, kind, x)
    clock[0] = 12.0
    sizes = []
    for cut in ({"idle_s": 5.0}, {"immediate": True}):
        want, got = ref.live.cut(**cut), col.live.cut(**cut)
        assert [lt.trace_id for lt in got] == [lt.trace_id for lt in want]
        _assert_tables_equal(cut_table(got), cut_table(want))
        col.live.forget(got)
        col.live.drop_chunks(got.spent_chunks())
        sizes.append((len(got), len(col.live.chunks)))
    # the first chunk outlives the idle cut (traces 0-4 and 7 hold it)
    assert sizes == ([(4, 2), (11, 0)] if beside == "columns"
                     else [(9, 2), (11, 0)])
    assert len(col.live) == 0 and len(col.live.index) == 0


# -- (b) one trace fed by both kinds ------------------------------------------

def test_trace_fed_by_dicts_and_columns_cuts_as_two_dict_pushes(tmp_path):
    it = StringInterner()
    spans = _k6_spans(20, groups=1, per=10)
    first, second = spans[0::2], spans[1::2]
    view = _stage(second, it).view()
    want = _reference_table([("dicts", bs.spans_by_trace(first)),
                             ("staged", view)])
    inst = _instance(tmp_path, "mixed")
    for tid, group in bs.spans_by_trace(first):
        assert inst.push_trace(tid, group) is None
    assert inst.push_columns(ColumnSource(view.staged.batch()[0], view.staged),
                             view.row_indices()) == {}
    kinds = {type(seg).__name__ for lt in inst.live.dict_traces.values()
             for seg in lt.segments}
    assert kinds == {"list", "ColumnSegment"}
    assert not inst.live.chunks
    _assert_tables_equal(cut_table(inst.live.cut(immediate=True)), want)


# -- (c) reads before the cut -------------------------------------------------

@pytest.mark.parametrize("held", ["live", "cutting"])
@pytest.mark.parametrize("case", ["k6", "rich", "repeated_span_id",
                                  "bare_batch", "dicts_beside_columns",
                                  "trace_over_chunks"])
def test_reads_before_the_cut_return_the_dict_routes_spans(case, held,
                                                          tmp_path):
    """Both reads see a chunk's traces while they are live and while a
    sweep holds them in `cutting`."""
    pushes = CASES[case](StringInterner())
    ref = _instance(tmp_path, "ref")
    col = _instance(tmp_path, "col")
    for kind, x in pushes:
        _push_dicts(ref.push_trace, kind, x)
        _push_columns(col, kind, x)
    want = ref.live.view().groups()
    assert col.live.view().groups() == want
    if held == "cutting":
        for inst in (ref, col):
            inst.cutting = inst.live.cut(immediate=True)
            assert len(inst.live) == 0 and len(inst.cutting) == len(want)
        assert col.cutting.groups() == want
    assert col.all_recent_traces() == ref.all_recent_traces()
    for tid, _ in want:
        assert col.find_trace_by_id(tid) == ref.find_trace_by_id(tid)
        assert col.find_trace_by_id(tid) is not None


# -- (d) the limits -----------------------------------------------------------

@pytest.mark.parametrize("case", ["k6", "rich", "sampled_view",
                                  "bare_batch", "three_pushes",
                                  "mixed_sizes"])
@pytest.mark.parametrize("limit", ["trace_too_large", "live_traces_exceeded",
                                   "both", "none"])
def test_limits_fire_on_the_same_pushes(case, limit, tmp_path):
    lim = {"trace_too_large": Limits(read=ReadLimits(max_bytes_per_trace=1100)),
           "live_traces_exceeded":
               Limits(ingestion=IngestionLimits(max_traces_per_user=3)),
           "both": Limits(ingestion=IngestionLimits(max_traces_per_user=5),
                          read=ReadLimits(max_bytes_per_trace=1100)),
           "none": Limits(ingestion=IngestionLimits(max_traces_per_user=0),
                          read=ReadLimits(max_bytes_per_trace=0))}[limit]
    pushes = CASES[case](StringInterner())
    ref = _instance(tmp_path, "ref", limits=lim)
    col = _instance(tmp_path, "col", limits=lim)
    answers = []
    for kind, x in pushes:
        want = _push_dicts(ref.push_trace, kind, x)
        assert _push_columns(col, kind, x) == want
        assert col.live.total_bytes == ref.live.total_bytes
        answers.append(want)
    assert col.discarded == ref.discarded
    assert col.live.pushes_rejected == ref.live.pushes_rejected
    if limit != "none" and case in ("k6", "three_pushes", "mixed_sizes"):
        assert sum(ref.discarded.values()) > 0
    if limit != "none" and case == "mixed_sizes":
        # one push with traces refused beside traces admitted
        assert any(None in w and set(w) - {None} for w in answers)
    if limit == "both" and case == "mixed_sizes":
        assert set(ref.discarded) == {"trace_too_large",
                                      "live_traces_exceeded"}
    held = [lt.trace_id for lt in ref.live.view()]
    assert [lt.trace_id for lt in col.live.view()] == held
    assert ([col.live.bytes_of(t) for t in held]
            == [ref.live.bytes_of(t) for t in held])
    _assert_tables_equal(cut_table(col.live.cut(immediate=True)),
                         cut_table(ref.live.cut(immediate=True)))
    assert col.live.total_bytes == ref.live.total_bytes == 0


def test_a_repeated_attr_key_counts_each_time_it_stands(tmp_path):
    """The one place the two routes' bytes differ: the columns are counted
    in one pass over the key slots, a dict keeps a repeated key once."""
    pushes = _case_repeated_attr_key(StringInterner())
    ref = _instance(tmp_path, "ref")
    col = _instance(tmp_path, "col")
    for kind, x in pushes:
        assert _push_columns(col, kind, x) == _push_dicts(
            ref.push_trace, kind, x) == [None]
    # `k` stands three times in one span, is kept once: 2 x 32 bytes over
    assert col.live.total_bytes == ref.live.total_bytes + 64


# -- (e) pushes against sweeps ------------------------------------------------

@pytest.mark.parametrize("ids", ["own", "shared"])
def test_threads_pushing_while_sweeps_run_lose_and_repeat_nothing(
        ids, tmp_path):
    """Four threads push while a fifth sweeps, the interpreter handed over
    every microsecond. With `shared` ids all four push to the same traces
    (each its own span ids), so a trace is cut while another thread pushes
    to it, and its id is pushed again after the cut while the index still
    names the slot the cut took."""
    it = StringInterner()
    inst = _instance(tmp_path, "race", trace_idle_s=0.0, trace_live_s=0.0)
    acked: list[set] = [set() for _ in range(4)]
    stop = threading.Event()

    def pusher(k: int) -> None:
        for r in range(6):
            spans = _k6_spans(100 + (0 if ids == "shared" else 10 * k) + r,
                              groups=2, per=50)
            if ids == "shared":
                spans = [dict(s, span_id=bytes([k]) + s["span_id"][1:])
                         for s in spans]
            staged = _stage(spans, it)
            assert inst.push_columns(
                ColumnSource(staged.batch()[0], staged),
                staged.view().row_indices()) == {}
            acked[k].update((s["trace_id"], s["span_id"]) for s in spans)

    def sweeper() -> None:
        while not stop.is_set():
            inst.cut_complete_traces(immediate=True)

    threads = [threading.Thread(target=pusher, args=(k,)) for k in range(4)]
    sweep = threading.Thread(target=sweeper)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        sweep.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        stop.set()
        sweep.join(60)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
    assert not sweep.is_alive() and not any(t.is_alive() for t in threads)
    want = set().union(*acked)
    assert len(want) == 4 * 6 * 100
    seen = [(s["trace_id"], s["span_id"]) for s in inst.head.iter_spans()]
    seen += [(s["trace_id"], s["span_id"])
             for lt in inst.live.view() for s in lt.spans]
    assert len(seen) == len(want) and set(seen) == want
    assert len(inst.head.segments()) >= 1


# -- (f) the segment a columnar cut wrote, replayed and completed --------------

def test_columnar_segment_is_rescanned_and_completes_to_the_same_block(tmp_path):
    import pyarrow.parquet as pq

    it = StringInterner()
    pushes = (_case_k6(it) + _case_rich(it) + _case_repeated_span_id(it))
    ded = (DedicatedColumn("span", "k6.vu"),
           DedicatedColumn("resource", "service.name"))
    ref = _instance(tmp_path, "ref", dedicated_columns=ded)
    col = _instance(tmp_path, "col", dedicated_columns=ded)
    for kind, x in pushes:
        _push_dicts(ref.push_trace, kind, x)
        _push_columns(col, kind, x)
    tables = {}
    for name, inst in (("ref", ref), ("col", col)):
        assert inst.cut_complete_traces(immediate=True) > 0
        assert inst.head.segments() == ["0000000.parquet"]
        found = [wb for wb in rescan_blocks(inst.wal_dir)]
        assert [wb.block_id for wb in found] == [inst.head.block_id]
        assert found[0].segments() == ["0000000.parquet"]
        sealed = inst.cut_block_if_ready(immediate=True)
        wal_spans = sealed.complete()
        meta = inst.complete_block(sealed)
        block = inst.complete[meta.block_id].block
        tables[name] = (wal_spans, meta.total_spans, meta.total_objects,
                        block.parquet_file().read())
    assert tables["col"][0] == tables["ref"][0]
    assert tables["col"][1:3] == tables["ref"][1:3]
    _assert_tables_equal(tables["col"][3], tables["ref"][3])
    assert tables["col"][3].column("ded_s_00").null_count < tables["col"][1]
    del pq


@pytest.mark.parametrize("case", ["rich", "three_pushes", "repeated_span_id"])
def test_a_wal_block_finds_a_trace_as_its_spans_read_back_whole(case,
                                                                tmp_path):
    """`WALBlock.find_trace_by_id` matches the trace-id column first: the
    same spans, in the same order, as filtering every span of every
    segment (ids shorter than 16 bytes included)."""
    inst = _instance(tmp_path, "w")
    for kind, x in CASES[case](StringInterner()):
        _push_columns(inst, kind, x)
        assert inst.cut_complete_traces(immediate=True) > 0
    spans = list(inst.head.iter_spans())
    assert len(inst.head.segments()) == len(CASES[case](StringInterner()))
    for tid in dict.fromkeys(s["trace_id"] for s in spans):
        for asked in (tid, tid.rstrip(b"\0")):
            assert inst.head.find_trace_by_id(asked) == [
                s for s in spans if s["trace_id"] == tid]
    assert inst.head.find_trace_by_id(_id(0xFFFF, 16)) is None


# -- the staged routes make no span dict; the counter says which form ----------

def _live_spans() -> dict[str, float]:
    return {form: LIVE_SPANS.value((form,)) for form in ("columns", "dicts")}


def _cut_spans() -> dict[str, float]:
    return {route: CUT_SPANS.value((route,))
            for route in ("columns", "dicts")}


def test_staged_push_and_localblocks_make_no_span_dicts(tmp_path, monkeypatch):
    from tempo_tpu.generator.processors.localblocks import (
        LocalBlocksConfig, LocalBlocksProcessor)
    from tempo_tpu.ingester.ingester import Ingester

    calls = {"view": 0, "batch": 0, "attrs": 0}
    for cls, key, name in ((StagedView, "view", "to_span_dicts"),
                           (SpanBatch, "batch", "to_span_dicts"),
                           (SpanBatch, "attrs", "_decode_attrs")):
        real = getattr(cls, name)

        def counted(self, *a, _real=real, _key=key, **kw):
            calls[_key] += 1
            return _real(self, *a, **kw)
        monkeypatch.setattr(cls, name, counted)

    it = StringInterner()
    spans = _k6_spans(30)
    staged = _stage(spans, it)
    ing = Ingester(str(tmp_path / "ing"))
    lb = LocalBlocksProcessor("t", LocalBlocksConfig(
        data_dir=str(tmp_path / "lb")))
    before = _live_spans()
    assert ing.push_staged("t", staged.view()) == {}
    lb.push_batch(staged.batch()[0])
    assert calls == {"view": 0, "batch": 0, "attrs": 0}
    after = _live_spans()
    assert after["columns"] - before["columns"] == 2 * len(spans)
    assert after["dicts"] == before["dicts"]
    # a read is what makes dicts, and gets the trace whole
    tid = spans[0]["trace_id"]
    got = lb.inst.find_trace_by_id(tid)
    assert len(got) == 5 and (calls["view"], calls["batch"]) == (0, 1)
    assert len(ing.instance("t").find_trace_by_id(tid)) == 5
    assert (calls["view"], calls["batch"]) == (1, 1)
    # the cuts make none either, and the traces read back from the WAL
    calls.update(view=0, batch=0, attrs=0)
    cut_before = _cut_spans()
    ing.sweep_instance("t", immediate=True)
    lb.cut_tick(immediate=False)
    lb.inst.cut_complete_traces(immediate=True)
    assert calls == {"view": 0, "batch": 0, "attrs": 0}
    assert len(ing.instance("t").live) == len(lb.inst.live) == 0
    assert not ing.instance("t").live.chunks and not lb.inst.live.chunks
    assert _cut_spans()["columns"] - cut_before["columns"] == 2 * len(spans)
    assert ([s["span_id"] for s in lb.inst.find_trace_by_id(tid)]
            == [s["span_id"] for s in got])
    # the dict route counts itself
    assert ing.push("t", bs.spans_by_trace(spans[:10])) == [None, None]
    assert _live_spans()["dicts"] - before["dicts"] == 10


@pytest.mark.parametrize("traces", [1, 7, 200])
def test_a_store_holds_a_chunk_a_push_and_no_live_trace_object(
        traces, tmp_path, monkeypatch):
    made = []
    real = livetraces.LiveTrace.__init__

    def counted(self, *a, **kw):
        made.append(1)
        real(self, *a, **kw)
    monkeypatch.setattr(livetraces.LiveTrace, "__init__", counted)
    it = StringInterner()
    inst = _instance(tmp_path, "n")
    pushes = 6
    for k in range(pushes):
        staged = _stage(_k6_spans(60 + k, groups=1, per=5 * traces), it)
        assert inst.push_columns(ColumnSource(staged.batch()[0], staged),
                                 None) == {}
    assert len(inst.live.chunks) == pushes and not inst.live.dict_traces
    assert len(inst.live) == pushes * traces and not made
    assert inst.cut_complete_traces(immediate=True) == pushes * traces
    assert not inst.live.chunks and not made and len(inst.live.index) == 0


@pytest.mark.parametrize("beside", ["nothing", "columns"])
def test_a_dict_push_asks_the_index_only_beside_column_traces(
        beside, tmp_path, monkeypatch):
    """The dict routes (Jaeger, Zipkin, gRPC, replay, blockbuilder) keep
    their traces in a dict by id: with no live column trace a push never
    asks the index; beside column traces it asks once a trace."""
    asked = []
    real = livetraces.TraceIndex.lookup

    def lookup(self, keys):
        asked.append(len(keys))
        return real(self, keys)
    monkeypatch.setattr(livetraces.TraceIndex, "lookup", lookup)
    inst = _instance(tmp_path, "d")
    if beside == "columns":
        staged = _stage(_k6_spans(70, groups=1, per=25), StringInterner())
        assert inst.push_columns(ColumnSource(staged.batch()[0], staged),
                                 None) == {}
        asked.clear()
    groups = bs.spans_by_trace(_k6_spans(71, groups=2, per=50))
    assert [inst.push_trace(t, s) for t, s in groups] == [None] * 20
    assert asked == ([] if beside == "nothing" else [1] * 20)
    columns = 5 if beside == "columns" else 0
    assert len(inst.live) == 20 + columns
    assert len(inst.live.dict_traces) == 20
    assert len(inst.live.index) == columns


@pytest.mark.parametrize("spread", [1, 3])
def test_a_read_of_one_trace_gathers_only_the_chunks_it_spans(spread,
                                                              tmp_path):
    """`find_trace_by_id` takes the chunks from the trace's first to its
    last, not every chunk of the store: six pushes, the trace's spans in
    the second (and, with `spread` 3, the fourth)."""
    it = StringInterner()
    target = _k6_spans(80, groups=1, per=5)
    inst = _instance(tmp_path, "f")
    for k in range(6):
        spans = _k6_spans(81 + k, groups=1, per=10)
        if k == 1:
            spans += target[:3] if spread == 3 else target
        if k == 3 and spread == 3:
            spans += target[3:]
        staged = _stage(spans, it)
        assert inst.push_columns(ColumnSource(staged.batch()[0], staged),
                                 None) == {}
    tid = target[0]["trace_id"]
    assert len(inst.live.chunks) == 6
    assert len(inst.live.view(tid).chunks) == spread
    got = inst.find_trace_by_id(tid)
    assert sorted(s["span_id"] for s in got) == sorted(
        s["span_id"] for s in target)


@pytest.mark.parametrize("case", ["k6", "dicts_beside_columns",
                                  "claimed_trace_id", "two_interners"])
def test_cut_spans_count_every_cut_span_once_by_route(case, tmp_path):
    """`tempo_ingester_cut_spans_total{route}`: spans taken from chunks
    count as columns, the rest (dict pushes, and the columns of a trace a
    dict span claims or staged against a second interner) as dicts."""
    pushes = CASES[case](StringInterner())
    inst = _instance(tmp_path, "c")
    for kind, x in pushes:
        _push_columns(inst, kind, x)
    staged = sum(x.n for kind, x in pushes if kind == "staged")
    total = staged + sum(len(spans) for kind, x in pushes if kind == "dicts"
                         for _, spans in x)
    demoted = {"claimed_trace_id": 5, "two_interners": 20}.get(case, 0)
    before = _cut_spans()
    assert inst.cut_complete_traces(immediate=True) > 0
    after = _cut_spans()
    assert after["columns"] - before["columns"] == staged - demoted
    assert after["dicts"] - before["dicts"] == total - staged + demoted
    # nothing left, nothing counted twice
    assert inst.cut_complete_traces(immediate=True) == 0
    assert _cut_spans() == after


def test_staging_without_span_attrs_is_refused_as_before(tmp_path):
    from tempo_tpu.ingester.ingester import Ingester

    it = StringInterner()
    staged = stage_otlp(encode_spans_otlp(_k6_spans(31, groups=1, per=5)), it,
                        include_span_attrs=False)
    if staged is None:
        pytest.skip("native staging unavailable")
    with pytest.raises(ValueError, match="without span attrs"):
        Ingester(str(tmp_path / "ing")).push_staged("t", staged.view())


# -- recorded, not repaired ---------------------------------------------------

def test_int_attr_above_2_24_reaches_the_wal_rounded_on_both_staged_routes(
        tmp_path):
    """A defect of the parent this PR keeps (PERF.md §7): on the staged
    route an int attribute rides the batch's f32 `fval` column
    (`_batch_from_staged._scalar_fvals`, read back by `int(fvals[j])` in
    `SpanBatch._decode_attrs`), so an int above 2^24 is rounded before it
    reaches the WAL. The columnar cut stores the same rounded value the
    dict route stored; the repair (an int64 column in the batch) changes
    what is stored and is its own issue. When it lands this test's last
    assertion flips."""
    it = StringInterner()
    span = dict(_k6_spans(40, groups=1, per=1)[0],
                attrs={"bytes.sent": 2**24 + 1, "small": 12345})
    view = _stage([span], it).view()
    want = _reference_table([("staged", view)])
    got = _column_table(tmp_path, [("staged", view)])
    _assert_tables_equal(got, want)
    assert got.column("sattr_int_keys").to_pylist() == [["bytes.sent", "small"]]
    stored = got.column("sattr_int_vals").to_pylist()[0]
    assert stored[1] == 12345
    assert stored[0] == 2**24 and stored[0] != span["attrs"]["bytes.sent"]
