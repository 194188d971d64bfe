"""A staged push's padded SpanBatch and its trace order, built by one
native pass (`model.otlp_batch._derive_staged`), against what the numpy
route (`_batch_from_staged`) and each store's own grouping
(`ColumnSource.chunk`) make of the same push: column for column, chunk for
chunk, and the cut's WAL table. The inputs that need Python take the numpy
route; every batch that is not the staged batch itself, whole, groups
itself, and `tempo_ingester_chunk_spans_total{grouping}` says which did."""

from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np
import pytest

from chipbench import loadgen_hotrod, spans as k6
from tempo_tpu import native
from tempo_tpu.block.live_columns import ColumnSource, cut_table
from tempo_tpu.ingester.instance import InstanceConfig, TenantInstance
from tempo_tpu.model import proto_wire as pw
from tempo_tpu.model.interner import StringInterner
from tempo_tpu.model.otlp import encode_spans_otlp
from tempo_tpu.model.otlp_batch import (
    _MAX_RES_ATTRS, _MAX_SPAN_ATTRS, _batch_from_staged, batch_from_otlp,
    stage_otlp)
from tempo_tpu.model.span_batch import SpanBatch
from tempo_tpu.utils.livetraces import CHUNK_SPANS

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483659            # beyond 32 signed bits, as the benchmark's seeds
T0 = 1_700_000_000_000_000_000
K6_SHAPE = k6.PushShape(8, 125, 5)
K6_SCHEMA = {"services": 8, "names": 4, "vus": 4, "end_jitter_ns": 10**9}


def _id(n: int, width: int) -> bytes:
    return n.to_bytes(width, "big")


def _span(t: int, s: int, tid_width: int = 16, **kw) -> dict:
    out = {"trace_id": _id(t + 1, tid_width), "span_id": _id(s + 1, 8),
           "parent_span_id": _id(s, 8) if s % 3 else b"",
           "name": f"op-{s % 5}", "service": f"svc-{t % 3}",
           "kind": s % 4, "status_code": 2 * (s % 7 == 0),
           "start_unix_nano": T0 + 10 * s, "end_unix_nano": T0 + 10 * s + 7,
           "attrs": {"k": f"v{s % 4}", "n": s, "ok": bool(s % 2),
                     "r": s / 4},
           "res_attrs": {"service.name": f"svc-{t % 3}", "host": "h"}}
    out.update(kw)
    return out


# -- the payloads --------------------------------------------------------------

def _k6(idx: int) -> bytes:
    return k6.encode_push(K6_SHAPE, k6.draw_push(
        SEED, 0, idx, K6_SHAPE, K6_SCHEMA, T0 + idx))


def _hotrod(chunk: int) -> bytes:
    with open(os.path.join(REPO, "chipbench", "configs",
                           "hotrod-otel-sdk.json")) as f:
        h = loadgen_hotrod.Hotrod(json.load(f)["schema"])
    got = loadgen_hotrod.draw_chunk(h, SEED, 0, chunk, 12, T0)
    return loadgen_hotrod.encode(h, [
        (s, loadgen_hotrod.service_rows(h, got, s))
        for s in range(len(h.services))])


def _resource_only() -> bytes:
    res = pw.enc_field_msg(1, pw.enc_field_msg(
        1, pw.enc_field_str(1, "service.name")
        + pw.enc_field_msg(2, pw.enc_field_str(1, "idle"))))
    return pw.enc_field_msg(1, res)


def _short_ids() -> bytes:
    # 7- and 16-byte ids of the same leading bytes, a 3-byte id, an empty one
    return encode_spans_otlp(
        [_span(1, s, 7) for s in range(4)] + [_span(1, s, 16) for s in (4, 5)]
        + [_span(9, 6, 3), _span(1, 7, 7)]
        + [dict(_span(2, 8), trace_id=b"")])


def _wide() -> bytes:
    # more attributes than either matrix keeps, on some spans only
    wide = {f"a{i:03d}": i for i in range(_MAX_SPAN_ATTRS + 6)}
    res = {f"r{i:03d}": f"x{i}" for i in range(_MAX_RES_ATTRS + 3)}
    return encode_spans_otlp(
        [_span(0, 0, attrs=wide), _span(0, 1), _span(1, 2, attrs={})]
        + [_span(2, 3, res_attrs=dict(res, **{"service.name": "wide"}))])


def _big_int() -> bytes:
    return encode_spans_otlp([_span(0, s, attrs={
        "big": 2**24 + 1, "huge": 2**62 + 2**38 + 1, "neg": -(2**40) - 3},
        res_attrs={"service.name": "s", "cpus": 2**25 + 1})
        for s in range(3)])


def _nonscalar() -> bytes:
    return encode_spans_otlp([_span(0, 0, attrs={"tags": ["x", 1],
                                                  "kv": {"a": 1}}),
                              _span(0, 1)])


def _nonscalar_resource() -> bytes:
    return encode_spans_otlp([_span(0, 0, res_attrs={
        "service.name": "s", "zones": ["a", "b"]})])


def _int_service() -> bytes:
    return encode_spans_otlp([_span(0, 0, res_attrs={"service.name": 42}),
                              _span(1, 1)])


def _many_traces() -> bytes:
    # more traces than the smallest hash table, ids that differ in one byte
    return encode_spans_otlp([_span(t, s) for t in range(150)
                              for s in range(t % 3 + 1)])


# name: (payload, include_span_attrs, include_res_attrs, native route)
CASES = {
    "k6": (lambda: _k6(0), True, True, True),
    "k6_later_push": (lambda: _k6(7), True, True, True),
    "k6_no_span_attrs": (lambda: _k6(1), False, True, True),
    "k6_no_res_attrs": (lambda: _k6(2), True, False, True),
    "hotrod": (lambda: _hotrod(0), True, True, True),
    "hotrod_later_chunk": (lambda: _hotrod(3), True, True, True),
    "empty": (lambda: b"", True, True, True),
    "resource_without_spans": (_resource_only, True, True, True),
    "short_ids": (_short_ids, True, True, True),
    "wide": (_wide, True, True, True),
    "big_int": (_big_int, True, True, True),
    "many_traces": (_many_traces, True, True, True),
    "nonscalar": (_nonscalar, True, True, False),
    "nonscalar_resource": (_nonscalar_resource, True, True, False),
    "int_service": (_int_service, True, True, False),
}


def _staged(case: str, interner: "StringInterner | None" = None):
    make, span_attrs, res_attrs, _ = CASES[case]
    raw = make()
    st = stage_otlp(raw, interner or StringInterner(),
                    include_span_attrs=span_attrs,
                    include_res_attrs=res_attrs)
    assert st is not None
    return raw, st


def _assert_batches_equal(got, want) -> None:
    sb, sizes = got
    ref, ref_sizes = want
    for f in dataclasses.fields(SpanBatch):
        a, b = getattr(sb, f.name), getattr(ref, f.name)
        if isinstance(b, np.ndarray):
            assert (a.dtype, a.shape) == (b.dtype, b.shape), f.name
            assert np.array_equal(a, b), f.name
            assert a.flags.c_contiguous, f.name
        else:
            assert a is b or a == b, f.name
    assert sizes.dtype == ref_sizes.dtype
    assert np.array_equal(sizes, ref_sizes)


# -- the batch -----------------------------------------------------------------

@pytest.mark.parametrize("case", list(CASES))
def test_the_native_batch_equals_the_numpy_route(case):
    raw, st = _staged(case)
    got = st.batch()
    want = _batch_from_staged(raw, st.interner,
                              (st.spans, st.sattrs, st.rattrs, st.res),
                              return_sizes=True,
                              include_span_attrs=st.has_span_attrs,
                              include_res_attrs=st.include_res_attrs)
    _assert_batches_equal(got, want)
    assert (got[0].trace_order is not None) == CASES[case][3]
    assert st.batch()[0] is got[0]


@pytest.mark.parametrize("case", ["k6", "hotrod", "short_ids", "nonscalar"])
def test_the_staged_records_and_their_wal_record_stay_as_they_were(case):
    from tempo_tpu.generator.wal import view_record

    _, st = _staged(case)
    arrays = (st.spans, st.sattrs, st.rattrs, st.res)
    before = [a.tobytes() for a in arrays]
    meta, rec = view_record(st.view(), 1.0, "p")
    st.batch()
    assert [a.tobytes() for a in arrays] == before
    meta2, rec2 = view_record(st.view(), 1.0, "p")
    assert meta2 == meta and rec2.keys() == rec.keys()
    for k in rec:
        assert rec2[k].dtype == rec[k].dtype
        assert rec2[k].tobytes() == rec[k].tobytes(), k


@pytest.mark.parametrize("fault", ["span_owner", "span_order", "res_owner",
                                   "res_idx"])
def test_records_out_of_their_owners_are_refused_before_a_write(fault):
    _, st = _staged("k6")
    spans, sattrs, rattrs, res = (a.copy() for a in (st.spans, st.sattrs,
                                                     st.rattrs, st.res))
    if fault == "span_owner":
        sattrs["owner"][5] = st.n + 3
    elif fault == "span_order":
        sattrs["owner"][[0, -1]] = sattrs["owner"][[-1, 0]]
    elif fault == "res_owner":
        rattrs["owner"][0] = len(res)
    else:
        spans["res_idx"][7] = len(res)
    widths = native.stage_widths(sattrs, rattrs, res, st.n, -1, True)
    assert (widths is None) == (fault != "res_idx")
    # the builder checks for itself, whatever widths it is handed
    assert native.stage_derive(spans, sattrs, rattrs, res, 1024, 4, 4,
                               0) is None


def test_an_int_above_2_24_is_rounded_as_the_numpy_route_rounds_it():
    _, st = _staged("big_int")
    sb, _ = st.batch()
    assert sb.trace_order is not None
    key = sb.span_attr_key[0].tolist()
    fval = sb.span_attr_fval[0]
    it = st.interner

    def as_numpy(v: int) -> np.float32:      # `_scalar_fvals`: int64 -> f32
        return np.array([v], np.int64).astype(np.float32)[0]

    assert fval[key.index(it.get("big"))] == as_numpy(2**24 + 1) \
        == np.float32(2**24)
    # one rounding, not two through a double: 2**62 + 2**38 alone would tie
    assert fval[key.index(it.get("huge"))] == as_numpy(2**62 + 2**38 + 1) \
        == np.float32(2**62 + 2**39)
    assert fval[key.index(it.get("neg"))] == as_numpy(-(2**40) - 3)
    rkey = sb.res_attr_key[0].tolist()
    assert sb.res_attr_fval[0][rkey.index(it.get("cpus"))] \
        == as_numpy(2**25 + 1)


def test_the_order_of_the_staged_batch_is_the_distributors_grouping():
    for case in ("k6", "hotrod", "short_ids", "many_traces"):
        _, st = _staged(case)
        order = st.batch()[0].trace_order
        first, inverse = native.group_keys_strided(st.spans, None)
        assert np.array_equal(order.first, first), case
        assert np.array_equal(order.inverse, inverse), case


# -- the chunk and the cut -----------------------------------------------------

def _sources(st, scope: str) -> ColumnSource:
    sb = st.batch()[0]
    return ColumnSource(sb, st) if scope == "ingester" else ColumnSource(sb)


GROUPED = [c for c, v in CASES.items() if v[3] and c not in (
    "empty", "resource_without_spans")]


@pytest.mark.parametrize("scope", ["ingester", "localblocks"])
@pytest.mark.parametrize("case", GROUPED)
def test_a_chunk_of_the_shared_order_equals_the_stores_own(case, scope):
    _, st = _staged(case)
    src = _sources(st, scope)
    shared = src.chunk(None)
    own = src.chunk(np.arange(st.n))
    assert own.grouping == "own"
    same = st.batch()[0].trace_order.same_length
    assert shared.grouping == ("staged" if scope == "ingester" or same
                               else "own")
    assert same == (case != "short_ids")
    for f in ("rows", "keys", "spans", "sizes"):
        a, b = getattr(shared, f), getattr(own, f)
        assert a.dtype == b.dtype and np.array_equal(a, b), f


def _instance(tmp_path, name: str) -> TenantInstance:
    return TenantInstance("t", str(tmp_path / name / "wal"),
                          str(tmp_path / name / "blocks"),
                          cfg=InstanceConfig())


@pytest.mark.parametrize("scope", ["ingester", "localblocks"])
@pytest.mark.parametrize("case", ["k6", "hotrod", "short_ids", "wide",
                                  "big_int"])
def test_the_cut_of_the_shared_order_equals_the_stores_own(case, scope,
                                                          tmp_path):
    it = StringInterner()
    pushes = [_staged(case, it)[1], _staged("k6_later_push", it)[1]]
    shared, own = _instance(tmp_path, "shared"), _instance(tmp_path, "own")
    before = CHUNK_SPANS.value(("staged",))
    for st in pushes:
        shared.push_columns(_sources(st, scope), None)
        own.push_columns(_sources(st, scope), np.arange(st.n))
    took = CHUNK_SPANS.value(("staged",)) - before
    assert took == sum(st.n for st in pushes
                       if scope == "ingester"
                       or st.batch()[0].trace_order.same_length)
    got = cut_table(shared.live.cut(immediate=True))
    want = cut_table(own.live.cut(immediate=True))
    assert got.schema.equals(want.schema)
    for name in want.schema.names:
        assert got.column(name).equals(want.column(name)), name
    assert got.equals(want)


# -- who groups itself ---------------------------------------------------------

def _counts() -> dict[str, float]:
    return {g: CHUNK_SPANS.value((g,)) for g in ("staged", "own")}


def _grown(before: dict) -> dict:
    return {g: v - before[g] for g, v in _counts().items()}


def test_the_counter_counts_both_stores_and_every_other_batch_as_own(
        tmp_path):
    from tempo_tpu.generator.processors.localblocks import (
        LocalBlocksConfig, LocalBlocksProcessor)
    from tempo_tpu.ingester.ingester import Ingester

    ing = Ingester(str(tmp_path / "ing"))
    lb = LocalBlocksProcessor("t", LocalBlocksConfig(
        data_dir=str(tmp_path / "lb")))

    # the staged batch itself, whole: both stores take its order
    _, st = _staged("k6")
    before = _counts()
    assert ing.push_staged("t", st.view()) == {}
    lb.push_batch(st.batch()[0])
    assert _grown(before) == {"staged": 2 * st.n, "own": 0}

    # a partial view (replicas over several ingesters, a sampled push)
    _, st = _staged("k6_later_push")
    rows = np.arange(0, st.n, 2)
    before = _counts()
    ing.push_staged("t", st.view(rows))
    assert _grown(before) == {"staged": 0, "own": len(rows)}

    # the slack filter's copy of the batch, and a gather of its rows
    sb = st.batch()[0]
    valid = sb.valid.copy()
    valid[[3, 11]] = False
    before = _counts()
    lb.push_batch(dataclasses.replace(sb, valid=valid))
    lb.push_batch(dataclasses.replace(sb))
    lb.push_batch(sb.take_rows(np.arange(10)))
    assert _grown(before) == {"staged": 0, "own": st.n - 2 + st.n + 10}

    # ids of mixed length: the ingester keys by the staging's lengths, a
    # bare batch by 16 bytes, so local-blocks groups itself
    _, st = _staged("short_ids")
    before = _counts()
    ing.push_staged("t", st.view())
    lb.push_batch(st.batch()[0])
    assert _grown(before) == {"staged": st.n, "own": st.n}

    # a batch the dict routes or the numpy fallback built
    _, st = _staged("int_service")
    sb2 = batch_from_otlp(_k6(3), st.interner)
    before = _counts()
    ing.push_staged("t", st.view())
    lb.push_batch(sb2)
    assert _grown(before) == {"staged": 0, "own": st.n + sb2.n}


def test_a_served_push_groups_once_for_the_distributor_and_both_stores(
        tmp_path, monkeypatch):
    from tempo_tpu.app import App
    from tempo_tpu.app.config import Config

    cfg = Config(target="all")
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.generator.localblocks.data_dir = str(tmp_path / "lb")
    cfg.overrides_defaults.generator.processors = (
        "span-metrics", "local-blocks")
    app = App(cfg)
    strided = []
    real = native.group_keys_strided
    monkeypatch.setattr(native, "group_keys_strided",
                        lambda *a: strided.append(1) or real(*a))
    try:
        raw = k6.encode_push(K6_SHAPE, k6.draw_push(
            SEED, 0, 0, K6_SHAPE, K6_SCHEMA, time.time_ns()))
        before = _counts()
        assert app.distributor.push_otlp("t", raw) == {}
        assert _grown(before) == {"staged": 2 * K6_SHAPE.n, "own": 0}
        assert strided == []
        assert len(app.ingester.instance("t").live.chunks) == 1
    finally:
        app.shutdown()
