"""The PRODUCT read path through the device plane: parity + routing.

Round-3 verdict weak #1: `BlockScanPlane` was bench/test-only. These tests
pin the integration — `TempoDB.query_range` and `TempoDB.search` must take
the fused device path for supported shapes (asserted via routing counters,
guarding against silent permanent fallback) and must produce the same
results as the host engine (device_plane=False) for every aggregation
kind, including `quantile_over_time` (the north-star query) and exact
integer boundary compares (round-3 weak #5: float32-only device compares).
"""

from __future__ import annotations

import numpy as np
import pytest

from tempo_tpu.backend.mem import MemBackend
from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig
from tempo_tpu.traceql.engine_metrics import (QueryRangeRequest,
                                              SeriesCombiner, metrics_kind)

T0 = 1_700_000_000
# durations engineered to sit ON compare boundaries, including values not
# representable in float32 (2**24 + 1) — the exactness regression surface
_DUR_CYCLE_NS = [
    123_000_000,          # = 123ms exactly
    123_000_001,
    122_999_999,
    16_777_216,           # 2**24 ns (f32-exact)
    16_777_217,           # 2**24 + 1 ns (NOT f32-representable)
    16_777_215,
    50_000_000,
    1,
]


def _mk_db(be, device_plane: bool) -> TempoDB:
    return TempoDB(be, be, TempoDBConfig(device_plane=device_plane))


@pytest.fixture(scope="module")
def dbs():
    rng = np.random.default_rng(7)
    be = MemBackend()
    dev = _mk_db(be, True)
    host = _mk_db(be, False)
    traces = []
    for i in range(800):
        tid = rng.bytes(16)
        start = int((T0 + i * 0.5) * 1e9)
        traces.append((tid, [{
            "trace_id": tid, "span_id": rng.bytes(8),
            "name": f"op-{i % 5}", "service": f"svc-{i % 3}",
            "kind": int(i % 6), "status_code": int(i % 3),
            "start_unix_nano": start,
            "end_unix_nano": start + _DUR_CYCLE_NS[i % len(_DUR_CYCLE_NS)],
            "attrs": ({"http.status_code": 200 + (i % 300),
                       "region": f"r{i % 4}", "retries": i % 7}
                      if i % 3 != 2 else   # svc-2 spans carry NO retries:
                      {"http.status_code": 200 + (i % 300),   # the host
                       "region": f"r{i % 4}"}),  # engine still emits a
        # zero/inf series for that group — fused emission must agree
        }]))
    dev.write_block("t", traces, replication_factor=1)
    dev.poll_now()
    host.poll_now()
    return dev, host


def _series_map(series) -> dict:
    return {tuple(sorted((str(k), str(v)) for k, v in s.labels)):
            np.nan_to_num(np.asarray(s.samples, np.float64))
            for s in series}


QUERIES = [
    '{ } | rate() by (resource.service.name)',
    '{ } | count_over_time() by (name)',
    '{ duration > 123ms } | rate() by (name)',
    '{ duration >= 123ms } | rate()',
    '{ duration = 16777217ns } | count_over_time()',
    '{ duration > 16777216ns && duration < 17ms } | count_over_time()',
    '{ name = "op-3" && kind = server } | rate() by (resource.service.name)',
    '{ status = error } | count_over_time() by (name)',
    '{ } | quantile_over_time(duration, .5, .99) by (resource.service.name)',
    '{ duration > 1ms } | quantile_over_time(duration, .99) by (name)',
    '{ } | histogram_over_time(duration) by (resource.service.name)',
    '{ } | min_over_time(duration) by (name)',
    '{ } | max_over_time(duration) by (resource.service.name)',
    '{ } | sum_over_time(duration) by (name)',
    '{ } | avg_over_time(duration) by (resource.service.name)',
    # group-by on a generic span attribute (plane adopts the attr column)
    '{ } | rate() by (span.region)',
    '{ span.http.status_code >= 400 } | rate() by (name)',
    # value attribute missing on every svc-2 span: the group still gets a
    # zero/inf series on both paths (obs-count emission gate)
    '{ } | sum_over_time(span.retries) by (resource.service.name)',
    '{ } | avg_over_time(span.retries) by (resource.service.name)',
    '{ } | min_over_time(span.retries) by (resource.service.name)',
    '{ } | quantile_over_time(span.retries, .9) by (resource.service.name)',
    # ... and missing on EVERY matching span: the host evaluator mints
    # the series without ever dispatching, and must still answer zeros
    # (found by test_plane_fuzz's moments arm, seed 149256142)
    '{ resource.service.name = "svc-2" } | avg_over_time(span.retries)',
    '{ resource.service.name = "svc-2" } | sum_over_time(span.retries)'
    ' by (name)',
    # two-key group-by (the RED-dashboard shape) rides the fused plane
    '{ } | rate() by (resource.service.name, name)',
    '{ duration > 50ms } | quantile_over_time(duration, .9)'
    ' by (resource.service.name, name)',
    '{ } | avg_over_time(duration) by (name, span.region)',
    # unsupported shapes must still match via host fallback
    '{ name = "op-1" || duration > 400ms } | rate() by (name)',
    # NEQ with a non-integral literal on an int column is constant-true
    # for present values but must still exclude spans MISSING the attr
    # (advisor r4 medium: the ("const", True) plan dropped the exists
    # mask; svc-2 spans carry no retries)
    '{ span.retries != 1.5 } | rate() by (resource.service.name)',
    # boolean literal filters: `false` matches nothing, `x && false`
    # matches nothing, `true` matches all — the extractor must not treat
    # the dropped literal as absent on the fused path (advisor r4 low)
    '{ false } | rate() by (name)',
    '{ name = "op-1" && false } | count_over_time() by (name)',
    '{ true } | rate() by (name)',
]


@pytest.mark.parametrize("query", QUERIES)
def test_query_range_product_parity(dbs, query):
    dev, host = dbs
    req = QueryRangeRequest(query=query, start_ns=int(T0 * 1e9),
                            end_ns=int((T0 + 400) * 1e9),
                            step_ns=int(60e9))
    a = _series_map(dev.query_range("t", req))
    b = _series_map(host.query_range("t", req))
    assert set(a) == set(b), query
    for k in b:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-4,
                                   err_msg=f"{query} {k}")


def test_fused_path_actually_engages(dbs):
    """Supported shapes must route through the device grid (guard against
    silent permanent fallback)."""
    dev, _ = dbs
    before = dev.plane_stats["fused_metric_blocks"]
    req = QueryRangeRequest(
        query='{ } | quantile_over_time(duration, .99) by (resource.service.name)',
        start_ns=int(T0 * 1e9), end_ns=int((T0 + 400) * 1e9),
        step_ns=int(60e9))
    dev.query_range("t", req)
    assert dev.plane_stats["fused_metric_blocks"] > before


def test_quantile_final_pass_parity(dbs):
    """End-to-end north-star shape: job-level series from the fused path
    must combine into the same interpolated quantiles as the host engine
    (`Log2Quantile` engine_metrics.go:1402)."""
    dev, host = dbs
    q = '{ } | quantile_over_time(duration, .99) by (resource.service.name)'
    req = QueryRangeRequest(query=q, start_ns=int(T0 * 1e9),
                            end_ns=int((T0 + 400) * 1e9), step_ns=int(60e9))
    out = {}
    for db in (dev, host):
        comb = SeriesCombiner(metrics_kind(q), req.n_steps)
        comb.add_all(db.query_range("t", req))
        out[db] = _series_map(comb.final(req))
    assert set(out[dev]) == set(out[host])
    for k in out[host]:
        np.testing.assert_allclose(out[dev][k], out[host][k], rtol=1e-6,
                                   err_msg=str(k))


def test_search_product_parity(dbs):
    dev, host = dbs
    for q in ('{ duration > 123ms }',
              '{ duration = 16777217ns }',
              '{ name = "op-2" && duration >= 50ms }',
              '{ resource.service.name = "svc-1" }',
              '{ span.region = "r2" && status = error }'):
        a = dev.search("t", q, limit=1000)
        b = host.search("t", q, limit=1000)
        ids = lambda res: sorted(m.trace_id for m in res)
        assert ids(a) == ids(b), q


def test_search_time_window_parity(dbs):
    """Windowed search: device and host prefilters must clip identically
    (both clip on span start from the same FetchSpansRequest bounds —
    regression guard for the suspected start-vs-overlap divergence)."""
    dev, host = dbs
    for lo, hi in ((T0 + 50, T0 + 150), (T0, T0 + 10), (T0 + 390, T0 + 500)):
        for q in ('{ duration > 50ms }', '{ name = "op-1" }'):
            a = sorted(m.trace_id for m in dev.search(
                "t", q, limit=1000, start_s=lo, end_s=hi))
            b = sorted(m.trace_id for m in host.search(
                "t", q, limit=1000, start_s=lo, end_s=hi))
            assert a == b, (q, lo, hi)


def test_search_uses_device_first_pass(dbs):
    dev, _ = dbs
    meta = dev.blocklist.metas("t")[0]
    cb = dev.planes.get(dev.backend_block(meta))
    before = cb.device_scans
    dev.search("t", '{ duration > 123ms }', limit=10)
    assert cb.device_scans > before


def test_row_group_shards_sum_to_whole(dbs):
    """Frontend-style row-group sharded sub-requests must tensor-add to
    the unsharded answer on the fused path."""
    dev, _ = dbs
    q = '{ } | count_over_time() by (name)'
    req = QueryRangeRequest(query=q, start_ns=int(T0 * 1e9),
                            end_ns=int((T0 + 400) * 1e9), step_ns=int(60e9))
    meta = dev.blocklist.metas("t")[0]
    n_rg = dev.backend_block(meta).parquet_file().num_row_groups
    whole = _series_map(dev.query_range("t", req, metas=[meta]))
    comb = SeriesCombiner(metrics_kind(q), req.n_steps)
    for rg in range(n_rg):
        comb.add_all(dev.query_range("t", req, metas=[meta],
                                     row_groups=[rg]))
    sharded = _series_map(list(comb.series.values()))
    assert set(whole) == set(sharded)
    for k in whole:
        np.testing.assert_allclose(sharded[k], whole[k], rtol=1e-6)


def test_plane_cache_lru_budget():
    """Device-byte budget evicts least-recently-used planes."""
    from tempo_tpu.db.plane_cache import PlaneCache

    rng = np.random.default_rng(3)
    be = MemBackend()
    db = _mk_db(be, True)
    for b in range(3):
        traces = []
        for i in range(50):
            tid = rng.bytes(16)
            start = int((T0 + i) * 1e9)
            traces.append((tid, [{
                "trace_id": tid, "span_id": rng.bytes(8),
                "name": f"op-{i % 3}", "service": "svc",
                "kind": 2, "status_code": 0,
                "start_unix_nano": start,
                "end_unix_nano": start + 1_000_000}]))
        db.write_block("t", traces, replication_factor=1)
    db.poll_now()
    db.planes = PlaneCache(budget_bytes=1, max_blocks=64)  # starvation budget
    req = QueryRangeRequest(query='{ } | rate() by (name)',
                            start_ns=int(T0 * 1e9),
                            end_ns=int((T0 + 100) * 1e9), step_ns=int(50e9))
    db.query_range("t", req)
    stats = db.planes.stats()
    assert stats["entries"] == 1          # budget keeps only the last block
    assert stats["misses"] >= 3


def test_exemplars_present_on_fused_path(dbs):
    dev, _ = dbs
    req = QueryRangeRequest(query='{ } | rate() by (name)',
                            start_ns=int(T0 * 1e9),
                            end_ns=int((T0 + 400) * 1e9), step_ns=int(60e9))
    series = dev.query_range("t", req)
    assert any(s.exemplars for s in series)


def test_nil_predicates_on_plane_path(dbs):
    """nil comparisons ride the plane's existence-mask term (regression:
    the packed-literal refactor missed the nil/const tuple arity and
    raised IndexError instead of serving or falling back)."""
    dev, host = dbs
    for q in ('{ span.retries != nil }', '{ span.retries = nil }',
              '{ span.nothere = nil }'):
        a = sorted(m.trace_id for m in dev.search("t", q, limit=1000))
        b = sorted(m.trace_id for m in host.search("t", q, limit=1000))
        assert a == b, q
    req = QueryRangeRequest(query='{ span.retries != nil } | rate() by (name)',
                            start_ns=int(T0 * 1e9),
                            end_ns=int((T0 + 400) * 1e9), step_ns=int(60e9))
    a = _series_map(dev.query_range("t", req))
    b = _series_map(host.query_range("t", req))
    assert set(a) == set(b)
    for k in b:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5)


def test_many_blocks_bounded_grid_drain():
    """More fused blocks than the in-flight grid window (8): the drain
    path must still sum identically to the host engine."""
    rng = np.random.default_rng(11)
    be = MemBackend()
    dev = _mk_db(be, True)
    host = _mk_db(be, False)
    for b in range(12):
        traces = []
        for i in range(40):
            tid = rng.bytes(16)
            start = int((T0 + b * 40 + i) * 1e9)
            traces.append((tid, [{
                "trace_id": tid, "span_id": rng.bytes(8),
                "name": f"op-{i % 3}", "service": f"svc-{b % 2}",
                "kind": 2, "status_code": 0,
                "start_unix_nano": start,
                "end_unix_nano": start + 5_000_000}]))
        dev.write_block("t", traces, replication_factor=1)
    dev.poll_now(); host.poll_now()
    req = QueryRangeRequest(
        query='{ } | quantile_over_time(duration, .9) by (name)',
        start_ns=int(T0 * 1e9), end_ns=int((T0 + 500) * 1e9),
        step_ns=int(100e9))
    a = _series_map(dev.query_range("t", req))
    b2 = _series_map(host.query_range("t", req))
    assert dev.plane_stats["fused_metric_blocks"] >= 12
    assert set(a) == set(b2)
    for k in b2:
        np.testing.assert_allclose(a[k], b2[k], rtol=1e-5)


def test_step_boundary_exact_bucketing():
    """Spans landing just either side of a step boundary — hours from the
    block base, where float32 seconds carry ~0.5ms of error — must bucket
    identically on the fused and host planes (advisor r4 low: the f32
    `rel + frac` path put boundary spans into the adjacent bucket; the
    limb-exact path snaps the estimate to the true integer floor in BOTH
    directions). Offsets are ±300ns: large enough to survive the float64
    `__startTime` quantization (ulp = 256ns at epoch 1.7e18) that erases
    ±1ns before either plane sees it, small enough that f32 rounds them
    onto the boundary."""
    be = MemBackend()
    dev = _mk_db(be, True)
    host = _mk_db(be, False)
    rng = np.random.default_rng(5)
    base_ns = int(T0 * 1e9)
    step_ns = int(60e9)
    traces = []
    # an anchor span AT base keeps time_base_ns == base_ns
    for k in range(1, 200):
        for off in (-300, 0, 300):
            tid = rng.bytes(16)
            start = base_ns + k * step_ns + off
            traces.append((tid, [{
                "trace_id": tid, "span_id": rng.bytes(8),
                "name": f"op-{k % 3}", "service": "svc",
                "kind": 2, "status_code": 0,
                "start_unix_nano": start,
                "end_unix_nano": start + 1_000_000}]))
    traces.append((rng.bytes(16), [{
        "trace_id": rng.bytes(16), "span_id": rng.bytes(8),
        "name": "op-0", "service": "svc", "kind": 2, "status_code": 0,
        "start_unix_nano": base_ns, "end_unix_nano": base_ns + 1_000_000}]))
    dev.write_block("t", traces, replication_factor=1)
    dev.poll_now(); host.poll_now()
    req = QueryRangeRequest(
        query='{ } | count_over_time() by (name)',
        start_ns=base_ns, end_ns=base_ns + 200 * step_ns, step_ns=step_ns)
    a = _series_map(dev.query_range("t", req))
    b = _series_map(host.query_range("t", req))
    assert dev.plane_stats["fused_metric_blocks"] >= 1
    assert set(a) == set(b)
    for k in b:
        np.testing.assert_array_equal(a[k], b[k], err_msg=str(k))


def test_plane_upload_race_refunds_budget():
    """A racing duplicate LUT upload must keep one entry and refund the
    loser's device_bytes (advisor r4 low: both uploads were counted, one
    entry overwritten, the eviction budget permanently over-counted)."""
    dev, _ = _race_dbs()
    meta = dev.blocklist.metas("t")[0]
    plane = dev.planes.get(dev.backend_block(meta)).plane
    before = plane.device_bytes
    # simulate the race: insert the key mid-upload via a patched _up
    key = ("rglut", (0,))
    real_up = plane._up

    def racing_up(arr, is_span_dim=True):
        out = real_up(arr, is_span_dim)     # our upload (accounted)
        if key not in plane._cols:
            # rival's insert
            plane._cols[key] = real_up(np.asarray(arr), is_span_dim)
        return out

    plane._up = racing_up
    try:
        got = plane._ensure_rg_lut([0])
    finally:
        plane._up = real_up
    rival = plane._cols[key]
    assert got is rival                     # the first insert won
    # exactly ONE surviving entry is accounted for
    assert plane.device_bytes == before + int(np.zeros(
        len(plane.sizes), bool).nbytes)


def _race_dbs():
    rng = np.random.default_rng(13)
    be = MemBackend()
    dev = _mk_db(be, True)
    traces = []
    for i in range(20):
        tid = rng.bytes(16)
        start = int((T0 + i) * 1e9)
        traces.append((tid, [{
            "trace_id": tid, "span_id": rng.bytes(8),
            "name": f"op-{i % 3}", "service": "svc", "kind": 2,
            "status_code": 0, "start_unix_nano": start,
            "end_unix_nano": start + 1_000_000}]))
    dev.write_block("t", traces, replication_factor=1)
    dev.poll_now()
    # a first query adopts the columns so the plane is resident
    dev.search("t", '{ name = "op-1" }', limit=10)
    return dev, None


def test_float_attribute_columns_on_fused_path():
    """Float-valued attribute columns ride the fused plane via the
    order-preserving sortable-int64 encoding (round-4 weak #4: they used
    to refuse and silently lose the whole fused win). Device must match
    host bit-for-bit on boundary literals, and the routing counters must
    show FUSED service, not a predicate fallback."""
    rng = np.random.default_rng(21)
    be = MemBackend()
    dev = _mk_db(be, True)
    host = _mk_db(be, False)
    # values engineered onto compare boundaries incl. negatives, exact
    # halves, and f32-unrepresentable doubles; svc-1 spans carry NO ratio
    vals = [0.5, 1.5, -2.25, 0.1, 16777217.5, -0.0, 3.0, 1e300]
    traces = []
    for i in range(400):
        tid = rng.bytes(16)
        start = int((T0 + i) * 1e9)
        attrs = {"ratio": vals[i % len(vals)]} if i % 3 != 1 else {}
        traces.append((tid, [{
            "trace_id": tid, "span_id": rng.bytes(8),
            "name": f"op-{i % 3}", "service": f"svc-{i % 2}",
            "kind": 2, "status_code": 0,
            "start_unix_nano": start,
            "end_unix_nano": start + 2_000_000,
            "attrs": attrs}]))
    dev.write_block("t", traces, replication_factor=1)
    dev.poll_now(); host.poll_now()
    queries = [
        '{ span.ratio > 0.5 } | rate() by (name)',
        '{ span.ratio >= 1.5 } | count_over_time()',
        '{ span.ratio < 0 } | rate() by (name)',
        '{ span.ratio = -2.25 } | count_over_time()',
        '{ span.ratio = 0.0 } | rate()',          # matches -0.0 rows too
        '{ span.ratio != 0.1 } | rate() by (name)',   # exists-gated NEQ
        '{ span.ratio = 16777217.5 } | count_over_time()',
        '{ span.ratio > 2 } | rate()',            # int literal, float col
    ]
    for q in queries:
        req = QueryRangeRequest(query=q, start_ns=int(T0 * 1e9),
                                end_ns=int((T0 + 500) * 1e9),
                                step_ns=int(100e9))
        a = _series_map(dev.query_range("t", req))
        b = _series_map(host.query_range("t", req))
        assert set(a) == set(b), q
        for k in b:
            np.testing.assert_array_equal(a[k], b[k], err_msg=f"{q} {k}")
        sa = sorted(m.trace_id for m in dev.search("t", q.split("|")[0].strip(),
                                                   limit=1000))
        sb = sorted(m.trace_id for m in host.search("t", q.split("|")[0].strip(),
                                                    limit=1000))
        assert sa == sb, q
    # every query above must have taken the fused path
    assert dev.plane_stats["fused_metric_blocks"] >= len(queries)
    assert not any(k.startswith("fallback_") for k in dev.plane_stats), \
        dev.plane_stats


def test_fallback_cause_counters():
    """Host fallbacks carry a cause in plane_stats (round-4 weak #4) and
    surface as tempo_read_plane_fallback_total{cause=...}."""
    dev, _ = _race_dbs()
    req = QueryRangeRequest(
        query='{ kind = server && (name = "op-1" || name = "op-2") }'
              ' | rate() by (name)',
        start_ns=int(T0 * 1e9), end_ns=int((T0 + 100) * 1e9),
        step_ns=int(50e9))
    dev.query_range("t", req)   # mixed AND/OR → not fusable (query shape;
    #                             pure disjunctions fuse since round 5)
    assert dev.plane_stats.get("fallback_query_shape", 0) >= 1
    # NaN column values have no consistent order → predicate cause
    rng = np.random.default_rng(23)
    be2 = MemBackend()
    dev2 = _mk_db(be2, True)
    traces = []
    for i in range(20):
        tid = rng.bytes(16)
        start = int((T0 + i) * 1e9)
        traces.append((tid, [{
            "trace_id": tid, "span_id": rng.bytes(8),
            "name": "op", "service": "svc", "kind": 2, "status_code": 0,
            "start_unix_nano": start, "end_unix_nano": start + 1_000_000,
            "attrs": {"x": float("nan") if i % 2 else 1.5}}]))
    dev2.write_block("t", traces, replication_factor=1)
    dev2.poll_now()
    req2 = QueryRangeRequest(
        query='{ span.x > 1.0 } | rate() by (name)',
        start_ns=int(T0 * 1e9), end_ns=int((T0 + 100) * 1e9),
        step_ns=int(50e9))
    dev2.query_range("t", req2)
    assert dev2.plane_stats.get("fallback_predicate", 0) >= 1, \
        dev2.plane_stats


def test_pure_or_filters_fuse_exactly(dbs):
    """`{ a || b } | rate()` (pure disjunction of pushable compares) rides
    the fused plane — the OR of exact device terms is exact (round 5);
    mixed AND/OR trees still fall back to the host's exact second pass."""
    dev, host = dbs
    before = dev.plane_stats["fused_metric_blocks"]
    for q in ('{ name = "op-1" || duration > 400ms } | rate() by (name)',
              '{ name = "op-0" || name = "op-2" || kind = server }'
              ' | count_over_time() by (resource.service.name)',
              '{ span.retries > 4 || status = error }'
              ' | quantile_over_time(duration, .9) by (name)'):
        req = QueryRangeRequest(query=q, start_ns=int(T0 * 1e9),
                                end_ns=int((T0 + 400) * 1e9),
                                step_ns=int(60e9))
        a = _series_map(dev.query_range("t", req))
        b = _series_map(host.query_range("t", req))
        assert set(a) == set(b), q
        for k in b:
            np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-4,
                                       err_msg=f"{q} {k}")
    assert dev.plane_stats["fused_metric_blocks"] >= before + 3
    # mixed tree: NOT a pure disjunction → host fallback stays
    before_host = dev.plane_stats["host_metric_blocks"]
    req = QueryRangeRequest(
        query='{ kind = server && (name = "op-1" || name = "op-2") }'
              ' | rate() by (name)',
        start_ns=int(T0 * 1e9), end_ns=int((T0 + 400) * 1e9),
        step_ns=int(60e9))
    a = _series_map(dev.query_range("t", req))
    b = _series_map(host.query_range("t", req))
    assert set(a) == set(b)
    for k in b:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-4)
    assert dev.plane_stats["host_metric_blocks"] > before_host


def test_pure_disjunction_rejects_spoofed_shapes(dbs):
    """OR trees whose leaves are NOT single pushable compares must stay on
    the host's exact second pass — the round-5 review crafted shapes where
    a count heuristic certified a SUPERSET mask as exact (dedup'd AND arm,
    zero-push boolean literal). Parity + routing pinned here."""
    dev, host = dbs
    before_host = dev.plane_stats["host_metric_blocks"]
    for q in ('{ name = "op-1" || (name = "op-1" && kind = server) }'
              ' | rate() by (name)',
              '{ (name = "op-1" && false) || kind = server }'
              ' | rate() by (name)'):
        req = QueryRangeRequest(query=q, start_ns=int(T0 * 1e9),
                                end_ns=int((T0 + 400) * 1e9),
                                step_ns=int(60e9))
        a = _series_map(dev.query_range("t", req))
        b = _series_map(host.query_range("t", req))
        assert set(a) == set(b), q
        for k in b:
            np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-4,
                                       err_msg=f"{q} {k}")
    assert dev.plane_stats["host_metric_blocks"] >= before_host + 2
