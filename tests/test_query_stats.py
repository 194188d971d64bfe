"""Request-scoped query stats pipeline + structured query logging.

Covers: contextvar scope isolation under the frontend's thread-pool
fan-out, stats merge across ≥3 shard jobs, RPC round-trip of serialized
stats, wire-compat decode of old single-`inspected` search responses,
qlog capture rules, and the /api/search SearchMetrics surface.
"""

from __future__ import annotations

import json
import logging
import threading

import pytest

from tempo_tpu.backend.mem import MemBackend
from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig
from tempo_tpu.frontend import Frontend, FrontendConfig
from tempo_tpu.obs import querystats
from tempo_tpu.obs.qlog import LOGGER_NAME, LatencySketch, QueryLogger
from tempo_tpu.obs.querystats import QueryStats
from tempo_tpu.querier import Querier

T0 = 1_700_000_000.0


def mkspan(tid, sid, name="op", svc="svc", t0_s=T0, dur_ms=50, **kw):
    t0 = int(t0_s * 1e9)
    return {"trace_id": tid, "span_id": sid, "name": name, "service": svc,
            "start_unix_nano": t0, "end_unix_nano": t0 + int(dur_ms * 1e6),
            **kw}


@pytest.fixture
def stack():
    """Two backend blocks behind a frontend that shards 1 row group per
    job (≥ 3 shard jobs for any full-range search)."""
    clock = [T0 + 3600.0]
    now = lambda: clock[0]
    be = MemBackend()
    db = TempoDB(be, be, cfg=TempoDBConfig(row_group_rows=2))
    for blk in range(2):
        traces = []
        for i in range(1, 6):
            tid = bytes([blk * 16 + i]) * 16
            traces.append((tid, [mkspan(tid, bytes([i]) * 8,
                                        svc=f"svc-{blk}", t0_s=T0 + i)]))
        db.write_block("t1", traces, replication_factor=1)
    db.poll_now()
    q = Querier(db)
    fe = Frontend(db, q, cfg=FrontendConfig(
        target_bytes_per_job=1,       # one job per row group
        qlog_sample_every=1), now=now)
    yield clock, now, db, q, fe
    fe.shutdown()
    db.shutdown()


# -- scope mechanics ---------------------------------------------------------


def test_scope_isolation_across_threads():
    """Scopes are contextvar-local: recording on one thread never leaks
    into another thread's scope, and an unscoped thread records nothing."""
    results = {}
    barrier = threading.Barrier(2)

    def worker(name, n):
        with querystats.scope() as st:
            barrier.wait()
            for _ in range(n):
                querystats.add(inspected_spans=1)
            results[name] = st.inspected_spans

    ts = [threading.Thread(target=worker, args=("a", 3)),
          threading.Thread(target=worker, args=("b", 7))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert results == {"a": 3, "b": 7}
    assert querystats.current() is None
    querystats.add(inspected_spans=99)           # no scope: silent no-op


def test_nested_scope_and_ensure_scope():
    with querystats.scope() as outer:
        with querystats.scope() as inner:
            querystats.add(cache_hits=1)
        assert inner.cache_hits == 1 and outer.cache_hits == 0
        with querystats.ensure_scope() as joined:
            assert joined is outer               # reuses the active scope
        querystats.add(cache_hits=1)
        assert outer.cache_hits == 1


def test_stage_timer_and_merge():
    with querystats.scope() as st:
        with querystats.stage("engine_eval"):
            pass
        with querystats.stage("engine_eval"):
            pass
    assert st.stage_ns["engine_eval"] > 0
    child = QueryStats(inspected_bytes=10, blocks_scanned=2,
                       stage_ns={"engine_eval": 5, "block_fetch": 7})
    st.merge(child)
    assert st.inspected_bytes == 10 and st.blocks_scanned == 2
    assert st.stage_ns["block_fetch"] == 7
    st.merge(st)                                  # self-merge: no double
    assert st.inspected_bytes == 10
    st.merge(None)


# -- frontend fan-out merge --------------------------------------------------


def _run_search(fe, now, limit=50):
    with querystats.scope() as st:
        res = fe.search("t1", "{ }", limit=limit, start_s=0, end_s=now())
    return res, st


def test_sharded_search_merges_stats_inline(stack):
    clock, now, db, q, fe = stack
    res, st = _run_search(fe, now)
    assert len(res) == 10
    assert st.total_jobs >= 3                    # 1-byte/job sharding
    assert st.completed_jobs == st.total_jobs
    assert st.blocks_scanned >= st.total_jobs    # one block slice per job
    assert st.total_blocks == 2
    assert st.inspected_bytes > 0
    assert st.inspected_traces >= 10
    assert st.inspected_spans >= 10
    assert st.stage_ns.get("block_fetch", 0) > 0
    assert st.stage_ns.get("engine_eval", 0) > 0
    assert st.stage_ns.get("merge", 0) > 0


def test_sharded_search_merges_stats_worker_pool(stack):
    """Thread-pool fan-out: jobs execute on worker threads that cannot see
    the issuer's contextvar — per-job stats objects + fold-time merge must
    still produce identical totals, and queue-wait appears."""
    clock, now, db, q, fe = stack
    _, inline = _run_search(fe, now)
    fe.start_workers(3)
    res, st = _run_search(fe, now)
    assert len(res) == 10
    assert st.completed_jobs == inline.completed_jobs
    assert st.inspected_bytes == inline.inspected_bytes
    assert st.inspected_traces == inline.inspected_traces
    assert "queue_wait" in st.stage_ns


def test_cache_hits_counted(stack):
    from tempo_tpu.backend.cache import CacheProvider

    clock, now, db, q, fe0 = stack
    fe = Frontend(db, q, cfg=FrontendConfig(target_bytes_per_job=1),
                  cache_provider=CacheProvider(), now=now)
    _, first = _run_search(fe, now)
    assert first.cache_hits == 0
    _, second = _run_search(fe, now)
    assert second.cache_hits == second.completed_jobs > 0
    assert second.inspected_bytes == 0           # nothing rescanned
    fe.shutdown()


# -- RPC serialization -------------------------------------------------------


def _full_stats() -> QueryStats:
    st = QueryStats()
    st.add(inspected_traces=11, inspected_bytes=1 << 30, inspected_spans=13,
           total_blocks=4, blocks_scanned=3, blocks_skipped=1,
           total_jobs=6, completed_jobs=6, cache_hits=2,
           device_scan_bytes=1 << 20, kernel_wall_ns=12345)
    st.add_stage_ns("queue_wait", 42)
    st.add_stage_ns("engine_eval", 1_000_000)
    return st


def test_stats_json_roundtrip():
    st = _full_stats()
    got = QueryStats.from_json(json.loads(json.dumps(st.to_json())))
    assert got.to_json() == st.to_json()
    assert QueryStats.from_json(None).to_json() == {}


def test_stats_proto_roundtrip_in_search_response():
    from tempo_tpu.model import tempopb

    st = _full_stats()
    body = tempopb.enc_search_response([], final=True, stats=st)
    mds, final, inspected, got = tempopb.dec_search_response(body)
    assert final and inspected == 11
    assert got.to_json() == st.to_json()


def test_old_format_search_response_still_decodes():
    """Old encoders emit only the single `inspected` varint (field 1 of
    the metrics submessage); new decoders must accept it."""
    from tempo_tpu.model import tempopb

    old = tempopb.enc_search_response([], inspected=7, final=False)
    mds, final, inspected, st = tempopb.dec_search_response(old)
    assert not final and inspected == 7
    assert st.inspected_traces == 7
    assert st.inspected_bytes == 0 and st.stage_ns == {}


def test_new_format_readable_by_old_decoder():
    """A peer running the OLD decode (reads only field 1 of the metrics
    submessage) must still see the legacy `inspected` scalar in a
    stats-bearing response — the wire-compat contract both ways."""
    from tempo_tpu.model import proto_wire as pw
    from tempo_tpu.model import tempopb

    body = tempopb.enc_search_response([], final=True, stats=_full_stats())
    d = pw.decode_fields(body)
    metrics = pw.decode_fields(bytes(d[2][0]))
    assert metrics[1][0] == 11                   # old decoder's view


def test_remote_worker_result_message_carries_stats(stack):
    """The worker-stream result path: a serialized stats payload on the
    result message merges into the job's stats object (server-side
    read_results analog) and then into the parent at fold."""
    st = _full_stats()
    wire = json.dumps({"stats": st.to_json()})
    child = QueryStats.from_json(json.loads(wire)["stats"])
    with querystats.scope() as parent:
        querystats.absorb(child)
    assert parent.inspected_bytes == st.inspected_bytes
    assert parent.stage_ns["engine_eval"] == st.stage_ns["engine_eval"]


# -- structured query log ----------------------------------------------------


def test_latency_sketch_quantile():
    sk = LatencySketch()
    for _ in range(99):
        sk.record(0.010)
    sk.record(10.0)
    p95 = sk.quantile(0.95)
    assert 0.005 < p95 < 0.025                   # log2 bucket of 10ms
    assert sk.quantile(1.0) > 5.0
    assert LatencySketch().quantile(0.5) == 0.0


def test_qlog_errors_always_slow_over_threshold_rest_sampled():
    ql = QueryLogger(slow_quantile=0.9, sample_every=1000,
                     min_observations=10, rate_limit_per_s=1e9)
    # errors log regardless of sketch state or sampling
    rec = ql.log_query(op="search", tenant="t", query="{}", status="error",
                       duration_s=0.001, error="boom")
    assert rec is not None and rec["reason"] == "error"
    # warm the sketch with fast queries (first one is the 1-in-N sample)
    reasons = [r["reason"] for r in
               (ql.log_query(op="search", tenant="t", query="{}",
                             status="ok", duration_s=0.001)
                for _ in range(50)) if r is not None]
    assert reasons.count("sampled") == 1
    # now a 100x outlier crosses the sketch-estimated p90
    rec = ql.log_query(op="search", tenant="t", query="{}", status="ok",
                       duration_s=0.1)
    assert rec is not None and rec["reason"] == "slow"
    assert ql.threshold("search") > 0
    assert ql.suppressed > 0
    reasons = dict(ql.emitted_by_reason())
    assert reasons[("error",)] == 1 and reasons[("slow",)] == 1


def test_qlog_rate_limit_spares_errors():
    t = [0.0]
    ql = QueryLogger(sample_every=1, min_observations=10**9,
                     rate_limit_per_s=0.0, burst=2, now=lambda: t[0])
    oks = [ql.log_query(op="s", tenant="t", query="{}", status="ok",
                        duration_s=0.01) for _ in range(5)]
    assert sum(r is not None for r in oks) == 2  # burst exhausted
    rec = ql.log_query(op="s", tenant="t", query="{}", status="error",
                       duration_s=0.01, error="x")
    assert rec is not None                       # errors bypass the bucket


def test_qlog_record_is_one_parseable_json_line(caplog):
    ql = QueryLogger(sample_every=1, rate_limit_per_s=1e9)
    with caplog.at_level(logging.INFO, logger=LOGGER_NAME):
        ql.log_query(op="search", tenant='te"nant', query='{ x = "y" }',
                     status="ok", duration_s=0.25, stats=_full_stats(),
                     trace_id="ab" * 16)
    lines = [r.getMessage() for r in caplog.records
             if r.name == LOGGER_NAME]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["msg"] == "query complete"
    assert rec["tenant"] == 'te"nant'
    assert rec["durationMs"] == 250.0
    assert rec["traceId"] == "ab" * 16
    assert rec["inspectedBytes"] == 1 << 30
    assert rec["stageDurationNanos"]["engine_eval"] == 1_000_000


def test_frontend_emits_exactly_one_query_complete_line(stack, caplog):
    """Acceptance: a sharded search emits ONE parseable JSON line whose
    numbers match the request's merged stats, carrying the active
    SelfTracer trace id."""
    from tempo_tpu.utils import tracing

    clock, now, db, q, fe = stack
    tracer = tracing.SelfTracer("http://127.0.0.1:9", flush_interval_s=3600)
    tracing.install(tracer)
    try:
        with caplog.at_level(logging.INFO, logger=LOGGER_NAME):
            res, st = _run_search(fe, now)
        lines = [r.getMessage() for r in caplog.records
                 if r.name == LOGGER_NAME]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec["op"] == "search" and rec["status"] == "ok"
        sm = st.search_metrics()
        assert rec["completedJobs"] == sm["completedJobs"] >= 3
        assert rec["inspectedBytes"] == sm["inspectedBytes"] > 0
        assert rec["totalBlocks"] == sm["totalBlocks"] == 2
        assert isinstance(rec["traceId"], str) and len(rec["traceId"]) == 32
    finally:
        tracing.install(tracing.Tracer())
        tracer._stop.set()


def test_self_tracer_counts_failed_export_as_dropped():
    """Satellite bugfix: a failed export must not silently swallow the
    batch NOR drop it immediately — it is held for exactly ONE retry on
    the next flush tick (export_retries) before counting into `dropped_spans`."""
    from tempo_tpu.utils import tracing

    tracer = tracing.SelfTracer("http://127.0.0.1:9", flush_interval_s=3600)
    try:
        with tracer.span("doomed"):
            pass
        assert tracer.stats["dropped_spans"] == 0
        assert tracer.flush() == 0               # unreachable endpoint
        assert tracer.stats["dropped_spans"] == 0               # held, not yet lost
        assert tracer.stats["export_retries"] == 1
        assert tracer.flush() == 0               # bounded retry fails too
        assert tracer.stats["dropped_spans"] == 1               # NOW it's a counted loss
        assert tracer.exported == 0
    finally:
        tracer._stop.set()


def test_tenant_read_cost_counters(stack):
    clock, now, db, q, fe = stack
    _, st = _run_search(fe, now)
    fam = fe.obs.get("tempo_tpu_query_inspected_bytes_total")
    series = dict(fam.fn())
    assert series[("t1",)] == st.inspected_bytes > 0
    fam = fe.obs.get("tempo_tpu_query_blocks_scanned_total")
    assert dict(fam.fn())[("t1",)] == st.blocks_scanned


# -- HTTP surface ------------------------------------------------------------


def test_api_search_response_includes_merged_stats(tmp_path):
    """Acceptance: a sharded /api/search response carries the merged
    SearchMetrics (and /api/metrics/query_range carries its own)."""
    import socket
    import urllib.parse
    import urllib.request

    from tempo_tpu.app import App
    from tempo_tpu.app.api import serve
    from tempo_tpu.app.config import Config

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    cfg = Config(target="all")
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.generator.localblocks.data_dir = str(tmp_path / "lb")
    cfg.server.http_listen_port = port
    cfg.frontend.target_bytes_per_job = 1
    app = App(cfg)
    srv = serve(app, block=False)
    try:
        traces = []
        for i in range(1, 6):
            tid = bytes([i]) * 16
            traces.append((tid, [mkspan(tid, bytes([i]) * 8)]))
        app.db.write_block("single-tenant", traces, replication_factor=1)
        app.db.poll_now()
        url = (f"http://127.0.0.1:{port}/api/search?q=%7B%20%7D"
               f"&start=0&end={T0 + 60}&limit=50")
        body = json.loads(urllib.request.urlopen(url, timeout=10).read())
        m = body["metrics"]
        assert len(body["traces"]) == 5
        assert m["inspectedTraces"] >= 5
        assert m["inspectedBytes"] > 0
        assert m["totalBlocks"] == 1
        assert m["completedJobs"] == m["totalJobs"] >= 1
        assert "stageDurationNanos" in m
        qr = (f"http://127.0.0.1:{port}/api/metrics/query_range"
              f"?q={urllib.parse.quote('{ } | rate()')}"
              f"&start={T0 - 60}&end={T0 + 60}&step=60")
        body = json.loads(urllib.request.urlopen(qr, timeout=10).read())
        assert "metrics" in body
        assert body["metrics"]["totalBlocks"] >= 1
    finally:
        srv.shutdown()
        app.shutdown()
