"""localblocks processor + span-metrics summary (traceqlmetrics analog)."""

from __future__ import annotations

import numpy as np
import pytest

from tempo_tpu.backend.mem import MemBackend
from tempo_tpu.generator.instance import GeneratorConfig, GeneratorInstance
from tempo_tpu.generator.processors.localblocks import (
    LocalBlocksConfig,
    LocalBlocksProcessor,
)
from tempo_tpu.model.span_batch import SpanBatchBuilder
from tempo_tpu.traceql.engine_metrics import QueryRangeRequest
from tempo_tpu.traceql.metrics_summary import (
    LatencyHistogram,
    bucketize_ns,
    get_metrics,
)
from tempo_tpu.traceql.memview import view_from_traces

T0 = 1_700_000_000.0


def build_batch(n=20, interner=None, t0_s=T0):
    b = SpanBatchBuilder(interner)
    for i in range(n):
        tid = bytes([i + 1]) * 16
        b.append(trace_id=tid, span_id=bytes([1]) * 8,
                 name=f"op-{i % 3}", service=f"svc-{i % 2}",
                 status_code=(2 if i % 5 == 0 else 0),
                 start_unix_nano=int((t0_s + i) * 1e9),
                 end_unix_nano=int((t0_s + i) * 1e9) + (1 << (20 + i % 4)),
                 attrs={"http.path": f"/p{i % 2}", "n": i})
    return b.build()


def test_span_dicts_respect_valid_mask():
    """Rows invalidated (e.g. slack-filtered) must not be persisted."""
    import dataclasses as dc
    sb = build_batch(5)
    valid = sb.valid.copy()
    valid[2] = False
    sb2 = dc.replace(sb, valid=valid)
    spans = sb2.to_span_dicts()
    assert len(spans) == 4
    assert all(s["trace_id"] != bytes([3]) * 16 for s in spans)


@pytest.mark.parametrize("cap,held", [(0, 20), (5, 5)])
def test_live_trace_cap_is_the_processors_own(cap, held, tmp_path):
    """`max_live_traces` (0 = unlimited) bounds the store — not the
    ingester's per-tenant default, which silently dropped every trace
    past 10,000 from the RF1 blocks TraceQL metrics read."""
    p = LocalBlocksProcessor("t", LocalBlocksConfig(
        data_dir=str(tmp_path), max_live_traces=cap))
    assert p.inst.live.max_live_traces == cap
    p.push_batch(build_batch(20))
    assert len(p.inst.live) == held


def test_span_dicts_round_trip():
    sb = build_batch(5)
    spans = sb.to_span_dicts()
    assert len(spans) == 5
    s = spans[0]
    assert s["name"] == "op-0" and s["service"] == "svc-0"
    assert s["attrs"]["http.path"] == "/p0" and s["attrs"]["n"] == 0
    assert isinstance(s["attrs"]["n"], int)
    assert s["status_code"] == 2


def test_bucketize_matches_reference_semantics():
    # smallest b with 2^b >= d (metrics.go Record)
    assert bucketize_ns(np.array([1])).tolist() == [0]
    assert bucketize_ns(np.array([2])).tolist() == [1]
    assert bucketize_ns(np.array([3])).tolist() == [2]
    assert bucketize_ns(np.array([1024])).tolist() == [10]
    assert bucketize_ns(np.array([1025])).tolist() == [11]


def test_latency_histogram_percentile():
    h = LatencyHistogram.empty()
    h.buckets[10] = 100  # all values in (512, 1024]
    p50 = h.percentile(0.5)
    assert 512 < p50 <= 1024
    assert h.percentile(1.0) == 1024
    # interpolation is monotone
    assert h.percentile(0.1) <= h.percentile(0.5) <= h.percentile(0.9)


def test_get_metrics_grouping_and_errors():
    sb = build_batch(20)
    traces = {}
    for s in sb.to_span_dicts():
        traces.setdefault(s["trace_id"], []).append(s)
    view = view_from_traces(list(traces.items()))
    views = [(view, np.arange(view.n))]
    res = get_metrics("{ }", ["resource.service.name"], iter(views))
    assert len(res.series) == 2
    total = sum(s.histogram.count for s in res.results())
    assert total == 20
    errs = sum(s.error_count for s in res.results())
    assert errs == 4  # i % 5 == 0 → 0,5,10,15
    # filtered
    views = [(view, np.arange(view.n))]
    res2 = get_metrics('{ resource.service.name = "svc-0" }', [], iter(views))
    assert res2.results()[0].histogram.count == 10
    js = res.results()[0].to_json()
    assert js["p50"] > 0 and js["spanCount"] > 0


def test_localblocks_lifecycle_and_query(tmp_path):
    clock = [T0 + 100]
    now = lambda: clock[0]
    be = MemBackend()
    p = LocalBlocksProcessor(
        "t1",
        LocalBlocksConfig(data_dir=str(tmp_path), trace_idle_s=1.0,
                          max_block_duration_s=10.0, flush_to_storage=True),
        flush_writer=be, now=now)
    p.push_batch(build_batch(20))
    # live → query works immediately
    req = QueryRangeRequest(query="{ } | rate()",
                            start_ns=int(T0 * 1e9),
                            end_ns=int((T0 + 60) * 1e9),
                            step_ns=int(60 * 1e9))
    series = p.query_range(req)
    assert sum(float(np.nansum(s.samples)) for s in series) > 0
    # cut to WAL then to complete block
    clock[0] += 2
    p.cut_tick()
    clock[0] += 11
    p.cut_tick()
    assert len(p.inst.complete_blocks()) == 1
    meta = next(iter(p.inst.complete.values())).meta
    assert meta.replication_factor == 1      # RF1: metrics-eligible
    # flushed to object storage
    from tempo_tpu.backend.raw import blocks as list_blocks
    assert meta.block_id in list_blocks(be, "t1")
    # queries still see the data (now in the complete block)
    series = p.query_range(req)
    # job-level series are raw counts; the frontend combiner divides by step
    assert sum(float(np.nansum(s.samples)) for s in series) == 20
    res = p.get_metrics("{ }", ["name"])
    assert sum(s.histogram.count for s in res.results()) == 20


def test_a_ticks_write_does_not_block_push_batch(tmp_path, monkeypatch):
    """`cut_tick` holds the processor's instance lock to take the cut
    traces, not through the segment's write: a `push_batch` that arrives
    while `append_table` is in flight returns before it ends."""
    import threading

    from tests.test_cut_lock import WAIT_S, _HeldWrite

    write = _HeldWrite(monkeypatch)
    p = LocalBlocksProcessor("t1", LocalBlocksConfig(data_dir=str(tmp_path)))
    p.push_batch(build_batch(20))
    tick = threading.Thread(target=p.cut_tick, args=(True,), daemon=True)
    tick.start()
    try:
        assert write.entered.wait(WAIT_S)
        push = threading.Thread(target=p.push_batch,
                                args=(build_batch(5, t0_s=T0 + 30),),
                                daemon=True)
        push.start()
        push.join(WAIT_S)
        assert not push.is_alive() and tick.is_alive()
        # the 20 traces being written; the 5 live ones carry ids of theirs
        assert len(p.inst.cutting) == 20 and len(p.inst.live) == 5
        assert len(p.inst.all_recent_traces()) == 20
    finally:
        write.release()
    tick.join(WAIT_S)
    assert not tick.is_alive() and p.inst.cutting is None
    assert len(p.inst.complete_blocks()) == 1 and len(p.inst.live) == 5


def test_generator_instance_localblocks_wiring(tmp_path):
    clock = [T0]
    cfg = GeneratorConfig(
        processors=("span-metrics", "local-blocks"),
        localblocks=LocalBlocksConfig(data_dir=str(tmp_path), trace_idle_s=1.0))
    gi = GeneratorInstance("t1", cfg, now=lambda: clock[0])
    sb = build_batch(10, interner=gi.registry.interner, t0_s=clock[0] - 5)
    gi.push_batch(sb)
    req = QueryRangeRequest(query="{ } | count_over_time()",
                            start_ns=int((clock[0] - 60) * 1e9),
                            end_ns=int((clock[0] + 60) * 1e9),
                            step_ns=int(120 * 1e9))
    series = gi.query_range(req)
    assert sum(float(np.nansum(s.samples)) for s in series) == 10
    res = gi.get_metrics("{ }", ["resource.service.name"])
    assert sum(s.histogram.count for s in res.results()) == 10
    gi.tick()  # maintenance pass runs without error


def test_generator_service_push_and_query(tmp_path):
    """Generator service: the distributor's client protocol end-to-end,
    through overrides-driven processor selection."""
    from tempo_tpu.generator import Generator
    from tempo_tpu.overrides import Overrides

    clock = [T0]
    ov = Overrides()
    ov.set_tenant_patch("t1", {"generator": {
        "processors": ["span-metrics", "local-blocks"]}})
    g = Generator(GeneratorConfig(
        localblocks=LocalBlocksConfig(data_dir=str(tmp_path))),
        overrides=ov, now=lambda: clock[0])
    spans = []
    for i in range(15):
        t0 = int((clock[0] - 5) * 1e9)
        spans.append({"trace_id": bytes([i + 1]) * 16, "span_id": b"\x01" * 8,
                      "name": "op", "service": "svc",
                      "start_unix_nano": t0, "end_unix_nano": t0 + 10 ** 7})
    g.push_spans("t1", spans)
    assert set(g.instance("t1").processors) == {"span-metrics", "local-blocks"}
    req = QueryRangeRequest(query="{ } | count_over_time()",
                            start_ns=int((clock[0] - 60) * 1e9),
                            end_ns=int((clock[0] + 60) * 1e9),
                            step_ns=int(120 * 1e9))
    series = g.query_range("t1", req)
    assert sum(float(np.nansum(s.samples)) for s in series) == 15
    # unknown tenant → empty, not an instance spawn
    assert g.query_range("ghost", req) == []
    assert "ghost" not in g.instances
    # collection tick covers all tenants
    g.collect_all()


def test_generator_without_localblocks_raises():
    gi = GeneratorInstance("t1", GeneratorConfig(processors=("span-metrics",)))
    with pytest.raises(RuntimeError):
        gi.get_metrics("{ }", [])
