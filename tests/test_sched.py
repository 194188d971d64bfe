"""Device-execution scheduler (tempo_tpu.sched) semantics.

Covers the ISSUE's scheduler contract: cross-tenant merge correctness
vs. unbatched results, priority ordering, deadline- and occupancy-based
batch close, shed accounting, backpressure propagation (distributor 429
+ Retry-After, frontend query shedding), zero steady-state jit
recompiles through the shape-bucket cache, and bit-identical
disabled-scheduler fallback.
"""

import threading
import time

import numpy as np
import pytest

from tempo_tpu import sched
from tempo_tpu.sched import (
    PRIO_COMPACTION,
    PRIO_INGEST,
    PRIO_QUERY,
    DeviceScheduler,
    SchedConfig,
    bucket_rows,
)


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _manual(cfg=None, now=None):
    """A scheduler driven by hand (no worker thread)."""
    return DeviceScheduler(cfg or SchedConfig(), now=now or time.monotonic,
                           start_worker=False)


# ---------------------------------------------------------------------------
# coalescer mechanics
# ---------------------------------------------------------------------------


def test_bucket_rows_pow2():
    assert bucket_rows(1) == 64
    assert bucket_rows(64) == 64
    assert bucket_rows(65) == 128
    assert bucket_rows(300) == 512
    assert bucket_rows(300, hi=256) == 256


def test_coalesce_merges_same_key_into_one_padded_tensor():
    sc = _manual()
    got = []

    def dispatch(slots, w):
        got.append((slots.copy(), w.copy()))

    for base in (0, 10, 20):
        sc.submit_rows("k", "state-a",
                       (np.arange(base, base + 5, dtype=np.int32),
                        np.full(5, 2.0, np.float32)), 5, dispatch,
                       pads=(-1, 0.0))
    sc.drain_once(force=True)
    assert len(got) == 1                       # three jobs, ONE dispatch
    slots, w = got[0]
    assert slots.shape == (64,)                # pow-2 bucket, min 64
    np.testing.assert_array_equal(
        slots[:15], np.concatenate([np.arange(b, b + 5) for b in
                                    (0, 10, 20)]))
    assert (slots[15:] == -1).all()            # padding rows drop on device
    assert (w[15:] == 0.0).all()
    assert sc.batches_total["k"] == 1
    assert sc.coalesced_total["k"] == 3
    assert sc.mean_occupancy("k") == pytest.approx(15 / 64)
    # waste: (64-15) rows * (4B slots + 4B weights)
    assert sc.padding_waste_bytes["k"] == (64 - 15) * 8


def test_pack_mode_ships_one_matrix_per_batch():
    """pack=True coalesces all roles into ONE row-major f32 matrix
    [n_roles, bucket] — the single-H2D dispatch shape — with per-role
    pad values on the padding columns."""
    sc = _manual()
    got = []
    for base in (0, 100):
        sc.submit_rows("k", "m",
                       (np.arange(base, base + 5, dtype=np.float32),
                        np.full(5, 2.5, np.float32)), 5,
                       lambda mat: got.append(mat.copy()),
                       pads=(-1.0, 0.0), pack=True)
    sc.drain_once(force=True)
    assert len(got) == 1
    mat = got[0]
    assert mat.shape == (2, 64) and mat.dtype == np.float32
    np.testing.assert_array_equal(
        mat[0, :10], np.concatenate([np.arange(0, 5), np.arange(100, 105)]))
    assert (mat[0, 10:] == -1.0).all() and (mat[1, 10:] == 0.0).all()
    assert (mat[1, :10] == 2.5).all()


def test_spanmetrics_packed_sched_route_matches_direct():
    """The production packed-coalescer route (slots riding f32 under the
    capacity < 2^24 gate) must reproduce the direct dispatch exactly."""
    sc = DeviceScheduler(SchedConfig(batch_window_ms=50.0),
                         start_worker=True)
    reg, proc = _mk_proc()
    ref, proc_ref = _mk_proc(use_scheduler=False)
    assert proc.calls.table.capacity < (1 << 24)   # the packed gate holds
    batches = [_spans_for("t", 48, seed=i) for i in range(4)]
    with sched.use(sc):
        for b in batches:
            _push_spans(proc, reg, b)
        sc.flush()
    for b in batches:
        _push_spans(proc_ref, ref, b)
    np.testing.assert_array_equal(np.asarray(proc.calls.state.values),
                                  np.asarray(proc_ref.calls.state.values))
    np.testing.assert_array_equal(np.asarray(proc.dd.counts),
                                  np.asarray(proc_ref.dd.counts))
    sc.stop()


def test_distinct_merge_keys_do_not_merge():
    sc = _manual()
    calls = {"a": 0, "b": 0}

    def mk(key):
        def dispatch(slots):
            calls[key] += 1
        return dispatch

    da, db = mk("a"), mk("b")
    sc.submit_rows("k", "a", (np.zeros(4, np.int32),), 4, da, pads=(-1,))
    sc.submit_rows("k", "b", (np.zeros(4, np.int32),), 4, db, pads=(-1,))
    sc.submit_rows("k", "a", (np.zeros(4, np.int32),), 4, da, pads=(-1,))
    sc.drain_once(force=True)
    assert calls == {"a": 1, "b": 1}           # no cross-state bleed
    assert sc.coalesced_total["k"] == 3 and sc.batches_total["k"] == 2


def test_max_batch_rows_chunks_oversized_groups():
    sc = _manual(SchedConfig(max_batch_rows=128, min_bucket_rows=64))
    seen = []
    for _ in range(4):
        sc.submit_rows("k", "m", (np.zeros(100, np.int32),), 100,
                       lambda slots: seen.append(len(slots)), pads=(-1,))
    sc.drain_once(force=True)
    # 4 x 100 rows with a 128-row cap → 4 dispatches of one job each
    assert len(seen) == 4 and all(s == 128 for s in seen)


@pytest.mark.parametrize("jobs, want", [
    (5, [4096, 1024]),          # a burst past the warm 4096: cut at it
    (9, [8192, 1024]),          # a backlog that fills 8192 grows into it
    (4, [4096]),                # the warm bucket holds them all
])
def test_a_burst_grows_into_a_new_bucket_only_where_it_fills_it(jobs, want):
    sc = _manual(SchedConfig(max_batch_rows=16384, min_bucket_rows=64))
    seen = []

    def submit(n):
        sc.submit_rows("k", "m", (np.zeros(n, np.int32),), n,
                       lambda slots: seen.append(len(slots)), pads=(-1,))

    submit(4000)                               # warms the 4096 bucket
    sc.drain_once(force=True)
    seen.clear()
    for _ in range(jobs):
        submit(1000)
    sc.drain_once(force=True)
    assert seen == want
    assert sc.coalesced_total["k"] == 1 + jobs


def test_a_kernel_that_never_dispatched_coalesces_freely():
    sc = _manual(SchedConfig(max_batch_rows=16384, min_bucket_rows=64))
    seen = []
    for _ in range(5):
        sc.submit_rows("k", "m", (np.zeros(1000, np.int32),), 1000,
                       lambda slots: seen.append(len(slots)), pads=(-1,))
    sc.drain_once(force=True)
    assert seen == [8192]


# ---------------------------------------------------------------------------
# batch-close policy: occupancy target or deadline, whichever first
# ---------------------------------------------------------------------------


def test_deadline_based_batch_close():
    clock = FakeClock()
    sc = _manual(SchedConfig(batch_window_ms=10.0, occupancy_target=1.0,
                             max_batch_rows=1 << 20), now=clock)
    done = []
    sc.submit_rows("k", "m", (np.zeros(8, np.int32),), 8,
                   lambda s: done.append(1), pads=(-1,))
    sc.drain_once()                            # window still open
    assert not done and sc.pending() == 1
    clock.t += 0.005
    sc.drain_once()                            # 5ms < 10ms: still open
    assert not done
    clock.t += 0.006                           # 11ms total: deadline hit
    sc.drain_once()
    assert done and sc.pending() == 0


def test_occupancy_target_closes_before_deadline():
    clock = FakeClock()
    sc = _manual(SchedConfig(batch_window_ms=10_000.0, occupancy_target=0.5,
                             max_batch_rows=1000), now=clock)
    done = []
    sc.submit_rows("k", "m", (np.zeros(100, np.int32),), 100,
                   lambda s: done.append(1), pads=(-1,))
    sc.drain_once()
    assert not done                            # 100 < 500 target rows
    sc.submit_rows("k", "m", (np.zeros(450, np.int32),), 450,
                   lambda s: done.append(1), pads=(-1,))
    sc.drain_once()                            # 550 >= 0.5 * 1000: close now
    assert done and sc.pending() == 0


# ---------------------------------------------------------------------------
# priority ordering + shed accounting
# ---------------------------------------------------------------------------


def test_priority_ordering_ingest_query_compaction():
    clock = FakeClock()
    sc = _manual(SchedConfig(batch_window_ms=0.0), now=clock)
    order = []
    results = []

    def submit_fn(tag, prio):
        job = sched.Job(priority=prio, kernel=tag,
                        fn=lambda: order.append(tag))
        with sc._cond:
            sc._queues[prio].append(job)
        results.append(job)

    submit_fn("compaction", PRIO_COMPACTION)
    submit_fn("query", PRIO_QUERY)
    sc.submit_rows("k", "m", (np.zeros(4, np.int32),), 4,
                   lambda s: order.append("ingest"), pads=(-1,))
    sc.drain_once()
    # compaction is deferred while better work exists…
    assert order == ["ingest", "query"]
    sc.drain_once()
    assert order == ["ingest", "query", "compaction"]


def test_query_jobs_never_wait_on_ingest_window():
    clock = FakeClock()
    sc = _manual(SchedConfig(batch_window_ms=10_000.0), now=clock)
    order = []
    sc.submit_rows("k", "m", (np.zeros(4, np.int32),), 4,
                   lambda s: order.append("ingest"), pads=(-1,))
    job = sched.Job(priority=PRIO_QUERY, kernel="q",
                    fn=lambda: order.append("query"))
    with sc._cond:
        sc._queues[PRIO_QUERY].append(job)
    sc.drain_once()
    assert order == ["query"]                  # window keeps ingest open


def test_shed_accounting_inline_execution():
    sc = _manual(SchedConfig(max_queue_ingest=2))
    dispatched_rows = []

    def dispatch(slots):
        dispatched_rows.append(int((slots >= 0).sum()))

    for _ in range(4):
        sc.submit_rows("k", "m", (np.zeros(4, np.int32),), 4, dispatch,
                       pads=(-1,))
    # two queued, two shed to inline dispatch (data is never dropped)
    assert sc.shed_total["ingest"] == 2
    assert dispatched_rows == [4, 4]           # the shed pair, one each
    sc.drain_once(force=True)
    # the queued pair merged into ONE dispatch carrying both jobs' rows
    assert dispatched_rows == [4, 4, 8]
    assert sc.jobs_total["ingest"] == 2


def test_run_sheds_inline_when_query_queue_full():
    sc = _manual(SchedConfig(max_queue_query=1))
    blocker = sched.Job(priority=PRIO_QUERY, kernel="q", fn=lambda: None)
    with sc._cond:
        sc._queues[PRIO_QUERY].append(blocker)
    out = sc.run(lambda: "inline")
    assert out == "inline"
    assert sc.shed_total["query"] == 1


def test_run_inline_when_idle_and_queued_when_busy():
    sc = _manual()
    assert sc.run(lambda: 7) == 7              # idle → inline, zero latency
    assert sc.jobs_total["query"] == 1
    sc.submit_rows("k", "m", (np.zeros(4, np.int32),), 4,
                   lambda s: None, pads=(-1,))
    done = {}

    def runner():
        done["v"] = sc.run(lambda: 9)

    t = threading.Thread(target=runner)
    t.start()
    deadline = time.monotonic() + 2.0
    while not sc._queues[PRIO_QUERY] and time.monotonic() < deadline:
        time.sleep(0.001)
    assert sc._queues[PRIO_QUERY], "busy scheduler should queue the job"
    sc.drain_once(force=True)
    t.join(2.0)
    assert done["v"] == 9


def test_flush_from_inside_a_dispatched_job_does_not_deadlock():
    """A scheduled job may itself need queued updates drained (e.g. a
    read that flushes sketch batches first): the nested flush drains
    queued work on the same thread instead of self-blocking."""
    sc = _manual(SchedConfig(batch_window_ms=60_000.0))
    seen = []

    def inner_dispatch(slots):
        seen.append("ingest")

    def outer():
        sc.submit_rows("k", "m", (np.zeros(4, np.int32),), 4,
                       inner_dispatch, pads=(-1,))
        sc.flush(timeout=2.0)              # nested: must not hang
        seen.append("outer-done")

    job = sched.Job(priority=PRIO_QUERY, kernel="q", fn=outer)
    with sc._cond:
        sc._queues[PRIO_QUERY].append(job)
    sc.drain_once(force=True)
    job.wait(2.0)
    assert seen == ["ingest", "outer-done"]


def test_dispatch_error_propagates_to_run_caller():
    sc = _manual()
    sc.submit_rows("k", "m", (np.zeros(4, np.int32),), 4,
                   lambda s: None, pads=(-1,))

    def boom():
        raise RuntimeError("kernel exploded")

    job = sched.Job(priority=PRIO_QUERY, kernel="q", fn=boom)
    with sc._cond:
        sc._queues[PRIO_QUERY].append(job)
    sc.drain_once(force=True)
    with pytest.raises(RuntimeError, match="kernel exploded"):
        job.wait(1.0)
    # fn-job errors belong to their waiting caller; dispatch_errors
    # counts only fire-and-forget ingest batches that were dropped
    assert sc.dispatch_errors == 0


def test_ingest_dispatch_error_is_counted():
    """Fire-and-forget ingest batches have no waiting caller: a failed
    dispatch must increment tempo_sched_dispatch_errors_total (and log)
    instead of vanishing."""
    sc = _manual()

    def bad_dispatch(slots):
        raise RuntimeError("scatter failed")

    job = sc.submit_rows("k", "m", (np.zeros(4, np.int32),), 4,
                         bad_dispatch, pads=(-1,))
    sc.drain_once(force=True)
    assert sc.dispatch_errors == 1
    with pytest.raises(RuntimeError, match="scatter failed"):
        job.wait(1.0)


# ---------------------------------------------------------------------------
# backpressure propagation
# ---------------------------------------------------------------------------


def _mini_distributor(now):
    from tempo_tpu.distributor import Distributor
    from tempo_tpu.overrides import Overrides
    from tempo_tpu.ring import ACTIVE, InstanceDesc, Ring
    from tempo_tpu.ring.ring import _instance_tokens

    class _NullIng:
        def push(self, tenant, traces):
            return [None] * len(traces)

        def push_otlp(self, tenant, payload):
            return {}

    ring = Ring(replication_factor=1, now=now)
    ring.register(InstanceDesc(id="i0", state=ACTIVE,
                               tokens=_instance_tokens("i0", 64),
                               heartbeat_ts=now()))
    ov = Overrides()
    ov.set_tenant_patch("t", {"ingestion": {"rate_limit_bytes": 1 << 40,
                                            "burst_size_bytes": 1 << 40}})
    return Distributor(ring, {"i0": _NullIng()}, overrides=ov, now=now)


def test_distributor_rejects_429_when_ingest_saturated():
    from tempo_tpu.distributor.distributor import (REASON_BACKPRESSURE,
                                                   RateLimited)

    now = FakeClock()
    sc = _manual(SchedConfig(max_queue_ingest=1, retry_after_s=3.0))
    sc.submit_rows("k", "m", (np.zeros(4, np.int32),), 4, lambda s: None,
                   pads=(-1,))
    assert sc.ingest_saturated()
    with sched.use(sc):
        d = _mini_distributor(now)
        spans = [{"trace_id": bytes([7]) * 16, "span_id": b"x" * 8,
                  "name": "op", "service": "s",
                  "start_unix_nano": 1, "end_unix_nano": 2}]
        with pytest.raises(RateLimited) as ei:
            d.push_spans("t", spans)
        assert ei.value.retry_after_s == 3.0
        assert ei.value.reason == REASON_BACKPRESSURE
        assert d.discarded.get(REASON_BACKPRESSURE) == 1
    # queue drained → admitted again
    sc.drain_once(force=True)
    with sched.use(sc):
        assert d.push_spans("t", spans) == {}


def test_backpressure_hook_injectable():
    from tempo_tpu.distributor.limiter import IngestBackpressure

    bp = IngestBackpressure(retry_after_fn=lambda: 2.5)
    assert bp.retry_after() == 2.5
    assert IngestBackpressure(lambda: None).retry_after() is None
    # default hook with no scheduler configured admits everything
    with sched.use(None):
        assert IngestBackpressure().retry_after() is None


def test_frontend_sheds_queries_when_query_class_saturated():
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db.tempodb import TempoDB
    from tempo_tpu.frontend import Frontend
    from tempo_tpu.querier import Querier
    from tempo_tpu.ring import Ring
    from tempo_tpu.sched import QueryBackpressure

    be = MemBackend()
    db = TempoDB(be, be)
    fe = Frontend(db, Querier(db, Ring(replication_factor=1), {}))
    sc = _manual(SchedConfig(max_queue_query=1, retry_after_s=2.0))
    blocker = sched.Job(priority=PRIO_QUERY, kernel="q", fn=lambda: None)
    with sc._cond:
        sc._queues[PRIO_QUERY].append(blocker)
    try:
        with sched.use(sc):
            with pytest.raises(QueryBackpressure) as ei:
                fe.search("t", "{ }")
            assert ei.value.retry_after_s == 2.0
            sc.drain_once(force=True)
            assert fe.search("t", "{ }") == []     # drained → admitted
    finally:
        fe.shutdown()
        db.shutdown()


# ---------------------------------------------------------------------------
# write-path integration: merge correctness, fallback parity, recompiles
# ---------------------------------------------------------------------------


def _push_spans(proc, reg, spans):
    from tests.test_generator import _mk_batch

    proc.push_batch(_mk_batch(spans, interner=reg.interner))


def _mk_proc(use_scheduler=True):
    from tempo_tpu.generator.processors.spanmetrics import (
        SpanMetricsConfig, SpanMetricsProcessor)
    from tempo_tpu.registry import ManagedRegistry

    reg = ManagedRegistry(now=FakeClock())
    proc = SpanMetricsProcessor(
        reg, SpanMetricsConfig(use_scheduler=use_scheduler))
    return reg, proc


def _spans_for(tenant_tag, n, seed):
    from tests.test_generator import _span

    rng = np.random.default_rng(seed)
    return [_span(1 + (i % 200), service=f"{tenant_tag}-svc-{i % 3}",
                  name=f"op-{i % 7}",
                  dur_ns=int(rng.integers(10**6, 10**10)))
            for i in range(n)]


def test_cross_tenant_merge_matches_unbatched_results():
    """Interleaved small pushes from two tenants through ONE scheduler
    must leave each tenant's device state equal to direct, unbatched
    dispatch — cross-tenant coalescing can amortize dispatch but never
    bleed state or drop rows (counts are exact integer adds in f32; the
    f32 latency sums only change accumulation order → allclose)."""
    sc = DeviceScheduler(SchedConfig(batch_window_ms=50.0),
                         start_worker=True)
    rega, proca = _mk_proc()
    regb, procb = _mk_proc()
    ref_a, proc_ref_a = _mk_proc(use_scheduler=False)
    ref_b, proc_ref_b = _mk_proc(use_scheduler=False)
    batches_a = [_spans_for("a", 40, seed=i) for i in range(6)]
    batches_b = [_spans_for("b", 40, seed=100 + i) for i in range(6)]
    with sched.use(sc):
        for sa, sb_ in zip(batches_a, batches_b):
            _push_spans(proca, rega, sa)
            _push_spans(procb, regb, sb_)
        sc.flush()
    for sa, sb_ in zip(batches_a, batches_b):
        _push_spans(proc_ref_a, ref_a, sa)
        _push_spans(proc_ref_b, ref_b, sb_)
    for proc, ref_proc in ((proca, proc_ref_a), (procb, proc_ref_b)):
        np.testing.assert_array_equal(
            np.asarray(proc.calls.state.values),
            np.asarray(ref_proc.calls.state.values))
        np.testing.assert_array_equal(
            np.asarray(proc.latency.state.bucket_counts),
            np.asarray(ref_proc.latency.state.bucket_counts))
        np.testing.assert_array_equal(np.asarray(proc.dd.counts),
                                      np.asarray(ref_proc.dd.counts))
        np.testing.assert_allclose(np.asarray(proc.latency.state.sums),
                                   np.asarray(ref_proc.latency.state.sums),
                                   rtol=1e-5, atol=1e-4)
    # the two tenants really did share batches through one scheduler
    assert sc.coalesced_total["spanmetrics_fused_update"] >= 12
    sc.stop()


def test_disabled_scheduler_fallback_bit_identical():
    """`use_scheduler=False` (or no configured scheduler) must take the
    untouched direct dispatch: states are BIT-identical, not just close."""
    sc = DeviceScheduler(SchedConfig(), start_worker=False)
    reg_off, proc_off = _mk_proc(use_scheduler=False)
    reg_none, proc_none = _mk_proc(use_scheduler=True)
    spans = [_spans_for("t", 64, seed=i) for i in range(3)]
    with sched.use(sc):
        for s in spans:                    # flag off, scheduler present
            _push_spans(proc_off, reg_off, s)
    with sched.use(None):
        for s in spans:                    # flag on, no scheduler
            _push_spans(proc_none, reg_none, s)
    np.testing.assert_array_equal(np.asarray(proc_off.calls.state.values),
                                  np.asarray(proc_none.calls.state.values))
    np.testing.assert_array_equal(np.asarray(proc_off.latency.state.sums),
                                  np.asarray(proc_none.latency.state.sums))
    np.testing.assert_array_equal(np.asarray(proc_off.dd.counts),
                                  np.asarray(proc_none.dd.counts))
    assert sc.jobs_total["ingest"] == 0    # nothing ever rode the scheduler


def test_zero_recompiles_after_warmup():
    """The shape-bucket cache satellite: steady-state scheduler traffic of
    VARYING caller batch sizes must trace each pow-2 bucket once and then
    never again — the obs compile counter stays flat."""
    from tempo_tpu.obs.jaxruntime import JIT_COMPILES

    sc = DeviceScheduler(SchedConfig(batch_window_ms=0.0),
                         start_worker=False)
    reg, proc = _mk_proc()
    label = ("spanmetrics_fused_update",)
    with sched.use(sc):
        for i in range(4):                 # warmup: buckets trace here
            _push_spans(proc, reg, _spans_for("t", 30 + 17 * i, seed=i))
            sc.drain_once(force=True)
        warm = JIT_COMPILES.value(label)
        warm_buckets = dict(sc.bucket_warmups)
        for i in range(8):                 # steady state: varying sizes
            _push_spans(proc, reg, _spans_for("t", 25 + 13 * i, seed=50 + i))
            sc.drain_once(force=True)
        assert JIT_COMPILES.value(label) == warm
        assert sc.bucket_warmups == warm_buckets


def test_collect_flushes_queued_batches():
    """A collection tick must see updates that were accepted before it
    (the instance wiring flushes the scheduler before purge+collect)."""
    from tests.test_generator import _span, series_value

    from tempo_tpu.generator.instance import (GeneratorConfig,
                                              GeneratorInstance)

    sc = DeviceScheduler(SchedConfig(batch_window_ms=60_000.0),
                         start_worker=False)
    with sched.use(sc):
        inst = GeneratorInstance("t", GeneratorConfig(
            processors=("span-metrics",)), now=FakeClock())
        from tests.test_generator import _mk_batch
        inst.push_batch(_mk_batch(
            [_span(1, service="s", name="op", start=10**12)],
            interner=inst.registry.interner))
        assert sc.pending() == 1           # queued, window far away
        inst.collect_and_push(ts_ms=1)
        assert sc.pending() == 0
        samples = inst.registry.collect(ts_ms=2)
        assert series_value(samples, "traces_spanmetrics_calls_total",
                            service="s", span_name="op") == 1.0


# ---------------------------------------------------------------------------
# read path: query stats threading + scheduler routing
# ---------------------------------------------------------------------------


def test_run_threads_query_stats_into_scheduled_jobs():
    from tempo_tpu.obs import querystats

    sc = _manual()
    sc.submit_rows("k", "m", (np.zeros(4, np.int32),), 4, lambda s: None,
                   pads=(-1,))               # make the scheduler non-idle
    with querystats.scope() as st:
        job = None

        def runner():
            with querystats.scope(st):
                sc.run(lambda: querystats.add(inspected_spans=5),
                       kernel="test_kernel")

        t = threading.Thread(target=runner)
        t.start()
        deadline = time.monotonic() + 2.0
        while not sc._queues[PRIO_QUERY] and time.monotonic() < deadline:
            time.sleep(0.001)
        sc.drain_once(force=True)
        t.join(2.0)
    assert st.sched_jobs == 1
    assert st.inspected_spans == 5          # recorded ON the worker thread
    assert st.stage_ns.get("sched_wait", 0) >= 0


def test_read_plane_routes_through_scheduler():
    """BlockScanPlane masks ride the scheduler's query class and still
    produce the same mask bits."""
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.block.device_scan import BlockScanPlane
    from tempo_tpu.block.fetch import condition_mask, scan_views
    from tempo_tpu.block.reader import BackendBlock
    from tempo_tpu.db.tempodb import TempoDB
    from tempo_tpu.traceql.conditions import extract_conditions
    from tempo_tpu.traceql.parser import parse

    rng = np.random.default_rng(7)
    be = MemBackend()
    db = TempoDB(be, be)
    traces = []
    for i in range(200):
        tid = rng.bytes(16)
        start = int((1_700_000_000 + i) * 1e9)
        traces.append((tid, [{
            "trace_id": tid, "span_id": rng.bytes(8),
            "name": f"op-{i % 5}", "service": f"svc-{i % 3}",
            "start_unix_nano": start,
            "end_unix_nano": start + 10**7}]))
    db.write_block("t", traces, replication_factor=1)
    db.poll_now()
    views = [v for m in db.blocklist.metas("t")
             for v, _ in scan_views(BackendBlock(db.r, m))]
    db.shutdown()
    req = extract_conditions(parse('{ name = "op-1" }'))
    preds = [c for c in req.conditions if c.op is not None]
    plane = BlockScanPlane(views)
    direct = plane.mask(preds, req.all_conditions)
    sc = DeviceScheduler(SchedConfig(), start_worker=True)
    with sched.use(sc):
        routed = plane.mask(preds, req.all_conditions)
    sc.stop()
    np.testing.assert_array_equal(direct, routed)
    want = np.concatenate([condition_mask(v, req) for v in views])
    np.testing.assert_array_equal(routed, want)
    assert sc.jobs_total["query"] >= 1


def test_obs_families_render_for_default_scheduler():
    """The sched metric families render on the process runtime registry
    (the drift gate's ground truth for dashboards/alerts)."""
    from tempo_tpu.obs.jaxruntime import RUNTIME
    from tempo_tpu.obs.registry import parse_exposition

    sc = sched.configure(SchedConfig(batch_window_ms=0.0))
    try:
        sc.submit_rows("k", "m", (np.zeros(4, np.int32),), 4,
                       lambda s: None, pads=(-1,))
        sc.flush()
        fams = parse_exposition(RUNTIME.render())
        for name in ("tempo_sched_queue_depth", "tempo_sched_queue_limit",
                     "tempo_sched_jobs_total", "tempo_sched_shed_jobs_total",
                     "tempo_sched_batches_total",
                     "tempo_sched_coalesced_jobs_total",
                     "tempo_sched_padding_waste_bytes_total",
                     "tempo_sched_bucket_warmups_total",
                     "tempo_sched_batch_occupancy_ratio",
                     "tempo_sched_dispatch_duration_seconds",
                     "tempo_sched_queue_wait_seconds"):
            assert name in fams, name
        key = ("tempo_sched_jobs_total", (("class", "ingest"),))
        assert fams["tempo_sched_jobs_total"]["samples"][key] >= 1.0
    finally:
        sched.reset()
