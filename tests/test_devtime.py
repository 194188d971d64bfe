"""Device-time ledger + online dispatch cost model + scheduler tuning.

ISSUE 8's test surface: ledger accounting and per-tenant attribution
invariants, the robust affine cost-model fit (synthetic affine data,
outlier poisoning, nearest-bucket extrapolation), the WindowTuner's
choices under an injected cost model (feasibility, latency minimization,
static fallback, hard clamps), tuned-vs-static BIT-IDENTITY of drained
state, the qlog/querystats device-seconds threading, the /status +
/metrics surfaces, and the tier-1 smoke of the soak loop.
"""

from __future__ import annotations

import numpy as np
import pytest

from tempo_tpu.obs import devtime
from tempo_tpu.obs.devtime import CostModel, DeviceTimeLedger
from tempo_tpu.sched import (
    DeviceScheduler,
    PRIO_QUERY,
    SchedConfig,
    WindowTuner,
)


@pytest.fixture(autouse=True)
def _fresh_devtime():
    devtime.reset()
    yield
    devtime.reset()


# -- ledger -----------------------------------------------------------------

def test_ledger_accounting_and_keys():
    led = DeviceTimeLedger()
    led.record_batch(kernel="k", bucket=256, prio=0, shards=0,
                     wall_ns=1000, rows=200, padded_rows=56,
                     queue_wait_ns=300, h2d_bytes=4096,
                     tenant_rows={"a": 150, "b": 50})
    led.record_batch(kernel="k", bucket=256, prio=0, shards=0,
                     wall_ns=500, rows=100, padded_rows=156,
                     queue_wait_ns=100, h2d_bytes=2048,
                     tenant_rows={"a": 100})
    led.record_batch(kernel="scan", bucket=0, prio=1, shards=4,
                     wall_ns=700, rows=0, padded_rows=0,
                     queue_wait_ns=0, h2d_bytes=0)
    snap = led.snapshot()
    cell = snap[("k", 256, "ingest", "")]
    assert cell == {"wall_ns": 1500, "batches": 2, "rows": 300,
                    "padded_rows": 212, "queue_wait_ns": 400,
                    "h2d_bytes": 6144}
    assert ("scan", 0, "query", "4") in snap
    assert led.total_device_ns() == 2200


def test_ledger_tenant_attribution_sums_to_total():
    led = DeviceTimeLedger()
    rng = np.random.default_rng(0)
    for i in range(200):
        tenants = {f"t{j}": int(rng.integers(1, 50))
                   for j in range(int(rng.integers(1, 5)))}
        led.record_batch(kernel=f"k{i % 3}", bucket=64, prio=0, shards=0,
                         wall_ns=int(rng.integers(1000, 100000)),
                         rows=sum(tenants.values()),
                         padded_rows=7, queue_wait_ns=5, h2d_bytes=1,
                         tenant_rows=tenants)
    # unattributed work keeps the invariant exact through its own bucket
    led.record_batch(kernel="fn", bucket=0, prio=1, shards=0,
                     wall_ns=12345, rows=0, padded_rows=0,
                     queue_wait_ns=0, h2d_bytes=0)
    total = led.total_device_ns()
    by_tenant = led.tenant_device_ns()
    assert by_tenant["_unattributed"] == 12345
    # integer-division truncation loses < len(tenants) ns per batch
    assert abs(total - sum(by_tenant.values())) <= total * 0.001
    st = led.status(top_tenants=3)
    assert len(st["top_tenant_device_seconds"]) == 3
    assert st["device_seconds_total"] == pytest.approx(total / 1e9,
                                                       rel=1e-3)


# -- cost model -------------------------------------------------------------

def test_cost_model_fits_affine_data():
    cm = CostModel(min_samples=10)
    rng = np.random.default_rng(1)
    a_true, b_true = 2e-4, 3e-6
    for _ in range(300):
        r = int(rng.integers(8, 64))
        cm.observe("k", 64, r, a_true + b_true * r
                   + float(rng.normal(0, 1e-6)))
    pred = cm.predict("k", 64, 32)
    assert pred == pytest.approx(a_true + b_true * 32, rel=0.05)
    assert cm.warm("k", 64)
    assert cm.rel_error_median("k", 64) <= 0.25
    assert cm.typical_error("k", 64) <= 0.25
    assert cm.status()[0]["typical_error"] is not None


def test_cost_model_winsorizes_outliers():
    cm = CostModel(min_samples=10, clip=8.0)
    for _ in range(50):
        cm.observe("k", 64, 32, 1e-4)
    # a burst of 1000x stalls must not poison the fit
    for _ in range(5):
        cm.observe("k", 64, 32, 0.1)
    assert cm.predict("k", 64, 32) < 1e-3
    # and the early-sample guard: stalls BEFORE warm are clipped too
    cm2 = CostModel(min_samples=20)
    cm2.observe("k", 64, 32, 1e-4)
    cm2.observe("k", 64, 32, 1e-4)
    cm2.observe("k", 64, 32, 1e-4)
    cm2.observe("k", 64, 32, 0.5)        # 5000x stall at n=3
    for _ in range(30):
        cm2.observe("k", 64, 32, 1e-4)
    assert cm2.predict("k", 64, 32) < 1e-3


def test_cost_model_cold_and_neighbor_extrapolation():
    cm = CostModel(min_samples=5)
    assert cm.predict("k", 64) is None
    for _ in range(10):
        cm.observe("k", 256, 200, 1e-3)
    # exact pair cold, same-kernel neighbor warm: extrapolate
    assert cm.predict("k", 512, 200) == pytest.approx(1e-3, rel=0.2)
    assert cm.predict("other", 256) is None
    assert cm.warm_pairs() == [("k", 256)]
    st = cm.status()
    assert st[0]["warm"] and st[0]["kernel"] == "k"


def test_cost_model_degenerate_single_rows_value():
    """One distinct rows value → variance 0 → fall back to a pure mean
    (b = 0), never a division blow-up."""
    cm = CostModel(min_samples=5)
    for _ in range(10):
        cm.observe("k", 64, 64, 2e-4)
    assert cm.predict("k", 64, 64) == pytest.approx(2e-4, rel=0.01)
    assert cm.predict("k", 64, 1) == pytest.approx(2e-4, rel=0.01)


# -- window tuner -----------------------------------------------------------

def _warm_model(kernel: str, bucket: int, cost_s: float, n: int = 80):
    for _ in range(n):
        devtime.COST_MODEL.observe(kernel, bucket, bucket, cost_s)


def test_tuner_cold_model_returns_none():
    t = [0.0]
    tu = WindowTuner(now=lambda: t[0])
    cfg = SchedConfig(tuning="auto")
    tu.note_rows("k", 1000)
    t[0] += 1.0
    assert tu.choice("k", cfg) is None
    assert tu.windows_ms() == []


def test_tuner_picks_feasible_latency_minimum():
    """Cheap dispatch → the smallest feasible window wins (cost ≤ w and
    w + cost minimal at the low end of the grid)."""
    t = [0.0]
    tu = WindowTuner(now=lambda: t[0])
    cfg = SchedConfig(tuning="auto", tuning_window_min_ms=0.25,
                      tuning_window_max_ms=8.0)
    _warm_model("k", 64, 1e-4)           # 0.1ms per dispatch
    tu.note_rows("k", 2000)
    t[0] += 1.0                          # rate = 2000 rows/s
    w_s, target = tu.choice("k", cfg)
    assert w_s == pytest.approx(0.25e-3, rel=0.01)
    assert target == 64
    assert dict(tu.windows_ms())["k"] == pytest.approx(0.25, rel=0.01)


def test_tuner_infeasible_cost_falls_back_to_max_window():
    """Dispatch slower than every candidate window → no feasible w →
    maximum amortization (largest window)."""
    t = [0.0]
    tu = WindowTuner(now=lambda: t[0])
    cfg = SchedConfig(tuning="auto", tuning_window_min_ms=0.25,
                      tuning_window_max_ms=4.0)
    _warm_model("k", 64, 0.05)           # 50ms per dispatch
    tu.note_rows("k", 1000)
    t[0] += 1.0
    w_s, _target = tu.choice("k", cfg)
    assert w_s == pytest.approx(4.0e-3, rel=0.01)


def test_tuner_choice_cached_until_interval():
    t = [0.0]
    tu = WindowTuner(now=lambda: t[0])
    cfg = SchedConfig(tuning="auto", tuning_interval_s=0.5)
    _warm_model("k", 64, 1e-4)
    tu.note_rows("k", 1000)
    t[0] += 1.0
    first = tu.choice("k", cfg)
    devtime.reset()                      # model gone...
    t[0] += 0.1
    assert tu.choice("k", cfg) == first  # ...but the cached choice holds
    t[0] += 1.0
    assert tu.choice("k", cfg) is None   # refit sees the cold model


def test_scheduler_close_params_hard_guard():
    """Auto mode can shrink the close target but never exceed the static
    occupancy close, and the window stays inside the clamp bounds."""
    sc = DeviceScheduler(SchedConfig(
        tuning="auto", batch_window_ms=2.0, occupancy_target=0.75,
        max_batch_rows=16384, tuning_window_min_ms=0.5,
        tuning_window_max_ms=3.0), start_worker=False)
    # cold model: static params
    w, target = sc._group_close_params("k")
    assert w == pytest.approx(2.0e-3)
    assert target == pytest.approx(0.75 * 16384)
    assert sc.tuned_window_ms("k") == pytest.approx(2.0)
    assert not sc.tuning_active()
    # warm model with a huge dispatch cost: tuner wants 8ms (its grid
    # max) but the config clamp holds it at 3ms
    _warm_model("k", 64, 0.05)
    sc._tuner.note_rows("k", 1000)
    sc._tuner._state["k"][1] = -10.0     # force a refit now
    w, target = sc._group_close_params("k")
    assert w <= 3.0e-3 + 1e-9
    assert target <= 0.75 * 16384
    assert sc.tuning_active()


def test_tuned_drain_bit_identical_to_static():
    """Tuning changes WHEN batches close, never what they compute: the
    same submitted jobs drain to the same final state."""
    def run(cfg: SchedConfig) -> np.ndarray:
        state = np.zeros(64, np.float64)

        def dispatch(slots, vals):
            np.add.at(state, slots[slots >= 0].astype(int),
                      vals[slots >= 0])

        sc = DeviceScheduler(cfg, start_worker=False)
        rng = np.random.default_rng(7)
        for i in range(50):
            n = int(rng.integers(1, 40))
            slots = rng.integers(0, 64, n).astype(np.float64)
            vals = rng.normal(size=n)
            sc.submit_rows("k", "m", (slots, vals), n, dispatch,
                           pads=(-1.0, 0.0), tenant=f"t{i % 5}")
            if i % 7 == 0:
                sc.drain_once(force=(i % 14 == 0))
        sc.flush()
        return state

    _warm_model("k", 64, 1e-4)
    static = run(SchedConfig(tuning="static"))
    devtime.reset()
    _warm_model("k", 64, 1e-4)
    auto = run(SchedConfig(tuning="auto", tuning_window_min_ms=0.25))
    assert np.array_equal(static, auto)


# -- scheduler → ledger wiring ---------------------------------------------

def test_dispatch_records_ledger_and_feeds_model():
    sc = DeviceScheduler(SchedConfig(), start_worker=False)
    seen = []
    lat0 = devtime.INGEST_LATENCY.snapshot(("k",))
    count0 = lat0["count"] if lat0 else 0   # RUNTIME histograms are
    #                                         process-wide, not reset

    def dispatch(slots, vals):
        seen.append(len(slots))

    for i in range(3):
        sc.submit_rows("k", "m", (np.full(30, i, np.float32),
                                  np.ones(30, np.float32)), 30, dispatch,
                       tenant=f"t{i}")
    sc.drain_once(force=True)
    assert seen == [128]                       # 90 rows → bucket 128
    snap = devtime.LEDGER.snapshot()
    cell = snap[("k", 128, "ingest", "")]
    assert cell["batches"] == 1 and cell["rows"] == 90
    assert cell["padded_rows"] == 128 - 90
    assert cell["h2d_bytes"] == 2 * 128 * 4    # two f32 roles, padded
    tenants = devtime.LEDGER.tenant_device_ns()
    assert set(tenants) == {"t0", "t1", "t2"}
    assert abs(devtime.LEDGER.total_device_ns()
               - sum(tenants.values())) <= 3
    # the cost model saw the clean dispatch
    with devtime.COST_MODEL._lock:
        assert ("k", 128) in devtime.COST_MODEL._pairs
    # and the per-job ingest-visible latency histogram has 3 new samples
    got = devtime.INGEST_LATENCY.snapshot(("k",))
    assert got is not None and got["count"] - count0 == 3


def test_failed_dispatch_ledgered_but_not_learned():
    sc = DeviceScheduler(SchedConfig(), start_worker=False)

    def boom(slots, vals):
        raise RuntimeError("kernel exploded")

    sc.submit_rows("k", "m", (np.zeros(4, np.float32),
                              np.zeros(4, np.float32)), 4, boom)
    sc.drain_once(force=True)
    assert devtime.LEDGER.total_device_ns() >= 0
    assert ("k", 64, "ingest", "") in devtime.LEDGER.snapshot()
    with devtime.COST_MODEL._lock:
        assert ("k", 64) not in devtime.COST_MODEL._pairs
    assert sc.dispatch_errors == 1


def test_run_fn_attributes_device_ns_to_querystats():
    from tempo_tpu.obs import querystats

    sc = DeviceScheduler(SchedConfig(), start_worker=False)
    with querystats.scope() as st:
        out = sc.run(lambda: 41 + 1, kernel="scan", priority=PRIO_QUERY,
                     tenant="tq")
    assert out == 42
    assert st.device_ns > 0
    assert st.search_metrics()["deviceNanos"] == st.device_ns
    # inline (idle) path still ledgered, attributed to the tenant
    assert devtime.LEDGER.tenant_device_ns().get("tq", 0) > 0
    assert ("scan", 0, "query", "") in devtime.LEDGER.snapshot()


def test_qlog_line_carries_device_seconds_and_wait_share():
    import logging

    from tempo_tpu.obs.qlog import QueryLogger
    from tempo_tpu.obs.querystats import QueryStats

    records = []

    class _H(logging.Handler):
        def emit(self, r):
            records.append(r.getMessage())

    lg = logging.getLogger("test.devtime.qlog")
    lg.addHandler(_H())
    lg.setLevel(logging.DEBUG)
    ql = QueryLogger(sample_every=1, logger=lg)
    st = QueryStats()
    st.add(device_ns=5_000_000)
    st.add_stage_ns("sched_wait", 20_000_000)
    rec = ql.log_query(op="search", tenant="t", query="{}", status="ok",
                       duration_s=0.1, stats=st)
    assert rec["deviceNanos"] == 5_000_000
    assert rec["deviceSeconds"] == pytest.approx(0.005)
    assert rec["schedWaitShare"] == pytest.approx(0.2)
    import json as _json
    assert _json.loads(records[-1])["schedWaitShare"] == pytest.approx(0.2)


def test_querystats_device_ns_round_trips_wire():
    from tempo_tpu.model import tempopb
    from tempo_tpu.obs.querystats import QueryStats

    st = QueryStats()
    st.add(device_ns=123456, inspected_traces=3)
    st2 = tempopb.dec_query_stats(tempopb.enc_query_stats(st))
    assert st2.device_ns == 123456
    assert st2.inspected_traces == 3
    st3 = QueryStats.from_json(st.to_json())
    assert st3.device_ns == 123456


# -- exposition -------------------------------------------------------------

def test_devtime_metric_families_render_conformant():
    from tempo_tpu.obs.jaxruntime import RUNTIME
    from tempo_tpu.obs.registry import parse_exposition

    devtime.LEDGER.record_batch(kernel="k", bucket=64, prio=0, shards=2,
                                wall_ns=1_000_000, rows=50,
                                padded_rows=14, queue_wait_ns=100,
                                h2d_bytes=512, tenant_rows={"a": 50})
    for _ in range(30):
        devtime.COST_MODEL.observe("k", 64, 50, 1e-4)
    fams = parse_exposition(RUNTIME.render())
    key = ("tempo_devtime_device_seconds_total",
           (("bucket", "64"), ("class", "ingest"), ("kernel", "k"),
            ("shard", "2")))
    assert fams["tempo_devtime_device_seconds_total"]["samples"][key] \
        == pytest.approx(1e-3)
    assert ("tempo_devtime_tenant_device_seconds_total",
            (("tenant", "a"),)) in \
        fams["tempo_devtime_tenant_device_seconds_total"]["samples"]
    for name in ("tempo_sched_cost_model_coeff_a_seconds",
                 "tempo_sched_cost_model_coeff_b_seconds_per_row",
                 "tempo_sched_cost_model_rel_error",
                 "tempo_sched_cost_model_rel_error_median",
                 "tempo_sched_cost_model_age_seconds"):
        assert any(k[0] == name for k in fams[name]["samples"])


def test_quantile_from_counts_interpolates():
    edges = (0.001, 0.002, 0.004, 0.008)
    assert devtime.quantile_from_counts(edges, [0, 0, 0, 0, 0], 0.99) == 0.0
    # all mass in one bucket: quantile inside (0.002, 0.004]
    q = devtime.quantile_from_counts(edges, [0, 0, 100, 0, 0], 0.5)
    assert 0.002 < q <= 0.004
    # overflow bucket floors at the top edge
    assert devtime.quantile_from_counts(edges, [0, 0, 0, 0, 10], 0.99) \
        == 0.008


def test_status_surfaces_devtime_and_cost_model(tmp_path):
    import json as _json
    import socket
    import urllib.request

    from tempo_tpu.app import App
    from tempo_tpu.app.api import serve
    from tempo_tpu.app.config import Config

    cfg = Config()
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.generator.localblocks.data_dir = str(tmp_path / "lb")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    cfg.server.http_listen_port = s.getsockname()[1]
    s.close()
    cfg.sched.tuning = "auto"
    app = App(cfg)
    srv = serve(app, block=False)
    try:
        devtime.LEDGER.record_batch(
            kernel="k", bucket=64, prio=0, shards=0, wall_ns=1000,
            rows=10, padded_rows=1, queue_wait_ns=1, h2d_bytes=1,
            tenant_rows={"a": 10})
        for _ in range(60):
            devtime.COST_MODEL.observe("k", 64, 50, 1e-4)
        url = (f"http://127.0.0.1:{cfg.server.http_listen_port}/status")
        with urllib.request.urlopen(url, timeout=10) as r:
            body = _json.loads(r.read())
        assert body["devtime"]["device_seconds_total"] > 0
        assert body["devtime"]["top_tenant_device_seconds"]["a"] > 0
        assert body["cost_model"]["tuning"] == "auto"
        pairs = body["cost_model"]["pairs"]
        assert pairs and pairs[0]["kernel"] == "k" and pairs[0]["warm"]
    finally:
        srv.shutdown()
        app.shutdown()


def test_config_warns_on_bad_tuning():
    from tempo_tpu.app.config import Config

    cfg = Config()
    cfg.sched.tuning = "bogus"
    assert any("sched.tuning" in w for w in cfg.check())
    cfg.sched.tuning = "auto"
    cfg.sched.tuning_window_min_ms = 5.0
    cfg.sched.tuning_window_max_ms = 1.0
    assert any("tuning_window" in w for w in cfg.check())
    cfg.sched.tuning_window_min_ms = 0.25
    cfg.sched.tuning_window_max_ms = 8.0
    assert not any("tuning" in w for w in cfg.check())


def test_sched_dispatch_span_emitted():
    from tempo_tpu.utils import tracing

    spans = []

    class _Tracer(tracing.Tracer):
        def span(self, name, **attrs):
            spans.append((name, attrs))
            return super().span(name, **attrs)

    tracing.install(_Tracer())
    try:
        sc = DeviceScheduler(SchedConfig(), start_worker=False)
        sc.submit_rows("k", "m", (np.zeros(4, np.float32),
                                  np.zeros(4, np.float32)), 4,
                       lambda *a: None, tenant="t")
        sc.drain_once(force=True)
    finally:
        tracing.install(tracing.Tracer())
    names = [s for s in spans if s[0] == "sched.dispatch"]
    assert names and names[0][1]["kernel"] == "k"
    assert names[0][1]["bucket"] == 64 and names[0][1]["rows"] == 4


# -- the tier-1 soak smoke --------------------------------------------------

def test_soak_smoke():
    """The soak loop in miniature: static + auto arms against a
    real App (distributor → ingester/generator, frontend reads, vulture
    canary over HTTP), gating the machinery — tuning goes active from a
    warm cost model, attribution sums, ledger populated, no tuning-loop
    recompiles, vulture writes read back. Arms are seconds, not
    minutes, so the p99/throughput comparison is reported, not gated."""
    from soak_harness import soak_run

    out = soak_run(n_tenants=12, warm_s=1.0, steady_s=2.0,
                   spans_per_push=64, duty=0.6,
                   read_every_s=0.5, vulture_every_s=1.0,
                   smoke=True)
    assert out["soak_accept_ok"], out
    assert out["soak_tenants_attributed"] >= 12
    assert out["soak_tuned_window_ms"]       # tuner published a window
    assert out["soak_vulture"]["read_missing"] == 0
