"""tempo_tpu.obs: registry exposition, conformance, drift gate, exemplars.

The observability substrate's own tests: Counter/Gauge/Histogram family
semantics, HELP/TYPE text exposition with centralized escaping, the
Prometheus text-format round-trip parser against a LIVE `/metrics`, the
alert/dashboard ↔ registry drift gate, the SelfTracer dogfood path
(spans exported over OTLP/HTTP into this very process, queryable by
trace id), and the slow-request trace-id exemplar bridge.
"""

from __future__ import annotations

import json
import socket
import time
import urllib.parse
import urllib.request

import pytest

from tempo_tpu.obs import (
    Registry,
    escape_label,
    exponential_buckets,
    parse_exposition,
)


# -- instrument / family semantics ------------------------------------------

def test_counter_gauge_render_with_help_type():
    reg = Registry()
    c = reg.counter("tempo_t_things_total", "things processed",
                    labels=("reason",))
    c.inc(2, ("full",))
    c.inc(labels=("full",))
    reg.gauge("tempo_t_depth", "queue depth").set(4.5)
    text = reg.render()
    assert "# HELP tempo_t_things_total things processed" in text
    assert "# TYPE tempo_t_things_total counter" in text
    assert '# TYPE tempo_t_depth gauge' in text
    assert 'tempo_t_things_total{reason="full"} 3' in text
    assert "tempo_t_depth 4.5" in text
    fams = parse_exposition(text)
    assert fams["tempo_t_things_total"]["type"] == "counter"
    key = ("tempo_t_things_total", (("reason", "full"),))
    assert fams["tempo_t_things_total"]["samples"][key] == 3.0


def test_get_or_create_identity_and_mismatch():
    reg = Registry()
    a = reg.counter("tempo_t_total", "h", labels=("x",))
    assert reg.counter("tempo_t_total", labels=("x",)) is a
    with pytest.raises(ValueError):          # kind mismatch
        reg.gauge("tempo_t_total", labels=("x",))
    with pytest.raises(ValueError):          # label-set mismatch
        reg.counter("tempo_t_total", labels=("y",))
    with pytest.raises(ValueError):          # wrong label arity at use
        a.inc(1, ())
    with pytest.raises(ValueError):          # invalid metric name
        reg.counter("tempo bad name")
    reg.counter_func("tempo_t_cb_total", lambda: [((), 1)])
    with pytest.raises(ValueError):          # func families never merge
        reg.counter_func("tempo_t_cb_total", lambda: [((), 2)])


def test_label_escaping_centralized_roundtrip():
    evil = 'a"} 9\ninjected{x="y'
    assert "\\n" in escape_label(evil) and '\\"' in escape_label(evil)
    reg = Registry()
    reg.counter("tempo_t_total", "h", labels=("tenant",)).inc(1, (evil,))
    text = reg.render()
    # every physical line is metadata or a well-formed sample — nothing
    # the attacker-controlled value injected
    fams = parse_exposition(text)
    (name, labels), v = next(iter(fams["tempo_t_total"]["samples"].items()))
    assert v == 1.0 and name == "tempo_t_total"
    # the parser un-escapes nothing: the escaped form survives intact
    assert "injected" in dict(labels)["tenant"]


def test_histogram_cumulative_buckets_and_exemplar():
    reg = Registry()
    h = reg.histogram("tempo_t_seconds", "latency", labels=("op",),
                      buckets=exponential_buckets(0.001, 2.0, 4))
    h.observe(0.0005, ("read",))             # below first edge
    h.observe(0.003, ("read",))
    h.observe(99.0, ("read",))               # above last edge -> +Inf only
    h.observe(0.1, ("read",), trace_id="ab" * 16)
    snap = h.snapshot(("read",))
    assert snap["count"] == 4
    assert snap["exemplar"][0] == "ab" * 16
    assert h.exemplar(("write",)) is None
    fams = parse_exposition(reg.render())
    samples = fams["tempo_t_seconds"]["samples"]
    inf_key = ("tempo_t_seconds_bucket",
               tuple(sorted((("op", "read"), ("le", "+Inf")))))
    assert samples[inf_key] == 4.0
    count_key = ("tempo_t_seconds_count", (("op", "read"),))
    assert samples[count_key] == 4.0
    # metric_names exposes the derived sample names for the drift gate
    assert "tempo_t_seconds_bucket" in reg.metric_names()


def test_func_families_and_failing_collector():
    state = {"hits": 3}
    reg = Registry()
    reg.counter_func("tempo_t_hits_total",
                     lambda: [((), state["hits"])], help="hits")
    reg.gauge_func("tempo_t_broken",
                   lambda: (_ for _ in ()).throw(RuntimeError("boom")),
                   help="always fails")
    text = reg.render()
    assert "tempo_t_hits_total 3" in text
    # a failing collector contributes nothing but never breaks /metrics
    assert "# TYPE tempo_t_broken gauge" in text
    parse_exposition(text)
    state["hits"] = 7
    assert "tempo_t_hits_total 7" in reg.render()


def test_disabled_registry_is_noop():
    reg = Registry(enabled=False)
    c = reg.counter("tempo_t_total", "h")
    h = reg.histogram("tempo_t_seconds", "h")
    c.inc()
    h.observe(1.0)
    assert c.value() == 0.0 and h.snapshot() is None
    reg.counter_func("tempo_t_cb_total", lambda: [((), 1)])
    assert reg.render() == "" and reg.metric_names() == set()


def test_parser_rejects_nonconformant_text():
    with pytest.raises(ValueError, match="no TYPE"):
        parse_exposition("tempo_x_total 1\n")
    dup = ("# TYPE tempo_x_total counter\n"
           "tempo_x_total 1\ntempo_x_total 2\n")
    with pytest.raises(ValueError, match="duplicate series"):
        parse_exposition(dup)
    bad_labels = ('# TYPE tempo_x_total counter\n'
                  'tempo_x_total{tenant="a} 1\n')
    with pytest.raises(ValueError, match="malformed"):
        parse_exposition(bad_labels)
    noncum = ('# TYPE tempo_h histogram\n'
              'tempo_h_bucket{le="0.1"} 5\n'
              'tempo_h_bucket{le="+Inf"} 3\n'
              'tempo_h_count 3\n')
    with pytest.raises(ValueError, match="not cumulative"):
        parse_exposition(noncum)


def test_route_template_bounds_label_cardinality():
    """Unauthenticated garbage paths must not mint new route labels."""
    from tempo_tpu.app.api import _route_of

    assert _route_of("/v1/traces") == "/v1/traces"
    assert _route_of("/api/traces/abcd1234") == "/api/traces/{id}"
    assert _route_of("/api/v2/search/tag/x/values") == \
        "/api/v2/search/tag/{name}/values"
    assert _route_of("/kv/collectors/i-12") == "/kv/{key}"
    assert _route_of("/internal/ingester/push") == "/internal/ingester/push"
    # attacker-controlled segments collapse to a bounded label
    assert _route_of("/internal/ingester/zzz9") == "/internal/other"
    assert _route_of("/internal/x/y/z/w") == "/internal/other"
    assert _route_of("/wp-admin/setup.php") == "other"


def test_queue_wait_observed_at_claim_exactly_once():
    """The wait histogram observes at CLAIM — the one point common to
    local workers, remote worker streams (which never invoke fn), and
    the issuer's inline fallback — and only for the winning claim."""
    import time as _time

    from tempo_tpu.frontend.frontend import _Job

    reg = Registry()
    h = reg.histogram("tempo_t_wait_seconds", "w")
    wj = _Job(job=None, fn=lambda j: None, spec={"kind": "x"})
    wj.enqueued_at = _time.perf_counter()
    wj.queue_wait = h
    assert wj.try_claim() is True       # remote-stream shape: claim only
    assert wj.try_claim() is False      # losers never double-observe
    assert h.snapshot(())["count"] == 1
    # a job that was never enqueued (inline run) records no wait
    wj2 = _Job(job=None, fn=lambda j: None)
    wj2.run()
    assert h.snapshot(())["count"] == 1


# -- live process: /metrics round-trip, drift gate, exemplars ----------------

def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _mk_app(tmp_path):
    from tempo_tpu.app import App
    from tempo_tpu.app.api import serve
    from tempo_tpu.app.config import Config

    cfg = Config(target="all")
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.generator.localblocks.data_dir = str(tmp_path / "lb")
    cfg.server.http_listen_port = _free_port()
    app = App(cfg)
    app.overrides.set_tenant_patch("single-tenant", {
        "generator": {"processors": ["span-metrics", "local-blocks"]}})
    app.start_loops()
    srv = serve(app, block=False)
    return app, srv, f"http://127.0.0.1:{cfg.server.http_listen_port}"


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    app, srv, base = _mk_app(tmp_path_factory.mktemp("obs"))
    yield app, base
    srv.shutdown()
    app.shutdown()


def _push_one_trace(base: str, tid_hex: str = "ab" * 16) -> None:
    t0 = int((time.time() - 3) * 1e9)
    otlp = {"resourceSpans": [{
        "resource": {"attributes": [
            {"key": "service.name", "value": {"stringValue": "shop"}}]},
        "scopeSpans": [{"spans": [{
            "traceId": tid_hex, "spanId": "cd" * 8, "name": "obs-op",
            "startTimeUnixNano": str(t0),
            "endTimeUnixNano": str(t0 + 1_000_000)}]}]}]}
    req = urllib.request.Request(
        f"{base}/v1/traces", data=json.dumps(otlp).encode(),
        headers={"Content-Type": "application/json"})
    urllib.request.urlopen(req, timeout=10).close()


def test_metrics_exposition_roundtrip(server):
    """`/metrics` is one registry render: HELP/TYPE on every family, no
    duplicate series, parseable end to end — and the duration histograms
    from every instrumented layer are present after real traffic."""
    app, base = server
    _push_one_trace(base)
    now = time.time()
    with urllib.request.urlopen(
            f"{base}/api/metrics/query_range?q=" +
            urllib.parse.quote("{ } | rate()") +
            f"&start={now - 300}&end={now}&step=300", timeout=10) as r:
        assert r.status == 200
    app.ingester.sweep_all()
    app.generator.collect_all()
    app.db.compact_tenant_once("single-tenant")
    with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    fams = parse_exposition(text)       # conformance: raises on violation
    histograms = {n for n, f in fams.items() if f["type"] == "histogram"}
    # >= 8 duration histograms across >= 6 modules (acceptance floor)
    for name in ("tempo_request_duration_seconds",              # app/api
                 "tempo_grpc_request_duration_seconds",         # grpcplane
                 "tempo_distributor_push_duration_seconds",     # distributor
                 "tempo_ingester_cut_duration_seconds",         # ingester
                 "tempo_ingester_flush_duration_seconds",
                 "tempo_query_frontend_request_duration_seconds",  # frontend
                 "tempo_query_frontend_queue_wait_seconds",
                 "tempo_querier_block_scan_duration_seconds",   # querier
                 "tempo_compactor_cycle_duration_seconds",      # compactor/db
                 "tempo_metrics_generator_collect_duration_seconds",
                 "tempo_jax_kernel_duration_seconds"):          # jax runtime
        assert name in histograms, name
    # byte-compat: every pre-registry metric name still present
    for name in ("tempo_distributor_spans_received_total",
                 "tempo_distributor_bytes_received_total",
                 "tempo_distributor_traces_pushed_total",
                 "tempo_distributor_push_failures_total",
                 "tempo_query_frontend_queries_total",
                 "tempo_query_frontend_cache_hits_total",
                 "tempo_query_frontend_cache_misses_total",
                 "tempo_read_plane_fused_metric_blocks_total",
                 "tempo_read_plane_host_metric_blocks_total",
                 "tempo_usage_stats_reports_written_total",
                 "tempo_ingester_live_traces"):
        assert name in fams, name
    # HELP metadata made it out for module-owned families
    assert fams["tempo_distributor_spans_received_total"]["help"]
    # traffic actually landed in the request-duration histogram
    dur = fams["tempo_request_duration_seconds"]["samples"]
    assert any(n == "tempo_request_duration_seconds_count" and v > 0
               for (n, _l), v in dur.items())
    # jit-compile counters from the instrumented spanmetrics path
    assert "tempo_jax_jit_compile_total" in fams
    assert any(v > 0 for (n, _l), v in
               fams["tempo_jax_jit_compile_total"]["samples"].items())


def test_usage_metrics_share_exposition_writer(server):
    """`/usage_metrics` renders through the same obs writer: HELP/TYPE
    lines, centralized escaping, parseable."""
    app, base = server
    _push_one_trace(base)
    with urllib.request.urlopen(f"{base}/usage_metrics", timeout=10) as r:
        text = r.read().decode()
    fams = parse_exposition(text)
    assert "tempo_usage_tracker_bytes_received_total" in fams
    assert fams["tempo_usage_tracker_bytes_received_total"]["type"] == \
        "counter"


def test_ops_metric_names_registered(server, tmp_path):
    """The drift gate: every tempo_* name referenced by alerts.yaml and
    the dashboards is registered; an aspirational name is caught."""
    import os

    import tempo_tpu.app.api as api_mod
    from tempo_tpu.obs import drift
    from tempo_tpu.obs.jaxruntime import RUNTIME

    app, _base = server
    ops_dir = os.path.join(os.path.dirname(api_mod.__file__),
                           "..", "..", "operations")
    refs = drift.referenced_metric_names(ops_dir)
    assert "tempo_distributor_push_failures_total" in refs
    assert drift.check_drift(ops_dir, [app.obs, RUNTIME]) == []
    # negative: a made-up metric in an alert expression must be flagged
    bogus = tmp_path / "ops"
    bogus.mkdir()
    (bogus / "alerts.yaml").write_text(
        "expr: rate(tempo_nonexistent_total[5m]) > 0\n")
    problems = drift.check_drift(str(bogus), [app.obs, RUNTIME])
    assert len(problems) == 1 and "tempo_nonexistent_total" in problems[0]
    # histogram PromQL suffixes (_bucket/_sum/_count) resolve via the
    # family's derived names
    (bogus / "alerts.yaml").write_text(
        "expr: rate(tempo_request_duration_seconds_bucket[5m])\n")
    assert drift.check_drift(str(bogus), [app.obs, RUNTIME]) == []


def test_bail_causes_documented(tmp_path):
    """The fallback-cause gate: every `_bail(...)` string in
    device_scan.py has a row in the runbook's cause table, and an
    undocumented cause is caught."""
    import os
    import shutil

    import tempo_tpu.app.api as api_mod
    from tempo_tpu.obs import drift

    ops_dir = os.path.abspath(os.path.join(
        os.path.dirname(api_mod.__file__), "..", "..", "operations"))
    assert drift.check_bail_causes(ops_dir) == []
    # negative: strip one documented cause from a runbook copy
    repo2 = tmp_path / "repo"
    (repo2 / "operations").mkdir(parents=True)
    (repo2 / "tempo_tpu" / "block").mkdir(parents=True)
    shutil.copy(
        os.path.join(os.path.dirname(ops_dir),
                     "tempo_tpu", "block", "device_scan.py"),
        repo2 / "tempo_tpu" / "block" / "device_scan.py")
    runbook = open(os.path.join(ops_dir, "runbook.md")).read()
    (repo2 / "operations" / "runbook.md").write_text(
        runbook.replace("| `grid_size` |", "| `gridsize_typo` |"))
    problems = drift.check_bail_causes(str(repo2 / "operations"))
    assert len(problems) == 1 and "grid_size" in problems[0]


def test_slow_request_exemplar_carries_trace_id(server):
    """A frontend op that misses its SLO stamps the active self-tracing
    span's trace id onto the histogram observation (the exemplar bridge:
    p99 spike -> concrete slow trace)."""
    from tempo_tpu.frontend.slos import SLOConfig
    from tempo_tpu.utils import tracing

    app, _base = server
    tracer = tracing.SelfTracer("http://127.0.0.1:1", flush_interval_s=3600)
    prev = tracing.tracer()
    app.frontend.slos.per_op["search"] = SLOConfig(duration_slo_s=1e-9)
    try:
        tracing.install(tracer)
        with tracing.span("slow-query") as s:
            app.frontend.search("single-tenant", "{ }", limit=5)
        ex = app.frontend.op_duration.exemplar(("search",))
        assert ex is not None and ex[0] == s.trace_id.hex()
        # a within-SLO op does not overwrite the exemplar with None
        app.frontend.slos.per_op["search"] = SLOConfig()
        app.frontend.search("single-tenant", "{ }", limit=5)
        assert app.frontend.op_duration.exemplar(("search",))[0] == \
            s.trace_id.hex()
    finally:
        app.frontend.slos.per_op.pop("search", None)
        tracing.install(prev)
        tracer.shutdown()


# -- SelfTracer dogfood: own spans queryable by trace id ---------------------

def test_dogfood_spans_queryable_by_trace_id(tmp_path):
    """Dogfood mode: the app's own spans export over OTLP/HTTP into this
    very process's distributor and are queryable BY TRACE ID under the
    self-tenant, like any user trace."""
    from tempo_tpu.app import App
    from tempo_tpu.app.api import serve
    from tempo_tpu.app.config import Config
    from tempo_tpu.utils import tracing

    port = _free_port()
    cfg = Config(target="all")
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.generator.localblocks.data_dir = str(tmp_path / "lb")
    cfg.server.http_listen_port = port
    cfg.self_tracing_endpoint = f"http://127.0.0.1:{port}"
    app = App(cfg)
    app.start_loops()
    srv = serve(app, block=False)
    base = f"http://127.0.0.1:{port}"
    try:
        assert isinstance(tracing.tracer(), tracing.SelfTracer)
        with tracing.span("obs-dogfood-root") as root:
            app.frontend.search("single-tenant", "{ }", limit=5)
            tid_hex = root.trace_id.hex()
        assert tracing.tracer().flush() > 0    # export into ourselves
        req = urllib.request.Request(
            f"{base}/api/traces/{tid_hex}",
            headers={"X-Scope-OrgID": app.cfg.self_tracing_tenant})
        with urllib.request.urlopen(req, timeout=10) as r:
            got = json.loads(r.read())
        assert got["trace_id"] == tid_hex
        names = {s["name"] for s in got["spans"]}
        assert "obs-dogfood-root" in names
        assert "frontend.Search" in names      # child span, same trace
    finally:
        srv.shutdown()
        app.shutdown()


# -- concurrent record + scrape (the device-time ledger adds a
#    high-frequency writer; a render racing a resizing series dict must
#    neither crash nor emit non-conformant text) --------------------------

def test_concurrent_record_and_scrape_conformant():
    import threading

    from tempo_tpu.obs import devtime

    reg = Registry()
    c = reg.counter("tempo_t_race_total", "r", labels=("k",))
    g = reg.gauge("tempo_t_race_depth", "r", labels=("k",))
    h = reg.histogram("tempo_t_race_seconds", "r", labels=("k",),
                      buckets=(0.1, 1.0, 10.0))
    led = devtime.DeviceTimeLedger()

    def by_ledger_key():
        return [(k, v / 1e9) for k, v in led._rows("wall_ns")]

    reg.counter_func(
        "tempo_t_race_ledger_seconds_total", by_ledger_key,
        labels=("kernel", "bucket", "class", "shard"))
    stop = threading.Event()
    errors: list = []

    def writer(i: int) -> None:
        n = 0
        while not stop.is_set():
            n += 1
            label = (f"k{n % 17}",)
            try:
                c.inc(1, label)
                g.set(n, label)
                h.observe(n % 13 / 3.0, label)
                led.record_batch(kernel=f"k{n % 17}", bucket=64 << (n % 3),
                                 prio=n % 3, shards=n % 2, wall_ns=1000,
                                 rows=10, padded_rows=3, queue_wait_ns=5,
                                 h2d_bytes=80,
                                 tenant_rows={f"t{i}": 7, "s": 3})
            except Exception as e:       # noqa: BLE001 — recorded
                errors.append(e)
                return

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    try:
        deadline = time.time() + 1.0
        renders = 0
        while time.time() < deadline:
            parse_exposition(reg.render())      # raises on nonconformance
            renders += 1
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
    assert not errors
    assert renders > 10
    # the ledger's tenant attribution stays consistent under the race
    total = led.total_device_ns()
    assert total > 0
    assert abs(total - sum(led.tenant_device_ns().values())) \
        <= total * 0.05


def test_compile_cache_is_placed_from_outside_or_inside_the_checkout(
        monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and no code sets another directory;
    unset, the cache is ONE fixed directory inside the checkout (the path
    is part of the cache key: a temp name would never hit)."""
    import os

    import jax

    from tempo_tpu.obs import jaxruntime

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert jaxruntime.configure_compile_cache() == "/placed/outside"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert jaxruntime.configure_compile_cache() == want
        assert jaxruntime.configure_compile_cache() == want    # idempotent
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
