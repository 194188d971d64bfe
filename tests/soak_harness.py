"""The soak rig `test_devtime.py::test_soak_smoke` drives: a static-window
arm, then a `sched.tuning: auto` arm, against a real in-memory App under
the same offered load. It lives here for that one test, and goes when
ROADMAP Design 4 decides the tuner."""

from __future__ import annotations

import os
import tempfile
import threading
import time

import numpy as np


def _soak_payload(seed: int, n_spans: int) -> bytes:
    """One tenant's pre-encoded OTLP payload: a few services × ops with
    a lognormal latency body (16-ish series per tenant against the
    shrunk per-tenant budget). Timestamps are stamped once; the soak
    rig widens the generator slack so pre-encoded payloads stay valid
    for the whole arm — encode cost must not gate the offered load."""
    from tempo_tpu.model.otlp import encode_spans_otlp

    t0_ns = int(time.time() * 1e9)
    rng = np.random.default_rng(seed)
    tids = rng.integers(0, 256, (n_spans, 16), dtype=np.uint8)
    durs = (rng.lognormal(-4.0, 1.0, n_spans) * 1e9).astype(np.int64)
    return encode_spans_otlp([
        {"trace_id": tids[i].tobytes(),
         "span_id": bytes([i % 251 + 1]) * 8,
         "name": f"op-{i % 4}", "service": f"svc-{i % 4}",
         "start_unix_nano": t0_ns + i,
         "end_unix_nano": t0_ns + i + int(durs[i]),
         "status_code": 2 if i % 64 == 0 else 0,
         "res_attrs": {"service.name": f"svc-{i % 4}"}}
        for i in range(n_spans)])


def _jit_compiles_total(prefix: str = "") -> float:
    from tempo_tpu.obs.jaxruntime import JIT_COMPILES
    with JIT_COMPILES._lock:
        return float(sum(v for k, v in JIT_COMPILES._series.items()
                         if k and k[0].startswith(prefix)))


def _soak_teardown(app, srv) -> None:
    """Stop a soak rig WITHOUT the graceful drain: `App.shutdown()`
    flushes every tenant's live traces and collects every registry —
    correct for production, minutes of wall for thousands of throwaway
    tenants whose stats the arm already extracted. Threads are
    stop-aware daemons; the state dies with the reference."""
    srv.shutdown()
    app.ready = False
    app._stop.set()
    for mod in (app.ingester, app.generator, app.frontend):
        stop = getattr(mod, "_stop", None)
        if stop is not None:
            stop.set()
    for mod in (app.ingester, app.generator):
        for t in getattr(mod, "_threads", ()) or ():
            t.join(timeout=5)
    if app.frontend is not None:
        app.frontend.shutdown()
    if app.distributor is not None:
        app.distributor.forwarders.shutdown()
    if app.db is not None:
        app.db.shutdown()


def _soak_prewarm(spans_per_push: int) -> None:
    """One throwaway rig before the arms: compiles are PROCESS-wide
    (module-level jitted kernels, shared shape caches), so first-use
    compiles — the fused update at every pow-2 bucket the coalescer can
    produce for this load, the read path's block-scan/metrics kernels —
    must happen here, not inside whichever arm runs first (arm-order
    bias) or mid-steady (a multi-second XLA compile on the worker
    thread reads as a latency cliff that has nothing to do with
    tuning). Uses the same per-tenant limits as the arms so state
    shapes match the jit cache keys."""
    import socket

    from tempo_tpu import sched
    from tempo_tpu.app import App
    from tempo_tpu.app.api import serve
    from tempo_tpu.app.config import Config
    from tempo_tpu.client import Client
    from tempo_tpu.vulture.__main__ import run_cycle
    import random as _random

    sched.reset()
    tmp = tempfile.mkdtemp(prefix="tempo-soak-warm-")
    cfg = Config()
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = os.path.join(tmp, "wal")
    cfg.generator.localblocks.data_dir = os.path.join(tmp, "lb")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    cfg.server.http_listen_port = s.getsockname()[1]
    s.close()
    cfg.usage_stats_enabled = False
    cfg.overrides_defaults.generator.processors = ("span-metrics",)
    cfg.overrides_defaults.generator.max_active_series = 64
    cfg.overrides_defaults.generator.ingestion_time_range_slack_s = 7200.0
    app = App(cfg)
    app.overrides.set_tenant_patch("warm-lb", {
        "generator": {"processors": ["span-metrics", "local-blocks"]}})
    app.start_loops()
    srv = serve(app, block=False)
    base = f"http://127.0.0.1:{cfg.server.http_listen_port}"
    # every pow-2 fused-update bucket a merged window can produce for
    # payloads of this size
    for n in (spans_per_push, 2 * spans_per_push, 4 * spans_per_push,
              8 * spans_per_push):
        # "warm-sm" rides the arms' own route (span-metrics alone: the
        # scheduler's packed windows), which "warm-lb" does not. Each
        # push lands ALONE: behind a cold compile (an empty persistent
        # cache) the later ones would queue and merge into one bigger
        # bucket, leaving theirs to compile mid-steady
        for tenant in ("warm-lb", "warm-sm"):
            app.distributor.push_otlp(tenant, _soak_payload(991 + n, n))
            sched.flush()
    c = Client(base, tenant="warm-lb")
    try:
        c.search('{ resource.service.name = "svc-0" }', limit=5)
        now = time.time()
        c.query_range("{ } | rate()", now - 120, now, step_s=30)
        run_cycle(Client(base, tenant="vulture"),
                  _random.Random(0), read_delay_s=0.2)
        # collection + block-flush kernels compile on FIRST use: the
        # arms run real collection ticks mid-steady, so those compiles
        # must land here, not there
        app.generator.collect_all()
        app.ingester.flush_all()
    except Exception:
        pass              # prewarm is best-effort; arms measure for real
    _soak_teardown(app, srv)
    sched.reset()


def _soak_arm(tuning: str, *, n_tenants: int, warm_s: float,
              steady_s: float, spans_per_push: int, duty: float,
              read_every_s: float, vulture_every_s: float,
              seed: int) -> dict:
    """One soak arm: a full in-memory App (distributor → ingester +
    generator, frontend + querier for reads), `n_tenants` simulated
    tenants pushed round-robin through the real OTLP decode path at a
    self-paced `duty` fraction of the host's push capacity, a reader
    keeping the frontend/read-plane caches hot, and a vulture
    write-read-verify canary over the public HTTP API. Steady-phase
    gates are measured from the device-time ledger surfaces."""
    import socket
    import jax  # noqa: F401 — ensure backend is up before timing

    from tempo_tpu import sched
    from tempo_tpu.app import App
    from tempo_tpu.app.api import serve
    from tempo_tpu.app.config import Config
    from tempo_tpu.client import Client
    from tempo_tpu.distributor.distributor import RateLimited
    from tempo_tpu.obs import devtime
    from tempo_tpu.vulture.__main__ import run_cycle

    sched.reset()
    tmp = tempfile.mkdtemp(prefix="tempo-soak-")
    cfg = Config()
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = os.path.join(tmp, "wal")
    cfg.generator.localblocks.data_dir = os.path.join(tmp, "lb")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    cfg.server.http_listen_port = s.getsockname()[1]
    s.close()
    # traces are cut by MAX AGE (one storm per trace_live_s), not by
    # idle: at thousands of tenants an idle-based cut fires a full
    # sort+combine+WAL sweep after EVERY round-robin pass, and on a
    # 2-core host that storm starves the writer to a crawl — age-based
    # cutting keeps the ingester persistence pipeline in the soak (it
    # runs at least twice per arm) without letting it BE the workload
    cfg.ingester.instance.trace_idle_s = 30.0
    cfg.ingester.instance.trace_live_s = 45.0
    cfg.usage_stats_enabled = False
    # thousands of tenants fit one host only with a per-tenant series
    # budget; pre-encoded payloads need a wide ingestion slack
    cfg.overrides_defaults.generator.processors = ("span-metrics",)
    cfg.overrides_defaults.generator.max_active_series = 64
    cfg.overrides_defaults.generator.ingestion_time_range_slack_s = 7200.0
    # collection ticks run for real mid-soak (their per-tenant
    # sched.flush barriers are part of the production load shape), but
    # at thousands of tenants a 15s cadence would flush the coalescer
    # near-continuously and erase the very window dynamics under test
    cfg.overrides_defaults.generator.collection_interval_s = 60.0
    cfg.sched.tuning = tuning
    app = App(cfg)
    tenants = [f"soak-{i}" for i in range(n_tenants)]
    # a subset additionally runs local-blocks so TraceQL metrics reads
    # (query_range → device read plane, both cache tiers) have blocks
    # to scan; every tenant still serves search from the ingester
    metrics_tenants = tenants[:min(32, max(1, n_tenants // 64))]
    for t in metrics_tenants:
        app.overrides.set_tenant_patch(t, {
            "generator": {"processors": ["span-metrics", "local-blocks"]}})
    app.start_loops()
    srv = serve(app, block=False)
    base = f"http://127.0.0.1:{cfg.server.http_listen_port}"
    payloads = {t: _soak_payload(seed + i, spans_per_push)
                for i, t in enumerate(tenants)}

    stop = threading.Event()
    lock = threading.Lock()
    stats = {"pushes": 0, "spans": 0, "rejected": 0, "reads_ok": 0,
             "read_errors": 0, "push_errors": 0, "push_error": ""}
    vult = {"cycles": 0, "written": 0, "read_ok": 0, "read_missing": 0,
            "search_ok": 0, "search_missing": 0, "errors": 0}

    def writer() -> None:
        i = 0
        while not stop.is_set():
            t = tenants[i % n_tenants]
            i += 1
            t0 = time.perf_counter()
            try:
                app.distributor.push_otlp(t, payloads[t])
                with lock:
                    stats["pushes"] += 1
                    stats["spans"] += spans_per_push
            except RateLimited:
                with lock:
                    stats["rejected"] += 1
            except Exception as e:       # noqa: BLE001 — must not die
                # a dead writer silently zeroes the offered load and
                # every gate downstream measures noise: count, remember
                # the first cause, keep offering
                with lock:
                    stats["push_errors"] += 1
                    if not stats["push_error"]:
                        stats["push_error"] = repr(e)[:300]
            # self-pacing: hold the offered load at `duty` of capacity
            # regardless of host speed — overload is the saturation
            # stage's job; the soak measures the tuned window's latency
            # effect below the backpressure point
            dt = time.perf_counter() - t0
            pause = dt * (1.0 - duty) / max(duty, 0.05)
            if pause > 0:
                stop.wait(pause)

    def reader() -> None:
        import random as _random
        rngr = _random.Random(seed + 1)
        cl: dict = {}
        while not stop.is_set():
            stop.wait(read_every_s)
            if stop.is_set():
                return
            t = tenants[rngr.randrange(n_tenants)]
            c = cl.get(t)
            if c is None:
                c = cl[t] = Client(base, tenant=t)
            mt = metrics_tenants[rngr.randrange(len(metrics_tenants))]
            m = cl.get(mt)
            if m is None:
                m = cl[mt] = Client(base, tenant=mt)
            try:
                c.search('{ resource.service.name = "svc-0" }', limit=5)
                now = time.time()
                # a NARROW metrics window: the read load must stay
                # roughly constant as the soak accumulates data, or the
                # reader degenerates into one ever-slower query hogging
                # the GIL and the arms measure read growth, not tuning
                m.query_range("{ } | rate()", now - 30, now, step_s=15)
                with lock:
                    stats["reads_ok"] += 1
            except Exception:
                with lock:
                    stats["read_errors"] += 1

    def vulture_loop() -> None:
        import random as _random
        rngv = _random.Random(seed + 2)
        c = Client(base, tenant="vulture")
        while not stop.is_set():
            stop.wait(vulture_every_s)
            if stop.is_set():
                return
            try:
                res = run_cycle(c, rngv, read_delay_s=0.3)
            except Exception:
                with lock:
                    vult["errors"] += 1
                continue
            with lock:
                vult["cycles"] += 1
                for k, v in res.items():
                    vult[k] = vult.get(k, 0) + v

    threads = [threading.Thread(target=f, daemon=True)
               for f in (writer, reader, vulture_loop)]
    for th in threads:
        th.start()

    # warm phase: at least warm_s AND one full pass over every tenant
    # (instance + device-state creation, first-shape jit compiles)
    warm_t0 = time.time()
    while time.time() - warm_t0 < warm_s or stats["pushes"] < n_tenants:
        time.sleep(0.05)
        if time.time() - warm_t0 > warm_s + 600:
            break                       # stuck rig: report, don't hang

    # steady-state recompile gate, scoped to the TUNING LOOP's own
    # dispatch: spanmetrics jit compiles + new (kernel, bucket) shape
    # signatures for the fused-update kernel — auto mode must not
    # introduce shapes static mode never traced (read-path first-use
    # compiles are warmed separately and are not what tuning can break)
    kernel = ("spanmetrics_fused_update",)
    snap0 = devtime.INGEST_LATENCY.snapshot(kernel) or {"buckets": []}
    jit0 = _jit_compiles_total("spanmetrics")
    warm0 = app.sched.bucket_warmups.get(kernel[0], 0)
    with lock:
        pushes0, spans0 = stats["pushes"], stats["spans"]
    t_steady = time.time()
    while time.time() - t_steady < steady_s:
        time.sleep(0.05)
    steady_wall = time.time() - t_steady
    snap1 = devtime.INGEST_LATENCY.snapshot(kernel) or {"buckets": []}
    jit1 = _jit_compiles_total("spanmetrics")
    warm1 = app.sched.bucket_warmups.get(kernel[0], 0)
    with lock:
        pushes1, spans1 = stats["pushes"], stats["spans"]
    stop.set()
    for th in threads:
        th.join(timeout=30)
    sched.flush()

    b0 = snap0["buckets"] or [0] * (len(devtime.INGEST_LATENCY.edges) + 1)
    b1 = snap1["buckets"] or [0] * (len(devtime.INGEST_LATENCY.edges) + 1)
    delta = [max(a - b, 0) for a, b in zip(b1, b0)]
    p99_s = devtime.quantile_from_counts(devtime.INGEST_LATENCY.edges,
                                         delta, 0.99)
    p50_s = devtime.quantile_from_counts(devtime.INGEST_LATENCY.edges,
                                         delta, 0.50)

    total_ns = devtime.LEDGER.total_device_ns()
    tenant_ns = devtime.LEDGER.tenant_device_ns()
    attr_gap = abs(total_ns - sum(tenant_ns.values())) / max(total_ns, 1)
    pairs = devtime.COST_MODEL.warm_pairs("spanmetrics_fused_update")
    # accuracy gate over pairs carrying real traffic (≥5% of the
    # kernel's dispatches): the tuner's choices are dominated by them;
    # a 50-sample tail pair fit from contended vulture dribble says
    # nothing about the model
    rows_by_pair = {
        (r["kernel"], r["bucket"]): r for r in devtime.COST_MODEL.status()
        if r["kernel"] == "spanmetrics_fused_update"}
    total_samples = sum(r["samples"] for r in rows_by_pair.values()) or 1
    errs = [r["typical_error"] for (k, b), r in rows_by_pair.items()
            if r["warm"] and r["typical_error"] is not None
            and r["samples"] >= 0.05 * total_samples]
    out = {
        "tuning": tuning,
        "ingest_p99_ms": round(p99_s * 1e3, 3),
        "ingest_p50_ms": round(p50_s * 1e3, 3),
        "steady_spans_per_sec": (spans1 - spans0) / steady_wall,
        "steady_pushes": pushes1 - pushes0,
        "total_pushes": stats["pushes"],
        "rejected_pushes": stats["rejected"],
        "steady_recompiles": int(jit1 - jit0),
        "steady_bucket_warmups": int(warm1 - warm0),
        "reads_ok": stats["reads_ok"],
        "read_errors": stats["read_errors"],
        "push_errors": stats["push_errors"],
        "push_error": stats["push_error"],
        "vulture": dict(vult),
        "device_seconds": round(total_ns / 1e9, 3),
        "tenants_attributed": len(tenant_ns),
        "attribution_gap": round(attr_gap, 5),
        "cost_model_warm_pairs": len(pairs),
        "cost_model_max_rel_err": round(max(errs), 4) if errs else None,
        "tuning_active": app.sched.tuning_active(),
        "tuned_window_ms": {k: round(v, 3)
                            for k, v in app.sched._tuner.windows_ms()},
    }
    _soak_teardown(app, srv)
    sched.reset()
    return out


def soak_run(*, n_tenants: int, warm_s: float, steady_s: float,
              spans_per_push: int = 128, duty: float = 0.65,
              read_every_s: float = 0.3, vulture_every_s: float = 5.0,
              seed: int = 0, smoke: bool = False) -> dict:
    """Static-window arm, then `tuning: auto` arm, same offered
    workload; gates per ISSUE 8: tuned p99 ≤ static p99, tuned
    throughput ≥ static (0.95 tolerance — single-pass arms on a
    contended host), zero steady-state recompiles, cost-model relative
    error ≤ 25% on warm pairs, per-tenant attribution within 5%, and a
    clean vulture ledger. `smoke=True` (the tier-1 variant) asserts the
    machinery gates only — arms too short for a fair p99 comparison."""
    kw = dict(n_tenants=n_tenants, warm_s=warm_s, steady_s=steady_s,
              spans_per_push=spans_per_push, duty=duty,
              read_every_s=read_every_s, vulture_every_s=vulture_every_s,
              seed=seed)
    _soak_prewarm(spans_per_push)
    static = _soak_arm("static", **kw)
    auto = _soak_arm("auto", **kw)
    tp_ratio = auto["steady_spans_per_sec"] \
        / max(static["steady_spans_per_sec"], 1e-9)
    v = {k: static["vulture"].get(k, 0) + auto["vulture"].get(k, 0)
         for k in set(static["vulture"]) | set(auto["vulture"])}
    gates = {
        "soak_gate_recompiles": static["steady_recompiles"] == 0
        and auto["steady_recompiles"] == 0
        and static["steady_bucket_warmups"] == 0
        and auto["steady_bucket_warmups"] == 0,
        # smoke arms are too short for the error EWMA to settle: the
        # tier-1 variant gates on the model being warm at all; the full
        # soak holds warm pairs to the 25% prediction-error bound
        "soak_gate_cost_model": auto["cost_model_warm_pairs"] > 0
        and (smoke or (auto["cost_model_max_rel_err"] or 0.0) <= 0.25),
        "soak_gate_attribution": static["attribution_gap"] <= 0.05
        and auto["attribution_gap"] <= 0.05,
        "soak_gate_tuning_active": bool(auto["tuning_active"]),
        "soak_gate_vulture": v.get("errors", 0) == 0
        and v.get("read_missing", 0) == 0
        and v.get("search_missing", 0) == 0 and v.get("cycles", 0) > 0,
        "soak_gate_reads": static["read_errors"] == 0
        and auto["read_errors"] == 0,
        "soak_gate_writes": static["push_errors"] == 0
        and auto["push_errors"] == 0,
    }
    if not smoke:
        gates["soak_gate_p99"] = \
            auto["ingest_p99_ms"] <= static["ingest_p99_ms"]
        gates["soak_gate_throughput"] = tp_ratio >= 0.95
    return {
        "soak_static_p99_ms": static["ingest_p99_ms"],
        "soak_tuned_p99_ms": auto["ingest_p99_ms"],
        "soak_static_p50_ms": static["ingest_p50_ms"],
        "soak_tuned_p50_ms": auto["ingest_p50_ms"],
        "soak_static_spans_per_sec": round(
            static["steady_spans_per_sec"], 1),
        "soak_tuned_spans_per_sec": round(auto["steady_spans_per_sec"], 1),
        "soak_throughput_ratio": round(tp_ratio, 4),
        "soak_n_tenants": n_tenants,
        "soak_steady_s": steady_s,
        "soak_tenants_attributed": auto["tenants_attributed"],
        "soak_attribution_gap": max(static["attribution_gap"],
                                    auto["attribution_gap"]),
        "soak_cost_model_max_rel_err": auto["cost_model_max_rel_err"],
        "soak_cost_model_warm_pairs": auto["cost_model_warm_pairs"],
        "soak_tuned_window_ms": auto["tuned_window_ms"],
        "soak_static_recompiles": static["steady_recompiles"],
        "soak_tuned_recompiles": auto["steady_recompiles"],
        "soak_rejected_pushes": static["rejected_pushes"]
        + auto["rejected_pushes"],
        "soak_push_errors": static["push_errors"] + auto["push_errors"],
        "soak_push_error": static["push_error"] or auto["push_error"],
        "soak_vulture": v,
        **gates,
        "soak_accept_ok": all(gates.values()),
    }
