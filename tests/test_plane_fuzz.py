"""Randomized device-vs-host parity gate for the DEFAULT read path.

Round-4 weak #6: the fused device plane is the default product path, but
its parity evidence was a fixed query list. This property test draws
random TraceQL queries from the AST grammar (filters over every column
family the plane adopts — int/float/string/missing attrs, intrinsics,
boundary literals, nil/boolean forms, OR-fallback shapes — times every
metrics kind and group-by arity) against randomized blocks, asserting the
device plane and the host engine agree on BOTH search results and metric
grids. The seed is printed on failure and can be pinned via
TEMPO_FUZZ_SEED; case count via TEMPO_FUZZ_CASES (default sized to keep
the whole module under a minute in CI).
"""

from __future__ import annotations

import math
import os
import random

import numpy as np
import pytest

from tempo_tpu.backend.mem import MemBackend
from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig
from tempo_tpu.traceql.engine_metrics import QueryRangeRequest

T0 = 1_700_000_000
SEED = int(os.environ.get("TEMPO_FUZZ_SEED",
                          random.SystemRandom().randrange(1 << 30)))
N_QUERIES = int(os.environ.get("TEMPO_FUZZ_CASES", 40))

# -- random query grammar ----------------------------------------------------

_DUR_LITS = ["1ns", "50ms", "123ms", "16777216ns", "16777217ns", "1s", "2s"]
_NUM_OPS = ["=", "!=", ">", ">=", "<", "<="]
_STR_OPS = ["=", "!=", "=~", "!~"]


def _pred(rng: random.Random) -> str:
    kind = rng.choice(["dur", "name", "svc", "int_attr", "float_attr",
                       "str_attr", "missing", "kindp", "status", "nil",
                       "bool_lit"])
    if kind == "dur":
        return f"duration {rng.choice(_NUM_OPS)} {rng.choice(_DUR_LITS)}"
    if kind == "name":
        return (f'name {rng.choice(_STR_OPS)} '
                f'"op-{rng.randrange(6)}{rng.choice(["", ".*"])}"')
    if kind == "svc":
        return (f'resource.service.name {rng.choice(["=", "!="])} '
                f'"svc-{rng.randrange(4)}"')
    if kind == "int_attr":
        lit = rng.choice([200, 204, 350, 499, 500, 0, -1])
        return f"span.http.status_code {rng.choice(_NUM_OPS)} {lit}"
    if kind == "float_attr":
        lit = rng.choice([0.5, 1.5, -2.25, 0.0, 3.0, 2, 0.1])
        return f"span.ratio {rng.choice(_NUM_OPS)} {lit}"
    if kind == "str_attr":
        return f'span.region {rng.choice(_STR_OPS)} "r{rng.randrange(3)}"'
    if kind == "missing":
        return f"span.nothere {rng.choice(_NUM_OPS)} 5"
    if kind == "kindp":
        return f'kind = {rng.choice(["server", "client", "internal"])}'
    if kind == "status":
        return f'status {rng.choice(["=", "!="])} error'
    if kind == "nil":
        attr = rng.choice(["span.ratio", "span.region", "span.nothere"])
        return f'{attr} {rng.choice(["=", "!="])} nil'
    return rng.choice(["true", "false"])


def _filter(rng: random.Random) -> str:
    n = rng.choice([0, 1, 1, 2, 2, 3])
    if n == 0:
        return "{ }"
    if n >= 3 and rng.random() < 0.3:
        # mixed AND/OR trees: NOT pure disjunctions — the fused plane must
        # refuse these (a superset mask would silently corrupt metrics;
        # the round-5 review found exactly this via crafted dedup shapes)
        a, b, c = (_pred(rng) for _ in range(3))
        return rng.choice([f"{{ {a} && ({b} || {c}) }}",
                           f"{{ ({a} && {b}) || {c} }}",
                           f"{{ {a} || ({a} && {b}) }}"])
    op = " && " if rng.random() < 0.7 else " || "
    return "{ " + op.join(_pred(rng) for _ in range(n)) + " }"


def _metrics(rng: random.Random) -> str:
    # 3-key arity exercises the composed mixed-radix group codes
    by_keys = rng.sample(["resource.service.name", "name", "span.region",
                          "kind"], k=rng.choice([0, 1, 1, 2, 3]))
    by = f" by ({', '.join(by_keys)})" if by_keys else ""
    agg = rng.choice(["rate()", "count_over_time()",
                      "min_over_time(duration)", "max_over_time(duration)",
                      "sum_over_time(duration)", "avg_over_time(duration)",
                      "sum_over_time(span.http.status_code)",
                      "avg_over_time(span.ratio)",
                      "quantile_over_time(duration, .5, .99)",
                      "histogram_over_time(duration)"])
    return f"{_filter(rng)} | {agg}{by}"


# -- random block ------------------------------------------------------------

@pytest.fixture(scope="module")
def fuzz_dbs():
    rng = np.random.default_rng(SEED)
    be = MemBackend()
    dev = TempoDB(be, be, TempoDBConfig(device_plane=True))
    host = TempoDB(be, be, TempoDBConfig(device_plane=False))
    n_blocks = 2
    for b in range(n_blocks):
        traces = []
        for i in range(1500):
            tid = rng.bytes(16)
            start = int((T0 + b * 400 + float(rng.random()) * 390) * 1e9)
            attrs = {}
            if rng.random() < 0.8:
                attrs["http.status_code"] = int(rng.integers(200, 501))
            if rng.random() < 0.6:
                attrs["ratio"] = float(rng.choice(
                    [0.5, 1.5, -2.25, 0.0, 3.0, 0.1, 2.0]))
            if rng.random() < 0.7:
                attrs["region"] = f"r{int(rng.integers(0, 3))}"
            traces.append((tid, [{
                "trace_id": tid, "span_id": rng.bytes(8),
                "name": f"op-{int(rng.integers(0, 6))}",
                "service": f"svc-{int(rng.integers(0, 4))}",
                "kind": int(rng.integers(0, 6)),
                "status_code": int(rng.integers(0, 3)),
                "start_unix_nano": start,
                "end_unix_nano": start + int(rng.choice(
                    [1, 50_000_000, 123_000_000, 16_777_216, 16_777_217,
                     int(rng.lognormal(16, 1.5))])),
                "attrs": attrs}]))
        traces.sort(key=lambda t: t[0])
        dev.write_block("t", traces, replication_factor=1)
    dev.poll_now()
    host.poll_now()
    return dev, host


def _smap(series) -> dict:
    return {tuple(sorted((str(k), str(v)) for k, v in s.labels)):
            np.nan_to_num(np.asarray(s.samples, np.float64))
            for s in series}


def test_fuzz_query_range_parity(fuzz_dbs):
    dev, host = fuzz_dbs
    rng = random.Random(SEED)
    for case in range(N_QUERIES):
        q = _metrics(rng)
        # random windows: offset starts exercise the q_steps/frac split of
        # the exact bucketing, sub-windows exercise the clip terms
        w0 = T0 + rng.choice([0, -120, 37, 333, 701])
        w1 = w0 + rng.choice([900, 301, 1500, 83])
        req = QueryRangeRequest(query=q, start_ns=int(w0 * 1e9),
                                end_ns=int(w1 * 1e9),
                                step_ns=int(rng.choice([30, 60, 300, 7])
                                            * 1e9))
        ctx = f"seed={SEED} case={case} query={q!r}"
        try:
            a = _smap(dev.query_range("t", req))
            b = _smap(host.query_range("t", req))
        except Exception as e:
            raise AssertionError(f"{ctx}: {e}") from e
        assert set(a) == set(b), f"{ctx}: series sets differ " \
            f"(only-dev={set(a) - set(b)}, only-host={set(b) - set(a)})"
        for k in b:
            np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-4,
                                       err_msg=f"{ctx} series={k}")


def test_fuzz_moments_tier_query_range_parity(fuzz_dbs):
    """The warm-read differential arm: the SAME random grammar (kind ×
    by-arity × predicates) under the moments query tier. Gates: count
    kinds stay bit-identical between the fused plane and the host
    engine; quantile series (solved off the moment rows both ways) stay
    inside the tier's error envelope; and the run must actually ride
    the moments grids (fused blocks move)."""
    from tempo_tpu.ops import moments as M

    dev, host = fuzz_dbs
    rng = random.Random(SEED + 11)
    fused0 = dev.plane_stats.get("fused_metric_blocks", 0)
    quantile_fused = 0
    # case 0 is pinned: a fused-eligible quantile shape, so the
    # rode-the-moments-grid assertion below cannot depend on the draw
    pinned = ("{ } | quantile_over_time(duration, .5, .99)"
              " by (resource.service.name)")
    with M.use_query_tier("moments"):
        for case in range(max(N_QUERIES // 4, 8)):
            q = pinned if case == 0 else _metrics(rng)
            w0 = T0 + rng.choice([0, -120, 37, 333])
            w1 = w0 + rng.choice([900, 301, 1500])
            req = QueryRangeRequest(query=q, start_ns=int(w0 * 1e9),
                                    end_ns=int(w1 * 1e9),
                                    step_ns=int(rng.choice([30, 60, 300])
                                                * 1e9))
            ctx = f"seed={SEED} case={case} query={q!r} tier=moments"
            f0 = dev.plane_stats.get("fused_metric_blocks", 0)
            try:
                a = _smap(dev.query_range("t", req))
                b = _smap(host.query_range("t", req))
            except Exception as e:
                raise AssertionError(f"{ctx}: {e}") from e
            if "quantile_over_time" in q:
                quantile_fused += (
                    dev.plane_stats.get("fused_metric_blocks", 0) - f0)
            assert set(a) == set(b), f"{ctx}: series sets differ " \
                f"(only-dev={set(a) - set(b)}, only-host={set(b) - set(a)})"
            for k in b:
                if "quantile_over_time" in q:
                    # moments error gate: both sides solve the maxent
                    # problem off independently-accumulated f32 moment
                    # sums — reduction order differs, the answer class
                    # (tier bound) must not
                    np.testing.assert_allclose(
                        a[k], b[k], rtol=5e-2, atol=1e-6,
                        err_msg=f"{ctx} series={k}")
                elif ("rate()" in q or "count_over_time" in q
                      or "histogram_over_time" in q):
                    # count kinds: integer grid cells → bit-identical
                    assert np.array_equal(a[k], b[k]), \
                        f"{ctx} series={k}: count-kind series not " \
                        f"bit-identical ({a[k]} vs {b[k]})"
                else:
                    # float-sum kinds carry f32 reduction-order noise
                    np.testing.assert_allclose(
                        a[k], b[k], rtol=1e-5, atol=1e-4,
                        err_msg=f"{ctx} series={k}")
    assert dev.plane_stats.get("fused_metric_blocks", 0) > fused0, \
        f"seed={SEED}: moments-tier run never rode the fused plane"
    assert quantile_fused > 0, \
        f"seed={SEED}: no quantile_over_time block rode the moments grid"


def test_forced_refusal_exercises_batched_fallback(fuzz_dbs):
    """≥1 deterministic refusal: a mixed AND/OR filter is NOT fusable
    (superset masks would corrupt metrics), so the block must route to
    the batched host fallback — the cause counter moves, the batched
    evaluator answers, and parity against the host-only instance still
    holds bit-for-bit."""
    dev, host = fuzz_dbs
    q = ('{ name = "op-1" && (resource.service.name = "svc-0" '
         '|| span.region = "r1") } | rate() by (name)')
    req = QueryRangeRequest(query=q, start_ns=int(T0 * 1e9),
                            end_ns=int((T0 + 900) * 1e9),
                            step_ns=int(60 * 1e9))
    before = dict(dev.plane_stats)
    a = _smap(dev.query_range("t", req))
    b = _smap(host.query_range("t", req))
    cause_delta = (dev.plane_stats.get("fallback_query_shape", 0)
                   - before.get("fallback_query_shape", 0))
    host_delta = (dev.plane_stats.get("host_metric_blocks", 0)
                  - before.get("host_metric_blocks", 0))
    assert cause_delta > 0 and host_delta > 0, \
        f"refusal did not route to the host fallback: {dev.plane_stats}"
    assert set(a) == set(b)
    for k in b:
        assert np.array_equal(a[k], b[k]), f"series={k}"


def test_zero_steady_state_recompiles_read_paths(fuzz_dbs):
    """Warm repeats of BOTH warm-read paths — the fused moments grid and
    the batched host fallback — must reuse their compiled traces: zero
    jit compiles across the steady-state phase (the ISSUE 20 acceptance
    gate, over the product entry point)."""
    from tempo_tpu.obs.jaxruntime import JIT_COMPILES
    from tempo_tpu.ops import moments as M

    dev, _host = fuzz_dbs
    fused_q = ("{ } | quantile_over_time(duration, .5, .99)"
               " by (resource.service.name)")
    refusal_q = ('{ name = "op-1" && (resource.service.name = "svc-0" '
                 '|| span.region = "r1") } | rate() by (name)')
    reqs = [QueryRangeRequest(query=q, start_ns=int(T0 * 1e9),
                              end_ns=int((T0 + 900) * 1e9),
                              step_ns=int(60 * 1e9))
            for q in (fused_q, refusal_q)]

    def total_compiles():
        with JIT_COMPILES._lock:
            return sum(JIT_COMPILES._series.values())

    with M.use_query_tier("moments"):
        for _ in range(2):                      # warm every shape bucket
            for req in reqs:
                dev.query_range("t", req)
        warm = total_compiles()
        for _ in range(3):
            for req in reqs:
                dev.query_range("t", req)
        assert total_compiles() == warm, \
            "steady-state repeats recompiled a read-path kernel"


def test_fuzz_search_parity(fuzz_dbs):
    dev, host = fuzz_dbs
    rng = random.Random(SEED + 1)
    for case in range(N_QUERIES):
        q = _filter(rng)
        ctx = f"seed={SEED} case={case} query={q!r}"
        try:
            a = sorted(m.trace_id for m in dev.search("t", q, limit=5000))
            b = sorted(m.trace_id for m in host.search("t", q, limit=5000))
        except Exception as e:
            raise AssertionError(f"{ctx}: {e}") from e
        assert a == b, f"{ctx}: {len(a)} dev vs {len(b)} host trace ids"


# -- paged-vs-dense differential arm -----------------------------------------
#
# The write-plane twin of the read-path parity gate above: random
# push/purge/collect/quantile interleavings across 3 tenants must be
# BIT-identical between the paged layout (registry/pages.py page-table
# arenas) and the dense fixed-capacity layout — including full-eviction
# rounds that free pages and the immediate reuse of the same physical
# pages (the free list is LIFO) by other tenants' new series.

def _pv_make_world(paged: bool):
    from tempo_tpu.generator.processors.spanmetrics import (
        SpanMetricsConfig, SpanMetricsProcessor)
    from tempo_tpu.registry import pages as device_pages
    from tempo_tpu.registry.registry import ManagedRegistry, RegistryOverrides

    clock = [1000.0]
    pool = device_pages.PagePool(device_pages.PagePoolConfig(
        enabled=True, page_rows=16, arena_slots=1024)) if paged else None
    tenants = {}
    with device_pages.use(pool):
        for t in ("a", "b", "c"):
            reg = ManagedRegistry(
                t, RegistryOverrides(max_active_series=64,
                                     stale_duration_s=50.0),
                now=lambda: clock[0])
            proc = SpanMetricsProcessor(reg, SpanMetricsConfig(
                use_scheduler=False, sketch_max_series=32))
            tenants[t] = (reg, proc)
    return clock, tenants, pool


def _pv_batch(reg, rng: random.Random, n: int):
    from tempo_tpu.model.span_batch import SpanBatchBuilder

    b = SpanBatchBuilder(reg.interner)
    for _ in range(n):
        b.append(trace_id=rng.getrandbits(128).to_bytes(16, "big"),
                 span_id=rng.getrandbits(64).to_bytes(8, "big"),
                 name=f"op-{rng.randrange(12)}",
                 service=f"svc-{rng.randrange(4)}",
                 kind=rng.randrange(6), status_code=rng.randrange(3),
                 start_unix_nano=10**18,
                 end_unix_nano=10**18 + rng.randrange(1, 10**9))
    return b.build()


def test_fuzz_paged_vs_dense_differential():
    n_ops = int(os.environ.get("TEMPO_FUZZ_CASES", 40))
    worlds = [_pv_make_world(paged) for paged in (True, False)]
    script = random.Random(SEED + 2)
    tenant_names = ("a", "b", "c")
    for step in range(n_ops):
        op = script.choice(["push", "push", "push", "purge", "collect",
                            "quantile", "idle"])
        t = script.choice(tenant_names)
        seed = script.randrange(1 << 30)
        n = script.choice([17, 64, 256])
        dt = script.choice([0.0, 5.0, 60.0])   # 60s+ steps age series out
        ctx = f"seed={SEED} step={step} op={op} tenant={t}"
        results = []
        for clock, tenants, _pool in worlds:
            reg, proc = tenants[t]
            rng = random.Random(seed)
            clock[0] += dt
            if op == "push":
                proc.push_batch(_pv_batch(reg, rng, n))
                results.append(reg.budget.used)
            elif op == "purge":
                results.append(reg.purge_stale())
            elif op == "collect":
                results.append(sorted(
                    (s.name, s.labels, s.value)
                    for s in reg.collect(step) if s.value == s.value))
            elif op == "quantile":
                results.append(proc.quantile(
                    rng.choice([0.5, 0.9, 0.99])))
            else:
                results.append(None)
        assert results[0] == results[1], ctx
    # deterministic coda (random scripts may not evict): age EVERY
    # series out, purge, and repopulate — the paged world must recycle
    # the just-freed physical pages (LIFO free list) for the new series
    for clock, tenants, _pool in worlds:
        clock[0] += 1000.0
        for t in tenant_names:
            tenants[t][0].purge_stale()
        rng = random.Random(SEED + 3)
        for t in tenant_names:
            tenants[t][1].push_batch(_pv_batch(tenants[t][0], rng, 64))
    # closing audit: every tenant's full state agrees bit-for-bit, and
    # the paged world actually exercised eviction + page reuse
    for t in tenant_names:
        outs = [sorted((s.name, s.labels, s.value)
                       for s in w[1][t][0].collect(10**6)
                       if s.value == s.value) for w in worlds]
        qq = [w[1][t][1].quantile(0.99) for w in worlds]
        assert outs[0] == outs[1], f"seed={SEED} tenant={t} final collect"
        assert qq[0] == qq[1], f"seed={SEED} tenant={t} final quantile"
    pool = worlds[0][2]
    assert pool.allocated_total > pool.total_pages() - pool.free_pages(), \
        f"seed={SEED}: fuzz script never recycled a page (weak run)"


# -- moments-vs-exact differential arm ---------------------------------------
#
# The quantile-accuracy twin of the paged-vs-dense arm: random WEIGHTED
# op scripts (pushes with Horvitz-Thompson-style weights, purges, and
# evict-then-reuse of slots) against moments-tier processors in BOTH
# layouts. Gates: (1) paged and dense moments worlds stay bit-identical,
# (2) every live series' quantile answers stay inside the tier's error
# bound versus an exactly-tracked weighted distribution — including
# series whose slot was recycled after a purge (stale history leaking
# into a reused row is exactly what this arm would catch), and (3) the
# solver never falls back in steady state.

# -- trace-analytics structural-plane differential arm ------------------------
#
# The write-plane gate for the structural tier (critical-path seconds,
# error root-cause counts, latency-share moments): random
# push/cut/purge/collect/quantile scripts across randomized trace DAGs
# must be BIT-identical (1) between the paged and dense layouts and
# (2) between the direct dispatch route and the device-scheduler route
# (one coalesced job per plane per cut) — including evict rounds that
# zero share-sketch rows and the immediate reuse of freed pages/slots.

def _ta_make_world(paged: bool, use_sched: bool):
    from tempo_tpu.generator.processors.traceanalytics import (
        TraceAnalyticsConfig, TraceAnalyticsProcessor)
    from tempo_tpu.registry import pages as device_pages
    from tempo_tpu.registry.registry import ManagedRegistry, RegistryOverrides

    clock = [1000.0]
    pool = device_pages.PagePool(device_pages.PagePoolConfig(
        enabled=True, page_rows=16, arena_slots=1024)) if paged else None
    with device_pages.use(pool):
        reg = ManagedRegistry(
            "ta", RegistryOverrides(max_active_series=64,
                                    stale_duration_s=50.0),
            now=lambda: clock[0])
        proc = TraceAnalyticsProcessor(reg, TraceAnalyticsConfig(
            trace_idle_s=1.0, use_scheduler=use_sched,
            sketch_max_series=32))
    return clock, reg, proc


def _ta_batch(reg, rng: random.Random, n_traces: int):
    from tempo_tpu.model.span_batch import SpanBatchBuilder

    b = SpanBatchBuilder(reg.interner)
    for _ in range(n_traces):
        tid = rng.getrandbits(128).to_bytes(16, "big")
        sids = [rng.getrandbits(64).to_bytes(8, "big")
                for _ in range(rng.randrange(2, 7))]
        t0 = 10**18
        for i, sid in enumerate(sids):
            par = b"" if i == 0 else sids[rng.randrange(0, i)]
            if rng.random() < 0.05:          # orphan pointer
                par = rng.getrandbits(64).to_bytes(8, "big")
            b.append(trace_id=tid, span_id=sid, parent_span_id=par,
                     name=f"op-{rng.randrange(8)}",
                     service=f"svc-{rng.randrange(4)}",
                     status_code=2 if rng.random() < 0.3 else 0,
                     start_unix_nano=t0 + i,
                     end_unix_nano=t0 + rng.randrange(10**6, 10**9))
    return b.build()


def test_fuzz_traceanalytics_paged_sched_differential():
    from tempo_tpu import sched
    from tempo_tpu.sched.scheduler import SchedConfig

    sched.configure(SchedConfig(batch_window_ms=0.0))
    n_ops = max(int(os.environ.get("TEMPO_FUZZ_CASES", 40)) // 2, 15)
    # three worlds, two axes: paged-vs-dense (direct route) and
    # direct-vs-scheduler (paged layout)
    worlds = [_ta_make_world(paged=True, use_sched=False),
              _ta_make_world(paged=False, use_sched=False),
              _ta_make_world(paged=True, use_sched=True)]
    script = random.Random(SEED + 9)
    for step in range(n_ops):
        op = script.choice(["push", "push", "cut", "cut", "purge",
                            "collect", "quantile", "idle"])
        seed = script.randrange(1 << 30)
        nt = script.choice([3, 8, 20])
        dt = script.choice([0.0, 2.0, 60.0])
        # drawn ONCE per step, not per world — a per-world draw can hand
        # the three worlds different flags and diverge them spuriously
        immediate = script.random() < 0.5
        ctx = f"seed={SEED} step={step} op={op}"
        results = []
        for clock, reg, proc in worlds:
            rng = random.Random(seed)
            clock[0] += dt
            if op == "push":
                proc.push_batch(_ta_batch(reg, rng, nt))
                results.append(proc.spans_buffered)
            elif op == "cut":
                proc.cut_tick(immediate=immediate)
                sched.flush()
                results.append(len(proc._live))
            elif op == "purge":
                sched.flush()       # in-flight adds land before eviction
                results.append(reg.purge_stale())
            elif op == "collect":
                sched.flush()
                results.append(sorted(
                    (s.name, s.labels, s.value)
                    for s in reg.collect(step) if s.value == s.value))
            elif op == "quantile":
                results.append(proc.quantile(rng.choice([0.5, 0.9])))
            else:
                results.append(None)
        assert results[0] == results[1] == results[2], ctx
    # deterministic evict-reuse coda: cut and age out EVERYTHING, purge
    # (zeroing the share rows of every evicted slot), then repopulate —
    # the paged worlds recycle freed physical pages, the dense world
    # reuses slots; answers must reflect ONLY the new stream
    for clock, reg, proc in worlds:
        proc.cut_tick(immediate=True)
        sched.flush()
        clock[0] += 1000.0
        reg.purge_stale()
        proc.push_batch(_ta_batch(reg, random.Random(SEED + 10), 12))
        proc.cut_tick(immediate=True)
        sched.flush()
    finals = [sorted((s.name, s.labels, s.value)
                     for s in w[1].collect(10**6) if s.value == s.value)
              for w in worlds]
    assert finals[0] == finals[1] == finals[2], f"seed={SEED} final collect"
    qq = [w[2].quantile(0.9) for w in worlds]
    assert qq[0] == qq[1] == qq[2], f"seed={SEED} final quantile"
    assert qq[0], f"seed={SEED}: coda produced no share-quantile series"


def _mx_make_world(paged: bool):
    from tempo_tpu.generator.processors.spanmetrics import (
        SpanMetricsConfig, SpanMetricsProcessor)
    from tempo_tpu.registry import pages as device_pages
    from tempo_tpu.registry.registry import ManagedRegistry, RegistryOverrides

    clock = [1000.0]
    pool = device_pages.PagePool(device_pages.PagePoolConfig(
        enabled=True, page_rows=16, arena_slots=512)) if paged else None
    with device_pages.use(pool):
        reg = ManagedRegistry(
            "m", RegistryOverrides(max_active_series=64,
                                   stale_duration_s=50.0),
            now=lambda: clock[0])
        proc = SpanMetricsProcessor(reg, SpanMetricsConfig(
            use_scheduler=False, sketch="moments", sketch_max_series=32))
    return clock, reg, proc


def _mx_weighted_quantile(samples: list, q: float) -> float:
    vals = np.array([v for v, _ in samples])
    wts = np.array([w for _, w in samples])
    order = np.argsort(vals)
    cum = np.cumsum(wts[order])
    i = int(np.searchsorted(cum, q * cum[-1], side="left"))
    return float(vals[order][min(i, len(vals) - 1)])


def test_fuzz_moments_vs_exact_differential():
    from tempo_tpu.model.span_batch import SpanBatchBuilder
    from tempo_tpu.ops import moments as M

    n_ops = max(int(os.environ.get("TEMPO_FUZZ_CASES", 40)) // 2, 12)
    script = random.Random(SEED + 4)
    worlds = [_mx_make_world(paged) for paged in (True, False)]
    exact: dict[str, list] = {}       # op name -> [(duration, weight)]
    fb0 = M.fallbacks_total

    def check():
        for q in (0.5, 0.99):
            per_world = [w[2].quantile(q) for w in worlds]
            assert per_world[0] == per_world[1], \
                f"seed={SEED} q={q}: paged != dense"
            for labels, est in per_world[0].items():
                op = dict(labels)["span_name"]
                samples = exact.get(op)
                if not samples or len(samples) < 16:
                    continue
                ex = _mx_weighted_quantile(samples, q)
                vals = np.sort(np.array([v for v, _ in samples]))
                rel = abs(est - ex) / max(ex, 1e-12)
                rank = abs(np.searchsorted(vals, est) / len(vals) - q)
                # tier bound at volume; sampling-noise slack below it
                # (the empirical quantile of a 100-point multi-scale
                # mixture is itself ~1/sqrt(n) uncertain, and a median
                # falling BETWEEN scale clusters is noisy in both the
                # estimate and the oracle — seed 59571098 misses a
                # 2.0/sqrt(n) slack by 1% on exactly that shape).
                # Corruption — stale history in a reused slot,
                # cross-layout drift — shows up as GROSS error either
                # way.
                tol = max(0.08, 2.5 / math.sqrt(len(samples)))
                assert min(rel, rank) <= tol, \
                    f"seed={SEED} op={op} q={q}: est={est} exact={ex}"

    for step in range(n_ops):
        op = script.choice(["push", "push", "push", "purge", "check",
                            "idle"])
        seed = script.randrange(1 << 30)
        dt = script.choice([0.0, 5.0, 60.0])
        for clock, reg, proc in worlds:
            clock[0] += dt
        if op == "push":
            rng = np.random.default_rng(seed)
            name = f"op-{script.randrange(6)}"
            n = script.choice([32, 64, 128])
            scale = script.choice([0.01, 0.1, 1.0])
            durs = rng.lognormal(np.log(scale), 0.7, n)
            wts = (rng.integers(1, 4, n).astype(np.float32)
                   if script.random() < 0.5 else np.ones(n, np.float32))
            exact.setdefault(name, []).extend(zip(durs.tolist(),
                                                  wts.tolist()))
            for clock, reg, proc in worlds:
                b = SpanBatchBuilder(reg.interner)
                for d in durs:
                    b.append(trace_id=bytes(16), span_id=bytes(8),
                             name=name, service="svc", kind=2,
                             status_code=0, start_unix_nano=10**18,
                             end_unix_nano=10**18 + int(d * 1e9))
                proc.push_batch(b.build(), sample_weights=wts)
        elif op == "purge":
            evicted = [w[1].purge_stale() for w in worlds]
            assert evicted[0] == evicted[1], f"seed={SEED} step={step}"
            if evicted[0]:
                # drop exact tracking for the ops that aged out (their
                # device rows were zeroed; a re-push starts both fresh)
                proc = worlds[0][2]
                live = {dict(proc.calls.labels_of(int(s)))["span_name"]
                        for s in proc.calls.table.active_slots()}
                for name in list(exact):
                    if name not in live:
                        del exact[name]
        elif op == "check":
            check()
    # deterministic evict-reuse coda: age everything out, repopulate the
    # SAME op names (paged world recycles freed pages, dense reuses
    # slots) — answers must reflect ONLY the new stream
    for clock, reg, proc in worlds:
        clock[0] += 1000.0
        assert reg.purge_stale() >= 0
    exact.clear()
    rng = np.random.default_rng(SEED + 5)
    durs = rng.lognormal(np.log(0.02), 0.4, 128)
    exact["op-0"] = [(d, 1.0) for d in durs.tolist()]
    for clock, reg, proc in worlds:
        b = SpanBatchBuilder(reg.interner)
        for d in durs:
            b.append(trace_id=bytes(16), span_id=bytes(8), name="op-0",
                     service="svc", kind=2, status_code=0,
                     start_unix_nano=10**18,
                     end_unix_nano=10**18 + int(d * 1e9))
        proc.push_batch(b.build())
    check()
    assert M.fallbacks_total == fb0, \
        f"seed={SEED}: solver fell back during the fuzz run"
