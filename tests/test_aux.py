"""Aux subsystems: usage tracker, hedged requests, cache roles."""

from __future__ import annotations

import threading
import time

import pytest

from tempo_tpu.backend.cache import CacheProvider, CachingReader, ROLE_BLOOM
from tempo_tpu.backend.mem import MemBackend
from tempo_tpu.backend.raw import KeyPath
from tempo_tpu.utils.hedging import HedgedMetrics, hedged_call
from tempo_tpu.utils.usage import OVERFLOW, UsageTracker, UsageTrackerConfig


def test_usage_tracker_dimensions_and_overflow():
    t = UsageTracker(UsageTrackerConfig(dimensions=("service",),
                                        max_cardinality=3))
    for i in range(5):
        t.observe("acme", [{"service": f"svc-{i}", "attrs": {}}])
    text = t.prometheus_text()
    assert 'service="svc-0"' in text
    assert OVERFLOW in text  # 4th/5th distinct services bucket to overflow
    assert 'tenant="acme"' in text
    # attr-sourced dimension
    t2 = UsageTracker(UsageTrackerConfig(dimensions=("team",)))
    t2.observe("acme", [{"attrs": {"team": "payments"}}], size_bytes=1000)
    assert 'team="payments"' in t2.prometheus_text()
    assert "1000" in t2.prometheus_text()


def test_hedged_call_fast_path_no_hedge():
    m = HedgedMetrics()
    assert hedged_call(lambda: 42, delay_s=0.5, metrics=m) == 42
    assert m.requests_total == 1 and m.hedged_total == 0


def test_hedged_call_hedges_slow_first_attempt():
    m = HedgedMetrics()
    calls = []
    lock = threading.Lock()

    def fn():
        with lock:
            calls.append(None)
            n = len(calls)
        if n == 1:
            time.sleep(1.0)  # slow first attempt
            return "slow"
        return "fast"

    t0 = time.perf_counter()
    out = hedged_call(fn, delay_s=0.05, metrics=m)
    assert out == "fast"
    assert time.perf_counter() - t0 < 0.8
    assert m.hedged_total == 1


def test_hedged_call_propagates_error_after_all_fail():
    def boom():
        raise RuntimeError("nope")
    with pytest.raises(RuntimeError, match="nope"):
        hedged_call(boom, delay_s=0.01)


def test_usage_label_escaping():
    t = UsageTracker(UsageTrackerConfig(dimensions=("service",)))
    evil = 'a"} 999\ninjected_metric{x="y'
    t.observe("ten\"ant", [{"service": evil}])
    text = t.prometheus_text()
    # no forged exposition line: every physical line is one of ours (a
    # sample or HELP/TYPE metadata from the shared obs renderer), raw
    # newlines/quotes in values are escaped
    for line in text.strip().splitlines():
        assert line.startswith(("tempo_usage_tracker_", "# ")), line
    assert '\\n' in text and '\\"' in text
    # and the output is well-formed exposition end to end
    from tempo_tpu.obs import parse_exposition
    parse_exposition(text)


def test_hedged_reader_wraps_reads():
    from tempo_tpu.utils.hedging import HedgedReader

    be = MemBackend()
    kp = KeyPath(("t", "b"))
    be.write("data", kp, b"hello")
    r = HedgedReader(be, delay_s=0.5)
    assert r.read("data", kp) == b"hello"
    assert r.read_range("data", kp, 1, 3) == b"ell"
    assert r.metrics.requests_total == 2 and r.metrics.hedged_total == 0


def test_forwarder_tee_filter_and_payload():
    from tempo_tpu.distributor.forwarder import (
        Forwarder,
        ForwarderConfig,
        ForwarderManager,
        otlp_json_payload,
    )

    got = []
    fwd = Forwarder(ForwarderConfig(
        name="tee", filter={"include": {"service": "svc-a"},
                            "exclude": {"name": "noisy"}}),
        sink=got.extend)
    mgr = ForwarderManager()
    mgr.register("t1", fwd)
    spans = [
        {"trace_id": b"\x01" * 16, "span_id": b"\x01" * 8, "name": "ok",
         "service": "svc-a", "start_unix_nano": 1, "end_unix_nano": 2,
         "attrs": {"k": 1}},
        {"trace_id": b"\x02" * 16, "span_id": b"\x02" * 8, "name": "noisy",
         "service": "svc-a", "start_unix_nano": 1, "end_unix_nano": 2},
        {"trace_id": b"\x03" * 16, "span_id": b"\x03" * 8, "name": "ok",
         "service": "svc-b", "start_unix_nano": 1, "end_unix_nano": 2},
    ]
    mgr.offer("t1", spans)
    mgr.offer("other-tenant", spans)  # not registered: no-op
    fwd.flush()
    mgr.shutdown()
    assert len(got) == 1 and got[0]["name"] == "ok"
    assert fwd.forwarded == 1
    payload = otlp_json_payload(got)
    sp = payload["resourceSpans"][0]["scopeSpans"][0]["spans"][0]
    assert sp["traceId"] == "01" * 16
    assert sp["attributes"] == [{"key": "k", "value": {"intValue": "1"}}]


def test_caching_reader_roles():
    be = MemBackend()
    kp = KeyPath(("t1", "blk"))
    be.write("bloom-0", kp, b"BLOOMDATA")
    be.write("data.parquet", kp, b"0123456789")
    prov = CacheProvider()
    r = CachingReader(be, prov)
    assert r.read("bloom-0", kp) == b"BLOOMDATA"
    assert r.read("bloom-0", kp) == b"BLOOMDATA"
    c = prov.cache_for(ROLE_BLOOM)
    assert c.hits == 1 and c.misses == 1
    # page ranges cached under page role
    assert r.read_range("data.parquet", kp, 2, 3) == b"234"
    assert r.read_range("data.parquet", kp, 2, 3) == b"234"


# -- usage stats (pkg/usagestats analog) ------------------------------------

def test_usage_reporter_leader_election_and_report():
    import json

    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.ring.kv import KVStore
    from tempo_tpu.backend.raw import KeyPath
    from tempo_tpu.utils.usagestats import REPORT_NAME, UsageReporter

    clock = [1000.0]
    now = lambda: clock[0]
    kv = KVStore()
    be = MemBackend()
    a = UsageReporter(kv, be, instance_id="a", lease_s=90, now=now)
    b = UsageReporter(kv, be, instance_id="b", lease_s=90, now=now)

    # one leader; the seed is cluster-wide stable
    assert a.try_acquire_leadership()
    assert not b.try_acquire_leadership()
    seed1, seed2 = a.get_or_create_seed(), b.get_or_create_seed()
    assert seed1 == seed2

    a.inc_stat("spans", 41)
    a.inc_stat("spans")
    a.set_stat("target", "all")
    assert a.report_once()
    rep = json.loads(be.read(REPORT_NAME, KeyPath(("usage-stats",))))
    assert rep["clusterID"] == seed1
    assert rep["metrics"]["spans"] == 42
    assert rep["target"] == "all"
    assert not b.report_once()          # not leader: no write

    # lease lapses -> the other member takes over
    clock[0] += 200
    assert b.try_acquire_leadership()
    assert not a.try_acquire_leadership()
    assert b.report_once()


def test_usage_reporter_over_replicated_kv():
    """Leader election against the replicated KV routes through ONE
    member (cas_primary): two contenders racing the same empty lease get
    exactly one winner, and the cluster seed is minted once."""
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.ring.kv import KVStore, ReplicatedKVStore, _LocalEndpoint
    from tempo_tpu.utils.usagestats import UsageReporter

    stores = [KVStore() for _ in range(3)]
    clock = [50.0]
    now = lambda: clock[0]

    def client():
        return ReplicatedKVStore([_LocalEndpoint(s) for s in stores])

    a = UsageReporter(client(), MemBackend(), instance_id="a", now=now)
    b = UsageReporter(client(), MemBackend(), instance_id="b", now=now)
    # concurrent contention for the same empty lease: exactly one winner
    import threading
    wins = {}
    barrier = threading.Barrier(2)
    def contend(r, key):
        barrier.wait()
        wins[key] = r.try_acquire_leadership()
    ts = [threading.Thread(target=contend, args=(r, k))
          for r, k in ((a, "a"), (b, "b"))]
    [t.start() for t in ts]; [t.join() for t in ts]
    assert sorted(wins.values()) == [False, True], wins
    # renewal keeps it with the winner
    clock[0] += 30
    winner, loser = (a, b) if wins["a"] else (b, a)
    assert winner.try_acquire_leadership()
    assert not loser.try_acquire_leadership()
    # the seed is minted once, cluster-wide
    assert a.get_or_create_seed() == b.get_or_create_seed()


# -- data quality warnings (pkg/dataquality analog) -------------------------

def test_dataquality_warnings():
    from tempo_tpu.utils.dataquality import (REASON_FUTURE, REASON_PAST,
                                             DataQuality)

    now = lambda: 1_000_000_000.0
    dq = DataQuality(now=now)
    ns = lambda s: int(s * 1e9)
    spans = [
        {"start_unix_nano": ns(1_000_000_000)},          # fine
        {"start_unix_nano": ns(1_000_000_000 + 3 * 3600)},   # future
        {"start_unix_nano": ns(1_000_000_000 - 15 * 86400)}, # way past
        {"start_unix_nano": 0},                          # absent: ignored
    ]
    dq.observe_spans("t1", spans)
    snap = dq.snapshot()
    assert snap[("t1", REASON_FUTURE)] == 1
    assert snap[("t1", REASON_PAST)] == 1


def test_dataquality_exposed_on_metrics(tmp_path):
    import urllib.request

    from tempo_tpu.app import App
    from tempo_tpu.app.api import serve
    from tempo_tpu.app.config import Config
    from tempo_tpu.utils.dataquality import REASON_FUTURE

    import socket
    s = socket.socket(); s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]; s.close()
    cfg = Config(target="all")
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.generator.localblocks.data_dir = str(tmp_path / "lb")
    cfg.server.http_listen_port = port
    app = App(cfg)
    srv = serve(app, block=False)
    try:
        app.distributor.dataquality.warn("single-tenant", REASON_FUTURE, 3)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert 'tempo_warnings_total{tenant="single-tenant",' \
               f'reason="{REASON_FUTURE}"}} 3' in body
    finally:
        srv.shutdown()
        app.shutdown()


# -- self-tracing (cmd/tempo/main.go:227-281 analog) ------------------------

def test_self_tracing_dogfood(tmp_path):
    """The app traces itself INTO ITSELF: spans from a push/search land as
    real traces under the self-tenant, queryable like any other tenant."""
    import socket
    import time
    import urllib.request

    from tempo_tpu.app import App
    from tempo_tpu.app.api import serve
    from tempo_tpu.app.config import Config
    from tempo_tpu.utils import tracing

    s = socket.socket(); s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]; s.close()
    cfg = Config(target="all")
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.generator.localblocks.data_dir = str(tmp_path / "lb")
    cfg.server.http_listen_port = port
    cfg.self_tracing_endpoint = f"http://127.0.0.1:{port}"
    app = App(cfg)
    app.start_loops()
    srv = serve(app, block=False)
    try:
        assert isinstance(tracing.tracer(), tracing.SelfTracer)
        # trigger traced entry points
        t0 = int((time.time() - 3) * 1e9)
        otlp = {"resourceSpans": [{"scopeSpans": [{"spans": [{
            "traceId": "ab" * 16, "spanId": "cd" * 8, "name": "user-op",
            "startTimeUnixNano": str(t0),
            "endTimeUnixNano": str(t0 + 1_000_000)}]}]}]}
        import json as _json
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/traces",
            data=_json.dumps(otlp).encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=10).close()
        app.frontend.search("single-tenant", "{ }", limit=5)
        # flush self-spans into this very process
        assert tracing.tracer().flush() > 0
        # nested child spans share the parent's trace
        with tracing.span("outer") as outer:
            with tracing.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_span_id == outer.span_id
        tracing.tracer().flush()
        # the self-tenant now holds framework spans, queryable
        names = set()
        inst = app.ingester.instance("tempo-self")
        for lt in inst.live.view():
            for sp in lt.spans:
                names.add(sp["name"])
        assert "distributor.PushSpans" in names, names
        assert "frontend.Search" in names, names
        # traceparent propagation surface
        with tracing.span("rpc-client"):
            tp = tracing.tracer().traceparent()
            assert tp and tp.startswith("00-")
    finally:
        srv.shutdown()
        app.shutdown()


def test_debug_profile_endpoints(tmp_path):
    import socket
    import urllib.request

    from tempo_tpu.app import App
    from tempo_tpu.app.api import serve
    from tempo_tpu.app.config import Config

    s = socket.socket(); s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]; s.close()
    cfg = Config(target="all")
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.generator.localblocks.data_dir = str(tmp_path / "lb")
    cfg.server.http_listen_port = port
    app = App(cfg)
    srv = serve(app, block=False)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/threads", timeout=10
        ).read().decode()
        assert "--- thread" in body and "serve_forever" in body
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/profile?seconds=0.3",
            timeout=10).read().decode()
        assert body.startswith("samples:")
    finally:
        srv.shutdown()
        app.shutdown()


def test_dashboards_generated_from_single_source():
    """The four ops dashboards are GENERATED (operations/gen_dashboards.py,
    the tempo-mixin dashboards.libsonnet analog) — committed JSON must
    match the generator exactly so panels cannot drift from the spec."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "operations",
                                      "gen_dashboards.py"), "--check"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr or proc.stdout


def test_runbook_covers_every_alert():
    """Every alert in operations/alerts.yaml has a matching `## <Alert>`
    runbook section AND a runbook_url annotation pointing at it
    (reference: operations/tempo-mixin/runbook.md maps alerts to operator
    actions)."""
    import os
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    alerts_text = open(os.path.join(root, "operations",
                                    "alerts.yaml")).read()
    runbook = open(os.path.join(root, "operations", "runbook.md")).read()
    alerts = re.findall(r"- alert: (\w+)", alerts_text)
    assert len(alerts) >= 9
    sections = set(re.findall(r"^## (\w+)", runbook, re.M))
    urls = set(re.findall(r"runbook_url: \S*#(\w+)", alerts_text))
    for a in alerts:
        assert a in sections, f"runbook section missing for alert {a}"
        assert a.lower() in urls, f"runbook_url missing for alert {a}"


# -- shared memcached cache tier (round 5, pkg/cache/memcached analog) -------

def test_memcached_client_roundtrip_and_sanitization():
    from tempo_tpu.backend.memcached import MemcachedCache, sanitize_key
    from tests.mock_memcached import start_mock_memcached

    srv, port, mock = start_mock_memcached()
    try:
        c = MemcachedCache(f"127.0.0.1:{port}")
        assert c.get("missing") is None and c.misses == 1
        c.put("k1", b"v1")
        c.flush()
        assert c.get("k1") == b"v1" and c.hits == 1
        # long + unsafe keys sanitize to sha1 (mock REJECTS illegal keys,
        # so a sloppy client would fail here, not silently miss)
        long_key = "tenant/" + "x" * 300 + " with spaces"
        c.put(long_key, b"v2")
        c.flush()
        assert c.get(long_key) == b"v2"
        assert mock.bad_requests == 0
        assert sanitize_key(long_key) != long_key.encode()
        c.close()
    finally:
        srv.shutdown()


def test_memcached_write_behind_drops_when_full():
    from tempo_tpu.backend.memcached import MemcachedCache

    # no server at this address: the writer can't drain, the queue fills,
    # further puts DROP (counted) instead of blocking the read path
    c = MemcachedCache("127.0.0.1:1", write_back_buffer=4)
    for i in range(64):
        c.put(f"k{i}", b"v")
    assert c.dropped_writes > 0
    assert c.get("k0") is None          # dead server degrades to miss
    c.close()


def test_memcached_cross_instance_shared_cache():
    """Two TempoDB instances with SEPARATE processes' worth of cache state
    share one memcached: blocks written+read through instance A leave
    bloom/footer entries that instance B's reads hit (scale-out read perf
    depends on this — in-process LRUs cannot give cross-replica hits)."""
    import numpy as np
    from tempo_tpu.backend.cache import CacheProvider, CachingReader
    from tempo_tpu.backend.memcached import MemcachedCache
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig
    from tests.mock_memcached import start_mock_memcached

    srv, port, mock = start_mock_memcached()
    try:
        be = MemBackend()
        roles = ("bloom", "parquet-footer")

        def mk_db():
            shared = MemcachedCache(f"127.0.0.1:{port}")
            prov = CacheProvider(caches={r: shared for r in roles})
            return TempoDB(CachingReader(be, prov), be,
                           TempoDBConfig(device_plane=False)), shared

        db_a, ca = mk_db()
        db_b, cb = mk_db()
        rng = np.random.default_rng(3)
        tid0 = None
        traces = []
        for i in range(50):
            tid = rng.bytes(16)
            tid0 = tid0 or tid
            start = 1_700_000_000_000_000_000 + i * 10**9
            traces.append((tid, [{
                "trace_id": tid, "span_id": rng.bytes(8), "name": "op",
                "service": "svc", "kind": 2, "status_code": 0,
                "start_unix_nano": start,
                "end_unix_nano": start + 10**6}]))
        traces.sort(key=lambda t: t[0])   # blocks are trace-id ordered
        db_a.write_block("t", traces, replication_factor=1)
        db_a.poll_now()
        db_b.poll_now()
        assert db_a.find_trace_by_id("t", tid0)   # A populates the tier
        ca.flush()
        before = cb.hits
        assert db_b.find_trace_by_id("t", tid0)   # B hits A's entries
        assert cb.hits > before, (cb.hits, cb.misses)
        assert mock.sets > 0 and mock.gets > 0
        db_a.shutdown(); db_b.shutdown()
    finally:
        srv.shutdown()


def test_redis_cache_client_roundtrip_and_expiry():
    """The RESP2 redis variant shares the write-behind + degradation
    semantics with the memcached tier (pkg/cache/redis_client.go analog);
    the strict mock rejects malformed framing."""
    from tempo_tpu.backend.memcached import RedisCache
    from tests.mock_memcached import start_mock_redis

    srv, port, mock = start_mock_redis()
    try:
        c = RedisCache(f"127.0.0.1:{port}", expiration_s=60)
        assert c.get("missing") is None and c.misses == 1
        c.put("k1", b"v1")
        c.flush()
        assert c.get("k1") == b"v1" and c.hits == 1
        assert mock.sets == 1 and mock.gets == 2
        # concurrent readers: per-thread connections, no cross-talk
        import threading as _t
        errs = []

        def reader(i):
            for _ in range(50):
                if c.get("k1") != b"v1":
                    errs.append(i)

        ts = [_t.Thread(target=reader, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        c.close()
    finally:
        srv.shutdown()


def test_app_wires_shared_cache_tier(tmp_path):
    from tempo_tpu.app import App
    from tempo_tpu.app.config import Config
    from tempo_tpu.backend.memcached import MemcachedCache, RedisCache
    from tests.mock_memcached import start_mock_redis

    srv, port, mock = start_mock_redis()
    try:
        cfg = Config(target="querier")
        cfg.storage.backend = "mem"
        cfg.storage.wal_path = str(tmp_path / "wal")
        cfg.storage.redis_addrs = f"127.0.0.1:{port}"
        app = App(cfg)
        c = app.cache_provider.cache_for("bloom")
        assert isinstance(c, RedisCache)
        c.put("k", b"v")
        c.flush()
        assert c.get("k") == b"v" and mock.sets == 1
        app.shutdown()
    finally:
        srv.shutdown()
