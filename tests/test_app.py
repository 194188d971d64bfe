"""App runtime: config load, module wiring, HTTP API end-to-end."""

from __future__ import annotations

import json
import socket
import urllib.error
import urllib.parse
import urllib.request

import pytest

from tempo_tpu.app import App, load_config
from tempo_tpu.app.config import Config


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_config_yaml_and_env(monkeypatch, tmp_path):
    monkeypatch.setenv("BUCKET", "my-bucket")
    p = tmp_path / "tempo.yaml"
    p.write_text("""
target: all
server:
  http_listen_port: 9999
storage:
  backend: mem
  cloud: {bucket: "${BUCKET}", region: "${REGION:-us-east1}"}
ingester:
  instance: {max_block_duration_s: 120.0}
frontend:
  target_bytes_per_job: 52428800
""")
    cfg = load_config(str(p))
    assert cfg.server.http_listen_port == 9999
    assert cfg.storage.cloud == {"bucket": "my-bucket", "region": "us-east1"}
    assert cfg.ingester.instance.max_block_duration_s == 120.0
    assert cfg.frontend.target_bytes_per_job == 50 * 1024 * 1024
    assert cfg.check() == []


def test_config_unknown_key_rejected():
    with pytest.raises(ValueError, match="unknown config key"):
        load_config(text="storage: {bukkit: x}")


@pytest.mark.parametrize("where, key, value", [
    ("config", "kernel", "xla"),
    ("config", "pallas_interpret", "false"),
    ("config", "compact_state", "false"),
    ("override", "kernel", "pallas"),
])
def test_removed_update_kernel_options(where, key, value):
    """The span-metrics update has one kernel formulation and one state
    dtype. A YAML that still names a removed option fails to load,
    naming the key; a per-tenant override that names one is passed over
    like any unknown override key, beside a known key that applies."""
    if where == "config":
        with pytest.raises(ValueError, match=f"unknown config key: {key} "
                                             "on SpanMetricsConfig"):
            load_config(text=f"generator: {{spanmetrics: {{{key}: {value}}}}}")
        return
    from tempo_tpu.generator.generator import Generator
    from tempo_tpu.generator.instance import GeneratorConfig
    from tempo_tpu.overrides import Overrides

    ov = Overrides()
    ov.set_tenant_patch("t", {"generator": {key: value, "sketch": "moments"}})
    assert not hasattr(ov.for_tenant("t").generator, key)
    cfg = GeneratorConfig(processors=("span-metrics",))
    cfg.registry.disable_collection = True
    proc = Generator(cfg, overrides=ov).instance("t") \
        .processors["span-metrics"]
    assert proc.cfg.sketch == "moments" and not hasattr(proc.cfg, key)


def test_config_warnings():
    cfg = load_config(text="ingester: {instance: {max_block_duration_s: 5}}")
    assert any("max_block_duration" in w for w in cfg.check())


def test_target_wiring(tmp_path):
    cfg = Config()
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.target = "querier"
    app = App(cfg)
    assert app.querier is not None and app.db is not None
    assert app.distributor is None and app.ingester is None
    with pytest.raises(ValueError):
        App(Config(target="bogus"))


@pytest.fixture
def server(tmp_path):
    cfg = Config()
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "d" / "wal")
    cfg.generator.localblocks.data_dir = str(tmp_path / "lb")
    cfg.server.http_listen_port = free_port()
    cfg.ingester.instance.trace_idle_s = 0.1
    app = App(cfg)
    app.overrides.set_tenant_patch("single-tenant", {
        "generator": {"processors": ["span-metrics", "local-blocks"]}})
    from tempo_tpu.app.api import serve
    app.start_loops()
    srv = serve(app, block=False)
    base = f"http://127.0.0.1:{cfg.server.http_listen_port}"
    yield app, base
    srv.shutdown()
    app.shutdown()


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, json.loads(r.read() or b"{}")


def _post(url: str, body: bytes, ctype="application/json"):
    req = urllib.request.Request(url, data=body,
                                 headers={"Content-Type": ctype})
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, json.loads(r.read() or b"{}")


OTLP = {"resourceSpans": [{
    "resource": {"attributes": [
        {"key": "service.name", "value": {"stringValue": "shop"}}]},
    "scopeSpans": [{"spans": [{
        "traceId": "0102030405060708090a0b0c0d0e0f10",
        "spanId": "0102030405060708",
        "name": "checkout", "kind": 3,
        "startTimeUnixNano": "{t0}",
        "endTimeUnixNano": "{t1}",
        "attributes": [{"key": "http.status_code",
                        "value": {"intValue": "200"}}],
        "status": {"code": 0}}]}]}]}


def test_zipkin_receiver(server):
    import time
    app, base = server
    ts = int((time.time() - 3) * 1e6)
    spans = [{"traceId": "cc" * 16, "id": "dd" * 8, "name": "zip-op",
              "kind": "SERVER", "timestamp": ts, "duration": 50_000,
              "localEndpoint": {"serviceName": "zipkin-svc"},
              "tags": {"http.method": "GET"}}]
    req = urllib.request.Request(f"{base}/api/v2/spans",
                                 data=json.dumps(spans).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 202
    code, tr = _get(f"{base}/api/traces/{'cc' * 16}")
    assert code == 200 and tr["spans"][0]["name"] == "zip-op"
    assert tr["spans"][0]["service"] == "zipkin-svc"
    assert tr["spans"][0]["attrs"]["http.method"] == "GET"


def test_http_e2e(server):
    import time
    app, base = server
    t0 = int((time.time() - 5) * 1e9)
    body = json.dumps(OTLP).replace('"{t0}"', str(t0)) \
                           .replace('"{t1}"', str(t0 + 50_000_000))
    code, _ = _post(f"{base}/v1/traces", body.encode())
    assert code == 200
    # ready/echo/status
    with urllib.request.urlopen(f"{base}/ready", timeout=10) as r:
        assert r.status == 200
    code, st = _get(f"{base}/status")
    assert st["target"] == "all" and "distributor" in st["modules"]
    # trace by id
    code, tr = _get(f"{base}/api/traces/0102030405060708090a0b0c0d0e0f10")
    assert code == 200 and len(tr["spans"]) == 1
    assert tr["spans"][0]["name"] == "checkout"
    # search (recent window → ingester)
    code, res = _get(f"{base}/api/search?q=" + urllib.parse.quote(
        '{ resource.service.name = "shop" }'))
    assert code == 200 and len(res["traces"]) == 1
    # tags
    code, tags = _get(f"{base}/api/search/tags")
    assert "http.status_code" in tags["tagNames"]          # v1: flat union
    code, tags2 = _get(f"{base}/api/v2/search/tags")
    span_tags = next(s["tags"] for s in tags2["scopes"] if s["name"] == "span")
    assert "http.status_code" in span_tags                 # v2: scoped
    # metrics query range (generator local-blocks path)
    now = time.time()
    code, qr = _get(f"{base}/api/metrics/query_range?q=" +
                    urllib.parse.quote("{ } | rate()") +
                    f"&start={now - 300}&end={now}&step=300")
    assert code == 200
    total = sum(d["value"] for s in qr["series"]
                for d in (s.get("samples") or []) if d["value"] == d["value"])
    assert total > 0
    # span-metrics summary
    code, sm = _get(f"{base}/api/metrics/summary?q=" +
                    urllib.parse.quote("{ }") + "&groupBy=name")
    assert code == 200 and sm["summaries"][0]["spanCount"] == 1
    # overrides API
    code, _ = _post(f"{base}/api/overrides", json.dumps(
        {"generator": {"collection_interval_s": 30.0}}).encode())
    assert code == 200
    code, ov = _get(f"{base}/api/overrides")
    assert ov["limits"]["generator"]["collection_interval_s"] == 30.0
    # prometheus self-metrics
    with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
        text = r.read().decode()
    assert "tempo_distributor_spans_received_total 1" in text


def test_tag_values_includes_ingester_recent_data(server):
    """/api/search/tag/{name}/values must see unflushed ingester data
    (ADVICE r1: previously only backend blocks were scanned)."""
    import time
    app, base = server
    t0 = int((time.time() - 5) * 1e9)
    body = json.dumps(OTLP).replace('"{t0}"', str(t0)) \
                           .replace('"{t1}"', str(t0 + 50_000_000))
    code, _ = _post(f"{base}/v1/traces", body.encode())
    assert code == 200
    code, res = _get(f"{base}/api/search/tag/.http.status_code/values")
    assert code == 200
    assert "200" in res["tagValues"]                       # v1: bare strings
    code, res = _get(
        f"{base}/api/v2/search/tag/resource.service.name/values")
    assert any(v["value"] == "shop" for v in res["tagValues"])  # v2: typed


def test_otlp_malformed_and_gzip(server):
    import gzip
    import time
    app, base = server
    # malformed protobuf → 400, not 500
    req = urllib.request.Request(
        f"{base}/v1/traces", data=b"\xff\xfe not proto",
        headers={"Content-Type": "application/x-protobuf"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=10)
    assert ei.value.code == 400
    # gzipped OTLP JSON is accepted
    t0 = int((time.time() - 5) * 1e9)
    body = json.dumps(OTLP).replace('"{t0}"', str(t0)) \
                           .replace('"{t1}"', str(t0 + 50_000_000)) \
                           .replace("0102030405060708090a0b0c0d0e0f10",
                                    "ab" * 16)
    req = urllib.request.Request(
        f"{base}/v1/traces", data=gzip.compress(body.encode()),
        headers={"Content-Type": "application/json",
                 "Content-Encoding": "gzip"})
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200
    code, tr = _get(f"{base}/api/traces/{'ab' * 16}")
    assert code == 200 and tr["spans"][0]["name"] == "checkout"


def test_metrics_summary_without_generator(tmp_path):
    cfg = Config()
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.target = "query-frontend"
    cfg.server.http_listen_port = free_port()
    app = App(cfg)
    from tempo_tpu.app.api import serve
    srv = serve(app, block=False)
    base = f"http://127.0.0.1:{cfg.server.http_listen_port}"
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/api/metrics/summary?q=%7B%20%7D",
                                   timeout=10)
        assert ei.value.code == 400  # clear error, not AttributeError 500
    finally:
        srv.shutdown()
        app.shutdown()


def _thrift_field(fid: int, ftype: int, payload: bytes) -> bytes:
    import struct
    return struct.pack(">bh", ftype, fid) + payload


def _thrift_str(s) -> bytes:
    import struct
    b = s if isinstance(s, bytes) else s.encode()
    return struct.pack(">i", len(b)) + b


def _thrift_list(etype: int, items: list[bytes]) -> bytes:
    import struct
    return struct.pack(">bi", etype, len(items)) + b"".join(items)


def _jaeger_tag(key: str, v) -> bytes:
    import struct
    out = _thrift_field(1, 11, _thrift_str(key))
    if isinstance(v, bool):
        out += _thrift_field(2, 8, struct.pack(">i", 2))
        out += _thrift_field(5, 2, b"\x01" if v else b"\x00")
    elif isinstance(v, int):
        out += _thrift_field(2, 8, struct.pack(">i", 3))
        out += _thrift_field(6, 10, struct.pack(">q", v))
    elif isinstance(v, float):
        out += _thrift_field(2, 8, struct.pack(">i", 1))
        out += _thrift_field(4, 4, struct.pack(">d", v))
    else:
        out += _thrift_field(2, 8, struct.pack(">i", 0))
        out += _thrift_field(3, 11, _thrift_str(v))
    return out + b"\x00"


def _jaeger_batch(service: str, spans: list[dict]) -> bytes:
    """Encode a jaeger.thrift Batch with TBinaryProtocol (test-side
    writer; the product only reads)."""
    import struct
    process = (_thrift_field(1, 11, _thrift_str(service)) +
               _thrift_field(2, 15, _thrift_list(
                   12, [_jaeger_tag("hostname", "h1")])) + b"\x00")
    enc_spans = []
    for s in spans:
        b = (_thrift_field(1, 10, struct.pack(">q", s["tid_lo"])) +
             _thrift_field(2, 10, struct.pack(">q", s.get("tid_hi", 0))) +
             _thrift_field(3, 10, struct.pack(">q", s["sid"])) +
             _thrift_field(4, 10, struct.pack(">q", s.get("psid", 0))) +
             _thrift_field(5, 11, _thrift_str(s["name"])) +
             _thrift_field(7, 8, struct.pack(">i", 1)) +
             _thrift_field(8, 10, struct.pack(">q", s["start_us"])) +
             _thrift_field(9, 10, struct.pack(">q", s["dur_us"])))
        tags = [_jaeger_tag(k, v) for k, v in s.get("tags", {}).items()]
        if tags:
            b += _thrift_field(10, 15, _thrift_list(12, tags))
        enc_spans.append(b + b"\x00")
    return (_thrift_field(1, 12, process) +
            _thrift_field(2, 15, _thrift_list(12, enc_spans)) + b"\x00")


def test_jaeger_receiver(server):
    import struct
    import time
    app, base = server
    start_us = int((time.time() - 3) * 1e6)
    batch = _jaeger_batch("jaeger-svc", [{
        "tid_lo": 0x0102030405060708, "tid_hi": 0x1112131415161718,
        "sid": 0x0A0B0C0D0E0F1011, "name": "jg-op",
        "start_us": start_us, "dur_us": 75_000,
        "tags": {"span.kind": "server", "http.status_code": 500,
                 "error": True, "peer.address": "10.0.0.9"},
    }])
    req = urllib.request.Request(f"{base}/api/traces", data=batch,
                                 headers={"Content-Type":
                                          "application/x-thrift"})
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 202
    tid_hex = "1112131415161718" + "0102030405060708"
    code, tr = _get(f"{base}/api/traces/{tid_hex}")
    assert code == 200 and tr["spans"][0]["name"] == "jg-op"
    sp = tr["spans"][0]
    assert sp["service"] == "jaeger-svc"
    assert sp["kind"] == 2                      # span.kind=server
    assert sp["status_code"] == 2               # error=true
    assert sp["attrs"]["http.status_code"] == 500
    assert sp["attrs"]["peer.address"] == "10.0.0.9"
    assert "span.kind" not in sp["attrs"]       # mapped, not duplicated
    assert sp["res_attrs"]["hostname"] == "h1"
    assert sp["end_unix_nano"] - sp["start_unix_nano"] == 75_000_000
    # the generator tee aggregated it (re-encoded OTLP wire path)
    inst = app.generator.instance("single-tenant")
    assert inst.spans_received >= 1
    # search finds it by service
    code, res = _get(f"{base}/api/search?q=" + urllib.parse.quote(
        '{ resource.service.name = "jaeger-svc" }'))
    assert code == 200 and len(res["traces"]) == 1
    # malformed payload -> 400
    bad = urllib.request.Request(f"{base}/api/traces", data=b"\x0b\x00\x01",
                                 headers={"Content-Type":
                                          "application/x-thrift"})
    try:
        urllib.request.urlopen(bad, timeout=10)
        raise AssertionError("expected 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_ops_files_reference_only_emitted_metrics(server):
    """Every tempo_* metric named in operations/ dashboards + alerts must
    be REGISTERED in the obs registry (the drift gate: no aspirational
    metric names), and the core write-path names must actually appear on
    /metrics after traffic — byte-compatible with the pre-registry
    exposition."""
    import os
    import re
    import time

    from tempo_tpu.obs import drift
    from tempo_tpu.obs.jaxruntime import RUNTIME

    app, base = server
    t0 = int((time.time() - 5) * 1e9)
    body = json.dumps(OTLP).replace('"{t0}"', str(t0)) \
                           .replace('"{t1}"', str(t0 + 50_000_000))
    _post(f"{base}/v1/traces", body.encode())
    _get(f"{base}/api/search?q=" + urllib.parse.quote("{ }"))
    now = time.time()
    _get(f"{base}/api/metrics/query_range?q=" +
         urllib.parse.quote("{ } | rate()") +
         f"&start={now - 300}&end={now}&step=300")

    import tempo_tpu.app.api as api_mod
    ops_dir = os.path.join(os.path.dirname(api_mod.__file__),
                           "..", "..", "operations")
    assert drift.referenced_metric_names(ops_dir), \
        "no metrics referenced — ops files missing?"
    problems = drift.check_drift(ops_dir, [app.obs, RUNTIME])
    assert not problems, "\n".join(problems)

    with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
        text = r.read().decode()
    emitted = set(re.findall(r"^(tempo_[a-z_]+)", text, re.M))
    for name in ("tempo_distributor_spans_received_total",
                 "tempo_distributor_bytes_received_total",
                 "tempo_query_frontend_queries_total",
                 "tempo_ingester_live_traces",
                 "tempo_request_duration_seconds_bucket"):
        assert name in emitted, name


def test_v2_api_endpoints(server):
    """v2 surface parity (`pkg/api/http.go:76-88`): buildinfo, v2 trace
    response, instant metrics query."""
    import time
    app, base = server
    t0 = int((time.time() - 5) * 1e9)
    body = json.dumps(OTLP).replace('"{t0}"', str(t0)) \
                           .replace('"{t1}"', str(t0 + 50_000_000))
    code, _ = _post(f"{base}/v1/traces", body.encode())
    assert code == 200
    # buildinfo needs no tenant
    code, bi = _get(f"{base}/api/status/buildinfo")
    assert code == 200 and bi["version"].startswith("tempo-tpu")
    # v2 trace-by-id wraps the trace with a status
    tid = OTLP["resourceSpans"][0]["scopeSpans"][0]["spans"][0]["traceId"]
    code, tr = _get(f"{base}/api/v2/traces/{tid}")
    assert code == 200 and tr["status"] == "COMPLETE"
    assert tr["trace"]["spans"][0]["name"] == "checkout"
    # instant metrics query: one value per series over [start, end)
    now = time.time()
    code, qi = _get(f"{base}/api/metrics/query?q=" +
                    urllib.parse.quote("{ } | rate()") +
                    f"&start={now - 300}&end={now}")
    assert code == 200
    assert any(s["value"] == s["value"] and s["value"] >= 0
               for s in qi["series"])


def test_status_usage_stats_endpoint(server):
    """PathUsageStats (`http.go:77`): the would-be-sent report, or 404
    when reporting is disabled."""
    app, base = server
    assert app.usage_reporter is not None
    code, rep = _get(f"{base}/status/usage-stats")
    assert code == 200 and "clusterID" in rep
    # a read poll must not mint a new seed per request
    code2, rep2 = _get(f"{base}/status/usage-stats")
    assert rep2["clusterID"] == rep["clusterID"]
    # disabled path → 404
    app.usage_reporter, saved = None, app.usage_reporter
    try:
        try:
            code, _ = _get(f"{base}/status/usage-stats")
        except urllib.error.HTTPError as e:
            code = e.code
        assert code == 404
    finally:
        app.usage_reporter = saved


# -- jaeger agent UDP (thrift-compact emitBatch, round 5) --------------------
#
# Test-side TCompactProtocol writer: an independent encoder so the
# decoder is checked against the SPEC (zigzag varints, delta field ids,
# header-embedded bools, little-endian doubles), not against itself.

def _c_varint(v: int) -> bytes:
    out = bytearray()
    while True:
        x = v & 0x7F
        v >>= 7
        if v:
            out.append(x | 0x80)
        else:
            out.append(x)
            return bytes(out)


def _c_zig(v: int) -> bytes:
    return _c_varint((v << 1) ^ (v >> 63) if v >= 0 else ((v << 1) ^ -1))


def _c_field(last_fid: int, fid: int, ctype: int) -> bytes:
    delta = fid - last_fid
    if 0 < delta <= 15:
        return bytes([(delta << 4) | ctype])
    return bytes([ctype]) + _c_zig(fid)


def _c_str(s) -> bytes:
    b = s.encode() if isinstance(s, str) else s
    return _c_varint(len(b)) + b


def _c_tag(key: str, v) -> bytes:
    out = _c_field(0, 1, 8) + _c_str(key)          # key
    if isinstance(v, bool):
        out += _c_field(1, 2, 5) + _c_zig(2)       # vType BOOL
        out += _c_field(2, 5, 1 if v else 2)       # bool in the HEADER
    elif isinstance(v, int):
        out += _c_field(1, 2, 5) + _c_zig(3)       # vType LONG
        out += _c_field(2, 6, 6) + _c_zig(v)
    elif isinstance(v, float):
        import struct as _s
        out += _c_field(1, 2, 5) + _c_zig(1)       # vType DOUBLE
        out += _c_field(2, 4, 7) + _s.pack("<d", v)
    else:
        out += _c_field(1, 2, 5) + _c_zig(0)       # vType STRING
        out += _c_field(2, 3, 8) + _c_str(v)
    return out + b"\x00"


def _c_list(structs: list[bytes]) -> bytes:
    n = len(structs)
    if n < 15:
        hdr = bytes([(n << 4) | 12])
    else:
        hdr = bytes([0xF0 | 12]) + _c_varint(n)
    return hdr + b"".join(structs)


def _agent_datagram(service: str, spans: list[dict]) -> bytes:
    span_structs = []
    for s in spans:
        b = (_c_field(0, 1, 6) + _c_zig(s["tid_lo"]) +
             _c_field(1, 2, 6) + _c_zig(s["tid_hi"]) +
             _c_field(2, 3, 6) + _c_zig(s["sid"]) +
             _c_field(3, 4, 6) + _c_zig(s.get("psid", 0)) +
             _c_field(4, 5, 8) + _c_str(s["name"]) +
             _c_field(5, 8, 6) + _c_zig(s["start_us"]) +   # delta 3
             _c_field(8, 9, 6) + _c_zig(s["dur_us"]))
        tags = [_c_tag(k, v) for k, v in s.get("tags", {}).items()]
        if tags:
            b += _c_field(9, 10, 9) + _c_list(tags)
        span_structs.append(b + b"\x00")
    process = (_c_field(0, 1, 8) + _c_str(service) +
               _c_field(1, 2, 9) + _c_list([_c_tag("hostname", "h7")]) +
               b"\x00")
    batch = (_c_field(0, 1, 12) + process +
             _c_field(1, 2, 9) + _c_list(span_structs) + b"\x00")
    args = _c_field(0, 1, 12) + batch + b"\x00"
    return (b"\x82" + bytes([(4 << 5) | 1]) +       # ONEWAY, version 1
            _c_varint(7) + _c_str("emitBatch") + args)


def test_jaeger_agent_udp_receiver():
    import socket as _socket
    import time as _time

    from tempo_tpu.distributor.receiver_agent import (JaegerAgentConfig,
                                                      JaegerAgentReceiver)

    pushed = []

    class _Rec:
        def push_spans(self, tenant, spans, size_bytes=None, **kw):
            pushed.append((tenant, spans))
            return {}

    rx = JaegerAgentReceiver(_Rec(), JaegerAgentConfig(host="127.0.0.1",
                                                       port=0))
    rx.start()
    try:
        gram = _agent_datagram("udp-svc", [{
            "tid_lo": 0x1234, "tid_hi": 0, "sid": 0x77, "psid": 0x55,
            "name": "udp-op", "start_us": 1_700_000_000_000_000,
            "dur_us": 25_000,
            "tags": {"span.kind": "server", "error": True,
                     "retries": 3, "ratio": 0.5, "note": "hé"}}])
        s = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
        s.sendto(gram, ("127.0.0.1", rx.port))
        s.sendto(b"\xff junk not thrift", ("127.0.0.1", rx.port))
        deadline = _time.time() + 5
        while _time.time() < deadline and (not pushed or rx.errors < 1):
            _time.sleep(0.02)
        assert rx.batches_received == 1 and rx.errors == 1
        tenant, spans = pushed[0]
        assert tenant == "single-tenant" and len(spans) == 1
        sp = spans[0]
        assert sp["name"] == "udp-op" and sp["service"] == "udp-svc"
        assert sp["trace_id"].hex() == "0" * 16 + "0000000000001234"
        assert sp["span_id"].hex() == "0000000000000077"
        assert sp["parent_span_id"].hex() == "0000000000000055"
        assert sp["kind"] == 2                       # span.kind=server
        assert sp["status_code"] == 2                # error=true
        assert sp["start_unix_nano"] == 1_700_000_000_000_000_000
        assert sp["end_unix_nano"] - sp["start_unix_nano"] == 25_000_000
        assert sp["attrs"]["retries"] == 3
        assert sp["attrs"]["ratio"] == 0.5
        assert sp["attrs"]["note"] == "hé"
        assert sp["res_attrs"] == {"hostname": "h7",
                                   "service.name": "udp-svc"}
    finally:
        rx.stop()


def test_jaeger_agent_wired_into_app(tmp_path):
    """distributor.jaeger_agent_port boots the UDP receiver inside the
    app; a datagram lands as a searchable trace end-to-end."""
    import socket as _socket
    import time as _time

    cfg = Config()
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.generator.localblocks.data_dir = str(tmp_path / "lb")
    cfg.distributor.jaeger_agent_port = free_port()
    app = App(cfg)
    app.start_loops()
    try:
        now_us = int(_time.time() * 1e6)
        gram = _agent_datagram("agent-svc", [{
            "tid_lo": 0xABCD, "tid_hi": 0, "sid": 1,
            "name": "agent-op", "start_us": now_us, "dur_us": 1000,
            "tags": {}}])
        s = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
        s.sendto(gram, ("127.0.0.1", app.jaeger_agent.port))
        deadline = _time.time() + 5
        while _time.time() < deadline and \
                app.jaeger_agent.spans_received < 1:
            _time.sleep(0.02)
        assert app.jaeger_agent.spans_received == 1
        tid = bytes(8) + (0xABCD).to_bytes(8, "big")
        spans = app.ingester.find_trace_by_id("single-tenant", tid)
        assert spans and spans[0]["name"] == "agent-op"
    finally:
        app.shutdown()


def test_jaeger_agent_dos_datagram_rejected_fast():
    """A crafted datagram claiming a huge fixed-size collection count must
    raise (and quickly) — fixed-size skips never touch the buffer, so an
    unbounded count would spin the receiver thread forever (remote
    unauthenticated DoS, round-5 review finding)."""
    import time as _time

    from tempo_tpu.model.jaeger import spans_from_jaeger_agent

    # message header + args struct holding field 1 as a LIST of BYTE with
    # a ~2^41 claimed count
    evil = (b"\x82" + bytes([(4 << 5) | 1]) + _c_varint(1) +
            _c_str("emitBatch") +
            bytes([(1 << 4) | 9]) +           # field 1, LIST
            bytes([0xF3]) +                   # long form, elem BYTE
            _c_varint(1 << 41) + b"\x00")
    t0 = _time.time()
    with pytest.raises(ValueError):
        spans_from_jaeger_agent(evil)
    assert _time.time() - t0 < 1.0
    # same for maps and doubles
    for elem in (7, 1):
        evil2 = (b"\x82" + bytes([(4 << 5) | 1]) + _c_varint(1) +
                 _c_str("emitBatch") +
                 bytes([(1 << 4) | 9]) + bytes([0xF0 | elem]) +
                 _c_varint(1 << 41) + b"\x00")
        with pytest.raises(ValueError):
            spans_from_jaeger_agent(evil2)


def test_app_rejects_both_cache_tiers():
    from tempo_tpu.app import App
    from tempo_tpu.app.config import Config

    cfg = Config(target="querier")
    cfg.storage.backend = "mem"
    cfg.storage.memcached_addrs = "127.0.0.1:11211"
    cfg.storage.redis_addrs = "127.0.0.1:6379"
    with pytest.raises(ValueError, match="ONE shared cache tier"):
        App(cfg)
