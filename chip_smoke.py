#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on one TPU chip.

Boots the single-binary App of `examples/single-binary.yaml` in THIS
process (one process may hold the chip), serves it over real HTTP, and
drives warm -> ingest -> collect -> read -> steady state from client
threads, checking every answer against a numpy oracle built from
`--seed`:

    python chip_smoke.py                      # one chip, the real size
    python chip_smoke.py --chips 4            # serving mesh vs one device, only
    python chip_smoke.py --recover            # kill -9 under the durable cell's
        # load and restart: the server is a CHILD here (see `recover`)
    JAX_PLATFORMS=cpu python chip_smoke.py --size rehearsal   # a rehearsal
        # of the control flow at a toy size, with or without a chip:
        # never prints "ok": true, always exits 1

Every line on stdout is one JSON object; the last one is the verdict.
A failed check raises: no phase sits in a try/except that lets the run
go on. It times nothing but its own phases' wall seconds, named as such.

The tenants' span-metrics series are not on `/metrics` (that surface is
the process's own telemetry); they leave the process by remote write and
are read here over `/internal/generator/collect`, the per-tenant
collection surface, while `/metrics` answers for discards, dispatch
errors, the sampler and the jit compile counters.

Not exercised here (so silence about them is not a pass): compaction,
the paged layout, matview; the ingest WAL and the fleet only by
`--recover`.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import types
import urllib.parse
from http.server import BaseHTTPRequestHandler, HTTPServer

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
TENANTS = ("k6-a", "k6-b")
PROCESSORS = ("span-metrics", "service-graphs", "local-blocks")
CLIENTS = 4
KINDS = (0, 1)                 # series kinds: UNSPECIFIED, INTERNAL
KIND_STRS = ("SPAN_KIND_UNSPECIFIED", "SPAN_KIND_INTERNAL", "SPAN_KIND_SERVER",
             "SPAN_KIND_CLIENT")
STATUS_STRS = ("STATUS_CODE_UNSET", "STATUS_CODE_OK", "STATUS_CODE_ERROR")
N_VUS = 16                     # distinct values of the span attribute k6.vu
SKETCH_REL_ERR = 0.01          # SpanMetricsConfig.sketch_rel_err default
# /api/metrics quantile_over_time rides log2 buckets with in-bucket
# interpolation: the answer is inside the exact value's power-of-two
# bucket, so it is off by less than a factor of two either way
LOG2_TIER_FACTOR = 2.0

# push shapes: (resource groups, spans per group, spans per trace)
SHAPES = {
    1000: (8, 125, 5),
    2000: (16, 125, 5),
    4000: (32, 125, 5),
    8000: (32, 250, 5),
    16384: (32, 512, 4),
}

SERVICES = 32
SMALL, BIG = 1000, 16384       # the k6 mix: mostly SMALL pushes, some BIG
WARM = tuple(SHAPES)           # one canary per pow-2 bucket the coalescer forms

SIZES = {
    # k6 write-path shape (BASELINE.json config 1) at the width the
    # defaults are sized for: >= 16,384 active series per tenant fill the
    # whole 16,384 x 1,269 DDSketch plane of the 65,536-row series table.
    # Per tenant and round 400 x 1,000 + 8 x 16,384 spans: with two
    # tenants, two rounds and the canaries, 2,187,056 spans in all
    "real": dict(names=96, n_small=400, n_big=8, min_series=16384),
    # control-flow rehearsal for a machine without a chip
    "rehearsal": dict(names=4, n_small=10, n_big=1, min_series=700),
}


class SmokeFailure(AssertionError):
    pass


def say(**kv) -> None:
    print(json.dumps(kv), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# OTLP payloads: one fixed byte layout per push shape, patched with numpy
# ---------------------------------------------------------------------------


class PushShape:
    """An ExportTraceServiceRequest of `groups` ResourceSpans x `per`
    spans whose every variable field sits at a fixed offset, so a push is
    a handful of numpy column writes (the clients must not spend the
    server's interpreter lock encoding protobuf span by span). Times ride
    fixed64, as the OTLP schema has them."""

    def __init__(self, groups: int, per: int, trace_len: int) -> None:
        from tempo_tpu.model import proto_wire as pw

        self.groups, self.per, self.trace_len = groups, per, trace_len
        self.n = groups * per
        span, self.off = b"", {}

        def put(key, head: bytes, width: int, tail: bytes = b"") -> None:
            nonlocal span
            span += head
            self.off[key] = (len(span), len(span) + width)
            span += bytes(width) + tail

        put("trace_id", b"\x0a\x10", 16)
        put("span_id", b"\x12\x08", 8)
        put("parent", b"\x22\x08", 8)
        put("name", b"\x2a\x07op-", 4)
        put("kind", b"\x30", 1)
        put("start", b"\x39", 8)
        put("end", b"\x41", 8)
        kv = pw.enc_field_str(1, "k6.vu") + pw.enc_field_msg(
            2, pw.enc_field_str(1, "vu-00"))
        put("vu", pw.enc_tag(9, 2) + pw.enc_varint(len(kv)) + kv[:-2], 2)
        put("status", b"\x7a\x02\x18", 1)
        self.span_len = len(span)
        rec = pw.enc_tag(2, 2) + pw.enc_varint(self.span_len) + span
        self.stride = len(rec)
        self.span_at = self.stride - self.span_len
        scope_spans = rec * per
        resource = pw.enc_field_msg(1, pw.enc_field_msg(
            1, pw.enc_field_str(1, "service.name")
            + pw.enc_field_msg(2, pw.enc_field_str(1, "svc-0000"))))
        group = pw.enc_field_msg(
            1, resource + pw.enc_field_msg(2, scope_spans))
        self.head = len(group) - len(scope_spans)
        self.svc_at = group.index(b"svc-0000") + 4
        self.template = np.frombuffer(group * groups, np.uint8).reshape(
            groups, len(group)).copy()

    def build(self, cols: dict) -> bytes:
        """`cols[key]` is a [groups, per, width] uint8 array per variable
        field, plus `service` [groups, 4]."""
        buf = self.template.copy()
        buf[:, self.svc_at:self.svc_at + 4] = cols["service"]
        spans = buf[:, self.head:].reshape(self.groups, self.per, self.stride)
        for key, (lo, hi) in self.off.items():
            spans[:, :, self.span_at + lo:self.span_at + hi] = cols[key]
        return buf.tobytes()


def digits(v: np.ndarray, width: int) -> np.ndarray:
    """Zero-padded ASCII decimal digits of `v`, as a trailing uint8 axis."""
    pows = 10 ** np.arange(width - 1, -1, -1)
    return (v[..., None] // pows % 10 + 48).astype(np.uint8)


def le_bytes(v: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(v.astype("<i8")).view(np.uint8).reshape(
        v.shape + (8,))


class Workload:
    """The seeded span stream of one tenant, and the record of what was
    pushed (the oracle's input). Span g of the stream takes series combo
    g mod (names x kinds x status) inside its resource group's service,
    so every series appears; one client->server pair per group feeds the
    service graph."""

    def __init__(self, seed: int, tenant_idx: int, size: dict,
                 shapes: dict) -> None:
        self.size, self.shapes = size, shapes
        self.seed, self.tenant_idx = seed, tenant_idx
        self.n_combo = size["names"] * len(KINDS) * 3
        self.lock = threading.Lock()
        self.pushed: list[dict] = []
        self.seq = 0

    def make(self, n_spans: int, push_idx: int) -> tuple[bytes, dict]:
        sh = self.shapes[n_spans]
        G, P, T = sh.groups, sh.per, sh.trace_len
        S = SERVICES
        rng = np.random.default_rng([self.seed, self.tenant_idx, push_idx])
        svc = (push_idx * G + np.arange(G)) % S                     # [G]
        # each service cycles through its combos across its appearances
        visit = push_idx * G // S
        combo = (visit * P + np.arange(P)[None, :] + svc[:, None] * 7) \
            % self.n_combo                                          # [G,P]
        name = combo // (len(KINDS) * 3)
        kind = np.asarray(KINDS)[combo // 3 % len(KINDS)]
        status = combo % 3
        dur_ns = np.clip(rng.lognormal(np.log(50e6), 1.0, (G, P)),
                         2e3, 10e9).astype(np.int64)
        vu = rng.integers(0, N_VUS, (G, P))
        span_id = rng.integers(1, 1 << 62, (G, P), dtype=np.int64)
        tid = rng.integers(0, 256, (G, P // T, 16), dtype=np.uint8)
        trace_id = np.repeat(tid, T, axis=1)                        # [G,P,16]
        parent = np.zeros((G, P), np.int64)
        first = np.arange(P) % T == 0
        # children hang off the first span of their trace
        parent[:, ~first] = np.repeat(span_id[:, first], T, axis=1)[:, ~first]
        # the service-graph pairs: the last span of group j is a CLIENT
        # call whose SERVER side sits in group j+1 (same trace, parent =
        # the client span); the last group calls itself. Both sides ride
        # this push, so every edge completes here and the half-edge store
        # stays empty — and the canary shapes mint no edge the mix does
        # not refresh (a series idle for 15 minutes is evicted)
        g = np.arange(G)
        srv_g = np.minimum(g + 1, G - 1)
        srv_p = np.where(g < G - 1, P - 2, P - 3)
        for gi, pi, k in ((g, P - 1, 3), (srv_g, srv_p, 2)):
            name[gi, pi] = self.size["names"]      # op-<names>: the call
            status[gi, pi] = 0
            kind[gi, pi] = k
        trace_id[srv_g, srv_p] = trace_id[g, P - 1]
        parent[srv_g, srv_p] = span_id[g, P - 1]
        now_ns = time.time_ns()
        end = now_ns - rng.integers(0, 10**9, (G, P))
        start = end - dur_ns
        payload = sh.build({
            "service": digits(svc, 4), "trace_id": trace_id,
            "span_id": le_bytes(span_id), "parent": le_bytes(parent),
            "name": digits(name, 4), "kind": kind[..., None].astype(np.uint8),
            "start": le_bytes(start), "end": le_bytes(end),
            "vu": digits(vu, 2), "status": status[..., None].astype(np.uint8),
        })
        rec = {"svc": np.repeat(svc, P), "name": name.ravel(),
               "kind": kind.ravel(), "status": status.ravel(),
               "dur_ns": dur_ns.ravel(), "start_ns": start.ravel(),
               "end_ns": end.ravel(), "vu": vu.ravel(),
               "trace_id": trace_id.reshape(-1, 16),
               "span_id": span_id.ravel(), "pairs": G}
        return payload, rec

    def note(self, rec: dict) -> None:
        with self.lock:
            self.pushed.append(rec)

    def column(self, key: str) -> np.ndarray:
        return np.concatenate([r[key] for r in self.pushed])


# ---------------------------------------------------------------------------
# HTTP
# ---------------------------------------------------------------------------


def http_call(port: int, method: str, path: str, tenant: str = "",
              body: bytes | None = None, timeout: float = 600.0
              ) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        headers = {"X-Scope-OrgID": tenant} if tenant else {}
        if body is not None:
            headers["Content-Type"] = "application/x-protobuf"
        conn.request(method, path, body=body, headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def get_json(port: int, path: str, tenant: str = "", **params) -> dict:
    if params:
        path += "?" + urllib.parse.urlencode(params)
    status, body = http_call(port, "GET", path, tenant)
    check(status == 200, f"GET {path} -> {status}: {body[:300]!r}")
    return json.loads(body)


def scrape(port: int) -> dict:
    """/metrics as {(sample name, ((label, value), ...)): float}."""
    from tempo_tpu.obs import parse_exposition

    status, body = http_call(port, "GET", "/metrics")
    check(status == 200, f"/metrics -> {status}")
    out: dict = {}
    for fam in parse_exposition(body.decode()).values():
        out.update(fam["samples"])
    return out


def metric_sum(m: dict, name: str, **labels) -> float:
    want = set(labels.items())
    return sum(v for (n, ls), v in m.items()
               if n == name and want <= set(ls))


def push(port: int, tenant: str, payload: bytes) -> None:
    """One OTLP push; anything but a clean 2xx is a failure, not a retry."""
    status, body = http_call(port, "POST", "/v1/traces", tenant, payload)
    check(200 <= status < 300,
          f"push to {tenant} answered {status}: {body[:300]!r}")
    check(not json.loads(body or b"{}").get("errors"),
          f"push to {tenant} acknowledged with discards: {body[:300]!r}")


class Sink:
    """The loopback remote-write receiver."""

    def __init__(self) -> None:
        sink = self
        self.bodies: list[int] = []

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0) or 0)
                sink.bodies.append(len(self.rfile.read(n)))
                self.send_response(200)
                self.send_header("Content-Length", "0")
                self.end_headers()

            def log_message(self, *a):
                pass

        self.srv = HTTPServer(("127.0.0.1", 0), Handler)
        self.url = f"http://127.0.0.1:{self.srv.server_address[1]}/api/v1/push"
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()

    def close(self) -> None:
        self.srv.shutdown()
        self.srv.server_close()


# ---------------------------------------------------------------------------
# the served App
# ---------------------------------------------------------------------------

# per-tenant overrides a deployment at this rate would set: the defaults
# (15 MB/s, 20 MB burst, 10,000 live traces) are below this run
LIMITS = {
    "ingestion": {"rate_limit_bytes": 2_000_000_000,
                  "burst_size_bytes": 2_000_000_000,
                  "max_traces_per_user": 5_000_000},
    "generator": {"processors": list(PROCESSORS)},
}


def boot(workdir: str, sink_url: str, mesh: dict | None = None):
    import yaml

    from tempo_tpu.app.api import serve
    from tempo_tpu.app.app import App
    from tempo_tpu.app.config import load_config

    limits_path = os.path.join(workdir, "overrides.yaml")
    with open(limits_path, "w") as f:
        yaml.safe_dump({"overrides": {t: LIMITS for t in TENANTS}}, f)
    moved = {
        "server": {"http_listen_port": 0},
        "storage": {"local_path": os.path.join(workdir, "blocks"),
                    "wal_path": os.path.join(workdir, "wal")},
        "per_tenant_override_config": limits_path,
        "generator": {
            "remote_write": {"url": sink_url},
            # RF1 blocks in the backend are what TraceQL metrics may read
            # (the reference's flush_to_storage); one block per cut, cut
            # by the smoke through the processor's own tick
            "localblocks": {"data_dir": os.path.join(workdir, "localblocks"),
                            "flush_to_storage": True,
                            "max_block_duration_s": 3600.0}},
        # reads of what was just written must reach the backend blocks
        "frontend": {"query_backend_after_s": 2.0},
        # compaction is not exercised: keep its loop from rewriting the
        # blocks under the read phase
        "compaction_interval_s": 86400.0,
        "usage_stats_enabled": False,
    }
    if mesh is not None:
        moved["mesh"] = mesh
    cfg = load_config(os.path.join(REPO, "examples", "single-binary.yaml"),
                      overrides=moved)
    app = App(cfg)
    app.start_loops()
    srv = serve(app, block=False)
    return app, srv, srv.server_address[1]


def run_clients(port: int, jobs: list, loads: dict) -> None:
    """CLIENTS closed-loop threads drain `jobs` = [(tenant, n_spans)]."""
    it = iter(jobs)
    lock = threading.Lock()
    errors: list[BaseException] = []

    def client() -> None:
        while not errors:
            with lock:
                job = next(it, None)
                if job is None:
                    return
                tenant, n_spans = job
                wl = loads[tenant]
                idx, wl.seq = wl.seq, wl.seq + 1
            try:
                payload, rec = wl.make(n_spans, idx)
                push(port, tenant, payload)
                wl.note(rec)
            except BaseException as e:     # re-raised by the caller below
                errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def ingest_mix(seed: int, round_idx: int, size: dict) -> list:
    jobs = [(t, SMALL) for t in TENANTS for _ in range(size["n_small"])]
    jobs += [(t, BIG) for t in TENANTS for _ in range(size["n_big"])]
    np.random.default_rng([seed, 1000 + round_idx]).shuffle(jobs)
    return [(t, int(n)) for t, n in jobs]


def compiles(m: dict) -> tuple[float, float]:
    return (metric_sum(m, "tempo_jax_jit_compile_total"),
            metric_sum(m, "tempo_jax_jit_compile_seconds_total"))


def check_no_loss(m: dict) -> None:
    """Nothing sampled out, shed to a 429, dropped by a failed dispatch or
    lost on the way to the generator: the exact counts mean nothing else."""
    discarded = {ls: v for (n, ls), v in m.items()
                 if n == "tempo_discarded_spans_total" and v}
    check(not discarded, f"spans were discarded: {discarded}")
    for name in ("tempo_sched_dispatch_errors_total",
                 "tempo_distributor_push_failures_total",
                 "tempo_remote_write_failed_sends_total"):
        check(metric_sum(m, name) == 0, f"{name} = {metric_sum(m, name)}")
    keep = metric_sum(m, "tempo_sched_ingest_keep_fraction")
    check(keep == 1.0, f"overload sampling is armed: keep fraction {keep}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_warm(app, port: int, loads: dict) -> None:
    """A canary before the load, as a deployment warms a cold server: one
    push of each pow-2 bucket the coalescer can form, each landing alone,
    so every bucket shape compiles (or is found in the persistent cache)
    before four closed-loop clients queue behind a cold compile."""
    for n_spans in WARM:
        for tenant in TENANTS:
            run_clients(port, [(tenant, n_spans)], loads)
            deadline = time.monotonic() + 900
            while app.sched.pending():
                check(time.monotonic() < deadline,
                      "scheduler did not drain a canary push in 900 s")
                time.sleep(0.05)


def series_key(labels) -> tuple:
    d = dict(labels)
    return (d["service"], d["span_name"], d["span_kind"], d["status_code"])


def phase_collect(app, port: int, loads: dict, sink: Sink, size: dict) -> dict:
    n_sink = len(sink.bodies)
    app.generator.collect_all()          # the collection loop's own call
    check(len(sink.bodies) > n_sink and max(sink.bodies[n_sink:]) > 0,
          "no remote-write request with a body reached the loopback sink")
    report = {}
    for tenant, wl in loads.items():
        samples = get_json(port, "/internal/generator/collect", tenant,
                           ts_ms=int(time.time() * 1000))["samples"]
        by_name: dict[str, float] = {}
        series = set()
        for s in samples:
            by_name[s["name"]] = by_name.get(s["name"], 0.0) + s["value"]
            if s["name"] == "traces_spanmetrics_calls_total":
                series.add(series_key(s["labels"]))
        n = len(wl.column("svc"))
        dur_s = ((wl.column("end_ns") - wl.column("start_ns")) / 1e9
                 ).astype(np.float32)
        pairs = sum(r["pairs"] for r in wl.pushed)
        calls = by_name.get("traces_spanmetrics_calls_total", 0.0)
        count = by_name.get("traces_spanmetrics_latency_count", 0.0)
        lat_sum = by_name.get("traces_spanmetrics_latency_sum", 0.0)
        edges = by_name.get("traces_service_graph_request_total", 0.0)
        want_sum = float(dur_s.astype(np.float64).sum())
        check(calls == n, f"{tenant}: calls_total {calls} != {n} pushed")
        check(count == n, f"{tenant}: latency_count {count} != {n} pushed")
        check(abs(lat_sum - want_sum) <= 1e-4 * want_sum,
              f"{tenant}: latency_sum {lat_sum} vs oracle {want_sum}")
        check(edges == pairs,
              f"{tenant}: service graph counted {edges} edges, {pairs} sent")
        check(len(series) >= size["min_series"],
              f"{tenant}: {len(series)} active series < {size['min_series']}")
        # quantiles of a few series against the sketch's stated bound
        oracle = _series_durations(wl, dur_s)
        probes = sorted(oracle, key=lambda k: -len(oracle[k]))[:4]
        worst = 0.0
        for q in (0.5, 0.99):
            got = {series_key(e["labels"]): e["value"] for e in get_json(
                port, "/internal/generator/quantile", tenant, q=q
            )["quantiles"]}
            check(len(got) >= min(size["min_series"], 16384),
                  f"{tenant}: quantile endpoint answered {len(got)} series")
            for key in probes:
                check(key in got, f"{tenant}: no quantile for series {key}")
                vals = np.sort(oracle[key].astype(np.float64))
                k = int(np.ceil(q * len(vals))) - 1
                lo = vals[max(k - 1, 0)] * (1 - 1.1 * SKETCH_REL_ERR)
                hi = vals[min(k + 1, len(vals) - 1)] * (1 + 1.1 * SKETCH_REL_ERR)
                check(lo <= got[key] <= hi,
                      f"{tenant}: q{q} of {key} = {got[key]} outside "
                      f"[{lo}, {hi}] ({len(vals)} spans)")
                worst = max(worst, abs(got[key] - vals[k]) / vals[k])
        proc = app.generator.instances[tenant].processors["span-metrics"]
        report[tenant] = {
            "spans": n, "series_active": len(series), "edges": pairs,
            "latency_sum_rel_err": abs(lat_sum - want_sum) / want_sum,
            "quantile_worst_rel_err_vs_rank": worst,
            "layout": app.generator.instances[tenant].state_layout,
            "on_mesh": proc._mesh is not None,
        }
    return report


def _series_durations(wl: Workload, dur_s: np.ndarray) -> dict:
    """{series key: f32 durations} of the four busiest series (the keys in
    the server's label form)."""
    code = ((wl.column("svc") * 10000 + wl.column("name")) * 10
            + wl.column("kind")) * 10 + wl.column("status")
    uniq, inv, cnt = np.unique(code, return_inverse=True, return_counts=True)
    out = {}
    for i in np.argsort(-cnt)[:4]:
        c = int(uniq[i])
        status, kind = c % 10, c // 10 % 10
        name, svc = c // 100 % 10000, c // 1000000
        out[(f"svc-{svc:04d}", f"op-{name:04d}", KIND_STRS[kind],
             STATUS_STRS[status])] = dur_s[inv == i]
    return out


def phase_read(app, port: int, loads: dict) -> dict:
    # cut everything buffered to blocks: the generator's local-blocks tick
    # and the ingester's flush, the calls their own loops make
    for tenant in TENANTS:
        app.generator.instances[tenant].tick(immediate=True)
    app.ingester.flush_all()
    # the flush workers complete and ship blocks on their own threads:
    # wait, as a reader does, until the poller sees every pushed span
    # both in the generator's RF1 blocks and in the ingester's
    want = {t: len(wl.column("svc")) for t, wl in loads.items()}
    deadline = time.monotonic() + 600
    while True:
        app.db.poll_now()
        held = {t: [sum(m.total_spans for m in app.db.blocklist.metas(t)
                        if (m.replication_factor == 1) == rf1)
                    for rf1 in (True, False)] for t in TENANTS}
        if all(held[t] == [want[t], want[t]] for t in TENANTS):
            break
        check(time.monotonic() < deadline,
              f"blocks never held every pushed span: {held} of {want}")
        time.sleep(0.5)
    report = {"blocks": {t: len(app.db.blocklist.metas(t)) for t in TENANTS},
              "spans_in_blocks": held}
    # every span must be behind the frontend's backend cutoff (2 s)
    newest_s = max(wl.column("end_ns").max() for wl in loads.values()) / 1e9
    time.sleep(max(0.0, newest_s + 3.0 - time.time()))
    stats0 = dict(app.db.plane_stats)
    for tenant, wl in loads.items():
        svc, dur_ns = wl.column("svc"), wl.column("dur_ns")
        start_ns = wl.column("start_ns")
        t0 = int(start_ns.min() // 10**9) - 1
        step = 10
        t1 = t0 + (int(start_ns.max() // 10**9) + 2 - t0 + step - 1) \
            // step * step
        by = "(resource.service.name)"
        # rate(): totals exact
        got = get_json(port, "/api/metrics/query_range", tenant,
                       q="{ } | rate() by " + by, start=t0, end=t1, step=step)
        totals = {}
        for s in got["series"]:
            name = _label(s, "resource.service.name")
            totals[name] = round(sum(
                float(p["value"]) for p in s["samples"]) * step)
        want = {f"svc-{i:04d}": int(c)
                for i, c in enumerate(np.bincount(svc)) if c}
        check(totals == want, f"{tenant}: rate() totals differ from the "
                              f"oracle: {_diff(totals, want)}")
        # quantile_over_time: one step over the whole window
        got = get_json(port, "/api/metrics/query_range", tenant,
                       q="{ } | quantile_over_time(duration, .99) by " + by,
                       start=t0, end=t1, step=t1 - t0)
        seen = 0
        for s in got["series"]:
            name = _label(s, "resource.service.name")
            vals = [float(p["value"]) for p in s["samples"]
                    if p["value"] is not None]
            check(len(vals) == 1, f"{tenant}: {name} p99 has {len(vals)} steps")
            exact = float(np.quantile(
                dur_ns[svc == int(name[4:])] / 1e9, 0.99))
            check(exact / LOG2_TIER_FACTOR <= vals[0] <= exact * LOG2_TIER_FACTOR,
                  f"{tenant}: p99 of {name} = {vals[0]}, exact {exact}")
            seen += 1
        check(seen == len(want), f"{tenant}: p99 for {seen} services, "
                                 f"{len(want)} pushed")
    stats1 = dict(app.db.plane_stats)
    moved = {k: stats1.get(k, 0) - stats0.get(k, 0)
             for k in stats1 if stats1.get(k, 0) != stats0.get(k, 0)}
    check(moved.get("fused_metric_blocks", 0) >= 2 * len(TENANTS),
          f"the device read plane did not answer the metrics queries: {moved}")
    check(not [k for k in moved if k.startswith("fallback_")
               or k == "host_metric_blocks"],
          f"metrics queries fell back to the host engine: {moved}")
    report["plane_stats_moved"] = moved
    # search: an attribute and a duration predicate with few matches
    tenant, wl = next(iter(loads.items()))
    vu, dur_ns, tids = wl.column("vu"), wl.column("dur_ns"), wl.column("trace_id")
    sel = vu == 3
    floor_ms = int(np.sort(dur_ns[sel])[-12] // 10**6)
    hit = sel & (dur_ns > floor_ms * 10**6)
    want_ids = {bytes(t).hex() for t in tids[hit]}
    start_ns = wl.column("start_ns")
    got = get_json(port, "/api/search", tenant,
                   q=f'{{ span.k6.vu = "vu-03" && duration > {floor_ms}ms }}',
                   start=int(start_ns.min() // 10**9) - 1,
                   end=int(time.time()) + 1, limit=100)
    got_ids = {t["traceID"].rjust(32, "0") for t in got["traces"]}
    check(got_ids == want_ids, f"{tenant}: search returned {len(got_ids)} "
                               f"traces, expected {len(want_ids)}: "
                               f"{sorted(got_ids ^ want_ids)[:4]}")
    report["search_matches"] = len(got_ids)
    # trace by id: every pushed span of the trace comes back
    tid = tids[np.flatnonzero(hit)[0]]
    rows = np.flatnonzero((tids == tid).all(axis=1))
    got = get_json(port, "/api/traces/" + bytes(tid).hex(), tenant)
    want_spans = {int(wl.column("span_id")[r]).to_bytes(8, "little").hex():
                  (int(start_ns[r]), int(wl.column("end_ns")[r]),
                   f"op-{int(wl.column('name')[r]):04d}") for r in rows}
    got_spans = {s["span_id"]: (int(s["start_unix_nano"]),
                                int(s["end_unix_nano"]), s["name"])
                 for s in got["spans"]}
    check(got_spans == want_spans,
          f"{tenant}: trace {bytes(tid).hex()} came back as {got_spans}, "
          f"pushed {want_spans}")
    report["trace_spans"] = len(got_spans)
    return report


def _label(series: dict, key: str) -> str:
    for lab in series["labels"]:
        if lab["key"] == key:
            return next(iter(lab["value"].values()))
    raise SmokeFailure(f"series without {key}: {series['labels']}")


def _diff(a: dict, b: dict) -> dict:
    return {k: (a.get(k), b.get(k)) for k in sorted(set(a) | set(b))
            if a.get(k) != b.get(k)}


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


def timed(walls: dict, name: str, fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    walls[name + "_wall_s"] = round(time.perf_counter() - t0, 3)
    return out


def make_loads(seed: int, size: dict) -> dict:
    shapes = {n: PushShape(*SHAPES[n]) for n in SHAPES}
    return {t: Workload(seed, i, size, shapes) for i, t in enumerate(TENANTS)}


def peak_bytes() -> dict:
    import jax

    out = {}
    for d in jax.devices():
        st = d.memory_stats() or {}
        out[str(d.id)] = {k: st.get(k) for k in
                          ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")}
    return out


def one_chip(args, size: dict, workdir: str) -> None:
    walls: dict = {}
    sink = Sink()
    app, srv, port = timed(walls, "boot", boot, workdir, sink.url)
    say(limits_set=LIMITS, tenants=TENANTS, clients=CLIENTS,
        processors=PROCESSORS, size=args.size)
    loads = make_loads(args.seed, size)
    c0 = compiles(scrape(port))
    timed(walls, "warm", phase_warm, app, port, loads)
    timed(walls, "ingest", run_clients, port,
          ingest_mix(args.seed, 1, size), loads)
    m = scrape(port)
    check_no_loss(m)
    c1 = compiles(m)
    report = timed(walls, "collect", phase_collect, app, port, loads, sink, size)
    say(phase="collect", tenants=report)
    say(phase="read", **timed(walls, "read", phase_read, app, port, loads))
    # steady state: the same mix again must compile nothing
    c2 = compiles(scrape(port))
    timed(walls, "steady_ingest", run_clients, port,
          ingest_mix(args.seed, 2, size), loads)
    m = scrape(port)
    check_no_loss(m)
    c3 = compiles(m)
    check(c3[0] == c2[0], f"{c3[0] - c2[0]} compilations in the second "
                          "ingest round: steady state re-traces")
    report = timed(walls, "steady_collect", phase_collect, app, port, loads,
                   sink, size)
    say(phase="steady", spans_pushed=sum(r["spans"] for r in report.values()),
        compiles_cold=c1[0] - c0[0],
        compile_seconds_cold=round(c1[1] - c0[1], 3),
        compiles_second_round=c3[0] - c2[0],
        compiles_total=c3[0], compile_seconds_total=round(c3[1], 3),
        compile_seconds_by_fn={
            dict(ls)["fn"]: [metric_sum(m, "tempo_jax_jit_compile_total",
                                        fn=dict(ls)["fn"]), round(v, 3)]
            for (n, ls), v in sorted(m.items())
            if n == "tempo_jax_jit_compile_seconds_total"},
        remote_write_requests=len(sink.bodies))
    say(wall_seconds=walls, peak_device_memory=peak_bytes())
    abandon(app, srv, sink)


EXACT_SUFFIXES = ("_total", "_count", "_bucket")   # integer-valued families


def snapshot(port: int) -> dict:
    """{tenant: ({(name, labels): value}, {q: {series key: quantile}})}."""
    out = {}
    for tenant in TENANTS:
        samples = get_json(port, "/internal/generator/collect", tenant,
                           ts_ms=1)["samples"]
        values = {(s["name"], tuple(map(tuple, s["labels"]))): s["value"]
                  for s in samples}
        quantiles = {q: {series_key(e["labels"]): e["value"] for e in get_json(
            port, "/internal/generator/quantile", tenant, q=q)["quantiles"]}
            for q in (0.5, 0.99)}
        out[tenant] = (values, quantiles)
    return out


def abandon(app, srv, sink: Sink) -> None:
    """Stop an App's servers and loops WITHOUT its shutdown flush (which
    would cut every live trace to blocks — minutes of host work the mesh
    comparison has no use for) so the next App has the process alone."""
    srv.shutdown()
    srv.server_close()
    for part in (app, app.ingester, app.generator):
        part._stop.set()
    # a collection tick in flight still sends to this App's sink: let it
    # land, or the process-wide failed-sends counter fails the next arm
    for t in app.generator._threads:
        t.join(timeout=300)
        check(not t.is_alive(), "the generator's collection loop did not "
                                "stop within 300 s")
    app.sched.flush()
    app.db.shutdown()
    sink.close()


def four_chips(args, size: dict, workdir: str) -> None:
    """The serving mesh against one device, and nothing else: the same
    seeded ingest into an App whose state is split four ways over
    'series' and into a single-device App, one after the other (the
    scheduler, the mesh and the page pool are process-wide), then the two
    collected states side by side."""
    import jax

    # a quarter of the one-chip mix per arm (four chips cost four times
    # the seconds): still every series of the full-width state
    size = dict(size, n_small=size["n_small"] // 4, n_big=size["n_big"] // 4)
    arms = (("mesh", {"enabled": True, "devices": 4, "series_shards": 4}),
            ("single", None))
    got, walls = {}, {}
    for arm, mesh in arms:
        sub = os.path.join(workdir, arm)
        os.makedirs(sub)
        sink = Sink()
        t0 = time.perf_counter()
        app, srv, port = boot(sub, sink.url, mesh=mesh)
        loads = make_loads(args.seed, size)
        phase_warm(app, port, loads)
        run_clients(port, ingest_mix(args.seed, 1, size), loads)
        check_no_loss(scrape(port))
        report = phase_collect(app, port, loads, sink, size)
        got[arm] = snapshot(port)
        if mesh is not None:
            spread = {}
            for tenant in TENANTS:
                proc = app.generator.instances[tenant].processors[
                    "span-metrics"]
                check(proc._mesh is not None,
                      f"{tenant}: the processor never joined the mesh")
                n = len(proc.calls.state.values.sharding.device_set)
                check(n == 4, f"{tenant}: series state lives on {n} devices")
                check(len(proc.dd.counts.sharding.device_set) == 4,
                      f"{tenant}: the DDSketch plane is not spread")
                spread[tenant] = n
            in_use = {str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
                      for d in jax.devices()[:4]}
            if jax.devices()[0].platform == "tpu":
                check(all(in_use.values()),
                      f"a device of the mesh holds nothing: {in_use}")
            say(arm=arm, state_device_count=spread, bytes_in_use=in_use)
        say(arm=arm, tenants=report)
        abandon(app, srv, sink)
        del app
        walls[arm + "_wall_s"] = round(time.perf_counter() - t0, 3)
    compared = {}
    for tenant in TENANTS:
        (vm, qm), (vs, qs) = got["mesh"][tenant], got["single"][tenant]
        check(vm.keys() == vs.keys(),
              f"{tenant}: series sets differ: {len(vm)} vs {len(vs)}")
        worst = 0.0
        for key, a in vm.items():
            b = vs[key]
            if key[0].endswith(EXACT_SUFFIXES):
                check(a == b, f"{tenant}: {key} = {a} on the mesh, {b} on "
                              "one device")
            else:
                check(abs(a - b) <= 1e-5 * max(abs(a), abs(b)),
                      f"{tenant}: {key} = {a} on the mesh, {b} on one device")
                worst = max(worst, abs(a - b) / max(abs(a), abs(b), 1e-30))
        n_q = 0
        for q in qm:
            # which series got one of the 16,384 sketch rows depends on
            # arrival order: compare those both runs gave a row
            both = qm[q].keys() & qs[q].keys()
            check(len(both) >= len(qm[q]) // 2,
                  f"{tenant}: only {len(both)} series have a q{q} in both")
            bad = [(k, qm[q][k], qs[q][k]) for k in both
                   if qm[q][k] != qs[q][k]]
            check(not bad, f"{tenant}: {len(bad)} q{q} values differ, "
                           f"e.g. {bad[:3]}")
            n_q += len(both)
        compared[tenant] = {"samples_equal": len(vm), "quantiles_equal": n_q,
                            "float_sum_worst_rel_diff": worst}
    say(phase="mesh_vs_single", tenants=compared, wall_seconds=walls)


# ---------------------------------------------------------------------------
# --recover: kill -9 under the durable cell's load, restart, compare
# ---------------------------------------------------------------------------

RECOVER_CELL = "k6-write-wal.steady"
RECOVER_SECONDS = 20.0      # of the cell's closed loop before the kill -9
# the kill falls this long before the load generator's window closes:
# every client is mid-push, and few connects are refused after it
KILL_BEFORE_END_S = 0.5
STOP_WAIT_S = 600.0         # the last SIGTERM's grace
MEMBERS: list = []          # every child started: none may outlive us


class Member:
    """One `tempo_tpu.fleet.worker` child from a written yaml: the chip is
    the child's, this process never starts JAX."""

    def __init__(self, yaml_path: str, log_path: str, wait_s: float) -> None:
        t0 = time.monotonic()
        self.log = open(log_path, "ab")
        MEMBERS.append(self)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tempo_tpu.fleet.worker", "--config",
             yaml_path], stdout=subprocess.PIPE, stderr=self.log, cwd=REPO)
        self.ready = None
        threading.Thread(target=self._read, daemon=True).start()
        while self.ready is None and time.monotonic() - t0 < wait_s \
                and self.proc.poll() is None:
            time.sleep(0.05)
        self.ready_s = time.monotonic() - t0
        if self.ready is None:
            self.kill()
            with open(log_path, "rb") as f:
                tail = f.read()[-3000:].decode(errors="replace")
            raise SmokeFailure(f"the member was not ready after "
                               f"{self.ready_s:.1f} s (rc "
                               f"{self.proc.returncode}): {tail}")
        self.port = self.ready["port"]

    def _read(self) -> None:
        for line in self.proc.stdout:      # the ready line, then drained
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if isinstance(doc, dict) and doc.get("ready"):
                self.ready = doc

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=30)

    def terminate(self, wait_s: float) -> float | None:
        """SIGTERM; seconds to its exit, None if it had to be killed."""
        t0 = time.monotonic()
        self.proc.terminate()
        try:
            self.proc.wait(timeout=wait_s)
            return time.monotonic() - t0
        except subprocess.TimeoutExpired:
            self.kill()
            return None


def member_yaml(config: dict, workdir: str, sink_url: str) -> str:
    """The deployment `chipbench.lib.boot` boots in process, written as a
    yaml for a child: the example's file under the same moves into
    `workdir` and the configuration's overrides
    (`tests/test_wal_cell.py` holds the two to one `Config`)."""
    import yaml

    from chipbench import lib

    limits_path = os.path.join(workdir, "overrides.yaml")
    with open(limits_path, "w") as f:
        yaml.safe_dump({"overrides": {t: config["tenant_limits"]
                                      for t in config["tenants"]}}, f)
    with open(os.path.join(REPO, config["example_yaml"])) as f:
        doc = yaml.safe_load(f)
    doc = lib.merged(lib.merged(doc, {
        "server": {"http_listen_port": 0},
        "storage": {"local_path": os.path.join(workdir, "blocks"),
                    "wal_path": os.path.join(workdir, "wal")},
        "per_tenant_override_config": limits_path,
        "generator": {
            "remote_write": {"url": sink_url},
            "localblocks": {"data_dir": os.path.join(workdir, "localblocks")}},
        "usage_stats_enabled": False}), config.get("yaml_overrides", {}))
    path = os.path.join(workdir, "member.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(doc, f)
    return path


def recover(args) -> int:
    """The durable single binary (`k6-single-binary-wal`) under its cell's
    traffic, killed with SIGKILL mid-push and restarted over the same
    directories. The cell's own mix does what it does in the cell, with
    the server a child: its set-up (canaries, prefill), RECOVER_SECONDS
    of its closed loop from its load generator, `kill -9`, its check of
    the log on disk (every push that got its 2xx is there, and besides
    them only pushes in flight at the kill), the restart (boot restore,
    WAL replay), one collect a tenant against the numpy oracle over
    every push the log holds, SIGTERM, its check of guarantee 4."""
    from unittest import mock

    from chipbench import lib, reference_wal
    from chipbench import run as bench_run
    from chipbench.mixes import otlp_push, otlp_push_wal

    rehearsal = args.size != "real"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == RECOVER_CELL)
    with open(os.path.join(REPO, next(
            c["file"] for c in bench["configs"]
            if c["name"] == cell["config"]))) as f:
        config = json.load(f)
    traffic = lib.load_json("traffic", cell["traffic"] + ".json")
    if rehearsal:
        config = lib.merged(config, config.get("rehearsal", {}))
        traffic = lib.merged(traffic, traffic.get("rehearsal", {}))
    seconds = 6.0 if rehearsal else RECOVER_SECONDS
    out_dir = os.path.join(REPO, "chiprun_out", "recover")
    os.makedirs(out_dir, exist_ok=True)
    t_start = time.monotonic()
    ctx = types.SimpleNamespace(
        args=types.SimpleNamespace(seconds=seconds), seed=args.seed,
        cell=cell, config=config, traffic=traffic, rehearsal=rehearsal,
        workdir=tempfile.mkdtemp(prefix="chip-recover-"), n_child=0,
        clock=lambda: round(time.monotonic() - t_start, 3))
    ctx.run_child = lambda spec, go=None: bench_run.run_child(ctx, spec, go)

    def boot_member(config: dict, workdir: str, sink_url: str):
        ctx.yaml = member_yaml(config, workdir, sink_url)
        member = Member(ctx.yaml, os.path.join(out_dir, "member1.err"), 900)
        say(phase="booted", ready_s=round(member.ready_s, 2),
            ready=member.ready)
        return member, None, member.port

    class ChildMix(otlp_push_wal.Mix):
        """The cell's mix with the server in a child. The child's
        scheduler is out of reach: a collect is the flush barrier."""

        def setup(self) -> None:
            self.place_log()
            with mock.patch.object(otlp_push, "boot", boot_member):
                otlp_push.Mix.setup(self)

        def drain(self, what: str) -> None:
            for tenant in self.tenants:
                self.collect_sums(tenant)

    mix = ChildMix(ctx)
    mix.setup()
    check(all(otlp_push.acked(d) for d in mix.sent),
          "a push of the set-up was refused")
    first = ctx.app
    killer = threading.Timer(seconds - KILL_BEFORE_END_S, first.kill)
    res = ctx.run_child(dict(mix.child_spec(), seconds=seconds),
                        killer.start)                # SIGKILL: nothing drains
    mix.note(res)
    unanswered = [d for d in res["done"] if not otlp_push.acked(d)]
    say(phase="killed", pushes=len(mix.sent) - len(unanswered),
        acknowledged=sum(map(otlp_push.acked, mix.sent)),
        unanswered=len(unanswered), first_errors=res["errors"][:4])
    check(first.proc.returncode == -9, "the member was gone before the kill")
    check(unanswered and all(d["status"] == -1 for d in unanswered),
          "a push was answered and refused")

    # the log as the kill left it: every acknowledged push is there; a
    # record that matches no push that returned was in flight, one a client
    complaints: list = []
    logs = mix.check_log(complaints, in_flight=traffic["clients"])
    check(not complaints, f"the log: {complaints[:5]}")
    columns = {tenant: [reference_wal.span_columns(arrays, strings)
                        for _, records in reference_wal.read_tenant(
                            mix.tenant_dir(tenant))[0]
                        for _, _, arrays, strings in records]
               for tenant in mix.tenants}
    n_logged = sum(len(cols) for cols in columns.values())
    spans_logged = sum(len(c["span_id"]) for cols in columns.values()
                       for c in cols)

    t0 = time.monotonic()
    second = Member(ctx.yaml, os.path.join(out_dir, "member2.err"), 1800)
    ctx.app, ctx.port = second, second.port
    m = lib.scrape(second.port)
    dead = lib.metric_sum(m, "tempo_wal_dead_letters_total")
    replayed = lib.metric_sum(m, "tempo_wal_replayed_batches_total")
    replay_s = lib.metric_sum(m, "tempo_span_duration_seconds_sum",
                              span="wal.replay")
    compiled = {dict(ls).get("fn", "?"): v for (name, ls), v in m.items()
                if name == "tempo_jax_jit_compile_total" and v}
    say(phase="restarted", restart_to_ready_s=round(second.ready_s, 2),
        ready=second.ready, replayed_batches=replayed,
        replay_s=round(replay_s, 3), replayed_spans=spans_logged,
        replay_spans_per_s=round(spans_logged / replay_s, 1)
        if replay_s else None, compiled=compiled, dead_letters=dead)
    check(second.ready.get("platform") == first.ready.get("platform"),
          f"the restart came up on {second.ready.get('platform')}, the "
          f"first on {first.ready.get('platform')}")
    check(replayed == n_logged,
          f"{replayed:g} records replayed, the log holds {n_logged}")
    check(not dead, "the replay wrote dead letters")

    # the oracle over every logged push: counts exact, the float sum and
    # the quantiles as the cell's judge holds them
    report = {}
    for tenant, cols in columns.items():
        got = mix.collect_sums(tenant)
        col = {k: np.concatenate([c[k] for c in cols])
               for k in ("service", "name", "kind", "status", "start_ns",
                         "end_ns")}
        dur_s = ((col["end_ns"] - col["start_ns"]) / 1e9).astype(np.float32)
        want_sum = float(dur_s.astype(np.float64).sum())
        rel = abs(got.get("traces_spanmetrics_latency_sum", 0.0)
                  - want_sum) / want_sum
        edges = sum(len(c["kind"]) // traffic["push"][1] for c in cols)
        for what, want in (("traces_spanmetrics_calls_total", len(dur_s)),
                           ("traces_spanmetrics_latency_count", len(dur_s)),
                           ("traces_service_graph_request_total", edges)):
            if got.get(what, 0.0) != want:
                complaints.append(f"{tenant}: {what} {got.get(what)} after "
                                  f"the replay, {want} in the log")
        if rel > traffic["latency_sum_rtol"]:
            complaints.append(f"{tenant}: latency_sum off by {rel:.3g}")
        if got["series"] < traffic["min_series"]:
            complaints.append(f"{tenant}: {got['series']} series")
        worst = mix.check_sketch(tenant, {
            "svc": np.asarray([int(s[4:]) for s in col["service"]]),
            "name": np.asarray([int(s[3:]) for s in col["name"]]),
            "kind": col["kind"], "status": col["status"]}, dur_s, complaints)
        report[tenant] = {"logged_spans": len(dur_s), "edges": edges,
                          "series": got["series"],
                          "latency_sum_rel_err": rel,
                          "sketch_worst_rel_err_vs_rank": worst}
    say(phase="compared", since_restart_s=round(time.monotonic() - t0, 2),
        oracle=report, complaints=complaints[:10])
    stop_s = second.terminate(15.0 if rehearsal else STOP_WAIT_S)
    faults = mix.stop_faults(logs)
    say(phase="stopped", sigterm_to_exit_s=stop_s and round(stop_s, 2),
        guarantee_4_faults=faults)
    shutil.rmtree(ctx.workdir, ignore_errors=True)
    check(not complaints, f"the replayed state differs: {complaints[:5]}")
    check(stop_s is not None and not faults,
          f"the clean stop: {stop_s} s, {faults}")
    ok = not rehearsal and second.ready.get("platform") == "tpu"
    # every acknowledged push was in the log and the log was replayed
    # whole: the checks above would have raised
    say(ok=ok, rehearsal=rehearsal, acknowledged_spans_lost=0,
        device=second.ready)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--size", choices=sorted(SIZES), default="real")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--recover", action="store_true",
                    help="kill -9 the durable single binary (a child "
                         "process) under its cell's load and restart it")
    args = ap.parse_args()
    if args.recover:
        try:
            return recover(args)     # before JAX: the chip is the child's
        finally:
            for member in MEMBERS:
                member.kill()

    import tempo_tpu  # noqa: F401  (fails here in a bare directory)
    import jax
    import jaxlib

    from tempo_tpu import native
    from tempo_tpu.obs.jaxruntime import configure_compile_cache

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and args.size == "real":
        print(f"chip_smoke: JAX found no TPU (devices: {devices}); the real "
              "size runs on the chip only", file=sys.stderr)
        return 2
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    say(jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu_version)
    say(devices=[str(d) for d in devices])
    say(compile_cache_dir=cache_dir,
        compile_cache_entries=len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0)
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX sees "
          f"{len(devices)}")
    say(native_decoder=native.available())
    check(native.available(), "the native OTLP decoder did not build: the "
                              "pure-Python decoder is not the served path")
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        if args.chips == 4:
            four_chips(args, SIZES[args.size], workdir)
        else:
            one_chip(args, SIZES[args.size], workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not on_tpu or args.size != "real":
        # a toy-size pass must not read like the real one, chip or not
        say(ok=False, rehearsal=True, device=device)
        return 1
    say(ok=True, device=device)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException:
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)      # a wedged dispatch thread must not hold the exit
    sys.stdout.flush()
    os._exit(rc)
