"""The deployment `hotrod-otel-sdk` at a small size, on the CPU: what the
chip cell `hotrod-sdk.steady` rests on.

- the configuration agrees with its own sums (spans, calls and database
  calls a HotROD request) and with its manifest entries;
- the served App, fed per-service pushes from the cell's own export queues
  (`chipbench/loadgen_hotrod.py`) with one held past the slack, and a
  sub-second service-graph wait set through the tenant's `sg_wait_s`
  override, collects after a flush push exactly what the plain reference
  (`chipbench/reference_hotrod.py`) pairs; the held push's spans are
  discarded as outside_slack by the generator and read back by id from the
  ingester;
- two tenants with different `sg_max_items` / `sg_wait_s` /
  `sg_peer_attributes` each run by their own, the store-full drop counter
  moves at the limit, and `sg_dimensions`, which the processor does not
  implement, is refused where overrides are loaded;
- a trace whose spans straddle an ingester cut reads back whole by id,
  both parts cut from columns.
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np
import pytest
import yaml

from chipbench import lib, reference_hotrod
from chipbench import loadgen_hotrod as lg
from tempo_tpu.overrides import Overrides
from tempo_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483659            # beyond 32 signed bits, as the benchmark's seeds
SG = "traces_service_graph_request"
DROPPED = "tempo_metrics_generator_processor_service_graphs_dropped_spans"
STORE = "tempo_metrics_generator_servicegraphs_store_items"
EDGES = "tempo_metrics_generator_servicegraphs_edges_total"
EXPIRED = "tempo_metrics_generator_processor_service_graphs_expired_edges"
# pushes carry the wall clock: the held push is sent once its spans are
# older than the slack, the others at once (the test holds the seed's
# roots and the sending time to that); the wait is longer than the two
# halves of one call take to arrive from one synchronous sender
SLACK_S, WAIT_S = 3.0, 2.5
SMALL = {"tenant_limits": {"generator": {
    "max_active_series": 1024, "ingestion_time_range_slack_s": SLACK_S,
    "sg_wait_s": WAIT_S}},
    "yaml_overrides": {"generator": {"registry": {
        "collection_interval_s": 3600.0}}}}


def _config() -> dict:
    with open(os.path.join(REPO, "chipbench", "configs",
                           "hotrod-otel-sdk.json")) as f:
        return json.load(f)


def _traffic() -> dict:
    return lib.load_json("traffic", "hotrod-sdk.steady.json")


# -- the configuration -------------------------------------------------------

def test_config_agrees_with_its_own_sums():
    cfg = _config()
    h = lg.Hotrod(cfg["schema"])
    tmpl = [h.templates[t] for t in h.slot_tmpl]
    kinds = [t["kind"] for t in tmpl]
    parent = h.slot_parent.tolist()
    pairs = sum(kinds[k] == "server" and p >= 0 and kinds[p] == "client"
                for k, p in enumerate(parent))
    db = sum(kinds[k] == "client" and "db.system" in t.get("attributes", {})
             for k, t in enumerate(tmpl))
    waits = db + sum(p < 0 for p in parent)
    want = cfg["per_request"]
    assert (h.n_slots, pairs, db, waits) == (
        want["spans"], want["pairs"], want["database_calls"],
        want["halves_that_wait"]) == (37, 12, 12, 13)
    assert {s: len(h.svc_slots[i]) for i, s in enumerate(h.services)} \
        == want["by_service"]
    # every client that is not a database call has its server
    assert pairs + db == kinds.count("client")
    # 5% of the Redis calls error, nothing else
    assert {t["name"]: t.get("error_share", 0.0) for t in tmpl
            if t.get("error_share")} == {"FindDriverIDs": 0.05,
                                         "GetDriver": 0.05}
    # the store holds about twice (2^20 / 527,027 = 1.99x) the halves
    # that wait at 300,000 spans/s
    sg = cfg["tenant_limits"]["generator"]
    pending = 300_000 / len(cfg["tenants"]) * waits / h.n_slots \
        * sg["sg_wait_s"]
    assert sg["sg_max_items"] == 1 << 20 and sg["sg_max_items"] > 1.98 * pending
    assert sg["ingestion_time_range_slack_s"] == 30.0
    tr = _traffic()
    assert tr["hold_s"][0] > sg["ingestion_time_range_slack_s"]
    assert tr["batch_spans"] == 512 and tr["max_age_s"] == 5.0
    assert cfg["reduced"] == ["replicas"] and len(cfg["source"]) <= 200
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert (entry["source"], entry["reduced"], entry["file"]) == (
        cfg["source"], cfg["reduced"],
        "chipbench/configs/hotrod-otel-sdk.json")
    cell = next(w for w in bench["workloads"]
                if w["name"] == "hotrod-sdk.steady")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        cfg["name"], "hotrod-sdk.steady", 1)
    # the reference imports nothing of the program
    with open(reference_hotrod.__file__) as f:
        assert "tempo_tpu" not in "".join(
            ln for ln in f if ln.lstrip().startswith(("import ", "from ")))


def test_unimplemented_service_graph_knob_is_refused_at_load(tmp_path):
    path = tmp_path / "overrides.yaml"
    path.write_text(yaml.safe_dump({"overrides": {"t": {"generator": {
        "sg_dimensions": ["http.method"]}}}}))
    with pytest.raises(ValueError, match="sg_dimensions"):
        Overrides(runtime_config_path=str(path))
    with pytest.raises(ValueError, match="sg_dimensions"):
        Overrides().set_tenant_patch("t", {"generator": {
            "sg_dimensions": ["http.method"]}})
    # the knobs it implements load
    Overrides().set_tenant_patch("t", {"generator": {
        "sg_max_items": 5, "sg_wait_s": 1.0, "sg_peer_attributes": ["x"],
        "sg_histogram_buckets": [0.5, 1.0]}})


# -- the served App -----------------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    workdir = str(tmp_path_factory.mktemp("hotrod"))
    before = set(threading.enumerate())
    sink = lib.Sink()
    config = lib.merged(_config(), SMALL)
    app, srv, port = lib.boot(config, workdir, sink.url)
    try:
        yield app, port, config
    finally:
        srv.shutdown()
        srv.server_close()
        app.shutdown()
        sink.srv.shutdown()
        sink.srv.server_close()
        # no thread of this App may close a span inside a later test, and
        # the spans of its stop are this module's, not the next test's
        for t in set(threading.enumerate()) - before:
            t.join(timeout=30)
        tracing.reset_span_rows()


def _collect(port: int, tenant: str) -> dict:
    samples = lib.get_json(port, "/internal/generator/collect", tenant,
                           ts_ms=1)["samples"]
    return {(s["name"], tuple(sorted(map(tuple, s["labels"])))): s["value"]
            for s in samples}


def _graph(got: dict) -> dict:
    """{(client, server, connection_type): {total, failed, client and
    server bucket counts (per bucket), client and server sums}}."""
    out: dict = {}
    for (name, ls), v in got.items():
        if not name.startswith(SG):
            continue
        d = dict(ls)
        key = (d["client"], d["server"], d.get("connection_type", ""))
        e = out.setdefault(key, {"buckets": {}})
        if name.endswith("_bucket"):
            side = name[len(SG) + 1:].split("_")[0]
            e["buckets"].setdefault(side, []).append((float(d["le"]), v))
        else:
            e[name[len(SG):]] = v
    for e in out.values():
        for side, rows in e.pop("buckets").items():
            cum = [v for _, v in sorted(rows)]
            e[side + "_buckets"] = np.diff([0.0] + cum).tolist()
    return out


def _assert_graph_equals_reference(got: dict, want: dict) -> None:
    graph = _graph(got)
    assert set(graph) == set(want)
    for key, w in want.items():
        g = graph[key]
        assert g["_total"] == w["total"], key
        assert g.get("_failed_total", 0.0) == w["failed"], key
        for side in ("client", "server"):
            assert g[side + "_buckets"] == w[side + "_buckets"].tolist(), key
            assert g[f"_{side}_seconds_count"] == w["total"], key
            assert g[f"_{side}_seconds_sum"] == pytest.approx(
                w[side + "_sum"], rel=1e-5), key


def _exporter(tenants: list, config: dict) -> lg.Exporter:
    return lg.Exporter({"seed": SEED, "tenants": tenants,
                        "schema": config["schema"], "batch_spans": 32,
                        "max_age_s": 60.0, "late_share": 0.0,
                        "hold_s": [0.0, 0.0], "chunk_requests": 4})


def _with_flush(config: dict) -> lg.Hotrod:
    """The schema's templates and, last, the cell's flush span."""
    return lg.Hotrod(config["schema"], extra=(_traffic()["flush_span"],))


def _flush(h: lg.Hotrod, key: int) -> list:
    """One in-slack INTERNAL span: a push that stores nothing and expires
    what waited out the tenant's wait."""
    return lg.single_columns(h, SEED, key, len(h.templates) - 1,
                             time.time_ns())


def _columns(sent: list) -> dict:
    parts, slack = [], []
    for d, groups in sent:
        for _, c in groups:
            parts.append(c)
            slack.append(np.full(len(c["tmpl"]), not d["held"]))
    cols = lg.cat(parts)
    cols["in_slack"] = np.concatenate(slack)
    return cols


def test_served_app_equals_the_reference_after_a_flush(served):
    app, port, config = served
    tenant = config["tenants"][0]
    ex, h = _exporter([tenant], config), _with_flush(config)
    m0 = lib.scrape(port)
    sent, held, times = [], None, []
    while len(sent) < 24:
        batch = ex.take()
        groups = [(batch["svc"], batch["cols"])]
        if held is None and batch["svc"] == 0 and len(sent) > 4:
            held = groups              # a retried export: sent last
            continue
        rec = lg.post(port, tenant, lg.encode(h, groups), 60.0)
        assert rec["status"] == 200, rec
        sent.append(({"held": False}, groups))
        times.append((rec["t0"], rec["t1"], rec["t1"] - rec["t0"] + (
            rec["sent_ns"] - batch["cols"]["end_ns"].min()) / 1e9))
    # older than the slack by half a second
    time.sleep(max((held[0][1]["end_ns"].max() + (SLACK_S + 0.5) * 1e9
                    - time.time_ns()) / 1e9, 0.0))
    assert lg.post(port, tenant, lg.encode(h, held), 60.0)["status"] == 200
    sent.append(({"held": True}, held))
    flush = _flush(h, 0)
    assert lg.post(port, tenant, lg.encode(h, flush), 60.0)["status"] == 200
    sent.append(({"held": False}, flush))
    app.sched.flush()
    # every half of a call reached the server inside the wait of the other,
    # and every span but the held ones inside the slack
    assert times[-1][1] - times[0][0] < WAIT_S
    assert max(age for _, _, age in times) < SLACK_S

    cols = _columns(sent)
    sg = config["tenant_limits"]["generator"]
    e = reference_hotrod.edges_of(cols, h.templates, h.services,
                                  sg["sg_peer_attributes"])
    assert e["completed"] > 100 and e["virtual"] > 100
    want = reference_hotrod.service_graph(
        cols, h.templates, h.services, sg["sg_peer_attributes"],
        sg["sg_histogram_buckets"])
    # the virtual nodes: user -> frontend, and the two databases
    assert {k for k in want if k[2] == "virtual_node"} == {
        ("user", "frontend", "virtual_node"),
        ("customer", "mysql", "virtual_node"),
        ("driver", "redis", "virtual_node")}
    got = _collect(port, tenant)
    _assert_graph_equals_reference(got, want)
    # the span metrics count every in-slack span once, by series
    durs = reference_hotrod.span_metrics(cols, h.templates, h.services)
    calls = {}
    for (name, ls), v in got.items():
        if name == "traces_spanmetrics_calls_total":
            d = dict(ls)
            calls[(d["service"], d["span_name"], d["span_kind"],
                   d["status_code"])] = v
    assert calls == {k: float(len(v)) for k, v in durs.items()}
    # the held push: discarded by the generator as outside_slack ...
    n_held = int((~cols["in_slack"]).sum())
    assert n_held == ex.spec["batch_spans"]
    assert app.generator.instances[tenant].spans_filtered_slack == n_held
    m1 = lib.scrape(port)
    assert lib.delta({"m0": m0, "m1": m1}, "tempo_discarded_spans_total",
                     reason="outside_slack") == n_held
    # ... the store empty and nothing dropped, edges and expiries counted
    assert lib.metric_sum(m1, STORE, tenant=tenant) == 0
    assert lib.metric_sum(m1, DROPPED, tenant=tenant) == 0
    assert lib.metric_sum(m1, EDGES, tenant=tenant, kind="completed") \
        == e["completed"]
    assert lib.metric_sum(m1, EDGES, tenant=tenant, kind="virtual") \
        == e["virtual"]
    assert lib.metric_sum(m1, EXPIRED, tenant=tenant) == e["lone_halves"]
    assert ("tempo_span_self_seconds_count",
            (("collect", "clear"), ("span", "servicegraphs.expire"))) in m1
    # ... and kept by the ingester: a held trace reads back whole
    tids = cols["trace_id"].view("V16").ravel()
    r = int(np.flatnonzero(~cols["in_slack"])[0])
    rows = np.flatnonzero(tids == tids[r])
    assert cols["in_slack"][rows].any()          # the rest came in time
    status, body = lib.http_call(port, "GET", "/api/traces/"
                                 + bytes(cols["trace_id"][r]).hex(), tenant)
    assert status == 200
    assert sorted(s["span_id"] for s in json.loads(body)["spans"]) == sorted(
        int(cols["span_id"][i]).to_bytes(8, "little").hex() for i in rows)


def test_two_tenants_run_their_own_service_graph_knobs(served):
    """`sg_max_items`, `sg_wait_s` and `sg_peer_attributes` of one tenant
    reach its processor and no other's; the drop counter moves at the
    limit."""
    app, port, config = served
    small, other = "hotrod-small", "hotrod-other"
    app.overrides.set_tenant_patch(small, {"generator": {
        "processors": ["service-graphs"], "sg_max_items": 5,
        "sg_wait_s": 0.5, "sg_peer_attributes": ["db.system"]}})
    app.overrides.set_tenant_patch(other, {"generator": {
        "processors": ["service-graphs"], "sg_wait_s": 30.0,
        "sg_peer_attributes": ["peer.service"],
        "sg_histogram_buckets": [0.25, 1.0]}})
    cfgs = {t: app.generator.instance(t).processors["service-graphs"].cfg
            for t in (small, other)}
    assert (cfgs[small].max_items, cfgs[small].wait_s,
            cfgs[small].peer_attributes) == (5, 0.5, ("db.system",))
    assert (cfgs[other].max_items, cfgs[other].wait_s,
            cfgs[other].peer_attributes, cfgs[other].histogram_buckets) == (
        10_000, 30.0, ("peer.service",), (0.25, 1.0))
    h = lg.Hotrod(config["schema"])
    chunk = lg.draw_chunk(h, SEED, 0, 0, 2, time.time_ns())
    # the driver service's spans of two requests: 2 servers, 22 Redis calls
    groups = [(2, lg.service_rows(h, chunk, 2))]
    for t in (small, other):
        assert lib.http_call(port, "POST", "/v1/traces", t,
                             lg.encode(h, groups))[0] == 200
    m = lib.scrape(port)
    assert lib.metric_sum(m, STORE, tenant=small) == 5
    assert lib.metric_sum(m, DROPPED, tenant=small) == 24 - 5
    assert lib.metric_sum(m, STORE, tenant=other) == 24
    assert lib.metric_sum(m, DROPPED, tenant=other) == 0
    # past the small tenant's wait, a push of each expires its own halves
    time.sleep(0.7)
    hf = _with_flush(config)
    for k, t in enumerate((small, other)):
        assert lib.http_call(port, "POST", "/v1/traces", t,
                             lg.encode(hf, _flush(hf, 10 + k)))[0] == 200
    m = lib.scrape(port)
    assert lib.metric_sum(m, EXPIRED, tenant=small) == 5
    assert lib.metric_sum(m, STORE, tenant=small) == 0
    assert lib.metric_sum(m, EXPIRED, tenant=other) == 0
    assert lib.metric_sum(m, STORE, tenant=other) == 24
    # the small tenant's five halves were Redis clients under db.system:
    # virtual server nodes named redis (or driver servers: no edge)
    got = _graph(_collect(port, small))
    assert set(got) <= {("driver", "redis", "virtual_node")}
    assert sum(e["_total"] for e in got.values()) == lib.metric_sum(
        m, EDGES, tenant=small, kind="virtual")


def test_a_trace_split_over_an_ingester_cut_reads_back_whole(served):
    app, port, config = served
    tenant = config["tenants"][1]
    h = lg.Hotrod(config["schema"])
    chunk = lg.draw_chunk(h, SEED, 1, 0, 1, time.time_ns())
    m0 = lib.scrape(port)
    first = [(s, lg.service_rows(h, chunk, s)) for s in (0, 1)]
    assert lib.http_call(port, "POST", "/v1/traces", tenant,
                         lg.encode(h, first))[0] == 200
    app.ingester.sweep_instance(tenant, immediate=True)      # cut part one
    rest = [(s, lg.service_rows(h, chunk, s)) for s in (2, 3)]
    assert lib.http_call(port, "POST", "/v1/traces", tenant,
                         lg.encode(h, rest))[0] == 200
    hexid = bytes(chunk["trace_id"][0, 0]).hex()
    status, body = lib.http_call(port, "GET", "/api/traces/" + hexid, tenant)
    want = sorted(int(s).to_bytes(8, "little").hex()
                  for s in chunk["span_id"][0])
    assert status == 200 and len(want) == 37
    assert sorted(s["span_id"] for s in json.loads(body)["spans"]) == want
    app.ingester.sweep_instance(tenant, immediate=True)      # cut part two
    status, body = lib.http_call(port, "GET", "/api/traces/" + hexid, tenant)
    assert sorted(s["span_id"] for s in json.loads(body)["spans"]) == want
    # both parts were cut from the columns of staged pushes
    m1 = lib.scrape(port)
    obs = {"m0": m0, "m1": m1}
    cut = "tempo_ingester_cut_spans_total"
    assert lib.delta(obs, cut, route="columns") >= 37
    assert lib.delta(obs, cut, route="dicts") == 0
