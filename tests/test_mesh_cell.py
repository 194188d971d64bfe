"""The deployment `k6-single-binary-mesh4` at a small size, on the CPU's
virtual devices: what the chip cell `k6-write-mesh4.steady` rests on.

- the served App with `mesh` 4 x 4 and all three processors, fed OTLP
  over HTTP from several threads, equals the numpy oracle the write cells
  are judged by AND the same stream served by the one-device deployment;
- the service-graph step (PR 27) beside span-metrics on the mesh, under
  one `state_lock`, with pushes racing pushes: every edge counted;
- the packed batch's upload is made inside `sched.h2d` and the closure
  receives a placed operand; off the mesh nothing changed;
- `tempo_mesh_shard_rows_total` equals a bincount of the oracle's slots,
  `tempo_mesh_unplaced_processors` shows a processor that fell back;
- the configuration and traffic files differ from their one-chip twins
  in the keys the issue names and in nothing else.
"""

from __future__ import annotations

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from chipbench import lib, spans
from tempo_tpu import sched
from tempo_tpu.obs.jaxruntime import RUNTIME
from tempo_tpu.parallel import serving
from tempo_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXACT_SUFFIXES = ("_total", "_count", "_bucket")    # integer-valued families
SCHEMA = {"services": 8, "names": 6, "vus": 4, "end_jitter_ns": 10**9}
SMALL = {"schema": SCHEMA,
         "tenant_limits": {"generator": {"max_active_series": 1024}}}
SEED = 2147483659            # the driver's seeds are beyond 32 signed bits

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 virtual devices")


@pytest.fixture(autouse=True)
def _reset_serving_mesh():
    yield
    serving.reset()


def _config(name: str) -> dict:
    with open(os.path.join(REPO, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def _mesh(devices: int = 4, series_shards: int = 4) -> serving.ServingMesh:
    return serving.ServingMesh(serving.MeshConfig(
        enabled=True, devices=devices, series_shards=series_shards))


def _mesh_families() -> dict:
    return {k: v for k, v in lib.parse_exposition(RUNTIME.render()).items()
            if k[0].startswith("tempo_mesh_")}


# -- the served App against the oracle and the one-device App --------------

def _serve_stream(config: dict, workdir: str, pushes: list) -> dict:
    """Boot the configuration as the chip cell does (`lib.boot`), send
    `pushes` = [(tenant index, push index, now_ns)]: the first half in
    order (it makes every series, as the chip cell's prefill does: two
    pushes that meet the SAME new series at once are a defect of the
    series table, PERF.md section 7), the second half from three
    threads; collect once. {tenant: (values, quantiles)} and what
    /metrics said."""
    os.makedirs(workdir)
    shape = spans.PushShape(4, 25, 5)
    sink = lib.Sink()
    app, srv, port = lib.boot(lib.merged(config, SMALL), workdir, sink.url)
    tenants, failed = config["tenants"], []
    first, todo = pushes[:len(pushes) // 2], pushes[len(pushes) // 2:]
    lock = threading.Lock()

    def client(todo: list = todo) -> None:
        while True:
            with lock:
                if not todo:
                    return
                ti, idx, now_ns = todo.pop(0)
            body = spans.encode_push(shape, spans.draw_push(
                SEED, ti, idx, shape, SCHEMA, now_ns))
            status, _ = lib.http_call(port, "POST", "/v1/traces",
                                      tenants[ti], body)
            if status != 200:
                failed.append(status)

    try:
        client(first)
        threads = [threading.Thread(target=client) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        assert not failed
        out = {}
        for tenant in tenants:
            samples = lib.get_json(port, "/internal/generator/collect",
                                   tenant, ts_ms=1)["samples"]
            values = {(s["name"], tuple(map(tuple, s["labels"]))): s["value"]
                      for s in samples}
            quantiles = {q: {tuple(map(tuple, e["labels"])): e["value"]
                             for e in lib.get_json(
                                 port, "/internal/generator/quantile",
                                 tenant, q=q)["quantiles"]}
                         for q in (0.5, 0.99)}
            out[tenant] = (values, quantiles)
        out["metrics"] = lib.scrape(port)
        out["on_mesh"] = {
            t: len(app.generator.instances[t].processors["span-metrics"]
                   .calls.state.values.sharding.device_set) for t in tenants}
        return out
    finally:
        # as `chip_smoke.abandon`: servers and loops stopped without the
        # shutdown flush (every live trace cut to a block: seconds the
        # comparison has no use for), so the next App has the process
        srv.shutdown()
        srv.server_close()
        for part in (app, app.ingester, app.generator):
            part._stop.set()
        for t in app.generator._threads:
            t.join(timeout=60)
        app.sched.flush()
        app.db.shutdown()
        sink.srv.shutdown()
        sink.srv.server_close()


SERIES = ("service", "span_name", "span_kind", "status_code")


def _series(labels) -> str:
    d = dict(labels)
    return "|".join(d.get(k, "") for k in SERIES)


def _by_name(values: dict, name: str) -> dict:
    return {_series(ls): v for (n, ls), v in values.items() if n == name}


def test_served_mesh_app_equals_the_oracle_and_the_one_device_app(tmp_path):
    now_ns = time.time_ns()
    pushes = [(ti, idx, now_ns + idx) for idx in range(12) for ti in (0, 1)]
    shape = spans.PushShape(4, 25, 5)
    occupancy = "tempo_sched_batch_occupancy_ratio_count"
    # the scheduler's families are the process's: earlier tests count too
    one_device_before = lib.metric_sum(
        lib.parse_exposition(RUNTIME.render()), occupancy,
        kernel="spanmetrics_fused_update", shard="")
    mesh = _serve_stream(_config("k6-single-binary-mesh4"),
                         str(tmp_path / "mesh"), pushes)
    m = mesh["metrics"]
    assert lib.metric_sum(m, "tempo_mesh_devices") == 4
    assert lib.metric_sum(m, "tempo_mesh_series_shards") == 4
    assert lib.metric_sum(m, "tempo_mesh_unplaced_processors") == 0
    assert mesh["on_mesh"] == {"k6-a": 4, "k6-b": 4}
    assert lib.metric_sum(m, occupancy, kernel="spanmetrics_fused_update",
                          shard="0") > 0
    assert lib.metric_sum(m, occupancy, kernel="spanmetrics_fused_update",
                          shard="") == one_device_before
    assert lib.metric_sum(m, "tempo_mesh_shard_rows_total",
                          plane="series") == 2400
    assert lib.metric_sum(m, "tempo_mesh_h2d_bytes_total") > 0
    single = _serve_stream(_config("k6-single-binary"),
                           str(tmp_path / "single"), pushes)
    assert single["on_mesh"] == {"k6-a": 1, "k6-b": 1}
    # off the mesh its families have no values (an unlabelled counter
    # renders its zero, as every such family of the registry does)
    assert {k: v for k, v in single["metrics"].items()
            if k[0].startswith("tempo_mesh")} == {
                ("tempo_mesh_h2d_bytes_total", ()): 0.0}

    for ti, tenant in enumerate(("k6-a", "k6-b")):
        values, quantiles = mesh[tenant]
        # the oracle: every acknowledged span counted once by every
        # processor, as `mixes/otlp_push.py::judge` holds the chip runs
        cols = [spans.draw_push(SEED, ti, idx, shape, SCHEMA, now)
                for t, idx, now in pushes if t == ti]
        col = {k: np.concatenate([c[k] for c in cols])
               for k in ("svc", "name", "kind", "status", "dur_ns")}
        keys = np.array([
            f"svc-{s:04d}|op-{n:04d}|{spans.KIND_STRS[k]}|"
            f"{spans.STATUS_STRS[st]}" for s, n, k, st in zip(
                col["svc"], col["name"], col["kind"], col["status"])])
        want = dict(zip(*np.unique(keys, return_counts=True)))
        assert _by_name(values, "traces_spanmetrics_calls_total") == want
        assert _by_name(values, "traces_spanmetrics_latency_count") == want
        dur_s = (col["dur_ns"] / 1e9).astype(np.float32)
        lat_sum = sum(_by_name(values, "traces_spanmetrics_latency_sum")
                      .values())
        want_sum = float(dur_s.astype(np.float64).sum())
        assert abs(lat_sum - want_sum) <= 1e-4 * want_sum
        edges = sum(v for (n, _), v in values.items()
                    if n == "traces_service_graph_request_total")
        assert edges == sum(c["pairs"] for c in cols)
        busiest = max(want, key=want.get)
        vals = np.sort(dur_s[keys == busiest].astype(np.float64))
        got_q = {_series(ls): v for ls, v in quantiles[0.5].items()}[busiest]
        k = int(np.ceil(0.5 * len(vals))) - 1
        assert vals[max(k - 1, 0)] * 0.989 <= got_q \
            <= vals[min(k + 1, len(vals) - 1)] * 1.011

        # the one-device deployment: integer families and quantiles
        # exactly, float sums within the runbook's 1e-5
        values_1, quantiles_1 = single[tenant]
        assert values.keys() == values_1.keys() and len(values) > 100
        for key, a in values.items():
            b = values_1[key]
            if key[0].endswith(EXACT_SUFFIXES):
                assert a == b, (key, a, b)
            else:
                assert abs(a - b) <= 1e-5 * max(abs(a), abs(b)), (key, a, b)
        assert quantiles == quantiles_1 and len(quantiles[0.99]) > 50


# -- the service-graph step beside span-metrics on the mesh ----------------

def test_servicegraphs_fused_step_under_the_mesh_loses_no_edge():
    """Span-metrics donates sharded state on the scheduler's thread and
    the service-graph emit donates unsharded state on the request
    threads, under the same registry's `state_lock`: with pushes racing
    pushes every edge and every call is counted."""
    import sys

    from tempo_tpu.generator.instance import (GeneratorConfig,
                                              GeneratorInstance)
    from tempo_tpu.model.span_batch import (KIND_CLIENT, KIND_SERVER,
                                            SpanBatchBuilder)
    from tempo_tpu.registry import RegistryOverrides

    n_threads, n_pushes, pairs = 4, 8, 5
    with serving.use(_mesh()):
        sc = sched.DeviceScheduler(sched.SchedConfig(pipeline_depth=0))
        with sched.use(sc):
            cfg = GeneratorConfig(
                processors=("span-metrics", "service-graphs"),
                registry=RegistryOverrides(max_active_series=512))
            g = GeneratorInstance("t", cfg, now=lambda: 1000.0)

            def batch(k: int):
                b = SpanBatchBuilder(g.registry.interner)
                for j in range(pairs):
                    tid = (k * pairs + j + 1).to_bytes(16, "big")
                    cid = (j + 1).to_bytes(8, "big")
                    b.append(trace_id=tid, span_id=cid, name="call",
                             service=f"front-{j}", kind=KIND_CLIENT,
                             status_code=0, start_unix_nano=999 * 10**9,
                             end_unix_nano=999 * 10**9 + 10**8)
                    b.append(trace_id=tid, span_id=bytes(7) + b"\xff",
                             parent_span_id=cid, name="serve",
                             service="back", kind=KIND_SERVER, status_code=0,
                             start_unix_nano=999 * 10**9,
                             end_unix_nano=999 * 10**9 + 10**7)
                return b.build()

            # batches stage on one thread: interning is not under test
            work = [[batch(i * n_pushes + j) for j in range(n_pushes)]
                    for i in range(n_threads)]
            # the series are made by one push that lands alone, as the
            # chip cell's canaries do: two pushes that meet the SAME new
            # series at once are a defect of the series table, not of
            # the mesh (PERF.md section 7)
            g.push_batch(batch(n_threads * n_pushes))
            old = sys.getswitchinterval()
            sys.setswitchinterval(1e-5)
            try:
                threads = [threading.Thread(
                    target=lambda w=w: [g.push_batch(sb) for sb in w])
                    for w in work]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=120)
                    assert not t.is_alive()
            finally:
                sys.setswitchinterval(old)
            assert sc.flush()
            sm, sg = g.processors["span-metrics"], \
                g.processors["service-graphs"]
            assert sm._mesh is not None
            assert len(sm.calls.state.values.sharding.device_set) == 4
            assert len(sg.total.state.values.sharding.device_set) == 1
            samples = g.registry.collect(1)
            n = (n_threads * n_pushes + 1) * pairs
            assert sum(s.value for s in samples if s.name ==
                       "traces_service_graph_request_total") == n
            assert sum(s.value for s in samples if s.name ==
                       "traces_spanmetrics_calls_total") == 2 * n
            sc.stop()


# -- the moved upload ------------------------------------------------------

def _proc(max_series: int = 512):
    from tempo_tpu.generator.processors.spanmetrics import (
        SpanMetricsConfig, SpanMetricsProcessor)
    from tempo_tpu.registry import ManagedRegistry, RegistryOverrides

    reg = ManagedRegistry("t", RegistryOverrides(max_active_series=max_series),
                          now=lambda: 1000.0)
    return reg, SpanMetricsProcessor(reg, SpanMetricsConfig())


def _batch(reg, n: int, n_series: int):
    from tempo_tpu.model.span_batch import SpanBatchBuilder

    b = SpanBatchBuilder(reg.interner)
    r = np.random.default_rng(n)
    for i in range(n):
        b.append(trace_id=r.bytes(16), span_id=r.bytes(8),
                 name=f"op-{i % n_series}", service="svc", kind=1,
                 status_code=0, start_unix_nano=10**18,
                 end_unix_nano=10**18 + int(r.lognormal(16, 1.0)))
    return b.build()


class _Spans(tracing.Tracer):
    """Keeps the attributes of every finished `sched.h2d` span."""

    exports = True

    def __init__(self) -> None:
        super().__init__()
        self.h2d: list[dict] = []

    def _begin(self, sp, parent) -> None:
        pass

    def _record(self, sp) -> None:
        if sp.name == "sched.h2d":
            self.h2d.append(dict(sp.attrs))


@pytest.mark.parametrize("on_mesh", [True, False], ids=["mesh", "one-device"])
def test_packed_upload_is_made_inside_sched_h2d(on_mesh, monkeypatch):
    """On the mesh the scheduler makes the sharded placement inside
    `sched.h2d` (4 x the matrix crosses the link) and hands the closure a
    placed operand; off the mesh the one `device_put` it made before."""
    got = {}
    with serving.use(_mesh() if on_mesh else None) as sm:
        sc = sched.DeviceScheduler(sched.SchedConfig(pipeline_depth=0),
                                   start_worker=False)
        with sched.use(sc):
            reg, proc = _proc()
            name = "_sched_dispatch_sharded_packed" if on_mesh \
                else "_sched_dispatch_packed"
            real = getattr(proc, name)

            def spy(operand):
                got["operand"] = operand
                got["inside"] = tracing._current_span.get().name
                real(operand)

            monkeypatch.setattr(proc, name, spy)
            tr = _Spans()
            tracing.install(tr)
            proc.push_batch(_batch(reg, 300, 7))
            assert sc.flush()
    operand = got["operand"]
    assert got["inside"] == "sched.enqueue"
    assert isinstance(operand, jax.Array) and operand.shape == (4, 512)
    assert len(operand.sharding.device_set) == (4 if on_mesh else 1)
    assert [a["h2d_bytes"] for a in tr.h2d] == \
        [4 * 512 * 4 * (4 if on_mesh else 1)]
    assert proc._mesh is sm
    assert float(np.asarray(proc.calls.state.values).sum()) == 300.0


# -- the counters ----------------------------------------------------------

def test_shard_rows_equal_a_bincount_of_the_slots():
    """Slots come off the free list in order and a shard owns a
    contiguous range: 300 series in a 512-row table live on shards 0-2
    of four, on both planes (here the sketch plane is the whole table)."""
    with serving.use(_mesh()) as sm:
        reg, proc = _proc()
        sb = _batch(reg, 2000, 300)
        proc.push_batch(sb)
        labels = np.stack([sb.service_id[:2000], sb.name_id[:2000]], axis=1)
        _, first, inverse = np.unique(labels, axis=0, return_index=True,
                                      return_inverse=True)
        # the oracle's slots: a series takes the next free slot at its
        # first appearance in the batch
        order = np.argsort(np.argsort(first))
        slots = order[inverse.ravel()]
        want = np.bincount(slots // (512 // 4), minlength=4)
        assert want.tolist() == sm.shard_rows["series"].tolist()
        assert want.tolist() == sm.shard_rows["sketch"].tolist()
        assert want[3] == 0 and want.sum() == 2000
        fams = _mesh_families()
        for plane in ("series", "sketch"):
            assert [fams[("tempo_mesh_shard_rows_total",
                          (("plane", plane), ("shard", str(i))))]
                    for i in range(4)] == want.tolist()
        assert fams[("tempo_mesh_h2d_bytes_total", ())] == sm.h2d_bytes > 0
        assert fams[("tempo_mesh_unplaced_processors", ())] == 0


@pytest.mark.parametrize("max_series,unplaced", [(510, 1), (512, 0)])
def test_unplaced_processor_shows_on_metrics(max_series, unplaced):
    with serving.use(_mesh()):
        reg, proc = _proc(max_series)
        proc.push_batch(_batch(reg, 100, 7))
        assert (proc._mesh is None) == bool(unplaced)
        assert _mesh_families()[("tempo_mesh_unplaced_processors", ())] \
            == unplaced
        del reg, proc           # the gauge counts live processors
        import gc
        gc.collect()
        assert _mesh_families()[("tempo_mesh_unplaced_processors", ())] == 0
    assert not any(_mesh_families().values())     # no mesh, no values


# -- the cell's files ------------------------------------------------------

def test_mesh4_files_differ_from_their_twins_only_where_named():
    base, mesh = _config("k6-single-binary"), _config("k6-single-binary-mesh4")
    differ = {"name", "source", "deployment", "yaml_overrides", "reduced",
              "device_state", "guarantees", "assumed"}
    assert base.keys() == mesh.keys()
    assert {k for k in base if base[k] != mesh[k]} == differ
    overrides = dict(mesh["yaml_overrides"])
    assert overrides.pop("mesh") == {"enabled": True, "devices": 4,
                                     "series_shards": 4}
    assert overrides == base["yaml_overrides"]
    # the one-chip deployment's assumptions and guarantees, and more
    for key in ("assumed", "guarantees"):
        assert mesh[key][:len(base[key])] == base[key]
        assert len(mesh[key]) == len(base[key]) + 1
    assert mesh["reduced"] == ["replicas"]
    traffic = {n: lib.load_json("traffic", n + ".json")
               for n in ("k6-write.steady", "k6-write-mesh4.steady")}
    a, b = traffic["k6-write.steady"], traffic["k6-write-mesh4.steady"]
    assert {k for k in a.keys() | b.keys() if a.get(k) != b.get(k)} == {"kind"}
    assert b["kind"] == "otlp_push_mesh"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == mesh["name"])
    assert (entry["source"], entry["reduced"]) == (mesh["source"],
                                                   mesh["reduced"])
    assert all("workloads" in m for m in bench["per_layer"])
