"""Generator processor tests — semantics mirrored from the reference's
`processor/spanmetrics/spanmetrics_test.go` and `servicegraphs_test.go`
table-driven fixtures, plus remote-write wire checks."""

import numpy as np
import pytest

from otlp_payload import make_otlp_payload
from tempo_tpu.generator.instance import GeneratorConfig, GeneratorInstance
from tempo_tpu.generator.processors.spanmetrics import SpanMetricsConfig, SpanMetricsProcessor
from tempo_tpu.generator.processors.servicegraphs import ServiceGraphsConfig, ServiceGraphsProcessor
from tempo_tpu.generator import remote_write as rw
from tempo_tpu.model import proto_wire as pw
from tempo_tpu.model.span_batch import (
    KIND_CLIENT,
    KIND_CONSUMER,
    KIND_PRODUCER,
    KIND_SERVER,
    STATUS_ERROR,
    SpanBatchBuilder,
)
from tempo_tpu.registry import ManagedRegistry, RegistryOverrides
from tempo_tpu.registry.series import Sample
from tempo_tpu.utils.spanfilter import AttributeMatch, FilterPolicy, PolicyMatch


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _mk_batch(spans=None, interner=None):
    b = SpanBatchBuilder(interner=interner)
    for sp in spans:
        b.append(**sp)
    return b.build()


def _span(i, service="svc-a", name="op", kind=KIND_SERVER, status=0, dur_ns=10**9,
          attrs=None, parent=b"", trace=None, start=10**9):
    return dict(
        trace_id=(trace if trace is not None else bytes([i]) * 16),
        span_id=bytes([i]) * 8,
        parent_span_id=parent,
        name=name, service=service, kind=kind, status_code=status,
        start_unix_nano=start, end_unix_nano=start + dur_ns,
        attrs=attrs or {},
    )


def series_value(samples, name, **labels):
    for s in samples:
        if s.name != name or s.is_stale_marker:
            continue
        d = dict(s.labels)
        if all(d.get(k) == v for k, v in labels.items()):
            return s.value
    return None


def test_spanmetrics_red_families():
    reg = ManagedRegistry(now=FakeClock())
    p = SpanMetricsProcessor(reg, SpanMetricsConfig())
    sb = _mk_batch(interner=reg.interner, spans=[
        _span(1, service="a", name="op1", dur_ns=10**9),
        _span(2, service="a", name="op1", dur_ns=2 * 10**9),
        _span(3, service="b", name="op2", status=STATUS_ERROR, dur_ns=10**8),
    ])
    p.push_batch(sb, span_sizes=np.full(sb.capacity, 100.0, np.float32))
    samples = reg.collect(ts_ms=1)
    assert series_value(samples, "traces_spanmetrics_calls_total",
                        service="a", span_name="op1") == 2.0
    assert series_value(samples, "traces_spanmetrics_calls_total",
                        service="b", span_name="op2",
                        status_code="STATUS_CODE_ERROR") == 1.0
    assert series_value(samples, "traces_spanmetrics_latency_sum",
                        service="a", span_name="op1") == pytest.approx(3.0)
    assert series_value(samples, "traces_spanmetrics_latency_count",
                        service="a", span_name="op1") == 2.0
    assert series_value(samples, "traces_spanmetrics_size_total",
                        service="a", span_name="op1") == 200.0
    # le=2.048 bucket holds both 1s and 2s observations
    assert series_value(samples, "traces_spanmetrics_latency_bucket",
                        service="a", span_name="op1", le="2.048") == 2.0


def test_spanmetrics_custom_dimensions_and_quantile():
    reg = ManagedRegistry(now=FakeClock())
    p = SpanMetricsProcessor(reg, SpanMetricsConfig(dimensions=("http.method",)))
    sb = _mk_batch(interner=reg.interner, spans=[
        _span(1, attrs={"http.method": "GET"}, dur_ns=10**9),
        _span(2, attrs={"http.method": "POST"}, dur_ns=10**9),
        _span(3, dur_ns=10**9),
    ])
    p.push_batch(sb)
    samples = reg.collect(1)
    assert series_value(samples, "traces_spanmetrics_calls_total",
                        http_method="GET") == 1.0
    assert series_value(samples, "traces_spanmetrics_calls_total",
                        http_method="") == 1.0
    qs = p.quantile(0.5)
    assert qs and all(abs(v - 1.0) < 0.05 for v in qs.values())


def test_spanmetrics_filter_policy():
    reg = ManagedRegistry(now=FakeClock())
    pol = FilterPolicy(include=PolicyMatch("strict", (AttributeMatch("kind", "SPAN_KIND_SERVER"),)))
    p = SpanMetricsProcessor(reg, SpanMetricsConfig(filter_policies=(pol,)))
    sb = _mk_batch(interner=reg.interner, spans=[
        _span(1, kind=KIND_SERVER),
        _span(2, kind=KIND_CLIENT),
    ])
    p.push_batch(sb)
    samples = reg.collect(1)
    assert series_value(samples, "traces_spanmetrics_calls_total",
                        span_kind="SPAN_KIND_SERVER") == 1.0
    assert series_value(samples, "traces_spanmetrics_calls_total",
                        span_kind="SPAN_KIND_CLIENT") is None
    assert p.spans_discarded == 1


def test_servicegraphs_edge_completion():
    clock = FakeClock()
    reg = ManagedRegistry(now=clock)
    p = ServiceGraphsProcessor(reg, ServiceGraphsConfig())
    t = bytes(16)
    sb = _mk_batch(interner=reg.interner, spans=[
        _span(1, service="frontend", kind=KIND_CLIENT, trace=t, dur_ns=3 * 10**8),
        _span(2, service="backend", kind=KIND_SERVER, trace=t,
              parent=bytes([1]) * 8, dur_ns=2 * 10**8, status=STATUS_ERROR),
    ])
    p.push_batch(sb)
    samples = reg.collect(1)
    assert series_value(samples, "traces_service_graph_request_total",
                        client="frontend", server="backend") == 1.0
    assert series_value(samples, "traces_service_graph_request_failed_total",
                        client="frontend", server="backend") == 1.0
    assert series_value(samples, "traces_service_graph_request_client_seconds_sum",
                        client="frontend", server="backend") == pytest.approx(0.3)
    assert series_value(samples, "traces_service_graph_request_server_seconds_sum",
                        client="frontend", server="backend") == pytest.approx(0.2)


@pytest.mark.parametrize("with_purge", [False, True], ids=["push", "purge"])
def test_servicegraphs_concurrent_pushes_lose_no_edge(with_purge):
    """One tenant's pushes arrive on concurrent handler threads while the
    housekeeping thread purges stale series; every completed edge must
    land (the store, and the families' state rebind against each other
    and against the purge's zeroing, are read-modify-write). A collector
    thread snapshots under the state_lock all the while: every emit
    DONATES the states it rebinds, and a reader outside the lock would
    meet a deleted buffer."""
    import sys
    import threading

    reg = ManagedRegistry(now=FakeClock())
    p = ServiceGraphsProcessor(reg, ServiceGraphsConfig())
    n_threads, n_pushes = 6, 15
    emits0 = emits_by_path()

    def batch(k: int):
        t = k.to_bytes(16, "big")
        return _mk_batch(interner=reg.interner, spans=[
            _span(1, service="frontend", kind=KIND_CLIENT, trace=t),
            _span(2, service="backend", kind=KIND_SERVER, trace=t,
                  parent=bytes([1]) * 8)])

    # batches stage on one thread: interning is not what is under test
    work = [[batch(i * n_pushes + j) for j in range(n_pushes)]
            for i in range(n_threads)]
    targets = [lambda w=w: [p.push_batch(sb) for sb in w] for w in work]
    evicted = []
    if with_purge:
        # one idle edge series per purge (last seen at t=0, the clock
        # stands at 1000 s, stale after 900 s), so every purge zeroes
        # rows of the very state arrays the pushes are rebinding
        junk = [reg.interner.intern_many([f"idle-{k}", "x", ""])[None, :]
                for k in range(40)]

        def purge():
            for row in junk:
                with reg.state_lock:
                    p.total.table.lookup_or_create(row, 0.0)
                evicted.append(reg.purge_stale())

        targets.append(purge)
    stop = threading.Event()
    snaps, errs = [], []

    def collector():
        while not stop.is_set():
            try:
                snaps.append(series_value(
                    reg.collect(1), "traces_service_graph_request_total",
                    client="frontend", server="backend") or 0.0)
            except Exception as e:      # pragma: no cover - the regression
                errs.append(repr(e))
                return

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=t) for t in targets]
        coll = threading.Thread(target=collector)
        coll.start()
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
            assert not th.is_alive()
    finally:
        stop.set()
        coll.join(timeout=60)
        sys.setswitchinterval(old)
    assert not errs, errs[:3]
    assert snaps == sorted(snaps)       # a counter never steps back
    assert emits_by_path() == {"fused": emits0["fused"] + n_threads * n_pushes,
                               "family": emits0["family"]}
    samples = reg.collect(1)
    assert series_value(samples, "traces_service_graph_request_total",
                        client="frontend", server="backend") \
        == n_threads * n_pushes
    if with_purge:
        assert evicted == [1] * len(junk)
        assert series_value(samples, "traces_service_graph_request_total",
                            client="idle-0") is None


def test_servicegraphs_expiry_virtual_nodes():
    clock = FakeClock()
    reg = ManagedRegistry(now=clock)
    p = ServiceGraphsProcessor(reg, ServiceGraphsConfig(wait_s=5.0))
    # unmatched ROOT server span -> "user" virtual client after expiry; a
    # server whose client span never came, and a client with no peer
    # attribute, expire into no edge (`servicegraphs.go` onExpire)
    sb = _mk_batch(interner=reg.interner, spans=[
        _span(1, service="api", kind=KIND_SERVER),
        _span(2, service="web", kind=KIND_CLIENT, attrs={"db.system": "mysql"}),
        _span(3, service="orphan", kind=KIND_SERVER, parent=bytes([9]) * 8),
        _span(4, service="web", kind=KIND_CLIENT),
    ])
    p.push_batch(sb)
    assert series_value(reg.collect(1), "traces_service_graph_request_total",
                        client="user") is None
    clock.t += 10.0
    p.push_batch(_mk_batch([], interner=reg.interner))  # tick
    samples = reg.collect(2)
    assert series_value(samples, "traces_service_graph_request_total",
                        client="user", server="api") == 1.0
    assert series_value(samples, "traces_service_graph_request_total",
                        client="web", server="mysql") == 1.0
    assert series_value(samples, "traces_service_graph_request_total",
                        server="orphan") is None
    assert sum(s.value for s in samples
               if s.name == "traces_service_graph_request_total") == 2.0
    assert p.expired == 4
    assert p.edges == {"completed": 0, "virtual": 2}


def emits_by_path() -> dict:
    from tempo_tpu.generator.processors.servicegraphs import EMITS

    return {path: EMITS.value((path,)) for path in ("fused", "family")}


def _edge_compiles() -> float:
    from tempo_tpu.obs.jaxruntime import JIT_COMPILES

    return JIT_COMPILES.value(("servicegraphs_edge_update",))


def _edges(reg, n: int, seed: int = 0) -> list:
    """`n` completed edges, as columns, over 5 (client, server) pairs, so
    slots repeat inside a batch; every third failed, every fourth
    messaging."""
    from tempo_tpu.generator.processors.servicegraphs import EdgeColumns

    it = reg.interner
    r = np.random.default_rng(seed)
    out = []
    for j in range(n):
        pair = int(r.integers(5))
        msg = j % 4 == 1
        out.append((it.intern(f"cli-{pair}"), it.intern(f"srv-{pair}"),
                    1 if msg else 0,
                    float(r.uniform(0.001, 20.0)), float(r.uniform(0.001, 20.0)),
                    j % 3 == 0, float(r.uniform(0.0, 2.0)) if msg else 0.0))
    return EdgeColumns(*(np.array(col, dt) for col, dt in zip(
        zip(*out), (np.int32, np.int32, np.int8, np.float64, np.float64,
                    np.bool_, np.float64))))


def _sg_pair(messaging: bool, clock=None):
    """Two processors over dense registries: the jitted step, and the
    family-level calls a paged registry takes (steered here, in the test:
    the program has no switch)."""
    out = []
    for fused in (True, False):
        reg = ManagedRegistry(now=clock or FakeClock())
        p = ServiceGraphsProcessor(reg, ServiceGraphsConfig(
            wait_s=5.0, enable_messaging_system_latency_histogram=messaging))
        assert p._fused
        p._fused = fused
        out.append((reg, p))
    return out


def _assert_same_edge_state(fused, family):
    (reg_f, pf), (reg_e, pe) = fused, family
    assert len(pf._families) == len(pe._families) >= 4
    for k, (ff, fe) in enumerate(zip(pf._families, pe._families)):
        sf, se = ff.state, fe.state
        if k < 2:                       # the two counters
            np.testing.assert_array_equal(sf.values, se.values)
            continue
        np.testing.assert_array_equal(sf.bucket_counts, se.bucket_counts)
        np.testing.assert_array_equal(sf.counts, se.counts)
        np.testing.assert_allclose(sf.sums, se.sums, rtol=1e-6)
    got, want = (sorted((s.name, s.labels, s.value) for s in reg.collect(7))
                 for reg in (reg_f, reg_e))
    assert [g[:2] for g in got] == [w[:2] for w in want]
    assert len(got) > 0
    for (name, _, g), (_, _, w) in zip(got, want):
        if name.endswith("_sum"):
            assert g == pytest.approx(w, rel=1e-6)
        else:
            assert g == w


@pytest.mark.parametrize("messaging", [False, True], ids=["plain", "messaging"])
@pytest.mark.parametrize("n", [1, 8, 16, 17, 40, 1100])
def test_servicegraphs_fused_step_equals_family_calls(messaging, n):
    """The jitted, donating step and the family-level calls run the same
    registry update functions: counts and buckets bit-equal, sums to f32
    accumulation order, over batches that fill, spill and pad their
    shape (16 or 512 rows; padding rides slot -1), three emits deep. An
    emit of more than 512 edges takes one step a 512 of them."""
    pair = _sg_pair(messaging)
    e0 = emits_by_path()
    for seed in range(3):
        for reg, p in pair:
            p._emit(_edges(reg, n, seed))
    steps = 3 * -(-n // 512)
    assert emits_by_path() == {"fused": e0["fused"] + steps,
                               "family": e0["family"] + steps}
    _assert_same_edge_state(*pair)
    total = pair[0][1].total.state.values
    assert float(total.sum()) == 3 * n


@pytest.mark.parametrize("messaging", [False, True], ids=["plain", "messaging"])
def test_servicegraphs_completed_and_expired_edges_ride_one_emit(messaging):
    """A push that completes edges while its TTL ring expires others
    (virtual nodes) pays ONE emit, on either path, and both paths agree."""
    clock = FakeClock()
    pair = _sg_pair(messaging, clock)

    def push(spans):
        for reg, p in pair:
            p.push_batch(_mk_batch(spans, interner=reg.interner))

    push([_span(1, service="api", kind=KIND_SERVER),
          _span(2, service="web", kind=KIND_CLIENT, attrs={"db.system": "mysql"})])
    clock.t += 10.0
    e0 = emits_by_path()
    t = bytes([7]) * 16
    push([_span(3, service="frontend", kind=KIND_PRODUCER, trace=t,
                dur_ns=3 * 10**8, status=STATUS_ERROR),
          _span(4, service="backend", kind=KIND_CONSUMER, trace=t,
                parent=bytes([3]) * 8, dur_ns=2 * 10**8, start=10**9 + 10**8)])
    assert emits_by_path() == {"fused": e0["fused"] + 1,
                               "family": e0["family"] + 1}
    assert [p.expired for _, p in pair] == [2, 2]
    _assert_same_edge_state(*pair)
    samples = pair[0][0].collect(9)
    for labels in (dict(client="user", server="api"),
                   dict(client="web", server="mysql"),
                   dict(client="frontend", server="backend",
                        connection_type="messaging_system")):
        assert series_value(samples, "traces_service_graph_request_total",
                            **labels) == 1.0
    if messaging:
        assert series_value(
            samples, "traces_service_graph_request_messaging_system_seconds_sum",
            client="frontend", server="backend") == pytest.approx(0.1)
        assert series_value(
            samples, "traces_service_graph_request_messaging_system_seconds_count",
            client="user", server="api") == 0.0


def _pair_batch(reg, pairs: int, k: int):
    """`pairs` client→server pairs, completed inside the push."""
    spans = []
    for j in range(pairs):
        t = (k * 1000 + j).to_bytes(16, "big")
        sid = bytes([j + 1]) * 8
        spans += [dict(_span(0, service=f"c{j % 3}", kind=KIND_CLIENT, trace=t),
                       span_id=sid),
                  dict(_span(0, service=f"s{j % 3}", kind=KIND_SERVER, trace=t,
                             parent=sid), span_id=bytes([j + 101]) * 8)]
    return _mk_batch(spans, interner=reg.interner)


def test_servicegraphs_one_dispatch_a_push():
    """N pushes of one bucket shape: N fused emits, no family-level one,
    and at most one compile a bucket shape (16 and 512 columns here)."""
    reg = ManagedRegistry(now=FakeClock())
    p = ServiceGraphsProcessor(reg, ServiceGraphsConfig())
    e0, c0 = emits_by_path(), _edge_compiles()
    n = 6
    for pairs in (8, 20):
        for k in range(n):
            p.push_batch(_pair_batch(reg, pairs, k))
    assert emits_by_path() == {"fused": e0["fused"] + 2 * n,
                               "family": e0["family"]}
    assert _edge_compiles() - c0 <= 2
    c1 = _edge_compiles()
    p.push_batch(_pair_batch(reg, 8, 99))
    assert _edge_compiles() == c1           # steady state: no new trace
    assert float(p.total.state.values.sum()) == n * 28 + 8
    # the families' counter is on /metrics beside the span families
    from tempo_tpu.obs.jaxruntime import RUNTIME
    assert 'tempo_metrics_generator_servicegraphs_emits_total{path="fused"}' \
        in RUNTIME.render()


def test_servicegraphs_fused_step_on_sharded_states():
    """Under the serving mesh a family's state is placed sharded over
    'series' (`metrics.place_state`): the jitted step takes the states
    as they are placed, the packed matrix replicated, and gives the
    dense answer."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tempo_tpu.registry import metrics as rm

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("series",))
    s1, s2 = NamedSharding(mesh, P("series")), NamedSharding(mesh, P("series", None))
    dense, sharded = _sg_pair(True)
    sharded[1]._fused = True
    for fam in sharded[1]._families:
        fam.state = rm.place_state(fam.state, s1, s2)
    for seed in range(3):
        for reg, p in (dense, sharded):
            p._emit(_edges(reg, 17, seed))
    assert len(sharded[1].total.state.values.sharding.device_set) == 4
    assert len(sharded[1].client_hist.state.bucket_counts.sharding.device_set) == 4
    _assert_same_edge_state(dense, sharded)


def test_generator_instance_slack_filter():
    clock = FakeClock(t=1000.0)
    cfg = GeneratorConfig(processors=("span-metrics",),
                          ingestion_time_range_slack_s=30.0)
    g = GeneratorInstance("t1", cfg, now=clock)
    now_ns = int(1000.0 * 1e9)
    sb = _mk_batch(interner=g.registry.interner, spans=[
        _span(1, start=now_ns - 10**9),            # recent: kept
        _span(2, start=now_ns - 3600 * 10**9),     # 1h old: dropped
    ])
    g.push_batch(sb)
    assert g.spans_filtered_slack == 1
    samples = g.registry.collect(1)
    total = sum(s.value for s in samples if s.name == "traces_spanmetrics_calls_total")
    assert total == 1.0


# -- remote write wire ------------------------------------------------------

def snappy_decompress(data: bytes) -> bytes:
    """Tiny snappy block decoder (literals + copies) to validate framing."""
    ulen, pos = pw.read_varint(data, 0)
    out = bytearray()
    while pos < len(data):
        tag = data[pos]; pos += 1
        t = tag & 3
        if t == 0:
            ln = (tag >> 2) + 1
            if ln > 60:
                nb = ln - 60
                ln = int.from_bytes(data[pos:pos + nb], "little") + 1
                pos += nb
            out += data[pos:pos + ln]; pos += ln
        else:
            raise AssertionError("copy ops unexpected from literal-only encoder")
    assert len(out) == ulen
    return bytes(out)


def test_snappy_roundtrip_various_sizes():
    for n in (0, 1, 59, 60, 61, 255, 256, 257, 70000, 200001):
        data = bytes(range(256)) * (n // 256) + bytes(range(n % 256))
        assert snappy_decompress(rw.snappy_compress(data)) == data


def test_write_request_encoding_decodes():
    samples = [
        Sample("m_total", (("__name__", "m_total"), ("svc", "a")), 42.0, 1234),
    ]
    body = rw.encode_write_request(samples)
    ts_msgs = [v for f, _, v in pw.iter_fields(body) if f == 1]
    assert len(ts_msgs) == 1
    fields = pw.decode_fields(bytes(ts_msgs[0]))
    labels = {}
    for lb in fields[1]:
        lf = pw.decode_fields(bytes(lb))
        labels[bytes(lf[1][0]).decode()] = bytes(lf[2][0]).decode()
    assert labels == {"__name__": "m_total", "svc": "a"}
    sf = pw.decode_fields(bytes(fields[2][0]))
    assert pw.f64(sf[1][0]) == 42.0 and sf[2][0] == 1234


def test_native_histogram_encoding():
    counts = np.zeros(64)
    counts[3] = 5  # bucket b=3 covers [4,8) -> prom schema-0 index 3: (4,8]
    counts[4] = 2
    counts[10] = 1
    body = rw.encode_native_histogram(counts, total=8, zeros=0, sum_=40.0, ts_ms=7)
    f = pw.decode_fields(body)
    assert f[1][0] == 8          # count_int
    assert pw.f64(f[3][0]) == 40.0
    spans = [pw.decode_fields(bytes(s)) for s in f[11]]
    # two spans: [idx3 len2], [idx10 len1]
    assert pw.zigzag_decode(spans[0][1][0]) == 3 and spans[0][2][0] == 2
    # second span starts at prom idx 10; previous span ended at idx 5 -> gap 5
    assert pw.zigzag_decode(spans[1][1][0]) == 5 and spans[1][2][0] == 1
    deltas = [pw.zigzag_decode(d) for d in f[12]]
    assert np.cumsum(deltas).tolist() == [5, 2, 1]


def test_native_histogram_encoding_with_offset():
    # offset=32: bucket b covers [2^(b-33), 2^(b-32)). A 0.5s latency has
    # b = floor(log2 .5)+1+32 = 32 -> prom index b-32 = 0: (0.5, 1].
    counts = np.zeros(64)
    counts[32] = 4
    body = rw.encode_native_histogram(counts, total=4, zeros=0, sum_=2.0,
                                      ts_ms=7, offset=32)
    f = pw.decode_fields(body)
    spans = [pw.decode_fields(bytes(s)) for s in f[11]]
    assert pw.zigzag_decode(spans[0][1][0]) == 0 and spans[0][2][0] == 1


# -- the collection tick in columns (PR 29) ----------------------------------
#
# `RemoteWriteClient.send` encodes a tick from `collect_columns()` and a
# family's per-series label blocks kept across ticks. The payload must be,
# byte for byte, what the plain encoder makes of one `Sample` a series.

# sorted with `__name__` and `le`: Alpha < __name__ < cluster < lb < le <
# lf < (service ... status_code) < zone
EXTERNAL = {"Alpha": "1", "cluster": "c", "lb": "ends in NUL\x00",
            "lf": "x", "zone": "z"}
LAYOUTS = ["dense", "paged",
           pytest.param("mesh", marks=pytest.mark.skipif(
               "len(__import__('jax').devices()) < 4",
               reason="needs 4 virtual devices"))]


def _reference_samples(reg, ts):
    """`ManagedRegistry.collect` as it was before the tick went columnar:
    a family, a slot, a `Sample` at a time. Leaves the stale markers
    pending."""
    from tempo_tpu.registry import registry as R

    out = []
    for mt in reg._metrics.values():
        snap = mt._snap()
        for s in mt.table.active_slots().tolist():
            base, ex = mt.labels_of(s), mt.exemplars.get(s)
            if isinstance(mt, R.Histogram):
                bc, sums, counts = snap
                cum = np.cumsum(bc[s])
                out.append(Sample(mt.name + "_count", base, float(counts[s]), ts))
                out.append(Sample(mt.name + "_sum", base, float(sums[s]), ts))
                for i, e in enumerate(mt.hist_edges()):
                    out.append(Sample(
                        mt.name + "_bucket", base + (("le", R._fmt_le(e)),),
                        float(cum[i]), ts,
                        exemplar=ex if ex and ex.value <= e else None))
                out.append(Sample(mt.name + "_bucket", base + (("le", "+Inf"),),
                                  float(cum[-1]), ts, exemplar=ex))
            elif isinstance(mt, R.NativeHistogram):
                sums, counts = snap
                out.append(Sample(mt.name + "_count", base, float(counts[s]), ts))
                out.append(Sample(mt.name + "_sum", base, float(sums[s]), ts))
            else:
                out.append(Sample(
                    mt.name, base, float(snap[0][s]), ts,
                    exemplar=None if isinstance(mt, R.Gauge) else ex))
        out += [Sample(mt.name, labels, R.STALE_NAN, ts, is_stale_marker=True)
                for labels, _ in mt._stale_pending]
    return out


def _wire_world(layout, cap=512, external=EXTERNAL):
    """(registry, span-metrics processor, clock) on `layout`: the calls
    counter and the latency histogram with exemplars under, between and
    over the edges, the size counter, a gauge, a native histogram, a
    service name that is not ASCII."""
    import contextlib

    from tempo_tpu.parallel import serving
    from tempo_tpu.registry import pages as P

    clock = FakeClock(1000.0)
    with contextlib.ExitStack() as stack:
        if layout == "paged":
            stack.enter_context(P.use(P.PagePool(P.PagePoolConfig(
                enabled=True, page_rows=64, arena_slots=4096))))
        if layout == "mesh":
            stack.enter_context(serving.use(serving.ServingMesh(
                serving.MeshConfig(enabled=True, devices=4, series_shards=4))))
            stack.callback(serving.reset)
        reg = ManagedRegistry("t", RegistryOverrides(
            max_active_series=cap, stale_duration_s=100.0,
            external_labels=dict(external)), now=clock)
        proc = SpanMetricsProcessor(reg, SpanMetricsConfig(
            sketch_max_series=min(cap, 256)))
        proc.push_batch(_wire_batch(reg, range(1, 61)))
    assert (reg.pages is not None) == (layout == "paged")
    assert len(proc.calls.state.values.sharding.device_set) == 4 \
        if layout == "mesh" else proc._mesh is None
    reg.new_gauge("queue_depth", ("svc",)).set(("a",), 2.5)
    reg.new_native_histogram("nat_seconds", ("svc",)).observe_batch(
        reg.interner.intern_many(["a"])[None, :], np.array([0.3], np.float32))
    vals = sorted(ex.value for ex in proc.calls.exemplars.values())
    assert vals[0] < 0.002 < vals[len(vals) // 2] < 16.384 < vals[-1]
    return reg, proc, clock


def _wire_batch(reg, ids, prefix="op"):
    return _mk_batch(
        [_span(i, service="café-日本" if i % 3 == 0 else f"svc-{i % 5}",
               name=f"{prefix}-{i % 7}", kind=1 + i % 3,
               dur_ns=(10**5, 5 * 10**8, 10**11)[i % 3]) for i in ids],
        interner=reg.interner)


def _tick(reg, ts, native=False):
    """One tick through `send`: (the WriteRequest posted, the plain
    encoder's over the same state, {kept, built} series counted)."""
    pending = {mt: list(mt._stale_pending) for mt in reg._metrics.values()}
    want_samples = _reference_samples(reg, ts)
    nat = reg.native_histograms(ts) if native else []
    want = rw.encode_write_request(want_samples, nat)
    # `collect()` returns what it returned (and drains the markers:
    # queue them again for the tick under test)
    assert repr(reg.collect(ts)) == repr(want_samples)
    for mt, was in pending.items():
        assert not mt._stale_pending
        mt._stale_pending = was
    client = rw.RemoteWriteClient(rw.RemoteWriteConfig(url="http://sink.invalid/"))
    posted = []
    client._post = lambda payload, n: posted.append((payload, n)) or True
    before = dict(rw._RW_SERIES)
    assert client.send(reg.collect_columns(ts), nat)
    (payload, n_samples), = posted
    assert n_samples == len(want_samples)
    assert not any(mt._stale_pending for mt in reg._metrics.values())
    return (snappy_decompress(payload), want,
            {k: v - before[k] for k, v in rw._RW_SERIES.items()})


def _series_labels(body):
    """[{name: value}] a TimeSeries of a WriteRequest."""
    out = []
    for _, _, ts_msg in pw.iter_fields(body):
        labels = [pw.decode_fields(bytes(lb))
                  for lb in pw.decode_fields(bytes(ts_msg))[1]]
        out.append({bytes(lf[1][0]).decode(): bytes(lf[2][0]).decode()
                    for lf in labels})
    return out


@pytest.mark.parametrize("native", [False, True], ids=["scalar", "native"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_columnar_payload_is_the_plain_encoders_byte_for_byte(layout, native):
    reg, proc, clock = _wire_world(layout)
    n_nat = 1 if native else 0
    got, want, grew = _tick(reg, 1_700_000_000_000, native)
    assert got == want
    n = len(_series_labels(want))
    assert grew == {"kept": 0, "built": n}          # every series is new
    got, want, grew = _tick(reg, 1_700_000_015_000, native)
    assert got == want
    assert grew == {"kept": n - n_nat, "built": n_nat}
    # some series idle out, the rest and the gauge stay: pending markers
    clock.t += 1000
    proc.push_batch(_wire_batch(reg, range(1, 21)))
    reg.metric("queue_depth").set(("a",), 3.5)
    n_stale = reg.purge_stale()
    assert n_stale and reg.metric("nat_seconds").table.active_count == 0
    got, want, grew = _tick(reg, 1_700_001_015_000, native)
    assert got == want
    labels = _series_labels(want)
    # calls, size and latency shared the evicted slots, the native
    # histogram had one of its own; a marker's labels are built each time
    assert grew["built"] == 3 * (n_stale - 1) + 1
    assert grew["kept"] == len(labels) - grew["built"]
    assert {"Alpha", "__name__", "cluster", "lb", "lf", "zone"} <= set(labels[0])
    assert any(lb.get("service") == "café-日本" for lb in labels)


def test_columnar_payload_with_a_label_named_le_takes_the_plain_encoder():
    """An external label called `le` sorts against every bucket's own by
    value: no kept block fits all edges, the family is encoded a Sample at
    a time, and the bytes are still the plain encoder's."""
    reg, _, _ = _wire_world("dense", external={"le": "0.1", "zone": "z"})
    for ts in (1, 2):
        got, want, grew = _tick(reg, ts)
        assert got == want
        assert grew == {"kept": 0, "built": len(_series_labels(want))}


def test_label_blocks_forget_an_evicted_slot():
    """A slot that is evicted and taken by a NEW series must not inherit
    the old series' labels: the payload carries the new labels, the stale
    marker the old, and only the new series count as `built`."""
    reg, proc, clock = _wire_world("dense")
    _tick(reg, 1)
    old = {s: proc.calls.labels_of(s)
           for s in proc.calls.table.active_slots().tolist()}
    clock.t += 1000
    assert reg.purge_stale() == len(old) + 2        # + gauge, native hist
    assert proc.calls.label_blocks._blocks == {}
    proc.push_batch(_wire_batch(reg, range(1, 31), prefix="new"))
    new = {s: proc.calls.labels_of(s)
           for s in proc.calls.table.active_slots().tolist()}
    assert new and set(new) <= set(old)             # the slots are reused
    got, want, grew = _tick(reg, 2)
    assert got == want
    labels = _series_labels(got)
    calls = [lb for lb in labels
             if lb["__name__"] == "traces_spanmetrics_calls_total"]
    assert [tuple(sorted(lb.items())) for lb in calls] == \
        list(new.values()) + list(old.values())     # live series, then markers
    assert all(lb["span_name"].startswith("new-") for lb in calls[:len(new)])
    n_new = sum(len(c.slots) * len(c.kinds) for c in reg.collect_columns(3))
    assert grew == {"kept": 0, "built": len(labels)} and n_new < len(labels)
    assert _tick(reg, 3)[2] == {"kept": n_new, "built": 0}


def test_label_blocks_follow_the_external_labels():
    reg, _, _ = _wire_world("dense")
    _, want, _ = _tick(reg, 1)
    assert all(lb["cluster"] == "c" for lb in _series_labels(want))
    reg.overrides.external_labels = {"cluster": "other", "region": "r"}
    got, want, grew = _tick(reg, 2)
    assert got == want
    labels = _series_labels(got)
    assert all(lb["cluster"] == "other" and lb["region"] == "r"
               and "zone" not in lb for lb in labels)
    assert grew == {"kept": 0, "built": len(labels)}
    assert _tick(reg, 3)[2] == {"kept": len(labels), "built": 0}


def test_label_blocks_of_a_tick_encoded_in_the_middle_of_an_eviction():
    """The encoder takes no registry lock, so a tick can be encoded after
    `note_stale` dropped the evicted slots' blocks and before the table
    forgets their label rows. What it builds then is the OLD series'
    block, and the slot's next series must not find it kept. (The race
    below meets this window by chance; here it is made.)"""
    reg, proc, clock = _wire_world("dense")
    _tick(reg, 1)
    cols = reg.collect_columns(2)           # gathered before the purge ...
    # ... and encoded inside it: a family's hooks run between its
    # `note_stale` and the table's purge, `sizes` is the table's last
    proc.sizes.evict_hooks.append(lambda padded: rw.encode_columns(cols))
    clock.t += 1000
    assert reg.purge_stale()
    assert proc.calls.label_blocks._blocks  # blocks of series that are gone
    proc.push_batch(_wire_batch(reg, range(1, 31), prefix="new"))
    got, want, _ = _tick(reg, 3)
    assert got == want


def test_label_blocks_survive_evictions_racing_the_encoder():
    """An eviction between a build's read of a slot's labels and its store
    must not leave the old block for the slot's next series: a purger and
    an encoder race (more switches than the default interval gives), then
    a quiet tick must still equal the plain encoder's."""
    import sys
    import threading

    reg, proc, clock = _wire_world("dense")
    stop, errs = threading.Event(), []

    def encoder():
        try:
            while not stop.is_set():
                rw.encode_columns(reg.collect_columns(1))
        except Exception as e:      # pragma: no cover - the regression
            errs.append(repr(e))

    t = threading.Thread(target=encoder)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    t.start()
    try:
        for k in range(25):
            clock.t += 1000
            reg.purge_stale()
            proc.push_batch(_wire_batch(reg, range(1, 41), prefix=f"r{k}"))
    finally:
        stop.set()
        t.join(timeout=60)
        sys.setswitchinterval(old)
    assert not t.is_alive() and not errs, errs[:3]
    got, want, _ = _tick(reg, 2)
    assert got == want


def test_a_tenants_collect_opens_each_collect_span_once():
    """`collect_gather_s`, `collect_format_s`, `collect_encode_s` and
    `collect_send_s` read one span each a tenant's collect: the columnar
    tick opens them where the per-sample tick did."""
    from tempo_tpu.utils import tracing

    inst = GeneratorInstance("t", GeneratorConfig(
        processors=("span-metrics",),
        remote_write=rw.RemoteWriteConfig(url="http://sink.invalid/")),
        now=FakeClock())
    inst.push_batch(_mk_batch(
        [_span(i, start=999 * 10**9) for i in range(1, 9)],
        interner=inst.registry.interner))
    posted = []
    inst.remote_write._post = lambda payload, n: posted.append(n) or True
    tracing.reset_span_rows()
    n = inst.collect_and_push(5)
    assert posted == [n] and n == len(inst.registry.collect(5)) > 0
    rows = tracing.span_rows()
    for name in ("generator.collect", "generator.drain", "registry.gather",
                 "registry.format", "remote_write.encode",
                 "remote_write.send"):
        assert rows[(name, "clear")][0] == (2 if name.startswith("registry.")
                                            else 1), name


def test_columnar_tick_is_5x_the_per_sample_tick_on_4096_series():
    """A ratio inside one process, not a wall limit: both sides slow down
    together under the suite's six workers. The floor is generous: the
    columnar tick measures 30-45x here (0.33 s against 15 s at 18,495
    series a tenant), and 5x is what the issue asks a CPU to hold."""
    import time

    reg = ManagedRegistry("t", RegistryOverrides(max_active_series=8192))
    calls = reg.new_counter("calls_total", ("service", "span_name"))
    lat = reg.new_histogram("latency", ("service", "span_name"))
    lat.share_table(calls)
    n = 4096
    rows = np.stack([
        reg.interner.intern_many([f"svc-{i % 32}" for i in range(n)]),
        reg.interner.intern_many([f"op-{i}" for i in range(n)])], axis=1)
    slots = calls.inc_batch(rows.astype(np.int32))
    lat.observe_slots(slots, np.linspace(0.001, 20.0, n).astype(np.float32))
    rw.encode_columns(reg.collect_columns(1))       # the tick that builds

    def best(fn):
        out = []
        for _ in range(3):
            t0 = time.perf_counter()
            fn()
            out.append(time.perf_counter() - t0)
        return min(out)

    columnar = best(lambda: rw.encode_columns(reg.collect_columns(2)))
    plain = best(lambda: rw.encode_write_request(reg.collect(2)))
    assert rw.encode_columns(reg.collect_columns(2)) == \
        rw.encode_write_request(reg.collect(2))
    assert plain / columnar >= 5.0, (plain, columnar)


# -- staged fast paths (round-5 e2e throughput work) -------------------------
#
# The dedicated-spanmetrics generator resolves staged records straight to
# device arrays in C++ (`native.spanmetrics_resolve`), and the in-process
# distributor tee hands over scan RECORDS without re-parsing or slicing
# (`native.spanmetrics_from_recs`). Both must be bit-identical to the full
# SpanBatch staging path — same series table, same device states.

def _fast_slow_pair(n_spans=4096):
    from tempo_tpu.generator.generator import Generator
    from tempo_tpu.generator.instance import GeneratorConfig
    from tempo_tpu.overrides import Overrides

    payload = make_otlp_payload(n_spans, seed=3)

    def mk():
        cfg = GeneratorConfig(processors=("span-metrics",))
        cfg.registry.disable_collection = True
        return Generator(cfg, overrides=Overrides())

    return payload, mk(), mk()


def _assert_state_equal(pa, pb):
    for a, b, what in (
            (pa.calls.state.values, pb.calls.state.values, "calls"),
            (pa.latency.state.bucket_counts, pb.latency.state.bucket_counts,
             "latency"),
            (pa.sizes.state.values, pb.sizes.state.values, "sizes"),
            (pa.dd.counts, pb.dd.counts, "ddsketch")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=what)


def test_staged_fast_path_matches_full_staging():
    payload, fast, slow = _fast_slow_pair()
    slow.instance("t").push_otlp_staged = lambda *a, **k: None  # force full
    for _ in range(2):                      # second push hits warm tables
        n1 = fast.push_otlp("t", payload)
        n2 = slow.push_otlp("t", payload)
    assert n1 == n2 == 4096
    pf = fast.instance("t").processors["span-metrics"]
    ps = slow.instance("t").processors["span-metrics"]
    _assert_state_equal(pf, ps)
    # collected samples agree (labels resolve through the same interner)
    sa = sorted((s.name, s.labels, s.value)
                for s in fast.instance("t").registry.collect(1000))
    sb = sorted((s.name, s.labels, s.value)
                for s in slow.instance("t").registry.collect(1000))
    assert sa == sb and sa


def test_tee_recs_route_matches_payload_route():
    from tempo_tpu import native
    payload, ga, gb = _fast_slow_pair()
    recs = native.otlp_scan(payload)
    if recs is None:
        pytest.skip("native layer unavailable")
    gb.push_otlp_recs = lambda *a, **k: None    # force payload-bytes route
    for _ in range(2):
        got = ga.push_otlp_recs("t", payload, recs)
        assert got == 4096
        gb.push_otlp("t", payload, trusted=True)
    _assert_state_equal(ga.instance("t").processors["span-metrics"],
                        gb.instance("t").processors["span-metrics"])


def test_tee_recs_route_sharded_subset():
    """A ring-sharded tee passes a record SUBSET with the ORIGINAL payload;
    series must match pushing the equivalent sliced payload."""
    from tempo_tpu import native
    from tempo_tpu.model.otlp import slice_otlp_payload
    payload, ga, gb = _fast_slow_pair()
    recs = native.otlp_scan(payload)
    if recs is None:
        pytest.skip("native layer unavailable")
    pick = np.arange(len(recs)) % 3 == 0
    sub = recs[pick]
    assert ga.push_otlp_recs("t", payload, sub) == int(pick.sum())
    sliced = slice_otlp_payload(payload, recs,
                                np.flatnonzero(pick).tolist())
    gb.push_otlp("t", sliced, trusted=True)
    _assert_state_equal(ga.instance("t").processors["span-metrics"],
                        gb.instance("t").processors["span-metrics"])


def test_staged_fast_path_slack_filter_counts():
    from tempo_tpu.generator.generator import Generator
    from tempo_tpu.generator.instance import GeneratorConfig
    from tempo_tpu.overrides import Overrides

    cfg = GeneratorConfig(processors=("span-metrics",))
    cfg.registry.disable_collection = True
    cfg.ingestion_time_range_slack_s = 30.0
    gen = Generator(cfg, overrides=Overrides())
    payload = make_otlp_payload(512, seed=9)
    import time as _time
    inst = gen.instance("t")
    # make every span stale: pushes far in the "future" slide the window
    inst.now = lambda: _time.time() + 10_000
    gen.push_otlp("t", payload)
    assert inst.spans_filtered_slack == 512
    assert inst.spans_received == 512


def test_slack_drops_on_metrics_beside_the_distributors_reasons():
    """The slack filter's drops are on /metrics as
    `tempo_discarded_spans_total{reason="outside_slack"}`: ONE family,
    which the distributor (registered second on the single binary) adds
    its own reasons to."""
    import time as _time
    from tempo_tpu.generator.generator import Generator
    from tempo_tpu.generator.instance import GeneratorConfig
    from tempo_tpu.obs.registry import Registry, parse_exposition
    from tempo_tpu.overrides import Overrides

    reg = Registry()
    cfg = GeneratorConfig(processors=("span-metrics",))
    cfg.registry.disable_collection = True
    cfg.ingestion_time_range_slack_s = 30.0
    gen = Generator(cfg, overrides=Overrides(), registry=reg)
    reg.counter_func("tempo_discarded_spans_total",
                     lambda: [(("rate_limited",), 2)],
                     labels=("reason",), shared=True)
    with pytest.raises(ValueError):          # unshared: still a clash
        reg.counter_func("tempo_discarded_spans_total", lambda: [],
                         labels=("reason",))
    inst = gen.instance("t")
    inst.now = lambda: _time.time() + 10_000     # every span is stale
    gen.push_otlp("t", make_otlp_payload(64, seed=9))
    fam = parse_exposition(reg.render())["tempo_discarded_spans_total"]
    assert fam["type"] == "counter"
    assert fam["samples"] == {
        ("tempo_discarded_spans_total",
         (("reason", "outside_slack"),)): 64.0,
        ("tempo_discarded_spans_total",
         (("reason", "rate_limited"),)): 2.0}
    assert inst.spans_filtered_slack == 64


def test_donating_push_vs_concurrent_collection():
    """The packed fast path DONATES state buffers; collect()/
    native_histograms()/quantile() run on the collection thread and must
    serialize on the registry state_lock — an unguarded reader dies with
    'Array has been deleted' (caught live by this hammer before the
    quantile read moved inside the lock)."""
    import threading

    from tempo_tpu.generator.generator import Generator
    from tempo_tpu.generator.instance import GeneratorConfig
    from tempo_tpu.overrides import Overrides

    payload = make_otlp_payload(2048, seed=8)
    gen = Generator(GeneratorConfig(processors=("span-metrics",)),
                    overrides=Overrides())
    gen.push_otlp("t", payload)
    inst = gen.instance("t")
    proc = inst.processors["span-metrics"]
    # the hammer is vacuous unless the DONATING staged path is live
    assert proc.supports_staged_fast_path()
    assert inst.push_otlp_staged(payload) is not None
    stop = threading.Event()
    errs: list = []

    def collector():
        while not stop.is_set():
            try:
                inst.registry.collect(1000)
                inst.registry.native_histograms(1000)
                proc.quantile(0.99)
            except Exception as e:      # pragma: no cover - the regression
                errs.append(repr(e))
                return

    t = threading.Thread(target=collector)
    t.start()
    try:
        for i in range(40):
            gen.push_otlp("t", payload)
            if i % 8 == 0:      # the dict route donates too (push_batch)
                gen.push_spans("t", [{
                    "trace_id": b"\x01" * 16, "span_id": bytes([i]) * 8,
                    "name": "d", "service": "s", "kind": 2,
                    "status_code": 0, "start_unix_nano": 1,
                    "end_unix_nano": 2}])
    finally:
        stop.set()
        t.join()
    assert not errs, errs[:3]
