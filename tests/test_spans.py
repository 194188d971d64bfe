"""The always-on part of `tracing.span()`: self times and the `collect`
label on `/metrics`, with nothing configured.

- self time is duration less same-thread children, on an injected clock;
  a span closed on another thread than its parent adds to no parent;
- the root of a thread's tree, and no span under it, reads a thread CPU
  clock (an injected one) beside the wall clock; its CPU lands in the
  row the duration lands in, and the real clocks tell a wait from work;
- `collect` flips to `met` for a span that straddles a collection tick;
- with no exporter a span takes no shared lock and draws no random bytes;
- one served push yields exactly the expected span names, once each, and
  the self times of every tree sum to its roots' durations;
- the `span` label's values are a frozen list (cardinality guard);
- the jit names the chip benchmark's layer files match by prefix are
  pinned, so a refactor fails here instead of nulling a metric there.
"""

import contextlib
import contextvars
import glob
import json
import os
import re
import socket
import threading
import time
import urllib.request

import numpy as np
import pytest

from tempo_tpu.obs.registry import parse_exposition
from tempo_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every span name the program can put on /metrics. A new layer boundary
# is a new line HERE too: the label's cardinality is decided in review,
# not by whatever string reaches span().
SPAN_NAMES = frozenset({
    "api.push",
    "distributor.admit", "distributor.decode", "distributor.PushSpans",
    "distributor.GeneratorTee", "distributor.turn",
    "ingester.push", "ingester.cut", "instance.cut_locked",
    "generator.Push", "generator.resolve", "generator.collect",
    "generator.drain", "generator.tick",
    "spanmetrics.push", "servicegraphs.push", "servicegraphs.expire",
    "localblocks.push", "traceanalytics.push",
    "registry.purge", "registry.gather", "registry.format", "pages.alloc",
    "remote_write.encode", "remote_write.send",
    "sched.wait", "sched.dispatch", "sched.h2d", "sched.enqueue",
    "wal.append", "wal.sync", "wal.replay", "rpc.push",
    "frontend.Search", "frontend.QueryRange",
    "querier.SearchBlock", "querier.QueryRangeBlock",
    "fleet.handoff", "fleet.checkpoint", "fleet.restore",
})


class _Clock:
    """perf_counter_ns that moves only when told to."""

    def __init__(self) -> None:
        self.t = 1_000

    def __call__(self) -> int:
        return self.t

    def tick(self, ns: int) -> None:
        self.t += ns


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(tracing, "_clock", c)
    return c


class _CpuClock:
    """thread_time_ns that moves only when told to, a clock a thread as
    the real one is: `tick` moves the calling thread's alone. `reads`
    counts the calls: each is a system call on the real clock."""

    def __init__(self) -> None:
        self.t: dict = {}
        self.reads = 0

    def __call__(self) -> int:
        self.reads += 1
        return self.t.get(threading.get_ident(), 7_000)

    def tick(self, ns: int) -> None:
        ident = threading.get_ident()
        self.t[ident] = self.t.get(ident, 7_000) + ns


@pytest.fixture
def cpu(monkeypatch):
    c = _CpuClock()
    monkeypatch.setattr(tracing, "_cpu_clock", c)
    return c


def _row(name: str, collect: str = "clear") -> list:
    return tracing.span_rows()[(name, collect)]


def test_self_time_is_duration_less_children(clock):
    with tracing.span("root"):
        clock.tick(5)
        with tracing.span("mid"):
            clock.tick(7)
            with tracing.span("leaf"):
                clock.tick(11)
            with tracing.span("leaf"):
                clock.tick(13)
            clock.tick(17)
        clock.tick(19)
    # [count, duration ns, self ns, ...]
    assert _row("leaf")[:3] == [2, 24, 24]
    assert _row("mid")[:3] == [1, 48, 24]
    assert _row("root")[:3] == [1, 72, 24]
    rows = tracing.span_rows()
    assert sum(r[2] for r in rows.values()) == _row("root")[1]
    # one observation a close in each family's buckets
    assert sum(_row("leaf")[3]) == sum(_row("leaf")[4]) == 2


def test_span_closed_on_another_thread_adds_to_no_parent(clock):
    """A child that runs beside its parent (context copied to a worker,
    as the frontend and the scheduler do) is not inside it: the parent's
    self time keeps that stretch."""
    with tracing.span("parent"):
        ctx = contextvars.copy_context()

        def work():
            with tracing.span("beside"):
                clock.tick(100)

        t = threading.Thread(target=lambda: ctx.run(work))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        clock.tick(1)
    assert _row("beside")[:3] == [1, 100, 100]
    assert _row("parent")[:3] == [1, 101, 101]
    # an adopted remote parent (another process's span) takes none either
    tr = tracing.SelfTracer(sink=lambda b: None, flush_interval_s=3600)
    tracing.install(tr)
    try:
        with tracing.adopted(f"00-{'ab' * 16}-{'cd' * 8}-01"):
            with tracing.span("adoptee"):
                clock.tick(3)
    finally:
        tr.shutdown()
    assert _row("adoptee")[:3] == [1, 3, 3]


@pytest.mark.parametrize("collect", ["clear", "met"])
def test_cpu_lands_in_the_row_the_duration_lands_in(clock, cpu, collect):
    """[..., CPU ns, spans that read the CPU clock] at the row's end,
    under the `collect` value the duration got; no other row is made,
    and the child, under a parent on its thread, reads no CPU clock."""
    with tracing.span("worked"):
        clock.tick(10)
        cpu.tick(4)
        with tracing.span("worked.child"):
            clock.tick(5)
            cpu.tick(1)
            if collect == "met":
                with tracing.collecting():
                    pass
    assert set(tracing.span_rows()) == {("worked", collect),
                                        ("worked.child", collect)}
    row = _row("worked", collect)
    assert row[:3] == [1, 15, 10] and row[5:] == [5, 1]
    assert _row("worked.child", collect)[5:] == [0, 0]
    assert cpu.reads == 2


def test_only_the_root_of_a_threads_tree_reads_the_cpu_clock(clock, cpu):
    """A three-deep tree costs two reads of the CPU clock, not eight: the
    root's CPU is all the CPU its tree had, and the wall clock's columns
    are as they were without a CPU clock."""
    for _ in range(2):
        with tracing.span("root"):
            cpu.tick(5)
            clock.tick(50)              # a wait: wall moves, CPU does not
            with tracing.span("mid"):
                cpu.tick(7)
                with tracing.span("leaf"):
                    cpu.tick(11)
                    clock.tick(11)
                with tracing.span("leaf"):
                    cpu.tick(13)
                cpu.tick(17)
            cpu.tick(19)
    assert cpu.reads == 4
    assert _row("root")[5:] == [144, 2]
    assert _row("mid")[5:] == _row("leaf")[5:] == [0, 0]
    assert _row("root")[:3] == [2, 122, 100]
    assert _row("mid")[:3] == [2, 22, 0] and _row("leaf")[:3] == [4, 22, 22]


def test_child_on_another_thread_reads_its_own_cpu(clock, cpu):
    """A span whose parent is on another thread is the root of its own
    thread's tree: it reads that thread's clock, and what it burnt there
    was never on its parent's."""
    with tracing.span("parent"):
        ctx = contextvars.copy_context()

        def work():
            with tracing.span("beside"):
                cpu.tick(100)
                with tracing.span("beside.child"):
                    cpu.tick(10)

        t = threading.Thread(target=lambda: ctx.run(work))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        cpu.tick(1)
    assert _row("beside")[5:] == [110, 1]
    assert _row("beside.child")[5:] == [0, 0]
    assert _row("parent")[5:] == [1, 1]


def _cpu_step_ns() -> int:
    """The step of the real thread CPU clock: nanoseconds on most hosts,
    10 ms where the kernel keeps thread times by a ticker (the chip's
    sealed host). The real-clock cases give their limits that much room."""
    a = time.thread_time_ns()
    end = time.perf_counter() + 0.1
    while time.perf_counter() < end:
        b = time.thread_time_ns()
        if b != a:
            return b - a
    return 100_000_000


def test_a_wait_reads_as_duration_and_no_cpu():
    """The real clocks: a span parked on a lock another thread holds for
    50 ms has the duration and none of the CPU."""
    step = _cpu_step_ns()
    lock = threading.Lock()
    lock.acquire()
    with tracing.span("parked"):
        # the release is timed from inside the span, so the span lasts
        # at least the 50 ms however late the main thread reaches it
        threading.Timer(0.05, lock.release).start()
        assert lock.acquire(timeout=10)
    row = _row("parked")
    assert row[1] >= 50e6 and 0 <= row[5] < 10e6 + step and row[6] == 1


def test_work_reads_as_cpu():
    """The real clocks: a span around a 30 ms busy loop (ten steps of a
    coarser clock) has its duration as CPU, within 30%. The machine is
    shared, so the best of five."""
    step = _cpu_step_ns()
    busy_s = max(0.03, 10 * step / 1e9)
    best = 0.0
    for _ in range(5):
        tracing.reset_span_rows()
        with tracing.span("busy"):
            end = time.perf_counter() + busy_s
            while time.perf_counter() < end:
                pass
        row = _row("busy")
        assert row[1] >= busy_s * 1e9 and row[5] <= row[1] + step + 20_000
        best = max(best, row[5] / row[1])
        if best >= 0.7:
            break
    assert best >= 0.7


def test_collect_label_met_only_when_a_tick_overlaps(clock):
    with tracing.span("before"):
        clock.tick(1)
    with tracing.span("straddles"):
        with tracing.collecting():
            with tracing.span("inside"):
                clock.tick(1)
        # the tick began and ended in between: still met at the close
        clock.tick(1)
    with tracing.collecting():
        pass
    with tracing.span("after"):
        clock.tick(1)
    rows = tracing.span_rows()
    assert {k for k in rows} == {("before", "clear"), ("straddles", "met"),
                                 ("inside", "met"), ("after", "clear")}
    # started inside a tick, ended after it
    with tracing.collecting():
        sp = tracing.span("tail")
        sp.__enter__()
    sp.__exit__(None, None, None)
    assert ("tail", "met") in tracing.span_rows()


def test_collect_all_is_the_tick(tmp_path):
    """`Generator.collect_all` is what bumps the mark: a span open across
    it reads `met`, one after it `clear`."""
    from tempo_tpu.generator.generator import Generator

    gen = Generator()
    with tracing.span("push-like"):
        gen.collect_all()
    with tracing.span("later"):
        pass
    rows = tracing.span_rows()
    assert ("push-like", "met") in rows and ("later", "clear") in rows


def test_no_exporter_no_shared_lock_no_urandom(monkeypatch):
    """With nothing configured the span path takes no lock another thread
    takes and draws no random bytes: ids and the tail buffer are the
    export part's."""
    class _Boom:
        def __enter__(self):
            raise AssertionError("a span took a shared lock")

        def __exit__(self, *a):
            return None

        acquire = release = __enter__

    def no_urandom(n):
        raise AssertionError("a span drew random bytes")

    assert not tracing.tracer().exports
    monkeypatch.setattr(tracing, "_collect_lock", _Boom())
    monkeypatch.setattr(tracing.os, "urandom", no_urandom)
    monkeypatch.setattr(threading, "Lock", _Boom)
    monkeypatch.setattr(threading, "RLock", _Boom)
    with tracing.span_for_tenant("outer", "t1", n=1) as sp:
        with tracing.span("inner"):
            pass
        assert sp.trace_id == b"" and tracing.current_trace_id_hex() is None
    with pytest.raises(ValueError):
        with tracing.span("errored"):
            raise ValueError("boom")
    monkeypatch.undo()
    assert {n for n, _ in tracing.span_rows()} == {"outer", "inner",
                                                   "errored"}


def test_concurrent_spans_lose_no_update():
    """More threads than cores, a short switch interval: every close is
    counted (each thread adds to rows of its own)."""
    import sys

    n_threads, n_spans = 16, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n_spans):
                with tracing.span("hot"):
                    with tracing.span("hot.child"):
                        pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    hot, child = _row("hot"), _row("hot.child")
    assert hot[0] == child[0] == n_threads * n_spans
    assert hot[1] - hot[2] == child[1]       # self = duration - children


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


WALL_FAMILIES = ("tempo_span_duration_seconds", "tempo_span_self_seconds")
CPU_FAMILY = "tempo_span_cpu_seconds"


def _span_samples(text: str, families=WALL_FAMILIES) -> dict:
    """{family: {(span, collect): value}} of the span families' `_sum`
    and `_count` samples in a /metrics body."""
    fams = parse_exposition(text)
    out: dict = {}
    for fam in families:
        assert fams[fam]["type"] == "histogram"
        for (name, labels), v in fams[fam]["samples"].items():
            if name.endswith("_bucket"):
                continue
            d = dict(labels)
            out.setdefault(name, {})[(d["span"], d["collect"])] = v
    return out


@contextlib.contextmanager
def _served_push(tmp_path):
    """A `target: all` App served over HTTP, nothing configured; yields
    `push()` (one OTLP push of four spans, through to the device) and
    `metrics()` (the /metrics body)."""
    from tempo_tpu import sched
    from tempo_tpu.app import App
    from tempo_tpu.app.api import serve
    from tempo_tpu.app.config import Config
    from tempo_tpu.model.otlp import encode_spans_otlp

    port = _free_port()
    cfg = Config(target="all")
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.generator.localblocks.data_dir = str(tmp_path / "lb")
    cfg.server.http_listen_port = port
    cfg.overrides_defaults.generator.processors = (
        "span-metrics", "service-graphs", "local-blocks")
    app = App(cfg)
    srv = serve(app, block=False)
    base = f"http://127.0.0.1:{port}"

    def push() -> None:
        t0 = int((time.time() - 3) * 1e9)
        payload = encode_spans_otlp([dict(
            trace_id=bytes([i + 1]) * 16, span_id=bytes([i + 1]) * 8,
            name="op", service="svc", kind=2, status_code=0,
            start_unix_nano=t0, end_unix_nano=t0 + 10**6,
            res_attrs={"service.name": "svc"}) for i in range(4)])
        req = urllib.request.Request(
            f"{base}/v1/traces", data=payload,
            headers={"Content-Type": "application/x-protobuf"})
        key = ("api.push", "clear")
        before = tracing.span_rows().get(key, [0])[0]
        urllib.request.urlopen(req, timeout=60).close()
        # `api.push` closes after its response is written: wait for the
        # server thread to record it before anything reads the rows
        deadline = time.monotonic() + 30
        while tracing.span_rows().get(key, [0])[0] == before \
                and time.monotonic() < deadline:
            time.sleep(0.001)
        sched.flush()

    def metrics() -> str:
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            return r.read().decode()

    try:
        assert not tracing.tracer().exports
        yield push, metrics
    finally:
        srv.shutdown()
        app.shutdown()


REQUEST_TREE = {"api.push", "distributor.admit", "distributor.decode",
                "distributor.PushSpans", "ingester.push",
                "distributor.GeneratorTee", "distributor.turn",
                "generator.Push", "spanmetrics.push", "generator.resolve",
                "servicegraphs.push", "servicegraphs.expire",
                "localblocks.push"}
DISPATCH_TREE = {"sched.dispatch", "sched.h2d", "sched.enqueue"}


def test_one_served_push_yields_the_expected_spans(tmp_path):
    """One OTLP push through the served App, nothing configured: every
    layer of the write path shows once on /metrics, and the self times
    sum to the durations of the roots (the request and the one coalesced
    dispatch it caused on the scheduler's thread)."""
    with _served_push(tmp_path) as (push, metrics):
        tracing.reset_span_rows()
        push()
        got = _span_samples(metrics())
    counts = got["tempo_span_duration_seconds_count"]
    assert counts == {(n, "clear"): 1.0
                      for n in REQUEST_TREE | DISPATCH_TREE}
    assert got["tempo_span_self_seconds_count"] == counts
    dur = got["tempo_span_duration_seconds_sum"]
    self_s = got["tempo_span_self_seconds_sum"]
    roots = dur[("api.push", "clear")] + dur[("sched.dispatch", "clear")]
    assert abs(sum(self_s.values()) - roots) <= 1e-6 * len(counts)
    # and per tree
    assert abs(sum(self_s[(n, "clear")] for n in REQUEST_TREE)
               - dur[("api.push", "clear")]) <= 1e-6 * len(REQUEST_TREE)


def test_cpu_family_and_the_process_counter_are_on_metrics(tmp_path):
    """The CPU family has the two roots of a served push (the request on
    its thread, the dispatch on the scheduler's) and no span under them,
    each counted once; a root has no more CPU than duration (two clocks:
    the CPU clock's step may part them); and `process_cpu_seconds_total`
    is there, upstream's name, and does not fall between two scrapes."""
    with _served_push(tmp_path) as (push, metrics):
        tracing.reset_span_rows()
        push()
        first = metrics()
        second = metrics()
    got = _span_samples(first, WALL_FAMILIES + (CPU_FAMILY,))
    counts = got["tempo_span_duration_seconds_count"]
    assert set(counts) == {(n, "clear")
                           for n in REQUEST_TREE | DISPATCH_TREE}
    assert got["tempo_span_self_seconds_count"] == counts
    assert got[CPU_FAMILY + "_count"] == {("api.push", "clear"): 1.0,
                                          ("sched.dispatch", "clear"): 1.0}
    dur = got["tempo_span_duration_seconds_sum"]
    slack = _cpu_step_ns() / 1e9 + 2e-5
    for key, cpu in got[CPU_FAMILY + "_sum"].items():
        assert 0.0 <= cpu <= dur[key] * 1.01 + slack
    assert "tempo_span_self_cpu_seconds" not in parse_exposition(first)
    # the CPU family keeps no buckets: `+Inf` alone
    buckets = [dict(labels)["le"] for (name, labels) in parse_exposition(
        first)[CPU_FAMILY]["samples"] if name.endswith("_bucket")]
    assert set(buckets) == {"+Inf"}
    fams = [parse_exposition(t)["process_cpu_seconds_total"]
            for t in (first, second)]
    assert fams[0]["type"] == "counter"
    a, b = (f["samples"][("process_cpu_seconds_total", ())] for f in fams)
    assert 0.0 < a <= b
    # every span under the rows is one of the frozen names, as ever
    assert len(SPAN_NAMES) == 40
    assert {n for n, _ in counts} <= SPAN_NAMES


def test_span_label_values_are_a_frozen_list():
    """Every name handed to span()/span_for_tenant() in the program is a
    literal (or a value of the one fixed table) and is in SPAN_NAMES."""
    from tempo_tpu.generator.instance import _PUSH_SPANS

    call = re.compile(r"\bspan(?:_for_tenant)?\(\s*([^,)\s]+)")
    found = set(_PUSH_SPANS.values())
    for path in glob.glob(os.path.join(REPO, "tempo_tpu", "**", "*.py"),
                          recursive=True):
        if path.endswith(os.path.join("utils", "tracing.py")):
            continue
        with open(path) as f:
            src = f.read()
        for m in call.finditer(src):
            arg = m.group(1)
            if arg == "_PUSH_SPANS[name]":
                continue
            assert arg[0] == arg[-1] == '"', \
                f"{path}: span name {arg} is not a literal"
            found.add(arg.strip('"'))
    assert found == SPAN_NAMES


# -- the jit names the benchmark's layer files match ----------------------

def _module_name(lowered) -> str:
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


def _layer_prefixes() -> set:
    out = set()
    for path in glob.glob(os.path.join(REPO, "chipbench", "layers",
                                       "*.json")):
        with open(path) as f:
            reader = json.load(f)["reader"]
        if "module" in reader:
            out.add(reader["module"])
    return out


def test_fused_update_keeps_its_jit_name():
    from tempo_tpu.generator.processors import spanmetrics as sm
    from tempo_tpu.registry import ManagedRegistry

    p = sm.SpanMetricsProcessor(ManagedRegistry("t"))
    name = _module_name(sm._fused_update_packed4._jit.lower(
        p.calls.state, p.latency.state, p.sizes.state, p.dd, p.mom,
        np.zeros((4, 64), np.float32)))
    assert name == "jit__fused_update_packed4_impl"
    # the one-device roofline's prefix, and not the mesh cell's
    assert {pre for pre in _layer_prefixes() if name.startswith(pre)} == {
        "jit__fused_update"}


def test_mesh_fused_update_keeps_its_jit_name():
    """The serving mesh's step (`jit(shard_map(...))`) is found in a
    profile by the name of the function handed to `shard_map`: inside
    the mesh cell's roofline prefix, and inside no other layer file's
    but the one-device roofline's `jit__fused_update` (which reads the
    one-device cell only, where this module never runs)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tempo_tpu.parallel.mesh import make_mesh, sharded_serving_step

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = make_mesh(4, series_shards=4)
    s1 = NamedSharding(mesh, P("series"))
    s2 = NamedSharding(mesh, P("series", None))
    f32 = np.float32
    vec = jax.ShapeDtypeStruct((64,), f32, sharding=s1)
    step = sharded_serving_step(mesh, (0.1, 1.0), 1.02, 1e-9, 64, 64,
                                packed=True)
    name = _module_name(step._jit.lower(
        vec, jax.ShapeDtypeStruct((64, 3), f32, sharding=s2), vec, vec, vec,
        jax.ShapeDtypeStruct((64, 8), f32, sharding=s2), vec,
        jax.ShapeDtypeStruct((4, 64), f32,
                             sharding=NamedSharding(mesh, P(None, "data")))))
    assert name == "jit__fused_update_mesh_impl"
    with open(os.path.join(REPO, "chipbench", "layers",
                           "fused_update_roofline_pct.mesh4.json")) as f:
        assert name.startswith(json.load(f)["reader"]["module"])
    assert {pre for pre in _layer_prefixes() if name.startswith(pre)} == {
        "jit__fused_update_mesh", "jit__fused_update"}


def test_paged_fused_update_keeps_its_jit_name():
    """The page pool's one-device step: inside the many-tenant cell's
    roofline prefix and the one-device roofline's `jit__fused_update`
    (the same work by the same `costs.fused_update_bytes`; that one reads
    the dense cell only, where this module never runs), and not inside
    the mesh cell's."""
    from tempo_tpu.ops import pages as op

    f32 = np.float32
    row, table = np.zeros(128, f32), np.zeros(2, np.int32)
    arenas = [row, row, row, row, np.zeros((128, 3), f32), row,
              np.zeros((128, 8), f32)]
    step = op.fused_step((0.1, 1.0), 1.02, 1e-9, 64, 6, True)
    name = _module_name(step._jit.lower(*arenas, *[table] * 7,
                                        np.zeros((4, 64), f32)))
    assert name == "jit__fused_update_paged_impl"
    with open(os.path.join(REPO, "chipbench", "layers",
                           "fused_update_roofline_pct.tenants.json")) as f:
        assert name.startswith(json.load(f)["reader"]["module"])
    assert {pre for pre in _layer_prefixes() if name.startswith(pre)} == {
        "jit__fused_update_paged", "jit__fused_update"}


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_edge_update_stays_outside_the_fused_update_match(layout):
    """The service-graph step is a module of its own in a profile on
    either layout: no layer file's prefix (`jit__fused_update` above all,
    the spanmetrics kernel's roofline) may pick it up but its own
    roofline's (`edge_update_roofline_pct.hotrod`, which reads the dense
    cell only, where the paged step never runs)."""
    from tempo_tpu.generator.processors import servicegraphs as sg
    from tempo_tpu.ops import pages as op
    from tempo_tpu.registry import ManagedRegistry

    packed = np.zeros((4, 16), np.float32)
    if layout == "dense":
        p = sg.ServiceGraphsProcessor(ManagedRegistry("t"))
        name = _module_name(sg._edge_update._jit.lower(
            tuple(f.state for f in p._families), packed))
    else:
        f32 = np.float32
        row, table = np.zeros(128, f32), np.zeros(2, np.int32)
        hist = [np.zeros((128, 3), f32), row, row]
        step = op.edge_step((0.1, 1.0), 6, False)
        name = _module_name(step._jit.lower(
            row, row, *hist, *hist, *[table] * 8, packed))
    assert name == {"dense": "jit__edge_update_impl",
                    "paged": "jit__edge_update_paged_impl"}[layout]
    assert "jit__fused_update" in _layer_prefixes()
    assert {pre for pre in _layer_prefixes() if name.startswith(pre)} == {
        "jit__edge_update"}


def test_search_mask_keeps_its_jit_name():
    from tempo_tpu.block.device_scan import _block_mask_kernel

    name = _module_name(_block_mask_kernel(64, (), (), True)._jit.lower(
        np.zeros(1, np.int32)))
    # what PERF.md's search breakdown finds the mask kernel by; no layer
    # file reads it yet
    assert name == "jit_fn"


def test_plane_grid_keeps_its_jit_name():
    from tempo_tpu.block.device_scan import BlockScanPlane
    from tempo_tpu.traceql.engine_metrics import (MetricsEvaluator,
                                                  QueryRangeRequest)
    from tempo_tpu.traceql.memview import view_from_traces

    t0 = 1_700_000_000
    traces = []
    for t in range(64):
        tid = bytes([t + 1]) * 16
        start = int((t0 + t) * 1e9)
        traces.append((tid, [
            {"trace_id": tid, "span_id": bytes([t + 1, i]) * 4,
             "name": f"op-{i}", "service": f"svc-{t % 4}",
             "res_attrs": {"service.name": f"svc-{t % 4}"},
             "start_unix_nano": start, "end_unix_nano": start + 10**6}
            for i in range(2)]))
    plane = BlockScanPlane([view_from_traces(traces)])
    req = QueryRangeRequest(
        query="{ } | rate() by (resource.service.name)",
        start_ns=int(t0 * 1e9), end_ns=int((t0 + 60) * 1e9),
        step_ns=int(10e9))
    ev = MetricsEvaluator(req, None, None, batched=True)
    preds = [c for c in ev.fetch_req.conditions if c.op is not None]

    def grid():
        handle, cause = plane.metrics_grid(
            ev.m, preds, ev.fetch_req.all_conditions, req.start_ns,
            req.end_ns, req.step_ns)
        assert cause is None
        return handle.fetch()

    grid()
    (key, fn), = plane._qr_cache.items()
    calls = []
    plane._qr_cache[key] = lambda *a: (calls.append(a), fn(*a))[1]
    grid()
    name = _module_name(fn._jit.lower(*calls[0]))
    assert name == "jit_build"
    assert name in _layer_prefixes()
