"""Spans: the one way this program marks a layer boundary.

`span(name, **attrs)` / `span_for_tenant(...)` do two things.

**Always, with nothing to turn on:**

- the span enters `jax.profiler.TraceAnnotation(name)`. With no profiler
  session that is a no-op inside the runtime; under a session the span
  lands in the `/host:CPU` plane of the `.xplane.pb`, on the clock of the
  device's `XLA Ops`, so an idle gap of the device is named by the layer
  the host was in. The profiler's own start is the switch;
- the span keeps its self time (its duration less the part its child
  spans cover; a child is a span opened under it ON THE SAME THREAD) and
  adds both, at close, to `tempo_span_duration_seconds{span,collect}` and
  `tempo_span_self_seconds{span,collect}` on `/metrics`. `span` is the
  span's name, from the fixed set in the code; attributes never become
  labels. `collect` is `met` when a generator collection tick ran at the
  span's start or end or began in between (`collecting()`), else `clear`:
  the collector holds the interpreter for seconds, so the `clear` rows
  are a path's own cost and the `met` rows are the stall;
- a span with no parent on its own thread (the root of a thread's tree:
  `api.push` on a request thread, `sched.dispatch` on the scheduler's,
  `ingester.cut`, `generator.collect`, `generator.tick`) reads a CPU
  clock too (`_cpu_clock`: user + system time of THIS thread), outside
  the wall clock's pair, and adds its CPU time to the same row as
  `tempo_span_cpu_seconds{span,collect}`, whose `_count` counts those
  root spans. A span under a same-thread parent reads no CPU clock: the
  read is a system call (5.7 us on the chip's sealed host, two a span
  were a twentieth of a cell's rate) and a thread's roots already sum
  to all the CPU its spans cover. Under one interpreter lock a wall
  clock reads the neighbours too; the two clocks split a root span into
  WORK = CPU: the span's Python plus the native code it runs on its own
  thread, with or without the interpreter lock (numpy kernels, snappy,
  the calling thread's part of `pq.write_table`), and WAIT = duration -
  CPU: queued for the interpreter lock, for another lock, for the disk,
  a socket or the device. Work that OTHER threads do for the span
  (Arrow's pool, XLA's) is on nobody's span: it is in
  `process_cpu_seconds_total` (registered here too) and in no
  `tempo_span_cpu_seconds` row. Where the host's thread clock is a
  coarse ticker (10 ms steps on the chip's sealed host) a span's CPU is
  right only as a mean over many spans, a span of milliseconds can read
  more CPU than duration, and WAIT can come out negative: trust the
  second-scale spans and the process counter there;
- this part takes no lock another thread takes and draws no random
  bytes: every thread adds to rows of its own, summed at the scrape.

**Configured (`selftrace.enabled` / `selftrace.endpoint`), `SelfTracer`:**
trace and span ids, W3C traceparent propagation, and OTLP export, with
two properties the reference gets from the OTel SDK + collector pair
(`cmd/tempo/main.go:227-281`):

- **Tail-keep.** Spans buffer per trace until the trace's last local
  span closes; the whole tree is then either kept (exported) or dropped
  by a deterministic head-sample coin on the trace id — EXCEPT that
  errored and explicitly `mark_keep()`-ed traces (SLO misses) are always
  kept. Sampling a trace id (not each span) keeps trees intact across
  threads and processes: every hop coins the same verdict.
- **Loopback.** Instead of an OTLP/HTTP endpoint, a `sink` callable can
  deliver encoded batches straight into this process's own distributor
  under a reserved ops tenant. Recursion is guarded twice: the sink runs
  with span creation suppressed, and `span_for_tenant()` suppresses the
  whole ingest call-tree for the reserved tenant (a remote fleet member
  ingesting a peer's self-spans must not trace that ingestion either).

Process-wide state: the installed tracer, the collect mark and the span
rows, all reset between tests by `tests/conftest.py`.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import contextvars
import dataclasses
import os
import random
import threading
import time
import urllib.request
from typing import Callable

from jax.profiler import TraceAnnotation

from tempo_tpu.obs.jaxruntime import RUNTIME

_current_span = contextvars.ContextVar("tempo_self_span", default=None)
# recursion guard: True while this process is ingesting its own export
# (loopback sink call, or any span_for_tenant() block for the reserved
# tenant). span() is a no-op under it.
_suppress = contextvars.ContextVar("tempo_self_suppress", default=False)

# bound on the forced-keep mark set and the keep-decision LRU; late spans
# (async sched jobs finishing after root close) look their verdict up here
_DECISION_LRU = 4096


@dataclasses.dataclass
class SelfTraceConfig:
    """The `selftrace:` config block (runbook "Tracing Tempo with
    Tempo"). `enabled` routes export into this process's OWN distributor
    under the reserved `tenant`; `endpoint` routes to an external OTLP
    host instead (mutually exclusive — loopback wins)."""

    enabled: bool = False
    endpoint: str = ""
    tenant: str = "tempo-self"
    head_sample_rate: float = 1.0
    flush_interval_s: float = 2.0
    max_buffer: int = 4096        # spans ready to export
    max_trace_spans: int = 256    # tail buffer: spans held per open trace
    max_open_traces: int = 1024   # tail buffer: concurrently open traces

    def check(self) -> list[str]:
        problems = []
        if not (0.0 <= self.head_sample_rate <= 1.0):
            problems.append(f"head_sample_rate {self.head_sample_rate} "
                            "outside [0, 1]")
        if self.flush_interval_s <= 0:
            problems.append("flush_interval_s must be > 0")
        if self.max_buffer < 1 or self.max_trace_spans < 2 \
                or self.max_open_traces < 1:
            problems.append("max_buffer/max_trace_spans/max_open_traces "
                            "must be positive (max_trace_spans >= 2)")
        if self.enabled and not self.tenant:
            problems.append("enabled requires a reserved tenant name")
        if self.enabled and self.endpoint:
            problems.append("both enabled (loopback) and endpoint set: "
                            "loopback wins, endpoint is ignored")
        return ["selftrace: " + p for p in problems] if problems else []


# -- the always-on part ------------------------------------------------------

_clock = time.perf_counter_ns          # tests inject another
_cpu_clock = time.thread_time_ns       # user + system of THIS thread

# The collect mark: [collection ticks running now, ticks begun so far].
# Written by the collector under `_collect_lock`; a span reads the pair at
# its start and at its end and takes no lock.
_collect = [0, 0]
_collect_lock = threading.Lock()


@contextlib.contextmanager
def collecting():
    """Mark a generator collection tick (`Generator.collect_all`): spans
    that overlap it carry `collect="met"`."""
    with _collect_lock:
        _collect[0] += 1
        _collect[1] += 1
    try:
        yield
    finally:
        with _collect_lock:
            _collect[0] -= 1


# Span rows: {thread ident: {span name: (clear row, met row)}}, a row
# being [count, duration ns, self ns, duration buckets, self buckets,
# CPU ns, spans that read the CPU clock].
# A thread writes only under its own ident (the OS hands a dead thread's
# ident to a new one, which then carries its rows on: the table is as
# large as the most threads alive at once), so no row has two writers.
_rows: dict[int, dict[str, tuple[list, list]]] = {}
_BUCKETS_S = (1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0, 10.0, 100.0)
_EDGES_NS = tuple(int(e * 1e9) for e in _BUCKETS_S)


def _new_row() -> list:
    n = len(_EDGES_NS) + 1
    return [0, 0, 0, [0] * n, [0] * n, 0, 0]


def span_rows() -> dict[tuple[str, str], list]:
    """{(span, collect): [count, duration ns, self ns, duration buckets,
    self buckets, CPU ns, spans that read the CPU clock]} summed over the
    threads: what `/metrics` renders."""
    out: dict[tuple[str, str], list] = {}
    for per_thread in list(_rows.values()):
        for name, pair in list(per_thread.items()):
            for label, row in zip(("clear", "met"), pair):
                if not row[0]:
                    continue
                agg = out.setdefault((name, label), _new_row())
                agg[0] += row[0]
                agg[1] += row[1]
                agg[2] += row[2]
                agg[5] += row[5]
                agg[6] += row[6]
                for i, c in enumerate(row[3]):
                    agg[3][i] += c
                for i, c in enumerate(row[4]):
                    agg[4][i] += c
    return out


def reset_span_rows() -> None:
    """Forget every span seen so far (tests)."""
    _rows.clear()


def _family(total_at: int, buckets_at: "int | None" = None,
            count_at: int = 0):
    """A family's rows off the span rows, those it counted nothing in
    left out; with no buckets kept for it (the CPU family) it renders
    `+Inf` alone."""
    def rows():
        return [(key, () if buckets_at is None else row[buckets_at],
                 row[total_at] / 1e9, row[count_at])
                for key, row in span_rows().items() if row[count_at]]
    return rows


RUNTIME.histogram_func(
    "tempo_span_duration_seconds", _family(1, 3),
    help="Host wall time of the program's own spans (tracing.span), by "
         "span name; collect=met when a generator collection tick "
         "overlapped the span, else clear",
    labels=("span", "collect"), buckets=_BUCKETS_S)
RUNTIME.histogram_func(
    "tempo_span_self_seconds", _family(2, 4),
    help="Self time of the program's own spans: duration less the part "
         "covered by child spans opened on the same thread; the self "
         "times of a tree sum to its root's duration",
    labels=("span", "collect"), buckets=_BUCKETS_S)
RUNTIME.histogram_func(
    "tempo_span_cpu_seconds", _family(5, count_at=6),
    help="CPU seconds (user + system) of the span's thread between the "
         "span's start and end, for spans with no parent on their thread "
         "(the others read no CPU clock): its work; mean duration less "
         "mean CPU is its wait (the interpreter lock, another lock, disk, "
         "socket, device), which a coarse host clock can turn negative "
         "for spans of milliseconds",
    labels=("span", "collect"))
RUNTIME.counter_func(
    "process_cpu_seconds_total", lambda: [((), time.process_time())],
    help="Total user and system CPU time spent in seconds.")


class _Span:
    """One span, and its own context manager. The fields up to
    `status_code` are the export part's and stay empty without one."""

    __slots__ = ("trace_id", "span_id", "parent_span_id", "name",
                 "start_ns", "end_ns", "attrs", "status_code",
                 "_tracer", "_parent", "_token", "_thread", "_ann",
                 "_t0", "_child_ns", "_c0", "_met", "_epoch")

    def __init__(self, tracer: "Tracer | None", name: str,
                 attrs: dict) -> None:
        self.trace_id = self.span_id = self.parent_span_id = b""
        self.name = name
        self.start_ns = self.end_ns = 0
        self.attrs = attrs
        self.status_code = 0
        self._tracer = tracer
        self._thread = None       # a remote parent is on no thread here
        self._child_ns = 0

    def __enter__(self) -> "_Span":
        parent = self._parent = _current_span.get()
        self._token = _current_span.set(self)
        if self._tracer.exports:
            self._tracer._begin(self, parent)
        self._thread = ident = threading.get_ident()
        self._met, self._epoch = _collect[0] > 0, _collect[1]
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        # the CPU clock is a system call: read at the root of a thread's
        # tree alone, and outside the wall clock's pair
        self._c0 = _cpu_clock() \
            if parent is None or parent._thread != ident else None
        self._t0 = _clock()
        return self

    def __exit__(self, etype, exc, tb) -> None:
        dur = _clock() - self._t0
        c0 = self._c0
        cpu = None if c0 is None else _cpu_clock() - c0
        self._ann.__exit__(etype, exc, tb)
        _current_span.reset(self._token)
        ident = self._thread
        parent = self._parent
        if parent is not None and parent._thread == ident:
            # a span closed on another thread than its parent ran beside
            # it, not inside it: the parent's self time keeps that part
            parent._child_ns += dur
        per_thread = _rows.get(ident)
        if per_thread is None:
            per_thread = _rows[ident] = {}
        pair = per_thread.get(self.name)
        if pair is None:
            pair = per_thread[self.name] = (_new_row(), _new_row())
        row = pair[self._met or _collect[0] > 0
                   or _collect[1] != self._epoch]
        self_ns = dur - self._child_ns
        row[0] += 1
        row[1] += dur
        row[2] += self_ns
        row[3][bisect.bisect_left(_EDGES_NS, dur)] += 1
        row[4][bisect.bisect_left(_EDGES_NS, self_ns)] += 1
        if cpu is not None:
            row[5] += cpu
            row[6] += 1
        if isinstance(exc, Exception):
            self.status_code = 2
            self.attrs["error.message"] = str(exc)[:200]
        if self._tracer.exports:
            self._tracer._record(self)


class _NoSpan:
    """What `span()` hands out while span creation is suppressed."""

    def __enter__(self) -> None:
        return None

    def __exit__(self, etype, exc, tb) -> None:
        return None


_NO_SPAN = _NoSpan()


class Tracer:
    """The installed tracer with no exporter (the default): spans feed
    the profiler and `/metrics` and go nowhere else."""

    exports = False
    exported = 0
    loopback = False
    tenant = None
    stats: dict = {}

    def span(self, name: str, **attrs):
        if _suppress.get():
            return _NO_SPAN          # ingesting our own export: no spans
        return _Span(self, name, attrs)

    def traceparent(self) -> "str | None":
        return None

    def adopt(self, traceparent):
        return None

    def mark_keep(self) -> None:
        pass

    def trace_kept(self) -> "str | None":
        return None

    def tail_buffered(self) -> int:
        return 0

    def status(self) -> "dict | None":
        return None

    def flush(self) -> int:
        return 0

    def shutdown(self) -> None:
        pass


class SelfTracer(Tracer):
    """The export part: ids, per-trace tail buffer, bounded export
    buffer, batch export thread. Spans export as OTLP (the codec this
    framework already speaks) so any OTLP endpoint — including this
    process (loopback) — can ingest its own traces."""

    exports = True

    def __init__(self, endpoint: str = "", *,
                 service_name: str = "tempo-tpu",
                 tenant: str = "tempo-self", flush_interval_s: float = 2.0,
                 max_buffer: int = 4096, head_sample_rate: float = 1.0,
                 max_trace_spans: int = 256, max_open_traces: int = 1024,
                 sink: Callable[[bytes], None] | None = None,
                 resource_attrs: dict | None = None,
                 now: Callable[[], float] = time.time) -> None:
        self.endpoint = endpoint.rstrip("/")
        self.service_name = service_name
        self.tenant = tenant
        self.sink = sink
        self.now = now
        self.max_buffer = max_buffer
        self.head_sample_rate = head_sample_rate
        self.max_trace_spans = max_trace_spans
        self.max_open_traces = max_open_traces
        self.resource_attrs = dict(resource_attrs or {})
        self._buf: list[_Span] = []          # decided-keep, export-ready
        self._traces: dict[bytes, list[_Span]] = {}   # tail buffer
        self._open: dict[bytes, int] = {}    # open local spans per trace
        self._keep: set[bytes] = set()       # forced-keep marks (undecided)
        self._decided: "collections.OrderedDict[bytes, bool]" = \
            collections.OrderedDict()        # keep-verdict LRU
        self._retry: list[_Span] = []        # one failed batch, held once
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.exported = 0
        # the tempo_selftrace_*_total families (app._init_app_obs)
        self.stats = {"spans": 0, "kept_traces": 0, "dropped_spans": 0,
                      "sampled_spans": 0, "export_retries": 0,
                      "loopback_batches": 0}
        self._thread = threading.Thread(
            target=self._loop, args=(flush_interval_s,), daemon=True)
        self._thread.start()

    @property
    def loopback(self) -> bool:
        return self.sink is not None

    # -- span API ----------------------------------------------------------

    def _begin(self, s: _Span, parent: "_Span | None") -> None:
        """Ids, wall-clock start and the open count of a span entering."""
        if parent is not None and parent.trace_id:
            s.trace_id, s.parent_span_id = parent.trace_id, parent.span_id
        else:
            s.trace_id = os.urandom(16)
        s.span_id = os.urandom(8)
        s.start_ns = int(self.now() * 1e9)
        with self._lock:
            self._open[s.trace_id] = self._open.get(s.trace_id, 0) + 1

    def mark_keep(self) -> None:
        """Force the current trace past head sampling (SLO miss, error):
        its whole tree exports even at head_sample_rate 0."""
        s = _current_span.get()
        if s is None:
            return
        with self._lock:
            self._mark_keep_locked(s.trace_id)

    def _mark_keep_locked(self, tid: bytes) -> None:
        if tid in self._decided:
            self._decided[tid] = True       # flip for late spans
        else:
            if len(self._keep) >= _DECISION_LRU:
                self._keep.pop()
            self._keep.add(tid)

    def trace_kept(self) -> str | None:
        """Hex trace id of the current trace IF its tree will be (or was)
        kept, else None — the qlog `selfTraceId` bridge. Deterministic
        head sampling makes the verdict knowable before root close."""
        s = _current_span.get()
        if s is None:
            return None
        tid = s.trace_id
        with self._lock:
            verdict = self._decided.get(tid)
            if verdict is None:
                verdict = tid in self._keep or self._head_keep(tid)
        return tid.hex() if verdict else None

    def _head_keep(self, tid: bytes) -> bool:
        if self.head_sample_rate >= 1.0:
            return True
        # deterministic per-trace coin: every hop of a distributed tree
        # (other threads, other processes) coins the same verdict
        return int.from_bytes(tid[:8], "big") \
            < int(self.head_sample_rate * 2.0 ** 64)

    # -- tail buffer -------------------------------------------------------

    def _record(self, s: _Span) -> None:
        s.end_ns = int(self.now() * 1e9)
        tid = s.trace_id
        with self._lock:
            self.stats["spans"] += 1
            if s.status_code == 2:
                self._mark_keep_locked(tid)
            open_n = self._open.get(tid, 0) - 1
            if open_n > 0:
                self._open[tid] = open_n
            else:
                self._open.pop(tid, None)
            verdict = self._decided.get(tid)
            if verdict is not None:
                # late span: trace already finalized (root closed before
                # an async job span, or evicted) — follow its verdict
                self._decided.move_to_end(tid)
                if verdict or s.status_code == 2:
                    self._decided[tid] = True
                    self._enqueue_locked([s])
                else:
                    self.stats["sampled_spans"] += 1
                return
            buf = self._traces.setdefault(tid, [])
            if len(buf) >= self.max_trace_spans:
                self.stats["dropped_spans"] += 1
            else:
                buf.append(s)
            if open_n <= 0:
                self._finalize_locked(tid)
            elif len(self._traces) > self.max_open_traces:
                # bound: force-decide the oldest open trace; its later
                # spans follow the cached verdict individually
                self._finalize_locked(next(iter(self._traces)))

    def _finalize_locked(self, tid: bytes) -> None:
        spans = self._traces.pop(tid, [])
        keep = tid in self._keep or self._head_keep(tid)
        self._keep.discard(tid)
        self._decided[tid] = keep
        while len(self._decided) > _DECISION_LRU:
            self._decided.popitem(last=False)
        if keep:
            self.stats["kept_traces"] += 1
            self._enqueue_locked(spans)
        else:
            self.stats["sampled_spans"] += len(spans)

    def _enqueue_locked(self, spans: list[_Span]) -> None:
        room = self.max_buffer - len(self._buf)
        if room < len(spans):
            self.stats["dropped_spans"] += len(spans) - max(0, room)
            spans = spans[:max(0, room)]
        self._buf.extend(spans)

    def tail_buffered(self) -> int:
        """Spans held in per-trace tail buffers (undecided traces) — the
        tempo_selftrace_tail_buffer_spans gauge."""
        with self._lock:
            return sum(len(v) for v in self._traces.values())

    def traceparent(self) -> str | None:
        """W3C traceparent for outgoing RPCs (`main.go:252-258`)."""
        s = _current_span.get()
        if s is None or not s.trace_id:
            return None
        return f"00-{s.trace_id.hex()}-{s.span_id.hex()}-01"

    def adopt(self, traceparent: str | None):
        """Continue an incoming W3C trace context; returns a context
        manager token holder or None when the header is absent/bad."""
        if not traceparent:
            return None
        parts = traceparent.split("-")
        if len(parts) < 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
            return None
        try:
            tid, sid = bytes.fromhex(parts[1]), bytes.fromhex(parts[2])
        except ValueError:
            return None      # W3C: invalid traceparent values are ignored
        remote = _Span(None, "remote-parent", {})
        remote.trace_id, remote.span_id = tid, sid
        return _current_span.set(remote)

    # -- export ------------------------------------------------------------

    def _drain(self) -> tuple[list[_Span], bool]:
        with self._lock:
            spans, retrying = self._retry + self._buf, bool(self._retry)
            self._retry, self._buf = [], []
        return spans, retrying

    def flush(self) -> int:
        """Export buffered spans now; returns how many went out. A failed
        export holds the batch for exactly ONE retry on the next flush
        tick (export_retries) before counting it into dropped."""
        spans, retrying = self._drain()
        if not spans:
            return 0
        from tempo_tpu.model.otlp import encode_spans_otlp

        res_attrs = {"service.name": self.service_name}
        res_attrs.update(self.resource_attrs)
        payload = encode_spans_otlp([{
            "trace_id": s.trace_id, "span_id": s.span_id,
            "parent_span_id": s.parent_span_id, "name": s.name,
            "service": self.service_name, "kind": 1,   # INTERNAL
            "status_code": s.status_code,
            "start_unix_nano": s.start_ns, "end_unix_nano": s.end_ns,
            "attrs": {k: v for k, v in s.attrs.items()},
            "res_attrs": res_attrs,
        } for s in spans])
        try:
            if self.sink is not None:
                # loopback: deliver into this process's own distributor.
                # Suppress span creation for the whole sink call — the
                # recursion guard's first line of defense (span_for_tenant
                # guards the remote-ingest half).
                token = _suppress.set(True)
                try:
                    self.sink(payload)
                finally:
                    _suppress.reset(token)
                with self._lock:
                    self.stats["loopback_batches"] += 1
            else:
                req = urllib.request.Request(
                    self.endpoint + "/v1/traces", data=payload,
                    headers={"Content-Type": "application/x-protobuf",
                             "X-Scope-OrgID": self.tenant})
                urllib.request.urlopen(req, timeout=5).close()
            self.exported += len(spans)
            return len(spans)
        except Exception:
            # self-tracing must never hurt the service — but the loss must
            # be visible: hold the batch once, then drop it where the
            # check_metrics_drift-gated alerting watches for span loss
            with self._lock:
                if retrying:
                    self.stats["dropped_spans"] += len(spans)
                else:
                    self._retry = spans
                    self.stats["export_retries"] += 1
            return 0

    def _loop(self, interval_s: float) -> None:
        # jittered: N fleet members must not export in lockstep
        while not self._stop.wait(interval_s * (0.5 + random.random())):
            self.flush()

    def status(self) -> dict:
        """/status block: export health at a glance."""
        with self._lock:
            stats = dict(self.stats)
            tail = sum(len(v) for v in self._traces.values())
        return {"tenant": self.tenant, "loopback": self.loopback,
                "endpoint": self.endpoint or None,
                "headSampleRate": self.head_sample_rate,
                "exported": self.exported, "tailBufferSpans": tail,
                **{k: v for k, v in stats.items()}}

    def shutdown(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2)
        self.flush()
        self.flush()        # second pass drains a held retry batch


_tracer: Tracer = Tracer()


def install(tracer: Tracer) -> None:
    global _tracer
    _tracer = tracer


def tracer() -> Tracer:
    return _tracer


def span(name: str, **attrs):
    """Module-level convenience: `with tracing.span("distributor.push"):`"""
    return _tracer.span(name, **attrs)


def mark_keep() -> None:
    """Force the current trace past head sampling (SLO miss / error)."""
    _tracer.mark_keep()


def kept_trace_id_hex() -> "str | None":
    """Hex id of the current trace if its tree will be kept, else None —
    stamped into qlog "query complete" lines as `selfTraceId`."""
    return _tracer.trace_kept()


def current_trace_id_hex() -> "str | None":
    """Trace id of the active span (local or adopted remote context), or
    None outside any span — the metrics-side exemplar bridge: slow
    requests stamp this onto their histogram observation."""
    s = _current_span.get()
    return s.trace_id.hex() if s is not None and s.trace_id else None


def reserved_tenant() -> "str | None":
    """The loopback ops tenant, when self-ingest is active — excluded
    from fleet handoff, matview auto-subscribe, and public push APIs."""
    t = _tracer
    return t.tenant if t.loopback else None


def is_reserved(tenant: str) -> bool:
    rt = reserved_tenant()
    return rt is not None and tenant == rt


def suppressed() -> bool:
    """True while span creation is suppressed (self-ingest in progress)."""
    return _suppress.get()


@contextlib.contextmanager
def suppress():
    """Suppress span creation for a block (self-ingest recursion guard)."""
    token = _suppress.set(True)
    try:
        yield None
    finally:
        _suppress.reset(token)


def span_for_tenant(name: str, tenant: str, **attrs):
    """Like span(), but for the self-tracing tenant it SUPPRESSES tracing
    for the whole block: in loopback mode (exporting into this very
    process, or into a fleet peer that forwards back) tracing the
    ingestion of our own spans would emit new spans per flush, forever.
    Plain nullcontext would only skip THIS span; nested wal.append /
    sched.dispatch spans under the ingest call-tree must go quiet too."""
    if _tracer.tenant == tenant:
        return suppress()
    return _tracer.span(name, tenant=tenant, **attrs)


@contextlib.contextmanager
def adopted(traceparent: str | None):
    """Continue an incoming W3C trace context for the duration of a
    request handler; resets cleanly afterwards (receiver-side half of
    `main.go:252-258` propagation)."""
    token = _tracer.adopt(traceparent)
    try:
        yield
    finally:
        if token is not None:
            _current_span.reset(token)


__all__ = ["Tracer", "SelfTracer", "SelfTraceConfig", "install",
           "tracer", "span", "span_for_tenant", "adopted", "mark_keep",
           "collecting", "span_rows", "reset_span_rows",
           "kept_trace_id_hex", "current_trace_id_hex", "reserved_tenant",
           "is_reserved", "suppress", "suppressed"]
