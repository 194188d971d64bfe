"""Live-trace accumulation with size/count limits and idle cutting.

Analog of `pkg/livetraces/livetraces.go:23-120` (used by the ingester
instance, generator localblocks, and blockbuilder): spans group per trace id
in memory; traces are "cut" (emitted for WAL append) once idle longer than
`idle_s`, older than `max_age_s`, or immediately on demand. Per-trace byte
and global count limits guard memory, mirroring the push error reasons of
`modules/ingester/instance.go:199-228` (`PushErrorReason`).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterable

from tempo_tpu.obs.jaxruntime import RUNTIME

ERR_LIVE_TRACES_EXCEEDED = "live_traces_exceeded"
ERR_TRACE_TOO_LARGE = "trace_too_large"

LIVE_SPANS = RUNTIME.counter(
    "tempo_ingester_live_spans_total",
    "Spans appended to live traces (the ingester's and the local-blocks "
    "processor's stores), by the form the store keeps them in: columns = "
    "a row slice of a staged push's columns; dicts = span dicts",
    labels=("form",))


@dataclasses.dataclass
class LiveTrace:
    """`segments` in arrival order: a list of span dicts (consecutive
    dict pushes share one), or a column slice of a staged push, an object
    with `to_span_dicts()` (`block.live_columns.ColumnSegment`)."""
    trace_id: bytes
    segments: list = dataclasses.field(default_factory=list)
    bytes: int = 0
    first_append: float = 0.0
    last_append: float = 0.0

    @property
    def spans(self) -> list[dict]:
        """Every span as a dict; column segments convert on demand."""
        return segment_spans(self.segments)

    def snapshot(self) -> list:
        """The segments as they stand (take it under the store's lock:
        a later dict push extends the last list), for `segment_spans`
        once the lock is released."""
        return [list(seg) if isinstance(seg, list) else seg
                for seg in self.segments]


def segment_spans(segments: Iterable) -> list[dict]:
    """A live trace's segments as span dicts, in arrival order."""
    out: list[dict] = []
    for seg in segments:
        out.extend(seg if isinstance(seg, list) else seg.to_span_dicts())
    return out


class LiveTraceStore:
    def __init__(self, max_live_traces: int = 0, max_trace_bytes: int = 0,
                 now: Callable[[], float] = time.time):
        self.max_live_traces = max_live_traces  # 0 = unlimited
        self.max_trace_bytes = max_trace_bytes
        self.now = now
        self.traces: dict[bytes, LiveTrace] = {}
        self.total_bytes = 0
        self.pushes_rejected: dict[str, int] = {}

    def __len__(self) -> int:
        return len(self.traces)

    def push(self, trace_id: bytes, spans: Iterable[dict],
             size_bytes: int | None = None) -> str | None:
        """Append spans to a live trace. Returns an error reason or None."""
        spans = list(spans)
        sz = size_bytes if size_bytes is not None else _approx_size(spans)
        lt = self._admit(trace_id, sz)
        if isinstance(lt, str):
            return lt
        if lt.segments and isinstance(lt.segments[-1], list):
            lt.segments[-1].extend(spans)
        else:
            lt.segments.append(spans)
        LIVE_SPANS.inc(len(spans), ("dicts",))
        return None

    def push_columns(self, trace_id: bytes, segment,
                     size_bytes: int) -> str | None:
        """Append a column slice of a staged push (anything with `len()`
        and `to_span_dicts()`) under the limits `push` enforces. The
        caller counts the spans into `LIVE_SPANS` (one increment a push,
        not one a trace)."""
        lt = self._admit(trace_id, size_bytes)
        if isinstance(lt, str):
            return lt
        lt.segments.append(segment)
        return None

    def _admit(self, trace_id: bytes, sz: int) -> "LiveTrace | str":
        """The live trace `sz` more bytes go to, its bookkeeping done, or
        the reason they may not."""
        lt = self.traces.get(trace_id)
        # Both limit checks run before any store mutation, so a rejected
        # first push leaves no empty LiveTrace behind.
        if self.max_trace_bytes and (lt.bytes if lt else 0) + sz > self.max_trace_bytes:
            self.pushes_rejected[ERR_TRACE_TOO_LARGE] = (
                self.pushes_rejected.get(ERR_TRACE_TOO_LARGE, 0) + 1)
            return ERR_TRACE_TOO_LARGE
        if lt is None:
            if self.max_live_traces and len(self.traces) >= self.max_live_traces:
                self.pushes_rejected[ERR_LIVE_TRACES_EXCEEDED] = (
                    self.pushes_rejected.get(ERR_LIVE_TRACES_EXCEEDED, 0) + 1)
                return ERR_LIVE_TRACES_EXCEEDED
            lt = self.traces[trace_id] = LiveTrace(
                trace_id, first_append=self.now())
        lt.bytes += sz
        lt.last_append = self.now()
        self.total_bytes += sz
        return lt

    def cut(self, idle_s: float = 0.0, max_age_s: float = 0.0,
            immediate: bool = False) -> list[LiveTrace]:
        """Remove and return traces idle > idle_s or older than max_age_s
        (`CutCompleteTraces` `instance.go:237`); immediate cuts everything."""
        now = self.now()
        out = []
        for tid in list(self.traces):
            lt = self.traces[tid]
            if (immediate
                    or (idle_s and now - lt.last_append >= idle_s)
                    or (max_age_s and now - lt.first_append >= max_age_s)):
                out.append(self.traces.pop(tid))
                self.total_bytes -= lt.bytes
        return out


def _approx_size(spans: list[dict]) -> int:
    # cheap stand-in for proto size: span count * nominal span bytes + attrs
    return sum(200 + 32 * (len(s.get("attrs") or {}) + len(s.get("res_attrs") or {}))
               for s in spans)
