"""Per-tenant generator instance: processors + registry + remote write.

The analog of `modules/generator/instance.go`: `push_batch` fans a span batch
to the enabled processors (`pushSpans` `instance.go:398-415`), processor
enable/disable diffing follows per-tenant overrides
(`instance.go:207-385`), and a collection tick drains the registry to the
remote-write client (`registry.go:206` + `storage/instance.go`).
Ingestion-slack filtering (`instance.go:442-473`) drops spans whose end time
is too far outside [now - slack, now + slack].
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

from tempo_tpu.generator.processors.servicegraphs import (
    ServiceGraphsConfig,
    ServiceGraphsProcessor,
)
from tempo_tpu.generator.processors.spanmetrics import (
    SpanMetricsConfig,
    SpanMetricsProcessor,
)
from tempo_tpu.generator.processors.traceanalytics import (
    TraceAnalyticsConfig,
    TraceAnalyticsProcessor,
)
from tempo_tpu.generator.remote_write import RemoteWriteClient, RemoteWriteConfig
from tempo_tpu.model.span_batch import SpanBatch
from tempo_tpu.registry import ManagedRegistry, RegistryOverrides
from tempo_tpu.utils import tracing


def _lb_config():
    # deferred: processors.localblocks re-enters this package's init
    from tempo_tpu.generator.processors.localblocks import LocalBlocksConfig
    return LocalBlocksConfig()


@dataclasses.dataclass
class GeneratorConfig:
    processors: tuple[str, ...] = ("span-metrics", "service-graphs")
    registry: RegistryOverrides = dataclasses.field(default_factory=RegistryOverrides)
    spanmetrics: SpanMetricsConfig = dataclasses.field(default_factory=SpanMetricsConfig)
    servicegraphs: ServiceGraphsConfig = dataclasses.field(default_factory=ServiceGraphsConfig)
    traceanalytics: TraceAnalyticsConfig = dataclasses.field(
        default_factory=TraceAnalyticsConfig)
    remote_write: RemoteWriteConfig = dataclasses.field(default_factory=RemoteWriteConfig)
    localblocks: "LocalBlocksConfig" = dataclasses.field(
        default_factory=_lb_config)
    localblocks_flush_writer: "object" = None  # RawWriter for flush_to_storage
    ingestion_time_range_slack_s: float = 30.0


# one span a processor and push, named from a fixed set: the keys are
# the processor names `update_processors` accepts
_PUSH_SPANS = {"span-metrics": "spanmetrics.push",
               "service-graphs": "servicegraphs.push",
               "local-blocks": "localblocks.push",
               "trace-analytics": "traceanalytics.push"}


class GeneratorInstance:
    def __init__(self, tenant: str, cfg: GeneratorConfig | None = None,
                 now=time.time):
        self.tenant = tenant
        self.cfg = cfg or GeneratorConfig()
        self.now = now
        self.registry = ManagedRegistry(tenant, self.cfg.registry, now=now)
        self.remote_write = RemoteWriteClient(self.cfg.remote_write)
        self.processors: dict[str, object] = {}
        self._lock = threading.Lock()
        self.update_processors(self.cfg.processors)
        self.spans_received = 0
        self.spans_filtered_slack = 0
        self._last_purge = 0.0
        # ingest-WAL bookkeeping (generator/wal.py): `wal_watermarks`
        # maps member instance_id -> [segment, seq] of the last WAL
        # record covered by restored checkpoints — carried FORWARD
        # through checkpoint handoffs so a member that restores its own
        # state back never replays records an earlier checkpoint already
        # holds. `_wal_mark` (set by Generator when the WAL is enabled)
        # reads this member's live watermark at snapshot time.
        self.wal_watermarks: dict[str, list] = {}
        self._wal_mark = None
        self.checkpointed_wal_seq: "int | None" = None
        # idempotent RPC push dedupe: push-id -> span count of recently
        # acked pushes. A client retrying a push whose RESPONSE was lost
        # (timeout, owner kill) must not double-scatter; WAL replay
        # re-seeds this so the window survives a crash-restart.
        self._push_ids: "dict[str, int]" = {}
        # in-flight push tracking (fleet handoff barrier): a checkpoint
        # cut must not race an acked-but-still-scattering push
        self._pushes_inflight = 0
        self._push_cv = threading.Condition()
        # set under _push_cv by Generator.pop_instance: handler threads
        # that resolved this instance but have not yet registered
        # in-flight must re-resolve instead of scattering into a fenced
        # snapshot
        self.detached = False
        # resolver for this tenant's CURRENT overrides (set by
        # Generator.instance); the materializer fingerprints it to
        # expire/rebuild grids when the tenant's limits change
        self._matview_limits: "object | None" = None

    def drain(self) -> None:
        """The collection/snapshot barrier: flush the device scheduler
        and every processor's ingest pipeline so all updates accepted
        before this call are IN device state. Shared by the collection
        tick, the fleet checkpoint cut, and the verification surfaces —
        a drift between them silently breaks snapshot consistency."""
        from tempo_tpu import sched
        sched.flush()
        # list(): an overrides reload may run update_processors while a
        # collection tick or checkpoint cut drains
        for proc in list(self.processors.values()):
            fn = getattr(proc, "drain_pipeline", None)
            if fn is not None:
                fn()

    def try_track(self) -> bool:
        """Register an in-flight push/collect unless this instance is
        detached (fleet handoff fence). A True return must be paired
        with `untrack()`."""
        with self._push_cv:
            if self.detached:
                return False
            self._pushes_inflight += 1
        return True

    def untrack(self) -> None:
        with self._push_cv:
            self._pushes_inflight -= 1
            self._push_cv.notify_all()

    def seen_push(self, push_id: str):
        """Recently seen push id state: an int span count (acked AND
        durable), a ("pending", count) tuple (scattered, WAL append not
        yet confirmed — a retry redoes only the append), or None."""
        with self._lock:
            return self._push_ids.get(push_id)

    def note_push(self, push_id: str, result) -> None:
        with self._lock:
            self._push_ids[push_id] = result
            while len(self._push_ids) > 512:   # bounded: FIFO eviction
                self._push_ids.pop(next(iter(self._push_ids)))

    def wait_pushes_idle(self, timeout_s: float = 5.0) -> bool:
        """Block until no push is mid-flight (bounded); the fleet
        handoff fence between popping this instance and snapshotting."""
        deadline = time.monotonic() + timeout_s
        with self._push_cv:
            while self._pushes_inflight > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._push_cv.wait(left)
        return True

    # -- processor lifecycle (instance.go:207-385) -------------------------

    def update_processors(self, desired: tuple[str, ...]) -> None:
        with self._lock:
            for name in list(self.processors):
                if name not in desired:
                    del self.processors[name]
            for name in desired:
                if name in self.processors:
                    continue
                if name == "span-metrics":
                    self.processors[name] = SpanMetricsProcessor(
                        self.registry, self.cfg.spanmetrics)
                elif name == "service-graphs":
                    self.processors[name] = ServiceGraphsProcessor(
                        self.registry, self.cfg.servicegraphs)
                elif name == "trace-analytics":
                    self.processors[name] = TraceAnalyticsProcessor(
                        self.registry, self.cfg.traceanalytics)
                elif name == "local-blocks":
                    from tempo_tpu.generator.processors.localblocks import (
                        LocalBlocksProcessor)
                    self.processors[name] = LocalBlocksProcessor(
                        self.tenant, self.cfg.localblocks,
                        flush_writer=self.cfg.localblocks_flush_writer,
                        now=self.now)
                else:
                    raise ValueError(f"unknown processor {name}")

    # -- ingest ------------------------------------------------------------

    def needs_attr_columns(self) -> tuple[bool, bool]:
        """(span_attrs, res_attrs) the enabled processors actually read —
        staging skips unrequested attr matrices AND the C++ scan skips
        interning them. Each processor answers for itself; ones without
        the hook (service-graphs peer attrs, local-blocks persistence)
        conservatively need everything."""
        need_span = need_res = False
        for proc in self.processors.values():
            fn = getattr(proc, "needs_attr_columns", None)
            s, r = fn() if fn is not None else (True, True)
            need_span |= s
            need_res |= r
        return need_span, need_res

    def _fast_spanmetrics(self) -> "SpanMetricsProcessor | None":
        """The single eligible spanmetrics processor for the staged fast
        routes, or None when full SpanBatch staging is required. A
        tenant with materialized query grids (tempo_tpu.matview) always
        takes the SpanBatch route: the matview appender evaluates
        TraceQL over the batch columns, which the StageRec fast path
        never materializes."""
        from tempo_tpu import matview
        mv = matview.materializer()
        if mv is not None and mv.wants(self.tenant):
            return None
        procs = list(self.processors.values())
        if len(procs) != 1 or not isinstance(procs[0], SpanMetricsProcessor):
            return None
        return procs[0] if procs[0].supports_staged_fast_path() else None

    def _slack_bounds(self, now_s: "float | None" = None
                      ) -> tuple[int, int]:
        # now_s: WAL replay passes the ORIGINAL push wall time so the
        # slack filter drops exactly the spans the live push dropped —
        # replay at boot must be bit-identical to the uninterrupted run
        slack = self.cfg.ingestion_time_range_slack_s
        if slack <= 0:
            return 0, 0
        now_ns = int((self.now() if now_s is None else now_s) * 1e9)
        return now_ns - int(slack * 1e9), now_ns + int(slack * 1e9)

    def push_otlp_recs(self, raw: bytes, recs) -> int | None:
        """In-process tee fast route: distributor scan records + original
        payload → fused resolve → device. Returns span count or None when
        ineligible (caller falls back to the payload-bytes path)."""
        proc = self._fast_spanmetrics()
        if proc is None:
            return None
        lo, hi = self._slack_bounds()
        got = proc.push_from_recs(raw, recs, lo, hi)
        if got is None:
            return None
        self.spans_received += len(recs)
        self.spans_filtered_slack += got[1]
        return len(recs)

    def push_staged_view(self, view, now_s: "float | None" = None
                         ) -> int | None:
        """Decode-once tee consumption: a row view over the distributor's
        shared staging. The dedicated-spanmetrics fast route feeds the
        StageRec rows straight to the fused resolve (no SpanBatch); every
        other processor mix rides the staged SpanBatch columns
        (`batch_slice` — a gather for sharded views, the SHARED batch for
        full ones). None only on interner mismatch (the staging was not
        built for this tenant's registry).

        Views from an overload-sampled push carry Horvitz-Thompson
        weights (`view.weights()`): spanmetrics upscales its rates with
        them so the sampled stream reports true-stream rates and bounded
        quantiles (span-multiplier semantics compose multiplicatively)."""
        st = view.staged
        if st.interner is not self.registry.interner:
            return None
        w = view.weights()
        proc = self._fast_spanmetrics()
        if proc is not None and not st.needs_service_fixup:
            spans = view.stage_rows()
            lo, hi = self._slack_bounds(now_s)
            _n_valid, n_filtered = proc.push_staged(spans, lo, hi, weights=w)
            self.spans_received += len(spans)
            self.spans_filtered_slack += n_filtered
            return len(spans)
        sb, sizes = view.batch_slice()
        self.push_batch(sb, span_sizes=sizes, sample_weights=w,
                        now_s=now_s)
        return view.n

    def push_otlp_staged(self, data: bytes, trusted: bool = False
                         ) -> int | None:
        """Dedicated-spanmetrics fast route: OTLP bytes → C++ stage →
        fused resolve → device, with no SpanBatch materialization.
        Returns the span count, or None when this instance isn't eligible
        (caller takes the full staging path). Eligibility is checked
        BEFORE any row-table mutation so a fallback never leaves pending
        entries behind."""
        from tempo_tpu import native

        proc = self._fast_spanmetrics()
        if proc is None:
            return None
        nat = getattr(self.registry.interner, "native_handle", lambda: None)()
        if nat is None:
            return None
        staged = native.otlp_stage(nat, data, skip_span_attrs=True,
                                   trust_attrs=trusted)
        if staged is None:
            return None
        spans, _sattrs, rattrs, _res = staged
        # non-string service.name values need the Python stringify fixup
        # (_batch_from_staged); bail to the full path for those payloads
        svc_key = self.registry.interner.intern("service.name")
        hits = rattrs["key_id"] == svc_key
        if hits.any() and (rattrs["typ"][hits] != 1).any():
            return None
        lo, hi = self._slack_bounds()
        n_valid, n_filtered = proc.push_staged(spans, lo, hi)
        self.spans_received += len(spans)
        self.spans_filtered_slack += n_filtered
        return len(spans)

    def push_batch(self, sb: SpanBatch, span_sizes: np.ndarray | None = None,
                   sample_weights: np.ndarray | None = None,
                   now_s: "float | None" = None) -> None:
        self.spans_received += sb.n
        sb = self._apply_slack(sb, now_s)
        # materialized query grids see the batch BEFORE the processor
        # fan: a grid (re)build backfills from local-blocks state, so
        # the backfill must not already contain this batch (the append
        # below would then double-count it)
        from tempo_tpu import matview
        mv = matview.materializer()
        if mv is not None and mv.wants(self.tenant):
            mv.observe_batch(self.tenant, sb,
                             lb=self.processors.get("local-blocks"),
                             limits_fn=self._matview_limits)
        for name, proc in self.processors.items():
            with tracing.span(_PUSH_SPANS[name]):
                if isinstance(proc, SpanMetricsProcessor):
                    proc.push_batch(sb, span_sizes,
                                    sample_weights=sample_weights)
                elif isinstance(proc, TraceAnalyticsProcessor):
                    proc.push_batch(sb, sample_weights=sample_weights)
                else:
                    proc.push_batch(sb)

    def _apply_slack(self, sb: SpanBatch,
                     now_s: "float | None" = None) -> SpanBatch:
        slack = self.cfg.ingestion_time_range_slack_s
        if slack <= 0:
            return sb
        lo, hi = self._slack_bounds(now_s)
        keep = (sb.end_unix_nano >= lo) & (sb.end_unix_nano <= hi)
        dropped = int((sb.valid & ~keep).sum())
        if dropped:
            self.spans_filtered_slack += dropped
            sb = dataclasses.replace(sb, valid=sb.valid & keep)
        return sb

    # -- collection tick ---------------------------------------------------

    def collect_and_push(self, ts_ms: int | None = None) -> int:
        """One collection: purge stale series, gather device state, remote
        write. Returns number of scalar samples pushed."""
        # drain first: updates accepted before this tick must land in
        # the collected state, and a stale-series purge must never zero
        # a slot that still has a queued batch targeting it (slot reuse
        # would misroute the update to a new series). The staging
        # pipeline reaps its buffer ring behind the same barrier, so
        # collected state is bit-identical to synchronous mode.
        with tracing.span_for_tenant("generator.collect", self.tenant):
            with tracing.span("generator.drain"):
                self.drain()
            if self.now() - self._last_purge > 60.0:
                with tracing.span("registry.purge"):
                    self.registry.purge_stale()
                self._last_purge = self.now()
            columns = self.registry.collect_columns(ts_ms)
            native = (self.registry.native_histograms(ts_ms)
                      if self.cfg.remote_write.send_native_histograms
                      else [])
            self.remote_write.send(columns, native)
            return sum(cols.n_series for cols in columns)

    # -- accounting --------------------------------------------------------

    @property
    def state_layout(self) -> str:
        return "paged" if self.registry.pages is not None else "dense"

    def device_state_bytes(self) -> int:
        """Device bytes this tenant's metric state holds: registry
        families plus processor-owned sketch sidecars. Dense tenants
        report their full pre-sized planes; paged tenants only the pages
        they actually backed — the /status + tempo_registry_state_bytes
        surface that makes the paging win visible without a heap dump."""
        total = self.registry.device_state_bytes()
        for proc in self.processors.values():
            fn = getattr(proc, "device_state_bytes", None)
            if fn is not None:
                total += fn()
        return total

    # -- maintenance -------------------------------------------------------

    def tick(self, immediate: bool = False) -> None:
        """Background maintenance: localblocks cut/complete/flush pass
        and the trace-analytics idle-trace cut."""
        lb = self.processors.get("local-blocks")
        if lb is not None:
            lb.cut_tick(immediate=immediate)
        ta = self.processors.get("trace-analytics")
        if ta is not None:
            ta.cut_tick(immediate=immediate)

    # -- reads (recent-data query entry points) ----------------------------

    def query_range(self, req, clip_start_ns: int | None = None):
        """TraceQL metrics over this tenant's local blocks (`QueryRange`
        `instance.go:487-556`). Raises if local-blocks isn't enabled, like
        the reference's errors when the processor is absent."""
        lb = self.processors.get("local-blocks")
        if lb is None:
            raise RuntimeError("local-blocks processor not enabled")
        return lb.query_range(req, clip_start_ns=clip_start_ns)

    def get_metrics(self, query: str, group_by, max_series: int = 1000):
        """Span-metrics summary (`GetMetrics` `instance.go:475`)."""
        lb = self.processors.get("local-blocks")
        if lb is None:
            raise RuntimeError("local-blocks processor not enabled")
        return lb.get_metrics(query, group_by, max_series=max_series)
