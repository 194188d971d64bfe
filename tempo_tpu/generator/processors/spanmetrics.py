"""spanmetrics processor: OTel-standard RED metrics from span batches.

Reference semantics (`modules/generator/processor/spanmetrics/spanmetrics.go`):

- metric families (`spanmetrics.go:27-31`): `traces_spanmetrics_calls_total`,
  `traces_spanmetrics_latency` (histogram, seconds),
  `traces_spanmetrics_size_total` (bytes), `traces_target_info` (gauge 1).
- intrinsic dimensions service / span_name / span_kind / status_code
  (+ status_message opt), custom dimensions from span+resource attrs
  (`aggregateMetricsForSpan` `spanmetrics.go:158-268`).
- filter policies include/exclude, span multiplier, exemplars = trace ids.

TPU re-architecture: the per-span label-build loop becomes (1) one
vectorized host staging pass that assembles the interned label-id row matrix
[N, L] and resolves series slots, then (2) ONE fused jitted device step that
scatter-updates calls counter + latency histogram + size counter together
(they share slots). Latency histograms additionally feed a DDSketch row per
series for <1%-error quantiles (the sketch plane the reference lacks).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import numpy as np

from tempo_tpu.model.interner import INVALID_ID
from tempo_tpu.model.span_batch import SpanBatch
from tempo_tpu.ops import moments, sketches
from tempo_tpu.registry import metrics as rm
from tempo_tpu.registry.registry import (DEFAULT_HISTOGRAM_EDGES,
                                         ManagedRegistry, _pad_len)
from tempo_tpu.utils import tracing
from tempo_tpu.utils.spanfilter import FilterPolicy, compile_policies

import logging

_TIER_LOG = logging.getLogger("tempo_tpu.spanmetrics")

_KIND_STRS = ("SPAN_KIND_UNSPECIFIED", "SPAN_KIND_INTERNAL", "SPAN_KIND_SERVER",
              "SPAN_KIND_CLIENT", "SPAN_KIND_PRODUCER", "SPAN_KIND_CONSUMER")
_STATUS_STRS = ("STATUS_CODE_UNSET", "STATUS_CODE_OK", "STATUS_CODE_ERROR")


@dataclasses.dataclass
class SpanMetricsConfig:
    """Subset of `modules/generator/processor/spanmetrics/config.go`."""

    histogram_buckets: tuple[float, ...] = DEFAULT_HISTOGRAM_EDGES
    intrinsic_dimensions: tuple[str, ...] = ("service", "span_name", "span_kind",
                                             "status_code")
    dimensions: tuple[str, ...] = ()          # extra span/resource attr keys
    enable_target_info: bool = False
    filter_policies: tuple[FilterPolicy, ...] = ()
    span_multiplier_key: str = ""             # attr holding a weight multiplier
    enable_quantile_sketch: bool = True       # quantile sidecar per series
    # quantile sketch tier: "dd" (the ~1100-bucket DDSketch plane,
    # ≤1% relative error), "moments" (the ~15-float moments sketch of
    # ops/moments.py — ~90x smaller state, psum-only combine, ≤5%-class
    # quantiles via the maxent solver), or "both" (moments answers,
    # DDSketch kept as the solver's per-series fallback). Per-tenant via
    # the overrides `generator.sketch` knob.
    sketch: str = "dd"
    moments_k: int = 12                       # moment count (2..16)
    sketch_rel_err: float = 0.01              # DDSketch relative-error budget
    sketch_min_s: float = 1e-6                # 1µs .. ~28h latency range
    sketch_max_s: float = 1e5
    sketch_max_series: int = 16384            # HBM bound for the sketch plane
    subprocessors: tuple[str, ...] = ("count", "latency", "size")
    # route fused updates through the process device scheduler
    # (tempo_tpu.sched): many small pushes coalesce into one padded
    # pow-2 dispatch. The synchronous direct path below is preserved
    # bit-identically and taken whenever this is off or no scheduler is
    # configured.
    use_scheduler: bool = True


def _fused_update_impl(calls, latency, sizes, dd, mom, slots, dur_s,
                       size_bytes, weights):
    """One device step for all spanmetrics families (slots shared).
    `dd` / `mom` are the optional quantile-sketch sidecars (the tier
    knob: dd, moments, or both); a None sidecar traces to exactly the
    pre-tier graph, keeping `sketch: dd` behavior bit-identical.

    The named scopes change no operation: they put the stage into each
    op's metadata (`op_name`), where a profile shows which plane a
    relayout or a scatter belongs to."""
    with jax.named_scope("spanmetrics.counters"):
        calls = rm.counter_update(calls, slots, weights)
    with jax.named_scope("spanmetrics.histogram"):
        latency = rm.histogram_update(latency, slots, dur_s, weights)
    with jax.named_scope("spanmetrics.counters"):
        sizes = rm.counter_update(sizes, slots, size_bytes * weights)
    if dd is not None:
        with jax.named_scope("spanmetrics.ddsketch"):
            keep = (slots >= 0) & (slots < dd.counts.shape[0])
            dd = sketches.dd_update(dd, jax.numpy.where(keep, slots, 0),
                                    dur_s, mask=keep, weights=weights)
    if mom is not None:
        with jax.named_scope("spanmetrics.moments"):
            mkeep = (slots >= 0) & (slots < mom.data.shape[0])
            mom = moments.moments_update(mom, slots, dur_s, mask=mkeep,
                                         weights=weights)
    return calls, latency, sizes, dd, mom


# donating jit of the fused step: without donation every push COPIES the
# full functional state (~90MB with the default DDSketch plane). Callers
# MUST hold the registry state_lock across call+rebind — donation deletes
# the input buffers at dispatch for any concurrent reader. The
# instrumented jit records compile count + seconds into the process-wide
# obs runtime registry (tempo_jax_jit_compile_* on /metrics).
from tempo_tpu.obs.jaxruntime import instrumented_jit

_fused_update_donated = instrumented_jit(
    _fused_update_impl, name="spanmetrics_fused_update",
    donate_argnums=(0, 1, 2, 3, 4))


def _fused_update_packed_impl(calls, latency, sizes, dd, mom, packed,
                              weights):
    """The fused step with (slots, dur_s, size_bytes) packed into ONE
    [3, cap] f32 H2D transfer (the staged fast paths): behind a
    high-latency device link the per-push transfer COUNT is the cost, not
    the bytes. Slots ride f32 exactly while the SERIES TABLE capacity is
    below 2^24 (the caller gates on that); weights are the cached device
    ones-vector, uploaded once. States are DONATED — a non-donating
    update copies the full state (the DDSketch plane alone is ~85MB at
    default capacity) every push; the caller holds the registry's
    state_lock across dispatch+rebind so the collection thread can never
    observe a donated-dead buffer."""
    slots = packed[0].astype(jax.numpy.int32)
    return _fused_update_impl(calls, latency, sizes, dd, mom, slots,
                              packed[1], packed[2], weights)


_fused_update_packed = instrumented_jit(
    _fused_update_packed_impl, name="spanmetrics_fused_update_packed",
    donate_argnums=(0, 1, 2, 3, 4))


def _fused_update_packed4_impl(calls, latency, sizes, dd, mom, packed):
    """The scheduler-coalesced form: the merged batch arrives as ONE
    [4, bucket] f32 matrix (slots, dur_s, size_bytes, weights) — one H2D
    per merged dispatch, the coalescer-side twin of the [3, cap] packed
    push path. Slots ride f32 exactly under the same capacity < 2^24
    gate; padding rows carry slot -1 and drop on device."""
    slots = packed[0].astype(jax.numpy.int32)
    return _fused_update_impl(calls, latency, sizes, dd, mom, slots,
                              packed[1], packed[2], packed[3])


_fused_update_packed4 = instrumented_jit(
    _fused_update_packed4_impl, name="spanmetrics_fused_update",
    donate_argnums=(0, 1, 2, 3, 4))

# the label every layout's batches carry in the scheduler's families
# (`tempo_sched_*{kernel=...}`): dashboards and the mesh cell's judge
# count batches under exactly this value
_SCHED_KERNEL = "spanmetrics_fused_update"


class SpanMetricsProcessor:
    def __init__(self, registry: ManagedRegistry, config: SpanMetricsConfig | None = None):
        self.cfg = config or SpanMetricsConfig()
        self.registry = registry
        dims = [d for d in self.cfg.intrinsic_dimensions] + [
            _sanitize(d) for d in self.cfg.dimensions]
        self._labels = tuple(dims)
        cap = registry.overrides.max_active_series
        self.calls = registry.new_counter("traces_spanmetrics_calls_total",
                                          self._labels)
        self.latency = registry.new_histogram(
            "traces_spanmetrics_latency", self._labels,
            edges=self.cfg.histogram_buckets)
        # size/ latency share the calls table so all three stay slot-aligned
        # (paged mode: the shared table's backing adopts their planes too).
        self.latency.share_table(self.calls)
        self.sizes = registry.new_counter("traces_spanmetrics_size_total", self._labels)
        self.sizes.share_table(self.calls)
        # paged layout (registry/pages.py): families above came back
        # paged; the sketch sidecars ride the same pool + shared backing
        self._pool = registry.pages
        self._paged = self._pool is not None and \
            hasattr(self.calls, "planes")
        self._pdd = None
        self._pmom = None
        self._paged_steps: dict[bool, object] = {}
        dd_rows = min(cap, self.cfg.sketch_max_series)
        # quantile sketch tier (ops/moments.py): which sidecar(s) the
        # latency stream feeds. Unknown names fall back to "dd" with a
        # warning (config.check() already surfaced the typo) so a bad
        # override can never silently drop the quantile surface.
        tier = self.cfg.sketch
        if tier not in ("dd", "moments", "both"):
            _TIER_LOG.warning(
                "spanmetrics %s: unknown sketch tier %r (use dd | moments "
                "| both) — falling back to dd", registry.tenant, tier)
            tier = "dd"
        self._tier = tier
        dd_on = self.cfg.enable_quantile_sketch and tier in ("dd", "both")
        mom_on = self.cfg.enable_quantile_sketch and \
            tier in ("moments", "both")
        if mom_on:
            mk = max(2, min(int(self.cfg.moments_k), 16))
            if mk != self.cfg.moments_k:
                _TIER_LOG.warning(
                    "spanmetrics %s: moments_k %d clamped to %d (supported "
                    "range 2..16)", registry.tenant, self.cfg.moments_k, mk)
            self._mom_meta = moments.moments_params(
                mk, self.cfg.sketch_min_s, self.cfg.sketch_max_s)
        else:
            self._mom_meta = None
        self.dd = None
        self.mom = None
        if self._paged and (dd_on or mom_on):
            from tempo_tpu.registry.pages import PagedPlane
            pr = self._pool.page_rows
            plane_rows = -(-dd_rows // pr) * pr  # page-aligned cover
            # back only the CONFIGURED sketch range: updates mask at
            # dd_rows exactly like the dense planes, so collect/quantile
            # stay bit-identical to the dense layout
            if dd_on:
                gamma, nb = sketches.dd_params(self.cfg.sketch_rel_err,
                                               self.cfg.sketch_min_s,
                                               self.cfg.sketch_max_s)
                ddc = PagedPlane(self._pool, "float32", nb, plane_rows,
                                 registry.tenant,
                                 role="traces_spanmetrics_latency/ddsketch")
                ddz = PagedPlane(self._pool, "float32", 1, plane_rows,
                                 registry.tenant,
                                 role="traces_spanmetrics_latency/ddzeros")
                self.calls.table.backing.add_plane(ddc, dd_rows)
                self.calls.table.backing.add_plane(ddz, dd_rows)
                self._pdd = (ddc, ddz, gamma, self.cfg.sketch_min_s, dd_rows)
            if mom_on:
                mk, mlo, mhi = self._mom_meta
                mp = PagedPlane(self._pool, "float32", moments.n_cols(mk),
                                plane_rows, registry.tenant,
                                role="traces_spanmetrics_latency/moments")
                self.calls.table.backing.add_plane(mp, dd_rows)
                self._pmom = (mp, mk, mlo, mhi, dd_rows)
        else:
            # Dense sidecar planes sized for HBM: DDSketch is
            # [min(series), ~1.1k buckets] f32; the moments plane is
            # [min(series), k+3] — the ~90x state shrink of the tier.
            if dd_on:
                self.dd = sketches.dd_init(dd_rows,
                                           rel_err=self.cfg.sketch_rel_err,
                                           min_value=self.cfg.sketch_min_s,
                                           max_value=self.cfg.sketch_max_s)
            if mom_on:
                mk, mlo, mhi = self._mom_meta
                self.mom = moments.MomentsSketch(
                    data=jax.numpy.zeros((dd_rows, moments.n_cols(mk)),
                                         jax.numpy.float32),
                    k=mk, lo=mlo, hi=mhi)
        if self._pdd is not None or self._pmom is not None or \
                self.dd is not None or self.mom is not None:
            # eviction must clear the sketch sidecar's rows along with
            # the family planes: a reused slot starting from another
            # series' latency history would corrupt its quantiles
            self.calls.evict_hooks.append(self._zero_sketch_slots)
        self.target_info = (registry.new_gauge("traces_target_info", ("service",))
                            if self.cfg.enable_target_info else None)
        self._policies = compile_policies(self.cfg.filter_policies)
        self.spans_discarded = 0
        self._dims_arr: np.ndarray | None = None   # staged-path caches
        self._kind_lut = self._status_lut = None
        # cap → DEVICE ones-vector (jax array), uploaded once per capacity
        self._ones_cache: dict[int, object] = {}
        # double-buffered staging ring (generator/pipeline.py), created
        # lazily when the scheduler route is live
        self._pipe = None
        # serving mesh (tempo_tpu.parallel.serving): resolved once at
        # first push; when active, this processor's state lives sharded
        # over 'series' as donated device buffers and fused updates go
        # through the single shard_map dispatch
        self._mesh = None
        self._mesh_checked = False

    def name(self) -> str:
        return "span-metrics"

    # -- device-scheduler route (tempo_tpu.sched) --------------------------

    def _sched(self):
        """The process scheduler when this processor's fused updates
        should ride it (config flag, default on), else None — callers
        then take the original synchronous dispatch unchanged."""
        if not self.cfg.use_scheduler:
            return None
        from tempo_tpu import sched as sched_mod
        sc = sched_mod.scheduler()
        return sc if sc is not None and sc.cfg.enabled else None

    # -- serving-mesh route (tempo_tpu.parallel.serving) -------------------

    def _serving_mesh(self):
        """The process serving mesh this processor's state lives on, or
        None (single-device dispatch). Resolved ONCE at first use: the
        placement rebinds live state onto 'series'-sharded buffers under
        the state_lock, and the processor stays on that mesh for its
        lifetime (reconfiguring the process mesh does not migrate
        already-placed tenants)."""
        if self._paged:
            # paged state composes with the mesh at the POOL level:
            # arenas shard page-aligned over 'series' and the paged fused
            # step is already mesh-aware — the dense placement path
            # (capacity-divisibility and all) does not apply
            return None
        if self._mesh_checked:
            return self._mesh
        from tempo_tpu.parallel import serving
        sm = serving.active()
        if sm is not None:
            with self.registry.state_lock:
                if not serving.place_spanmetrics_state(self, sm):
                    sm = None
        self._mesh = sm
        self._mesh_checked = True
        return sm

    def _mesh_fused_step(self, sm, packed: bool = False):
        dd = self.dd
        mom = self.mom
        return sm.serving_step(
            tuple(self.latency.state.edges),
            dd.gamma if dd is not None else sketches.dd_params(0.01)[0],
            dd.min_value if dd is not None else 1e-9,
            self.calls.table.capacity,
            dd.counts.shape[0] if dd is not None else 0,
            packed=packed,
            mom_rows=mom.data.shape[0] if mom is not None else 0,
            mom_meta=(mom.k, mom.lo, mom.hi) if mom is not None else None)

    def _mesh_step_rebind(self, sm, step, batch) -> None:
        """Run one sharded donating step over the live state and rebind
        — the mesh twin of the single-device state_lock discipline:
        donation deletes the old shards at dispatch for any concurrent
        reader, so the whole call+rebind sits under the lock."""
        with self.registry.state_lock:
            cs, hs, zs, dd, mom = (self.calls.state, self.latency.state,
                                   self.sizes.state, self.dd, self.mom)
            if getattr(cs.values, "sharding", None) != sm.series_1d:
                # a stale-series purge's eager zero_slots may have moved
                # the state off its mesh placement; re-place before the
                # donating sharded dispatch (rare — eviction cadence)
                from tempo_tpu.parallel import serving
                serving.place_spanmetrics_state(self, sm)
                cs, hs, zs, dd, mom = (self.calls.state, self.latency.state,
                                       self.sizes.state, self.dd, self.mom)
            args = [cs.values, hs.bucket_counts, hs.sums, hs.counts,
                    zs.values]
            if dd is not None:
                args += [dd.counts, dd.zeros]
            if mom is not None:
                args.append(mom.data)
            out = step(*args, *batch)
            i = 5
            if dd is not None:
                self.dd = sketches.DDSketch(out[5], out[6], dd.gamma,
                                            dd.min_value)
                i = 7
            if mom is not None:
                self.mom = dataclasses.replace(mom, data=out[i])
            self.calls.state = rm.CounterState(out[0])
            self.latency.state = rm.HistogramState(out[1], out[2], out[3],
                                                   hs.edges)
            self.sizes.state = rm.CounterState(out[4])

    def _mesh_update(self, sm, slots, dur_s, sizes, weights) -> None:
        """One fused update on the serving mesh: the whole padded batch
        rides ONE `shard_map` dispatch — span rows split over 'data',
        each 'series' shard scatter-updates only the slots it owns, and
        the state buffers (sharded, device-resident) are DONATED exactly
        like the single-device fast paths. Below the 2^24 capacity gate
        the batch ships as one packed [4, n] f32 matrix (single H2D,
        like the packed push paths); above it, per-role vectors."""
        n = len(slots)
        if self.calls.table.capacity < (1 << 24):
            mat = np.empty((4, n), np.float32)
            mat[0] = slots
            mat[1] = dur_s
            mat[2] = sizes
            mat[3] = weights
            self._mesh_dispatch_packed(sm, mat)
            return
        d = sm.data_shards
        if n % d:
            # batch must split evenly over 'data' (the sched coalescer
            # aligns its buckets; direct pushes are pow-2 padded already,
            # this covers odd hand-built batches)
            pad = d - n % d
            slots = np.concatenate([slots, np.full(pad, -1, np.int32)])
            dur_s = np.concatenate([dur_s, np.zeros(pad, np.float32)])
            sizes = np.concatenate([sizes, np.zeros(pad, np.float32)])
            weights = np.concatenate([weights, np.zeros(pad, np.float32)])
        step = self._mesh_fused_step(sm)
        self._mesh_note_rows(sm, slots)
        batch = sm.put_batch(
            np.ascontiguousarray(slots, np.int32),
            np.asarray(dur_s, np.float32), np.asarray(sizes, np.float32),
            np.asarray(weights, np.float32))
        self._mesh_step_rebind(sm, step, batch)

    def _mesh_note_rows(self, sm, slots: np.ndarray) -> None:
        """Tell the mesh which shards own this batch's rows
        (tempo_mesh_shard_rows_total)."""
        sk = self.dd.counts if self.dd is not None else \
            self.mom.data if self.mom is not None else None
        sm.note_rows(slots, self.calls.table.capacity,
                     sk.shape[0] if sk is not None else 0)

    def _mesh_place_packed(self, mat: np.ndarray):
        """The upload of one packed [4, bucket] f32 batch: the matrix
        placed on the mesh (columns over 'data', a copy a 'series'
        shard) and the bytes that cost the host link. The scheduler
        calls this inside `sched.h2d`, so the closure below receives a
        placed operand and `sched.enqueue` holds no transfer."""
        sm = self._mesh
        self._mesh_note_rows(sm, mat[0])
        return sm.put_packed(mat), sm.link_bytes(mat)

    def _mesh_dispatch_packed(self, sm, mat: np.ndarray) -> None:
        """Packed mesh dispatch off the scheduler (a direct push): ONE
        [4, bucket] f32 H2D (columns sharded over 'data'), one shard_map
        launch. Slot ids ride f32 exactly under the capacity < 2^24 gate
        the callers hold."""
        d = sm.data_shards
        if mat.shape[1] % d:
            pad = d - mat.shape[1] % d
            ext = np.zeros((4, pad), np.float32)
            ext[0] = -1.0
            mat = np.concatenate([mat, ext], axis=1)
        self._sched_dispatch_sharded_packed(self._mesh_place_packed(mat)[0])

    def _sched_dispatch_sharded(self, slots, dur_s, sizes, weights) -> None:
        """Merged-batch dispatch on the scheduler worker, serving-mesh
        form (capacity >= 2^24 — per-role vectors): the coalescer
        aligned the bucket to the 'data' shard count, so the whole
        window lands in one shard_map launch."""
        self._mesh_update(self._mesh, slots, dur_s, sizes, weights)

    def _sched_dispatch_sharded_packed(self, placed) -> None:
        """Packed-coalescer mesh dispatch: the merged window arrives as
        the coalescer's ONE [4, bucket] f32 matrix, already on the mesh
        (`_mesh_place_packed`, made by the scheduler inside `sched.h2d`)
        — one shard_map launch feeds every shard."""
        sm = self._mesh
        self._mesh_step_rebind(sm, self._mesh_fused_step(sm, packed=True),
                               (placed,))

    def _pipeline(self, sc):
        """The staging pipeline riding scheduler `sc`, or None when the
        decode/update overlap ring is off (no scheduler, or
        sched.pipeline_depth == 0 — every push then allocates fresh
        staging, the pre-pipeline behavior)."""
        if sc is None:
            return None
        depth = getattr(sc.cfg, "pipeline_depth", 0)
        if depth <= 0:
            return None
        if self._pipe is None or self._pipe.depth != depth:
            from tempo_tpu.generator.pipeline import IngestPipeline
            self._pipe = IngestPipeline(depth)
        return self._pipe

    def drain_pipeline(self, timeout_s: float = 30.0) -> None:
        """Reap the staging ring behind the sched.flush() barrier (the
        collection tick's drain-before-collect)."""
        if self._pipe is not None:
            self._pipe.drain(timeout_s)

    def _sched_dispatch(self, slots, dur_s, sizes, weights) -> None:
        """One merged-batch device step, on the scheduler worker: the
        same donating fused kernel + state-lock discipline as the direct
        paths. Padding/merged-away rows carry slot -1 and are dropped on
        device, so cross-push (and cross-tenant-window) concatenation is
        exact for the commutative sketch updates."""
        with self.registry.state_lock:
            (self.calls.state, self.latency.state, self.sizes.state,
             self.dd, self.mom) = _fused_update_donated(
                self.calls.state, self.latency.state, self.sizes.state,
                self.dd, self.mom, slots, dur_s, sizes, weights)

    def _sched_dispatch_packed(self, packed) -> None:
        """Packed-coalescer dispatch: the merged batch is one [4, bucket]
        f32 matrix — ONE H2D per dispatch behind a high-latency device
        link. Gated by the caller on capacity < 2^24 (slot ids exact in
        f32)."""
        with self.registry.state_lock:
            (self.calls.state, self.latency.state, self.sizes.state,
             self.dd, self.mom) = _fused_update_packed4(
                self.calls.state, self.latency.state, self.sizes.state,
                self.dd, self.mom, packed)

    # -- paged route (registry/pages.py + ops/pages.py) --------------------

    def _paged_step(self, packed: bool):
        """The paged fused step for this processor's static meta — cached
        process-wide in ops.pages, so every tenant with the same config
        shares ONE trace (page tables and arenas are operands). The
        resolved callable is memoized per processor: meta, pool, and
        mesh are all fixed for the processor's lifetime, and the key
        build (tuple + mesh fingerprint) is hot-path overhead."""
        step = self._paged_steps.get(packed)
        if step is None:
            step = self._paged_steps[packed] = self._build_paged_step(packed)
        return step

    def _build_paged_step(self, packed: bool):
        from tempo_tpu.ops import pages as op
        pool = self._pool
        dd_rows = self._pdd[4] if self._pdd is not None else 0
        gamma = self._pdd[2] if self._pdd is not None else 1.0202
        minv = self._pdd[3] if self._pdd is not None else 1e-9
        mom_rows = self._pmom[4] if self._pmom is not None else 0
        mom_meta = tuple(self._pmom[1:4]) if self._pmom is not None else None
        mesh = pool.mesh
        if mesh is None:
            mesh_key = jmesh = None
        else:
            # value identity, not shape: a re-configured mesh with the
            # same (devices, shards) shape but different device layout
            # must NOT hit the old mesh's cached shard_map step (the
            # id-reuse aliasing class mesh_fingerprint exists for)
            from tempo_tpu.parallel.mesh import mesh_fingerprint
            jmesh = mesh.registry_mesh
            mesh_key = mesh_fingerprint(jmesh)
        return op.fused_step(
            tuple(self.cfg.histogram_buckets), gamma, minv, dd_rows,
            pool.page_shift, packed,
            mesh_key=mesh_key, mesh=jmesh,
            series_shards=1 if mesh is None else mesh.series_shards,
            mom_rows=mom_rows, mom_meta=mom_meta)

    def _paged_update(self, slots, dur_s, sizes, weights) -> None:
        """One paged fused update: gather each row's physical page
        through the indirection tables, scatter into the pooled arenas
        (donated — the registry state lock IS the pool lock). Below the
        2^24 capacity gate the batch ships as one packed [4, n] f32
        matrix, mirroring the dense packed push paths."""
        if self.calls.table.capacity < (1 << 24):
            n = len(slots)
            mat = np.empty((4, n), np.float32)
            mat[0] = slots
            mat[1] = dur_s
            mat[2] = sizes
            mat[3] = weights
            self._paged_dispatch_packed4(mat)
            return
        self._paged_dispatch_vec(
            np.ascontiguousarray(slots, np.int32),
            np.asarray(dur_s, np.float32), np.asarray(sizes, np.float32),
            np.asarray(weights, np.float32))

    def _paged_planes(self):
        """Role-aligned plane tuple for the fused paged step: (calls,
        hist_sums, hist_counts, sizes, hist_buckets[, dd_zeros,
        dd_counts][, moments])."""
        lat = self.latency
        planes = (self.calls.values, lat.sums, lat.counts,
                  self.sizes.values, lat.buckets)
        if self._pdd is not None:
            planes += (self._pdd[1], self._pdd[0])
        if self._pmom is not None:
            planes += (self._pmom[0],)
        return planes

    def _paged_args(self):
        """(arenas, tables) operand tuples for the fused paged step.
        Caller holds the pool lock."""
        planes = self._paged_planes()
        return (tuple(p.data for p in planes),
                tuple(p.device_map() for p in planes))

    def _paged_rebind(self, out) -> None:
        for plane, new in zip(self._paged_planes(), out):
            plane.rebind(new)

    def _paged_dispatch_packed4(self, mat) -> None:
        """Packed dispatch (direct pushes AND the sched coalescer's
        merged [4, bucket] windows — the page table is an extra operand,
        not a new trace per tenant)."""
        step = self._paged_step(packed=True)
        with self.registry.state_lock:
            arenas, tables = self._paged_args()
            self._paged_rebind(step(*arenas, *tables, mat))

    def _paged_dispatch_vec(self, slots, dur_s, sizes, weights) -> None:
        """Per-role-vector dispatch (capacity >= 2^24: slot ids do not
        survive the f32 matrix)."""
        step = self._paged_step(packed=False)
        with self.registry.state_lock:
            arenas, tables = self._paged_args()
            self._paged_rebind(step(*arenas, *tables, slots, dur_s,
                                    sizes, weights))

    def _submit_rows(self, sc, slots: np.ndarray, dur_s: np.ndarray,
                     sizes: np.ndarray, weights: np.ndarray):
        # slot ids round-trip f32 exactly below 2^24: ride the packed
        # single-transfer dispatch (one [4, bucket] H2D per merged
        # window — same gate as the direct packed push path). On the
        # serving mesh the coalescer additionally aligns the bucket to
        # the 'data' shard count so ONE shard_map launch feeds every
        # device.
        sm = self._serving_mesh()
        packed = self.calls.table.capacity < (1 << 24)
        if self._paged:
            dispatch = self._paged_dispatch_packed4 if packed \
                else self._paged_dispatch_vec
        elif sm is not None:
            dispatch = self._sched_dispatch_sharded_packed if packed \
                else self._sched_dispatch_sharded
        else:
            dispatch = self._sched_dispatch_packed if packed \
                else self._sched_dispatch
        arrays = (np.asarray(slots, np.float32 if packed else np.int32),
                  np.asarray(dur_s, np.float32),
                  np.asarray(sizes, np.float32),
                  np.asarray(weights, np.float32))
        return sc.submit_rows(
            _SCHED_KERNEL, self, arrays, len(slots), dispatch,
            pads=(-1.0, 0.0, 0.0, 0.0) if packed else (-1, 0.0, 0.0, 0.0),
            tenant=self.registry.tenant, pack=packed,
            align=sm.data_shards if sm is not None else 1,
            shards=sm.data_shards if sm is not None else 0,
            place=self._mesh_place_packed
            if packed and sm is not None else None)

    def needs_attr_columns(self) -> tuple[bool, bool]:
        """(span_attrs, res_attrs) this processor reads — owned HERE so a
        future attr-reading feature updates the answer with the code that
        reads (staging skips unrequested matrices)."""
        c = self.cfg
        need = bool(c.dimensions or c.filter_policies
                    or c.span_multiplier_key)
        return need, need

    # -- fused staged fast path (dedicated-spanmetrics generators) ---------

    _DIM_CODES = {"service": 0, "span_name": 1, "span_kind": 2,
                  "status_code": 3}

    def supports_staged_fast_path(self) -> bool:
        """True when push can go StageRec → device directly: intrinsic
        dims only (the default config), no policies/multiplier/target_info
        — and the native row table is live. Anything else needs the full
        SpanBatch staging."""
        c = self.cfg
        return (not c.dimensions and not c.filter_policies
                and not c.span_multiplier_key and not c.enable_target_info
                and all(d in self._DIM_CODES for d in c.intrinsic_dimensions)
                and self.calls.table._nat is not None)

    def _staged_dims(self):
        if self._dims_arr is None:
            it = self.registry.interner
            self._kind_lut = np.asarray(it.intern_many(_KIND_STRS), np.int32)
            self._status_lut = np.asarray(it.intern_many(_STATUS_STRS),
                                          np.int32)
            # the mark goes last: a tenant's first pushes race here, and
            # a thread that finds it set takes all three as built (two
            # that both build them intern the same strings to the same ids)
            self._dims_arr = np.asarray(
                [self._DIM_CODES[d] for d in self.cfg.intrinsic_dimensions],
                np.int32)
        return self._dims_arr, self._kind_lut, self._status_lut

    def push_staged(self, spans: np.ndarray, slack_lo: int,
                    slack_hi: int,
                    weights: "np.ndarray | None" = None) -> tuple[int, int]:
        """One fused pass: staged StageRec[:n] → slots/durations/sizes in
        C++ (label build + rowtable resolve + slack filter + last_seen
        stamp) → ONE device scatter update. The Python cost per push is
        the native call, the (rare) new-series misses, and the jit
        dispatch — no SpanBatch, no numpy label stack, no second hash
        pass. Returns (n_valid, n_filtered)."""
        from tempo_tpu import native
        from tempo_tpu.model.span_batch import _pad_rows

        n = len(spans)
        cap = _pad_rows(max(n, 1))
        dims, klut, slut = self._staged_dims()
        now = self.registry.now()
        sc = self._sched()
        pipe = self._pipeline(sc)
        bufs = pipe.acquire(cap, len(dims)) if pipe is not None else None
        with tracing.span("generator.resolve", rows=n):
            got = native.spanmetrics_resolve(
                self.calls.table._nat, spans, dims, klut, slut,
                slack_lo, slack_hi, now, self.calls.table.last_seen, cap,
                out=bufs)
        return self._push_resolved(got, spans["trace_id"], n, now,
                                   sc=sc, pipe=pipe, bufs=bufs,
                                   weights=weights)

    def push_from_recs(self, raw: bytes, recs: np.ndarray, slack_lo: int,
                       slack_hi: int) -> "tuple[int, int] | None":
        """The in-process tee route: the distributor's otlp_scan records +
        the ORIGINAL payload bytes go straight to slots — no second
        protobuf walk, no payload re-encode for ring-sharded subsets.
        None when the payload needs the Python service.name fixup."""
        from tempo_tpu import native
        from tempo_tpu.model.span_batch import _pad_rows

        nat_it = self.registry.interner.native_handle()
        if nat_it is None:
            return None
        n = len(recs)
        cap = _pad_rows(max(n, 1))
        dims, klut, slut = self._staged_dims()
        now = self.registry.now()
        sc = self._sched()
        pipe = self._pipeline(sc)
        bufs = pipe.acquire(cap, len(dims)) if pipe is not None else None
        with tracing.span("generator.resolve", rows=n):
            got = native.spanmetrics_from_recs(
                self.calls.table._nat, nat_it._h, raw, recs, dims, klut,
                slut, slack_lo, slack_hi, now, self.calls.table.last_seen,
                cap, out=bufs)
        if got is None:
            if pipe is not None:
                pipe.release(bufs)   # fixup bail: full path re-stages
            return None
        return self._push_resolved(got, recs["trace_id"], n, now,
                                   sc=sc, pipe=pipe, bufs=bufs)

    def _push_resolved(self, got, trace_ids, n: int, now: float,
                       sc=None, pipe=None, bufs=None,
                       weights=None) -> tuple[int, int]:
        """`weights` (len n, optional) are per-span Horvitz-Thompson
        upscale factors from the distributor's overload sampling stage:
        they multiply calls/size counts and weight the latency
        histogram+sketch so rates and quantiles describe the TRUE
        stream. None (the unsampled common case) keeps the cached
        device ones-vector and the exact pre-sampling dispatch."""
        slots, packed, rows, valid, miss, n_valid, n_filtered = got
        if miss.size:
            # new series only: the rare half of the resolve, outside the
            # generator.resolve span
            self.calls.table.apply_misses(rows, slots, miss, valid, now)
        if sc is None:
            sc = self._sched()
        if sc is not None:
            # scheduler route: trim to the real rows (filtered rows carry
            # slot -1 and drop on device; the coalescer re-pads the merged
            # batch to its pow-2 bucket) and enqueue for the next batch
            # window — the dispatch itself runs on the worker thread. The
            # pipeline (when on) adopts the job so the staging buffers
            # recycle the moment its dispatch lands.
            job = None
            if n:
                w = np.ones(n, np.float32) if weights is None \
                    else np.asarray(weights[:n], np.float32)
                job = self._submit_rows(sc, slots[:n], packed[1][:n],
                                        packed[2][:n], w)
            # exemplars read slots/packed BEFORE the buffers are handed
            # to the pipeline ring: track() makes them reclaimable the
            # moment the job lands (inline on the shed path), and a
            # concurrent push's acquire() could overwrite them mid-read
            self.calls.note_exemplars(slots[:n], trace_ids, packed[1],
                                      int(now * 1000))
            self.latency.exemplars = self.calls.exemplars
            if pipe is not None:
                if job is not None:
                    pipe.track(job, bufs)
                else:
                    pipe.release(bufs)
            return n_valid, n_filtered
        if self._paged:
            # paged direct path (no scheduler): one fused paged dispatch
            # over the pooled arenas — same padded staging arrays
            wfull = np.ones(len(slots), np.float32)
            if weights is not None:
                wfull[:n] = weights[:n]
            self._paged_update(slots, packed[1], packed[2], wfull)
            self.calls.note_exemplars(slots[:n], trace_ids, packed[1],
                                      int(now * 1000))
            self.latency.exemplars = self.calls.exemplars
            return n_valid, n_filtered
        sm = self._serving_mesh()
        if sm is not None:
            # mesh-resident direct path (no scheduler): the padded
            # staging arrays ride one shard_map dispatch; weights default
            # to host ones (the batch upload is sharded per push anyway)
            wfull = np.ones(len(slots), np.float32)
            if weights is not None:
                wfull[:n] = weights[:n]
            self._mesh_update(sm, slots, packed[1], packed[2], wfull)
            self.calls.note_exemplars(slots[:n], trace_ids, packed[1],
                                      int(now * 1000))
            self.latency.exemplars = self.calls.exemplars
            return n_valid, n_filtered
        cap = len(slots)
        ones = self._ones_cache.get(cap)
        if ones is None:
            import jax.numpy as jnp

            # the weights vector is constant on the fast path: upload it
            # ONCE per capacity and reuse the device copy every push
            ones = self._ones_cache[cap] = jnp.ones(cap, jnp.float32)
        if weights is not None:
            # sampled push: per-span upscale weights replace the cached
            # ones-vector (same shape/dtype — no re-trace, one extra H2D
            # only while sampling is active)
            wfull = np.ones(cap, np.float32)
            wfull[:n] = weights[:n]
            ones = wfull
        if self.calls.table.capacity < (1 << 24):
            # single packed H2D for (slots, dur, sizes) — f32 holds every
            # possible SLOT ID exactly while the series-table capacity
            # stays below 2^24 (slot values, not batch length, are what
            # round-trip through f32). The state_lock spans the DONATING
            # dispatch + rebind: collect() on the collection thread takes
            # the same lock, so it can never read a donated-dead buffer.
            packed[0] = slots
            with self.registry.state_lock:
                (self.calls.state, self.latency.state, self.sizes.state,
                 self.dd, self.mom) = _fused_update_packed(
                    self.calls.state, self.latency.state, self.sizes.state,
                    self.dd, self.mom, packed, ones)
        else:
            # same donation + lock discipline as the packed branch — an
            # unlocked non-donating dispatch here could read buffers the
            # dict route just donated
            with self.registry.state_lock:
                (self.calls.state, self.latency.state, self.sizes.state,
                 self.dd, self.mom) = _fused_update_donated(
                    self.calls.state, self.latency.state, self.sizes.state,
                    self.dd, self.mom, slots, packed[1], packed[2], ones)
        self.calls.note_exemplars(slots[:n], trace_ids, packed[1],
                                  int(now * 1000))
        self.latency.exemplars = self.calls.exemplars
        return n_valid, n_filtered

    # -- staging -----------------------------------------------------------

    def _label_rows(self, sb: SpanBatch) -> np.ndarray:
        it = self.registry.interner
        cols = []
        n = sb.capacity
        for dim in self.cfg.intrinsic_dimensions:
            if dim == "service":
                cols.append(sb.service_id)
            elif dim == "span_name":
                cols.append(sb.name_id)
            elif dim == "span_kind":
                lut = it.intern_many(_KIND_STRS)
                cols.append(lut[np.clip(sb.kind, 0, 5)])
            elif dim == "status_code":
                lut = it.intern_many(_STATUS_STRS)
                cols.append(lut[np.clip(sb.status_code, 0, 2)])
            elif dim == "status_message":
                cols.append(np.where(sb.status_message_id >= 0, sb.status_message_id,
                                     it.intern("")))
            else:
                raise ValueError(f"unknown intrinsic dimension {dim}")
        empty = it.intern("")
        for key in self.cfg.dimensions:
            col = sb.attr_sval_column(key)
            rcol = sb.attr_sval_column(key, scope="resource")
            col = np.where(col != INVALID_ID, col, rcol)
            cols.append(np.where(col != INVALID_ID, col, empty))
        return np.stack(cols, axis=1).astype(np.int32)

    def push_batch(self, sb: SpanBatch, span_sizes: np.ndarray | None = None,
                   sample_weights: np.ndarray | None = None) -> None:
        """Aggregate one batch. `span_sizes` ≈ proto bytes per span (size
        subproc); `sample_weights` (len ≤ capacity) are overload-sampling
        upscale factors, composed multiplicatively with the span
        multiplier (both are per-span observation weights)."""
        if sb.interner is not self.registry.interner:
            raise ValueError(
                "SpanBatch must be built with the tenant registry's interner "
                "(id spaces are shared between batch staging and series labels)")
        valid = sb.valid.copy()
        if self._policies:
            keep = self._policies(sb)
            self.spans_discarded += int((valid & ~keep).sum())
            valid &= keep
        with tracing.span("generator.resolve", rows=sb.n):
            rows = self._label_rows(sb)
            slots = self.calls.resolve_slots(rows, valid=valid)
        dur_s = (sb.duration_ns / 1e9).astype(np.float32)
        if span_sizes is None:
            span_sizes = np.zeros(sb.capacity, np.float32)
        weights = np.ones(sb.capacity, np.float32)
        if self.cfg.span_multiplier_key:
            mult = _attr_fval(sb, self.cfg.span_multiplier_key)
            weights = np.where(mult > 0, mult, 1.0).astype(np.float32)
        if sample_weights is not None:
            sw = np.ones(sb.capacity, np.float32)
            sw[:len(sample_weights)] = sample_weights
            weights = weights * sw
        sc = self._sched()
        if sc is not None:
            self._submit_rows(sc, slots, dur_s,
                              span_sizes.astype(np.float32), weights)
        elif self._paged:
            self._paged_update(slots, dur_s,
                               span_sizes.astype(np.float32), weights)
        else:
            sm = self._serving_mesh()
            if sm is not None:
                self._mesh_update(sm, slots, dur_s,
                                  span_sizes.astype(np.float32), weights)
            else:
                with self.registry.state_lock:
                    (self.calls.state, self.latency.state, self.sizes.state,
                     self.dd, self.mom) = _fused_update_donated(
                        self.calls.state, self.latency.state,
                        self.sizes.state, self.dd, self.mom, slots, dur_s,
                        span_sizes.astype(np.float32), weights)
        ts_ms = int(self.registry.now() * 1000)
        self.calls.note_exemplars(slots, sb.trace_id, dur_s, ts_ms)
        self.latency.exemplars = self.calls.exemplars
        if self.target_info is not None:
            svc_rows = np.unique(sb.service_id[sb.valid])[:, None]
            self.target_info.set_batch(svc_rows, np.ones(svc_rows.shape[0], np.float32))

    # -- sketch quantiles ---------------------------------------------------

    def _zero_sketch_slots(self, padded: np.ndarray) -> None:
        """Purge hook (under the registry state lock): zero the evicted
        slots' DDSketch rows in whichever layout owns them. Slots past
        the sketch plane — including the registry's capacity-valued
        padding — drop on device."""
        if self._pdd is not None:
            dd_rows = self._pdd[4]
            s = np.where(padded < dd_rows, padded, -1)
            self._pdd[0].zero_slots(s)
            self._pdd[1].zero_slots(s)
        elif self.dd is not None:
            self.dd = rm.zero_slots(self.dd, padded)
        if self._pmom is not None:
            s = np.where(padded < self._pmom[4], padded, -1)
            self._pmom[0].zero_slots(s)
        elif self.mom is not None:
            self.mom = moments.moments_zero_slots(self.mom, padded)

    # -- fleet checkpoint/restore (tempo_tpu/fleet/checkpoint.py) ----------

    def sketch_checkpoint(self, slots: np.ndarray) -> tuple[dict | None, dict]:
        """(meta, rows) for the sketch sidecars of the given calls-table
        slots — the movable half of a tenant checkpoint. `*_sel` arrays
        index into `slots` (the sketch plane may cover a strict prefix
        of the series table). Caller holds the registry state lock."""
        meta: dict = {"tier": self._tier, "dd": None, "mom": None}
        rows: dict[str, np.ndarray] = {}
        if self._pdd is not None or self.dd is not None:
            if self._pdd is not None:
                ddc, ddz, gamma, minv, lim = self._pdd
                nb = ddc.width
            else:
                gamma, minv = self.dd.gamma, self.dd.min_value
                lim, nb = self.dd.counts.shape
            sel = np.flatnonzero(slots < lim)
            ss = slots[sel]
            if self._pdd is not None:
                padded = np.full(_pad_len(max(ss.size, 1)), -1, np.int32)
                padded[:ss.size] = ss
                counts = np.asarray(ddc.gather(padded))[:ss.size]
                zeros = np.asarray(ddz.gather(padded))[:ss.size]
            else:
                counts = np.asarray(self.dd.counts)[ss]
                zeros = np.asarray(self.dd.zeros)[ss]
            meta["dd"] = {"gamma": float(gamma), "min_value": float(minv),
                          "nb": int(nb)}
            rows["dd_sel"] = sel.astype(np.int64)
            rows["dd_counts"] = counts
            rows["dd_zeros"] = zeros
        if self._pmom is not None or self.mom is not None:
            mk, mlo, mhi = self._mom_meta
            lim = self._pmom[4] if self._pmom is not None \
                else self.mom.data.shape[0]
            sel = np.flatnonzero(slots < lim)
            ss = slots[sel]
            if self._pmom is not None:
                padded = np.full(_pad_len(max(ss.size, 1)), -1, np.int32)
                padded[:ss.size] = ss
                mrows = np.asarray(self._pmom[0].gather(padded))[:ss.size]
            else:
                mrows = np.asarray(self.mom.data)[ss]
            meta["mom"] = {"k": int(mk), "lo": float(mlo), "hi": float(mhi)}
            rows["mom_sel"] = sel.astype(np.int64)
            rows["mom_rows"] = mrows
        if meta["dd"] is None and meta["mom"] is None:
            return None, {}
        return meta, rows

    def sketch_meta_check(self, meta: dict) -> None:
        """Validate a checkpoint's sketch metadata against this
        instance's planes via the existing ValueError-raising merge
        guards — called BEFORE any restore row is written."""
        dd = meta.get("dd")
        live_dd = self._pdd is not None or self.dd is not None
        if (dd is not None) != live_dd:
            raise ValueError(
                f"fleet restore: dd-sketch tier mismatch (checkpoint "
                f"{'has' if dd else 'lacks'} a DDSketch plane, live "
                f"instance {'has' if live_dd else 'lacks'} one)")
        if dd is not None:
            if self._pdd is not None:
                _, _, gamma, minv, _ = self._pdd
                nb = self._pdd[0].width
            else:
                gamma, minv = self.dd.gamma, self.dd.min_value
                nb = self.dd.counts.shape[1]
            sketches._merge_check(
                "fleet_restore/dd",
                ("gamma", gamma, "min_value", minv),
                ("gamma", dd["gamma"], "min_value", dd["min_value"]),
                (int(nb),), (int(dd["nb"]),))
        mom = meta.get("mom")
        live_mom = self._pmom is not None or self.mom is not None
        if (mom is not None) != live_mom:
            raise ValueError(
                f"fleet restore: moments tier mismatch (checkpoint "
                f"{'has' if mom else 'lacks'} a moments plane, live "
                f"instance {'has' if live_mom else 'lacks'} one)")
        if mom is not None:
            mk, mlo, mhi = self._mom_meta
            probe = np.zeros((1, moments.n_cols(int(mom["k"]))), np.float32)
            moments.merge_meta_check(
                moments.MomentsSketch(
                    data=np.zeros((1, moments.n_cols(mk)), np.float32),
                    k=mk, lo=mlo, hi=mhi),
                moments.MomentsSketch(data=probe, k=int(mom["k"]),
                                      lo=float(mom["lo"]),
                                      hi=float(mom["hi"])))

    def sketch_restore(self, meta: dict, live_slots: np.ndarray,
                       ok: np.ndarray, rows: dict) -> None:
        """Merge checkpointed sketch rows into the live planes: ADD for
        the DDSketch grid and the moments count+sums, MAX for the two
        moments bound columns — exactly the cross-shard combine. Caller
        holds the registry state lock; `sketch_meta_check` already ran."""
        from tempo_tpu.fleet.checkpoint import _paged_phys
        if meta.get("dd") is not None and "dd_sel" in rows:
            sel = rows["dd_sel"].astype(np.int64)
            keep = ok[sel]
            ls = live_slots[sel][keep]
            counts = rows["dd_counts"][keep]
            zeros = rows["dd_zeros"][keep]
            lim = self._pdd[4] if self._pdd is not None \
                else self.dd.counts.shape[0]
            within = ls < lim
            ls, counts, zeros = ls[within], counts[within], zeros[within]
            if ls.size:
                if self._pdd is not None:
                    ddc, ddz = self._pdd[0], self._pdd[1]
                    phys = _paged_phys(ddc, ls)
                    ddc.rebind(ddc.data.at[phys].add(
                        counts.astype(ddc.data.dtype)))
                    phys = _paged_phys(ddz, ls)
                    ddz.rebind(ddz.data.at[phys].add(
                        zeros.astype(ddz.data.dtype)))
                else:
                    self.dd = dataclasses.replace(
                        self.dd,
                        counts=self.dd.counts.at[ls].add(
                            counts.astype(np.float32)),
                        zeros=self.dd.zeros.at[ls].add(
                            zeros.astype(np.float32)))
        if meta.get("mom") is not None and "mom_sel" in rows:
            mk = self._mom_meta[0]
            sel = rows["mom_sel"].astype(np.int64)
            keep = ok[sel]
            ls = live_slots[sel][keep]
            mrows = rows["mom_rows"][keep].astype(np.float32)
            lim = self._pmom[4] if self._pmom is not None \
                else self.mom.data.shape[0]
            within = ls < lim
            ls, mrows = ls[within], mrows[within]
            if ls.size:
                if self._pmom is not None:
                    mp = self._pmom[0]
                    phys = _paged_phys(mp, ls)
                    data = mp.data.at[phys, :mk + 1].add(mrows[:, :mk + 1])
                    mp.rebind(data.at[phys, mk + 1:].max(mrows[:, mk + 1:]))
                else:
                    data = self.mom.data.at[ls, :mk + 1].add(
                        mrows[:, :mk + 1])
                    self.mom = dataclasses.replace(
                        self.mom,
                        data=data.at[ls, mk + 1:].max(mrows[:, mk + 1:]))

    def device_state_bytes(self) -> int:
        """Device bytes of the processor-OWNED sketch sidecar (the
        registry families report their own); paged: backed pages only."""
        total = 0
        if self._pdd is not None:
            total += (self._pdd[0].device_state_bytes()
                      + self._pdd[1].device_state_bytes())
        elif self.dd is not None:
            total += int(self.dd.counts.nbytes) + int(self.dd.zeros.nbytes)
        if self._pmom is not None:
            total += self._pmom[0].device_state_bytes()
        elif self.mom is not None:
            total += int(self.mom.data.nbytes)
        return total

    def quantile(self, q: float) -> dict[tuple[tuple[str, str], ...], float]:
        """Per-series latency quantile from the configured sketch tier.
        Takes the registry state lock: the packed ingest path DONATES the
        previous sketch buffers at dispatch."""
        if self._pmom is not None or self.mom is not None:
            return self._moments_quantile(q)
        if self._pdd is not None:
            return self._paged_quantile(q)
        if self.dd is None:
            return {}
        # drain any queued scheduler batches first: a quantile read must
        # see every update that was accepted before it
        from tempo_tpu import sched as sched_mod
        sched_mod.flush()
        # The sketch plane may be smaller than the series table
        # (sketch_max_series < max_active_series); slots beyond it were
        # masked out of dd_update and have no quantile. The whole device
        # read happens INSIDE the lock: donation deletes the old buffers
        # at the next push's dispatch no matter who still references them,
        # so an out-of-lock np.asarray on a snapshot is not safe.
        with self.registry.state_lock:
            dd = self.dd
            nrows = dd.counts.shape[0]
            vals = np.asarray(sketches.dd_quantile(dd, q))
        slots = self.calls.table.active_slots()
        slots = slots[slots < nrows]
        return {self.calls.labels_of(int(s)): float(vals[int(s)]) for s in slots}

    def _moments_quantile(self, q: float) -> dict:
        """Moments-tier quantile: gather the ~15-float rows of the
        active slots (dense slice or one paged gather — versus the
        ~1100-bucket DDSketch rows of the dd tier), run the host maxent
        solver once per distinct row (cached), and substitute the
        bucket-sketch answer for any row whose solve failed to converge
        ("both": the DDSketch value; "moments": the classic latency
        histogram interpolation). Solver fallbacks increment
        tempo_moments_solver_fallback_total."""
        from tempo_tpu import sched as sched_mod
        sched_mod.flush()
        mk, mlo, mhi = self._mom_meta
        with self.registry.state_lock:
            limit = self._pmom[4] if self._pmom is not None \
                else self.mom.data.shape[0]
            slots = self.calls.table.active_slots()
            slots = slots[slots < limit]
            if not slots.size:
                return {}
            if self._pmom is not None:
                padded = np.full(_pad_len(slots.size), -1, np.int32)
                padded[:slots.size] = slots
                rows = self._pmom[0].gather(padded)[:slots.size]
            else:
                rows = np.asarray(self.mom.data)[slots]
        vals, failed = moments.quantiles_for_rows(rows, mk, mlo, mhi, [q])
        vals = vals[:, 0]
        if failed.any():
            vals = self._sketch_fallback(q, slots, vals, failed)
        return {self.calls.labels_of(int(s)): float(vals[i])
                for i, s in enumerate(slots.tolist())}

    def _sketch_fallback(self, q: float, slots: np.ndarray,
                         vals: np.ndarray, failed: np.ndarray) -> np.ndarray:
        """Fill failed moments solves from the bucket sketches (under
        the state lock — a concurrent donating push invalidates the
        buffers otherwise)."""
        idx = np.flatnonzero(failed)
        with self.registry.state_lock:
            if self._pdd is not None or self.dd is not None:
                if self._pdd is not None:
                    ddc, ddz, gamma, minv, dd_rows = self._pdd
                    padded = np.full(_pad_len(idx.size), -1, np.int32)
                    padded[:idx.size] = slots[idx]
                    cg, zg = ddc.gather_dev(padded), ddz.gather_dev(padded)
                    dd = sketches.DDSketch(cg, zg, gamma, minv)
                    vals[idx] = np.asarray(
                        sketches.dd_quantile(dd, q))[:idx.size]
                else:
                    dq = np.asarray(sketches.dd_quantile(self.dd, q))
                    vals[idx] = dq[slots[idx]]
                return vals
            # moments-only tier: interpolate the classic latency
            # histogram (the log2-class bounded-resolution answer)
            edges = np.asarray(self.cfg.histogram_buckets, np.float64)
            if self._paged:
                padded = np.full(_pad_len(idx.size), -1, np.int32)
                padded[:idx.size] = slots[idx]
                bc = self.latency.buckets.gather(padded)[:idx.size]
            else:
                bc = np.asarray(self.latency.state.bucket_counts)[slots[idx]]
        cum = np.cumsum(np.asarray(bc, np.float64), axis=1)
        total = cum[:, -1]
        target = np.maximum(q * total, 1e-12)
        b = np.minimum((cum < target[:, None]).sum(axis=1),
                       cum.shape[1] - 1)
        prev = np.where(b > 0, cum[np.arange(len(b)), np.maximum(b - 1, 0)],
                        0.0)
        inb = bc[np.arange(len(b)), b]
        frac = np.where(inb > 0, (target - prev) / np.maximum(inb, 1e-30),
                        1.0)
        lo = np.where(b > 0, edges[np.minimum(np.maximum(b - 1, 0),
                                              len(edges) - 1)], 0.0)
        hi = edges[np.minimum(b, len(edges) - 1)]
        est = np.where(total > 0, lo + (hi - lo) * frac, 0.0)
        vals[idx] = est
        return vals

    def _paged_quantile(self, q: float) -> dict:
        """Paged sketch quantile: gather the active slots' rows through
        the page table (device-side), run the SAME per-row dd_quantile —
        row contents are bijective with the dense plane, so values are
        bit-identical."""
        from tempo_tpu import sched as sched_mod
        sched_mod.flush()
        ddc, ddz, gamma, minv, dd_rows = self._pdd
        with self.registry.state_lock:
            slots = self.calls.table.active_slots()
            slots = slots[slots < dd_rows]
            if not slots.size:
                return {}
            padded = np.full(_pad_len(slots.size), -1, np.int32)
            padded[:slots.size] = slots
            counts = ddc.gather_dev(padded)
            zeros = ddz.gather_dev(padded)
            vals = np.asarray(sketches.dd_quantile(
                sketches.DDSketch(counts, zeros, gamma, minv), q))
        return {self.calls.labels_of(int(s)): float(vals[i])
                for i, s in enumerate(slots.tolist())}


def _sanitize(k: str) -> str:
    out = "".join(c if c.isalnum() else "_" for c in k)
    return "__" + out if out and out[0].isdigit() else out


def _attr_fval(sb: SpanBatch, key: str) -> np.ndarray:
    kid = sb.interner.get(key)
    out = np.zeros(sb.capacity, np.float32)
    if kid == INVALID_ID or sb.span_attr_key.shape[1] == 0:
        return out
    hit = sb.span_attr_key == kid
    has = hit.any(axis=1)
    idx = hit.argmax(axis=1)
    out[has] = sb.span_attr_fval[np.arange(sb.capacity), idx][has]
    return out
