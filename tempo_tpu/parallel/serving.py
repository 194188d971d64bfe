"""Process-wide serving mesh: the data×series mesh as a FIRST-CLASS
serving mode, not a parity demo.

`parallel.mesh` / `parallel.product` prove the sharded BlockScanPlane
kernels and `shard_map` spanmetrics pushes bit-match single-device
answers; this module is the production wiring that keeps the serving
process on the mesh permanently:

- the generator's registry and sketch planes (`registry/`,
  `ops/sketches.py`, spanmetrics) live sharded over 'series' as DONATED
  device buffers — one live copy per shard, no per-push state copy and
  no host round-trip (`place_spanmetrics_state` + the donated
  `mesh.sharded_serving_step`);
- the sched coalescer becomes mesh-aware: one padded batch window feeds
  every shard with a single `shard_map` dispatch (`submit_rows` align /
  shards), instead of per-device launches;
- the frontend combiner's cross-shard fold collapses into the in-mesh
  reduce (`engine_metrics.SeriesCombiner` consults `active()`), so
  merged series leave the mesh exactly once;
- the tempodb read plane adopts the same devices data-major
  (`plane_mesh`), the sequence-parallel scan of SNIPPETS [1]/[3].

Axis choice: 'series' is the PRIMARY serving axis — the same axis the
paged-state refactor (ROADMAP item 2, "Ragged Paged Attention") will
page over. Series sharding shrinks every shard's state plane (cache- and
HBM-bound scatter), needs NO collectives on the write path (each slot
lives on exactly one shard), and keeps collect() bit-identical at every
shard count: each shard scatters the same rows in the same order into
the slots it owns. The 'data' axis (batch rows sharded, delta psum)
remains available for real multi-chip row scaling; changing its size
changes float summation order, so the bit-stability guarantee is
per-data-layout.

Like `tempo_tpu.sched`, the mesh is process-level state: `App` calls
`configure()` from the `mesh:` config block before any module that
dispatches kernels is constructed; standalone callers (tests, bench)
use `use()` / `reset()`.

Nothing here imports jax at module import time — `Config` imports this
for the `mesh:` dataclass and must stay light.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import weakref

import numpy as np

_LOG = logging.getLogger("tempo_tpu.mesh")


@dataclasses.dataclass
class MeshConfig:
    """Knobs for the serving mesh (`mesh:` in the app YAML)."""

    enabled: bool = False
    # devices to enlist; 0 = every visible device. More than JAX sees is
    # an error. Non-power-of-two counts are clamped DOWN to the largest
    # power of two so pow-2 coalescer buckets always split evenly
    # across shards.
    devices: int = 0
    # series shards; 0 = auto (all enlisted devices — data axis 1, the
    # bit-stable no-collective layout). Must divide the device count;
    # devices // series_shards becomes the 'data' axis.
    series_shards: int = 0
    # frontend in-mesh combine: minimum pending sample count
    # (series x steps) before the cross-shard fold rides the device
    # reduce — small folds are microseconds on the host, and the device
    # path pays a matrix build + H2D + dispatch + gather
    combine_min_elements: int = 16384

    def check(self) -> list[str]:
        """Config warnings (chained into `app.config.Config.check()`).
        Pure shape math — never touches jax (config load must not
        initialize a backend)."""
        problems = []
        if self.devices < 0:
            problems.append("mesh.devices must be >= 0 (0 = all)")
        elif self.devices and self.devices & (self.devices - 1):
            problems.append(
                f"mesh.devices ({self.devices}) is not a power of two: "
                f"serve time clamps to {_pow2_floor(self.devices)} so "
                "pow-2 batch buckets split evenly across shards")
        if self.series_shards < 0:
            problems.append("mesh.series_shards must be >= 0 (0 = auto)")
        if self.devices and self.series_shards:
            from tempo_tpu.parallel.mesh import validate_mesh_shape
            problems += validate_mesh_shape(_pow2_floor(self.devices),
                                            self.series_shards)
        if self.combine_min_elements < 1:
            problems.append("mesh.combine_min_elements must be >= 1")
        return ["mesh: " + p for p in problems] if problems else []


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


class ServingMesh:
    """The resolved serving mesh + its sharding/step caches.

    Built once per `configure()`; every cache lives on the instance, so
    a reconfigure drops the old meshes AND their jitted steps together —
    no `id()`-keyed global cache to alias (see `mesh.mesh_fingerprint`
    for the product-path fix of that bug class).
    """

    def __init__(self, cfg: MeshConfig) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tempo_tpu.parallel.mesh import make_mesh, validate_mesh_shape

        self.cfg = cfg
        devs = jax.devices()
        n = cfg.devices or len(devs)
        if n > len(devs):
            # never a silent clamp: a four-chip deployment that came up
            # on one device would serve, and report, as if it were four
            raise ValueError(
                f"mesh.devices asks for {n} devices, JAX sees "
                f"{len(devs)} ({devs[0].platform})")
        p2 = _pow2_floor(max(n, 1))
        if p2 != n:
            _LOG.warning(
                "serving mesh: clamping %d devices to %d (largest power of "
                "two) so pow-2 batch buckets split evenly across shards",
                n, p2)
            n = p2
        series = cfg.series_shards or n
        if validate_mesh_shape(n, series):
            # keep as much series sharding as the clamped device count
            # allows (n is a power of two, so any pow-2 <= n divides it)
            # — falling all the way to 1 would silently pick the
            # data-parallel O(state) delta+psum layout instead
            fallback = _pow2_floor(max(min(series, n), 1))
            _LOG.warning(
                "serving mesh: series_shards %d invalid for %d devices "
                "(%s); falling back to %d",
                series, n, "; ".join(validate_mesh_shape(n, series)),
                fallback)
            series = fallback
        self.n_devices = n
        self.series_shards = series
        self.data_shards = n // series
        # registry mesh: the write-path layout (state over 'series',
        # batch over 'data')
        self.registry_mesh = make_mesh(n, series_shards=series)
        # read-plane mesh: every device on 'data' — BlockScanPlane
        # shards span columns sequence-parallel, XLA inserts the reduces
        self.plane_mesh = self.registry_mesh if series == 1 \
            else make_mesh(n, series_shards=1)
        self.series_1d = NamedSharding(self.registry_mesh, P("series"))
        self.series_2d = NamedSharding(self.registry_mesh,
                                       P("series", None))
        self.data_sharding = NamedSharding(self.registry_mesh, P("data"))
        # the packed [roles, bucket] batch matrix: columns over 'data' —
        # one H2D per dispatch (the transfer COUNT is the cost behind a
        # high-latency device link, mirroring the packed push paths)
        self.packed_sharding = NamedSharding(self.registry_mesh,
                                             P(None, "data"))
        self._steps: dict[tuple, object] = {}
        self._combine: dict[tuple, object] = {}
        self._lock = threading.Lock()
        # what the write path counts about itself (the tempo_mesh_*
        # families read these at the scrape): real batch rows by the
        # 'series' shard that owns them, on the series table and on the
        # sketch plane; bytes the batch uploads cost the host link; and
        # the processors that asked for this mesh and stayed on one
        # device. The scheduler's thread, a shed push and a direct push
        # may all write, hence the lock.
        self._stats_lock = threading.Lock()
        self.shard_rows = {plane: np.zeros(series, np.int64)
                           for plane in ("series", "sketch")}
        self.h2d_bytes = 0
        self.unplaced = weakref.WeakSet()

    # -- write path --------------------------------------------------------

    def fits_state(self, capacity: int, dd_rows: int,
                   mom_rows: int = 0) -> bool:
        """Whether a (series table, sketch planes) set can shard over
        this mesh (every shard needs an equal slot range)."""
        s = self.series_shards
        return capacity % s == 0 and (not dd_rows or dd_rows % s == 0) \
            and (not mom_rows or mom_rows % s == 0)

    def serving_step(self, edges: tuple, gamma: float, min_value: float,
                     capacity: int, dd_rows: int, packed: bool = False,
                     mom_rows: int = 0, mom_meta: "tuple | None" = None):
        """The donated sharded fused spanmetrics step, memoized per
        hyperparameter set (the mesh itself is fixed per instance)."""
        key = (tuple(edges), float(gamma), float(min_value),
               int(capacity), int(dd_rows), bool(packed),
               int(mom_rows), mom_meta)
        with self._lock:
            fn = self._steps.get(key)
            if fn is None:
                from tempo_tpu.parallel.mesh import sharded_serving_step
                fn = self._steps[key] = sharded_serving_step(
                    self.registry_mesh, tuple(edges), gamma, min_value,
                    capacity, dd_rows, packed=packed, mom_rows=mom_rows,
                    mom_meta=mom_meta)
            return fn

    def link_bytes(self, *arrays) -> int:
        """What a batch upload costs the host link: the rows split over
        'data' and every 'series' shard's device receives its own copy,
        so the batch crosses once a series shard."""
        return sum(int(a.nbytes) for a in arrays) * self.series_shards

    def _note_h2d(self, *arrays) -> None:
        n = self.link_bytes(*arrays)
        with self._stats_lock:
            self.h2d_bytes += n

    def put_batch(self, *arrays):
        """Host batch vectors → device, leading dim sharded over 'data'.
        Lengths must be a multiple of `data_shards` (the coalescer's
        `align` guarantees it for scheduled dispatches)."""
        import jax

        self._note_h2d(*arrays)
        return tuple(jax.device_put(a, self.data_sharding) for a in arrays)

    def put_packed(self, mat: np.ndarray):
        """One [roles, bucket] f32 matrix → device, columns over 'data'
        — the single-transfer batch upload."""
        import jax

        self._note_h2d(mat)
        return jax.device_put(mat, self.packed_sharding)

    def note_rows(self, slots: np.ndarray, capacity: int,
                  sketch_rows: int) -> None:
        """Count one batch's real rows (slot >= 0; padding carries -1)
        by the 'series' shard that owns them: on the series table, and
        on the sketch plane under its own slot→shard mapping (a slot
        beyond the plane has no sketch row). Slots come off the table's
        free list in order and a shard owns a contiguous slot range, so
        this is where a part-filled table shows as one hot shard. One
        bincount a plane a batch."""
        s = self.series_shards
        real = slots[slots >= 0].astype(np.int64)
        series = np.bincount(real // (capacity // s), minlength=s)
        sketch = None
        if sketch_rows:
            sketch = np.bincount(
                real[real < sketch_rows] // (sketch_rows // s), minlength=s)
        with self._stats_lock:
            self.shard_rows["series"] += series
            if sketch is not None:
                self.shard_rows["sketch"] += sketch

    # -- frontend combine --------------------------------------------------

    def combine(self, stacked: np.ndarray, op: str) -> np.ndarray:
        """The in-mesh cross-shard fold: `stacked` is [K, C, T] f32 —
        K merged series (sharded over 'series'), C per-series
        contributions (sub-requests/shards/jobs), T steps. One device
        reduce over C (the psum/pmax of the combiner tree), one gather
        out — merged series leave the mesh exactly once. K must divide
        by series_shards (callers pad; identity fill rows reduce to the
        identity)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = (op, stacked.shape[1], stacked.shape[2])
        with self._lock:
            fn = self._combine.get(key)
            if fn is None:
                from tempo_tpu.obs.jaxruntime import instrumented_jit

                red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[op]
                fn = self._combine[key] = instrumented_jit(
                    lambda m: red(m, axis=1),
                    name="frontend_mesh_combine")
        sh = NamedSharding(self.registry_mesh, P("series", None, None))
        out = fn(jax.device_put(stacked, sh))
        return np.asarray(out)


# ---------------------------------------------------------------------------
# the process-wide mesh (configured by App, consulted everywhere)
# ---------------------------------------------------------------------------

_active: "ServingMesh | None" = None
_lock = threading.Lock()


def configure(cfg: MeshConfig | None) -> "ServingMesh | None":
    """Build (or drop) the process serving mesh from the `mesh:` config
    block. Returns the active mesh or None when disabled. A bad SHAPE
    warns and falls back inside `ServingMesh` (serve time must not die
    on a shard-count typo; `Config.check()` already surfaced it); asking
    for more devices than JAX sees raises — that is not a typo, it is a
    deployment on the wrong machine."""
    global _active
    with _lock:
        _active = None            # a failed build leaves no stale mesh
        if cfg is not None and cfg.enabled:
            _active = ServingMesh(cfg)
        return _active


def active() -> "ServingMesh | None":
    """The process serving mesh, or None — callers fall back to their
    single-device dispatch."""
    return _active


def reset() -> None:
    """Drop the process mesh (test isolation)."""
    global _active
    with _lock:
        _active = None


class use:
    """Install a mesh (or None) as the process serving mesh for a
    with-block (tests, bench arms)."""

    def __init__(self, sm: "ServingMesh | None") -> None:
        self.sm = sm
        self._prev: "ServingMesh | None" = None

    def __enter__(self) -> "ServingMesh | None":
        global _active
        with _lock:
            self._prev, _active = _active, self.sm
        return self.sm

    def __exit__(self, *exc) -> None:
        global _active
        with _lock:
            _active = self._prev


def place_spanmetrics_state(proc, sm: "ServingMesh | None" = None) -> bool:
    """Re-place a SpanMetricsProcessor's device state onto the serving
    mesh: slot dims shard over 'series', replicated over 'data'.
    Idempotent (device_put to the same sharding is a no-op move).
    Returns False (and leaves state alone) when the capacities don't
    split evenly across the shards. Caller holds the registry
    state_lock — this rebinds live state."""
    sm = sm or _active
    if sm is None:
        return False
    if getattr(proc, "_paged", False):
        # paged processors shard at the POOL level: arenas are placed
        # page-aligned over 'series' when the pool is built, and the
        # paged fused step is mesh-aware — there is no per-tenant dense
        # state to move (and no capacity-divisibility requirement)
        return False
    from tempo_tpu.ops.moments import moments_place
    from tempo_tpu.ops.sketches import dd_place
    from tempo_tpu.registry import metrics as rm

    dd_rows = proc.dd.counts.shape[0] if proc.dd is not None else 0
    mom = getattr(proc, "mom", None)
    mom_rows = mom.data.shape[0] if mom is not None else 0
    if not sm.fits_state(proc.calls.table.capacity, dd_rows, mom_rows):
        # on /metrics too (tempo_mesh_unplaced_processors): a mesh
        # deployment that serves from one device must not read like one
        # that serves from four
        sm.unplaced.add(proc)
        _LOG.warning(
            "serving mesh: capacity %d / sketch rows %d/%d not divisible "
            "by series_shards %d — processor stays single-device",
            proc.calls.table.capacity, dd_rows, mom_rows, sm.series_shards)
        return False
    proc.calls.state = rm.place_state(proc.calls.state, sm.series_1d,
                                      sm.series_2d)
    proc.latency.state = rm.place_state(proc.latency.state, sm.series_1d,
                                        sm.series_2d)
    proc.sizes.state = rm.place_state(proc.sizes.state, sm.series_1d,
                                      sm.series_2d)
    if proc.dd is not None:
        proc.dd = dd_place(proc.dd, sm.series_1d, sm.series_2d)
    if mom is not None:
        proc.mom = moments_place(mom, sm.series_2d)
    return True


# ---------------------------------------------------------------------------
# obs: mesh families in the process-wide runtime registry
# ---------------------------------------------------------------------------

from tempo_tpu.obs.jaxruntime import RUNTIME  # noqa: E402

RUNTIME.gauge_func(
    "tempo_mesh_devices",
    lambda: [] if _active is None else [((), float(_active.n_devices))],
    help="Devices enlisted in the serving mesh (absent family values "
         "when mesh mode is off)")
RUNTIME.gauge_func(
    "tempo_mesh_series_shards",
    lambda: [] if _active is None else [((), float(_active.series_shards))],
    help="'series' axis size of the serving mesh: registry/sketch slot "
         "ranges are partitioned this many ways")
RUNTIME.gauge_func(
    "tempo_mesh_data_shards",
    lambda: [] if _active is None else [((), float(_active.data_shards))],
    help="'data' axis size of the serving mesh: coalesced batch rows "
         "split this many ways per dispatch")


def _shard_rows():
    sm = _active
    if sm is None:
        return []
    with sm._stats_lock:
        return [((plane, str(i)), float(n))
                for plane, rows in sm.shard_rows.items()
                for i, n in enumerate(rows)]


RUNTIME.counter_func(
    "tempo_mesh_shard_rows_total", _shard_rows, labels=("plane", "shard"),
    help="Real (non-padding) rows of the span-metrics batches dispatched "
         "on the serving mesh, by the 'series' shard that owns their "
         "slots: plane=\"series\" the series table, plane=\"sketch\" the "
         "sketch plane (its own slot ranges). Even shares mean an even "
         "mesh; one hot shard means the table is filled from slot 0 up")
RUNTIME.counter_func(
    "tempo_mesh_h2d_bytes_total",
    lambda: [] if _active is None else [((), float(_active.h2d_bytes))],
    help="Bytes the serving mesh's batch uploads cost the host link: "
         "batch bytes times the 'series' shards that each receive a copy")
RUNTIME.gauge_func(
    "tempo_mesh_unplaced_processors",
    lambda: [] if _active is None else [((), float(len(_active.unplaced)))],
    help="Span-metrics processors that asked for the serving mesh and "
         "stayed on one device (capacities not divisible by "
         "series_shards); 0 on a healthy mesh")


__all__ = ["MeshConfig", "ServingMesh", "configure", "active", "reset",
           "use", "place_spanmetrics_state"]
