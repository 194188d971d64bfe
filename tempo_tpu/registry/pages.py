"""Process-wide device page pool: paged, ragged registry/sketch state.

The dense layout sizes every tenant family for the worst tenant
(`capacity` rows up front — ~85MB/tenant for the DDSketch plane alone at
defaults). This module kills that: one large per-(dtype, row-width) HBM
arena per process, carved into fixed-size pages (pow-2 rows each),
allocated ON DEMAND as series tables hand out slots and returned to the
free list by the existing staleness sweeps. A sparse tenant costs a few
pages instead of a full dense plane; thousands of tenants share the
arenas (ROADMAP item 2, "Ragged Paged Attention" style — PAPERS.md).

Pieces:

- `PagePool` — process-level state like `tempo_tpu.sched` and the
  serving mesh: `App` calls `configure()` from the `pages:` config block
  (AFTER the mesh — arenas shard page-aligned over 'series' when the
  serving mesh is on); standalone callers use `use()` / `reset()`.
  The pool's RLock is THE state lock for every paged tenant: arenas are
  shared and donated at dispatch, so all device reads/rebinds serialize
  through it (ManagedRegistry adopts it as `state_lock`).
- `_Arena` — one device buffer per (dtype, width): `[rows]` or
  `[rows, width]`, rows = `arena_slots` rounded up to whole pages (and
  to a page-aligned multiple of the mesh's series shards).
- `PagedPlane` — a family plane's view: host page map (logical page →
  physical page or -1), per-page active-slot refcounts, cached device
  copy of the map (re-uploaded only when allocation/eviction dirties
  it — the indirection table is an extra OPERAND of the fused kernels,
  not a new trace per tenant).
- `PageBacking` — per-SeriesTable allocator: `ensure_slot` backs the
  slot's page in every attached plane (all-or-nothing; exhaustion makes
  the series allocation fail exactly like a spent series budget),
  `release` decrements refcounts and frees empty pages (rows already
  zeroed by the eviction sweep; `free` re-zeroes the whole page anyway
  so a reused page can never leak rows).

Device kernels live in `tempo_tpu.ops.pages`. Nothing here imports jax
at module import time — `Config` imports this for the `pages:`
dataclass and must stay light.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time

import numpy as np

_LOG = logging.getLogger("tempo_tpu.pages")

_DTYPE_BYTES = {"float32": 4, "int32": 4}


@dataclasses.dataclass
class PagePoolConfig:
    """Knobs for the device page pool (`pages:` in the app YAML)."""

    enabled: bool = False
    # rows per page; must be a power of two and divide every paged
    # family's capacity (max_active_series, sketch_max_series)
    page_rows: int = 256
    # arena size per (dtype, width) kind, in SLOTS (rows) — every active
    # series consumes one row in each plane kind it touches, so this is
    # the process-wide active-series budget of the paged layout
    arena_slots: int = 131072

    def check(self, capacities: "tuple[int, ...]" = ()) -> list[str]:
        """Config problems (chained into `app.config.Config.check()`).
        `capacities` are the per-family logical capacities the serving
        config implies (max_active_series, sketch_max_series): paged
        mode refuses page sizes that do not divide them."""
        problems = []
        if self.page_rows < 1 or self.page_rows & (self.page_rows - 1):
            problems.append(
                f"pages.page_rows ({self.page_rows}) must be a power of two")
        if self.arena_slots < self.page_rows:
            problems.append(
                f"pages.arena_slots ({self.arena_slots}) < page_rows "
                f"({self.page_rows}): the pool could not back a single page")
        for cap in capacities:
            if self.page_rows >= 1 and \
                    not (self.page_rows & (self.page_rows - 1)) and \
                    cap % self.page_rows:
                problems.append(
                    f"pages.page_rows ({self.page_rows}) does not divide "
                    f"the configured series capacity {cap}: paged mode "
                    "refuses capacity-indivisible page sizes (pick a pow-2 "
                    "page_rows that divides max_active_series and "
                    "sketch_max_series)")
        if capacities and self.arena_slots < max(capacities):
            problems.append(
                f"pages.arena_slots ({self.arena_slots}) is below the "
                f"largest single-tenant capacity ({max(capacities)}): one "
                "full tenant exhausts the pool; size the arena for the "
                "expected ACTIVE series across all tenants (runbook "
                "'Sizing the page pool')")
        return ["pages: " + p for p in problems] if problems else []


class _Arena:
    """One device buffer per (dtype, width, role) + its page free list.

    The ROLE key (config-derived, e.g. "traces_spanmetrics_latency/
    buckets") keeps `arena_slots` meaning exactly "rows per plane role":
    every active series consumes ONE row in each role's arena, so the
    knob is the process-wide active-series budget — without it the five
    width-1 planes of a spanmetrics tenant would share (and 5x-starve)
    one anonymous arena. Tenants with the same family config share the
    same arenas."""

    def __init__(self, pool: "PagePool", dtype: str, width: int,
                 role: str) -> None:
        import jax
        import jax.numpy as jnp

        self.dtype = dtype
        self.width = width
        self.role = role
        self.n_pages = pool._arena_pages
        self.rows = self.n_pages * pool.page_rows
        shape = (self.rows,) if width == 1 else (self.rows, width)
        data = jnp.zeros(shape, dtype)
        if pool.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            spec = P("series") if width == 1 else P("series", None)
            data = jax.device_put(
                data, NamedSharding(pool.mesh.registry_mesh, spec))
        self.data = data
        # physical page 0 is RESERVED as the trash page: a page no
        # tenant can ever own, which stays zero. The scatter kernels
        # never see it (page tables only hold allocated ids, all ≥ 1,
        # or -1, and -1 drops); a kernel whose block index maps must
        # name a real block for an unbacked logical page would point
        # them here. Arena shapes and `tempo_pages_total` count on it.
        self.free: list[int] = list(range(self.n_pages - 1, 0, -1))
        self.owners: list[str | None] = [None] * self.n_pages

    @property
    def page_bytes(self) -> int:
        return 0 if self.rows == 0 else \
            (self.rows // self.n_pages) * self.width * _DTYPE_BYTES[self.dtype]


class PagePool:
    """The process device page pool (see module docstring)."""

    def __init__(self, cfg: PagePoolConfig) -> None:
        self.cfg = cfg
        self.page_rows = cfg.page_rows
        self.page_shift = cfg.page_rows.bit_length() - 1
        # THE paged-state lock: arenas are shared across tenants and
        # donated at dispatch — every read and rebind serializes here
        # (re-entrant: collect()'s family snapshots nest gathers)
        self.lock = threading.RLock()
        self.arenas: dict[tuple[str, int, str], _Arena] = {}
        self.allocated_total = 0
        self.evicted_total = 0
        self.alloc_failures = 0
        self.gather_seconds = 0.0
        # serving-mesh composition: arenas shard page-aligned over
        # 'series' — page ownership replaces the per-tenant
        # capacity-divisibility requirement of the dense mesh placement.
        # Needs data axis 1 (the serving default): the fused paged step
        # is a no-collective owned-rows scatter.
        from tempo_tpu.parallel import serving
        sm = serving.active()
        if sm is not None and sm.data_shards != 1:
            _LOG.warning(
                "page pool: serving mesh has data_shards=%d — paged "
                "arenas need the series-only layout (data=1); arenas "
                "stay single-device", sm.data_shards)
            sm = None
        self.mesh = sm
        shards = sm.series_shards if sm is not None else 1
        # +1: physical page 0 is the reserved trash page (see _Arena) —
        # `arena_slots` keeps meaning USABLE rows per plane role
        pages = -(-cfg.arena_slots // cfg.page_rows) + 1
        if pages % shards:
            pages += shards - pages % shards  # page-aligned shard ranges
        self._arena_pages = pages

    # -- arenas ------------------------------------------------------------

    def arena(self, dtype: str, width: int, role: str) -> _Arena:
        """Get-or-create the (dtype, width, role) arena (device alloc is
        lazy: a process that never pages a role never pays its arena)."""
        key = (dtype, int(width), role)
        with self.lock:
            a = self.arenas.get(key)
            if a is None:
                a = self.arenas[key] = _Arena(self, dtype, width, role)
            return a

    def alloc_page(self, arena: _Arena, tenant: str) -> int:
        """One physical page off the free list, or -1 (pool exhausted —
        the caller's series allocation fails like a spent budget)."""
        with self.lock:
            if not arena.free:
                self.alloc_failures += 1
                return -1
            page = arena.free.pop()
            arena.owners[page] = tenant
            self.allocated_total += 1
            return page

    def release_pages(self, arena: _Arena, pages: np.ndarray) -> None:
        """Zero the pages' rows (ONE batched dispatch, pow-2 padded so a
        sweep of any size keeps a handful of warm shapes) and return
        them to the free list."""
        from tempo_tpu.ops import pages as op
        from tempo_tpu.sched import bucket_rows
        if not len(pages):
            return
        with self.lock:
            padded = np.full(bucket_rows(len(pages), lo=8), -1, np.int32)
            padded[:len(pages)] = pages
            arena.data = op.zero_pages_step(arena.data.ndim, self.page_rows)(
                arena.data, padded)
            for page in np.asarray(pages).tolist():
                arena.owners[page] = None
                arena.free.append(page)
            self.evicted_total += len(pages)

    # -- accounting --------------------------------------------------------

    def total_pages(self) -> int:
        """USABLE pages across arenas (the reserved trash page of each
        arena is not allocatable and not counted)."""
        with self.lock:
            return sum(a.n_pages - 1 for a in self.arenas.values())

    def free_pages(self) -> int:
        with self.lock:
            return sum(len(a.free) for a in self.arenas.values())

    def tenant_bytes(self) -> dict[str, int]:
        """Arena bytes held per tenant (page ownership × page bytes) —
        what the devtime ledger surfaces next to device-seconds."""
        out: dict[str, int] = {}
        with self.lock:
            for a in self.arenas.values():
                pb = a.page_bytes
                for owner in a.owners:
                    if owner is not None:
                        out[owner] = out.get(owner, 0) + pb
        return out

    def status(self) -> dict:
        """The /status "pages" object."""
        with self.lock:
            arenas = [{
                "role": a.role, "dtype": a.dtype, "width": a.width,
                "pages": a.n_pages - 1, "reserved": 1,
                "free": len(a.free),
                "page_bytes": a.page_bytes,
                "bytes": a.page_bytes * a.n_pages,
            } for a in self.arenas.values()]
        top = sorted(self.tenant_bytes().items(), key=lambda kv: -kv[1])[:10]
        return {
            "page_rows": self.page_rows,
            "arena_pages": self._arena_pages,
            "series_shards": self.mesh.series_shards
            if self.mesh is not None else 1,
            "allocated_total": self.allocated_total,
            "evicted_total": self.evicted_total,
            "alloc_failures": self.alloc_failures,
            "arenas": arenas,
            "top_tenant_bytes": [{"tenant": t, "bytes": b} for t, b in top],
        }


class PagedPlane:
    """One family plane's logical slot space over a pooled arena."""

    def __init__(self, pool: PagePool, dtype: str, width: int,
                 capacity: int, tenant: str, role: str = "") -> None:
        if capacity % pool.page_rows:
            raise ValueError(
                f"paged plane capacity {capacity} not divisible by "
                f"page_rows {pool.page_rows}")
        self.pool = pool
        self.width = int(width)
        self.capacity = capacity
        self.tenant = tenant
        self._arena = pool.arena(dtype, width, role)
        self.n_lpages = capacity // pool.page_rows
        self.page_map = np.full(self.n_lpages, -1, np.int32)
        self.refcnt = np.zeros(self.n_lpages, np.int64)
        self._dev_map = None

    # -- host management ---------------------------------------------------

    def backed(self, lpage: int) -> bool:
        return self.page_map[lpage] >= 0

    def alloc(self, lpage: int) -> bool:
        page = self.pool.alloc_page(self._arena, self.tenant)
        if page < 0:
            return False
        self.page_map[lpage] = page
        self._dev_map = None
        return True

    def free_lpages(self, lpages: np.ndarray) -> None:
        """Unmap + free the listed logical pages (one batched device
        zeroing for the whole set)."""
        lpages = np.asarray(lpages)
        phys = self.page_map[lpages]
        live = phys[phys >= 0]
        if not live.size:
            return
        self.page_map[lpages] = -1
        self._dev_map = None
        self.pool.release_pages(self._arena, live)

    def pages_backed(self) -> int:
        return int((self.page_map >= 0).sum())

    def device_state_bytes(self) -> int:
        return self.pages_backed() * self._arena.page_bytes

    # -- device views (callers hold pool.lock) -----------------------------

    def device_map(self):
        """The indirection table as a device operand (re-uploaded only
        when allocation/eviction dirtied it)."""
        if self._dev_map is None:
            import jax
            self._dev_map = jax.device_put(self.page_map)
        return self._dev_map

    @property
    def data(self):
        return self._arena.data

    def rebind(self, new_data) -> None:
        self._arena.data = new_data

    def gather(self, slots: np.ndarray) -> np.ndarray:
        """Host read of the slots' rows ([n] or [n, width]); unbacked or
        negative slots read 0. Caller holds pool.lock (arenas are
        donated by concurrent pushes)."""
        from tempo_tpu.ops import pages as op
        t0 = time.perf_counter()
        got = np.asarray(op.gather_step(self._arena.data.ndim,
                                        self.pool.page_shift)(
            self._arena.data, self.device_map(),
            np.ascontiguousarray(slots, np.int32)))
        self.pool.gather_seconds += time.perf_counter() - t0
        return got

    def gather_dev(self, slots: np.ndarray):
        """Like `gather` but stays on device (quantile pipelines)."""
        from tempo_tpu.ops import pages as op
        return op.gather_step(self._arena.data.ndim, self.pool.page_shift)(
            self._arena.data, self.device_map(),
            np.ascontiguousarray(slots, np.int32))

    def zero_slots(self, slots: np.ndarray) -> None:
        """Zero the slots' rows (eviction sweep; dense `zero_slots` twin).
        Caller holds pool.lock."""
        from tempo_tpu.ops import pages as op
        self._arena.data = op.zero_step(
            self._arena.data.ndim, self.pool.page_shift)(
            self._arena.data, self.device_map(),
            np.ascontiguousarray(slots, np.int32))


class PageBacking:
    """Per-SeriesTable page allocator over one or more planes.

    Families sharing a table (the spanmetrics trio + sketch sidecar)
    register every plane here; slot allocation backs the slot's page in
    ALL of them or fails atomically, so a series either fully exists in
    the paged layout or was never admitted (mirroring the budget gate).
    """

    def __init__(self, pool: PagePool) -> None:
        self.pool = pool
        self.planes: list[tuple[PagedPlane, int]] = []

    def add_plane(self, plane: PagedPlane, limit: "int | None" = None) -> None:
        """Attach a plane; `limit` caps the slot range it backs (the
        sketch plane may be a strict prefix of the series table)."""
        self.planes.append((plane, plane.capacity if limit is None
                            else min(limit, plane.capacity)))

    def adopt(self, other: "PageBacking") -> None:
        self.planes.extend(other.planes)

    def ensure_slot(self, slot: int) -> bool:
        """Back `slot`'s page in every attached plane (all-or-nothing)."""
        shift = self.pool.page_shift
        with self.pool.lock:
            need: list[tuple[PagedPlane, int]] = []
            per_arena: dict[int, int] = {}
            for plane, limit in self.planes:
                if slot >= limit or plane.backed(slot >> shift):
                    continue
                need.append((plane, slot >> shift))
                per_arena[id(plane._arena)] = \
                    per_arena.get(id(plane._arena), 0) + 1
            # feasibility first: a partial allocation must not strand pages
            arenas = {id(p._arena): p._arena for p, _ in need}
            for aid, want in per_arena.items():
                if len(arenas[aid].free) < want:
                    self.pool.alloc_failures += 1
                    return False
            if need:
                from tempo_tpu.utils import tracing
                # a series' first sighting on a fresh page: the pages and
                # the upload of every page table they change, under the
                # pool's lock (the next dispatch would pay the upload)
                with tracing.span("pages.alloc"):
                    for plane, lpage in need:
                        if not plane.alloc(lpage):  # pragma: no cover
                            return False            # — prechecked
                    for plane, _ in need:
                        plane.device_map()
            for plane, limit in self.planes:
                if slot < limit:
                    plane.refcnt[slot >> shift] += 1
            return True

    def release(self, slots: np.ndarray) -> None:
        """Evicted slots: drop refcounts, free pages that emptied."""
        slots = np.asarray(slots)
        if not slots.size:
            return
        shift = self.pool.page_shift
        with self.pool.lock:
            for plane, limit in self.planes:
                ss = slots[slots < limit]
                if not ss.size:
                    continue
                np.subtract.at(plane.refcnt, ss >> shift, 1)
                empty = np.flatnonzero(
                    (plane.refcnt <= 0) & (plane.page_map >= 0))
                plane.free_lpages(empty)


# ---------------------------------------------------------------------------
# the process-wide pool (configured by App, consulted by ManagedRegistry)
# ---------------------------------------------------------------------------

_active: "PagePool | None" = None
_lock = threading.Lock()


def configure(cfg: "PagePoolConfig | None") -> "PagePool | None":
    """Build (or drop) the process page pool from the `pages:` config
    block. Returns the active pool or None when disabled. Never raises
    on a bad config — it warns and falls back to the dense layout
    (`Config.check()` already surfaced the problem)."""
    global _active
    with _lock:
        if cfg is None or not cfg.enabled:
            _active = None
            return None
        problems = cfg.check()
        if problems:
            _LOG.error("page pool disabled: %s", "; ".join(problems))
            _active = None
            return None
        _active = PagePool(cfg)
        return _active


def active() -> "PagePool | None":
    """The process page pool, or None — registries then build dense."""
    return _active


def reset() -> None:
    """Drop the process pool (test isolation)."""
    global _active
    with _lock:
        _active = None


class use:
    """Install a pool (or None) as the process page pool for a
    with-block (tests, bench arms)."""

    def __init__(self, pool: "PagePool | None") -> None:
        self.pool = pool
        self._prev: "PagePool | None" = None

    def __enter__(self) -> "PagePool | None":
        global _active
        with _lock:
            self._prev, _active = _active, self.pool
        return self.pool

    def __exit__(self, *exc) -> None:
        global _active
        with _lock:
            _active = self._prev


# ---------------------------------------------------------------------------
# obs: page-pool families in the process-wide runtime registry
# ---------------------------------------------------------------------------

from tempo_tpu.obs.jaxruntime import RUNTIME  # noqa: E402

_ARENA_LABELS = ("role", "dtype", "width")


def _arena_rows(field):
    pool = _active
    if pool is None:
        return []
    with pool.lock:
        return [((a.role, a.dtype, str(a.width)), float(field(a)))
                for a in pool.arenas.values()]


RUNTIME.gauge_func(
    "tempo_pages_total",
    lambda: _arena_rows(lambda a: a.n_pages - 1),
    help="Usable device pages per arena kind (absent families when the "
         "page pool is off; excludes each arena's reserved trash page)",
    labels=_ARENA_LABELS)
RUNTIME.gauge_func(
    "tempo_pages_free",
    lambda: _arena_rows(lambda a: len(a.free)),
    help="Free device pages per arena kind — 0 with allocation failures "
         "rising means the pool is exhausted (runbook 'Sizing the page "
         "pool')", labels=_ARENA_LABELS)
RUNTIME.counter_func(
    "tempo_pages_allocated_total",
    lambda: [] if _active is None else [((), float(_active.allocated_total))],
    help="Pages handed out since process start (demand-driven: series "
         "table slot allocation backs pages on first touch)")
RUNTIME.counter_func(
    "tempo_pages_evicted_total",
    lambda: [] if _active is None else [((), float(_active.evicted_total))],
    help="Pages returned to the free list by staleness sweeps / purges")
RUNTIME.counter_func(
    "tempo_pages_alloc_failures_total",
    lambda: [] if _active is None else [((), float(_active.alloc_failures))],
    help="Series allocations refused because the page pool was "
         "exhausted (the paged twin of a spent series budget)")
RUNTIME.counter_func(
    "tempo_pages_gather_overhead_seconds_total",
    lambda: [] if _active is None else [((), float(_active.gather_seconds))],
    help="Wall seconds spent gathering paged rows to the host through "
         "the indirection table (collect/native-payload reads)")


__all__ = ["PagePoolConfig", "PagePool", "PagedPlane", "PageBacking",
           "configure", "active", "reset", "use"]
