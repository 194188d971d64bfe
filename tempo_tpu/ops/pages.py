"""Paged device state: page-table translation + paged scatter kernels.

The dense registry/sketch layout allocates full `capacity` rows per
tenant family up front — the DDSketch plane alone is ~85MB/tenant at the
default capacity, sized for the worst tenant. This module is the device
half of the page-table rebuild (ROADMAP item 2, in the style of "Ragged
Paged Attention", PAPERS.md): state lives in a few process-wide HBM
arenas carved into fixed-size pages (pow-2 rows each), and every kernel
gathers the physical page id per row through a small indirection table
before scattering:

    logical slot s  →  page_table[s >> page_shift]          (gather)
                    →  phys_page * page_rows + (s & mask)   (arena row)

Discards keep the dense -1 semantics: a negative slot OR an unbacked
page (table entry -1) translates to an out-of-bounds arena row, and
every scatter runs `mode="drop"` — no host-side filtering, exactly like
`registry.metrics._mask_slots`.

Bit-identity with the dense layout: a paged update applies the same
per-row values in the same order to bijectively-mapped cells, so
per-cell float accumulation order is unchanged — collect()/quantile()
are bit-identical to the dense plane (gated by tests/test_plane_fuzz.py's
paged-vs-dense differential arm).

Every builder below memoizes its jitted step in a module-level cache
keyed ONLY by static hyperparameters — page tables and arenas are plain
operands, so two thousand tenants with the same config share one trace
(`tests/test_pages.py::test_many_tenants_share_one_trace`).

Host-side pool/plane management (allocation, eviction, refcounts) lives
in `tempo_tpu.registry.pages`.

The standalone sketch builders (`log2_hist_step`, `dd_step`, `hll_step`)
are the paged twins of the PUBLIC `ops.sketches.*_update` API — library
kernels for sketch planes beyond the fused spanmetrics path (which
inlines its own dd/log2 scatters for fusion), parity-gated against the
dense implementations in tests/test_pages.py.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map

from tempo_tpu.obs.jaxruntime import instrumented_jit
from tempo_tpu.ops import sketches


def translate(page_table: jax.Array, slots: jax.Array, page_shift: int,
              arena_rows: int) -> jax.Array:
    """Logical slots → physical arena rows; discards/unbacked → OOB
    (`arena_rows`), so downstream scatters with mode="drop" skip them."""
    s = jnp.asarray(slots, jnp.int32)
    lp = s >> page_shift
    phys = page_table[jnp.clip(lp, 0, page_table.shape[0] - 1)]
    row = (phys << page_shift) | (s & ((1 << page_shift) - 1))
    bad = (s < 0) | (phys < 0) | (lp >= page_table.shape[0])
    return jnp.where(bad, arena_rows, row)


# ---------------------------------------------------------------------------
# step cache
# ---------------------------------------------------------------------------

_STEPS: dict[tuple, object] = {}


def _cached(key: tuple, build):
    fn = _STEPS.get(key)
    if fn is None:
        fn = _STEPS[key] = build()
    return fn


def reset_steps() -> None:
    """Drop every cached jitted step. Not needed for correctness in
    normal operation — arenas/tables are operands, so cached steps stay
    valid across pool reconfigures, and the mesh variants key on
    `mesh_fingerprint` (value identity) — but tests that count compiles
    use it to start cold."""
    _STEPS.clear()


# ---------------------------------------------------------------------------
# generic per-family updates (the non-fused registry paths)
# ---------------------------------------------------------------------------

def counter_add_step(page_shift: int):
    """fn(arena[R], table, slots, vals) -> arena — paged counter/gauge-add."""

    def build():
        def step(arena, table, slots, vals):
            r = translate(table, slots, page_shift, arena.shape[0])
            return arena.at[r].add(jnp.asarray(vals, arena.dtype),
                                   mode="drop")
        return instrumented_jit(step, name="paged_counter_update",
                                donate_argnums=(0,))
    return _cached(("counter_add", page_shift), build)


def gauge_set_step(page_shift: int):
    """fn(arena[R], table, slots, vals) -> arena — paged gauge set
    (host already resolved last-wins per slot, like the dense path)."""

    def build():
        def step(arena, table, slots, vals):
            r = translate(table, slots, page_shift, arena.shape[0])
            return arena.at[r].set(jnp.asarray(vals, jnp.float32),
                                   mode="drop")
        return instrumented_jit(step, name="paged_gauge_update",
                                donate_argnums=(0,))
    return _cached(("gauge_set", page_shift), build)


def _hist_scatter(arena2d, table, slots, buckets, w, page_shift):
    """Scatter weights into a wide arena at (row(slot), bucket).

    2D scatter, NOT a flattened one: `rows * width` overflows int32 at
    ~1.57M arena slots with the DDSketch width — exactly the
    millions-of-series scale the paged layout exists for. Discard rows
    translate to the OOB row index and drop."""
    r = translate(table, slots, page_shift, arena2d.shape[0])
    return arena2d.at[r, buckets].add(w, mode="drop")


def _add1(arena, table, slots, vals, page_shift):
    r = translate(table, slots, page_shift, arena.shape[0])
    return arena.at[r].add(vals, mode="drop")


def _observe(ab, a_sums, a_counts, t_bucket, t_sums, t_counts, slots,
             values, weights, edges: tuple, page_shift: int):
    """A classic histogram's paged update: bucket increments in the wide
    arena, then sums and counts (the family step's body and the edge
    step's, so the two cannot drift)."""
    v = jnp.asarray(values, jnp.float32)
    w = jnp.asarray(weights, jnp.float32)
    e = jnp.asarray(edges, jnp.float32)
    b = jnp.sum(v[:, None] > e[None, :], axis=1).astype(jnp.int32)
    return (_hist_scatter(ab, t_bucket, slots, b, w, page_shift),
            _add1(a_sums, t_sums, slots, v * w, page_shift),
            _add1(a_counts, t_counts, slots, w, page_shift))


def histogram_observe_step(edges: tuple, page_shift: int):
    """fn(a_sums, a_counts, ab[Rb,B+1], t_bucket, t_sums, t_counts,
    slots, values, weights) -> (a_sums, a_counts, ab) — classic
    histogram: bucket increments in the wide arena, sums/counts each in
    their own width-1 role arena."""
    edges = tuple(edges)

    def build():
        def step(a_sums, a_counts, ab, t_bucket, t_sums, t_counts, slots,
                 values, weights):
            ab, a_sums, a_counts = _observe(
                ab, a_sums, a_counts, t_bucket, t_sums, t_counts, slots,
                values, weights, edges, page_shift)
            return a_sums, a_counts, ab
        return instrumented_jit(step, name="paged_histogram_update",
                                donate_argnums=(0, 1, 2))
    return _cached(("hist", edges, page_shift), build)


def native_hist_step(offset: int, page_shift: int):
    """fn(a_sums, a_counts, a_zeros, ah[Rh,64], t_hist, t_sums, t_counts,
    t_zeros, slots, values, weights) -> (a_sums, a_counts, a_zeros, ah)
    — exponential histogram: log2 sketch in the wide arena + sum/count/
    zero-count rows in their own width-1 role arenas."""

    def build():
        def step(a_sums, a_counts, a_zeros, ah, t_hist, t_sums, t_counts,
                 t_zeros, slots, values, weights):
            v = jnp.asarray(values, jnp.float32)
            w = jnp.asarray(weights, jnp.float32)
            b = sketches.log2_bucket(v, offset)
            ah = _hist_scatter(ah, t_hist, slots, b, w, page_shift)
            a_sums = _add1(a_sums, t_sums, slots, v * w, page_shift)
            a_counts = _add1(a_counts, t_counts, slots, w, page_shift)
            a_zeros = _add1(a_zeros, t_zeros, slots,
                            jnp.where(v == 0, w, 0.0), page_shift)
            return a_sums, a_counts, a_zeros, ah
        return instrumented_jit(step, name="paged_native_histogram_update",
                                donate_argnums=(0, 1, 2, 3))
    return _cached(("native_hist", offset, page_shift), build)


def log2_hist_step(offset: int, page_shift: int):
    """fn(ah[Rh,64], table, slots, values, weights) -> ah — the bare
    paged Log2Histogram update (sketch-plane parity with
    `sketches.log2_hist_update`)."""

    def build():
        def step(ah, table, slots, values, weights):
            b = sketches.log2_bucket(values, offset)
            return _hist_scatter(ah, table, slots, b,
                                 jnp.asarray(weights, jnp.float32),
                                 page_shift)
        return instrumented_jit(step, name="paged_log2_hist_update",
                                donate_argnums=(0,))
    return _cached(("log2", offset, page_shift), build)


def dd_step(gamma: float, min_value: float, page_shift: int):
    """fn(a_zeros, ad[Rd,B], t_counts, t_zeros, slots, values, weights)
    -> (a_zeros, ad) — paged DDSketch: log-γ bucket counts in the wide
    arena, zero counts in their width-1 role arena. Slot masking (plane
    smaller than the series table) is the CALLER's job — pass -1 for
    masked rows."""
    log_gamma = math.log(gamma)

    def build():
        def step(a_zeros, ad, t_counts, t_zeros, slots, values, weights):
            v = jnp.asarray(values, jnp.float32)
            w = jnp.asarray(weights, jnp.float32)
            nb = ad.shape[-1]
            is_zero = v <= min_value
            idx = jnp.ceil(jnp.log(jnp.maximum(v, min_value) / min_value)
                           / log_gamma)
            idx = jnp.clip(idx, 0, nb - 1).astype(jnp.int32)
            ad = _hist_scatter(ad, t_counts, slots, idx,
                               jnp.where(is_zero, 0.0, w), page_shift)
            a_zeros = _add1(a_zeros, t_zeros, slots,
                            jnp.where(is_zero, w, 0.0), page_shift)
            return a_zeros, ad
        return instrumented_jit(step, name="paged_dd_update",
                                donate_argnums=(0, 1))
    return _cached(("dd", float(gamma), float(min_value), page_shift), build)


def hll_step(precision: int, page_shift: int):
    """fn(ar[Rh,m] i32, table, slots, h1, h2) -> ar — paged HyperLogLog:
    scatter-max of rho into the register row the page table resolves."""

    def build():
        def step(ar, table, slots, h1, h2):
            r = translate(table, slots, page_shift, ar.shape[0])
            idx = (jnp.asarray(h1, jnp.uint32)
                   >> jnp.uint32(32 - precision)).astype(jnp.int32)
            rho = (lax.clz(jnp.asarray(h2, jnp.uint32).astype(jnp.int32))
                   + 1).astype(jnp.int32)
            return ar.at[r, idx].max(rho, mode="drop")
        return instrumented_jit(step, name="paged_hll_update",
                                donate_argnums=(0,))
    return _cached(("hll", precision, page_shift), build)


# ---------------------------------------------------------------------------
# reads: gather / zero through the table
# ---------------------------------------------------------------------------

def gather_step(ndim: int, page_shift: int):
    """fn(arena, table, slots) -> rows [n] or [n, width] (device array;
    unbacked/negative slots read 0 — freed pages are zeroed, so a stale
    table entry can never leak another tenant's rows)."""

    def build():
        def step(arena, table, slots):
            r = translate(table, slots, page_shift, arena.shape[0])
            # fill_value must be concrete; python 0 weak-casts per dtype
            if ndim == 1:
                return arena.at[r].get(mode="fill", fill_value=0)
            return arena.at[r, :].get(mode="fill", fill_value=0)
        return instrumented_jit(step, name="paged_gather")
    return _cached(("gather", ndim, page_shift), build)


def zero_step(ndim: int, page_shift: int):
    """fn(arena, table, slots) -> arena with the slots' rows zeroed
    (paged twin of `registry.metrics.zero_slots`, eviction cadence)."""

    def build():
        def step(arena, table, slots):
            r = translate(table, slots, page_shift, arena.shape[0])
            zero = jnp.zeros((), arena.dtype)
            if ndim == 1:
                return arena.at[r].set(zero, mode="drop")
            return arena.at[r, :].set(zero, mode="drop")
        return instrumented_jit(step, name="paged_zero_slots",
                                donate_argnums=(0,))
    return _cached(("zero", ndim, page_shift), build)


def zero_pages_step(ndim: int, page_rows: int):
    """fn(arena, phys_pages[k]) -> arena with every listed page's rows
    zeroed in ONE dispatch (negative page ids pad and drop) — pages
    return to the free list all-zero so the next owner starts clean
    without an allocation-time wipe. Batched: a mass staleness sweep
    frees thousands of pages under the pool lock, and one kernel per
    page would serialize that many device round-trips while every paged
    tenant's ingest blocks."""

    def build():
        def step(arena, pages):
            p = jnp.asarray(pages, jnp.int32)
            rows = (p[:, None] * page_rows
                    + jnp.arange(page_rows, dtype=jnp.int32)[None, :])
            rows = jnp.where(p[:, None] < 0, arena.shape[0], rows)
            zero = jnp.zeros((), arena.dtype)
            if ndim == 1:
                return arena.at[rows.reshape(-1)].set(zero, mode="drop")
            return arena.at[rows.reshape(-1), :].set(zero, mode="drop")
        return instrumented_jit(step, name="paged_page_free",
                                donate_argnums=(0,))
    return _cached(("zero_pages", ndim, page_rows), build)


# ---------------------------------------------------------------------------
# the fused spanmetrics step (calls + latency hist + size + DDSketch)
# ---------------------------------------------------------------------------

def _moments_scatter(am, table, slots, dur_s, w, mom_meta: tuple,
                     page_shift: int):
    """Paged moments-sketch update (ops/moments.py layout): count +
    Chebyshev log-moment sums scatter-add into columns 0..k of the
    [Rm, k+3] arena row the page table resolves; the two shifted bound
    columns scatter-MAX. Discard slots translate OOB and drop."""
    from tempo_tpu.ops import moments as msk

    mk, mlo, mhi = mom_meta
    r = translate(table, slots, page_shift, am.shape[0])
    z, basis = msk.moments_basis(dur_s, mk, mlo, mhi)
    cols = jnp.arange(mk + 1, dtype=jnp.int32)[None, :]
    am = am.at[r[:, None], cols].add(basis * w[:, None], mode="drop")
    # bounds mirror the dense moments_update exactly: padding/discard
    # rows translate OOB and drop; kept rows bound the support at their
    # true value regardless of weight (HT-sampled rows included)
    am = am.at[r, mk + 1].max(jnp.maximum(z - mlo, 0.0), mode="drop")
    am = am.at[r, mk + 2].max(jnp.maximum(mhi - z, 0.0), mode="drop")
    return am


def _fused_body(arenas, tables, slots, dur_s, sizes, weights,
                edges: tuple, gamma: float, min_value: float,
                dd_rows: int, page_shift: int, mom_rows: int = 0,
                mom_meta: "tuple | None" = None):
    """One paged device step for all spanmetrics families. `arenas` /
    `tables` are role-aligned: (calls, hist_sums, hist_counts, sizes,
    hist_buckets[, dd_zeros, dd_counts][, moments]) — each plane
    scatters into its OWN role arena through its own indirection
    table. The dd / moments sidecars are tier-gated (either, both, or
    neither may be present)."""
    dd = bool(dd_rows)
    mom = bool(mom_rows)
    a_calls, a_hs, a_hc, a_sz, ab = arenas[:5]
    t_calls, t_hs, t_hc, t_sz, t_hb = tables[:5]
    if dd:
        a_ddz, ad = arenas[5], arenas[6]
        t_ddz, t_ddc = tables[5], tables[6]
    if mom:
        am, t_mom = arenas[-1], tables[-1]
    w = jnp.asarray(weights, jnp.float32)
    v = jnp.asarray(dur_s, jnp.float32)
    a_calls = _add1(a_calls, t_calls, slots, w, page_shift)
    # latency histogram
    e = jnp.asarray(edges, jnp.float32)
    b = jnp.sum(v[:, None] > e[None, :], axis=1).astype(jnp.int32)
    ab = _hist_scatter(ab, t_hb, slots, b, w, page_shift)
    a_hs = _add1(a_hs, t_hs, slots, v * w, page_shift)
    a_hc = _add1(a_hc, t_hc, slots, w, page_shift)
    a_sz = _add1(a_sz, t_sz, slots,
                 jnp.asarray(sizes, jnp.float32) * w, page_shift)
    out = (a_calls, a_hs, a_hc, a_sz, ab)
    if dd:
        # DDSketch sidecar: plane may be a strict prefix of the table
        dd_slots = jnp.where(slots < dd_rows, slots, -1)
        log_gamma = math.log(gamma)
        nb = ad.shape[-1]
        is_zero = v <= min_value
        idx = jnp.ceil(jnp.log(jnp.maximum(v, min_value) / min_value)
                       / log_gamma)
        idx = jnp.clip(idx, 0, nb - 1).astype(jnp.int32)
        ad = _hist_scatter(ad, t_ddc, dd_slots, idx,
                           jnp.where(is_zero, 0.0, w), page_shift)
        a_ddz = _add1(a_ddz, t_ddz, dd_slots,
                      jnp.where(is_zero, w, 0.0), page_shift)
        out += (a_ddz, ad)
    if mom:
        mom_slots = jnp.where(slots < mom_rows, slots, -1)
        out += (_moments_scatter(am, t_mom, mom_slots, v, w, mom_meta,
                                 page_shift),)
    return out


def fused_step(edges: tuple, gamma: float, min_value: float, dd_rows: int,
               page_shift: int, packed: bool, mesh_key: "tuple | None" = None,
               mesh=None, series_shards: int = 1, mom_rows: int = 0,
               mom_meta: "tuple | None" = None):
    """The paged fused spanmetrics step, memoized per static meta.

    Signature (dd on):
      fn(*arenas7, *tables7, batch) — arenas/tables role-aligned as
      (calls, hist_sums, hist_counts, sizes, hist_buckets, dd_zeros,
      dd_counts). `batch` is ONE [4, bucket] f32 matrix (slots, dur_s,
    sizes, weights — the coalescer/packed-push single-H2D form, slot ids
    exact in f32 under the caller's capacity < 2^24 gate) when `packed`,
    else four separate row vectors. With dd off (dd_rows=0): 5 arenas /
    5 tables. Arenas are DONATED — callers hold the pool lock across
    dispatch + rebind, the same discipline as the dense fast paths.

    `mesh` (series-sharded serving): the step runs under `shard_map`
    with arenas sharded over 'series' on their row dim — each shard owns
    a page-aligned contiguous range of PHYSICAL arena rows (the pool
    rounds arena pages to a multiple of the shard count), scatters only
    rows it owns and needs no collective: per-cell accumulation order is
    independent of the shard count, so collect() stays bit-identical at
    every series_shards. Page tables ride replicated (they are a few KB).
    Requires the mesh's 'data' axis == 1 (the serving default); `mesh_key`
    is the cache fingerprint for the mesh.
    """
    edges = tuple(edges)
    key = ("fused", edges, float(gamma), float(min_value), int(dd_rows),
           page_shift, bool(packed), mesh_key, int(series_shards),
           int(mom_rows), mom_meta)

    def build():
        n_arenas = n_tables = 5 + (2 if dd_rows else 0) + \
            (1 if mom_rows else 0)

        def split(args):
            arenas = args[:n_arenas]
            tables = args[n_arenas:n_arenas + n_tables]
            rest = args[n_arenas + n_tables:]
            if packed:
                mat = rest[0]
                slots = mat[0].astype(jnp.int32)
                dur_s, sizes, weights = mat[1], mat[2], mat[3]
            else:
                slots, dur_s, sizes, weights = rest
            return arenas, tables, slots, dur_s, sizes, weights

        # the name is the step's handle in a profile (module
        # `jit__fused_update_paged_impl`): the chip benchmark's roofline
        # reader finds the kernel by it, and tests/test_spans.py pins it
        def _fused_update_paged_impl(*args):
            arenas, tables, slots, dur_s, sizes, weights = split(args)
            return _fused_body(arenas, tables, slots, dur_s, sizes,
                               weights, edges, gamma, min_value, dd_rows,
                               page_shift, mom_rows, mom_meta)

        if mesh is None:
            return instrumented_jit(_fused_update_paged_impl,
                                    name="spanmetrics_fused_update",
                                    donate_argnums=tuple(range(n_arenas)))

        # series-sharded form: translate globally, keep owned rows. The
        # shard's arena slice starts at my_shard * local_rows; a global
        # row maps to local row r - base when inside the slice, OOB
        # otherwise (mode="drop" masks it).
        from jax.sharding import PartitionSpec as P

        def sharded(*args):
            arenas = args[:n_arenas]
            tables = args[n_arenas:n_arenas + n_tables]
            rest = args[n_arenas + n_tables:]
            if packed:
                mat = rest[0]
                slots = mat[0].astype(jnp.int32)
                dur_s, sizes, weights = mat[1], mat[2], mat[3]
            else:
                slots, dur_s, sizes, weights = rest
            my = lax.axis_index("series")

            def localize(table, local_rows):
                """A per-shard pseudo page table: pages this shard owns
                keep their LOCAL page id, others go -1 (unbacked) — the
                ownership test collapses into the existing translate."""
                pages_per_shard = local_rows >> page_shift
                local_page = table - my * pages_per_shard
                owned = (table >= 0) & (local_page >= 0) & \
                    (local_page < pages_per_shard)
                return jnp.where(owned, local_page, -1)

            ltabs = tuple(localize(t, a.shape[0])
                          for t, a in zip(tables, arenas))
            return _fused_body(arenas, ltabs, slots, dur_s,
                               sizes, weights, edges, gamma, min_value,
                               dd_rows, page_shift, mom_rows, mom_meta)

        arena_specs = (P("series"),) * 4 + (P("series", None),)
        if dd_rows:
            arena_specs += (P("series"), P("series", None))
        if mom_rows:
            arena_specs += (P("series", None),)
        table_specs = (P(),) * n_tables
        batch_specs = (P(),) if packed else (P(),) * 4
        fn = _shard_map(sharded, mesh=mesh,
                        in_specs=arena_specs + table_specs + batch_specs,
                        out_specs=arena_specs, check_vma=False)
        return instrumented_jit(fn, name="spanmetrics_fused_update_paged_mesh",
                                donate_argnums=tuple(range(n_arenas)))

    return _cached(key, build)


# ---------------------------------------------------------------------------
# the service-graph edge step (request counters + client/server histograms)
# ---------------------------------------------------------------------------

def edge_step(edges: tuple, page_shift: int, messaging: bool):
    """The paged service-graph emit, memoized per static meta: ONE
    donating step for the edge families that `counter_add_step` and
    `histogram_observe_step` update a launch each.

    Signature: fn(*arenas, *tables, packed) -> arenas, arenas and tables
    role-aligned as (total/values, failed/values, client buckets, sums,
    counts, server buckets, sums, counts[, messaging buckets, sums,
    counts]). `packed` is the emit's ONE f32 matrix of rows `slots, fail,
    cdur, sdur` (+ `mslots, mdur` with `messaging`), slot ids exact in f32
    under the caller's capacity < 2^24 gate; pad rows ride slot -1 and
    drop, as do rows of unbacked pages. The families' helpers run in the
    families' order with their weights (1 a row), so the two routes give
    the same cells. Arenas are DONATED: callers hold the pool lock across
    dispatch + rebind."""
    edges = tuple(edges)
    n = 11 if messaging else 8

    def build():
        # the name is the step's handle in a profile (module
        # `jit__edge_update_paged_impl`): outside every
        # `jit__fused_update*` prefix, pinned by tests/test_spans.py
        def _edge_update_paged_impl(*args):
            a, t, packed = args[:n], args[n:2 * n], args[2 * n]
            slots = packed[0].astype(jnp.int32)
            ones = jnp.ones_like(packed[0])
            out = (_add1(a[0], t[0], slots, ones, page_shift),
                   _add1(a[1], t[1], slots, packed[1], page_shift))
            out += _observe(*a[2:5], *t[2:5], slots, packed[2], ones,
                            edges, page_shift)
            out += _observe(*a[5:8], *t[5:8], slots, packed[3], ones,
                            edges, page_shift)
            if messaging:
                out += _observe(*a[8:], *t[8:], packed[4].astype(jnp.int32),
                                packed[5], ones, edges, page_shift)
            return out

        return instrumented_jit(_edge_update_paged_impl,
                                name="servicegraphs_edge_update_paged",
                                donate_argnums=tuple(range(n)))
    return _cached(("edge", edges, page_shift, bool(messaging)), build)
