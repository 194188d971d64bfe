"""Paged ragged device state (registry/pages.py + ops/pages.py):
page-table registry/sketch planes vs the dense fixed-capacity layout.

The contract under test: with the page pool on, every family and the
spanmetrics fused path produce BIT-identical collect()/quantile()
output to the dense layout — across push/purge/evict interleavings,
across the direct / scheduler-coalesced / serving-mesh routes, and
across series shard counts {1,2,4} — while allocating only the pages
active series actually touch. Exhaustion degrades to series discards
(the spent-budget analog), never to wrong numbers.
"""

from __future__ import annotations

import numpy as np
import pytest

from tempo_tpu.registry import pages as P
from tempo_tpu.registry.registry import ManagedRegistry, RegistryOverrides


def _pool(page_rows=16, arena_slots=512):
    return P.PagePool(P.PagePoolConfig(enabled=True, page_rows=page_rows,
                                       arena_slots=arena_slots))


def _registry(pool, cap=64, now=None, tenant="t"):
    with P.use(pool):
        return ManagedRegistry(
            tenant, RegistryOverrides(max_active_series=cap,
                                      stale_duration_s=100.0),
            now=now or (lambda: 1000.0))


def _collect_exact(reg, ts=5000) -> list:
    return sorted((s.name, s.labels, s.value) for s in reg.collect(ts)
                  if s.value == s.value)  # NaN stale markers compare by count


# -- pool mechanics ----------------------------------------------------------

def test_pages_allocate_on_demand_and_free_on_purge():
    t = [1000.0]
    pool = _pool()
    reg = _registry(pool, now=lambda: t[0])
    c = reg.new_counter("c_total", ("svc",))
    assert pool.allocated_total == 0
    c.inc(["a"])
    assert pool.allocated_total == 1
    assert c.table.active_count == 1
    # same page serves the whole slot range it covers
    c.inc(["b"])
    assert pool.allocated_total == 1
    assert pool.tenant_bytes()["t"] == pool.cfg.page_rows * 4
    # idle out both series: the page returns to the free list
    t[0] += 1000
    reg.purge_stale()
    assert pool.evicted_total == 1
    assert pool.free_pages() == pool.total_pages()
    assert pool.tenant_bytes() == {}


def test_page_reuse_starts_from_zero():
    t = [1000.0]
    pool = _pool()
    reg = _registry(pool, now=lambda: t[0])
    c = reg.new_counter("c_total", ("svc",))
    c.inc(["a"], 7.0)
    t[0] += 1000
    reg.purge_stale()
    # the freed physical page is re-handed to a NEW series; its rows
    # must read zero, not the evicted tenant's 7.0
    c.inc(["z"], 1.0)
    vals = {s.labels: s.value for s in reg.collect(1)
            if not s.is_stale_marker}
    assert list(vals.values()) == [1.0]


def test_pool_exhaustion_discards_like_spent_budget():
    pool = _pool(page_rows=16, arena_slots=16)  # exactly one page/kind
    reg = _registry(pool, cap=64)
    c = reg.new_counter("c_total", ("svc",))
    rows = reg.interner.intern_many(
        [f"s{i}" for i in range(32)])[:, None]
    slots = c.inc_batch(rows, np.ones(32, np.float32))
    # first 16 slots fit the single page; the rest were refused
    assert (slots >= 0).sum() == 16
    assert c.table.discarded == 16
    assert pool.alloc_failures > 0
    # existing series keep updating after exhaustion
    before = c._snap()[0][slots[0]]
    c.inc_batch(rows[:1], np.ones(1, np.float32))
    assert c._snap()[0][slots[0]] == before + 1.0


def test_backing_all_or_nothing_across_planes():
    # a histogram series needs pages in THREE role arenas (buckets,
    # sums, counts). Exhaust the sums arena via a same-named family in
    # another tenant registry (arenas are shared per role), then
    # allocate a series in this one: it must be refused entirely — the
    # buckets/counts arenas keep their pages, nothing is stranded
    pool = _pool(page_rows=16, arena_slots=16)  # one page per role arena
    other = _registry(pool, cap=16, tenant="hog")
    other.new_histogram("h", ("svc",)).observe(["x"], 0.1)
    reg = _registry(pool, cap=16)
    h = reg.new_histogram("h", ("svc",))
    h.observe(["b"], 0.5)
    assert h.table.discarded == 1
    assert pool.alloc_failures > 0
    wide = pool.arena("float32", len(h.hist_edges()) + 1, "h/buckets")
    assert len(wide.free) == 0          # the hog's page, not a stranded one
    assert wide.owners.count("hog") == 1
    assert "t" not in pool.tenant_bytes()


def test_config_check_bounds():
    assert P.PagePoolConfig(page_rows=48).check()          # non-pow2
    assert P.PagePoolConfig(page_rows=64, arena_slots=32).check()
    assert not P.PagePoolConfig().check()
    # capacity-indivisible page sizes are refused with a clear error
    msgs = P.PagePoolConfig(page_rows=256).check(capacities=(1000,))
    assert any("capacity-indivisible" in m for m in msgs)
    msgs = P.PagePoolConfig(arena_slots=4096).check(capacities=(65536,))
    assert any("below the largest single-tenant capacity" in m for m in msgs)


def test_app_config_check_surfaces_pages_problems():
    from tempo_tpu.app.config import load_config
    cfg = load_config(text="""
pages: {enabled: true, page_rows: 48}
""")
    assert any("pages:" in w for w in cfg.check())
    # and a clean block stays quiet
    cfg = load_config(text="""
pages: {enabled: true, page_rows: 256, arena_slots: 131072}
""")
    assert not [w for w in cfg.check() if "pages:" in w]


def test_configure_refuses_bad_config_gracefully():
    assert P.configure(P.PagePoolConfig(enabled=True, page_rows=48)) is None
    assert P.active() is None
    pool = P.configure(P.PagePoolConfig(enabled=True, page_rows=16,
                                        arena_slots=256))
    assert pool is not None and P.active() is pool
    P.reset()


def test_indivisible_tenant_falls_back_dense():
    pool = _pool(page_rows=16)
    with P.use(pool):
        reg = ManagedRegistry(
            "odd", RegistryOverrides(max_active_series=100))  # 100 % 16 != 0
        assert reg.pages is None
        c = reg.new_counter("c_total", ("svc",))
        assert not hasattr(c, "planes")  # dense family


# -- family bit-identity -----------------------------------------------------

def _drive_families(reg, t):
    rng = np.random.default_rng(7)
    c = reg.new_counter("c_total", ("svc",))
    g = reg.new_gauge("g", ("svc",))
    h = reg.new_histogram("h", ("svc",))
    nh = reg.new_native_histogram("nh", ("svc",))
    outs = []
    for round_ in range(3):
        for _ in range(4):
            rows = reg.interner.intern_many(
                [f"s{j}" for j in rng.integers(0, 9, 32)])[:, None]
            c.inc_batch(rows, rng.random(32).astype(np.float32))
            g.set_batch(rows, rng.random(32).astype(np.float32))
            h.observe_batch(rows, (rng.random(32) * 3).astype(np.float32))
            nh.observe_batch(rows, (rng.random(32) * 3).astype(np.float32))
        outs.append(_collect_exact(reg, ts=round_))
        payload = nh.native_payload()
        outs.append([(np.asarray(x).tolist() if hasattr(x, "shape") else x)
                     for x in payload[2:]])
        t[0] += 1000
        reg.purge_stale()   # evict EVERYTHING, then the next round reuses
    return outs


def test_families_bit_identical_paged_vs_dense_with_eviction():
    t1, t2 = [1000.0], [1000.0]
    paged = _drive_families(_registry(_pool(), now=lambda: t1[0]), t1)
    dense = _drive_families(
        ManagedRegistry("t", RegistryOverrides(max_active_series=64,
                                               stale_duration_s=100.0),
                        now=lambda: t2[0]), t2)
    assert paged == dense


# -- spanmetrics routes ------------------------------------------------------

def _mk_proc(paged, cap=512, use_sched=False, page_rows=64,
             arena_slots=4096, sketch_max=256):
    from tempo_tpu.generator.processors.spanmetrics import (
        SpanMetricsConfig, SpanMetricsProcessor)

    pool = _pool(page_rows, arena_slots) if paged else None
    t = [1000.0]
    with P.use(pool):
        reg = ManagedRegistry("t",
                              RegistryOverrides(max_active_series=cap,
                                                stale_duration_s=100.0),
                              now=lambda: t[0])
        proc = SpanMetricsProcessor(reg, SpanMetricsConfig(
            use_scheduler=use_sched, sketch_max_series=sketch_max))
    return reg, proc, t, pool


def _batch(reg, seed, n=1500):
    from tempo_tpu.model.span_batch import SpanBatchBuilder

    b = SpanBatchBuilder(reg.interner)
    r = np.random.default_rng(seed)
    for i in range(n):
        b.append(trace_id=r.bytes(16), span_id=r.bytes(8),
                 name=f"op-{i % 9}", service=f"svc-{i % 3}",
                 kind=int(i % 6), status_code=int(i % 3),
                 start_unix_nano=10**18,
                 end_unix_nano=10**18 + int(r.lognormal(16, 1.0)))
    return b.build()


def _run_proc(paged, use_sched=False, purge=True):
    from tempo_tpu import sched

    reg, proc, t, _pool_ = _mk_proc(paged, use_sched=use_sched)
    sc = sched.DeviceScheduler() if use_sched else None
    if sc is not None:
        sc.start()
    with (sched.use(sc) if sc is not None else _null()):
        for seed in (1, 2):
            proc.push_batch(_batch(reg, seed))
        if purge:
            if sc is not None:
                sc.flush()
            t[0] += 1000
            reg.purge_stale()       # evict-then-reuse the same pages
            t0 = t[0]
            del t0
            for seed in (3, 4):
                proc.push_batch(_batch(reg, seed))
        if sc is not None:
            sc.flush()
        out = _collect_exact(reg)
        qq = proc.quantile(0.99)
    if sc is not None:
        sc.stop()
    return out, qq


def _null():
    import contextlib
    return contextlib.nullcontext()


def test_spanmetrics_paged_direct_bit_identical():
    assert _run_proc(True) == _run_proc(False)


def test_spanmetrics_paged_sched_bit_identical():
    assert _run_proc(True, use_sched=True) == _run_proc(False)


def test_sketch_plane_prefix_masked_like_dense():
    # sketch_max_series < capacity: slots past the plane must have no
    # quantile in either layout (the paged plane rounds its page cover
    # up but masks at the CONFIGURED row count)
    rp, pp, _, _ = _mk_proc(True, cap=512, sketch_max=96, page_rows=64)
    rd, pd, _, _ = _mk_proc(False, cap=512, sketch_max=96)
    for seed in (1, 2, 3):
        pp.push_batch(_batch(rp, seed))
        pd.push_batch(_batch(rd, seed))
    assert pp.quantile(0.5) == pd.quantile(0.5)
    assert _collect_exact(rp) == _collect_exact(rd)


def test_servicegraphs_paged_bit_identical():
    from tempo_tpu.generator.processors.servicegraphs import (
        ServiceGraphsConfig, ServiceGraphsProcessor)

    def run(paged):
        pool = _pool(page_rows=16, arena_slots=512) if paged else None
        with P.use(pool):
            reg = ManagedRegistry(
                "t", RegistryOverrides(max_active_series=64),
                now=lambda: 1000.0)
            proc = ServiceGraphsProcessor(reg, ServiceGraphsConfig())
        proc.push_batch(_sg_batch(reg))
        return _collect_exact(reg)

    assert run(True) == run(False)


def test_servicegraphs_paged_emits_through_the_families():
    """One step a push on either layout: the same pushes count under
    `fused` with the page pool on (the paged edge step over the arenas,
    under the pool's lock) and on the dense layout, never compile the
    dense step for a paged tenant, and give the same answers."""
    from tempo_tpu.generator.processors.servicegraphs import (
        EMITS, ServiceGraphsConfig, ServiceGraphsProcessor)
    from tempo_tpu.obs.jaxruntime import JIT_COMPILES

    def run(paged):
        pool = _pool(page_rows=16, arena_slots=512) if paged else None
        with P.use(pool):
            reg = ManagedRegistry(
                "t", RegistryOverrides(max_active_series=64),
                now=lambda: 1000.0)
            proc = ServiceGraphsProcessor(reg, ServiceGraphsConfig())
        before = {k: EMITS.value((k,)) for k in ("fused", "family")}
        compiles = JIT_COMPILES.value(("servicegraphs_edge_update",))
        for n in (5, 5, 5, 12):
            proc.push_batch(_sg_batch(reg, n))
        grew = {k: EMITS.value((k,)) - v for k, v in before.items()}
        return (grew, JIT_COMPILES.value(("servicegraphs_edge_update",))
                - compiles, _collect_exact(reg))

    grew_p, compiles_p, out_p = run(True)
    grew_d, compiles_d, out_d = run(False)
    assert grew_p == {"fused": 4, "family": 0} and compiles_p == 0
    assert grew_d == {"fused": 4, "family": 0} and compiles_d <= 1
    assert out_p == out_d and out_p


def _sg_batch(reg, n=200):
    from tempo_tpu.model.span_batch import SpanBatchBuilder

    b = SpanBatchBuilder(reg.interner)
    r = np.random.default_rng(3)
    for i in range(n):
        tid = r.bytes(16)
        parent = r.bytes(8)
        start = 10**18
        b.append(trace_id=tid, span_id=parent, name="cli",
                 service=f"svc-{i % 3}", kind=3, status_code=int(i % 2),
                 start_unix_nano=start, end_unix_nano=start + 5_000_000)
        b.append(trace_id=tid, span_id=r.bytes(8), parent_span_id=parent,
                 name="srv", service=f"svc-{(i + 1) % 3}", kind=2,
                 status_code=0, start_unix_nano=start + 1_000_000,
                 end_unix_nano=start + 4_000_000)
    return b.build()


# -- serving-mesh composition ------------------------------------------------

@pytest.mark.skipif("len(__import__('jax').devices()) < 4",
                    reason="needs 4 virtual devices")
def test_paged_collect_bit_identical_across_series_shards():
    """Arenas shard page-aligned over 'series'; each shard scatters the
    same rows in order into the pages it owns — collect() and the
    sketch quantile must be bit-identical at shards {1,2,4} AND equal
    to the dense single-device answer."""
    from tempo_tpu.parallel import serving

    dense = _run_proc(False)
    outs = {}
    for shards in (1, 2, 4):
        sm = serving.ServingMesh(serving.MeshConfig(
            enabled=True, devices=shards, series_shards=shards))
        with serving.use(sm):
            outs[shards] = _run_proc(True)
        assert P.active() is None
    assert outs[1] == outs[2] == outs[4] == dense


@pytest.mark.skipif("len(__import__('jax').devices()) < 4",
                    reason="needs 4 virtual devices")
def test_pool_on_data_parallel_mesh_stays_single_device():
    from tempo_tpu.parallel import serving

    sm = serving.ServingMesh(serving.MeshConfig(
        enabled=True, devices=4, series_shards=2))  # data axis = 2
    with serving.use(sm):
        pool = _pool()
        assert pool.mesh is None      # warned, arenas single-device
        reg, proc, _, _ = _mk_proc(False)
    del reg, proc


# -- zero steady-state recompiles across tenants -----------------------------

def test_many_tenants_share_one_trace():
    """2k-tenant scaling rests on this: tenant #2's dispatch must hit
    tenant #1's compiled step (page tables are operands, the static
    meta is config-derived)."""
    from tempo_tpu.obs.jaxruntime import JIT_COMPILES

    pool = _pool(page_rows=64, arena_slots=4096)
    with P.use(pool):
        regs = []
        procs = []
        from tempo_tpu.generator.processors.spanmetrics import (
            SpanMetricsConfig, SpanMetricsProcessor)
        for i in range(4):
            r = ManagedRegistry(f"t{i}",
                                RegistryOverrides(max_active_series=512),
                                now=lambda: 1000.0)
            procs.append(SpanMetricsProcessor(
                r, SpanMetricsConfig(use_scheduler=False,
                                     sketch_max_series=256)))
            regs.append(r)
        procs[0].push_batch(_batch(regs[0], 1))  # warm the step
        before = JIT_COMPILES.value(("spanmetrics_fused_update",))
        for i in range(1, 4):
            procs[i].push_batch(_batch(regs[i], 1))
        after = JIT_COMPILES.value(("spanmetrics_fused_update",))
    assert after == before, "per-tenant dispatch retraced the fused step"


# -- paged sketch kernels (HLL / log2) ---------------------------------------

def test_paged_hll_and_log2_match_dense_sketches():
    import jax.numpy as jnp

    from tempo_tpu.ops import pages as op
    from tempo_tpu.ops import sketches

    rng = np.random.default_rng(11)
    n, n_series, page_rows = 256, 32, 8
    sids = rng.integers(0, n_series, n).astype(np.int32)
    h1 = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    h2 = rng.integers(1, 1 << 32, n, dtype=np.uint32)
    vals = rng.lognormal(0, 2, n).astype(np.float32)
    w = np.ones(n, np.float32)
    shift = page_rows.bit_length() - 1

    # identity page table: logical page i -> physical page i
    table = np.arange(n_series // page_rows, dtype=np.int32)

    hll_d = sketches.hll_update(sketches.hll_init(n_series, precision=6),
                                sids, h1, h2)
    ar = jnp.zeros((n_series, 1 << 6), jnp.int32)
    ar = op.hll_step(6, shift)(ar, table, sids, h1, h2)
    np.testing.assert_array_equal(np.asarray(hll_d.registers),
                                  np.asarray(ar))

    lg_d = sketches.log2_hist_update(
        sketches.log2_hist_init(n_series, offset=32), sids, vals, weights=w)
    ah = jnp.zeros((n_series, 64), jnp.float32)
    ah = op.log2_hist_step(32, shift)(ah, table, sids, vals, w)
    np.testing.assert_array_equal(np.asarray(lg_d.counts), np.asarray(ah))

    # standalone paged DDSketch step (the fused path has its own inline
    # dd scatter; this keeps the bare builder honest too)
    dd_d = sketches.dd_update(
        sketches.dd_init(n_series, rel_err=0.02, min_value=1e-6,
                         max_value=1e3), sids, vals, weights=w)
    az, ad = op.dd_step(dd_d.gamma, dd_d.min_value, shift)(
        jnp.zeros((n_series,), jnp.float32),
        jnp.zeros(dd_d.counts.shape, jnp.float32), table, table,
        sids, vals, w)
    np.testing.assert_array_equal(np.asarray(dd_d.counts), np.asarray(ad))
    np.testing.assert_array_equal(np.asarray(dd_d.zeros), np.asarray(az))


# -- the fused step alone, against a numpy scatter -----------------------------
#
# Arenas of six physical pages of eight rows: page 0 is the trash page,
# logical pages 0..2 sit on physical 1..3, logical page 3 is deliberately
# UNBACKED and physical 4..5 were never handed out.

OP_EDGES = (0.002, 0.008, 0.032, 0.128, 0.512)
OP_PAGE_ROWS, OP_PAGE_SHIFT, OP_N_PHYS = 8, 3, 6
OP_GAMMA, OP_MIN, OP_NB = 1.1, 1e-6, 32
OP_MOM = (4, float(np.log(1e-6)), float(np.log(1e5)))


def _op_arenas(dd: bool, mom: bool) -> list:
    rows = OP_N_PHYS * OP_PAGE_ROWS
    out = [np.zeros(rows, np.float32) for _ in range(4)]
    out.append(np.zeros((rows, len(OP_EDGES) + 1), np.float32))
    if dd:
        out += [np.zeros(rows, np.float32),
                np.zeros((rows, OP_NB), np.float32)]
    if mom:
        out.append(np.zeros((rows, OP_MOM[0] + 3), np.float32))
    return out


def _op_tables(n_roles: int, lpages: int = 4) -> list:
    t = np.full(lpages, -1, np.int32)
    t[:3] = [1, 2, 3]
    return [t] * n_roles


def _op_batch(seed: int, n: int = 32, lpages: int = 4) -> np.ndarray:
    rng = np.random.default_rng(seed)
    mat = np.empty((4, n), np.float32)
    mat[0] = rng.integers(-1, lpages * OP_PAGE_ROWS, n)   # incl. discards
    mat[1] = rng.lognormal(-3, 1.5, n)
    mat[1, ::5] = 0.0                           # the sketches' zero rows
    mat[2] = rng.integers(100, 5000, n)
    mat[3] = rng.integers(1, 4, n)              # integer HT weights
    return mat


def _op_scatter_oracle(arenas: list, table: np.ndarray, mat: np.ndarray,
                       dd_rows: int, mom_rows: int) -> None:
    """One batch into float64 `arenas`, a row at a time: what the step's
    docstrings say, written without the step's code."""
    f32 = np.float32
    calls, h_sums, h_counts, sizes, h_buckets = arenas[:5]
    for s, v, size, w in zip(mat[0].astype(np.int64), mat[1], mat[2],
                             mat[3]):
        page = table[s >> OP_PAGE_SHIFT] if 0 <= s < len(table) * \
            OP_PAGE_ROWS else -1
        if page < 0:
            continue                     # discard or unbacked: dropped
        r = page * OP_PAGE_ROWS + (s & (OP_PAGE_ROWS - 1))
        calls[r] += w
        h_sums[r] += f32(v * w)
        h_counts[r] += w
        sizes[r] += f32(size * w)
        h_buckets[r, int((v > np.asarray(OP_EDGES, f32)).sum())] += w
        if dd_rows and s < dd_rows:
            if v <= f32(OP_MIN):
                arenas[5][r] += w
            else:
                idx = np.ceil(np.log(v / f32(OP_MIN)) / f32(np.log(OP_GAMMA)))
                arenas[6][r, int(np.clip(idx, 0, OP_NB - 1))] += w
        if mom_rows and s < mom_rows:
            k, lo, hi = OP_MOM
            z = np.log(np.clip(v, f32(np.exp(lo)), f32(np.exp(hi))))
            x = np.clip((z - (lo + hi) / 2) / ((hi - lo) / 2), -1.0, 1.0)
            row = arenas[-1][r]
            row[:k + 1] += np.cos(np.arange(k + 1) * np.arccos(x)) * w
            row[k + 1] = max(row[k + 1], z - lo)
            row[k + 2] = max(row[k + 2], hi - z)


@pytest.mark.parametrize("dd, mom", [(True, True), (True, False),
                                     (False, True)],
                         ids=["dd+moments", "dd", "moments"])
def test_fused_step_matches_numpy_scatter(dd, mom):
    from tempo_tpu.ops import pages as op

    dd_rows = 2 * OP_PAGE_ROWS if dd else 0      # strict prefixes of the
    mom_rows = 3 * OP_PAGE_ROWS if mom else 0    # 32-slot series table
    step = op.fused_step(OP_EDGES, OP_GAMMA, OP_MIN, dd_rows, OP_PAGE_SHIFT,
                         True, mom_rows=mom_rows,
                         mom_meta=OP_MOM if mom else None)
    got = _op_arenas(dd, mom)
    want = [a.astype(np.float64) for a in got]
    tabs = _op_tables(len(got))
    for seed in range(3):
        mat = _op_batch(seed)
        got = step(*got, *tabs, mat)
        _op_scatter_oracle(want, tabs[0], mat, dd_rows, mom_rows)
    for role, (g, w) in enumerate(zip(got, want)):
        g = np.asarray(g)
        if role in (1, 3) or (mom and role == len(want) - 1):
            # float sums: f32 accumulation against the f64 oracle
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5,
                                       err_msg=f"role {role}")
        else:
            # counts under integer weights are exact
            np.testing.assert_array_equal(g, w, err_msg=f"role {role}")
        assert g[OP_PAGE_ROWS:4 * OP_PAGE_ROWS].any(), f"role {role} empty"
        assert not g[:OP_PAGE_ROWS].any(), f"role {role} trash page"
        assert not g[4 * OP_PAGE_ROWS:].any(), f"role {role} free pages"


def test_fused_step_vec_route_equals_packed_route():
    from tempo_tpu.ops import pages as op

    meta = (OP_EDGES, OP_GAMMA, OP_MIN, 2 * OP_PAGE_ROWS, OP_PAGE_SHIFT)
    tabs = _op_tables(7)
    mat = _op_batch(7)
    packed = op.fused_step(*meta, True)(*_op_arenas(True, False), *tabs, mat)
    vec = op.fused_step(*meta, False)(
        *_op_arenas(True, False), *tabs, mat[0].astype(np.int32), mat[1],
        mat[2], mat[3])
    assert np.asarray(packed[0]).any()
    for role, (a, b) in enumerate(zip(packed, vec)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"role {role}")


def test_fused_step_drops_discards_and_unbacked_pages():
    from tempo_tpu.ops import pages as op

    step = op.fused_step(OP_EDGES, OP_GAMMA, OP_MIN, 0, OP_PAGE_SHIFT, True)
    mat = np.zeros((4, 16), np.float32)
    mat[0, :8] = -1                               # discards
    mat[0, 8:] = 3 * OP_PAGE_ROWS + np.arange(8)  # the unbacked page
    mat[1], mat[2], mat[3] = 0.5, 100.0, 1.0
    out = step(*_op_arenas(False, False), *_op_tables(5), mat)
    for role, a in enumerate(out):
        assert not np.asarray(a).any(), f"role {role} should be untouched"


# -- obs / status surfaces ---------------------------------------------------

def test_pool_status_and_obs_families_render():
    from tempo_tpu.obs.jaxruntime import RUNTIME

    pool = _pool()
    with P.use(pool):
        reg = ManagedRegistry(
            "t9", RegistryOverrides(max_active_series=64),
            now=lambda: 1000.0)
        c = reg.new_counter("c_total", ("svc",))
        c.inc(["a"])
        st = pool.status()
        assert st["allocated_total"] == 1
        # status reports USABLE pages: every arena reserves physical
        # page 0 as the trash page
        assert st["arenas"][0]["pages"] == pool._arena_pages - 1
        assert st["arenas"][0]["reserved"] == 1
        assert st["top_tenant_bytes"][0]["tenant"] == "t9"
        text = RUNTIME.render()
        assert "tempo_pages_free" in text
        assert "tempo_pages_allocated_total 1" in text


def test_registry_state_bytes_gauge_and_status():
    from tempo_tpu.generator.generator import Generator
    from tempo_tpu.generator.instance import GeneratorConfig
    from tempo_tpu.obs.registry import Registry

    span = {"trace_id": b"\x01" * 16, "span_id": b"\x02" * 8,
            "name": "op", "service": "svc", "kind": 2, "status_code": 0,
            "start_unix_nano": 10**18, "end_unix_nano": 10**18 + 10**6}

    def mk_cfg():
        cfg = GeneratorConfig(processors=("span-metrics",))
        cfg.registry.max_active_series = 128
        cfg.spanmetrics.sketch_max_series = 64
        return cfg

    pool = _pool(page_rows=16, arena_slots=1024)
    with P.use(pool):
        obs = Registry()
        gen = Generator(mk_cfg(), registry=obs, now=lambda: 1e9)
        gen.push_spans("acme", [span])
        inst = gen.instances["acme"]
        assert inst.state_layout == "paged"
        paged_bytes = inst.device_state_bytes()
        assert 0 < paged_bytes < 10 * (1 << 20)
        text = obs.render()
        assert 'tempo_registry_state_bytes{' in text and \
            'layout="paged"' in text
    # dense comparison: same tenant shape costs the full pre-sized planes
    gen_d = Generator(mk_cfg(), registry=Registry(), now=lambda: 1e9)
    gen_d.push_spans("acme", [span])
    dense_bytes = gen_d.instances["acme"].device_state_bytes()
    assert gen_d.instances["acme"].state_layout == "dense"
    assert dense_bytes >= 4 * paged_bytes


# -- full App integration ----------------------------------------------------

def test_app_serves_paged_layout_end_to_end(tmp_path):
    """target=all App with `pages.enabled`: OTLP over HTTP lands in
    paged state through the production distributor→sched→generator
    path, /status exposes the pool + per-tenant bytes, /metrics renders
    the page families."""
    import json
    import socket
    import urllib.request

    from tempo_tpu.app import App
    from tempo_tpu.app.api import serve
    from tempo_tpu.app.config import Config

    cfg = Config()
    cfg.storage.backend = "mem"
    cfg.storage.wal_path = str(tmp_path / "wal")
    cfg.generator.localblocks.data_dir = str(tmp_path / "lb")
    cfg.generator.registry.max_active_series = 4096
    cfg.generator.spanmetrics.sketch_max_series = 1024
    cfg.pages.enabled = True
    cfg.pages.page_rows = 64
    cfg.pages.arena_slots = 4096
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        cfg.server.http_listen_port = s.getsockname()[1]
    assert not [w for w in cfg.check() if "pages:" in w]
    app = App(cfg)
    app.overrides.set_tenant_patch("single-tenant", {
        "generator": {"processors": ["span-metrics"]}})
    try:
        assert app.pages is not None
        srv = serve(app, block=False)
        base = f"http://127.0.0.1:{cfg.server.http_listen_port}"
        import time as _time
        t0 = int(_time.time() * 1e9)   # inside the ingestion slack window
        otlp = json.dumps({"resourceSpans": [{
            "resource": {"attributes": [
                {"key": "service.name",
                 "value": {"stringValue": "shop"}}]},
            "scopeSpans": [{"spans": [{
                "traceId": "0102030405060708090a0b0c0d0e0f10",
                "spanId": "0102030405060708", "name": "checkout",
                "kind": 3, "startTimeUnixNano": str(t0),
                "endTimeUnixNano": str(t0 + 5 * 10**6)}]}]}]}).encode()
        req = urllib.request.Request(
            base + "/v1/traces", data=otlp,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.status == 200
        from tempo_tpu import sched
        sched.flush()
        with urllib.request.urlopen(base + "/status", timeout=10) as r:
            st = json.loads(r.read())
        assert st["pages"] is not None
        assert st["pages"]["allocated_total"] >= 1
        layouts = {v["layout"] for v in st["registry_state_bytes"].values()}
        assert layouts == {"paged"}
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "tempo_pages_allocated_total" in text
        assert 'tempo_registry_state_bytes{' in text
        srv.shutdown()
    finally:
        app.shutdown()
