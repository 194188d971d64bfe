"""The service-graph emit on the paged layout: ONE jitted, donating step a
push (`ops.pages.edge_step`, module `jit__edge_update_paged_impl`) over
the edge families' arenas, in place of the families' own launches.

The contract: the paged step's collected state equals the family route's
on the same pool and the dense layout's for the same pushes (counts and
buckets bit for bit, sums to f32 accumulation order, as the dense step is
held in tests/test_generator.py), at every padded shape and across a
chunked emit; pad rows, refused series and unbacked pages drop; many
tenants share one compile; every arena is donated once and no page table
at all.
"""

from __future__ import annotations

import numpy as np
import pytest

from tempo_tpu.generator.processors.servicegraphs import (
    EMITS, EdgeColumns, ServiceGraphsConfig, ServiceGraphsProcessor)
from tempo_tpu.obs.jaxruntime import JIT_COMPILES
from tempo_tpu.ops import pages as op
from tempo_tpu.registry import pages as P
from tempo_tpu.registry.registry import ManagedRegistry, RegistryOverrides

PAGED, DENSE = "servicegraphs_edge_update_paged", "servicegraphs_edge_update"


class Clock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


def _pool(page_rows=16, arena_slots=512):
    return P.PagePool(P.PagePoolConfig(enabled=True, page_rows=page_rows,
                                       arena_slots=arena_slots))


def _proc(pool, messaging, clock, tenant="t", cap=64):
    with P.use(pool):
        reg = ManagedRegistry(tenant, RegistryOverrides(
            max_active_series=cap, stale_duration_s=100.0), now=clock)
        proc = ServiceGraphsProcessor(reg, ServiceGraphsConfig(
            enable_messaging_system_latency_histogram=messaging))
    return proc


def _trio(messaging, clock=None, pools=None, **kw):
    """The paged step, the family route on a pool of its own (forced
    here, in the test: the program has no switch) and the dense layout."""
    clock = clock or Clock()
    pools = pools or (_pool(), _pool())
    fused = _proc(pools[0], messaging, clock, **kw)
    family = _proc(pools[1], messaging, clock, **kw)
    family._fused, family._paged_step = False, None
    dense = _proc(None, messaging, clock, **kw)
    assert fused._fused and fused._paged_step is not None
    assert dense._fused and dense._paged_step is None
    return fused, family, dense


def _edges(proc, n: int, seed: int = 0, pairs: int = 5) -> EdgeColumns:
    """`n` edges over `pairs` (client, server) pairs, so slots repeat in a
    step; every third failed, every fourth messaging."""
    it = proc.registry.interner
    r = np.random.default_rng(seed)
    pair = r.integers(pairs, size=n)
    msg = np.arange(n) % 4 == 1
    return EdgeColumns(
        client=np.array([it.intern(f"cli-{p}") for p in pair], np.int32),
        server=np.array([it.intern(f"srv-{p}") for p in pair], np.int32),
        conn=msg.astype(np.int8),
        client_s=r.uniform(0.001, 20.0, n), server_s=r.uniform(0.001, 20.0, n),
        failed=np.arange(n) % 3 == 0,
        messaging_s=np.where(msg, r.uniform(0.0, 2.0, n), 0.0))


def _emits() -> dict:
    return {k: EMITS.value((k,)) for k in ("fused", "family")}


def _assert_same(a, *others) -> list:
    """Family for family, role for role: counters and bucket/count rows
    bit for bit, sums to f32 order; then the collected samples (one
    collect each: a collect hands its stale markers out once)."""
    got = sorted((s.name, s.labels, s.value) for s in a.registry.collect(7))
    for b in others:
        assert len(a._families) == len(b._families) >= 4
        for fa, fb in zip(a._families, b._families):
            sa, sb = fa._snap(), fb._snap()
            assert len(sa) == len(sb)
            for k, (x, y) in enumerate(zip(sa, sb)):
                if len(sa) == 3 and k == 1:      # a histogram's sums
                    np.testing.assert_allclose(x, y, rtol=1e-6)
                else:
                    np.testing.assert_array_equal(x, y)
        want = sorted((s.name, s.labels, s.value)
                      for s in b.registry.collect(7))
        assert [g[:2] for g in got] == [w[:2] for w in want]
        for (name, _, g), (_, _, w) in zip(got, want):
            if g != g:                           # a stale marker
                assert w != w
            elif name.endswith("_sum"):
                assert g == pytest.approx(w, rel=1e-6)
            else:
                assert g == w
    return got


@pytest.mark.parametrize("messaging", [False, True], ids=["plain", "messaging"])
@pytest.mark.parametrize("n", [0, 1, 16, 17, 512, 513])
def test_paged_step_equals_family_route_and_dense(messaging, n):
    """Two emits at each size: 16 and 512 rows pad to one step each, 17
    and 513 spill into the next shape or a second step of 512."""
    trio = _trio(messaging)
    e0 = _emits()
    for seed in range(2):
        for p in trio:
            p._emit(_edges(p, n, seed))
    steps = 2 * -(-n // 512)
    assert _emits() == {"fused": e0["fused"] + 2 * steps,
                        "family": e0["family"] + steps}
    assert bool(_assert_same(*trio)) == (n > 0)
    assert float(trio[0].total._snap()[0].sum()) == 2 * n


@pytest.mark.parametrize("messaging", [False, True], ids=["plain", "messaging"])
@pytest.mark.parametrize("full", ["table", "pool"])
def test_refused_series_drop(messaging, full):
    """24 pairs into a series table of 16 slots, or of 32 slots over a
    pool of one page a role: rows whose series the table refuses, or
    whose page the pool cannot give, ride slot -1, and both routes drop
    the same rows."""
    cap, slots = (16, 512) if full == "table" else (32, 16)
    pools = (_pool(arena_slots=slots), _pool(arena_slots=slots))
    fused, family, _ = _trio(messaging, pools=pools, cap=cap)
    for seed in range(3):
        for p in (fused, family):
            p._emit(_edges(p, 40, seed, pairs=24))
    refused = [p.total.table.discarded for p in (fused, family)]
    assert refused[0] == refused[1] > 0
    assert (pools[0].alloc_failures > 0) == (full == "pool")
    assert _assert_same(fused, family)
    assert 0 < float(fused.total._snap()[0].sum()) < 3 * 40


def test_unbacked_pages_and_pad_rows_drop_in_the_step():
    """At the step itself: a slot on a page the table leaves unbacked
    (-1) and pad rows (slot -1) write nothing; every other row lands in
    the cell the family steps put it in, and a numpy scatter agrees."""
    shift, rows, edges = 2, 32, (0.5, 1.0, 2.0)
    table = np.array([3, -1, 1, 6], np.int32)     # logical page 1 unbacked
    rng = np.random.default_rng(5)
    slots = rng.integers(0, 16, 48).astype(np.int32)
    slots[40:] = -1
    packed = np.stack([slots.astype(np.float32),
                       (rng.random(48) < 0.3).astype(np.float32),
                       rng.uniform(0.0, 3.0, 48).astype(np.float32),
                       rng.uniform(0.0, 3.0, 48).astype(np.float32)])
    base = [rng.random(rows).astype(np.float32) for _ in range(2)]
    hist = [rng.random((rows, len(edges) + 1)).astype(np.float32),
            rng.random(rows).astype(np.float32),
            rng.random(rows).astype(np.float32)]
    arenas = base + hist + [h.copy() for h in hist]
    got = op.edge_step(edges, shift, False)(
        *[a.copy() for a in arenas], *[table] * 8, packed)

    ones = np.ones(48, np.float32)
    want = [op.counter_add_step(shift)(arenas[0].copy(), table, slots, ones),
            op.counter_add_step(shift)(arenas[1].copy(), table, slots,
                                       packed[1])]
    for k, vals in ((2, packed[2]), (5, packed[3])):
        s, c, b = op.histogram_observe_step(edges, shift)(
            arenas[k + 1].copy(), arenas[k + 2].copy(), arenas[k].copy(),
            table, table, table, slots, vals, ones)
        want += [b, s, c]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    phys = table[slots >> shift]
    keep = (slots >= 0) & (phys >= 0)
    row = (phys << shift) | (slots & 3)
    calls = arenas[0].copy()
    np.add.at(calls, row[keep], 1.0)
    np.testing.assert_array_equal(np.asarray(got[0]), calls)
    bucket = np.sum(packed[2][:, None] > np.array(edges, np.float32), axis=1)
    cells = arenas[2].copy()
    np.add.at(cells, (row[keep], bucket[keep]), 1.0)
    np.testing.assert_array_equal(np.asarray(got[2]), cells)
    untouched = np.flatnonzero(~np.isin(np.arange(rows), row[keep]))
    assert untouched.size
    for g, a in zip(got, arenas):
        np.testing.assert_array_equal(np.asarray(g)[untouched], a[untouched])


def test_two_tenants_on_one_pool_across_a_purge():
    """Two tenants' pushes interleave on one pool's arenas; a staleness
    purge frees one tenant's pages (zeroed back to the free list) between
    them. Each tenant ends equal to its dense twin and to the family
    route on a pool of its own."""
    clock = Clock()
    pools = (_pool(), _pool())
    a = _trio(False, clock=clock, pools=pools, tenant="a")
    b = _trio(False, clock=clock, pools=pools, tenant="b")
    for seed in range(2):
        for tenant in (a, b):
            for p in tenant:
                p._emit(_edges(p, 17, seed))
    clock.t += 60.0
    for p in b:
        p._emit(_edges(p, 9, 7))
    clock.t += 60.0                                # a's series are stale
    freed = pools[0].free_pages()
    for tenant in (a, b):
        for p in tenant:
            p.registry.purge_stale()
    assert pools[0].free_pages() > freed
    for tenant, seed in ((a, 11), (b, 12), (a, 13)):
        for p in tenant:
            p._emit(_edges(p, 5, seed, pairs=3))
    for tenant in (a, b):
        assert _assert_same(*tenant)


def test_many_tenants_share_one_compile():
    """Arenas and page tables are operands: a step compiled for one
    tenant serves every other at the same shape, and no paged tenant
    compiles the dense step."""
    pool = _pool(page_rows=16, arena_slots=2048)
    procs = [_proc(pool, False, Clock(), tenant=f"t{i}") for i in range(5)]
    procs[0]._emit(_edges(procs[0], 3))
    procs[0]._emit(_edges(procs[0], 300))
    before = {k: JIT_COMPILES.value((k,)) for k in (PAGED, DENSE)}
    for i, p in enumerate(procs[1:]):
        p._emit(_edges(p, 3, i))
        p._emit(_edges(p, 300, i))
    assert {k: JIT_COMPILES.value((k,)) for k in (PAGED, DENSE)} == before


@pytest.mark.parametrize("messaging", [False, True], ids=["plain", "messaging"])
def test_every_arena_donated_once_and_no_table(messaging):
    """The step's operands: one arena a role (eight, eleven with the
    messaging histogram), none twice; the emit gives each back rebound
    and deletes the buffer it donated, while the page tables live on."""
    pool = _pool()
    p = _proc(pool, messaging, Clock())
    n = 11 if messaging else 8
    assert len(p._planes) == n
    assert len({id(pl._arena) for pl in p._planes}) == n
    p._emit(_edges(p, 4))                          # back every page
    before = [pl.data for pl in p._planes]
    tables = [pl.device_map() for pl in p._planes]
    p._emit(_edges(p, 4, 1))
    assert all(old.is_deleted() for old in before)
    assert not any(t.is_deleted() for t in tables)
    assert all(pl.data is not old for pl, old in zip(p._planes, before))
