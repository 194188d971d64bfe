"""Mesh-resident serving path (tempo_tpu.parallel.serving): registry
state sharded over 'series' as donated device buffers, mesh-aware
coalescer dispatch, in-mesh frontend combine — bit-identity + donation
guarantees on the virtual 8-device CPU mesh (conftest)."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from tempo_tpu import sched
from tempo_tpu.parallel import serving

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


@pytest.fixture(autouse=True)
def _reset_serving_mesh():
    yield
    serving.reset()


def _mk_proc(max_series: int = 512):
    from tempo_tpu.generator.processors.spanmetrics import (
        SpanMetricsConfig, SpanMetricsProcessor)
    from tempo_tpu.registry import ManagedRegistry, RegistryOverrides

    reg = ManagedRegistry("t", RegistryOverrides(max_active_series=max_series),
                          now=lambda: 1000.0)
    return reg, SpanMetricsProcessor(reg, SpanMetricsConfig())


def _batch(reg, seed: int, n: int = 2000):
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


def _collect_exact(reg) -> list:
    # EXACT float values — the bit-identity surface
    return sorted((s.name, s.labels, s.value) for s in reg.collect(5000))


def _mesh(devices: int, series_shards: int,
          combine_min_elements: int = 16384) -> serving.ServingMesh:
    return serving.ServingMesh(serving.MeshConfig(
        enabled=True, devices=devices, series_shards=series_shards,
        combine_min_elements=combine_min_elements))


# -- bit identity ------------------------------------------------------------

def test_collect_bit_identical_across_series_shards():
    """collect() (and the quantile sketch plane) must be BIT-identical
    at series_shards 1, 2, 4: each shard scatters the same rows in the
    same order into the slots it owns, so per-slot float accumulation
    order never depends on the shard count (data axis fixed at 1)."""
    outs, quants = {}, {}
    for shards in (1, 2, 4):
        with serving.use(_mesh(shards, shards)):
            reg, proc = _mk_proc()
            for seed in (1, 2, 3):
                proc.push_batch(_batch(reg, seed))
            outs[shards] = _collect_exact(reg)
            quants[shards] = proc.quantile(0.99)
    assert outs[1] and outs[1] == outs[2] == outs[4]
    assert quants[1] and quants[1] == quants[2] == quants[4]


def test_mesh_vs_single_device_parity():
    """Mesh collect vs the plain single-device processor: same series
    set, values equal at float tolerance (the base+delta association
    differs, so bit-equality is not the contract here)."""
    with serving.use(_mesh(4, 4)):
        reg_m, proc_m = _mk_proc()
        for seed in (1, 2):
            proc_m.push_batch(_batch(reg_m, seed))
        got = _collect_exact(reg_m)
    reg_1, proc_1 = _mk_proc()
    for seed in (1, 2):
        proc_1.push_batch(_batch(reg_1, seed))
    ref = _collect_exact(reg_1)
    assert len(got) == len(ref) > 100
    for (n1, l1, v1), (n2, l2, v2) in zip(ref, got):
        assert (n1, l1) == (n2, l2)
        np.testing.assert_allclose(v2, v1, rtol=1e-5, atol=1e-6)


def test_scheduler_route_bit_identical_across_series_shards():
    """The mesh-aware coalescer (one aligned window, one shard_map
    dispatch) keeps the bit-identity guarantee when pushes ride the
    device scheduler."""
    outs = {}
    for shards in (1, 2, 4):
        with serving.use(_mesh(shards, shards)):
            sc = sched.DeviceScheduler(sched.SchedConfig(pipeline_depth=0),
                                       start_worker=False)
            with sched.use(sc):
                reg, proc = _mk_proc()
                for seed in (1, 2):
                    proc.push_batch(_batch(reg, seed))
                assert sc.flush()
                assert sc.batches_total.get("spanmetrics_fused_update",
                                            0) >= 1
                outs[shards] = _collect_exact(reg)
    assert outs[1] and outs[1] == outs[2] == outs[4]


# -- donation + residency ----------------------------------------------------

def test_sharded_state_donated_no_copy():
    """The sharded fused update DONATES: the previous device buffers are
    invalidated at dispatch (no per-push state copy), state stays a
    sharded device array (no host round-trip), and the sketch plane
    rides the same discipline."""
    with serving.use(_mesh(4, 4)) as sm:
        reg, proc = _mk_proc()
        proc.push_batch(_batch(reg, 1))
        calls0, dd0 = proc.calls.state.values, proc.dd.counts
        assert isinstance(calls0, jax.Array)
        assert calls0.sharding == sm.series_1d
        assert dd0.sharding.is_equivalent_to(sm.series_2d, dd0.ndim)
        assert len(calls0.sharding.device_set) == 4
        proc.push_batch(_batch(reg, 2))
        assert calls0.is_deleted()      # donated, not copied
        assert dd0.is_deleted()
        assert isinstance(proc.calls.state.values, jax.Array)
        assert proc.calls.state.values.sharding == sm.series_1d


def test_purge_then_push_keeps_working():
    """A stale-series purge (eager zero_slots) must not wedge the mesh
    route — the next dispatch re-places if placement drifted."""
    clock = [1000.0]
    from tempo_tpu.generator.processors.spanmetrics import (
        SpanMetricsConfig, SpanMetricsProcessor)
    from tempo_tpu.registry import ManagedRegistry, RegistryOverrides

    with serving.use(_mesh(4, 4)):
        reg = ManagedRegistry(
            "t", RegistryOverrides(max_active_series=512,
                                   stale_duration_s=10.0),
            now=lambda: clock[0])
        proc = SpanMetricsProcessor(reg, SpanMetricsConfig())
        proc.push_batch(_batch(reg, 1))
        clock[0] += 100.0
        assert reg.purge_stale() > 0
        proc.push_batch(_batch(reg, 2))
        calls = np.asarray(proc.calls.state.values)
        assert calls.sum() > 0


def test_unshardable_capacity_falls_back_single_device():
    """Capacities that don't split across the shards leave the processor
    on its single-device path (warned, never fatal)."""
    with serving.use(_mesh(4, 4)):
        reg, proc = _mk_proc(max_series=510)     # 510 % 4 != 0
        proc.push_batch(_batch(reg, 1, n=100))
        assert proc._mesh is None
        assert np.asarray(proc.calls.state.values).sum() > 0


# -- mesh-aware coalescer ----------------------------------------------------

def test_coalescer_aligns_bucket_and_emits_shard_obs():
    """submit_rows(align=N) rounds the merged bucket to a multiple of
    the data shards and mesh dispatches emit per-shard occupancy +
    padding-waste rows under the `shard` label."""
    from tempo_tpu.obs.jaxruntime import RUNTIME
    from tempo_tpu.obs.registry import parse_exposition

    got = {}
    sc = sched.DeviceScheduler(sched.SchedConfig(min_bucket_rows=64),
                               start_worker=False)
    with sched.use(sc):     # the obs render funcs read the process slot
        sc.submit_rows("mesh_k", "m", (np.zeros(48, np.int32),), 48,
                       lambda *a: got.setdefault("shape", a[0].shape),
                       pads=(-1,), align=3, shards=3)
        sc.drain_once(force=True)
        assert got["shape"] == (66,)   # pow2 64 rounded up to 3's multiple
        fams = parse_exposition(RUNTIME.render())
        occ = fams["tempo_sched_batch_occupancy_ratio"]["samples"]
        shard_rows = {k for k in occ
                      if k[0] == "tempo_sched_batch_occupancy_ratio_bucket"
                      and dict(k[1]).get("kernel") == "mesh_k"
                      and dict(k[1]).get("shard") in ("0", "1", "2")}
        assert shard_rows, "per-shard occupancy rows missing"
        pad = fams["tempo_sched_padding_waste_bytes_total"]["samples"]
        tail = [(k, v) for k, v in pad.items()
                if dict(k[1]).get("kernel") == "mesh_k"
                and dict(k[1]).get("shard") == "2"]
        assert tail and tail[0][1] > 0     # padding concentrates on the tail


# -- in-mesh frontend combine ------------------------------------------------

def test_frontend_combine_in_mesh_matches_host_fold():
    """SeriesCombiner under the serving mesh: count-exact kinds merge
    via the single in-mesh reduce, bit-equal to the host fold."""
    from tempo_tpu.traceql import ast as A
    from tempo_tpu.traceql.engine_metrics import SeriesCombiner, TimeSeries

    rng = np.random.default_rng(7)
    T = 10

    def mk_lists():
        return [[TimeSeries((("name", f"op-{i}"),),
                            rng.integers(0, 500, T).astype(np.float64),
                            [{"traceId": f"{j}-{i}"}])
                 for i in range(11)] for j in range(4)]

    for kind in (A.MetricsKind.RATE, A.MetricsKind.COUNT_OVER_TIME,
                 A.MetricsKind.MIN_OVER_TIME, A.MetricsKind.MAX_OVER_TIME):
        lists = mk_lists()

        def run(combiner):
            for lst in lists:
                combiner.add_all([TimeSeries(t.labels, t.samples.copy(),
                                             list(t.exemplars))
                                  for t in lst])
            return {k: (v.samples, len(v.exemplars))
                    for k, v in combiner.series.items()}

        ref = run(SeriesCombiner(kind, T))
        # threshold 1: force even this small fold onto the device path
        with serving.use(_mesh(4, 2, combine_min_elements=1)):
            got = run(SeriesCombiner(kind, T))
        assert set(ref) == set(got)
        for k in ref:
            np.testing.assert_array_equal(ref[k][0], got[k][0],
                                          err_msg=str(kind))
            assert ref[k][1] == got[k][1]


def test_frontend_combine_bit_identical_across_shard_counts():
    from tempo_tpu.traceql import ast as A
    from tempo_tpu.traceql.engine_metrics import SeriesCombiner, TimeSeries

    rng = np.random.default_rng(9)
    lists = [[TimeSeries((("svc", f"s{i}"),),
                         rng.integers(0, 100, 6).astype(np.float64))
              for i in range(9)] for _ in range(3)]
    outs = {}
    for shards in (1, 2, 4):
        with serving.use(_mesh(4, shards, combine_min_elements=1)):
            c = SeriesCombiner(A.MetricsKind.RATE, 6)
            for lst in lists:
                c.add_all([TimeSeries(t.labels, t.samples.copy())
                           for t in lst])
            outs[shards] = {k: v.samples.tobytes()
                            for k, v in c.series.items()}
    assert outs[1] == outs[2] == outs[4]


# -- config surface ----------------------------------------------------------

def test_mesh_config_check_warnings():
    from tempo_tpu.app.config import load_config

    cfg = load_config(text="mesh:\n  enabled: true\n  series_shards: -1\n")
    assert any("mesh" in w and "series_shards" in w for w in cfg.check())
    cfg = load_config(text="mesh:\n  enabled: true\n  devices: 4\n"
                           "  series_shards: 3\n")
    assert any("divide" in w for w in cfg.check())
    assert not load_config(text="mesh:\n  enabled: true\n").check()


def test_configure_falls_back_on_bad_shape():
    """serving.configure never raises at serve time — bad shapes warn
    and fall back to the largest pow-2 series sharding that fits (NOT
    all the way to the data-parallel layout) or disable."""
    sm = serving.configure(serving.MeshConfig(enabled=True, devices=4,
                                              series_shards=3))
    assert sm is not None and sm.series_shards == 2
    assert serving.configure(serving.MeshConfig(enabled=False)) is None
    assert serving.active() is None


def test_configure_refuses_more_devices_than_visible():
    """A mesh over more devices than JAX sees is a deployment on the
    wrong machine: an error at boot, never a silent clamp that serves —
    and reports — one device as if it were four."""
    import jax

    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"asks for {n + 1} devices"):
        serving.configure(serving.MeshConfig(enabled=True, devices=n + 1))
    assert serving.active() is None


def test_step_cache_not_keyed_by_mesh_id():
    """product._cached_step keys by mesh VALUE identity — two meshes
    with identical layouts share an entry; id() reuse can't alias."""
    from tempo_tpu.parallel.mesh import make_mesh, mesh_fingerprint
    from tempo_tpu.parallel.product import _STEP_CACHE, _cached_step

    _STEP_CACHE.clear()
    m1 = make_mesh(4, series_shards=2)
    m2 = make_mesh(4, series_shards=2)
    assert mesh_fingerprint(m1) == mesh_fingerprint(m2)
    f1 = _cached_step(m1, (0.1, 1.0), 1.02, 1e-9)
    f2 = _cached_step(m2, (0.1, 1.0), 1.02, 1e-9)
    assert f1 is f2 and len(_STEP_CACHE) == 1
    m3 = make_mesh(8, series_shards=2)
    assert mesh_fingerprint(m3) != mesh_fingerprint(m1)
    assert _cached_step(m3, (0.1, 1.0), 1.02, 1e-9) is not f1
    assert len(_STEP_CACHE) == 2
    _STEP_CACHE.clear()
