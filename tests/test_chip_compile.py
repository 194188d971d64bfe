"""Ask the chip's compiler, without a chip.

The TPU compiler is installed here and compiles for a v5e that is
described, not attached (`jax.experimental.topologies`): what Mosaic or
XLA:TPU refuses, it refuses in this file — at no chip time — and what
fits the device's memory is known before the first chip call. Nothing
runs, so nothing here says a kernel is right or fast.

State widths are the defaults `examples/single-binary.yaml` ships
(65,536-row series table, 16,384 x 1,269 DDSketch plane, `page_rows` 256,
`arena_slots` 131072); the batch side is one 1,024-row bucket, the k6
push size — the dense step compiles as slowly at 1,024 rows as at 16,384
(~30 s either way), the state is what the compiler chews on.

ONE file on purpose: only one process at a time may load libtpu, so one
xdist worker describes the topology (inside the fixture, never at import)
and every other worker just collects these tests.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

CAP, DD_ROWS = 65536, 16384          # max_active_series, sketch_max_series
PAGE_ROWS, ARENA_SLOTS = 256, 131072
BUCKET = 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _meta():
    from tempo_tpu.generator.processors.spanmetrics import SpanMetricsConfig
    from tempo_tpu.ops import sketches

    cfg = SpanMetricsConfig()
    gamma, nb = sketches.dd_params(cfg.sketch_rel_err, cfg.sketch_min_s,
                                   cfg.sketch_max_s)
    return cfg, gamma, nb


def _fits_one_chip(compiled) -> None:
    ma = compiled.memory_analysis()
    live = ma.argument_size_in_bytes + ma.temp_size_in_bytes \
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    assert live < 16e9, f"{live / 1e9:.1f} GB does not fit a 16 GB v5e"


def test_dense_fused_update_compiles(one_chip):
    """(a) the default hot kernel: the scheduler-coalesced dense fused
    spanmetrics update at the default state shapes."""
    import jax.numpy as jnp

    from tempo_tpu.generator.processors import spanmetrics as sm
    from tempo_tpu.ops import sketches
    from tempo_tpu.registry import metrics as rm

    cfg, gamma, nb = _meta()
    edges = tuple(cfg.histogram_buckets)
    f32 = jnp.float32
    vec = _shape((CAP,), f32, one_chip)
    compiled = sm._fused_update_packed4._jit.lower(
        rm.CounterState(vec),
        rm.HistogramState(_shape((CAP, len(edges) + 1), f32, one_chip),
                          vec, vec, edges),
        rm.CounterState(vec),
        sketches.DDSketch(_shape((DD_ROWS, nb), f32, one_chip),
                          _shape((DD_ROWS,), f32, one_chip),
                          gamma, cfg.sketch_min_s),
        None, _shape((4, BUCKET), f32, one_chip)).compile()
    _fits_one_chip(compiled)
    # every state buffer is donated: the update is in place (the output
    # tuple's own index table is the only byte not aliased)
    ma = compiled.memory_analysis()
    assert ma.output_size_in_bytes - ma.alias_size_in_bytes < 4096


def test_servicegraphs_edge_update_compiles(one_chip):
    """The service-graph emit's one step a push, at the default family
    shapes and the k6 cell's 16-column bucket (four families: what every
    shipped example runs): every state is donated, so the update is in
    place on the chip too."""
    import jax.numpy as jnp

    from tempo_tpu.generator.processors import servicegraphs as sg
    from tempo_tpu.registry import metrics as rm
    from tempo_tpu.registry.registry import DEFAULT_HISTOGRAM_EDGES as edges

    f32 = jnp.float32
    vec = _shape((CAP,), f32, one_chip)
    hist = rm.HistogramState(_shape((CAP, len(edges) + 1), f32, one_chip),
                             vec, vec, edges)
    compiled = sg._edge_update._jit.lower(
        (rm.CounterState(vec), rm.CounterState(vec), hist, hist),
        _shape((4, 16), f32, one_chip)).compile()
    _fits_one_chip(compiled)
    ma = compiled.memory_analysis()
    assert ma.output_size_in_bytes - ma.alias_size_in_bytes < 4096


def test_serving_mesh_step_compiles(topo):
    """(b) the step `mesh.enabled` dispatches, over the four described
    chips with the state split four ways over 'series'. With the data
    axis at 1 each shard owns its slots outright: no collective."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from tempo_tpu.parallel.mesh import sharded_serving_step

    cfg, gamma, nb = _meta()
    edges = tuple(cfg.histogram_buckets)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "series"))
    s1 = NamedSharding(mesh, P("series"))
    s2 = NamedSharding(mesh, P("series", None))
    f32 = jnp.float32
    vec = _shape((CAP,), f32, s1)
    step = sharded_serving_step(mesh, edges, gamma, cfg.sketch_min_s, CAP,
                                DD_ROWS, packed=True)
    compiled = step._jit.lower(
        vec, _shape((CAP, len(edges) + 1), f32, s2), vec, vec, vec,
        _shape((DD_ROWS, nb), f32, s2), _shape((DD_ROWS,), f32, s1),
        _shape((4, BUCKET), f32, NamedSharding(mesh, P(None, "data")))
    ).compile()
    _fits_one_chip(compiled)
    hlo = compiled.as_text()
    # the name the mesh cell's roofline reader finds the step by
    assert hlo.startswith("HloModule jit__fused_update_mesh_impl")
    assert not any(op in hlo for op in ("all-reduce", "all-gather",
                                        "all-to-all", "collective-permute"))


@pytest.mark.parametrize("query", [
    "{ } | rate() by (resource.service.name)",
    "{ } | quantile_over_time(duration, .99) by (resource.service.name)",
])
def test_read_plane_metrics_grid_compiles(one_chip, query):
    """(c) the read plane's fused metrics grid over one 1M-span block.
    The kernel is a closure `metrics_grid` builds per plane, so the plane
    answers the query once here on the CPU, and the same jit is then
    lowered for the chip with the argument shapes that call carried."""
    import jax

    from tempo_tpu.block.device_scan import BlockScanPlane
    from tempo_tpu.traceql.engine_metrics import (MetricsEvaluator,
                                                  QueryRangeRequest)
    from tempo_tpu.traceql.memview import view_from_traces

    t0 = 1_700_000_000
    rng = np.random.default_rng(0)
    traces = []
    for t in range(2048):
        tid = rng.bytes(16)
        start = int((t0 + t % 600) * 1e9)
        traces.append((tid, [
            {"trace_id": tid, "span_id": rng.bytes(8), "name": f"op-{i}",
             "service": f"svc-{t % 32:04d}",
             "res_attrs": {"service.name": f"svc-{t % 32:04d}"},
             "start_unix_nano": start,
             "end_unix_nano": start + int(rng.lognormal(17, 1))}
            for i in range(4)]))
    plane = BlockScanPlane([view_from_traces(traces)] * 128)
    assert plane.n == 1 << 20
    req = QueryRangeRequest(query=query, start_ns=int(t0 * 1e9),
                            end_ns=int((t0 + 600) * 1e9), step_ns=int(10e9))
    ev = MetricsEvaluator(req, None, None, batched=True)
    preds = [c for c in ev.fetch_req.conditions if c.op is not None]

    def grid():
        handle, cause = plane.metrics_grid(
            ev.m, preds, ev.fetch_req.all_conditions, req.start_ns,
            req.end_ns, req.step_ns)
        assert cause is None
        return handle.fetch()

    grid()
    (key, fn), = plane._qr_cache.items()
    calls = []
    plane._qr_cache[key] = lambda *a: (calls.append(a), fn(*a))[1]
    _, _, cnt, _ = grid()
    assert cnt.sum() == plane.n
    args = [None if a is None else
            _shape(np.shape(a), a.dtype, one_chip) for a in calls[0]]
    assert (1 << 20,) in [a.shape for a in args if a is not None]
    _fits_one_chip(fn._jit.lower(*args).compile())
    assert jax.default_backend() == "cpu"    # nothing above ran on a chip


def test_traceql_grid_update_compiles(one_chip):
    """(d) the host evaluator's device half: one log2-bucket grid scatter
    (quantile_over_time over blocks the plane refused)."""
    import jax.numpy as jnp

    from tempo_tpu.traceql import engine_metrics as em

    rows = _shape((65536,), jnp.int32, one_chip)
    _fits_one_chip(em._scatter_add3._jit.lower(
        _shape((256, 64, em.HBUCKETS), jnp.float32, one_chip), rows, rows,
        rows, _shape((65536,), jnp.float32, one_chip)).compile())


@pytest.mark.parametrize("arena_slots", [ARENA_SLOTS, 2 * ARENA_SLOTS])
def test_paged_xla_step_compiles(one_chip, arena_slots):
    """(e) the paged layout's composed-scatter step (`pages.enabled`), at
    the shipped arena and at `multitenant-zipf-256`'s 262,144 slots. The
    arena is what the compiler chews on: the step carries a temporary as
    large as the DDSketch arena (`_fits_one_chip` counts it)."""
    import jax.numpy as jnp

    from tempo_tpu.ops import pages as op

    cfg, gamma, nb = _meta()
    edges = tuple(cfg.histogram_buckets)
    f32, i32 = jnp.float32, jnp.int32
    row = _shape((arena_slots,), f32, one_chip)
    arenas = [row, row, row, row,
              _shape((arena_slots, len(edges) + 1), f32, one_chip),
              row, _shape((arena_slots, nb), f32, one_chip)]
    tables = [_shape((CAP // PAGE_ROWS,), i32, one_chip)] * 5 \
        + [_shape((DD_ROWS // PAGE_ROWS,), i32, one_chip)] * 2
    step = op.fused_step(edges, gamma, cfg.sketch_min_s, DD_ROWS,
                         PAGE_ROWS.bit_length() - 1, True)
    _fits_one_chip(step._jit.lower(
        *arenas, *tables, _shape((4, BUCKET), f32, one_chip)).compile())

