"""Mesh construction and the sharded aggregation step.

Parallelism axes (the analog of the reference's strategies, SURVEY.md §2.6):

- `data`: span batches are split across devices — the ring-of-ingesters /
  shuffle-shard fan-out (`distributor.go:511-547`) becomes a sharded array
  dimension. Registry updates happen on local shards; the quorum-merge
  becomes a `psum` over this axis.
- `series`: metric series slots are sharded — the per-instance registry
  partitioning becomes a sharded state dimension. Each device owns
  max_active_series / series_shards slots; a slot's owner is slot//shard_cap,
  so updates need no all-to-all (mirroring how the reference routes series to
  exactly one generator instance via the partition ring).

The canonical step below (spanmetrics fused update under shard_map) is what
`__graft_entry__.dryrun_multichip` compiles across an N-device mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tempo_tpu.ops import sketches
from tempo_tpu.registry import metrics as rm


def validate_mesh_shape(n_devices: int, series_shards: int) -> list[str]:
    """Config-style problem list for a proposed mesh shape (empty = ok).
    Shared by `config.check()` (the `mesh:` block warnings) and the mesh
    constructors, so a bad shard count surfaces as a standard config
    warning at load time instead of an AssertionError at serve time."""
    problems = []
    if series_shards < 1:
        problems.append(f"mesh series_shards must be >= 1 "
                        f"(got {series_shards})")
    elif series_shards > n_devices:
        problems.append(f"mesh series_shards ({series_shards}) exceeds the "
                        f"device count ({n_devices}): shards <= devices")
    elif n_devices % series_shards:
        problems.append(f"mesh series_shards ({series_shards}) must divide "
                        f"the device count ({n_devices})")
    return problems


def make_mesh(n_devices: int | None = None, series_shards: int = 1) -> Mesh:
    """2D mesh ('data', 'series'). series_shards must divide device count."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    devs = np.array(devs[:n])
    problems = validate_mesh_shape(n, series_shards)
    if problems:
        raise ValueError("; ".join(problems))
    return Mesh(devs.reshape(n // series_shards, series_shards), ("data", "series"))


def make_multihost_mesh(series_shards: int = 1) -> Mesh:
    """Multi-host mesh: 'data' spans hosts (DCN), 'series' stays within a
    host's slice (ICI) — collectives on 'series' ride ICI, the data-psum
    crosses DCN once per step, mirroring how the reference keeps ingester
    traffic local and only ships merged series to the frontend.

    Falls back to the flat single-host mesh when only one process exists.
    """
    if jax.process_count() == 1:
        return make_mesh(series_shards=series_shards)
    from jax.experimental import mesh_utils

    per_host = jax.local_device_count()
    problems = validate_mesh_shape(per_host, series_shards)
    if problems:
        raise ValueError("; ".join(problems))
    devs = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=(per_host // series_shards, series_shards),
        dcn_mesh_shape=(jax.process_count(), 1))
    return Mesh(devs, ("data", "series"))


def mesh_fingerprint(mesh: Mesh) -> tuple:
    """Value identity for a mesh, safe to key caches on. `id(mesh)` is NOT:
    ids are reused after garbage collection, so a cache keyed on it can
    alias a dead mesh's jitted step onto a brand-new mesh with a
    different device layout."""
    return (tuple(mesh.shape.items()),
            tuple(d.id for d in mesh.devices.flat))


def shard_batch_arrays(mesh: Mesh, arrays: dict) -> dict:
    """Place host batch columns with leading dim sharded over 'data'."""
    sh = NamedSharding(mesh, P("data"))
    return {k: jax.device_put(v, sh) for k, v in arrays.items()}


def merge_sketch_states(state, axis_name: str = "data"):
    """Collective merge of sketch/registry pytrees inside shard_map/pjit:
    HLL registers merge with pmax, everything else (counts/sums) with psum."""

    def merge(path, leaf):
        if any(getattr(p, "name", "") == "registers" for p in path):
            return jax.lax.pmax(leaf, axis_name)
        return jax.lax.psum(leaf, axis_name)

    return jax.tree_util.tree_map_with_path(merge, state)


def sharded_spanmetrics_step(mesh: Mesh, edges: tuple, gamma: float,
                             min_value: float):
    """Build the jitted multi-device spanmetrics step over `mesh`.

    Layout: span columns sharded over 'data' (replicated over 'series');
    registry state arrays sharded over 'series' on their slot dim and
    replicated over 'data'. Each device updates only the slots it owns; a
    psum over 'data' yields the global state — the collective that replaces
    the reference's frontend combiner tree.

    Takes/returns raw arrays (static hyperparams via closure) so the
    shard_map in/out specs are flat.
    """

    def step(calls_v, h_buckets, h_sums, h_counts, size_v, dd_counts,
             dd_zeros, slots, dur_s, sizes, weights):
        shard_cap = calls_v.shape[0]  # local slot count
        my_shard = jax.lax.axis_index("series")
        owner = jnp.where(slots >= 0, slots // shard_cap, -1)
        local = jnp.where(owner == my_shard, slots - my_shard * shard_cap, -1)

        # Updates start from ZERO states so only the delta is psum'd over
        # 'data' (the base state is replicated across data shards; summing it
        # would multiply prior state by the data-shard count every step).
        z = jnp.zeros_like
        calls_d = rm.counter_update(rm.CounterState(z(calls_v)), local, weights)
        hist_d = rm.histogram_update(
            rm.HistogramState(z(h_buckets), z(h_sums), z(h_counts), edges),
            local, dur_s, weights)
        size_d = rm.counter_update(rm.CounterState(z(size_v)), local,
                                   sizes * weights)
        keep = local >= 0
        dd_d = sketches.dd_update(
            sketches.DDSketch(z(dd_counts), z(dd_zeros), gamma, min_value),
            jnp.where(keep, local, 0), dur_s, mask=keep, weights=weights)
        deltas = (calls_d.values, hist_d.bucket_counts, hist_d.sums,
                  hist_d.counts, size_d.values, dd_d.counts, dd_d.zeros)
        base = (calls_v, h_buckets, h_sums, h_counts, size_v, dd_counts, dd_zeros)
        return tuple(b + jax.lax.psum(d, "data") for b, d in zip(base, deltas))

    state_specs = (P("series"), P("series", None), P("series"), P("series"),
                   P("series"), P("series", None), P("series"))
    batch_specs = (P("data"),) * 4
    fn = _shard_map(step, mesh=mesh,
                    in_specs=state_specs + batch_specs,
                    out_specs=state_specs)
    return jax.jit(fn)


def sharded_serving_step(mesh: Mesh, edges: tuple, gamma: float,
                         min_value: float, capacity: int, dd_rows: int,
                         packed: bool = False, mom_rows: int = 0,
                         mom_meta: "tuple | None" = None):
    """The MESH-RESIDENT serving twin of `sharded_spanmetrics_step`:
    the fused spanmetrics update a `SpanMetricsProcessor` dispatches when
    the process serving mesh is on (`tempo_tpu.parallel.serving`).

    Differences from the dryrun step above:

    - **Donated**: the state arrays (the ~90MB fused plane at default
      capacity) are donated like the single-device fast paths — one
      live copy per shard, no per-push state copy. Callers hold the
      registry `state_lock` across dispatch + rebind, same discipline as
      `_fused_update_donated`.
    - **Sketch plane capacity**: the DDSketch plane may be SMALLER than
      the series table (`sketch_max_series < max_active_series`), so its
      slot→shard mapping uses its own shard capacity; slots beyond the
      plane are masked, matching `_fused_update_impl`. `dd_rows=0`
      builds a sketchless step (no dd arguments at all).
    - **Bit-stability across series shard counts**: each series shard
      scatters the SAME batch rows in the same order into the slots it
      owns (others drop), so per-slot float accumulation order is
      independent of `series_shards` — collect() is bit-identical at
      every shard count as long as the data axis stays fixed. (Changing
      DATA shards changes psum association: close, not bit-equal.)
    - **Packed form** (`packed=True`): the batch arrives as ONE
      [4, bucket] f32 matrix (slots, dur_s, sizes, weights) sharded
      over 'data' on its column axis — a single H2D per dispatch, the
      mesh twin of `_fused_update_packed4`. Slot ids ride f32 exactly
      under the caller's capacity < 2^24 gate.

    `mom_rows` / `mom_meta` = (k, lo, hi): the moments-sketch sidecar
    plane (ops/moments.py) — rides the same slot→shard mapping as the
    DDSketch plane; its state array appends AFTER the dd pair. Combine
    on the data axis: the moment-sum columns psum like every counter,
    the two bound columns pmax (see `moments_merge`).

    Returns jit(fn(states..., slots, dur_s, sizes, weights) -> states)
    — or jit(fn(states..., packed_matrix) -> states) when `packed`.
    """
    from tempo_tpu.ops import moments as msk

    n_series_shards = mesh.shape["series"]
    data_shards = mesh.shape["data"]
    if capacity % n_series_shards or \
            (dd_rows and dd_rows % n_series_shards) or \
            (mom_rows and mom_rows % n_series_shards):
        raise ValueError(
            f"serving mesh: state capacities ({capacity}, dd {dd_rows}, "
            f"moments {mom_rows}) must divide by series_shards "
            f"({n_series_shards})")
    shard_cap = capacity // n_series_shards
    dd_shard = dd_rows // n_series_shards if dd_rows else 0
    mom_shard = mom_rows // n_series_shards if mom_rows else 0
    n_sketch = (2 if dd_shard else 0) + (1 if mom_shard else 0)

    # the name is the step's handle in a profile (module
    # `jit__fused_update_mesh_impl`): the chip benchmark's roofline reader
    # finds the kernel by it, and tests/test_spans.py pins it
    def _fused_update_mesh_impl(calls_v, h_buckets, h_sums, h_counts, size_v,
                                *rest):
        sk = rest[:n_sketch]
        dd_counts = dd_zeros = mom_data = None
        if dd_shard:
            dd_counts, dd_zeros = sk[0], sk[1]
        if mom_shard:
            mom_data = sk[-1]
        rest = rest[n_sketch:]
        if packed:
            mat = rest[0]
            slots = mat[0].astype(jnp.int32)
            dur_s, sizes, weights = mat[1], mat[2], mat[3]
        else:
            slots, dur_s, sizes, weights = rest
        my_shard = jax.lax.axis_index("series")
        owner = jnp.where(slots >= 0, slots // shard_cap, -1)
        local = jnp.where(owner == my_shard, slots - my_shard * shard_cap, -1)
        if dd_shard:
            # the sketch plane's OWN slot→shard mapping (it may be a
            # strict prefix of the series table)
            dd_keep = (slots >= 0) & (slots < dd_rows) & \
                (slots // dd_shard == my_shard)
            local_dd = jnp.where(dd_keep, slots - my_shard * dd_shard, 0)
        if mom_shard:
            mom_keep = (slots >= 0) & (slots < mom_rows) & \
                (slots // mom_shard == my_shard)
            local_mom = jnp.where(mom_keep, slots - my_shard * mom_shard, -1)
            mk, mlo, mhi = mom_meta
        if data_shards == 1:
            # series-only layout (the serving default): each shard owns
            # its slots OUTRIGHT, so the scatter lands straight in the
            # donated base state — no zero-delta staging, no full-state
            # add, no collective at all. This is also what keeps the
            # update cost per dispatch O(batch + touched rows) instead
            # of O(state): the delta+psum form below walks the whole
            # ~90MB fused plane every dispatch.
            calls = rm.counter_update(rm.CounterState(calls_v), local,
                                      weights)
            hist = rm.histogram_update(
                rm.HistogramState(h_buckets, h_sums, h_counts, edges),
                local, dur_s, weights)
            size_c = rm.counter_update(rm.CounterState(size_v), local,
                                       sizes * weights)
            out = (calls.values, hist.bucket_counts, hist.sums, hist.counts,
                   size_c.values)
            if dd_shard:
                dd = sketches.dd_update(
                    sketches.DDSketch(dd_counts, dd_zeros, gamma, min_value),
                    local_dd, dur_s, mask=dd_keep, weights=weights)
                out += (dd.counts, dd.zeros)
            if mom_shard:
                mom = msk.moments_update(
                    msk.MomentsSketch(mom_data, mk, mlo, mhi),
                    local_mom, dur_s, mask=mom_keep, weights=weights)
                out += (mom.data,)
            return out
        # data-parallel layout: deltas from ZERO state so only the delta
        # psums over 'data' (the base state is replicated across data
        # shards; summing it would multiply prior state every step)
        z = jnp.zeros_like
        calls_d = rm.counter_update(rm.CounterState(z(calls_v)), local,
                                    weights)
        hist_d = rm.histogram_update(
            rm.HistogramState(z(h_buckets), z(h_sums), z(h_counts), edges),
            local, dur_s, weights)
        size_d = rm.counter_update(rm.CounterState(z(size_v)), local,
                                   sizes * weights)
        deltas = [calls_d.values, hist_d.bucket_counts, hist_d.sums,
                  hist_d.counts, size_d.values]
        base = [calls_v, h_buckets, h_sums, h_counts, size_v]
        if dd_shard:
            dd_d = sketches.dd_update(
                sketches.DDSketch(z(dd_counts), z(dd_zeros), gamma,
                                  min_value),
                local_dd, dur_s, mask=dd_keep, weights=weights)
            deltas += [dd_d.counts, dd_d.zeros]
            base += [dd_counts, dd_zeros]
        out = [b + jax.lax.psum(d, "data") for b, d in zip(base, deltas)]
        if mom_shard:
            # the moments delta: sum columns psum like every counter;
            # the two bound columns combine with pmax (support maxes)
            mom_d = msk.moments_update(
                msk.MomentsSketch(z(mom_data), mk, mlo, mhi),
                local_mom, dur_s, mask=mom_keep, weights=weights).data
            summed = mom_data[..., :mk + 1] + \
                jax.lax.psum(mom_d[..., :mk + 1], "data")
            bounds = jnp.maximum(mom_data[..., mk + 1:],
                                 jax.lax.pmax(mom_d[..., mk + 1:], "data"))
            out.append(jnp.concatenate([summed, bounds], axis=-1))
        return tuple(out)

    n_states = 5 + n_sketch
    state_specs = (P("series"), P("series", None), P("series"), P("series"),
                   P("series"))
    if dd_shard:
        state_specs += (P("series", None), P("series"))
    if mom_shard:
        state_specs += (P("series", None),)
    batch_specs = (P(None, "data"),) if packed else (P("data"),) * 4
    # check_vma=False: the base-scatter branch's outputs ARE replicated
    # over 'data' (the axis has size 1 there), but without a psum the
    # static replication checker can't infer it
    fn = _shard_map(_fused_update_mesh_impl, mesh=mesh,
                    in_specs=state_specs + batch_specs,
                    out_specs=state_specs, check_vma=False)
    # instrumented: compiles of the serving step are counted per fn
    # (`tempo_jax_jit_compile_total`; the cells' judges refuse a run
    # that compiled inside its window)
    from tempo_tpu.obs.jaxruntime import instrumented_jit

    return instrumented_jit(fn, name="spanmetrics_fused_update_mesh",
                            donate_argnums=tuple(range(n_states)))


def sharded_query_range_step(mesh: Mesh, n_buckets: int = 0):
    """Multi-device TraceQL-metrics observation: the sequence-parallel scan.

    The reference shards a query's *time/span space* into jobs combined at
    the frontend (`metrics_query_range_sharder.go` + `combiner/`); here the
    span batch is the sharded sequence dimension and the combine is one
    psum. Layout: spans (slots/steps/values) sharded over 'data'; the
    [series, steps] (or [series, steps, buckets] when n_buckets>0 — the
    quantile histogram plane) grid sharded over 'series' on dim 0. Each
    device scatter-adds its span shard into the slots it owns; psum over
    'data' is the cross-shard combine.

    Returns jit(fn(grid, slots, steps, values) -> grid).
    """

    def step(grid, slots, steps, values):
        shard_cap = grid.shape[0]
        my_shard = jax.lax.axis_index("series")
        owner = jnp.where(slots >= 0, slots // shard_cap, -1)
        local = jnp.where(owner == my_shard, slots - my_shard * shard_cap,
                          shard_cap)  # OOB row + mode=drop = masked
        delta = jnp.zeros_like(grid)
        if n_buckets:
            b = jnp.clip(jnp.ceil(jnp.log2(jnp.maximum(values, 1.0))),
                         0, n_buckets - 1).astype(jnp.int32)
            delta = delta.at[local, steps, b].add(1.0, mode="drop")
        else:
            delta = delta.at[local, steps].add(values, mode="drop")
        return grid + jax.lax.psum(delta, "data")

    grid_spec = P("series", None, None) if n_buckets else P("series", None)
    fn = _shard_map(step, mesh=mesh,
                    in_specs=(grid_spec, P("data"), P("data"), P("data")),
                    out_specs=grid_spec)
    return jax.jit(fn)
