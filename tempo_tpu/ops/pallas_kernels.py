"""Pallas TPU kernels for the sketch-update plane.

The hot op of this framework is a masked segment scatter-add: N spans fold
into S series of {count, duration-sum, size, log2/DD histogram buckets}.
Three device formulations exist, and WHICH one wins depends on whether
the state is dense or paged:

1. **XLA scatter** (`ops/sketches.py` / `registry/metrics.py`,
   `.at[slots, ...].add`): XLA:TPU lowers batched scatters to a sort +
   segmented reduction. On DENSE state this is the production default;
   its speed on the chip is not measured on today's code (the served
   path runs on a v5e — `chip_smoke.py` — and nothing has timed it).
2. **MXU one-hot matmul** (`fused_spanmetrics_matmul`): each span block
   builds a one-hot slot matrix and a feature matrix
   (count|dur|size|hist-onehot), and the partial state is
   `onehotᵀ @ features` — a dense [S, F] accumulation on the systolic
   array across a sequential grid over span blocks. This is the
   canonical "scatter as matmul" TPU trick; it pays S*F*N FLOPs for a
   job that is information-theoretically O(N*F), so it could only win
   when S is tiny. **Refused by the v5e compiler, never run on a chip**
   (Mosaic: "XLA layout ({0:T(1024)}) does not match Mosaic layout
   ({0:T(512)}) for an operand of shape s32[16384]" — the 1-D
   `(block,)` operands); it runs in interpret mode only.
3. **Paged ragged fused update** (`paged_fused_update`, this PR): the
   paged layout (`registry/pages.py`) changed the shape of the problem.
   There the composed-scatter path (`ops/pages.py` `_fused_body`) issues
   SEVEN-to-EIGHT separate scatters per ingest batch — calls, latency
   sum, latency count, size, the latency histogram grid, the DDSketch
   grid + zeros, the moments row — and EVERY one re-gathers the same
   page-table indirection and pays its own sort + segmented reduction
   over the same slot vector. The information content of the batch did
   not grow eight-fold; the dispatch overhead did. This kernel is the
   "Ragged Paged Attention" formulation of the update (PAPERS.md): the
   per-role page tables ride as SCALAR-PREFETCH operands, the grid walks
   the logical pages of the series table, each grid step translates the
   page ONCE through the prefetched tables (data-dependent BlockSpec
   index maps — the RPA trick), accumulates every role's delta for that
   page in one VMEM-resident `onehotᵀ @ [all features]` MXU pass, and
   the pipeline writes each touched page back to its arena exactly once.
   Unbacked / discard slots redirect to the pool's reserved trash page
   (physical page 0, never allocated, predicated to stay zero), which
   keeps the dense `-1 drops` semantics without host-side filtering.

Numerics contract of the paged kernel (gated by the plane-fuzz
differential arm in tests/test_plane_fuzz.py):

- Integer-count planes — calls, latency bucket grid, latency count,
  DDSketch grid + zeros — are BIT-IDENTICAL to the composed-scatter
  path for unit and integer HT weights (f32 integer sums are exact below
  2^24 regardless of association), so `quantile()` off the DDSketch
  plane is bit-identical between kernel tiers.
- Float-sum planes (latency sum, size sum, moment sums, fractional
  weights) agree to f32 reduction-order tolerance (~1e-6 relative): the
  MXU reduces in tree order, the scatter in sort order.
- The optional compact-state tier (`compact=True`) stores counts and
  bucket grids as int32 (each dispatch's per-cell delta rounded to
  nearest — exact for integer weights, ≤0.5 absolute per touched cell
  per dispatch otherwise) and the latency sum as a bf16 Kahan PAIR
  (running sum + compensation, ~1% relative tolerance documented in the
  runbook "Choosing the update kernel"). The default `sketch: dd` f32
  tier stays bit-identical as above.

**Refused by the v5e compiler, never run on a chip.** Asked without a
chip (tests/test_chip_compile.py, the strict xfail), Mosaic refuses
`paged_fused_update` at the default paged shapes: "cannot statically
prove that index in dimension 0 is a multiple of 1024" at the
`slots_ref[pl.ds(base, blk)]` load; with the two repairs that message
names (1,024-aligned span chunks via `pl.multiple_of`, slots as a
`(1, n)` block) it next refuses "infer-vector-layout: unsupported shape
cast" at `tpu.reshape vector<1024xi1> -> vector<1024x1xi1>` — the
`[:, None]` column broadcasts the one-hot build rests on. More probably
waits behind it (1-D `(page_rows,)` arena blocks, `acc_ref[:, c]` column
reads, the unaligned feature concatenate). Everything this docstring
says about one page-table walk and one writeback per page describes the
formulation, checked in interpret mode on the CPU only; the tier stays
opt-in (`spanmetrics.kernel: pallas`, default `xla`) and its ≥2x target
over the composed scatters has never been evaluated.

The dense MXU kernel is kept as the grid/BlockSpec/accumulator template
the paged kernel grew from; ROADMAP Design 9 decides whether either
stays.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_SLOT_DROPS = True  # slots < 0 contribute nothing (padding mask)


def _fused_kernel(slots_ref, dur_ref, size_ref, w_ref, out_ref, *,
                  n_series: int, n_buckets: int, edges):
    """One grid step: fold a span block into the [S, F] state block.

    Feature layout F = 3 + n_buckets:
      0: weighted count   1: weighted duration sum   2: weighted size sum
      3..: bucketed duration histogram (log2-spaced `edges` closed-over)
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    slots = slots_ref[:]                      # [N]
    dur = dur_ref[:]
    size = size_ref[:]
    w = jnp.where(slots >= 0, w_ref[:], 0.0)  # mask padding / dropped rows

    n = slots.shape[0]
    # one-hot slot matrix [N, S] — TPU needs 2D iota
    series_ids = jax.lax.broadcasted_iota(jnp.int32, (n, n_series), 1)
    onehot = jnp.where(series_ids == slots[:, None], w[:, None], 0.0)

    # per-span feature matrix [N, F]; edges unroll statically (python
    # floats — pallas kernels cannot capture traced array constants)
    bucket = jnp.zeros((n,), jnp.int32)
    for e in edges:
        bucket = bucket + (dur > e).astype(jnp.int32)
    bucket_ids = jax.lax.broadcasted_iota(jnp.int32, (n, n_buckets), 1)
    hist = jnp.where(bucket_ids == bucket[:, None], 1.0, 0.0)
    feats = jnp.concatenate(
        [jnp.ones((n, 1), jnp.float32), dur[:, None], size[:, None], hist],
        axis=1)

    # precision=HIGHEST: the MXU would otherwise contract in bf16, drifting
    # ~0.4% from the exact scatter — unacceptable for count-exact metrics.
    out_ref[:] += jax.lax.dot_general(
        onehot, feats, dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def fused_spanmetrics_matmul(slots, dur_s, sizes, weights, *,
                             n_series: int, edges: tuple,
                             block: int = 512, interpret: bool = False):
    """MXU formulation of the fused spanmetrics update.

    Returns [n_series, 3 + len(edges)+1] f32: count | dur_sum | size_sum |
    histogram buckets. Pure function of the batch (caller adds to state).
    """
    n = slots.shape[0]
    assert n % block == 0, (n, block)
    n_buckets = len(edges) + 1
    f = 3 + n_buckets
    kernel = functools.partial(
        _fused_kernel, n_series=n_series, n_buckets=n_buckets,
        edges=tuple(float(e) for e in edges))
    return pl.pallas_call(
        kernel,
        grid=(n // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))
                  for _ in range(4)],
        out_specs=pl.BlockSpec((n_series, f), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_series, f), jnp.float32),
        interpret=interpret,
    )(slots, dur_s, sizes, weights)


def fused_spanmetrics_scatter(slots, dur_s, sizes, weights, *,
                              n_series: int, edges: tuple):
    """The XLA-scatter formulation producing the same [S, F] output, for
    apples-to-apples benchmarking against the Pallas matmul kernel."""
    n_buckets = len(edges) + 1
    f = 3 + n_buckets
    keep = slots >= 0
    s = jnp.where(keep, slots, n_series)     # OOB + drop = masked
    w = jnp.where(keep, weights, 0.0)
    out = jnp.zeros((n_series, f), jnp.float32)
    out = out.at[s, 0].add(w, mode="drop")
    out = out.at[s, 1].add(dur_s * w, mode="drop")
    out = out.at[s, 2].add(sizes * w, mode="drop")
    bucket = jnp.searchsorted(jnp.asarray(edges, jnp.float32), dur_s,
                              side="left")
    out = out.at[s, 3 + bucket].add(w, mode="drop")
    return out


# ---------------------------------------------------------------------------
# the paged ragged fused update (ROADMAP item 2 / "Ragged Paged Attention")
# ---------------------------------------------------------------------------

def _round_i32(x):
    """Compact-tier integer projection: nearest int of the accumulated
    f32 delta — exact for unit/integer HT weights."""
    return jnp.round(x).astype(jnp.int32)


def paged_fused_update(tables, slots, vals, arenas, *, page_rows: int,
                       edges: tuple, gamma: float, min_value: float,
                       dd_rows: int, mom_rows: int,
                       mom_meta: "tuple | None",
                       compact: bool = False, interpret: bool = False,
                       span_block: int = 512):
    """One Pallas pass updating the whole spanmetrics plane family.

    Arguments (all shapes static under jit):
      tables  [R, P] int32 — per-role page tables stacked and padded to
              the series table's logical page count P with -1 (unbacked).
              Physical page 0 is the pool's reserved trash page; no real
              page ever maps there.
      slots   [N] int32 — logical series slots; negative = discard.
      vals    [3, N] f32 — (dur_s, size_bytes, weights) rows.
      arenas  role-aligned plane arenas, the `ops.pages._fused_body`
              order: (calls, hist_sums, hist_counts, sizes, hist_buckets
              [, dd_zeros, dd_counts][, moments]). All share the same row
              count (pool arenas are sized process-wide).

    Static meta mirrors `ops.pages.fused_step`: `edges` (latency
    histogram), `gamma`/`min_value` (DDSketch), `dd_rows`/`mom_rows`
    (sketch-plane slot limits, 0 = tier off), `mom_meta` = (k, lo, hi).
    `compact` expects int32 count arenas + a [rows, 2] bf16 Kahan-pair
    sums arena (see module docstring). Returns the updated arenas
    (aliased in-place on TPU via input_output_aliases).

    Grid = one step per LOGICAL page of the series table. Each step
    reads every role's physical page for this logical page from the
    scalar-prefetched tables (one page-table walk), accumulates all
    roles' deltas in a single [page_rows, F_total] VMEM scratch via one
    one-hot MXU contraction per span chunk, and writes each role's page
    back once through the pipelined BlockSpec (unbacked roles redirect
    to the trash page and write it back unchanged).
    """
    n_roles = len(arenas)
    dd = dd_rows > 0
    mom = mom_rows > 0
    want = 5 + (2 if dd else 0) + (1 if mom else 0)
    if n_roles != want:   # real error, not assert: -O must not strip it
        raise ValueError(
            f"paged_fused_update: {n_roles} arenas for dd_rows={dd_rows} "
            f"mom_rows={mom_rows} (want {want})")
    n = slots.shape[0]
    p_pages = tables.shape[1]
    # span-chunk size: the largest divisor of n up to span_block (gcd —
    # coalescer buckets are pow-2 multiples of a configurable floor, so
    # a non-pow-2 floor like 96 must shrink the chunk, not crash)
    blk = math.gcd(n, span_block) if n > span_block else n
    n_chunks = n // blk
    edges = tuple(float(e) for e in edges)
    n_hist = len(edges) + 1
    shift = page_rows.bit_length() - 1
    if page_rows != 1 << shift:
        raise ValueError(f"page_rows {page_rows} must be a power of two")

    # feature-plane layout of the single accumulation scratch
    c_calls, c_hsum, c_hcnt, c_size = 0, 1, 2, 3
    s_hist = slice(4, 4 + n_hist)
    f_total = 4 + n_hist
    if dd:
        nb_dd = arenas[6].shape[-1]
        c_ddz = f_total
        s_dd = slice(f_total + 1, f_total + 1 + nb_dd)
        f_total += 1 + nb_dd
    if mom:
        mk, mlo, mhi = mom_meta
        s_mom = slice(f_total, f_total + mk + 1)
        f_total += mk + 1
    log_gamma = math.log(gamma) if dd else 1.0

    def kernel(tables_ref, slots_ref, vals_ref, *refs):
        ins = refs[:n_roles]
        outs = refs[n_roles:2 * n_roles]
        acc_ref, bounds_ref = refs[2 * n_roles:]
        t = pl.program_id(0)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        bounds_ref[...] = jnp.zeros_like(bounds_ref)

        def chunk(c, carry):
            base = c * blk
            sl = slots_ref[pl.ds(base, blk)]
            dur = vals_ref[0, pl.ds(base, blk)]
            size = vals_ref[1, pl.ds(base, blk)]
            w = vals_ref[2, pl.ds(base, blk)]
            lp = lax.shift_right_arithmetic(sl, shift)
            off = lax.bitwise_and(sl, page_rows - 1)
            inpage = (sl >= 0) & (lp == t)
            row_ids = lax.broadcasted_iota(jnp.int32, (blk, page_rows), 1)
            onehot = jnp.where((row_ids == off[:, None]) & inpage[:, None],
                               1.0, 0.0)
            # latency histogram bucket (static edges unroll, like the
            # dense kernel — pallas cannot capture traced constants)
            hbucket = jnp.zeros((blk,), jnp.int32)
            for e in edges:
                hbucket = hbucket + (dur > e).astype(jnp.int32)
            hist_ids = lax.broadcasted_iota(jnp.int32, (blk, n_hist), 1)
            feats = [w[:, None], (dur * w)[:, None], w[:, None],
                     (size * w)[:, None],
                     jnp.where(hist_ids == hbucket[:, None], w[:, None],
                               0.0)]
            if dd:
                ddm = jnp.where(sl < dd_rows, 1.0, 0.0) * w
                is_zero = dur <= min_value
                idx = jnp.ceil(
                    jnp.log(jnp.maximum(dur, min_value) / min_value)
                    / log_gamma)
                idx = jnp.clip(idx, 0, nb_dd - 1).astype(jnp.int32)
                dd_ids = lax.broadcasted_iota(jnp.int32, (blk, nb_dd), 1)
                feats.append(jnp.where(is_zero, ddm, 0.0)[:, None])
                feats.append(jnp.where(
                    dd_ids == idx[:, None],
                    jnp.where(is_zero, 0.0, ddm)[:, None], 0.0))
            if mom:
                from tempo_tpu.ops.moments import moments_basis
                mm = jnp.where(sl < mom_rows, 1.0, 0.0)
                z, basis = moments_basis(dur, mk, mlo, mhi)
                feats.append(basis * (w * mm)[:, None])
                # support bounds ride a masked segment-max, not the
                # matmul: both columns are non-negative with 0 == empty,
                # so the zero fill is the max identity
                sel = (row_ids == off[:, None]) & inpage[:, None] \
                    & (sl < mom_rows)[:, None]
                b1 = jnp.where(sel, jnp.maximum(z - mlo, 0.0)[:, None], 0.0)
                b2 = jnp.where(sel, jnp.maximum(mhi - z, 0.0)[:, None], 0.0)
                bounds_ref[:, 0] = jnp.maximum(bounds_ref[:, 0],
                                               jnp.max(b1, axis=0))
                bounds_ref[:, 1] = jnp.maximum(bounds_ref[:, 1],
                                               jnp.max(b2, axis=0))
            fmat = jnp.concatenate(feats, axis=1)
            # the whole plane family in ONE MXU contraction per chunk;
            # HIGHEST precision — bf16 contraction drift is unacceptable
            # for count-exact metrics (same constraint as the dense
            # kernel above)
            acc_ref[...] += lax.dot_general(
                onehot, fmat, dimension_numbers=(((0,), (0,)), ((), ())),
                precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            return carry

        lax.fori_loop(0, n_chunks, chunk, 0)

        def combined(r, delta_cols):
            """in + delta under the role arena's storage rule."""
            ref = ins[r]
            if compact and ref.dtype == jnp.int32:
                return ref[...] + _round_i32(delta_cols)
            return ref[...] + delta_cols

        def write(r, new):
            # unbacked role page → the index map redirected every ref to
            # the trash page; write it back unchanged so it stays zero
            valid = tables_ref[r, t] > 0
            outs[r][...] = jnp.where(valid, new, ins[r][...])

        write(0, combined(0, acc_ref[:, c_calls]))
        if compact:
            # bf16 Kahan pair: stored (sum, compensation); the f32 page
            # delta folds in with the classic compensated step
            s = ins[1][:, 0].astype(jnp.float32)
            comp = ins[1][:, 1].astype(jnp.float32)
            y = acc_ref[:, c_hsum] + comp
            tot = s + y
            comp_new = y - (tot - s)
            write(1, jnp.stack([tot, comp_new],
                               axis=1).astype(ins[1].dtype))
        else:
            write(1, combined(1, acc_ref[:, c_hsum]))
        write(2, combined(2, acc_ref[:, c_hcnt]))
        write(3, combined(3, acc_ref[:, c_size]))
        write(4, combined(4, acc_ref[:, s_hist]))
        if dd:
            write(5, combined(5, acc_ref[:, c_ddz]))
            write(6, combined(6, acc_ref[:, s_dd]))
        if mom:
            r = n_roles - 1
            old = ins[r][...]
            new = old.at[:, :mk + 1].add(acc_ref[:, s_mom])
            new = new.at[:, mk + 1].set(
                jnp.maximum(old[:, mk + 1], bounds_ref[:, 0]))
            new = new.at[:, mk + 2].set(
                jnp.maximum(old[:, mk + 2], bounds_ref[:, 1]))
            write(r, new)

    def spec(r, arena):
        if arena.ndim == 1:
            return pl.BlockSpec(
                (page_rows,),
                lambda t, tr, r=r: (jnp.maximum(tr[r, t], 0),))
        return pl.BlockSpec(
            (page_rows, arena.shape[1]),
            lambda t, tr, r=r: (jnp.maximum(tr[r, t], 0), 0))

    arena_specs = [spec(r, a) for r, a in enumerate(arenas)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(p_pages,),
        in_specs=[
            pl.BlockSpec((n,), lambda t, tr: (0,)),
            pl.BlockSpec((3, n), lambda t, tr: (0, 0)),
            *arena_specs,
        ],
        out_specs=list(arena_specs),
        scratch_shapes=[
            pltpu.VMEM((page_rows, f_total), jnp.float32),
            pltpu.VMEM((page_rows, 2), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arenas],
        # inputs are (tables, slots, vals, *arenas): arena i aliases out i
        input_output_aliases={3 + i: i for i in range(n_roles)},
        interpret=interpret,
    )(tables, jnp.asarray(slots, jnp.int32), vals, *arenas)
    return tuple(out)
