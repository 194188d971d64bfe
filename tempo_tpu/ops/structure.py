"""Structural trace analytics: per-trace DAG reconstruction as device ops.

Given one cut batch of spans (many traces concatenated, pow-2 padded),
reconstruct every trace's parent-pointer forest and derive the two
structural signals the TAAF line of work argues are the real unit of
trace analysis:

- **critical path**: the chain of spans bounding the trace's end-to-end
  latency — the trace's anchor root (latest-finishing root span) down
  through each span's *bounding child* (the child that finishes last).
  Per-span self-time on that path is the span's end minus its on-path
  child's end (a leaf contributes its full duration), clamped at zero
  for async overlap.
- **error propagation**: for every errored span, the *root cause* is
  the deepest errored descendant reachable by repeatedly stepping to
  the latest-finishing errored child — the fixed point of that step
  function.

Everything is resolved with three vectorized primitives, so one jit
kernel per (span-bucket, trace-bucket) shape pair covers every cut:

1. parent-pointer resolution: a single stable multi-key `lax.sort`
   over 2N interleaved (definition, query) entries keyed by
   (trace, id_hi, id_lo, tag) with a last-non-null `associative_scan`
   — NOT an O(N^2) id comparison and NOT a host hash join;
2. lexicographic segment-argmax (3 `segment_max` passes over the
   (end_hi, end_lo, row) key) for bounding children, errored bounding
   children, and per-trace anchor roots — deterministic down to the
   row-index tiebreak so the pure-Python oracle can match bit-exactly;
3. log-depth pointer jumping (`ptr = ptr[ptr]` squaring) for on-path
   membership and the error fixed point: ⌈log2 N⌉+1 doublings cover any
   chain, so corrupt traces (parent cycles) TERMINATE and are flagged
   rather than hanging a worker — cycles never reach the sentinel and
   surface in the `cyclic` mask; unresolvable parent ids surface as
   orphans (parent_row == -2).

64-bit span ids and nanosecond end times ride as two uint32 limbs
(JAX runs in 32-bit mode); comparisons are exact, never float-ranked.

`reference_analysis` is the pure-Python oracle implementing the same
contract span by span — the differential tests diff the kernel against
it, so the tiebreak rules above are load-bearing, not stylistic.
"""

from __future__ import annotations

import math

import numpy as np

from tempo_tpu.obs.jaxruntime import instrumented_jit

# parent_row sentinels
ROOT = -1      # no parent id (all-zero parent span id)
ORPHAN = -2    # parent id set but unresolved within the trace at cut time

_kernel_cache: dict = {}


def _get_kernel():
    """Build the jitted kernel lazily (first cut pays the trace)."""
    got = _kernel_cache.get("k")
    if got is not None:
        return got

    import jax
    import jax.numpy as jnp

    def kernel(grp, id_hi, id_lo, pid_hi, pid_lo, has_parent,
               end_hi, end_lo, err, valid, *, t_pad):
        n = grp.shape[0]
        row = jnp.arange(n, dtype=jnp.int32)
        dump_g = jnp.int32(t_pad)

        # -- 1. parent resolution: sorted-id matching over 2N entries --
        # definition entries carry each span's own id, query entries its
        # parent id; after the stable 4-key sort every query sits right
        # of the definitions sharing its key (tag breaks the tie), and a
        # last-non-null scan hands it the latest matching definition.
        d_grp = jnp.where(valid, grp, dump_g)
        q_grp = jnp.where(valid & has_parent, grp, dump_g)
        e_grp = jnp.concatenate([d_grp, q_grp])
        e_hi = jnp.concatenate([id_hi, pid_hi])
        e_lo = jnp.concatenate([id_lo, pid_lo])
        e_tag = jnp.concatenate([jnp.zeros(n, jnp.int32),
                                 jnp.ones(n, jnp.int32)])
        e_row = jnp.concatenate([row, row])
        s_grp, s_hi, s_lo, s_tag, s_row = jax.lax.sort(
            (e_grp, e_hi, e_lo, e_tag, e_row), num_keys=4)
        s_def = jnp.where(s_tag == 0, s_row, -1)
        last_def = jax.lax.associative_scan(
            lambda a, b: jnp.where(b < 0, a, b), s_def)
        c = jnp.clip(last_def, 0, n - 1)
        okm = (last_def >= 0) & (s_tag == 1) & (s_grp < dump_g) \
            & (d_grp[c] == s_grp) & (id_hi[c] == s_hi) & (id_lo[c] == s_lo)
        hp = has_parent[jnp.clip(s_row, 0, n - 1)] \
            & valid[jnp.clip(s_row, 0, n - 1)]
        qval = jnp.where(okm, last_def, jnp.where(hp, ORPHAN, ROOT))
        parent = jnp.full(n, ROOT, jnp.int32).at[
            jnp.where(s_tag == 1, s_row, n)].set(qval, mode="drop")

        # -- 2. lexicographic segment argmax by (end_hi, end_lo, row) --
        def lex_argmax(ok, seg, nseg):
            mh = jax.ops.segment_max(jnp.where(ok, end_hi, 0), seg,
                                     num_segments=nseg)
            ok1 = ok & (end_hi == mh[seg])
            seg1 = jnp.where(ok1, seg, nseg - 1)
            ml = jax.ops.segment_max(jnp.where(ok1, end_lo, 0), seg1,
                                     num_segments=nseg)
            ok2 = ok1 & (end_lo == ml[seg1])
            seg2 = jnp.where(ok2, seg, nseg - 1)
            mr = jax.ops.segment_max(jnp.where(ok2, row, -1), seg2,
                                     num_segments=nseg)
            cnt = jax.ops.segment_sum(ok.astype(jnp.int32), seg,
                                      num_segments=nseg)
            return jnp.where(cnt > 0, mr, -1)

        is_child = valid & (parent >= 0)
        child_seg = jnp.where(is_child, parent, n)
        bc = lex_argmax(is_child, child_seg, n + 1)[:n]
        is_err_child = is_child & err
        ebc = lex_argmax(is_err_child,
                         jnp.where(is_err_child, parent, n), n + 1)[:n]
        is_root = valid & (parent == ROOT)
        anchor = lex_argmax(is_root, jnp.where(is_root, grp, t_pad),
                            t_pad + 1)[:t_pad]

        # -- 3a. on-path membership: AND-prefix over ancestor chains --
        pc = jnp.clip(parent, 0, n - 1)
        ga = anchor[jnp.clip(grp, 0, t_pad - 1)]
        is_bc = valid & jnp.where(parent >= 0, bc[pc] == row,
                                  (parent == ROOT) & (ga == row))
        # sentinel node n: ptr fixed point with val True — roots and
        # orphans park there (an orphan's False is_bc kills its subtree)
        ptr = jnp.concatenate([
            jnp.where(valid & (parent >= 0), parent, n),
            jnp.full(1, n, jnp.int32)])
        val = jnp.concatenate([is_bc, jnp.ones(1, bool)])
        k_iters = max(1, int(math.ceil(math.log2(max(n, 2)))) + 1)
        # fori_loop, NOT an unrolled Python loop: unrolling k_iters
        # dependent gather pairs makes XLA:CPU's fusion pass super-linear
        # in n (measured 149s compile at n=4096, >550s at 16384; ~1s
        # with the loop op at every size). Same values either way.
        val, ptr = jax.lax.fori_loop(
            0, k_iters,
            lambda _, c: (c[0] & c[0][c[1]], c[1][c[1]]), (val, ptr))
        on_path = val[:n] & (ptr[:n] == n) & valid
        cyclic = valid & (ptr[:n] != n)

        # -- 3b. error fixed point: squared composition of the errored-
        # bounding-child step (fixed points absorb; cycles terminate at
        # the iteration cap and are masked out host-side via `ebc`)
        g = jnp.where(ebc >= 0, ebc, row)
        rc = jax.lax.fori_loop(0, k_iters, lambda _, g: g[g], g)
        return parent, on_path, bc, ebc, rc, cyclic, anchor

    got = instrumented_jit(kernel, name="traceanalytics_structure",
                           static_argnames=("t_pad",))
    _kernel_cache["k"] = got
    return got


def _split_u64(vals: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(hi, lo) uint32 limbs of a non-negative int64 column."""
    v = np.asarray(vals, np.int64)
    return ((v >> 32) & 0xFFFFFFFF).astype(np.uint32), \
        (v & 0xFFFFFFFF).astype(np.uint32)


def id_limbs(id_mat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(hi, lo) uint32 limbs of an [n, 8] uint8 id column."""
    v = np.ascontiguousarray(id_mat, np.uint8).view(np.uint32)
    return v[:, 0].copy(), v[:, 1].copy()


def analyze(grp: np.ndarray, span_id: np.ndarray, parent_id: np.ndarray,
            end_ns: np.ndarray, err: np.ndarray, n_traces: int,
            n_pad: int, t_pad: int) -> dict[str, np.ndarray]:
    """Run the structural kernel over one cut batch.

    All inputs are length-n host arrays (n real spans); `grp` maps each
    span to its dense trace index in [0, n_traces). `n_pad`/`t_pad` are
    the pow-2 shape buckets (callers bucket so steady state re-traces
    nothing). Returns host arrays clipped back to n:
    parent_row ([n] int32, ROOT/ORPHAN sentinels), on_path, bounding
    child `bc`, errored bounding child `ebc`, error fixed point `rc`,
    `cyclic`, and the per-trace `anchor` root row ([n_traces] int32).
    """
    n = len(grp)
    if not (0 < n <= n_pad and 0 < n_traces <= t_pad):
        raise ValueError(f"bad pad: n={n}/{n_pad} t={n_traces}/{t_pad}")

    def pad1(a, fill, dtype):
        out = np.full(n_pad, fill, dtype)
        out[:n] = a
        return out

    id_hi, id_lo = id_limbs(span_id)
    pid_hi, pid_lo = id_limbs(parent_id)
    has_parent = (pid_hi != 0) | (pid_lo != 0)
    base = int(np.min(end_ns))
    end_hi, end_lo = _split_u64(np.asarray(end_ns, np.int64) - base)
    kern = _get_kernel()
    parent, on_path, bc, ebc, rc, cyclic, anchor = kern(
        pad1(grp, t_pad - 1, np.int32),
        pad1(id_hi, 0, np.uint32), pad1(id_lo, 0, np.uint32),
        pad1(pid_hi, 0, np.uint32), pad1(pid_lo, 0, np.uint32),
        pad1(has_parent, False, bool),
        pad1(end_hi, 0, np.uint32), pad1(end_lo, 0, np.uint32),
        pad1(err, False, bool), pad1(np.ones(n, bool), False, bool),
        t_pad=t_pad)
    return {
        "parent_row": np.asarray(parent)[:n],
        "on_path": np.asarray(on_path)[:n],
        "bc": np.asarray(bc)[:n],
        "ebc": np.asarray(ebc)[:n],
        "rc": np.asarray(rc)[:n],
        "cyclic": np.asarray(cyclic)[:n],
        "anchor": np.asarray(anchor)[:n_traces],
    }


# ---------------------------------------------------------------------------
# pure-Python oracle — the differential tests' reference
# ---------------------------------------------------------------------------

def reference_analysis(grp, span_id, parent_id, end_ns, err
                       ) -> dict[str, np.ndarray]:
    """Same contract as `analyze`, resolved span by span in plain
    Python. Every tiebreak matches the kernel: duplicate span ids
    resolve to the LARGEST row index; bounding children / anchors
    maximize (end_ns, row); cycles are chains that never terminate at a
    root or orphan; the error root cause descends latest-finishing
    errored children to a fixed point (cyclic error chains surface via
    `ebc[rc] >= 0` — callers mask them exactly like the kernel path)."""
    n = len(grp)
    grp = np.asarray(grp)
    end_ns = np.asarray(end_ns, np.int64)
    err = np.asarray(err, bool)
    sid = [bytes(span_id[i]) for i in range(n)]
    pid = [bytes(parent_id[i]) for i in range(n)]
    defs: dict[tuple[int, bytes], int] = {}
    for i in range(n):                       # last definition wins
        defs[(int(grp[i]), sid[i])] = i
    parent = np.full(n, ROOT, np.int32)
    for i in range(n):
        if pid[i] == b"\0" * 8:
            continue
        j = defs.get((int(grp[i]), pid[i]))
        parent[i] = ORPHAN if j is None else j
    children: dict[int, list[int]] = {}
    for i in range(n):
        if parent[i] >= 0:
            children.setdefault(int(parent[i]), []).append(i)

    def best(rows):
        return max(rows, key=lambda r: (int(end_ns[r]), r)) if rows else -1

    bc = np.full(n, -1, np.int32)
    ebc = np.full(n, -1, np.int32)
    for p, rows in children.items():
        bc[p] = best(rows)
        ebc[p] = best([r for r in rows if err[r]])
    n_traces = int(grp.max()) + 1 if n else 0
    anchor = np.full(n_traces, -1, np.int32)
    for t in range(n_traces):
        anchor[t] = best([i for i in range(n)
                          if int(grp[i]) == t and parent[i] == ROOT])
    on_path = np.zeros(n, bool)
    cyclic = np.zeros(n, bool)
    for i in range(n):
        path_ok, j, steps = True, i, 0
        while True:
            if steps > n:                    # never terminated: cycle
                cyclic[i] = True
                path_ok = False
                break
            if parent[j] == ORPHAN:
                path_ok = False
                break
            if parent[j] == ROOT:
                path_ok = path_ok and anchor[int(grp[j])] == j
                break
            path_ok = path_ok and bc[int(parent[j])] == j
            j = int(parent[j])
            steps += 1
        # every hop must ALSO be its parent's bounding child incl. i
        if path_ok and parent[i] >= 0:
            path_ok = bc[int(parent[i])] == i
        on_path[i] = path_ok
    rc = np.arange(n, dtype=np.int32)
    for i in range(n):
        j, steps = i, 0
        while ebc[j] >= 0 and steps <= n:
            j = int(ebc[j])
            steps += 1
        rc[i] = j
    return {"parent_row": parent, "on_path": on_path, "bc": bc,
            "ebc": ebc, "rc": rc, "cyclic": cyclic, "anchor": anchor}


def self_times_ns(start_ns, end_ns, res: dict) -> np.ndarray:
    """Per-span critical-path self-time (int64 ns, exact): end minus the
    on-path child's end, clamped at 0; an on-path leaf contributes its
    full duration. Zero off the path. Shared by the kernel path and the
    oracle so the decomposition rule lives in exactly one place."""
    start_ns = np.asarray(start_ns, np.int64)
    end_ns = np.asarray(end_ns, np.int64)
    bc = res["bc"]
    on = res["on_path"]
    child_end = np.where(bc >= 0, end_ns[np.clip(bc, 0, len(bc) - 1)],
                         start_ns)
    return np.where(on, np.maximum(end_ns - child_end, 0), 0)


__all__ = ["analyze", "reference_analysis", "self_times_ns", "id_limbs",
           "ROOT", "ORPHAN"]
