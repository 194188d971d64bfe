"""Device compaction ops: merge, dedup, and re-sort of block spans.

The host compactor (`db/compactor.py`) merges K sorted trace streams
with `heapq.merge` and dedups spans per trace via `combine_spans`
(first occurrence of a span_id wins, concatenation order preserved).
That contract is reproduced here as two `lax.sort` passes over the
concatenated span rows of all input blocks — one device dispatch per
pow-2 shape bucket:

1. sort by (trace_id limbs, span_id limbs, concat row) — runs of equal
   (trace, span) ids become adjacent with the FIRST concatenated
   occurrence leading, so a first-of-run flag scattered back to the
   original row index is exactly `combine_spans`' keep set;
2. sort by (trace_id limbs, concat row) — the output permutation:
   traces ascend by trace-id *bytes* and spans within a trace keep
   concatenation (= block, then row) order, which is exactly what
   `heapq.merge` over per-block streams yields (streams are keyed by
   trace-id bytes and the merge is stable in block order).

Trace ids ride as four **big-endian** uint32 limbs (span ids as two):
lexicographic limb order must equal bytes order, so the limbs are
byte-swapped on little-endian hosts — `ops/structure.py`'s
`id_limbs` is native-endian and would rank ids wrongly here.

`reference_merge_order` is the pure-Python oracle (explicit sorted()
over byte keys + per-trace seen-set); the differential tests diff the
kernel against it row by row.

The sidecar builder (`build_sidecar_arrays`) reuses the block-resident
columns to produce the per-block mergeable summaries: a moments row
per (service, name) series (`ops/moments.py`, k+3 floats) and one HLL
register row over trace ids (`ops/sketches.py`) — both fold across
blocks with elementwise add/max, which is what makes historical
quantiles a psum-style fold instead of a re-scan.
"""

from __future__ import annotations

import numpy as np

from tempo_tpu.obs.jaxruntime import instrumented_jit

_kernel_cache: dict = {}

# pad rows carry all-ones limbs so they sort after every real row; a
# real trace id of 16 0xFF bytes still wins via the row-index key.
_PAD = 0xFFFFFFFF


def _get_merge_kernel():
    got = _kernel_cache.get("merge")
    if got is not None:
        return got

    import jax
    import jax.numpy as jnp

    def kernel(t0, t1, t2, t3, s0, s1, valid):
        n = t0.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        # pass 1: adjacency by (trace, span) id, first concat row leads
        st0, st1, st2, st3, ss0, ss1, sidx = jax.lax.sort(
            (t0, t1, t2, t3, s0, s1, idx), num_keys=7)
        same = ((st0[1:] == st0[:-1]) & (st1[1:] == st1[:-1])
                & (st2[1:] == st2[:-1]) & (st3[1:] == st3[:-1])
                & (ss0[1:] == ss0[:-1]) & (ss1[1:] == ss1[:-1]))
        first = jnp.concatenate([jnp.ones(1, bool), ~same])
        keep = jnp.zeros(n, bool).at[sidx].set(
            first & valid[jnp.clip(sidx, 0, n - 1)])
        # pass 2: output order — trace-id bytes, then concat row
        _, _, _, _, perm = jax.lax.sort((t0, t1, t2, t3, idx), num_keys=5)
        return keep, perm

    got = instrumented_jit(kernel, name="compaction_merge")
    _kernel_cache["merge"] = got
    return got


def trace_id_limbs(mat: np.ndarray) -> tuple[np.ndarray, ...]:
    """Four uint32 limbs of an [n, 16] uint8 trace-id column, ordered so
    lexicographic limb comparison equals bytes comparison (big-endian
    reads, unlike `structure.id_limbs`)."""
    v = np.ascontiguousarray(mat, np.uint8).view(np.dtype(">u4"))
    v = v.astype(np.uint32)
    return v[:, 0], v[:, 1], v[:, 2], v[:, 3]


def span_id_limbs(mat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Two big-endian uint32 limbs of an [n, 8] uint8 span-id column."""
    v = np.ascontiguousarray(mat, np.uint8).view(np.dtype(">u4"))
    v = v.astype(np.uint32)
    return v[:, 0], v[:, 1]


def pad_pow2(n: int, floor: int = 64) -> int:
    p = floor
    while p < n:
        p <<= 1
    return p


def merge_order(trace_id: np.ndarray, span_id: np.ndarray,
                n_pad: int | None = None) -> np.ndarray:
    """Device merge/dedup/re-sort over the concatenated rows of all
    input blocks (block order, row order within a block).

    Returns the output row order as indices into the concatenation:
    traces ascend by trace-id bytes, spans within a trace keep concat
    order, and duplicate (trace_id, span_id) pairs keep only their
    first occurrence — bit-compatible with `heapq.merge` +
    `combine_spans` in the host compactor.
    """
    n = len(trace_id)
    if n == 0:
        return np.zeros(0, np.int64)
    if n_pad is None:
        n_pad = pad_pow2(n)
    if not n <= n_pad:
        raise ValueError(f"bad pad: n={n}/{n_pad}")

    def pad1(a):
        out = np.full(n_pad, _PAD, np.uint32)
        out[:n] = a
        return out

    t0, t1, t2, t3 = trace_id_limbs(trace_id)
    s0, s1 = span_id_limbs(span_id)
    valid = np.zeros(n_pad, bool)
    valid[:n] = True
    kern = _get_merge_kernel()
    keep, perm = kern(pad1(t0), pad1(t1), pad1(t2), pad1(t3),
                      pad1(s0), pad1(s1), valid)
    keep = np.asarray(keep)
    perm = np.asarray(perm, np.int64)
    perm = perm[perm < n]
    return perm[keep[perm]]


def reference_merge_order(trace_id: np.ndarray,
                          span_id: np.ndarray) -> np.ndarray:
    """Pure-Python oracle for `merge_order`: stable sort on trace-id
    bytes, then a per-trace first-wins span_id seen set."""
    n = len(trace_id)
    order = sorted(range(n), key=lambda i: (bytes(trace_id[i]), i))
    seen: set[tuple[bytes, bytes]] = set()
    out = []
    for i in order:
        key = (bytes(trace_id[i]), bytes(span_id[i]))
        if key in seen:
            continue
        seen.add(key)
        out.append(i)
    return np.asarray(out, np.int64)


# ---------------------------------------------------------------------------
# sketch sidecars — per-block mergeable summaries built while resident
# ---------------------------------------------------------------------------

SIDECAR_HLL_PRECISION = 10   # 1024 int32 registers ≈ 3KB JSON per block


def _mix32(x: np.ndarray, salt: int) -> np.ndarray:
    """xorshift-multiply finalizer — cheap, stable across processes
    (unlike Python's salted hash())."""
    x = (x.astype(np.uint64) + np.uint64(salt)) & np.uint64(0xFFFFFFFF)
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x7FEB352D)) & np.uint64(0xFFFFFFFF)
    x ^= x >> np.uint64(15)
    x = (x * np.uint64(0x846CA68B)) & np.uint64(0xFFFFFFFF)
    x ^= x >> np.uint64(16)
    return x.astype(np.uint32)


def trace_hashes(trace_id: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Two quasi-independent uint32 hashes per trace id for `hll_update`.

    Both hashes see ALL 128 id bits, combined two different ways (xor vs
    multiply-add): low-entropy id generators that vary only one half
    still spread across registers, and the pair jointly keeps ~64 bits.
    """
    t0, t1, t2, t3 = trace_id_limbs(trace_id)
    a = _mix32(t0 ^ _mix32(t1, 0x9E3779B9), 0x85EBCA6B)
    b = _mix32(t2 ^ _mix32(t3, 0xC2B2AE35), 0x27D4EB2F)
    h1 = _mix32(a ^ b, 0x165667B1)
    h2 = _mix32((a.astype(np.uint64) * np.uint64(2654435761) + b)
                & np.uint64(0xFFFFFFFF), 0xD3A2646C)
    return h1, h2


def build_sidecar_arrays(series_ids: np.ndarray, duration_ns: np.ndarray,
                         n_series: int, trace_id: np.ndarray,
                         k: int, lo: float, hi: float
                         ) -> tuple[np.ndarray, np.ndarray]:
    """One device pass over block-resident columns → the sidecar planes.

    Returns (moment rows [n_series, k+3] f32, HLL registers [m] int32):
    a moments row per dense (service, name) series over span durations
    and one HLL row over trace ids (distinct-trace cardinality). Both
    merge across blocks elementwise (add / max).
    """
    from tempo_tpu.ops import moments as msk
    from tempo_tpu.ops import sketches as sk

    state = msk.moments_init(max(n_series, 1), k, min_value=float(np.exp(lo)),
                             max_value=float(np.exp(hi)))
    hll = sk.hll_init(1, precision=SIDECAR_HLL_PRECISION)
    if len(duration_ns):
        state = msk.moments_update(
            state, np.asarray(series_ids, np.int32),
            np.asarray(duration_ns, np.float32))
        h1, h2 = trace_hashes(trace_id)
        hll = sk.hll_update(hll, np.zeros(len(h1), np.int32), h1, h2)
    return (np.asarray(state.data, np.float32),
            np.asarray(hll.registers, np.int32)[0])


__all__ = ["merge_order", "reference_merge_order", "trace_id_limbs",
           "span_id_limbs", "pad_pow2", "build_sidecar_arrays",
           "trace_hashes", "SIDECAR_HLL_PRECISION"]
