"""Device scan plane for backend blocks.

The storage-level first pass (`condition_mask`) evaluated every pushdown
predicate as a numpy mask over object-dtype string columns — the hot loop
of SURVEY §3.3 (ref `block_traceql.go:1538` compiling conditions into
per-value predicate iterators, `parquetquery/predicates.go:15`) never
touched the chip. Here the dictionary-coded form of the scan does:

- string columns stay dictionary-coded (parquet already stores them that
  way): codes are an int32 device column; a predicate becomes a tiny
  boolean lookup table built on host over the DICTIONARY (|dict| entries,
  not |rows|) — equality and full regex both cost O(|dict|) host work —
  then one device gather. This is the reference's dictionary-page
  predicate pushdown (`predicates.go` `*DictionaryPredicate`) turned into
  a gather instead of a page scan.
- integer columns (duration, kind, status, nested-set coords, int/bool
  attributes, timestamps) compare EXACTLY on device: each int64 value is
  split into two int32 halves (hi = v >> 31, lo = v & 0x7fffffff) and a
  literal compare becomes a lexicographic (hi, lo) compare — no float32
  rounding, so the device mask is bit-identical to the float64 numpy
  plane for every integral column (the whole intrinsic set is integral).
  Non-integral literals are normalized on host (`duration > 1.5` ⇒
  `>= 2`); genuinely float-valued attribute columns fall back to host.
- masks AND/OR-combine on device; one transfer returns the final mask.

Two planes share this machinery:

`device_pred_mask` — per-row-group sync offload for `condition_mask`,
OPT-IN via TEMPO_TPU_DEVICE_SCAN=1 (each mask pays a device round trip;
float32 compares). Kept for diagnostics.

`BlockScanPlane` — the PRODUCTION plane: per immutable block, columns are
adopted lazily (first query referencing a column pays one host factorize
+ upload; blocks are immutable so adoption is permanent), and a query's
whole first pass — predicates, time clip, row-group shard selection,
step bucketing, group-by, metric scatter — runs as ONE fused dispatch.
`db/tempodb.py` routes product search/query_range through it via
`db/plane_cache.py`.
"""

from __future__ import annotations

import functools
import re
import os
import threading
import time
from typing import Optional, Sequence

import numpy as np

from tempo_tpu.block.fetch import _dict_codes
from tempo_tpu.traceql import ast as A
from tempo_tpu.traceql.eval import (BOOL, KIND, NUM, STATUS, STR, Col,
                                    eval_expr)

_NUM_OPS = {A.Op.EQ, A.Op.NEQ, A.Op.GT, A.Op.GTE, A.Op.LT, A.Op.LTE}
_STR_OPS = {A.Op.EQ, A.Op.NEQ, A.Op.REGEX, A.Op.NOT_REGEX}

_NUM_INTRINSICS = {
    A.Intrinsic.DURATION: "duration",
    A.Intrinsic.KIND: "kind",
    A.Intrinsic.STATUS: "status",
    A.Intrinsic.NESTED_SET_LEFT: "nestedSetLeft",
    A.Intrinsic.NESTED_SET_RIGHT: "nestedSetRight",
    A.Intrinsic.NESTED_SET_PARENT: "nestedSetParent",
}

# static type → column type tag, for the reference's comparability lattice
# (`enum_statics.go`: status/kind/num are distinct; see eval._comparable)
_STATIC_T = {
    A.StaticType.INT: NUM, A.StaticType.FLOAT: NUM,
    A.StaticType.DURATION: NUM, A.StaticType.STRING: STR,
    A.StaticType.BOOL: BOOL, A.StaticType.STATUS: STATUS,
    A.StaticType.KIND: KIND,
}

_INT_MAX = 1 << 62   # |values| beyond this can't ride the hi/lo split


def enabled() -> bool:
    """Per-row-group sync offload policy for `condition_mask` — OPT-IN
    (TEMPO_TPU_DEVICE_SCAN=1): each synchronous mask pays a full device
    round trip and compares in float32. The block-level `BlockScanPlane`
    (one fused dispatch per block, exact int compares) is the production
    device plane."""
    return os.environ.get("TEMPO_TPU_DEVICE_SCAN", "") == "1"


# ---------------------------------------------------------------------------
# shared host-side predicate compilation
# ---------------------------------------------------------------------------

_STR_ORD = {A.Op.GT: lambda a, b: a > b, A.Op.GTE: lambda a, b: a >= b,
            A.Op.LT: lambda a, b: a < b, A.Op.LTE: lambda a, b: a <= b}


def _dict_term(op: A.Op, v, dvals: list):
    """Compile a string predicate over dictionary values into a (sig
    entry, lut) pair; None when the shape is unsupported. Regexes are
    ANCHORED (fullmatch), matching `eval.regex_match_col` / pkg/regexp.
    Ordered compares are lexicographic like the numpy plane's astype(str)
    compare."""
    if not isinstance(v, str):
        return None
    if op in (A.Op.EQ, A.Op.NEQ):
        matched = [i for i, s in enumerate(dvals) if s == v]
    elif op in _STR_ORD:
        f = _STR_ORD[op]
        matched = [i for i, s in enumerate(dvals) if f(s, v)]
    elif op in (A.Op.REGEX, A.Op.NOT_REGEX):
        try:
            rx = re.compile(v)
        except re.error:
            return None
        matched = [i for i, s in enumerate(dvals) if rx.fullmatch(s)]
    else:
        return None
    lut = np.zeros(len(dvals), bool)
    if matched:
        lut[np.asarray(matched)] = True
    return ("lut", None, op in (A.Op.NEQ, A.Op.NOT_REGEX)), lut


def _num_term(op: A.Op, v):
    """(sig entry, float literal) for a numeric compare; None otherwise."""
    if op not in _NUM_OPS or isinstance(v, (str, bytes)):
        return None
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return ("cmp", op, False), f


def _int_literal(op: A.Op, v) -> tuple:
    """Normalize (op, literal) for the exact integer plane.

    Returns ("const", bool) when the comparison is decidable on host
    (non-integral EQ, out-of-range literals) or ("icmp", op', int_lit).
    Non-integral range literals shift to the nearest integer bound:
    `v > 1.5` over ints ⟺ `v >= 2`; `v < 1.5` ⟺ `v <= 1`.
    """
    try:
        f = float(v)
    except (TypeError, ValueError):
        return ("const", False)
    if f != f:                                   # NaN compares are false
        return ("const", False)
    if float(f).is_integer() and abs(f) < _INT_MAX:
        return ("icmp", op, int(f))
    if op == A.Op.EQ:
        return ("const", False)
    if op == A.Op.NEQ:
        return ("const", True)
    if abs(f) >= _INT_MAX:
        big = f > 0
        if op in (A.Op.GT, A.Op.GTE):
            return ("const", not big)
        return ("const", big)                    # LT / LTE
    import math

    if op in (A.Op.GT, A.Op.GTE):
        return ("icmp", A.Op.GTE, int(math.ceil(f)))
    return ("icmp", A.Op.LTE, int(math.floor(f)))


def _split_i64(v: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """int64 → (hi, lo) int32 halves; lexicographic (hi, lo) order equals
    the int64 order (hi is the arithmetic shift, lo is non-negative)."""
    v = np.asarray(v, np.int64)
    return (v >> 31).astype(np.int32), (v & 0x7FFFFFFF).astype(np.int32)


def _sortable_f64(v: np.ndarray) -> np.ndarray:
    """float64 → order-preserving int64 (no NaN): non-negative floats keep
    their bit pattern (already increasing); negative floats reflect so
    more-negative maps lower. -0.0 and +0.0 both map to 0 — equal floats
    must encode equal."""
    b = np.asarray(v, np.float64).view(np.int64)
    return np.where(b >= 0, b, np.int64(-2**63) - b)


def _split_i64_biased(v: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """FULL-RANGE int64 → (hi, lo) int32 halves whose signed lexicographic
    order equals the int64 order: 32/32 split with the low half's sign
    bit flipped (signed compare of the biased low == unsigned compare of
    the true low). The 33/31 `_split_i64` would overflow hi for |v| ≥
    2^62 — which sortable-float encodings reach."""
    v = np.asarray(v, np.int64)
    hi = (v >> 32).astype(np.int32)
    lo = ((v & 0xFFFFFFFF).astype(np.uint32)
          ^ np.uint32(0x80000000)).view(np.int32)
    return hi, lo


def _split_lit_biased(lit: int) -> tuple[int, int]:
    x = (int(lit) & 0xFFFFFFFF) ^ 0x80000000
    if x >= 1 << 31:
        x -= 1 << 32
    return int(lit) >> 32, x


def _split_lit(lit: int) -> tuple[int, int]:
    return int(lit >> 31), int(lit & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# fused mask kernels
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _compiled_mask(sig: tuple, all_conditions: bool):
    """One fused jitted kernel per predicate-plan shape: the whole
    conjunction/disjunction is a single device dispatch per row group.
    (float32 numeric path — the per-row-group opt-in plane only.)"""
    import jax
    import jax.numpy as jnp

    def fn(*args):
        i = 0
        mask = None
        for kind, op, neg in sig:
            if kind == "lut":
                codes, lut = args[i], args[i + 1]
                i += 2
                m = jnp.take(lut, codes)
                if neg:
                    m = ~m
            else:
                col, lit = args[i], args[i + 1]
                i += 2
                if op == A.Op.EQ:
                    m = col == lit
                elif op == A.Op.NEQ:
                    m = col != lit
                elif op == A.Op.GT:
                    m = col > lit
                elif op == A.Op.GTE:
                    m = col >= lit
                elif op == A.Op.LT:
                    m = col < lit
                else:
                    m = col <= lit
            mask = m if mask is None else (mask & m if all_conditions
                                           else mask | m)
        return mask

    from tempo_tpu.obs.jaxruntime import instrumented_jit
    return instrumented_jit(fn, name="plane_predicate_mask")


def _icmp(jnp, op: A.Op, hi, lo, lh, ll):
    """Exact int64 compare from (hi, lo) int32 halves."""
    if op == A.Op.EQ:
        return (hi == lh) & (lo == ll)
    if op == A.Op.NEQ:
        return (hi != lh) | (lo != ll)
    if op == A.Op.GT:
        return (hi > lh) | ((hi == lh) & (lo > ll))
    if op == A.Op.GTE:
        return (hi > lh) | ((hi == lh) & (lo >= ll))
    if op == A.Op.LT:
        return (hi < lh) | ((hi == lh) & (lo < ll))
    return (hi < lh) | ((hi == lh) & (lo <= ll))


def _term_masks(jnp, sig: tuple, args, n: int, ivec, ibase: int):
    """Evaluate each term of a plan signature → list of bool vectors.

    Device arrays ride in `args` (consumed left to right); EVERY scalar
    literal is an element of the single packed int32 vector `ivec`
    (starting at `ibase`) — one H2D transfer per call however many
    predicates the plan holds, which is what makes the plane win behind
    a high-latency device link. Term shapes:
      ("lut", neg, has_ex)    args: codes, lut, [exists]
      ("icmp", op, has_ex)    args: hi, lo, [exists]; ivec: lh, ll
      ("nil", want, has_ex)   args: [exists]   (x = nil / x != nil)
      ("const", val)          —
    Missing attributes never match (exists ANDs after negation), matching
    `Col.bool_mask` in the numpy plane.
    """
    out = []
    i = 0
    k = ibase
    for term in sig:
        kind = term[0]
        if kind == "lut":
            _, neg, has_ex = term
            codes, lut = args[i], args[i + 1]
            i += 2
            m = jnp.take(lut, codes)
            if neg:
                m = ~m
            if has_ex:
                m = m & args[i]
                i += 1
        elif kind == "icmp":
            _, op, has_ex = term
            hi, lo = args[i], args[i + 1]
            i += 2
            m = _icmp(jnp, op, hi, lo, ivec[k], ivec[k + 1])
            k += 2
            if has_ex:
                m = m & args[i]
                i += 1
        elif kind == "nil":
            _, want, has_ex = term
            if has_ex:
                ex = args[i]
                i += 1
                m = ex if want else ~ex
            else:
                m = jnp.full((n,), bool(want))
        else:                                    # ("const", val)
            m = jnp.full((n,), bool(term[1]))
        out.append(m)
    return out, i, k


@functools.lru_cache(maxsize=128)
def _block_mask_kernel(n: int, pred_sig: tuple, extra_sig: tuple,
                       all_conditions: bool):
    """Fused block mask: predicate terms combine per all_conditions;
    extra terms (time clip, row-group shard) always AND."""
    import jax
    import jax.numpy as jnp

    def fn(ivec, *args):
        pred_masks, used, k = _term_masks(jnp, pred_sig, args, n, ivec, 0)
        extra_masks, _, _ = _term_masks(jnp, extra_sig, args[used:], n,
                                        ivec, k)
        mask = None
        for m in pred_masks:
            mask = m if mask is None else (mask & m if all_conditions
                                           else mask | m)
        if mask is None:
            mask = jnp.ones((n,), bool)
        for m in extra_masks:
            mask = mask & m
        # bit-pack on device: the D2H is n/8 bytes instead of n (the
        # transfer is the cost behind a network-attached device)
        pad = (-n) % 8
        mp = jnp.pad(mask, (0, pad)).reshape(-1, 8).astype(jnp.uint8)
        weights = jnp.asarray([128, 64, 32, 16, 8, 4, 2, 1], jnp.uint8)
        return (mp * weights).sum(axis=1).astype(jnp.uint8)

    from tempo_tpu.obs.jaxruntime import instrumented_jit
    return instrumented_jit(fn, name="plane_packed_mask")


# ---------------------------------------------------------------------------
# per-row-group opt-in plane (diagnostic; float32 numerics)
# ---------------------------------------------------------------------------



def _col_for(view, attr: A.Attribute):
    """("dict", key, codes, dictvals) | ("num", key, values) | None."""
    if attr.intrinsic == A.Intrinsic.NAME:
        c = view.meta.get("name_col")
        if c is not None:
            return ("dict", "name") + _dict_codes(view, "name", c)
    if (attr.intrinsic == A.Intrinsic.NONE and attr.name == "service.name"
            and attr.scope in (A.Scope.RESOURCE, A.Scope.NONE)):
        c = view.meta.get("service_col")
        if c is not None:
            return ("dict", "service") + _dict_codes(view, "service", c)
    key = _NUM_INTRINSICS.get(attr.intrinsic)
    if key:
        col = view.col(key)
        if col is not None:
            return ("num", key, col.values)
    return None


def _dev_array(view, key: str, values: np.ndarray, dtype):
    """Device-resident copy of a scan column, cached on the view so a
    multi-query/multi-pass scan transfers each column once."""
    import jax.numpy as jnp

    cache = view.meta.setdefault("_dev_arrays", {})
    arr = cache.get(key)
    if arr is None:
        arr = cache[key] = jnp.asarray(np.asarray(values, dtype))
    return arr


def device_pred_mask(view, preds: Sequence, all_conditions: bool
                     ) -> Optional[np.ndarray]:
    """Evaluate pushdown predicates on device; None when unsupported."""
    if not enabled() or not preds:
        return None
    import jax.numpy as jnp

    sig = []
    args = []
    for c in preds:
        if not c.operands:
            return None
        info = _col_for(view, c.attr)
        if info is None:
            return None
        v = c.operands[0].value
        if info[0] == "dict":
            _, key, codes, dvals = info
            term = _dict_term(c.op, v, dvals)
            if term is None:
                return None
            sig.append(term[0])
            args.append(_dev_array(view, f"dict:{key}", codes, np.int32))
            args.append(jnp.asarray(term[1]))
        else:
            _, key, values = info
            term = _num_term(c.op, v)
            if term is None:
                return None
            sig.append(term[0])
            args.append(_dev_array(view, f"num:{key}", values, np.float32))
            args.append(jnp.float32(term[1]))
    if not sig:
        return None
    fn = _compiled_mask(tuple(sig), all_conditions)
    return np.asarray(fn(*args))


# ---------------------------------------------------------------------------
# the production block plane
# ---------------------------------------------------------------------------

class GridHandle:
    """An in-flight fused metrics grid: the dispatch is async; fetch()
    performs the single packed D2H and unpacks (labels, main, cnt, vcnt).
    Callers launch every block's grid before fetching any, so N blocks
    pipeline their device round trips instead of serializing them."""

    __slots__ = ("labels", "_packed", "_main_shape", "_cnt_shape")

    def __init__(self, labels, packed, main_shape, cnt_shape):
        self.labels = labels
        self._packed = packed
        self._main_shape = main_shape
        self._cnt_shape = cnt_shape

    def fetch(self):
        flat = np.asarray(self._packed)
        m = int(np.prod(self._main_shape))
        c = int(np.prod(self._cnt_shape))
        main = flat[:m].reshape(self._main_shape)
        cnt = flat[m:m + c].reshape(self._cnt_shape)
        vcnt = flat[m + c:].reshape(self._cnt_shape)
        return self.labels, main, cnt, vcnt


def _fmt_group_labels(values: np.ndarray, t: str) -> tuple[np.ndarray, list]:
    """Factorize a host column into int32 codes + formatted label strings,
    matching `engine_metrics._group_slots` label semantics exactly (object
    arrays go through astype("U"): None → "None")."""
    from tempo_tpu.traceql.engine_metrics import _fmt_label

    if values.dtype == object:
        values = values.astype("U")
    u, inv = np.unique(values, return_inverse=True)
    labels = [_fmt_label(v, t) for v in u]
    return inv.astype(np.int32), labels


class BlockScanPlane:
    """Device-resident scan cache for one immutable block.

    Columns adopt LAZILY: the first query touching a column pays one host
    materialization (via the same `eval_expr` path the numpy engine uses,
    so scoping/parent/intrinsic semantics are identical by construction)
    plus one upload; every later query reuses the device copy. A query's
    whole first pass then costs one fused dispatch for the whole block and
    one small boolean D2H — the economics that make the device plane win
    even when the chip sits behind a high-latency link.

    Numeric columns ride the exact (hi, lo) int32 split when integral
    (all intrinsics are); float-valued attribute columns are refused
    (caller falls back to the float64 host plane) — the exactness story
    demanded before this became the default path.
    """

    def __init__(self, views: Sequence, mesh=None) -> None:
        self.views = list(views)
        self.sizes = [int(v.n) for v in self.views]
        self.offsets = np.concatenate(
            [[0], np.cumsum(self.sizes)]).astype(np.int64)
        self.n = int(self.offsets[-1])
        # optional multi-device mesh: span-dim columns shard over its
        # 'data' axis; LUTs/grids replicate, and XLA's SPMD partitioner
        # inserts the cross-device reduce for the grid scatters — the SAME
        # fused kernels run single- or multi-chip (scaling-book recipe:
        # annotate shardings, let the compiler place collectives)
        self.mesh = mesh
        self.time_base_ns = 0
        self._cols: dict = {}          # (kind, key) → entry | None
        self._qr_cache: dict = {}
        self._lock = threading.RLock()
        self.device_bytes = 0
        self.host_bytes = 0            # adoption-side host copies (budget)
        # why the last metrics_grid call refused, + running cause counts
        # (round-4 weak #4: fallbacks were invisible — a workload that
        # silently loses the fused-plane win must show WHERE on /metrics)
        self.last_fallback: "str | None" = None
        self.fallback_causes: dict = {}

    def _bail(self, reason: str) -> str:
        """Record a fused-path refusal cause and return it; `metrics_grid`
        surfaces the cause in its return value so callers never read it
        back off shared plane state (a concurrent query on the same
        cached plane could overwrite it in between)."""
        with self._lock:
            self.last_fallback = reason
            self.fallback_causes[reason] = \
                self.fallback_causes.get(reason, 0) + 1
        return reason

    # -- adoption ----------------------------------------------------------

    def _up(self, arr: np.ndarray, is_span_dim: bool = True):
        import jax
        import jax.numpy as jnp

        if self.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            # span-dim arrays shard over 'data'; everything else (dict
            # LUTs, row-group tables) replicates. The flag is EXPLICIT
            # from each adoption site (ADVICE r5 #3): a replicated LUT
            # whose length coincidentally equals the span count must not
            # be sharded — XLA SPMD would stay correct but pay gathers/
            # collectives on every kernel using it. The shape check stays
            # as a belt-and-braces guard for span-dim arrays.
            spec = P("data") if (is_span_dim
                                 and getattr(arr, "ndim", 0) >= 1
                                 and arr.shape[0] == self.n) else P()
            d = jax.device_put(np.asarray(arr),
                               NamedSharding(self.mesh, spec))
        else:
            d = jnp.asarray(arr)
        self.device_bytes += int(arr.nbytes)
        from tempo_tpu.obs.jaxruntime import record_device_put
        record_device_put(int(arr.nbytes), "plane_column")
        # per-request attribution: the query that forced this adoption
        # pays the upload — later queries ride the resident copy for free
        from tempo_tpu.obs import querystats
        querystats.add(device_scan_bytes=int(arr.nbytes))
        return d

    def _host_col(self, attr: A.Attribute) -> Optional[Col]:
        with self._lock:
            key = ("host", attr)
            if key in self._cols:
                return self._cols[key]
            cols = [eval_expr(v, attr) for v in self.views]
            t = cols[0].t if cols else NUM
            if not cols or any(c.t != t for c in cols):
                ent = None
            else:
                ent = Col(t, np.concatenate([c.values for c in cols]),
                          np.concatenate([c.exists for c in cols]))
                self.host_bytes += int(ent.values.nbytes + ent.exists.nbytes)
            self._cols[key] = ent
            return ent

    def _arrow_dict_fast(self, attr: A.Attribute):
        """(codes[int32], labels) for name/service straight from the
        on-disk arrow dictionary encoding — an index remap instead of the
        generic object-array factorize (the hottest two columns)."""
        if attr.intrinsic == A.Intrinsic.NAME:
            meta_key, ckey = "name_col", "name"
        elif (attr.intrinsic == A.Intrinsic.NONE
                and attr.name == "service.name"
                and attr.scope in (A.Scope.RESOURCE, A.Scope.NONE)):
            meta_key, ckey = "service_col", "service"
        else:
            return None
        parts = []
        block_ids: dict = {}
        for v in self.views:
            c = v.meta.get(meta_key)
            if c is None:
                return None
            codes, dvals = _dict_codes(v, ckey, c)
            lut = np.empty(len(dvals), np.int32)
            for i, s in enumerate(dvals):
                lut[i] = block_ids.setdefault(s, len(block_ids))
            parts.append(lut[codes] if len(dvals) else codes)
        labels = [s for s, _ in sorted(block_ids.items(),
                                       key=lambda kv: kv[1])]
        cat = (np.concatenate(parts) if parts
               else np.zeros(0, np.int32)).astype(np.int32)
        return cat, labels

    def _ensure_dict(self, attr: A.Attribute):
        """("dict", codes_dev, labels, exists_dev|None) for a STR column."""
        with self._lock:
            key = ("dict", attr)
            if key in self._cols:
                return self._cols[key]
            ent = None
            fast = self._arrow_dict_fast(attr)
            if fast is not None:
                codes, labels = fast
                ent = ("dict", self._up(codes), labels, None)
            else:
                c = self._host_col(attr)
                if c is not None and c.t == STR:
                    codes, labels = _fmt_group_labels(c.values, STR)
                    ex = None if c.exists.all() else self._up(c.exists)
                    ent = ("dict", self._up(codes), labels, ex)
            self._cols[key] = ent
            return ent

    def _ensure_int(self, attr: A.Attribute):
        """("int"|"flt", hi, lo, exists|None, t) — exact numeric column.

        Integral columns keep their int64 value; genuinely FLOAT-valued
        columns (round-4 weak #4: they used to refuse and lose the whole
        fused-plane win) are encoded as ORDER-PRESERVING int64 — the
        float64 bit pattern, with negatives reflected so the int order
        equals the float order (`_sortable_f64`). Literals map through
        the same encoding, so the (hi, lo) limb compare is bit-identical
        to the host engine's float64 compare (ref predicate analog:
        pkg/parquetquery/predicates.go:15-120). NaN values (no consistent
        order) still fall back."""
        with self._lock:
            key = ("int", attr)
            if key in self._cols:
                return self._cols[key]
            c = self._host_col(attr)
            ent = None
            if c is not None and c.t in (NUM, STATUS, KIND, BOOL):
                vals = np.asarray(c.values)
                kind = "int"
                if vals.dtype == bool:
                    iv = vals.astype(np.int64)
                elif vals.dtype == object:
                    iv = None
                else:
                    v = vals.astype(np.float64)
                    chk = v[c.exists]
                    if np.isnan(chk).any():
                        iv = None              # NaN has no order: fallback
                    elif (np.isfinite(chk).all()
                            and (np.floor(chk) == chk).all()
                            and (np.abs(chk) < _INT_MAX).all()):
                        iv = np.where(c.exists, v, 0.0).astype(np.int64)
                    else:
                        kind = "flt"
                        iv = _sortable_f64(np.where(c.exists, v, 0.0))
                if iv is not None:
                    hi, lo = (_split_i64_biased(iv) if kind == "flt"
                              else _split_i64(iv))
                    ex = None if c.exists.all() else self._up(c.exists)
                    ent = (kind, self._up(hi), self._up(lo), ex, c.t)
            self._cols[key] = ent
            return ent

    def _host_group_codes(self, expr):
        """(codes[int32], labels, host_exists|None) for one by()-able key —
        ONE factorization (arrow-dict fast path or host np.unique), cached
        host-side (budget-accounted) and shared by the single-key upload
        and the two-key composition."""
        with self._lock:
            key = ("hgroup", expr)
            if key in self._cols:
                return self._cols[key]
            ent = None
            if isinstance(expr, A.Attribute):
                fast = self._arrow_dict_fast(expr)
                if fast is not None:
                    ent = (fast[0], fast[1], None)
                else:
                    c = self._host_col(expr)
                    if c is not None and c.t in (STR, NUM, STATUS, KIND,
                                                 BOOL):
                        codes, labels = _fmt_group_labels(
                            np.asarray(c.values), c.t)
                        ent = (codes, labels,
                               None if c.exists.all() else c.exists)
            if ent is not None:
                self.host_bytes += int(ent[0].nbytes)
                if ent[2] is not None:
                    self.host_bytes += int(ent[2].nbytes)
            self._cols[key] = ent
            return ent

    def _ensure_group(self, expr):
        """("group", codes_dev, labels, exists_dev|None) for any by()-able
        column type (STR dict, status/kind/num/bool factorized)."""
        with self._lock:
            key = ("group", expr)
            if key in self._cols:
                return self._cols[key]
            h = self._host_group_codes(expr)
            ent = None
            if h is not None:
                codes, labels, hex_ = h
                ex = None if hex_ is None else self._up(hex_)
                ent = ("group", self._up(codes), labels, ex)
            self._cols[key] = ent
            return ent

    # hard construction bound for composed multi-key grids: label lists
    # and code composition stay sane; the caller's max_groups applies per
    # query
    _GROUP2_BUILD_CAP = 1 << 20

    def _ensure_groupn(self, exprs):
        """("groupn", codes_dev, labels, exists|None) for a multi-key
        by() (2 or 3 keys): codes compose mixed-radix on host at adoption
        (c1*|d2|*|d3| + c2*|d3| + c3 — the engine's `group_slots`
        composition, engine_metrics.py), labels are value tuples in the
        same slot order (itertools.product iterates the last key fastest,
        matching the composition). Unobserved combos cost grid rows but
        never emit (the obs-count gate). The whole build runs under the
        plane lock like every other adoption (a racing duplicate would
        double-count device_bytes)."""
        import itertools

        with self._lock:
            key = ("groupn",) + tuple(exprs)
            if key in self._cols:
                return self._cols[key]
            ent = None
            hs = [self._host_group_codes(e) for e in exprs]
            if all(h is not None for h in hs):
                prod = 1
                for h in hs:
                    prod *= len(h[1])
                if 0 < prod <= self._GROUP2_BUILD_CAP:
                    codes = np.zeros(self.n, np.int64)
                    for h in hs:
                        codes = codes * len(h[1]) + h[0]
                    labels = [tuple(p) for p in
                              itertools.product(*[h[1] for h in hs])]
                    ex = None
                    if any(h[2] is not None for h in hs):
                        both = np.ones(self.n, bool)
                        for h in hs:
                            if h[2] is not None:
                                both &= h[2]
                        ex = self._up(both)
                    ent = ("groupn", self._up(codes.astype(np.int32)),
                           labels, ex)
            self._cols[key] = ent
            return ent

    def _ensure_group2(self, e1, e2):
        """Back-compat shim for the former two-key entry point."""
        return self._ensure_groupn((e1, e2))

    def _ensure_value(self, attr):
        """("val", f32_dev, bucket_dev, exists|None): the measured column of
        a metrics aggregate — f32 values (seconds for duration intrinsics,
        mirroring the engine's ns→s divide) + precomputed log2 buckets
        (exact: host float64 bucketing at adoption, ref `Log2Bucketize`
        engine_metrics.go:1392)."""
        from tempo_tpu.traceql.engine_metrics import (_is_duration_attr,
                                                      log2_bucket_np)

        with self._lock:
            key = ("val", attr)
            if key in self._cols:
                return self._cols[key]
            ent = None
            c = self._host_col(attr) if isinstance(attr, A.Attribute) else None
            if c is not None and c.t == NUM and c.values.dtype != object:
                v = np.asarray(c.values, np.float64)
                buckets = log2_bucket_np(np.where(c.exists, v, 1.0))
                scaled = v / 1e9 if _is_duration_attr(attr) else v
                ex = None if c.exists.all() else self._up(c.exists)
                ent = ("val", self._up(scaled.astype(np.float32)),
                       self._up(buckets.astype(np.int32)), ex)
            self._cols[key] = ent
            return ent

    def _ensure_value_log(self, attr):
        """("vlog", z_dev, exists|None): clipped log values (ns domain)
        for the moments-tier quantile grid — host float64 log at
        adoption, f32 cast, the SAME computation MetricsEvaluator's
        dispatch applies to its staged values, so fused and host moment
        sums agree up to f32 scatter order (inside the moments error
        gate). Missing rows log a placeholder 1.0; the value-exists
        mask drops them before they reach the grid."""
        import math

        from tempo_tpu.ops import moments as msk

        with self._lock:
            key = ("vlog", attr)
            if key in self._cols:
                return self._cols[key]
            ent = None
            c = self._host_col(attr) if isinstance(attr, A.Attribute) else None
            if c is not None and c.t == NUM and c.values.dtype != object:
                v = np.asarray(c.values, np.float64)
                z = np.log(np.clip(np.where(c.exists, v, 1.0),
                                   math.exp(msk.QUERY_LO),
                                   math.exp(msk.QUERY_HI))
                           ).astype(np.float32)
                ex = None if c.exists.all() else self._up(c.exists)
                ent = ("vlog", self._up(z), ex)
            self._cols[key] = ent
            return ent

    def _ensure_times(self) -> bool:
        with self._lock:
            if ("times",) in self._cols:
                return self._cols[("times",)] is not None
            cols = [v.col("__startTime") for v in self.views]
            if not cols or any(c is None for c in cols):
                self._cols[("times",)] = None
                return False
            starts = np.concatenate([np.asarray(c.values, np.float64)
                                     for c in cols]).astype(np.int64)
            self.time_base_ns = int(starts.min()) if len(starts) else 0
            hi, lo = _split_i64(starts)
            self._cols[("times",)] = (
                self._up(((starts - self.time_base_ns) / 1e9
                          ).astype(np.float32)),
                self._up(hi), self._up(lo))
            return True

    def _ensure_rgids(self):
        with self._lock:
            if ("rgids",) in self._cols:
                return self._cols[("rgids",)]
            ids = np.repeat(np.arange(len(self.sizes), dtype=np.int32),
                            self.sizes)
            ent = self._cols[("rgids",)] = self._up(ids)
            return ent

    def load_times(self, views: Sequence = ()) -> None:
        """Back-compat shim: time columns now adopt lazily."""
        self._ensure_times()

    # -- plan compilation ---------------------------------------------------

    def _plan_pred(self, c) -> Optional[tuple]:
        """One Condition → (sig entry, args list) or None (unsupported)."""
        import jax.numpy as jnp

        if not c.operands or not isinstance(c.attr, A.Attribute):
            return None
        static = c.operands[0]
        v = static.value
        # nil comparisons prune on the existence mask alone
        if getattr(static, "type", None) == A.StaticType.NIL:
            if c.op not in (A.Op.EQ, A.Op.NEQ):
                return (("const", False), [], [])
            host = self._host_col(c.attr)
            if host is None:
                return None
            want = c.op == A.Op.NEQ
            if host.exists.all():
                return (("const", want), [], [])
            with self._lock:
                ex = self._cols.get(("ex", c.attr))
                if ex is None:
                    ex = self._cols[("ex", c.attr)] = self._up(host.exists)
            return (("nil", want, True), [ex], [])
        lit_t = _STATIC_T.get(getattr(static, "type", None))
        if lit_t is None:
            return None
        if lit_t == STR:
            ent = self._ensure_dict(c.attr)
            if ent is None:
                # a scalar non-STR column compared to a string is
                # incomparable → constant false (the type lattice); list
                # and mixed columns fall back to the host plane
                host = self._host_col(c.attr)
                if host is not None and host.t in (NUM, STATUS, KIND, BOOL):
                    return (("const", False), [], [])
                return None
            # the uploaded lut is cached per (attr, op, value): repeated
            # queries pay ZERO H2D transfers for their predicates. The
            # cache stores (neg, lut) so _dict_term stays the single
            # source of negation truth; entries are budget-accounted and
            # capacity-capped (high-cardinality literal workloads must
            # not grow device memory unboundedly)
            lkey = ("plut", c.attr, c.op, v)
            with self._lock:
                cached = self._cols.get(lkey)
            if cached is None:
                term = _dict_term(c.op, v, ent[2])
                if term is None:
                    return None
                (kind, _, neg), lut = term
                lut_dev = self._up(lut, is_span_dim=False)
                with self._lock:
                    # re-check under the lock: a racing thread may have
                    # inserted the same key while we uploaded — keep its
                    # entry and refund our duplicate's budget accounting
                    again = self._cols.get(lkey)
                    if again is not None:
                        self.device_bytes -= int(lut.nbytes)
                        neg, lut_dev = again
                    else:
                        pluts = [k for k in self._cols if k[0] == "plut"]
                        if len(pluts) >= 256:
                            for k in pluts[:128]:
                                arr = self._cols.pop(k)[1]
                                self.device_bytes -= int(arr.nbytes)
                        self._cols[lkey] = (neg, lut_dev)
            else:
                neg, lut_dev = cached
            has_ex = ent[3] is not None
            args = [ent[1], lut_dev]
            if has_ex:
                args.append(ent[3])
            return (("lut", neg, has_ex), args, [])
        # numeric-family literal
        if c.op not in _NUM_OPS:
            return None
        ent = self._ensure_int(c.attr)
        if ent is None:
            host = self._host_col(c.attr)
            if host is not None and host.t == STR:
                return (("const", False), [], [])  # str col vs num literal
            return None                          # float col → host fallback
        ekind, hi, lo, ex, col_t = ent
        if col_t != lit_t:                       # distinct lattices → false
            return (("const", False), [], [])
        if ekind == "flt":
            # float-valued column: the literal rides the same
            # order-preserving encoding, ops unchanged (monotone map)
            f = float(v if not isinstance(v, bool) else int(v))
            if f != f:                           # NaN literal: host plane
                return None
            lh, ll = _split_lit_biased(
                int(_sortable_f64(np.asarray([f]))[0]))
            has_ex = ex is not None
            args = [hi, lo] + ([ex] if has_ex else [])
            return (("icmp", c.op, has_ex), args, [lh, ll])
        norm = _int_literal(c.op, v if not isinstance(v, bool) else int(v))
        if norm[0] == "const":
            if not norm[1] or ex is None:
                return (("const", norm[1]), [], [])
            # the literal-compare is constant-TRUE for every present value
            # (e.g. `.x != 1.5` on an int column), but spans missing the
            # attribute must still be excluded — the host plane ANDs
            # l.exists (eval._compare) — so emit the existence mask, not
            # a bare const
            return (("nil", True, True), [ex], [])
        _, op2, lit = norm
        lh, ll = _split_lit(lit)
        has_ex = ex is not None
        args = [hi, lo]
        if has_ex:
            args.append(ex)
        return (("icmp", op2, has_ex), args, [lh, ll])

    def _plan(self, preds: Sequence, all_conditions: bool):
        sig, args, ints = [], [], []
        for c in preds:
            got = self._plan_pred(c)
            if got is None:
                return None
            sig.append(got[0])
            args.extend(got[1])
            ints.extend(got[2])
        return tuple(sig), args, ints

    def _ensure_rg_lut(self, row_groups):
        key = ("rglut", tuple(row_groups))
        with self._lock:
            got = self._cols.get(key)
        if got is None:
            lut = np.zeros(len(self.sizes), bool)
            sel = [g for g in row_groups if 0 <= g < len(self.sizes)]
            if sel:
                lut[np.asarray(sel)] = True
            # row-group LUT: replicated, never span-dim (budget-accounted
            # like all uploads)
            got = self._up(lut, is_span_dim=False)
            with self._lock:
                again = self._cols.get(key)
                if again is not None:         # lost an upload race: refund
                    self.device_bytes -= int(lut.nbytes)
                    got = again
                else:
                    rgluts = [k for k in self._cols if k[0] == "rglut"]
                    if len(rgluts) >= 64:
                        for k in rgluts[:32]:
                            self.device_bytes -= int(self._cols.pop(k).nbytes)
                    self._cols[key] = got
        return got

    def _extra_terms(self, time_range, row_groups):
        """Always-AND terms: exact time clip + row-group shard selection.
        Returns (sig, device args, int literals)."""
        sig, args, ints = [], [], []
        if time_range is not None and any(time_range):
            lo_ns, hi_ns = time_range
            if not self._ensure_times():
                return None
            _, thi, tlo = self._cols[("times",)]
            # the host plane compares float64 start values against the
            # literal PROMOTED to float64; round the clip bounds the same
            # way so boundary spans classify identically on both paths
            if lo_ns:
                lh, ll = _split_lit(int(np.float64(lo_ns)))
                sig.append(("icmp", A.Op.GTE, False))
                args.extend([thi, tlo])
                ints.extend([lh, ll])
            if hi_ns:
                lh, ll = _split_lit(int(np.float64(hi_ns)))
                sig.append(("icmp", A.Op.LT, False))
                args.extend([thi, tlo])
                ints.extend([lh, ll])
        if row_groups is not None:
            sig.append(("lut", None, False))
            args.extend([self._ensure_rgids(),
                         self._ensure_rg_lut(row_groups)])
        return tuple(sig), args, ints

    # -- masks --------------------------------------------------------------

    def mask_async(self, preds: Sequence, all_conditions: bool,
                   time_range=None, row_groups=None):
        """Launch the fused block mask; returns a BIT-PACKED device array
        (uint8, big-endian bit order — unpack with `unpack_mask`) or None
        when a predicate shape is unsupported. No sync, no D2H; a single
        packed-literal H2D rides along with the call."""
        plan = self._plan(list(preds), all_conditions)
        if plan is None:
            return None
        extra = self._extra_terms(time_range, row_groups)
        if extra is None:
            return None
        sig, args, ints = plan
        esig, eargs, eints = extra
        fn = _block_mask_kernel(self.n, sig, esig, all_conditions)
        ivec = np.asarray(ints + eints, np.int32)
        # query-class job on the shared device scheduler: live-ingest
        # batches order ahead of scans, the dispatch is accounted, and
        # the launch stays async (the handle returns without a sync)
        from tempo_tpu import sched
        return sched.run(lambda: fn(ivec, *args, *eargs),
                         kernel="plane_packed_mask")

    def mask(self, preds: Sequence, all_conditions: bool,
             time_range=None, row_groups=None) -> Optional[np.ndarray]:
        from tempo_tpu.obs import querystats

        m = self.mask_async(preds, all_conditions, time_range, row_groups)
        if m is None:
            return None
        t0 = time.perf_counter_ns()
        with querystats.stage("device_scan"):
            packed = np.asarray(m)        # the sync point: device → host
        querystats.add(kernel_wall_ns=time.perf_counter_ns() - t0)
        return self.unpack_mask(packed)

    def unpack_mask(self, packed: np.ndarray) -> np.ndarray:
        """Bit-packed device mask → bool[n]."""
        return np.unpackbits(np.asarray(packed, np.uint8))[:self.n]             .astype(bool)

    def split_mask(self, packed: np.ndarray) -> list[np.ndarray]:
        """Bit-packed block mask → per-row-group candidate row arrays."""
        mask = self.unpack_mask(packed)
        return [np.flatnonzero(mask[self.offsets[i]:self.offsets[i + 1]])
                for i in range(len(self.sizes))]

    # -- fused metrics grid -------------------------------------------------

    def metrics_grid(self, m, preds: Sequence, all_conditions: bool,
                     start_ns: int, end_ns: int, step_ns: int,
                     clip_start_ns: int | None = None,
                     clip_end_ns: int | None = None,
                     row_groups=None, max_groups: int = 65536,
                     moments: bool = False):
        """The FULL device metrics path: predicate mask → exact time clip →
        step bucketing → per-group scatter into device grids, one fused
        dispatch over the resident block (SURVEY §3.4's hot loop with zero
        host work per span). Covers every `*_over_time` kind including the
        log2-bucket histogram axis behind `quantile_over_time` /
        `histogram_over_time` (ref `Log2Bucketize` engine_metrics.go:1392).

        `m` is the A.MetricsAggregate. Returns `(handle, cause)`:
        `(None, cause)` when any shape is unsupported (caller falls back
        to the host engine; `cause` is the refusal reason, returned here
        rather than stashed on shared plane state so concurrent queries
        on one cached plane cannot misattribute each other's fallbacks),
        else `(handle, None)` — a GridHandle whose fetch() yields
        (group_label_list, main_grid, obs_count_grid, value_count_grid):
          count/rate       main [G, steps] counts
          min/max/sum/avg  main [G, steps]
          quantile/hist    main [G, steps, 64] bucket counts
        obs counts gate series emission (group matched the filter);
        value counts back avg's companion `__meta: count` series.

        Transfer economics (the plane must win through a high-latency
        device link): per call, H2D is ONE packed int32 literal vector +
        ONE packed f32 vector; D2H is ONE packed grid (the three grids
        concatenate raveled). Launches are async — the caller launches
        every block's grid before fetching any (`db/tempodb.py`).
        """
        import jax
        import jax.numpy as jnp

        kind_tag = {
            A.MetricsKind.RATE: "count",
            A.MetricsKind.COUNT_OVER_TIME: "count",
            A.MetricsKind.MIN_OVER_TIME: "min",
            A.MetricsKind.MAX_OVER_TIME: "max",
            A.MetricsKind.SUM_OVER_TIME: "sum",
            A.MetricsKind.AVG_OVER_TIME: "avg",
            A.MetricsKind.QUANTILE_OVER_TIME: "hist",
            A.MetricsKind.HISTOGRAM_OVER_TIME: "hist",
        }.get(m.kind)
        if moments and m.kind == A.MetricsKind.QUANTILE_OVER_TIME:
            # moments query tier: quantile accumulates a [G, steps, k+3]
            # moment grid (k+1 Chebyshev sums + the two support-bound
            # planes) instead of the log2 bucket axis — add-merge for
            # the sums, max-merge for the bounds, both grid-shaped, so
            # the same packed D2H and combiner conventions apply
            kind_tag = "mom"
        if kind_tag is None or step_ns <= 0 or end_ns <= start_ns:
            return None, self._bail("shape")
        if len(m.by) > 3:
            return None, self._bail("group")
        if not self._ensure_times():
            return None, self._bail("times")

        plan = self._plan(list(preds), all_conditions)
        if plan is None:
            return None, self._bail("predicate")
        clip_lo = max(start_ns, clip_start_ns or start_ns)
        clip_hi = min(end_ns, clip_end_ns or end_ns)
        extra = self._extra_terms((clip_lo, clip_hi), row_groups)
        if extra is None:
            return None, self._bail("times")
        sig, args, ints = plan
        esig, eargs, eints = extra

        if len(m.by) >= 2:
            gent = self._ensure_groupn(tuple(m.by))
            if gent is None or len(gent[2]) > max_groups:
                return None, self._bail("group")
            _, gcodes, glabels, gex = gent
        elif m.by:
            gent = self._ensure_group(m.by[0])
            if gent is None or len(gent[2]) > max_groups:
                return None, self._bail("group")
            _, gcodes, glabels, gex = gent
        else:
            gcodes, glabels, gex = None, [None], None

        from tempo_tpu.ops import moments as _mom
        mom_cols = _mom.QUERY_K + 3
        needs_value = kind_tag in ("min", "max", "sum", "avg", "hist", "mom")
        vargs = []
        if needs_value:
            if m.attr is None:
                return None, self._bail("value")
            if kind_tag == "mom":
                vent = self._ensure_value_log(m.attr)
                if vent is None:
                    return None, self._bail("value")
                _, zvals, vex = vent
                vargs = [zvals]
            else:
                vent = self._ensure_value(m.attr)
                if vent is None:
                    return None, self._bail("value")
                _, vvals, vbuckets, vex = vent
                vargs = [vbuckets if kind_tag == "hist" else vvals]
            if vex is not None:
                vargs.append(vex)
            v_has_ex = vex is not None
        else:
            v_has_ex = False

        n_steps = max(int(-(-(end_ns - start_ns) // step_ns)), 1)
        n_groups = len(glabels)
        grid_width = {"hist": 64, "mom": mom_cols}.get(kind_tag, 1)
        if n_groups * n_steps * grid_width * 4 > 1 << 28:
            return None, self._bail("grid_size")
        delta_ns = self.time_base_ns - start_ns
        q_steps = delta_ns // step_ns              # exact whole steps (host)
        frac_ns = delta_ns - q_steps * step_ns     # in [0, step_ns)
        if abs(q_steps) > 1 << 30:
            return None, self._bail("window")

        # exact step bucketing is available when the grid is small enough
        # that 16-bit limb products stay in int32 and the f32 estimate is
        # provably within one step of the truth (guard below); outside it
        # the f32 path applies with a documented boundary tolerance
        exact = (n_steps <= (1 << 14) and abs(q_steps) <= (1 << 20)
                 and start_ns >= 0 and step_ns > 0
                 and start_ns + (n_steps + 1) * step_ns < (1 << 63))
        key = (sig, esig, all_conditions, kind_tag, n_groups, n_steps,
               gcodes is not None, gex is not None, v_has_ex, exact)
        with self._lock:
            fn = self._qr_cache.get(key)
        if fn is None:
            n = self.n

            def build(rel, thi, tlo, ivec, fvec, gcodes, gex, vcol, vex,
                      *margs):
                q_steps = ivec[0]
                frac_s, step_s = fvec[0], fvec[1]
                pred_masks, used, k = _term_masks(jnp, sig, margs, n,
                                                  ivec, 1)
                extra_masks, _, _ = _term_masks(jnp, esig, margs[used:], n,
                                                ivec, k)
                mask = None
                for pm in pred_masks:
                    mask = pm if mask is None else (
                        mask & pm if all_conditions else mask | pm)
                if mask is None:
                    mask = jnp.ones((n,), bool)
                for em in extra_masks:
                    mask = mask & em
                # step index split for precision: the whole-step offset
                # between window start and block base is EXACT int host
                # math; f32 only covers the sub-step fraction + intra-
                # block offsets. The f32 estimate is then snapped to the
                # EXACT integer floor((t_ns - start_ns) / step_ns) by
                # comparing the resident (hi, lo) int timestamps against
                # the limb-computed boundaries start_ns + q*step_ns — the
                # host engine's float64 bucketing is exact for ns < 2^53,
                # so boundary spans classify identically on both planes.
                local = rel + frac_s
                step_idx = q_steps + jnp.floor(local / step_s
                                               ).astype(jnp.int32)
                if exact:
                    # ivec tail: step_ns 16-bit limbs (4), start_ns 16-bit
                    # limbs (4), low-to-high; the guard (n_steps <= 2^14,
                    # |q_steps| <= 2^20) bounds the f32 error under one
                    # step and keeps every limb product inside int32
                    sl = [ivec[-8 + i] for i in range(4)]
                    ul = [ivec[-4 + i] for i in range(4)]
                    # t_ns = thi * 2^31 + tlo (the 33/31 _split_i64 form;
                    # tlo is non-negative) → 16-bit limbs low-to-high
                    w = [tlo & 0xffff,
                         ((tlo >> 16) & 0x7fff) | ((thi & 1) << 15),
                         (thi >> 1) & 0xffff,
                         (thi >> 17) & 0xffff]

                    def ge_boundary(q):
                        # t_ns >= start_ns + q*step_ns, via 16-bit limbs
                        carry = 0
                        r = []
                        for i in range(4):
                            v = ul[i] + q * sl[i] + carry
                            r.append(v & 0xffff)
                            carry = v >> 16
                        ge = w[0] >= r[0]
                        for wi, ri in zip(w[1:], r[1:]):
                            ge = jnp.where(wi == ri, ge, wi > ri)
                        return ge

                    qc = jnp.clip(step_idx, 0, n_steps)
                    # the guard bounds |estimate - truth| <= 1, so the
                    # true index is qc+1, qc, or qc-1 (qc-1 is -1 when
                    # the span truly precedes the window, since qc
                    # clips at 0 — the ok mask drops it)
                    step_idx = jnp.where(
                        ge_boundary(qc + 1), qc + 1,
                        jnp.where(ge_boundary(qc), qc, qc - 1))
                ok = mask & (step_idx >= 0) & (step_idx < n_steps)
                if gcodes is not None:
                    slots = gcodes
                    if gex is not None:
                        ok = ok & gex
                else:
                    slots = jnp.zeros((n,), jnp.int32)
                steps = jnp.clip(step_idx, 0, n_steps - 1)
                # obs counts IGNORE the value-exists mask: the host engine
                # registers a group's series when any span matches the
                # filter, even if the measured attribute is missing on all
                # of them (zero/inf series) — emission must agree
                obs_slots = jnp.where(ok, slots, n_groups)
                cnt = jnp.zeros((n_groups, n_steps), jnp.float32
                                ).at[obs_slots, steps].add(
                    jnp.where(ok, 1.0, 0.0), mode="drop")
                pack = lambda main, vcnt: jnp.concatenate(
                    [main.reshape(-1), cnt.reshape(-1), vcnt.reshape(-1)])
                if kind_tag == "count":
                    return pack(cnt, cnt)
                okv = ok & vex if vex is not None else ok
                slots = jnp.where(okv, slots, n_groups)
                ones = jnp.where(okv, 1.0, 0.0)
                if kind_tag == "hist":
                    grid = jnp.zeros((n_groups, n_steps, 64), jnp.float32)
                    grid = grid.at[slots, steps, vcol].add(ones, mode="drop")
                    return pack(grid, cnt)
                if kind_tag == "mom":
                    # vcol is the clipped log-ns value; the Chebyshev
                    # recurrence runs on device — the SAME basis the host
                    # evaluator scatters — and the two support-bound
                    # planes ride the last two columns of the one grid
                    # (add-merge sums, max-merge bounds; non-matching
                    # rows carry slot == n_groups and drop)
                    c0 = (_mom.QUERY_LO + _mom.QUERY_HI) / 2.0
                    h0 = (_mom.QUERY_HI - _mom.QUERY_LO) / 2.0
                    sb = jnp.clip((vcol - c0) / h0, -1.0, 1.0)
                    basis = jnp.stack(
                        _mom.chebyshev_basis(sb, _mom.QUERY_K), axis=-1)
                    mcols = jnp.arange(_mom.QUERY_K + 1, dtype=jnp.int32)
                    grid = jnp.zeros((n_groups, n_steps, mom_cols),
                                     jnp.float32)
                    grid = grid.at[slots[:, None], steps[:, None],
                                   mcols[None, :]].add(basis, mode="drop")
                    grid = grid.at[slots, steps, _mom.QUERY_K + 1].max(
                        vcol - _mom.QUERY_LO, mode="drop")
                    grid = grid.at[slots, steps, _mom.QUERY_K + 2].max(
                        _mom.QUERY_HI - vcol, mode="drop")
                    return pack(grid, cnt)
                vals = vcol
                if kind_tag == "min":
                    grid = jnp.full((n_groups, n_steps), jnp.inf,
                                    jnp.float32)
                    grid = grid.at[slots, steps].min(
                        jnp.where(okv, vals, jnp.inf), mode="drop")
                    return pack(grid, cnt)
                if kind_tag == "max":
                    grid = jnp.full((n_groups, n_steps), -jnp.inf,
                                    jnp.float32)
                    grid = grid.at[slots, steps].max(
                        jnp.where(okv, vals, -jnp.inf), mode="drop")
                    return pack(grid, cnt)
                grid = jnp.zeros((n_groups, n_steps), jnp.float32
                                 ).at[slots, steps].add(
                    jnp.where(okv, vals, 0.0), mode="drop")
                if kind_tag == "avg":
                    # avg's companion count series counts VALUED spans only
                    vcnt = jnp.zeros((n_groups, n_steps), jnp.float32
                                     ).at[slots, steps].add(ones,
                                                            mode="drop")
                    return pack(grid, vcnt)
                return pack(grid, cnt)

            from tempo_tpu.obs.jaxruntime import instrumented_jit
            fn = instrumented_jit(build, name="plane_query_range_grid")
            with self._lock:
                if len(self._qr_cache) >= 64:
                    self._qr_cache.pop(next(iter(self._qr_cache)))
                fn = self._qr_cache.setdefault(key, fn)

        ivals = [q_steps] + ints + eints
        if exact:
            ivals += [(step_ns >> s) & 0xffff for s in (0, 16, 32, 48)]
            ivals += [(start_ns >> s) & 0xffff for s in (0, 16, 32, 48)]
        ivec = np.asarray(ivals, np.int32)
        fvec = np.asarray([frac_ns / 1e9, step_ns / 1e9], np.float32)
        trel, thi, tlo = self._cols[("times",)]
        # fused grid launch rides the scheduler's query class (async —
        # the GridHandle fetch is the only sync point)
        from tempo_tpu import sched
        packed = sched.run(
            lambda: fn(trel, thi, tlo, ivec, fvec,
                       gcodes, gex, vargs[0] if vargs else None,
                       vargs[1] if len(vargs) > 1 else None,
                       *args, *eargs),
            kernel="plane_query_range_grid")
        main_shape = ((n_groups, n_steps, 64) if kind_tag == "hist"
                      else (n_groups, n_steps, mom_cols)
                      if kind_tag == "mom" else (n_groups, n_steps))
        return GridHandle(glabels, packed, main_shape,
                          (n_groups, n_steps)), None

    # -- back-compat wrapper (tests from round 3) ---------------------------

    def query_range_grid(self, preds: Sequence, all_conditions: bool,
                         group: str | None, start_ns: int, end_ns: int,
                         step_ns: int):
        """rate/count grid keyed by the legacy "name"/"service" group
        names; returns (labels, grid ndarray) or None."""
        by = ()
        if group == "name":
            by = (A.Attribute.intrinsic_of(A.Intrinsic.NAME),)
        elif group == "service":
            by = (A.Attribute("service.name", A.Scope.RESOURCE),)
        m = A.MetricsAggregate(kind=A.MetricsKind.COUNT_OVER_TIME, by=by)
        got, _cause = self.metrics_grid(m, preds, all_conditions, start_ns,
                                        end_ns, step_ns)
        if got is None:
            return None
        labels, main, _cnt, _vcnt = got.fetch()
        return labels, main
