"""ManagedRegistry: per-tenant metric families over device state.

Reference behavior being reproduced (`modules/generator/registry/registry.go`):

- `NewCounter/NewGauge/NewHistogram/NewNativeHistogram` → metric families
  sharing one per-tenant active-series budget (`max_active_series`,
  `registry.go:184-197`).
- `CollectMetrics` tick (`registry.go:206-256`): walk active series, append
  samples at a synchronized timestamp; histograms expand to cumulative
  `_bucket`/`_sum`/`_count`; exemplars carry trace ids.
- stale-series purge (`registry.go:258-277`): series idle > staleness window
  are dropped, device rows zeroed, staleness markers (NaN) appended once.
- extra const labels and per-tenant external labels merged into every series.

Device work is batched: each metric family stages (slots, values) on host and
runs one scatter kernel; `collect` gathers each family's arrays once.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Callable, Iterable, Sequence

import jax
import numpy as np

from tempo_tpu.model.interner import StringInterner
from tempo_tpu.registry import metrics as m
from tempo_tpu.registry.series import Exemplar, Sample, SeriesBudget, SeriesTable
from tempo_tpu.utils import tracing

STALE_NAN = float("nan")

_LOG = logging.getLogger("tempo_tpu.registry")

DEFAULT_HISTOGRAM_EDGES = (0.002, 0.004, 0.008, 0.016, 0.032, 0.064, 0.128,
                           0.256, 0.512, 1.024, 2.048, 4.096, 8.192, 16.384)


@dataclasses.dataclass
class RegistryOverrides:
    """Per-tenant knobs (subset of `modules/overrides/config.go:71-200`)."""

    max_active_series: int = 65536
    collection_interval_s: float = 15.0
    stale_duration_s: float = 900.0
    external_labels: dict[str, str] = dataclasses.field(default_factory=dict)
    disable_collection: bool = False


@dataclasses.dataclass
class FamilyColumns:
    """One family's share of a collection tick, in columns. A slot's
    series go out in the order of `kinds`, slots ascending, then one
    staleness marker per evicted series: the order of `samples()` and of
    the remote-write payload, which are both read from here."""

    family: "_MetricBase"
    ts_ms: int
    slots: np.ndarray       # [S] active slots
    # a slot's series: (sample-name suffix, `le` value or None)
    kinds: tuple[tuple[str, "str | None"], ...]
    values: np.ndarray      # [S, K] float64 (f32 state widened)
    exemplars: list         # [S] the slot's last Exemplar, or None
    carries: np.ndarray     # [S, K] bool: this series carries the exemplar
    stale: list             # label sets of evicted series, a marker each

    @property
    def n_series(self) -> int:
        return self.values.size + len(self.stale)

    def stale_samples(self) -> list[Sample]:
        return [Sample(self.family.name, labels, STALE_NAN, self.ts_ms,
                       is_stale_marker=True) for labels in self.stale]

    def samples(self) -> list[Sample]:
        fam, ts = self.family, self.ts_ms
        names = [fam.name + suffix for suffix, _ in self.kinds]
        les = [() if le is None else (("le", le),) for _, le in self.kinds]
        out: list[Sample] = []
        for slot, vals, ex, carries in zip(
                self.slots.tolist(), self.values.tolist(), self.exemplars,
                self.carries.tolist()):
            base = fam.labels_of(slot)
            out.extend(
                Sample(name, base + le, v, ts, exemplar=ex if c else None)
                for name, le, v, c in zip(names, les, vals, carries))
        return out + self.stale_samples()


class _MetricBase:
    def __init__(self, registry: "ManagedRegistry", name: str,
                 label_names: Sequence[str], capacity: int):
        self.registry = registry
        self.name = name
        self.label_names = tuple(label_names)
        self.table = SeriesTable(capacity, len(self.label_names),
                                 budget=registry.budget)
        self.exemplars: dict[int, Exemplar] = {}  # slot -> last exemplar
        self._stale_pending: list[tuple[tuple[tuple[str, str], ...], float]] = []
        self._ex_cursor = 0   # rotating exemplar-sampling window offset
        # the remote-write encoder's per-slot label blocks (it creates
        # and fills the store); this side only forgets evicted slots
        self.label_blocks = None
        # processor-owned sidecar planes keyed to this family's slots
        # (the spanmetrics DDSketch) register here so the staleness purge
        # zeroes THEIR rows too — slot reuse must not inherit another
        # series' sketch history. Called with the padded eviction batch,
        # inside the registry state lock.
        self.evict_hooks: list = []

    # -- staging helpers ---------------------------------------------------

    def resolve_slots(self, label_rows: np.ndarray,
                      valid: np.ndarray | None = None) -> np.ndarray:
        """[n, L] interned label-value rows → [n] slots (-1 = discarded)."""
        return self.table.lookup_or_create(label_rows, self.registry.now(), valid=valid)

    def labels_of(self, slot: int) -> tuple[tuple[str, str], ...]:
        it = self.registry.interner
        vals = it.lookup_many(self.table.slot_keys[slot])
        pairs = dict(zip(self.label_names, vals))
        pairs.update(self.registry.overrides.external_labels)
        pairs["__name__"] = self.name
        return tuple(sorted(pairs.items()))

    def note_exemplars(self, slots: np.ndarray, trace_ids: np.ndarray,
                       values: np.ndarray, ts_ms: int, max_new: int = 16) -> None:
        """Record up to max_new last-seen exemplars (budget per push, like
        the engine's exemplar budgeting `engine_metrics.go:1070`).
        Exemplars are last-seen hints that pushes continually overwrite —
        a small per-push budget keeps them fresh under steady traffic
        while keeping the hex/dict work off the ingest hot path. One
        exemplar per DISTINCT series per push (deduped before the hex
        conversions; repeatedly hexing 100 ids of the same few series was
        measurable at 4M spans/s)."""
        ok = np.flatnonzero(slots >= 0)
        if len(ok) == 0:
            return
        # dedupe over a bounded ROTATING window (a full-batch unique is a
        # 16k sort per push — 1.3ms, costlier than what it saved). The
        # rotation guarantees tail series of a stably-ordered batch get
        # their turn across pushes, which a fixed head would starve.
        win = max_new * 16
        start = self._ex_cursor % len(ok)
        self._ex_cursor = start + win
        head = ok[start:start + win]
        if len(head) < win and start:
            head = np.concatenate([head, ok[:win - len(head)]])
        _, first = np.unique(slots[head], return_index=True)
        for i in head[np.sort(first)[:max_new]].tolist():
            tid = trace_ids[i].tobytes().hex()
            self.exemplars[int(slots[i])] = Exemplar(tid, float(values[i]), ts_ms)

    def note_stale(self, slots: np.ndarray) -> None:
        """Capture label sets before slot_keys are wiped (markers emitted on
        the next collect) and forget exemplars for evicted slots."""
        for slot in slots.tolist():
            self._stale_pending.append((self.labels_of(slot), self.registry.now()))
            self.exemplars.pop(slot, None)
        if self.label_blocks is not None:
            self.label_blocks.drop(slots)

    def _columns(self, ts_ms: int, slots: np.ndarray, kinds: tuple,
                 values: Sequence[np.ndarray], exemplars: list | None = None,
                 carries: np.ndarray | None = None) -> FamilyColumns:
        """`values`: [S] and [S, n] arrays, a column a kind between them.
        Drains the pending markers."""
        shape = (len(slots), len(kinds))
        stale = [labels for labels, _ in self._stale_pending]
        self._stale_pending = []
        return FamilyColumns(
            self, ts_ms, slots, kinds,
            np.column_stack(values).astype(np.float64).reshape(shape),
            [None] * len(slots) if exemplars is None else exemplars,
            np.zeros(shape, bool) if carries is None else carries, stale)

    def _slot_exemplars(self, slots: np.ndarray) -> tuple[list, np.ndarray]:
        """([S] Exemplar or None, [S] bool has one). Pushes write the dict
        meanwhile, so it is read a key at a time and never iterated."""
        exemplars = [self.exemplars.get(s) for s in slots.tolist()]
        return exemplars, np.array([e is not None for e in exemplars], bool)

    def collect(self, ts_ms: int, snap: tuple | None = None) -> list[Sample]:
        return self.columns(ts_ms, snap).samples()

    def share_table(self, other: "_MetricBase") -> None:
        """Adopt `other`'s series table so the families stay slot-aligned
        (the spanmetrics calls/latency/size trio). In the paged layout
        the shared table's backing adopts THIS family's planes, so one
        slot allocation backs every co-tabled plane atomically."""
        mine = self.table
        if mine is other.table:
            return
        if getattr(other.table, "backing", None) is not None and \
                getattr(mine, "backing", None) is not None:
            other.table.backing.adopt(mine.backing)
        self.table = other.table

    def zero_evicted(self, padded_slots: np.ndarray) -> None:
        """Zero the device rows of evicted slots (staleness purge).
        Paged families override to scatter through their page tables."""
        self.state = m.zero_slots(self.state, padded_slots)

    def device_state_bytes(self) -> int:
        """Device bytes this family holds (dense: full pre-sized arrays;
        paged override: backed pages only)."""
        state = getattr(self, "state", None)
        return sum(int(getattr(leaf, "nbytes", 0))
                   for leaf in jax.tree.leaves(state))


class Counter(_MetricBase):
    def __init__(self, registry, name, label_names, capacity):
        super().__init__(registry, name, label_names, capacity)
        self.state = m.counter_init(capacity)

    def inc_batch(self, label_rows: np.ndarray, weights: np.ndarray | None = None,
                  valid: np.ndarray | None = None) -> np.ndarray:
        slots = self.resolve_slots(label_rows, valid)
        self.add_slots(slots, weights)
        return slots

    def add_slots(self, slots: np.ndarray,
                  weights: np.ndarray | None = None) -> None:
        """Device half with slots already resolved (processors that share
        one resolve across families — servicegraphs, spanmetrics)."""
        self.state = m.counter_update(self.state, slots, weights, None)

    def inc(self, label_values: Sequence[str], value: float = 1.0) -> None:
        row = self.registry.interner.intern_many(label_values)[None, :]
        self.inc_batch(row, np.array([value], np.float32))

    def _snap(self) -> tuple:
        return (np.asarray(self.state.values),)

    def columns(self, ts_ms: int, snap: tuple | None = None) -> FamilyColumns:
        (vals,) = snap if snap is not None else self._snap()
        slots = self.table.active_slots()
        exemplars, has = self._slot_exemplars(slots)
        return self._columns(ts_ms, slots, (("", None),), [vals[slots]],
                             exemplars, has[:, None])


class Gauge(_MetricBase):
    def __init__(self, registry, name, label_names, capacity):
        super().__init__(registry, name, label_names, capacity)
        self.state = m.gauge_init(capacity)

    def set_batch(self, label_rows: np.ndarray, values: np.ndarray,
                  valid: np.ndarray | None = None) -> None:
        slots = self.resolve_slots(label_rows, valid)
        # last-wins per slot, resolved on host (scatter order is unspecified)
        order = np.arange(slots.shape[0])
        keep = {}
        for i in order.tolist():
            if slots[i] >= 0:
                keep[int(slots[i])] = i
        if not keep:
            return
        idx = np.fromiter(keep.values(), int)
        # pad to a pow-2 shape bucket: the distinct-slot count varies per
        # batch and an unbucketed scatter would re-trace on every new
        # cardinality (padding slots are -1 → dropped on device)
        n = len(idx)
        cap = _pad_len(n)
        s = np.full(cap, -1, np.int32)
        s[:n] = slots[idx]
        v = np.zeros(cap, np.float32)
        v[:n] = values[idx]
        self._device_set(s, v)

    def _device_set(self, slots: np.ndarray, values: np.ndarray) -> None:
        self.state = m.gauge_set(self.state, slots, values, None)

    def set(self, label_values: Sequence[str], value: float) -> None:
        row = self.registry.interner.intern_many(label_values)[None, :]
        self.set_batch(row, np.array([value], np.float32))

    def _snap(self) -> tuple:
        return (np.asarray(self.state.values),)

    def columns(self, ts_ms: int, snap: tuple | None = None) -> FamilyColumns:
        (vals,) = snap if snap is not None else self._snap()
        slots = self.table.active_slots()
        return self._columns(ts_ms, slots, (("", None),), [vals[slots]])


class Histogram(_MetricBase):
    """Classic histogram family → `_count`/`_sum`/`_bucket{le=...}` series."""

    def __init__(self, registry, name, label_names, capacity,
                 edges: tuple[float, ...] = DEFAULT_HISTOGRAM_EDGES):
        super().__init__(registry, name, label_names, capacity)
        self.state = m.histogram_init(capacity, edges)

    def observe_batch(self, label_rows: np.ndarray, values: np.ndarray,
                      weights: np.ndarray | None = None,
                      valid: np.ndarray | None = None) -> np.ndarray:
        slots = self.resolve_slots(label_rows, valid)
        self.observe_slots(slots, values, weights)
        return slots

    def observe_slots(self, slots: np.ndarray, values: np.ndarray,
                      weights: np.ndarray | None = None) -> None:
        self.state = m.histogram_update(self.state, slots, values, weights, None)

    def observe(self, label_values: Sequence[str], value: float) -> None:
        row = self.registry.interner.intern_many(label_values)[None, :]
        self.observe_batch(row, np.array([value], np.float32))

    def hist_edges(self) -> tuple:
        return self.state.edges

    def _snap(self) -> tuple:
        return (np.asarray(self.state.bucket_counts),
                np.asarray(self.state.sums), np.asarray(self.state.counts))

    def columns(self, ts_ms: int, snap: tuple | None = None) -> FamilyColumns:
        bc, sums, counts = snap if snap is not None else self._snap()
        slots = self.table.active_slots()
        edges = self.hist_edges()
        cum = np.cumsum(bc[slots], axis=1)
        kinds = (("_count", None), ("_sum", None),
                 *(("_bucket", _fmt_le(e)) for e in edges),
                 ("_bucket", "+Inf"))
        exemplars, has = self._slot_exemplars(slots)
        # a bucket carries the slot's exemplar where its value fits under
        # the edge, `+Inf` always, `_count` and `_sum` never
        carries = np.zeros((len(slots), len(kinds)), bool)
        ex_vals = np.array([e.value if e is not None else np.nan
                            for e in exemplars], np.float64)
        carries[:, 2:-1] = ex_vals[:, None] <= np.asarray(edges, np.float64)
        carries[:, -1] = has
        return self._columns(
            ts_ms, slots, kinds,
            [counts[slots], sums[slots], cum[:, :len(edges)], cum[:, -1]],
            exemplars, carries)


class NativeHistogram(_MetricBase):
    """Exponential histogram family (remote-write native histogram payloads)."""

    def __init__(self, registry, name, label_names, capacity):
        super().__init__(registry, name, label_names, capacity)
        self.state = m.native_histogram_init(capacity)

    def observe_batch(self, label_rows: np.ndarray, values: np.ndarray,
                      weights: np.ndarray | None = None,
                      valid: np.ndarray | None = None) -> np.ndarray:
        slots = self.resolve_slots(label_rows, valid)
        self.observe_slots(slots, values, weights)
        return slots

    def observe_slots(self, slots: np.ndarray, values: np.ndarray,
                      weights: np.ndarray | None = None) -> None:
        self.state = m.native_histogram_update(self.state, slots, values,
                                               weights, None)

    def _snap(self) -> tuple:
        return (np.asarray(self.state.sums), np.asarray(self.state.counts))

    def columns(self, ts_ms: int, snap: tuple | None = None) -> FamilyColumns:
        # Scalar samples for visibility; the remote-write encoder additionally
        # reads `native_payload()` for real native-histogram protos.
        sums, counts = snap if snap is not None else self._snap()
        slots = self.table.active_slots()
        return self._columns(ts_ms, slots, (("_count", None), ("_sum", None)),
                             [counts[slots], sums[slots]])

    def hist_offset(self) -> int:
        return self.state.hist.offset

    def native_payload(self):
        """(slots, labels, log2 counts, sums, counts, zeros) for remote write."""
        slots = self.table.active_slots()
        return (slots, [self.labels_of(s) for s in slots.tolist()],
                np.asarray(self.state.hist.counts)[slots],
                np.asarray(self.state.sums)[slots],
                np.asarray(self.state.counts)[slots],
                np.asarray(self.state.zeros)[slots])


def _fmt_le(e: float) -> str:
    return repr(round(e, 9)) if e != int(e) else str(int(e))


class ManagedRegistry:
    """Per-tenant registry: metric families + limits + collection."""

    def __init__(self, tenant: str = "single-tenant",
                 overrides: RegistryOverrides | None = None,
                 interner: StringInterner | None = None,
                 now: Callable[[], float] = time.time):
        self.tenant = tenant
        self.overrides = overrides or RegistryOverrides()
        self.interner = interner if interner is not None else StringInterner()
        self.now = now
        self.budget = SeriesBudget(self.overrides.max_active_series)
        self._metrics: dict[str, _MetricBase] = {}
        # paged layout (registry/pages.py): when the process page pool is
        # on and this tenant's capacity splits into whole pages, families
        # are built PAGED — device rows live in the pooled arenas behind
        # per-family indirection tables instead of full dense planes
        from tempo_tpu.registry import pages as pages_mod
        self.pages = pages_mod.active()
        if self.pages is not None and \
                self.overrides.max_active_series % self.pages.page_rows:
            _LOG.warning(
                "registry %s: max_active_series %d not divisible by "
                "pages.page_rows %d — tenant stays on the dense layout",
                tenant, self.overrides.max_active_series,
                self.pages.page_rows)
            self.pages = None
        # serializes device-state REBINDS that donate the old buffers
        # (the packed ingest fast path) against state READERS (collect /
        # native_histograms / purge's zero_slots): a donated input is
        # DELETED at dispatch, so an unlocked concurrent np.asarray on the
        # collection thread would hit a dead array. Paged tenants share
        # the POOL's re-entrant lock — arenas are cross-tenant state.
        self.state_lock = self.pages.lock if self.pages is not None \
            else threading.Lock()

    # -- family constructors ----------------------------------------------

    def _capacity_share(self) -> int:
        # Every family's table has full capacity; the cross-family total of
        # allocated label combos is enforced by the shared `budget` that all
        # SeriesTables consult on allocation (registry.go:184-197 analog).
        return self.overrides.max_active_series

    def _family_types(self):
        if self.pages is not None:
            from tempo_tpu.registry import paged
            return (paged.PagedCounter, paged.PagedGauge,
                    paged.PagedHistogram, paged.PagedNativeHistogram)
        return (Counter, Gauge, Histogram, NativeHistogram)

    def new_counter(self, name: str, label_names: Sequence[str]) -> Counter:
        c = self._family_types()[0](self, name, label_names,
                                    self._capacity_share())
        self._metrics[name] = c
        return c

    def new_gauge(self, name: str, label_names: Sequence[str]) -> Gauge:
        g = self._family_types()[1](self, name, label_names,
                                    self._capacity_share())
        self._metrics[name] = g
        return g

    def new_histogram(self, name: str, label_names: Sequence[str],
                      edges: tuple[float, ...] = DEFAULT_HISTOGRAM_EDGES
                      ) -> Histogram:
        h = self._family_types()[2](self, name, label_names,
                                    self._capacity_share(), edges)
        self._metrics[name] = h
        return h

    def new_native_histogram(self, name: str, label_names: Sequence[str]) -> NativeHistogram:
        h = self._family_types()[3](self, name, label_names,
                                    self._capacity_share())
        self._metrics[name] = h
        return h

    # -- bookkeeping -------------------------------------------------------

    @property
    def active_series(self) -> int:
        # Families may share a SeriesTable (the spanmetrics trio); count each
        # table once so the figure is comparable to max_active_series, which
        # gates allocation per table.
        seen: dict[int, int] = {}
        for mt in self._metrics.values():
            seen[id(mt.table)] = mt.table.active_count
        return sum(seen.values())

    @property
    def discarded_series(self) -> int:
        return sum(mt.table.discarded for mt in self._metrics.values())

    def collect_columns(self, ts_ms: int | None = None) -> list[FamilyColumns]:
        """The collection tick (`registry.go:206-256`): one synchronized
        timestamp across all families, device state gathered once each,
        handed on in columns (what the remote write encodes from)."""
        if self.overrides.disable_collection:
            return []
        ts = int(self.now() * 1000) if ts_ms is None else ts_ms
        # ONLY the device snapshots sit under the lock (they are what a
        # donating push would invalidate); cutting them to the active
        # slots runs outside so ingest never stalls behind it
        with tracing.span("registry.gather"), self.state_lock:
            snaps = [(mt, mt._snap()) for mt in self._metrics.values()]
        with tracing.span("registry.format"):
            return [mt.columns(ts, snap) for mt, snap in snaps]

    def collect(self, ts_ms: int | None = None) -> list[Sample]:
        """The tick as one `Sample` a series, built from the columns."""
        return [s for cols in self.collect_columns(ts_ms)
                for s in cols.samples()]

    def purge_stale(self) -> int:
        """Evict idle series and zero their device rows; returns eviction
        count (of label combos). Families may share a SeriesTable (e.g. the
        spanmetrics calls/latency/size trio stays slot-aligned); eviction is
        computed once per table but EVERY family on that table gets its
        device rows zeroed and its staleness markers queued."""
        cutoff = self.now() - self.overrides.stale_duration_s
        by_table: dict[int, list[_MetricBase]] = {}
        for mt in self._metrics.values():
            by_table.setdefault(id(mt.table), []).append(mt)
        total = 0
        for fams in by_table.values():
            table = fams[0].table
            stale = np.flatnonzero(table.active & (table.last_seen < cutoff))
            if not stale.size:
                continue
            # pad to a small set of static shapes to bound recompiles
            padded = np.full(_pad_len(stale.size), table.capacity, np.int32)
            padded[: stale.size] = stale
            # one lock over the WHOLE shared-table eviction: a concurrent
            # collect must never see the slot-aligned trio half-zeroed
            with self.state_lock:
                for mt in fams:
                    mt.note_stale(stale)
                    mt.zero_evicted(padded)
                    for hook in mt.evict_hooks:
                        hook(padded)
                table.purge_stale(cutoff)
            total += stale.size
        return total

    def device_state_bytes(self) -> int:
        """Device bytes across this registry's families (dense: full
        pre-sized planes; paged: backed pages only). Processor-owned
        sidecars (the spanmetrics DDSketch plane) are NOT included —
        `GeneratorInstance.device_state_bytes` adds those."""
        return sum(mt.device_state_bytes() for mt in self._metrics.values())

    def native_histograms(self, ts_ms: int | None = None) -> list[tuple]:
        """(labels, log2_counts, sum, count, zeros, ts, offset) per active
        native-histogram series, in the shape encode_write_request consumes."""
        ts = int(self.now() * 1000) if ts_ms is None else ts_ms
        out = []
        with self.state_lock:
            payloads = [(mt, getattr(mt, "native_payload", None))
                        for mt in self._metrics.values()]
            payloads = [(mt, p()) for mt, p in payloads if p is not None]
        for mt, payload in payloads:
            slots, labels, hists, sums, counts, zeros = payload
            offset = mt.hist_offset()
            for i in range(len(labels)):
                out.append((labels[i], hists[i], float(sums[i]),
                            float(counts[i]), float(zeros[i]), ts, offset))
        return out

    def metric(self, name: str) -> _MetricBase:
        return self._metrics[name]


def _pad_len(n: int) -> int:
    # the shared shape-bucket policy (device scheduler coalescer), floor 16
    from tempo_tpu.sched import bucket_rows

    return bucket_rows(max(n, 1), lo=16)
