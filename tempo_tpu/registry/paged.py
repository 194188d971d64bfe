"""Paged metric families: the dense registry families over pooled pages.

Each class keeps the dense family's HOST half untouched (series table,
exemplars, staleness markers, collect formatting — inherited) and swaps
ONLY the device half: rows live in the process page pool's arenas behind
a per-family indirection table (`registry/pages.py`), updates go through
the paged scatter kernels (`ops/pages.py`), and snapshots gather active
slots back through the same table into capacity-shaped host arrays so
`collect()` emits bit-identical samples to the dense layout.

Every device op runs under the registry state lock, which for paged
tenants IS the pool's re-entrant lock: arenas are shared across tenants
and DONATED at dispatch, the same discipline as the dense fast paths.

`ManagedRegistry` picks these classes automatically when the process
page pool is configured (`pages.enabled`); nothing else changes for
callers.
"""

from __future__ import annotations

import numpy as np

from tempo_tpu.ops import pages as op
from tempo_tpu.registry import metrics as m
from tempo_tpu.registry.pages import PageBacking, PagedPlane
from tempo_tpu.registry.registry import (
    Counter,
    Gauge,
    Histogram,
    NativeHistogram,
    _MetricBase,
    _pad_len,
)


class _PagedBase(_MetricBase):
    """Shared paged plumbing: planes + backing + gather snapshots."""

    def _init_paged(self, registry, name, label_names, capacity) -> None:
        _MetricBase.__init__(self, registry, name, label_names, capacity)
        self.pool = registry.pages
        self.planes: dict[str, PagedPlane] = {}
        self.table.backing = PageBacking(self.pool)

    def _plane(self, role: str, width: int,
               limit: "int | None" = None) -> PagedPlane:
        p = PagedPlane(self.pool, "float32", width, self.table.capacity
                       if limit is None else limit,
                       self.registry.tenant,
                       role=f"{self.name}/{role}")
        self.planes[role] = p
        self.table.backing.add_plane(p, limit)
        return p

    def _padded_active(self) -> tuple[np.ndarray, int]:
        """Active slots padded to a pow-2 bucket (-1 rows read 0) so the
        gather kernel keeps a handful of warm shapes."""
        slots = self.table.active_slots()
        padded = np.full(_pad_len(max(slots.size, 1)), -1, np.int32)
        padded[:slots.size] = slots
        return padded, slots.size

    def _gather_full(self, plane: PagedPlane) -> np.ndarray:
        """Capacity-shaped host array with active rows filled — the shape
        the dense `_snap`/`collect` pipeline already consumes."""
        padded, n = self._padded_active()
        shape = (self.table.capacity,) if plane.width == 1 \
            else (self.table.capacity, plane.width)
        full = np.zeros(shape, np.float32)
        if n:
            full[padded[:n]] = plane.gather(padded)[:n]
        return full

    def zero_evicted(self, padded_slots: np.ndarray) -> None:
        for p in self.planes.values():
            # the registry pads the eviction batch with `table.capacity`
            # (dense OOB); the paged discard encoding is NEGATIVE slots
            # (positive OOB would clip into the last logical page), and
            # planes may cover a strict prefix of the table
            p.zero_slots(np.where(padded_slots < p.capacity,
                                  padded_slots, -1))

    def device_state_bytes(self) -> int:
        return sum(p.device_state_bytes() for p in self.planes.values())

    def _w(self, slots, weights) -> np.ndarray:
        return np.ones(len(slots), np.float32) if weights is None \
            else np.asarray(weights, np.float32)


class PagedCounter(_PagedBase, Counter):
    def __init__(self, registry, name, label_names, capacity):
        self._init_paged(registry, name, label_names, capacity)
        self.values = self._plane("values", 1)

    def add_slots(self, slots: np.ndarray,
                  weights: np.ndarray | None = None) -> None:
        with self.registry.state_lock:
            self.values.rebind(op.counter_add_step(self.pool.page_shift)(
                self.values.data, self.values.device_map(),
                np.ascontiguousarray(slots, np.int32),
                self._w(slots, weights)))

    def _snap(self) -> tuple:
        return (self._gather_full(self.values),)


class PagedGauge(_PagedBase, Gauge):
    def __init__(self, registry, name, label_names, capacity):
        self._init_paged(registry, name, label_names, capacity)
        self.values = self._plane("values", 1)

    def _device_set(self, slots: np.ndarray, values: np.ndarray) -> None:
        with self.registry.state_lock:
            self.values.rebind(op.gauge_set_step(self.pool.page_shift)(
                self.values.data, self.values.device_map(),
                np.ascontiguousarray(slots, np.int32),
                np.asarray(values, np.float32)))

    def _snap(self) -> tuple:
        return (self._gather_full(self.values),)


class PagedHistogram(_PagedBase, Histogram):
    def __init__(self, registry, name, label_names, capacity,
                 edges: tuple[float, ...] = None):
        from tempo_tpu.registry.registry import DEFAULT_HISTOGRAM_EDGES
        self._init_paged(registry, name, label_names, capacity)
        self.edges = tuple(DEFAULT_HISTOGRAM_EDGES if edges is None else edges)
        self.buckets = self._plane("buckets", len(self.edges) + 1)
        self.sums = self._plane("sums", 1)
        self.counts = self._plane("counts", 1)

    def hist_edges(self) -> tuple:
        return self.edges

    def observe_slots(self, slots: np.ndarray, values: np.ndarray,
                      weights: np.ndarray | None = None) -> None:
        with self.registry.state_lock:
            a_sums, a_counts, ab = op.histogram_observe_step(
                self.edges, self.pool.page_shift)(
                self.sums.data, self.counts.data, self.buckets.data,
                self.buckets.device_map(), self.sums.device_map(),
                self.counts.device_map(),
                np.ascontiguousarray(slots, np.int32),
                np.asarray(values, np.float32), self._w(slots, weights))
            self.sums.rebind(a_sums)
            self.counts.rebind(a_counts)
            self.buckets.rebind(ab)

    def _snap(self) -> tuple:
        return (self._gather_full(self.buckets),
                self._gather_full(self.sums),
                self._gather_full(self.counts))


class PagedNativeHistogram(_PagedBase, NativeHistogram):
    def __init__(self, registry, name, label_names, capacity):
        self._init_paged(registry, name, label_names, capacity)
        self.offset = m.NATIVE_HISTOGRAM_OFFSET
        self.hist = self._plane("hist", 64)
        self.sums = self._plane("sums", 1)
        self.counts = self._plane("counts", 1)
        self.zeros = self._plane("zeros", 1)

    def hist_offset(self) -> int:
        return self.offset

    def observe_slots(self, slots: np.ndarray, values: np.ndarray,
                      weights: np.ndarray | None = None) -> None:
        with self.registry.state_lock:
            a_sums, a_counts, a_zeros, ah = op.native_hist_step(
                self.offset, self.pool.page_shift)(
                self.sums.data, self.counts.data, self.zeros.data,
                self.hist.data,
                self.hist.device_map(), self.sums.device_map(),
                self.counts.device_map(), self.zeros.device_map(),
                np.ascontiguousarray(slots, np.int32),
                np.asarray(values, np.float32), self._w(slots, weights))
            self.sums.rebind(a_sums)
            self.counts.rebind(a_counts)
            self.zeros.rebind(a_zeros)
            self.hist.rebind(ah)

    def _snap(self) -> tuple:
        return (self._gather_full(self.sums),
                self._gather_full(self.counts))

    def native_payload(self):
        padded, n = self._padded_active()
        slots = padded[:n]
        return (slots, [self.labels_of(s) for s in slots.tolist()],
                self.hist.gather(padded)[:n],
                self.sums.gather(padded)[:n],
                self.counts.gather(padded)[:n],
                self.zeros.gather(padded)[:n])


__all__ = ["PagedCounter", "PagedGauge", "PagedHistogram",
           "PagedNativeHistogram"]
