"""servicegraphs processor: client/server span pairing → edge metrics.

Reference semantics (`modules/generator/processor/servicegraphs/`):

- `consume` (`servicegraphs.go:172-255`): CLIENT/PRODUCER spans register an
  edge keyed by (trace id, span id); SERVER/CONSUMER spans match on
  (trace id, parent span id). A completed edge emits:
  `traces_service_graph_request_total`, `_failed_total` (either side errored),
  `_client_seconds` / `_server_seconds` histograms (+ messaging-system delay
  for PRODUCER/CONSUMER pairs), labeled (client, server) service names.
- expiring edge store (`store/store.go:29,78,119`): TTL ring; expired
  half-edges infer virtual nodes (`servicegraphs.go:390-421`): an unmatched
  ROOT SERVER span (no parent) gets client="user"; an unmatched CLIENT span
  that carries a peer attribute (`peer_attributes`, first present wins)
  gets a server node named by its value. Any other expired half (a server
  whose client never came, a client with no peer attribute) emits nothing.
  Expiry runs inside the tenant's own pushes: a tenant that stops pushing
  keeps its pending halves until its next push.

TPU split: edge *matching* stays on the host, and a push's halves are
paired in ONE native call: the pending halves are columns by store slot
(`_HalfStore`), their 24-byte trace+span keys in `native.HalfIndex` (an
open-addressing map; a dict under `use_native=False`), and the TTL ring is
arrays of (expire_at, key) that expiry takes a due prefix of at once. The
metric updates for a push's edges (completed and expired together) are ONE
jitted, donating device step over the shared registry's families, fed by
one packed f32 matrix: on the dense layout over the families' states
(`_edge_update_impl`), on the paged layout over their arenas through the
page tables (`ops.pages.edge_step`). Latencies feed the classic
histograms only; there is no sketch per edge series.
"""

from __future__ import annotations

import dataclasses
import threading

import jax.numpy as jnp
import numpy as np

from tempo_tpu.model.interner import INVALID_ID
from tempo_tpu.model.span_batch import (
    KIND_CLIENT,
    KIND_CONSUMER,
    KIND_PRODUCER,
    KIND_SERVER,
    STATUS_ERROR,
    SpanBatch,
)
from tempo_tpu.native import HalfIndex, first_svals
from tempo_tpu.obs.jaxruntime import RUNTIME, instrumented_jit
from tempo_tpu.ops import pages as pages_op
from tempo_tpu.registry import metrics as rm
from tempo_tpu.registry.registry import DEFAULT_HISTOGRAM_EDGES, ManagedRegistry
from tempo_tpu.utils import tracing, turn

_PEER_ATTRS = ("peer.service", "db.name", "db.system", "messaging.system",
               "net.peer.name")  # `servicegraphs.go:287-343` heuristics
# edges a device step: an emit of more (a push that completes hundreds of
# edges while a stall's worth of halves expires) takes several steps. A
# step pads to one of TWO shapes, 16 rows (a push of a few pairs) or
# _EMIT_ROWS: every shape is a cold compile on the chip, and a step's
# time there (~0.3 ms on a v5e) is the relayout of its state planes, not
# its rows
_EMIT_ROWS = 512
_EMIT_MIN_ROWS = 16

EMITS = RUNTIME.counter(
    "tempo_metrics_generator_servicegraphs_emits_total",
    "Service-graph edge emits by device path: fused = one jitted, "
    "donating step a push, dense or paged; family = the families' own "
    "calls (a series table of 2^24 slots or more)",
    labels=("path",))
HALVES = RUNTIME.counter(
    "tempo_metrics_generator_servicegraphs_halves_total",
    "Service-graph halves paired or stored, by the route of the pairing "
    "call: native = one C++ walk a push, dict = its Python fallback",
    labels=("route",))


def _edge_update_impl(states, packed):
    """One device step for all edge families (slots shared). `states` is
    (total, failed, client_hist, server_hist[, messaging_hist]); `packed`
    is ONE f32 matrix of rows `slots, fail, cdur, sdur` (+ `mslots, mdur`
    with the messaging histogram on; without it the four-family graph is
    traced). Slots ride f32 exactly while the series table's capacity is
    below 2^24 (the caller gates on that); padding rows carry slot -1 and
    drop on the device. The registry's update functions are the ones the
    family-level calls run, so the two paths cannot drift."""
    total, failed, client_hist, server_hist, *messaging = states
    slots = packed[0].astype(jnp.int32)
    out = (rm.counter_update(total, slots),
           rm.counter_update(failed, slots, packed[1]),
           rm.histogram_update(client_hist, slots, packed[2]),
           rm.histogram_update(server_hist, slots, packed[3]))
    if messaging:
        out += (rm.histogram_update(
            messaging[0], packed[4].astype(jnp.int32), packed[5]),)
    return out


# donating, as the spanmetrics fused step is: callers hold the registry's
# state_lock across call + rebind, since donation deletes the input
# buffers at dispatch for any concurrent reader. The module is named
# `jit__edge_update_impl` in a profile: outside the `jit__fused_update*`
# prefix the benchmark's spanmetrics roofline reads
_edge_update = instrumented_jit(
    _edge_update_impl, name="servicegraphs_edge_update", donate_argnums=0)


@dataclasses.dataclass
class ServiceGraphsConfig:
    histogram_buckets: tuple[float, ...] = DEFAULT_HISTOGRAM_EDGES
    wait_s: float = 10.0                 # edge TTL before expiry
    max_items: int = 10000               # store capacity
    # span / resource attributes that name an uninstrumented peer of an
    # unmatched CLIENT span, in order of precedence
    peer_attributes: tuple[str, ...] = _PEER_ATTRS
    enable_client_server_prefix: bool = False
    enable_messaging_system_latency_histogram: bool = False
    enable_virtual_node_label: bool = False


# connection types of an edge, by the code `EdgeColumns.conn` carries
_CONNS = ("", "messaging_system", "virtual_node")
_MESSAGING, _VIRTUAL = 1, 2


@dataclasses.dataclass(frozen=True)
class EdgeColumns:
    """Edges to emit, a column a field and an edge a row: client and
    server service ids, connection type (an index into `_CONNS`), client
    and server seconds, either side failed, and the messaging-system
    delay (server start - client start, 0 where it is negative)."""

    client: np.ndarray
    server: np.ndarray
    conn: np.ndarray
    client_s: np.ndarray
    server_s: np.ndarray
    failed: np.ndarray
    messaging_s: np.ndarray

    @classmethod
    def empty(cls) -> "EdgeColumns":
        return cls(*(np.zeros(0, dt) for dt in (
            np.int32, np.int32, np.int8, np.float64, np.float64, np.bool_,
            np.float64)))

    @property
    def n(self) -> int:
        return len(self.client)

    def columns(self) -> tuple:
        return tuple(getattr(self, f.name)
                     for f in dataclasses.fields(self))

    def rows(self, lo: int, hi: int) -> "EdgeColumns":
        return EdgeColumns(*(c[lo:hi] for c in self.columns()))

    @classmethod
    def cat(cls, *parts: "EdgeColumns") -> "EdgeColumns":
        return cls(*(np.concatenate(cs)
                     for cs in zip(*(p.columns() for p in parts))))


# a pending half's columns, each an array by store slot (a record array
# would be one gather, but numpy gathers records ~20x slower than a
# column). `virtual` says which edge its expiry may name
# (`servicegraphs.go:390-421`): 1 a client whose peer attribute names a
# server node, 2 a ROOT server (client "user"), 0 none
_HALF = (("service", np.int32), ("peer", np.int32), ("start_ns", np.int64),
         ("dur_s", np.float64), ("failed", np.bool_),
         ("is_messaging", np.bool_), ("virtual", np.int8),
         ("expire_at", np.float64))
_TO_PEER, _FROM_USER = 1, 2
# a half's key as one void scalar: 1-D arrays of them copy without giving
# the interpreter lock up, which 2-D byte arrays of a push's size do
_KEY = np.dtype(f"V{HalfIndex.width}")


class _HalfStore:
    """The pending halves: a column a field (`_HALF`), a half a store
    slot, the slots no half holds on a free stack, and each waiting half's
    key -> `2 * slot + is_client` in a `HalfIndex`.
    The TTL ring is arrays of (expire_at, key) from `head` to `tail`,
    appended in push order: an entry names a KEY, as the reference's ring
    does, so an entry whose half was matched or replaced finds the key's
    present half, or none."""

    def __init__(self, use_native: bool = True) -> None:
        self.index = HalfIndex(use_native=use_native)
        for name, dt in _HALF:
            setattr(self, name, np.zeros(0, dt))
        self.free = np.zeros(0, np.int64)   # stack: free[:n_free]
        self.n_free = 0
        self.ring_at = np.zeros(0, np.float64)
        self.ring_key = np.zeros(0, _KEY)
        self.head = self.tail = 0

    def fresh(self, n: int) -> np.ndarray:
        """`n` free slots, the stack's top first (`take` pops them)."""
        if self.n_free < n:
            old = len(self.service)
            cap = max(2 * old, old + n, 1024)
            for name, dt in _HALF:
                setattr(self, name, np.concatenate(
                    [getattr(self, name), np.zeros(cap - old, dt)]))
            free = np.zeros(cap, np.int64)
            free[:self.n_free] = self.free[:self.n_free]
            free[self.n_free:self.n_free + cap - old] = np.arange(
                cap - 1, old - 1, -1)
            self.free, self.n_free = free, self.n_free + cap - old
        return self.free[self.n_free - n:self.n_free][::-1].copy()

    def take(self, n: int) -> None:
        self.n_free -= n

    def give_back(self, slots: np.ndarray) -> None:
        self.free[self.n_free:self.n_free + len(slots)] = slots
        self.n_free += len(slots)

    def ring_append(self, at, keys: np.ndarray) -> None:
        n = len(keys)
        if self.tail + n > len(self.ring_at):
            live = self.tail - self.head
            size = len(self.ring_at)
            if live + n > size // 2:
                size = max(2 * size, live + n, 1024)
            ring_at = np.zeros(size, np.float64)
            ring_key = np.zeros(size, _KEY)
            ring_at[:live] = self.ring_at[self.head:self.tail]
            ring_key[:live] = self.ring_key[self.head:self.tail]
            self.ring_at, self.ring_key = ring_at, ring_key
            self.head, self.tail = 0, live
        self.ring_at[self.tail:self.tail + n] = at
        self.ring_key[self.tail:self.tail + n] = keys
        self.tail += n

    def ring_due(self, now: float) -> np.ndarray:
        """Pop the keys of the ring's prefix up to its first entry due
        after `now` (not a search: a clock that stepped back leaves it
        unsorted). Scanned in growing chunks from the head: a prefix is
        about a push's halves."""
        end, step = self.head, 256
        while end < self.tail:
            late = self.ring_at[end:min(end + step, self.tail)] > now
            if late.any():
                end += int(late.argmax())
                break
            end, step = end + len(late), 2 * step
        lo, self.head = self.head, end
        return self.ring_key[lo:end].copy()


class ServiceGraphsProcessor:
    def __init__(self, registry: ManagedRegistry,
                 config: ServiceGraphsConfig | None = None,
                 use_native: bool = True):
        self.cfg = config or ServiceGraphsConfig()
        self.registry = registry
        labels = ("client", "server", "connection_type")
        edges = self.cfg.histogram_buckets
        self.total = registry.new_counter("traces_service_graph_request_total", labels)
        self.failed = registry.new_counter("traces_service_graph_request_failed_total", labels)
        self.client_hist = registry.new_histogram(
            "traces_service_graph_request_client_seconds", labels, edges=edges)
        self.server_hist = registry.new_histogram(
            "traces_service_graph_request_server_seconds", labels, edges=edges)
        for fam in (self.failed, self.client_hist, self.server_hist):
            fam.share_table(self.total)  # edge families stay slot-aligned
        if self.cfg.enable_messaging_system_latency_histogram:
            self.messaging_hist = registry.new_histogram(
                "traces_service_graph_request_messaging_system_seconds", labels, edges=edges)
            self.messaging_hist.share_table(self.total)
        else:
            self.messaging_hist = None
        self._families = [self.total, self.failed, self.client_hist,
                          self.server_hist] + (
            [self.messaging_hist] if self.messaging_hist is not None else [])
        # one jitted step a push while slots ride its f32 matrix exactly
        # (below 2^24), over the dense families' states or, bound here,
        # the paged families' arenas; above it the families' own calls
        self._fused = self.total.table.capacity < (1 << 24)
        self._paged_step = None
        if self._fused and registry.pages is not None:
            self._paged_step = pages_op.edge_step(
                self.client_hist.edges, registry.pages.page_shift,
                self.messaging_hist is not None)
            self._planes = [p for f in self._families
                            for p in f.planes_in_order()]
        self._store = _HalfStore(use_native)
        self._route = "native" if self._store.index.native else "dict"
        self._nodes = np.zeros(0, np.int32)   # peer value -> node, `_node_of`
        # one tenant's pushes arrive on concurrent HTTP handler threads:
        # the half-edge store's pop / check / put-back must see one push
        # at a time, or two halves of one edge each find the store empty.
        # Device state is NOT this lock's: `_emit` takes the registry's
        # state_lock (order: store lock, then state_lock)
        self._store_lock = threading.Lock()
        # read by the generator's per-tenant families on /metrics
        self.dropped = 0  # store-full drops (`store.go` max_items)
        self.expired = 0  # halves that waited out `wait_s` unmatched
        self.edges = {"completed": 0, "virtual": 0}   # edges emitted

    def name(self) -> str:
        return "service-graphs"

    def store_items(self) -> int:
        """Pending halves in the store (no lock: a length read)."""
        return len(self._store.index)

    # -- ingestion ---------------------------------------------------------

    def push_batch(self, sb: SpanBatch) -> None:
        if sb.interner is not self.registry.interner:
            raise ValueError(
                "SpanBatch must be built with the tenant registry's interner")
        with self._store_lock:
            self._push_batch(sb)

    def _push_batch(self, sb: SpanBatch) -> None:
        now = self.registry.now()
        kinds = sb.kind
        client_like = (kinds == KIND_CLIENT) | (kinds == KIND_PRODUCER)
        server_like = (kinds == KIND_SERVER) | (kinds == KIND_CONSUMER)
        rows = np.flatnonzero(sb.valid & (client_like | server_like))
        parts = [self._pair(sb, rows, client_like[rows], now)] \
            if rows.size else []
        # completed and expired edges ride ONE emit a push: adds commute
        self._emit(EdgeColumns.cat(*parts, self._expire(now)))

    def _pair(self, sb: SpanBatch, rows: np.ndarray, is_client: np.ndarray,
              now: float) -> EdgeColumns:
        """Pair the push's halves (`rows`, in row order) in one call: a
        client waits under (trace id, span id), a server under (trace id,
        parent span id); a half meets the other side's waiting half under
        its key, or waits itself (`HalfIndex.pair`). Returns the completed
        edges in row order."""
        st = self._store
        keys, out, matched, prev, root, taken = st.index.pair(
            sb.trace_id, sb.span_id, sb.parent_span_id, rows, is_client,
            self.cfg.max_items, st.fresh(len(rows)))
        st.take(taken)
        service = sb.service_id[rows]
        start = sb.start_unix_nano[rows]
        dur_s = (sb.end_unix_nano[rows] - start) / 1e9
        failed = sb.status_code[rows] == STATUS_ERROR
        kinds = sb.kind[rows]
        is_msg = (kinds == KIND_PRODUCER) | (kinds == KIND_CONSUMER)
        stored = np.flatnonzero(~matched & (out >= 0))
        if stored.size:
            slots, cli = out[stored], is_client[stored]
            peer = np.full(len(stored), INVALID_ID, np.int32)
            peer[cli] = first_svals(
                sb.span_attr_key, sb.span_attr_sval, rows[stored[cli]],
                [k for k in map(sb.interner.get, self.cfg.peer_attributes)
                 if k != INVALID_ID], st.index.native)
            st.service[slots] = service[stored]
            st.peer[slots] = peer
            st.start_ns[slots] = start[stored]
            st.dur_s[slots] = dur_s[stored]
            st.failed[slots] = failed[stored]
            st.is_messaging[slots] = is_msg[stored]
            st.virtual[slots] = np.where(
                cli, np.where(peer != INVALID_ID, _TO_PEER, 0),
                np.where(root[stored], _FROM_USER, 0))
            st.expire_at[slots] = now + self.cfg.wait_s
            st.ring_append(now + self.cfg.wait_s, keys[stored])
        # a matched half comes from its slot, the row is the other side
        o, rc = out[matched], is_client[matched]
        gap = np.where(rc, st.start_ns[o] - start[matched],
                       start[matched] - st.start_ns[o])
        edges = EdgeColumns(
            client=np.where(rc, service[matched], st.service[o]),
            server=np.where(rc, st.service[o], service[matched]),
            conn=np.where(is_msg[matched] | st.is_messaging[o],
                          np.int8(_MESSAGING), np.int8(0)),
            client_s=np.where(rc, dur_s[matched], st.dur_s[o]),
            server_s=np.where(rc, st.dur_s[o], dur_s[matched]),
            failed=failed[matched] | st.failed[o],
            messaging_s=np.where(gap > 0, gap / 1e9, 0.0))
        # the matched halves' slots and those of halves a same-side row
        # replaced are free again after the call, never inside it
        st.give_back(np.concatenate([o, prev[prev >= 0]]))
        self.dropped += len(rows) - len(stored) - len(o)
        HALVES.inc(len(stored) + len(o), (self._route,))
        self.edges["completed"] += edges.n
        return edges

    # -- emission ----------------------------------------------------------

    def _emit(self, edges: EdgeColumns) -> None:
        for at in range(0, edges.n, _EMIT_ROWS):
            self._emit_step(edges.rows(at, at + _EMIT_ROWS))

    def _emit_step(self, edges: EdgeColumns) -> None:
        it = self.registry.interner
        conn_ids = np.array([it.intern(c) for c in _CONNS], np.int32)
        n = edges.n
        # pad the edge batch to a fixed shape: the matched-edge count
        # varies per push and unbucketed scatters would re-trace on every
        # new cardinality (padding rows ride slot -1 → dropped)
        cap = _EMIT_MIN_ROWS if n <= _EMIT_MIN_ROWS else _EMIT_ROWS
        messaging = self.messaging_hist is not None
        rows = np.stack([edges.client, edges.server, conn_ids[edges.conn]],
                        axis=1).astype(np.int32)
        # rows: slots, fail, cdur, sdur (+ mslots, mdur), as both steps
        # (`_edge_update_impl`, `pages_op.edge_step`) read them
        packed = np.zeros((6 if messaging else 4, cap), np.float32)
        packed[1, :n] = edges.failed
        packed[2, :n] = edges.client_s
        packed[3, :n] = edges.server_s
        if messaging:
            packed[5, :n] = edges.messaging_s
            msg = edges.conn == _MESSAGING
        # the update reads, updates and REBINDS device state, as the
        # staleness purge's zeroing and the collect's snapshot do on
        # their threads: all sit under the registry's state_lock (the
        # spanmetrics dispatch discipline), or one side's rebind drops
        # the other's and a reader meets a donated buffer. The slot
        # resolve rides inside so a purge cannot free a slot between its
        # resolve and its update. The wait for it is the device's, so a
        # push gives its turn through the distributor up before it waits
        with turn.waiting_for(self.registry.state_lock):
            slots = np.full(cap, -1, np.int32)
            slots[:n] = self.total.resolve_slots(rows)
            packed[0] = slots
            if messaging:
                mslots = np.full(cap, -1, np.int32)
                mslots[:n] = np.where(msg, slots[:n], -1)
                packed[4] = mslots
            if self._paged_step is not None:
                arenas = self._paged_step(
                    *(p.data for p in self._planes),
                    *(p.device_map() for p in self._planes), packed)
                for plane, arena in zip(self._planes, arenas):
                    plane.rebind(arena)
            elif self._fused:
                states = _edge_update(
                    tuple(f.state for f in self._families), packed)
                for fam, state in zip(self._families, states):
                    fam.state = state
            else:
                # the families' own calls, a launch each: slots of a
                # table of 2^24 or more do not ride the f32 matrix exactly
                self.total.add_slots(slots)
                self.failed.add_slots(slots, packed[1])
                self.client_hist.observe_slots(slots, packed[2])
                self.server_hist.observe_slots(slots, packed[3])
                if messaging:
                    self.messaging_hist.observe_slots(mslots, packed[5])
            EMITS.inc(1, ("fused" if self._fused else "family",))

    def _expire(self, now: float) -> EdgeColumns:
        """Expired half-edges become virtual-node edges (`servicegraphs.go:390-421`)."""
        with tracing.span("servicegraphs.expire"):
            edges = self._expire_halves(now)
        self.edges["virtual"] += edges.n
        return edges

    def _expire_halves(self, now: float) -> EdgeColumns:
        """Take the ring's due prefix at once (`HalfIndex.expire`): each
        entry finds its key's present half; one due by `now` expires at
        the first entry that finds it, one due later (the key was taken
        again since) is queued again at the ring's end under the half's
        own time."""
        st = self._store
        keys = st.ring_due(now)
        if not len(keys):
            return EdgeColumns.empty()
        gone, later, later_at = st.index.expire(keys, st.expire_at, now)
        if len(later):
            st.ring_append(later_at, keys[later])
        st.give_back(gone)
        self.expired += len(gone)
        g = gone[st.virtual[gone] != 0]
        if not len(g):
            return EdgeColumns.empty()
        # a client's peer attribute names a server node (db, queue, ...)
        # where its value is not empty; an unmatched ROOT server's request
        # came from outside (a browser, curl): client "user"
        to_peer = st.virtual[g] == _TO_PEER
        node = np.full(len(g), INVALID_ID, np.int32)
        if to_peer.any():
            node[to_peer] = self._node_of(st.peer[g[to_peer]])
        named = ~to_peer | (node != INVALID_ID)
        g, to_peer, node = g[named], to_peer[named], node[named]
        if not len(g):
            return EdgeColumns.empty()
        user = INVALID_ID if to_peer.all() \
            else self.registry.interner.intern("user")
        service, dur = st.service[g], st.dur_s[g]
        zero = np.zeros(len(g))
        return EdgeColumns(
            client=np.where(to_peer, service, user).astype(np.int32),
            server=np.where(to_peer, node, service).astype(np.int32),
            conn=np.full(len(g), _VIRTUAL, np.int8),
            client_s=np.where(to_peer, dur, zero),
            server_s=np.where(to_peer, zero, dur),
            failed=st.failed[g],
            messaging_s=zero)

    def _node_of(self, peer: np.ndarray) -> np.ndarray:
        """The server node each interned peer value names: the value
        itself, INVALID_ID where it is empty. Kept by id: the interner
        only grows."""
        if peer.max() >= len(self._nodes):
            self._nodes = np.concatenate([self._nodes, np.full(
                max(len(self._nodes), int(peer.max()) + 1), -2, np.int32)])
        node = self._nodes[peer]
        if (node == -2).any():
            it = self.registry.interner
            for pid in np.unique(peer[node == -2]).tolist():
                name = it.lookup(pid)
                self._nodes[pid] = it.intern(name) if name else INVALID_ID
            node = self._nodes[peer]
        return node
