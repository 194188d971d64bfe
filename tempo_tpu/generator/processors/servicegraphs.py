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

TPU split: edge *matching* is pointer-chasing and stays on the host (a dict
keyed by 24-byte trace+span ids, vectorized staging in/out); the metric
updates for a push's edges (completed and expired together) are ONE device
step over the shared registry's families: on the dense layout one jitted,
donating call fed by one packed f32 matrix (`_edge_update_impl`), on the
paged layout the families' own arena scatters. Latencies feed the classic
histograms only; there is no sketch per edge series.
"""

from __future__ import annotations

import collections
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
    void_keys,
)
from tempo_tpu.obs.jaxruntime import RUNTIME, instrumented_jit
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
    "donating step over the dense families; family = the families' own "
    "calls (paged layout)",
    labels=("path",))


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


@dataclasses.dataclass
class _HalfEdge:
    service_id: int
    duration_s: float
    failed: bool
    is_client: bool
    is_messaging: bool
    peer_id: int          # interned peer-attr value (client side), or INVALID_ID
    start_ns: int
    expire_at: float
    is_root: bool = False  # a server span with no parent


class ServiceGraphsProcessor:
    def __init__(self, registry: ManagedRegistry, config: ServiceGraphsConfig | None = None):
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
        # the layout decides the device path: dense families take the
        # jitted step (slots ride its f32 matrix exactly below 2^24),
        # paged families keep their arena scatters under the pool's lock
        self._fused = registry.pages is None \
            and self.total.table.capacity < (1 << 24)
        self._store: dict[bytes, _HalfEdge] = {}
        self._ttl: collections.deque[tuple[float, bytes]] = collections.deque()
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
        return len(self._store)

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
        interesting = np.flatnonzero(sb.valid & (client_like | server_like))
        if interesting.size == 0:
            self._emit(self._expire(now))
            return
        dur_s = sb.duration_ns / 1e9
        failed = sb.status_code == STATUS_ERROR
        peer_col = self._peer_col(sb)
        # client keys on own span id; server keys on parent span id —
        # both key columns built in two vectorized void views instead of
        # three `.tobytes()` calls per span (`keys[i].item()` is the
        # exact 24-byte concatenation the old loop produced)
        keys_client = void_keys(sb.trace_id, sb.span_id)
        keys_server = void_keys(sb.trace_id, sb.parent_span_id)
        root = ~sb.parent_span_id.any(axis=1)
        completed: list[tuple] = []
        for i in interesting.tolist():
            is_client = bool(client_like[i])
            is_messaging = kinds[i] in (KIND_PRODUCER, KIND_CONSUMER)
            key = (keys_client[i] if is_client else keys_server[i]).item()
            other = self._store.pop(key, None)
            if other is not None and other.is_client != is_client:
                cli, srv = (other, None) if other.is_client else (None, other)
                if is_client:
                    cli = _HalfEdge(int(sb.service_id[i]), float(dur_s[i]),
                                    bool(failed[i]), True, is_messaging,
                                    int(peer_col[i]), int(sb.start_unix_nano[i]), 0)
                else:
                    srv = _HalfEdge(int(sb.service_id[i]), float(dur_s[i]),
                                    bool(failed[i]), False, is_messaging,
                                    INVALID_ID, int(sb.start_unix_nano[i]), 0)
                if cli is None:
                    cli = other
                if srv is None:
                    srv = other
                conn = ("messaging_system" if (cli.is_messaging or srv.is_messaging)
                        else "")
                completed.append((cli.service_id, srv.service_id, conn,
                                  cli.duration_s, srv.duration_s,
                                  cli.failed or srv.failed,
                                  max(0.0, (srv.start_ns - cli.start_ns) / 1e9)))
            else:
                if other is not None:
                    self._store[key] = other  # same side dup; put back
                if len(self._store) >= self.cfg.max_items:
                    self.dropped += 1
                    continue
                he = _HalfEdge(int(sb.service_id[i]), float(dur_s[i]), bool(failed[i]),
                               is_client, is_messaging, int(peer_col[i]),
                               int(sb.start_unix_nano[i]), now + self.cfg.wait_s,
                               not is_client and bool(root[i]))
                self._store[key] = he
                self._ttl.append((he.expire_at, key))
        self.edges["completed"] += len(completed)
        # completed and expired edges ride ONE emit a push: adds commute
        self._emit(completed + self._expire(now))

    def _peer_col(self, sb: SpanBatch) -> np.ndarray:
        col = np.full(sb.capacity, INVALID_ID, np.int32)
        for key in self.cfg.peer_attributes:
            nxt = sb.attr_sval_column(key)
            col = np.where(col != INVALID_ID, col, nxt)
        return col

    # -- emission ----------------------------------------------------------

    def _emit(self, edges: list[tuple]) -> None:
        for at in range(0, len(edges), _EMIT_ROWS):
            self._emit_step(edges[at:at + _EMIT_ROWS])

    def _emit_step(self, edges: list[tuple]) -> None:
        it = self.registry.interner
        conn_ids = {c: it.intern(c) for c in ("", "messaging_system", "virtual_node")}
        n = len(edges)
        # pad the edge batch to a fixed shape: the matched-edge count
        # varies per push and unbucketed scatters would re-trace on every
        # new cardinality (padding rows ride slot -1 → dropped)
        cap = _EMIT_MIN_ROWS if n <= _EMIT_MIN_ROWS else _EMIT_ROWS
        messaging = self.messaging_hist is not None
        rows = np.array([(e[0], e[1], conn_ids[e[2]]) for e in edges], np.int32)
        # rows: slots, fail, cdur, sdur (+ mslots, mdur), `_edge_update_impl`
        packed = np.zeros((6 if messaging else 4, cap), np.float32)
        packed[1:4, :n] = np.array([(e[5], e[3], e[4]) for e in edges],
                                   np.float32).T
        if messaging:
            packed[5, :n] = [e[6] for e in edges]
            msg = [e[2] == "messaging_system" for e in edges]
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
            if self._fused:
                states = _edge_update(
                    tuple(f.state for f in self._families), packed)
                for fam, state in zip(self._families, states):
                    fam.state = state
            else:
                # family-level slot updates: the families own the device
                # half, which the paged layout (registry/pages.py) swaps
                # for arena scatters
                self.total.add_slots(slots)
                self.failed.add_slots(slots, packed[1])
                self.client_hist.observe_slots(slots, packed[2])
                self.server_hist.observe_slots(slots, packed[3])
                if messaging:
                    self.messaging_hist.observe_slots(mslots, packed[5])
            EMITS.inc(1, ("fused" if self._fused else "family",))

    def _expire(self, now: float) -> list[tuple]:
        """Expired half-edges become virtual-node edges (`servicegraphs.go:390-421`)."""
        with tracing.span("servicegraphs.expire"):
            edges = self._expire_halves(now)
        self.edges["virtual"] += len(edges)
        return edges

    def _expire_halves(self, now: float) -> list[tuple]:
        it = self.registry.interner
        expired_edges = []
        while self._ttl and self._ttl[0][0] <= now:
            _, key = self._ttl.popleft()
            he = self._store.get(key)
            if he is None:   # already matched
                continue
            if he.expire_at > now:
                # key was reused by a newer half-edge; re-queue, don't evict
                self._ttl.append((he.expire_at, key))
                continue
            del self._store[key]
            self.expired += 1
            if he.is_client:
                # client → peer-derived virtual server node (db, queue, ...)
                peer = it.lookup(he.peer_id) if he.peer_id != INVALID_ID else None
                if peer:
                    expired_edges.append((he.service_id, it.intern(peer),
                                          "virtual_node", he.duration_s, 0.0,
                                          he.failed, 0.0))
            elif he.is_root:
                # unmatched root server: the request came from outside
                # (a browser, curl) → synthetic "user" client. A server
                # with a parent lost its client span; it names no edge
                expired_edges.append((it.intern("user"), he.service_id,
                                      "virtual_node", 0.0, he.duration_s,
                                      he.failed, 0.0))
        return expired_edges
