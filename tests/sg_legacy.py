"""The service-graph processor's half-edge store as a dict of objects
and a deque, one Python step a half: the reference that
`tests/test_servicegraphs_store.py` holds the array store and its native
pairing call to, state for state. Its emit is the tuple-list form the
processor had with it; the device step and the families are the
processor module's own."""

from __future__ import annotations

import collections
import dataclasses
import threading

import numpy as np

from tempo_tpu.generator.processors.servicegraphs import (
    _EMIT_MIN_ROWS,
    _EMIT_ROWS,
    EMITS,
    ServiceGraphsConfig,
    _edge_update,
)
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
from tempo_tpu.registry.registry import ManagedRegistry
from tempo_tpu.utils import tracing, turn


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


class LegacyServiceGraphs:
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
