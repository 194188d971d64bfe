"""HotROD requests exported by an OpenTelemetry SDK in every service: the
load generator child of the `otlp_push_hotrod` mix. Never imports JAX.

    python chipbench/loadgen_hotrod.py <spec.pkl> <out.pkl>

The call graph is the configuration's `schema` (Jaeger's HotROD: a root
`/dispatch` on frontend, its calls to customer, driver and ten to route,
and the databases the services call, which are not instrumented). Every
(tenant, service) keeps one export queue, as a BatchSpanProcessor does: a
request's spans join the queue of the service that made them, and a queue
is sent as one push of ONE ResourceSpans when it holds `batch_spans`
spans or its oldest span is `max_age_s` old. So the client span of a call
and the server span it meets reach the server in different pushes.
A seeded share of the batches is held `hold_s` before it is sent (a
retried export), which puts every span in it outside the generator's
slack.

The closed loop: `clients` threads each send the next batch when their
last push was answered; a held batch whose time has come goes first, then
a full or aged queue, and where there is none the next chunk of requests
is drawn (tenants in turn) until a batch is full. With a window the
child sends from its start, says `ready` after `warm_s` of traffic, keeps
sending, and opens the window on the parent's `go`.

Every span is a pure function of (seed, tenant, chunk, the chunk's wall
clock): the child reports each chunk's clock and each push's range of its
queue's stream, and the judge draws the same spans again.
"""

from __future__ import annotations

import collections
import heapq
import os
import pickle
import resource
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import spans  # noqa: E402
from chipbench.lib import http_call  # noqa: E402
from chipbench.spans import _msg, _str, _tag, _varint  # noqa: E402

KINDS = {"internal": 1, "server": 2, "client": 3}
STATUS_ERROR = 2
COLUMNS = ("tmpl", "trace_id", "span_id", "parent", "start_ns", "end_ns",
           "status")


class Hotrod:
    """The request of a schema, expanded to one slot a span, and the OTLP
    byte layout of every span template."""

    def __init__(self, schema: dict, extra: tuple = ()) -> None:
        self.services = list(schema["services"])
        self.templates = list(schema["templates"]) + list(extra)
        tmpl, parent, first = [], [], {}
        for g, group in enumerate(schema["request"]):
            r = int(group.get("repeat", 1))
            first[g] = (len(tmpl), r)
            for k in range(r):
                tmpl.append(group["template"])
                p = group.get("parent")
                if p is None:
                    parent.append(-1)
                    continue
                at, pr = first[p]
                if pr not in (1, r):
                    raise ValueError(f"group {g}: {r} spans under {pr}")
                parent.append(at + (k if pr == r else 0))
        self.slot_tmpl = np.asarray(tmpl, np.int64)
        self.slot_parent = np.asarray(parent, np.int64)
        if self.slot_parent[0] != -1 or (self.slot_parent[1:] < 0).any() \
                or (self.slot_parent[1:] >= np.arange(1, len(tmpl))).any():
            raise ValueError("the request needs one root, first, and every "
                             "parent before its children")
        self.tmpl_svc = np.asarray([self.services.index(t["service"])
                                    for t in self.templates], np.int64)
        self.slot_err = np.asarray([self.templates[t].get("error_share", 0.0)
                                    for t in tmpl])
        self.svc_slots = [np.flatnonzero(self.tmpl_svc[self.slot_tmpl] == s)
                          for s in range(len(self.services))]
        self.layout = {(k, p): _span_layout(t, p)
                       for k, t in enumerate(self.templates)
                       for p in (False, True)}

    @property
    def n_slots(self) -> int:
        return len(self.slot_tmpl)


def _span_layout(t: dict, with_parent: bool) -> tuple:
    """One span record (ScopeSpans field 2, length included) of template
    `t` and the offsets of its variable fields."""
    span, off = b"", {}

    def put(key, head: bytes, width: int) -> None:
        nonlocal span
        span += head
        off[key] = (len(span), len(span) + width)
        span += bytes(width)

    put("trace_id", b"\x0a\x10", 16)
    put("span_id", b"\x12\x08", 8)
    if with_parent:
        put("parent", b"\x22\x08", 8)
    span += _str(5, t["name"]) + _tag(6, 0) + _varint(KINDS[t["kind"]])
    put("start_ns", b"\x39", 8)
    put("end_ns", b"\x41", 8)
    for key, value in t.get("attributes", {}).items():
        span += _msg(9, _str(1, key) + _msg(2, _str(1, value)))
    put("status", b"\x7a\x02\x18", 1)
    head = _tag(2, 2) + _varint(len(span))
    return (np.frombuffer(head + span, np.uint8),
            {k: (lo + len(head), hi + len(head)) for k, (lo, hi) in off.items()})


def encode(h: Hotrod, groups: list) -> bytes:
    """An ExportTraceServiceRequest of one ResourceSpans a (service,
    columns) of `groups`; inside one, the spans grouped by template (the
    order of spans in a push carries no meaning)."""
    out = b""
    for svc, c in groups:
        key = c["tmpl"] * 2 + (c["parent"] != 0)
        scope = []
        for k in np.unique(key).tolist():
            rows = np.flatnonzero(key == k)
            tpl, off = h.layout[(k // 2, bool(k % 2))]
            buf = np.tile(tpl, (len(rows), 1))
            for f, (lo, hi) in off.items():
                v = c[f][rows]
                buf[:, lo:hi] = v if f == "trace_id" else (
                    v[:, None].astype(np.uint8) if f == "status"
                    else spans.le_bytes(v))
            scope.append(buf.tobytes())
        res = _msg(1, _msg(1, _str(1, "service.name")
                           + _msg(2, _str(1, h.services[svc]))))
        out += _msg(1, res + _msg(2, b"".join(scope)))
    return out


# -- drawing ----------------------------------------------------------------

def draw_chunk(h: Hotrod, seed: int, ti: int, ci: int, n_req: int,
               now_ns: int) -> dict:
    """Requests `ci * n_req` .. `+ n_req` of tenant `ti`, each ending at
    `now_ns`: columns [n_req, slots]. The root's duration is drawn as
    `spans.draw_durations`; a child draws its own, cut to its parent's,
    and starts at a uniform point that keeps it inside the parent."""
    rng = np.random.default_rng([seed, ti, ci])
    S = h.n_slots
    tid = rng.integers(0, 256, (n_req, 16), dtype=np.uint8)
    sid = rng.integers(1, 1 << 62, (n_req, S), dtype=np.int64)
    dur = spans.draw_durations(rng, (n_req, S))
    u = rng.random((n_req, S))
    start = np.empty((n_req, S), np.int64)
    end = np.empty((n_req, S), np.int64)
    end[:, 0] = now_ns
    start[:, 0] = now_ns - dur[:, 0]
    for k in range(1, S):
        p = h.slot_parent[k]
        room = end[:, p] - start[:, p]
        d = np.minimum(dur[:, k], room)
        start[:, k] = start[:, p] + (u[:, k] * (room - d)).astype(np.int64)
        end[:, k] = start[:, k] + d
    parent = np.where(h.slot_parent >= 0,
                      sid[:, np.maximum(h.slot_parent, 0)], 0)
    status = np.where(rng.random((n_req, S)) < h.slot_err, STATUS_ERROR, 0)
    return {"tmpl": np.broadcast_to(h.slot_tmpl, (n_req, S)),
            "trace_id": np.repeat(tid[:, None, :], S, axis=1),
            "span_id": sid, "parent": parent, "start_ns": start,
            "end_ns": end, "status": status}


def service_rows(h: Hotrod, chunk: dict, svc: int) -> dict:
    """The spans of one service in a chunk, request by request: the order
    its export queue receives them."""
    at = h.svc_slots[svc]
    return {k: (v[:, at].reshape(-1, 16) if k == "trace_id"
                else np.ascontiguousarray(v[:, at]).ravel())
            for k, v in chunk.items()}


def pair_columns(h: Hotrod, seed: int, key: int, pairs: int, client: int,
                 server: int, now_ns: int) -> list:
    """`pairs` calls of template `client` to template `server` in traces of
    their own, both halves in one push: [(service, columns)] of the two
    sides. Set-up's canaries; a pure function of its arguments."""
    rng = np.random.default_rng([seed, 29, key])
    tid = rng.integers(0, 256, (pairs, 16), dtype=np.uint8)
    ids = rng.integers(1, 1 << 62, (2, pairs), dtype=np.int64)
    dur = spans.draw_durations(rng, (2, pairs))
    cdur = np.maximum(dur[0], dur[1])
    out = []
    for tmpl, sid, parent, d in ((client, ids[0], np.zeros(pairs, np.int64),
                                  cdur),
                                 (server, ids[1], ids[0], dur[1])):
        out.append((int(h.tmpl_svc[tmpl]), {
            "tmpl": np.full(pairs, tmpl), "trace_id": tid, "span_id": sid,
            "parent": parent, "start_ns": now_ns - cdur,
            "end_ns": now_ns - cdur + d, "status": np.zeros(pairs, np.int64)}))
    return out


def single_columns(h: Hotrod, seed: int, key: int, tmpl: int,
                   now_ns: int) -> list:
    """One span of template `tmpl` in a trace of its own, ending at
    `now_ns`: the judge's flush push."""
    rng = np.random.default_rng([seed, 31, key])
    return [(int(h.tmpl_svc[tmpl]), {
        "tmpl": np.array([tmpl]),
        "trace_id": rng.integers(0, 256, (1, 16), dtype=np.uint8),
        "span_id": rng.integers(1, 1 << 62, 1, dtype=np.int64),
        "parent": np.zeros(1, np.int64), "start_ns": np.array([now_ns - 10**6]),
        "end_ns": np.array([now_ns]), "status": np.zeros(1, np.int64)})]


def take_rows(cols: dict, lo: int, hi: int) -> dict:
    return {k: v[lo:hi] for k, v in cols.items()}


def cat(parts: list) -> dict:
    return {k: np.concatenate([p[k] for p in parts]) for k in COLUMNS}


def post(port: int, tenant: str, body: bytes, timeout: float) -> dict:
    t0 = time.monotonic()
    sent_ns = time.time_ns()
    status, reply = http_call(port, "POST", "/v1/traces", tenant, body,
                              timeout)
    return {"tenant": tenant, "t0": t0, "t1": time.monotonic(),
            "sent_ns": sent_ns, "status": status,
            "body": reply if reply not in (b"", b"{}") else b""}


# -- the export queues --------------------------------------------------------

class Exporter:
    """Every (tenant, service)'s BatchSpanProcessor queue, and the held
    (retried) batches. `take` under one lock hands out the next batch."""

    def __init__(self, spec: dict) -> None:
        self.spec = spec
        self.h = Hotrod(spec["schema"])
        self.tenants = list(spec["tenants"])
        self.lock = threading.Lock()
        self.queues = {(ti, s): {"parts": [], "n": 0, "pos": 0, "since": 0.0,
                                 "batches": 0}
                       for ti in range(len(self.tenants))
                       for s in range(len(self.h.services))}
        self.chunks = {ti: [] for ti in range(len(self.tenants))}
        self.ready: collections.deque = collections.deque()
        self.held: list = []                  # heap of (release, k, batch)
        self.k = 0

    def take(self):
        spec, now = self.spec, time.monotonic()
        with self.lock:
            if self.held and self.held[0][0] <= now:
                return heapq.heappop(self.held)[2]
            if not self.ready:
                for q, state in self.queues.items():
                    if state["n"] and now - state["since"] >= spec["max_age_s"]:
                        self._cut(q, state["n"])
            while not self.ready:
                if self.held and self.held[0][0] <= now:
                    return heapq.heappop(self.held)[2]
                self._draw()
            return self.ready.popleft()

    def _draw(self) -> None:
        """The next chunk of requests, for the tenant with the fewest."""
        spec = self.spec
        ti = min(self.chunks, key=lambda t: len(self.chunks[t]))
        now_ns = time.time_ns()
        chunk = draw_chunk(self.h, spec["seed"], ti, len(self.chunks[ti]),
                           spec["chunk_requests"], now_ns)
        self.chunks[ti].append(now_ns)
        now = time.monotonic()
        for s in range(len(self.h.services)):
            state = self.queues[(ti, s)]
            rows = service_rows(self.h, chunk, s)
            if not state["n"]:
                state["since"] = now
            state["parts"].append(rows)
            state["n"] += len(rows["tmpl"])
            while state["n"] >= spec["batch_spans"]:
                self._cut((ti, s), spec["batch_spans"])

    def _cut(self, q: tuple, n: int) -> None:
        spec, state = self.spec, self.queues[q]
        rows = cat(state["parts"])
        batch = {"ti": q[0], "svc": q[1], "lo": state["pos"],
                 "hi": state["pos"] + n, "n": n,
                 "cols": take_rows(rows, 0, n)}
        rest = take_rows(rows, n, len(rows["tmpl"]))
        state["parts"] = [rest] if len(rest["tmpl"]) else []
        state["n"] -= n
        state["pos"] += n
        state["since"] = time.monotonic()
        rng = np.random.default_rng([spec["seed"], 23, q[0], q[1],
                                     state["batches"]])
        state["batches"] += 1
        late, hold = rng.random() < spec["late_share"], rng.uniform(
            *spec["hold_s"])
        batch["hold_s"] = hold if late else 0.0
        if late:
            self.k += 1
            heapq.heappush(self.held, (time.monotonic() + hold, self.k, batch))
        else:
            self.ready.append(batch)

    def send(self, batch: dict) -> dict:
        spec = self.spec
        body = encode(self.h, [(batch["svc"], batch["cols"])])
        rec = post(spec["port"], self.tenants[batch["ti"]], body,
                   spec["timeout"])
        rec.update({k: batch[k] for k in ("ti", "svc", "lo", "hi", "n",
                                          "hold_s")}, kind="batch")
        return rec


def main() -> int:
    with open(sys.argv[1], "rb") as f:
        spec = pickle.load(f)
    src = Exporter(spec)
    done: list[dict] = []
    errors: list[str] = []
    deadline = [float("inf")]

    def client() -> None:
        while time.monotonic() < deadline[0]:
            batch = src.take()
            try:
                done.append(src.send(batch))
            except Exception as e:     # a dead socket is a failed request
                errors.append(f"{type(e).__name__}: {e}")
                done.append({"kind": "batch", "status": -1, "t0": 0.0,
                             "t1": 0.0, "body": b"", "error": str(e),
                             **{k: batch[k] for k in ("ti", "svc", "lo",
                                                      "hi", "n", "hold_s")}})

    threads = [threading.Thread(target=client) for _ in range(spec["clients"])]
    t_start = time.monotonic()
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    for t in threads:
        t.start()
    time.sleep(max(t_start + spec["warm_s"] - time.monotonic(), 0.0))
    print("ready", flush=True)
    sys.stdin.readline()                           # the parent's "go"
    t_go = time.monotonic()
    deadline[0] = t_go + spec["seconds"]
    for t in threads:
        t.join()
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    with open(sys.argv[2], "wb") as f:
        pickle.dump({"t_go": t_go, "t_start": t_start,
                     "t_end": time.monotonic(), "done": done,
                     "errors": errors, "chunks": src.chunks,
                     "cpu_s": (ru1.ru_utime - ru0.ru_utime
                               + ru1.ru_stime - ru0.ru_stime)}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
