"""Seeded spans: OTLP push payloads and block columns, numpy only.

Copied in idea from `chip_smoke.py` (`PushShape`, `Workload`): one fixed
byte layout per push shape, patched with numpy column writes, so that a
client spends microseconds a push and not the server's time. Imported by
the load generator's child process, so nothing here may import JAX or
the program under test.
"""

from __future__ import annotations

import numpy as np

KINDS = (0, 1)                 # series kinds: UNSPECIFIED, INTERNAL
KIND_STRS = ("SPAN_KIND_UNSPECIFIED", "SPAN_KIND_INTERNAL", "SPAN_KIND_SERVER",
             "SPAN_KIND_CLIENT")
STATUS_STRS = ("STATUS_CODE_UNSET", "STATUS_CODE_OK", "STATUS_CODE_ERROR")


# -- protobuf wire format, the little of it a template needs ---------------

def _varint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append(v & 0x7F | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _tag(field: int, wire: int) -> bytes:
    return _varint(field << 3 | wire)


def _msg(field: int, body: bytes) -> bytes:
    return _tag(field, 2) + _varint(len(body)) + body


def _str(field: int, s: str) -> bytes:
    return _msg(field, s.encode())


class PushShape:
    """An ExportTraceServiceRequest of `groups` ResourceSpans x `per`
    spans whose every variable field sits at a fixed offset. Times ride
    fixed64, as the OTLP schema has them."""

    def __init__(self, groups: int, per: int, trace_len: int) -> None:
        self.groups, self.per, self.trace_len = groups, per, trace_len
        self.n = groups * per
        span, self.off = b"", {}

        def put(key, head: bytes, width: int, tail: bytes = b"") -> None:
            nonlocal span
            span += head
            self.off[key] = (len(span), len(span) + width)
            span += bytes(width) + tail

        put("trace_id", b"\x0a\x10", 16)
        put("span_id", b"\x12\x08", 8)
        put("parent", b"\x22\x08", 8)
        put("name", b"\x2a\x07op-", 4)
        put("kind", b"\x30", 1)
        put("start", b"\x39", 8)
        put("end", b"\x41", 8)
        kv = _str(1, "k6.vu") + _msg(2, _str(1, "vu-00"))
        put("vu", _tag(9, 2) + _varint(len(kv)) + kv[:-2], 2)
        put("status", b"\x7a\x02\x18", 1)
        self.span_len = len(span)
        rec = _tag(2, 2) + _varint(self.span_len) + span
        self.stride = len(rec)
        self.span_at = self.stride - self.span_len
        scope_spans = rec * per
        resource = _msg(1, _msg(1, _str(1, "service.name")
                                + _msg(2, _str(1, "svc-0000"))))
        group = _msg(1, resource + _msg(2, scope_spans))
        self.head = len(group) - len(scope_spans)
        self.svc_at = group.index(b"svc-0000") + 4
        self.template = np.frombuffer(group * groups, np.uint8).reshape(
            groups, len(group)).copy()

    def build(self, cols: dict) -> bytes:
        """`cols[key]` is a [groups, per, width] uint8 array per variable
        field, plus `service` [groups, 4]."""
        buf = self.template.copy()
        buf[:, self.svc_at:self.svc_at + 4] = cols["service"]
        spans = buf[:, self.head:].reshape(self.groups, self.per, self.stride)
        for key, (lo, hi) in self.off.items():
            spans[:, :, self.span_at + lo:self.span_at + hi] = cols[key]
        return buf.tobytes()


def digits(v: np.ndarray, width: int) -> np.ndarray:
    """Zero-padded ASCII decimal digits of `v`, as a trailing uint8 axis."""
    pows = 10 ** np.arange(width - 1, -1, -1)
    return (v[..., None] // pows % 10 + 48).astype(np.uint8)


def le_bytes(v: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(v.astype("<i8")).view(np.uint8).reshape(
        v.shape + (8,))


def draw_durations(rng, shape) -> np.ndarray:
    """Log-normal around 50 ms (sigma 1), clipped to [2 us, 10 s], int64 ns
    (`chip_smoke.Workload`)."""
    return np.clip(rng.lognormal(np.log(50e6), 1.0, shape),
                   2e3, 10e9).astype(np.int64)


def draw_push(seed: int, tenant_idx: int, push_idx: int, shape: PushShape,
              schema: dict, now_ns: int) -> dict:
    """The spans of one push, as flat columns; a pure function of its
    arguments, so the parent draws again what the child sent. Span g takes
    series combo g mod (names x kinds x status) inside its resource
    group's service, so every series appears; one client->server pair per
    group feeds the service graph (`chip_smoke.Workload.make`)."""
    G, P, T = shape.groups, shape.per, shape.trace_len
    S, names, vus = schema["services"], schema["names"], schema["vus"]
    n_combo = names * len(KINDS) * 3
    rng = np.random.default_rng([seed, tenant_idx, push_idx])
    svc = (push_idx * G + np.arange(G)) % S
    visit = push_idx * G // S
    combo = (visit * P + np.arange(P)[None, :] + svc[:, None] * 7) % n_combo
    name = combo // (len(KINDS) * 3)
    kind = np.asarray(KINDS)[combo // 3 % len(KINDS)]
    status = combo % 3
    dur_ns = draw_durations(rng, (G, P))
    vu = rng.integers(0, vus, (G, P))
    span_id = rng.integers(1, 1 << 62, (G, P), dtype=np.int64)
    tid = rng.integers(0, 256, (G, P // T, 16), dtype=np.uint8)
    trace_id = np.repeat(tid, T, axis=1)
    parent = np.zeros((G, P), np.int64)
    first = np.arange(P) % T == 0
    parent[:, ~first] = np.repeat(span_id[:, first], T, axis=1)[:, ~first]
    # the last span of group j is a CLIENT call whose SERVER side sits in
    # group j+1 (same trace, parent = the client span); the last group
    # calls itself. Both sides ride this push, so every edge completes here
    g = np.arange(G)
    srv_g = np.minimum(g + 1, G - 1)
    srv_p = np.where(g < G - 1, P - 2, P - 3)
    for gi, pi, k in ((g, P - 1, 3), (srv_g, srv_p, 2)):
        name[gi, pi] = names                   # op-<names>: the call
        status[gi, pi] = 0
        kind[gi, pi] = k
    trace_id[srv_g, srv_p] = trace_id[g, P - 1]
    parent[srv_g, srv_p] = span_id[g, P - 1]
    end = now_ns - rng.integers(0, schema["end_jitter_ns"], (G, P))
    return {"svc_g": svc, "svc": np.repeat(svc, P), "name": name.ravel(),
            "kind": kind.ravel(), "status": status.ravel(),
            "dur_ns": dur_ns.ravel(), "start_ns": (end - dur_ns).ravel(),
            "end_ns": end.ravel(), "vu": vu.ravel(),
            "trace_id": trace_id.reshape(-1, 16), "span_id": span_id.ravel(),
            "parent": parent.ravel(), "pairs": G}


def encode_push(shape: PushShape, c: dict) -> bytes:
    G, P = shape.groups, shape.per
    sq = lambda a: a.reshape(G, P)             # noqa: E731
    return shape.build({
        "service": digits(c["svc_g"], 4),
        "trace_id": c["trace_id"].reshape(G, P, 16),
        "span_id": le_bytes(sq(c["span_id"])),
        "parent": le_bytes(sq(c["parent"])),
        "name": digits(sq(c["name"]), 4),
        "kind": sq(c["kind"])[..., None].astype(np.uint8),
        "start": le_bytes(sq(c["start_ns"])), "end": le_bytes(sq(c["end_ns"])),
        "vu": digits(sq(c["vu"]), 2),
        "status": sq(c["status"])[..., None].astype(np.uint8),
    })


def draw_block(seed: int, block_idx: int, spec: dict, t0_ns: int) -> dict:
    """Columns of one backend block: `spans` spans in traces of
    `trace_len` (a root and its children), start times uniform over the
    block's `block_seconds` from `t0_ns`, sorted by trace id as a block
    is. Service, name, k6.vu uniform; `error_share` of the spans errored."""
    n, T = spec["spans_per_block"], spec["trace_len"]
    n_tr = n // T
    rng = np.random.default_rng([seed, 7000 + block_idx])
    hi = np.sort(rng.integers(0, 1 << 63, n_tr, dtype=np.int64))
    lo = rng.integers(0, 1 << 63, n_tr, dtype=np.int64)
    tid = np.empty((n_tr, 2), ">u8")
    tid[:, 0], tid[:, 1] = hi, lo
    trace_id = np.repeat(tid.view(np.uint8).reshape(n_tr, 16), T, axis=0)
    span_id = rng.integers(1, 1 << 62, n, dtype=np.int64)
    root = np.repeat(span_id[::T], T)
    pos = np.tile(np.arange(T), n_tr)
    span_ns = int(spec["block_seconds"]) * 10**9
    tr_start = np.repeat(rng.integers(0, span_ns - 10**9, n_tr), T)
    start = t0_ns + tr_start + np.where(pos == 0, 0, rng.integers(0, 10**9, n))
    u = rng.random(n)
    status = np.where(u < spec["error_share"], 2,
                      np.where(u < spec["error_share"] + 0.1, 1, 0))
    return {"trace_id": trace_id, "span_id": span_id,
            "parent": np.where(pos == 0, 0, root), "pos": pos,
            "svc": np.repeat(rng.integers(0, spec["services"], n_tr), T),
            "name": rng.integers(0, spec["names"], n),
            "kind": np.where(pos == 0, 2, 1).astype(np.int8),
            "status": status.astype(np.int8),
            "vu": rng.integers(0, spec["vus"], n),
            "start_ns": start, "dur_ns": draw_durations(rng, n)}
