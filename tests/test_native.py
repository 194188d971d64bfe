"""Native C++ layer: token hashing and OTLP wire scan vs python refs."""

from __future__ import annotations

import numpy as np
import pytest

from otlp_payload import make_otlp_payload
from tempo_tpu import native
from tempo_tpu.model.otlp import spans_from_otlp_proto
from tempo_tpu.ops import hashing

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native build unavailable")


def test_fnv_tokens_match_numpy():
    rng = np.random.default_rng(0)
    tids = rng.integers(0, 256, (100, 16), dtype=np.uint8)
    a = native.token_for("tenant-x", tids)
    b = hashing.token_for("tenant-x", tids)
    np.testing.assert_array_equal(a, b)


def _sample_proto() -> bytes:
    from tempo_tpu.model import proto_wire as pw

    def anyval_str(s):
        return pw.enc_field_str(1, s)

    def kv(k, v_msg):
        return pw.enc_field_str(1, k) + pw.enc_field_msg(2, v_msg)

    def span(tid, sid, name, start, end, kind=2, code=2, msg="boom",
             attrs=()):
        b = pw.enc_field_bytes(1, tid) + pw.enc_field_bytes(2, sid)
        b += pw.enc_field_str(5, name)
        b += pw.enc_field_varint(6, kind)
        b += pw.enc_field_varint(7, start) + pw.enc_field_varint(8, end)
        for k, v in attrs:
            b += pw.enc_field_msg(9, kv(k, anyval_str(v)))
        b += pw.enc_field_msg(15, pw.enc_field_str(2, msg)
                              + pw.enc_field_varint(3, code))
        return b

    # ResourceSpans.resource → Resource{attributes: [KeyValue]}
    resource = pw.enc_field_msg(
        1, pw.enc_field_msg(1, kv("service.name", anyval_str("svc-a"))))
    spans = b"".join(
        pw.enc_field_msg(2, span(bytes([i]) * 16, bytes([i]) * 8, f"op-{i}",
                                 10 ** 18 + i, 10 ** 18 + i + 1000,
                                 attrs=(("http.path", f"/p{i}"),)))
        for i in range(1, 6))
    scope_spans = pw.enc_field_msg(2, spans)
    return pw.enc_field_msg(1, resource + scope_spans)


def test_otlp_scan_matches_python_decoder():
    data = _sample_proto()
    nat = native.spans_from_otlp_proto_native(data)
    ref = list(spans_from_otlp_proto(data))
    assert nat is not None and len(nat) == len(ref) == 5
    for a, b in zip(nat, ref):
        for k in ("trace_id", "span_id", "name", "service", "kind",
                  "status_code", "status_message", "start_unix_nano",
                  "end_unix_nano", "attrs", "res_attrs"):
            assert a[k] == b[k], (k, a[k], b[k])


def test_otlp_scan_malformed_raises():
    with pytest.raises(ValueError):
        native.otlp_scan(b"\x0a\xff\xff\xff\xff\xff\xff\xff\xff\xff\xff")


def test_otlp_scan_grows_capacity():
    """>16 spans with cap_hint=1 (clamped to 16) forces the re-scan/grow
    branch for both the span and attr buffers."""
    from tempo_tpu.model import proto_wire as pw
    spans = b"".join(
        pw.enc_field_msg(2,
            pw.enc_field_bytes(1, bytes([i]) * 16)
            + pw.enc_field_bytes(2, bytes([i]) * 8)
            + pw.enc_field_str(5, f"s{i}")
            + pw.enc_field_msg(9, pw.enc_field_str(1, "k")
                               + pw.enc_field_msg(2, pw.enc_field_str(1, "v"))))
        for i in range(1, 41))
    data = pw.enc_field_msg(1, pw.enc_field_msg(2, spans))
    recs = native.otlp_scan(data, cap_hint=1)
    assert len(recs) == 40
    recs2, attrs = native.otlp_scan2(data, cap_hint=1)
    assert len(recs2) == 40 and len(attrs) == 40


def test_missing_trace_id_matches_python_contract():
    """A span without a trace id must decode to b'' so the distributor's
    invalid-id validation fires identically on both paths."""
    from tempo_tpu.model import proto_wire as pw
    span = pw.enc_field_bytes(2, b"\x01" * 8) + pw.enc_field_str(5, "x")
    data = pw.enc_field_msg(1, pw.enc_field_msg(2, pw.enc_field_msg(2, span)))
    nat = native.spans_from_otlp_proto_native(data)
    ref = list(spans_from_otlp_proto(data))
    assert nat[0]["trace_id"] == ref[0]["trace_id"] == b""


def test_resource_after_spans_field_order():
    """Resource serialized after ScopeSpans is legal wire order; both
    decoders must attribute the service correctly."""
    from tempo_tpu.model import proto_wire as pw

    def kv(k, v):
        return pw.enc_field_str(1, k) + pw.enc_field_msg(2, pw.enc_field_str(1, v))

    span = (pw.enc_field_bytes(1, b"\x05" * 16) + pw.enc_field_bytes(2, b"\x01" * 8)
            + pw.enc_field_str(5, "x"))
    scope_spans = pw.enc_field_msg(2, pw.enc_field_msg(2, span))
    resource = pw.enc_field_msg(1, pw.enc_field_msg(1, kv("service.name", "late")))
    data = pw.enc_field_msg(1, scope_spans + resource)  # spans FIRST
    nat = native.spans_from_otlp_proto_native(data)
    ref = list(spans_from_otlp_proto(data))
    assert nat[0]["service"] == ref[0]["service"] == "late"


def test_large_int_attr_exact():
    from tempo_tpu.model import proto_wire as pw
    big = (1 << 53) + 1
    attr = (pw.enc_field_str(1, "n")
            + pw.enc_field_msg(2, pw.enc_field_varint(3, big)))
    span = (pw.enc_field_bytes(1, b"\x06" * 16) + pw.enc_field_bytes(2, b"\x01" * 8)
            + pw.enc_field_msg(9, attr))
    data = pw.enc_field_msg(1, pw.enc_field_msg(2, pw.enc_field_msg(2, span)))
    nat = native.spans_from_otlp_proto_native(data)
    assert nat[0]["attrs"]["n"] == big  # exact, no double round-trip


def test_group_keys_matches_numpy_grouping():
    """Native hash grouping must partition identically to np.unique over
    void views (group ids may differ — first-occurrence vs sorted order —
    but the induced partition and first-row sets must match)."""
    from tempo_tpu import native

    rng = np.random.default_rng(5)
    keys = rng.integers(0, 4, size=(2000, 17)).astype(np.uint8)
    first, inverse = native.group_keys(keys)
    void = np.ascontiguousarray(keys).view([("v", "V17")]).ravel()
    _, f2, inv2 = np.unique(void, return_index=True, return_inverse=True)
    assert len(first) == len(f2)
    # bijection between label spaces
    fwd: dict = {}
    for a, b in zip(inverse.tolist(), inv2.tolist()):
        assert fwd.setdefault(a, b) == b
    # each group's first row really is its earliest occurrence
    for g, fi in enumerate(first.tolist()):
        rows = np.flatnonzero(inverse == g)
        assert rows[0] == fi


def test_otlp_scan_mt_matches_sequential(monkeypatch):
    """The threaded scan must produce byte-identical records in the same
    order as the sequential scan, and reject malformed payloads."""
    from tempo_tpu import native

    if not native.available():
        pytest.skip("native layer unavailable")

    payload = make_otlp_payload(8192, n_services=13)
    monkeypatch.setattr(native, "_SCAN_MT_BYTES", 1)      # force MT
    mt = native.otlp_scan(payload)
    monkeypatch.setattr(native, "_SCAN_MT_BYTES", 1 << 60)  # force seq
    seq = native.otlp_scan(payload)
    assert len(mt) == len(seq) == 8192
    assert (mt == seq).all()
    monkeypatch.setattr(native, "_SCAN_MT_BYTES", 1)
    with pytest.raises(ValueError):
        native.otlp_scan(payload[:-3])


def test_otlp_stage_mt_matches_serial(monkeypatch):
    """Parallel staging (skip-attrs shape) must emit the same records in
    the same order as the serial stage — intern ids may differ between
    interners, so string CONTENT is compared."""
    from tempo_tpu.model.interner import StringInterner

    if not native.available():
        pytest.skip("native layer unavailable")

    payload = make_otlp_payload(8192, n_services=13)
    it_mt, it_s = StringInterner(), StringInterner()
    monkeypatch.setattr(native, "_SCAN_MT_BYTES", 1)
    monkeypatch.setattr(native, "_SCAN_THREADS", 4)   # force MT even on 1 cpu
    a = native.otlp_stage(it_mt.native_handle(), payload,
                          skip_span_attrs=True)
    monkeypatch.setattr(native, "_SCAN_MT_BYTES", 1 << 60)
    b = native.otlp_stage(it_s.native_handle(), payload,
                          skip_span_attrs=True)
    it_mt.sync(); it_s.sync()
    sa, sb = a[0], b[0]
    assert len(sa) == len(sb) == 8192
    for col in ("trace_id", "span_id", "start_ns", "end_ns", "kind",
                "status_code", "res_idx", "span_len"):
        assert (sa[col] == sb[col]).all(), col
    na = [it_mt.lookup(int(i)) for i in sa["name_id"]]
    nb = [it_s.lookup(int(i)) for i in sb["name_id"]]
    assert na == nb
    va = [it_mt.lookup(int(i)) for i in sa["service_id"]]
    vb = [it_s.lookup(int(i)) for i in sb["service_id"]]
    assert va == vb
    # malformed rejection on the mt path too
    monkeypatch.setattr(native, "_SCAN_MT_BYTES", 1)
    with pytest.raises(ValueError):
        native.otlp_stage(it_mt.native_handle(), payload[:-5],
                          skip_span_attrs=True)


@pytest.mark.parametrize("use_native", [True, False],
                         ids=["native", "dict"])
def test_trace_index_upserts_looks_up_and_discards_what_it_still_names(
        use_native):
    """The live stores' id -> slot index: both forms answer alike through
    growth, tombstones and a discard that an upsert got ahead of."""
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 256, (50_000, 17), dtype=np.uint8)
    keys[:, 16] = 16
    keys[0] = 0                       # a key that is all zero bytes
    keys[1] = keys[2]                 # another key's padded bytes,
    keys[1, 16] = 7                   # another length
    idx = native.TraceIndex(use_native=use_native)
    slots = np.arange(len(keys), dtype=np.int64) + 10
    idx.upsert(keys, slots)
    assert len(idx) == len(keys)
    np.testing.assert_array_equal(idx.lookup(keys), slots)
    other = keys.copy()
    other[:, 0] ^= 1
    assert (idx.lookup(other[:1000]) == -1).all()
    # discard half; one of them was given a new slot first, so it stays
    idx.upsert(keys[:1], np.array([7], np.int64))
    idx.discard(keys[::2], slots[::2])
    want = slots.copy()
    want[::2] = -1
    want[0] = 7
    np.testing.assert_array_equal(idx.lookup(keys), want)
    assert len(idx) == len(keys) // 2 + 1
    # tombstones are reused and the index still answers after a rehash
    idx.upsert(other, slots + 100_000)
    np.testing.assert_array_equal(idx.lookup(other), slots + 100_000)
    np.testing.assert_array_equal(idx.lookup(keys), want)
    assert idx.lookup(keys[:0]).shape == (0,)
