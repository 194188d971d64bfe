"""A synthetic OTLP ExportTraceServiceRequest, for the tests that feed
the decode and staging paths bytes rather than span dicts."""

from __future__ import annotations

import time

import numpy as np


def make_otlp_payload(n_spans: int, n_services: int = 16,
                       n_names: int = 64, seed: int = 0) -> bytes:
    """Synthesize a realistic OTLP ExportTraceServiceRequest."""
    from tempo_tpu.model.proto_wire import (
        enc_field_bytes, enc_field_msg, enc_field_str, enc_field_varint)

    rng = np.random.default_rng(seed)
    t0 = int(time.time() * 1e9)

    def attr(k: str, v: str | int) -> bytes:
        if isinstance(v, int):
            av = enc_field_varint(3, v)
        else:
            av = enc_field_str(1, v)
        return enc_field_str(1, k) + enc_field_msg(2, av)

    out = []
    per_rs = max(n_spans // n_services, 1)
    left = n_spans
    for svc in range(n_services):
        take = min(per_rs, left) if svc < n_services - 1 else left
        left -= take
        if take <= 0:
            break
        spans = []
        for _ in range(take):
            dur = int(rng.lognormal(16, 1.0))
            start = t0 - int(rng.integers(0, 10**9))
            b = (enc_field_bytes(1, rng.bytes(16)) +
                 enc_field_bytes(2, rng.bytes(8)) +
                 enc_field_str(5, f"op-{int(rng.integers(0, n_names))}") +
                 enc_field_varint(6, int(rng.integers(1, 6))) +
                 enc_field_varint(7, start) +
                 enc_field_varint(8, start + dur) +
                 enc_field_msg(9, attr("http.status_code",
                                       int(rng.integers(200, 500)))) +
                 enc_field_msg(9, attr("http.method", "GET")) +
                 enc_field_msg(15, enc_field_varint(3, int(rng.integers(0, 3)))))
            spans.append(enc_field_msg(2, b))
        rs = (enc_field_msg(1, enc_field_msg(
                  1, attr("service.name", f"svc-{svc}"))) +
              enc_field_msg(2, b"".join(spans)))
        out.append(enc_field_msg(1, rs))
    return b"".join(out)


