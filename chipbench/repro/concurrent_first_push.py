#!/usr/bin/env python3
"""DEFECT 7 (the program's; seen by ISSUE 35's probe, repaired in PR 35).

The first pushes of several threads to a NEW tenant raised `TypeError:
int() argument must be ... not 'NoneType'` in `native.spanmetrics_resolve`
(`status_lut` is None). `SpanMetricsProcessor._staged_dims` built its three
lookup arrays lazily and published the first (`_dims_arr`, the "already
built" mark) before the other two: a second thread that arrived in between
took the mark for the whole and handed `None` to the native resolve. The
chip cells make tenants one at a time with canaries, so none showed it.

    JAX_PLATFORMS=cpu python chipbench/repro/concurrent_first_push.py

Four threads push at once to each of 40 tenants nobody pushed to before,
through `Generator.push_otlp`, as the issue's probe did. Exits 0 while the
defect shows (any push raised), 1 once it is repaired.
"""

import sys
import threading
import time

from common import verdict

from chipbench import spans  # noqa: E402  (common put the repo on the path)
from tempo_tpu.generator.generator import Generator  # noqa: E402
from tempo_tpu.generator.instance import GeneratorConfig  # noqa: E402

SCHEMA = {"services": 8, "names": 6, "vus": 4, "end_jitter_ns": 10**9}
shape = spans.PushShape(4, 25, 5)
gen = Generator(GeneratorConfig(processors=("span-metrics",)))
raised: list[str] = []


def first_push(tenant: str, idx: int, barrier: threading.Barrier) -> None:
    body = spans.encode_push(shape, spans.draw_push(
        35, 0, idx, shape, SCHEMA, time.time_ns()))
    barrier.wait()
    try:
        gen.push_otlp(tenant, body)
    except Exception as e:
        raised.append(f"{tenant}: {type(e).__name__}: {e}")


old = sys.getswitchinterval()
sys.setswitchinterval(1e-6)
try:
    for k in range(40):
        barrier = threading.Barrier(4)
        threads = [threading.Thread(target=first_push,
                                    args=(f"new-{k:02d}", i, barrier))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
finally:
    sys.setswitchinterval(old)
verdict(not raised, f"{len(raised)} of 160 first pushes raised: {raised[:2]}")
