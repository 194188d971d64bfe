#!/usr/bin/env python3
"""DEFECT 1 (the program's; found by `blockread.metrics`, PR 23 and PR 24).

`block/fetch.py` hands the engines a block's start times as float64
(`start.astype(float)`), and `block/device_scan.py::_ensure_times` goes
through float64 too. At today's epoch (1.79e18 ns) a float64 has a 256 ns
ulp, so a span that started within 128 ns of a step edge is counted in the
neighbouring step, by the device plane and by the host engine alike.
Upstream bins integer nanoseconds. In the 8M-span data set about two
spans sit that close to a whole second, and 1 in 15 of the 7200 s / 30 s
queries meets one (4 of 266 answers in my chip run, PR 24).

    JAX_PLATFORMS=cpu python chipbench/repro/step_edge_rounding.py

Three spans around a 30 s edge E: E-100 ns, E, E+100 ns. rate() over
[E-30 s, E+30 s) at step 30 must count [1, 2]; the program answers [0, 3].
Exits 0 while the defect shows, 1 once it is repaired.
"""

from common import HOUR_AGO_S, get, serve_block, verdict

EDGE = HOUR_AGO_S * 10**9
spans = [{"trace_id": bytes([i + 1]) * 16, "span_id": bytes([i + 1]) * 8,
          "start_unix_nano": EDGE + off, "end_unix_nano": EDGE + off + 1000}
         for i, off in enumerate((-100, 0, 100))]
app, port = serve_block(spans)
got = get(port, "/api/metrics/query_range", q="{ } | rate()",
          start=HOUR_AGO_S - 30, end=HOUR_AGO_S + 30, step=30)
counts = [round(float(p["value"]) * 30) for p in got["series"][0]["samples"]]
verdict(counts == [1, 2], f"rate() per 30 s step around the edge: {counts}, "
                          "integer binning gives [1, 2]")
