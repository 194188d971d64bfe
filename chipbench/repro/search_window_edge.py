#!/usr/bin/env python3
"""DEFECT 2 (the program's; found by `blockread.search`, PR 24).

`/api/search` with `start`/`end` drops a trace whose matching span began
before `start` and ended inside the window. The engine's own second pass
(`traceql/engine.py::_simple_filter_spansets`: max(end) >= start and
min(start) < end) keeps such a span set, and upstream Tempo keeps any
trace whose time range overlaps the window; but the first pass filters on
the span's START time, so the span never reaches the second pass. The
host engine (`device_plane=False`) has the same first pass and agrees
with the served answer. 3 of 305 and 2 of 213 answers in my chip runs
(PR 24): about one search in 40 over a 3600 s window at tens of matches.

    JAX_PLATFORMS=cpu python chipbench/repro/search_window_edge.py

One 3 s span that starts 2 s before the window: the search must find its
trace. Exits 0 while the defect shows, 1 once it is repaired.
"""

from common import HOUR_AGO_S, get, serve_block, verdict

START = HOUR_AGO_S * 10**9
spans = [{"trace_id": b"\x07" * 16, "span_id": b"\x07" * 8,
          "start_unix_nano": START - 2 * 10**9,
          "end_unix_nano": START + 10**9}]
app, port = serve_block(spans)
got = get(port, "/api/search", q="{ duration > 2s }", start=HOUR_AGO_S,
          end=HOUR_AGO_S + 600, limit=20)
ids = [t["traceID"] for t in got["traces"]]
verdict(len(ids) == 1, f"a 3 s span from 2 s before the window: search "
                       f"returned {ids}, one trace overlaps the window")
