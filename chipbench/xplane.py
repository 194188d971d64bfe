"""From a profiler trace (`.xplane.pb`) to numbers.

Read with `jax.profiler.ProfileData`, which needs nothing but JAX. A TPU
trace has one plane per chip, `/device:TPU:<n>`, with the lines `XLA
Modules` (one event per executed program, named `jit_<fn>(<id>)`) and
`XLA Ops` (one event per operation inside it), and one `/host:CPU` plane
with a line per host thread, all on one clock in nanoseconds.

- busy: the union of the `XLA Ops` intervals of a chip, averaged over the
  chips; idle is the traced window less busy.
- module time: the summed durations of `XLA Modules` events whose name
  starts with a prefix (the jit name is the only stable handle today).
- idle gaps: the longest intervals in which no operation ran on chip 0,
  each named by the host event that covers most of it.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_trace(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals, in the unit
    of the input."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The complement of the union inside [lo, hi]."""
    out, at = [], lo
    for a, b in sorted(intervals):
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return out


def reduce_trace(path: str, top: int = 10) -> dict:
    """{"busy_s", "extent_s", "modules": {name: [calls, seconds]},
    "device_ops": [[name, seconds]], "idle_gaps": [[host event, seconds]],
    "chips"}; busy averaged over the chips with a device plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    busy, modules, ops, chip0, host = [], {}, {}, None, []
    lo, hi = float("inf"), 0.0
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            spans = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        spans.append((e.start_ns, e.start_ns + e.duration_ns))
                        ops[e.name] = ops.get(e.name, 0.0) + e.duration_ns
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        name = e.name.split("(")[0]
                        m = modules.setdefault(name, [0, 0.0])
                        m[0] += 1
                        m[1] += e.duration_ns / 1e9
            busy.append(union_seconds(spans) / 1e9)
            if spans:
                lo = min(lo, min(s[0] for s in spans))
                hi = max(hi, max(s[1] for s in spans))
            if chip0 is None:
                chip0 = spans
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events)
    out = {"chips": len(busy),
           "busy_s": sum(busy) / len(busy) if busy else 0.0,
           "extent_s": max(hi - lo, 0.0) / 1e9 if busy and hi else 0.0,
           "modules": modules,
           "device_ops": [[n[:160], s / 1e9] for n, s in sorted(
               ops.items(), key=lambda kv: -kv[1])[:top]],
           "idle_gaps": []}
    if chip0:
        longest = sorted(gaps(chip0, lo, hi), key=lambda g: g[0] - g[1])[:top]
        for a, b in longest:
            cover: dict[str, float] = {}
            for ha, hb, name in host:
                ov = min(b, hb) - max(a, ha)
                if ov > 0:
                    cover[name] = cover.get(name, 0.0) + ov
            name = max(cover, key=cover.get) if cover else "no host event"
            out["idle_gaps"].append([name[:160], (b - a) / 1e9])
    return out


def module_seconds(reduced: dict, prefix: str) -> tuple[int, float]:
    calls = secs = 0
    for name, (n, s) in reduced["modules"].items():
        if name.startswith(prefix):
            calls, secs = calls + n, secs + s
    return calls, secs
