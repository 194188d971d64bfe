"""What a kernel's work needs, from its shapes: the numerator of a
roofline share. Bytes, because both kernels are scatter/reduce passes with
no arithmetic to speak of: the bound is HBM bandwidth (`peaks.json`).
Padding rows and whatever the compiled program moves beyond this (the
copies of whole state planes the v5e trace shows) are NOT counted: they
are what the share is there to expose.
"""

from __future__ import annotations

F32 = 4


def fused_update_bytes(spans: int) -> int:
    """`spanmetrics_fused_update` over `spans` real rows: each row reads
    its packed [slot, duration, size, weight] (4 x f32) and reads and
    writes one cell in each of seven places: calls, latency count,
    latency sum, one latency-histogram bucket, size sum, the DDSketch
    bucket and the DDSketch row count."""
    return spans * (4 * F32 + 7 * 2 * F32)


def plane_grid_bytes(rows: int, groups: int, steps: int, hist: bool) -> int:
    """`BlockScanPlane.metrics_grid` (module `jit_build`) over one
    resident block: reads the start-time and group-code columns (and the
    duration column for a quantile), i32/f32 a row, and writes the
    [groups, steps] f32 grid, x 64 log2 buckets for a quantile."""
    cols = 3 if hist else 2
    return rows * cols * F32 + groups * steps * (64 if hist else 1) * F32
