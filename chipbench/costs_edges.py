"""What the service-graph step needs, from its shapes: the numerator of
`edge_update_roofline_pct.hotrod`. Bytes, as `costs.py` counts them: the
step is scatter-adds with no arithmetic to speak of, so the bound is HBM
bandwidth (`peaks.json`). Padding rows (an emit is padded to a pow-2
bucket) are NOT counted: they are part of what the share exposes.
"""

from __future__ import annotations

F32 = 4


def edge_update_bytes(edges: int) -> int:
    """`servicegraphs._edge_update_impl` over `edges` real rows (dense
    layout, no messaging histogram): each row reads its packed [slot,
    failed, client seconds, server seconds] (4 x f32) and reads and
    writes one cell in each of eight places: the request counter, the
    failed counter, and for each of the client and server histograms one
    bucket, the sum and the count."""
    return edges * (4 * F32 + 8 * 2 * F32)
