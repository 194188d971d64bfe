"""Mean of a histogram family over the window: growth of `_sum` over
growth of `_count`, times `scale`. Nothing observed, nothing returned."""

from chipbench.lib import delta


def read(p: dict, obs: dict):
    labels = p.get("labels", {})
    n = delta(obs, p["family"] + "_count", **labels)
    if n <= 0:
        return None
    return delta(obs, p["family"] + "_sum", **labels) / n * p.get("scale", 1.0)
