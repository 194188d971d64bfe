"""A gauge at the closing scrape: the sum of the samples of `family` under
the fixed labels `labels`, times `scale`. A state and not a growth, so the
opening scrape is not read. No such family: nothing returned."""

from chipbench.lib import metric_sum


def read(p: dict, obs: dict):
    if not any(name == p["family"] for name, _ in obs["m1"]):
        return None
    return metric_sum(obs["m1"], p["family"], **p.get("labels", {})) \
        * p.get("scale", 1.0)
