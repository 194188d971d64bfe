"""The largest label value's share of a family's growth: of the samples
of `family` under the fixed labels `labels`, grouped by the label `by`,
the largest group's growth over the growth of all of them, times `scale`
(100 / the number of values = even; 100 = one value took everything).
Nothing grew, or no such family: nothing returned."""


def read(p: dict, obs: dict):
    want = set(p.get("labels", {}).items())
    grown: dict = {}
    for (name, ls), v in obs["m1"].items():
        if name == p["family"] and want <= set(ls):
            key = dict(ls).get(p["by"])
            grown[key] = grown.get(key, 0.0) + v - obs["m0"].get((name, ls), 0.0)
    total = sum(grown.values())
    if total <= 0:
        return None
    return max(grown.values()) / total * p.get("scale", 1.0)
