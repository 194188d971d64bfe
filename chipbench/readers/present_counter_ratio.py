"""`counter_ratio` for counters that the parent of the PR that brought
them lacks: the same ratio, and nothing where a `num` family has no sample
on `/metrics` at all. To `counter_ratio` an absent family reads as no
growth, and it would report a 0 that nobody measured."""

from chipbench.readers import counter_ratio


def read(p: dict, obs: dict):
    present = {name for name, _ in obs["m1"]}
    if not all(f in present for f in p["num"]):
        return None
    return counter_ratio.read(p, obs)
