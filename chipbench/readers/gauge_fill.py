"""How full a pool is at the closing scrape: 100 x (1 - `free` / `total`)
over the samples of the two gauge families under the fixed labels
`labels`. A state and not a growth, so the opening scrape is not read.
No such family, or a pool of nothing: nothing returned."""

from chipbench.lib import metric_sum


def read(p: dict, obs: dict):
    labels = p.get("labels", {})
    total = metric_sum(obs["m1"], p["total"], **labels)
    if total <= 0:
        return None
    return 100.0 * (1.0 - metric_sum(obs["m1"], p["free"], **labels) / total)
