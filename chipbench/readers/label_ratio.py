"""Growth of one sample family under the labels `num_labels` over its
growth under `den_labels`, times `scale`: the share of a counter's
increments that carried a label value (`counter_ratio` sums whole
families and cannot tell labels apart)."""

from chipbench.lib import delta


def read(p: dict, obs: dict):
    den = delta(obs, p["family"], **p["den_labels"])
    if den <= 0:
        return None
    return delta(obs, p["family"], **p["num_labels"]) / den \
        * p.get("scale", 1.0)
