"""Growth of the counters `num` over growth of the counters `den` (each a
list of families, summed), times `scale`; `den` may be the word
"requests": the requests that ended in the window."""

from chipbench.lib import delta


def read(p: dict, obs: dict):
    num = sum(delta(obs, f) for f in p["num"])
    den = obs["requests"] if p["den"] == "requests" \
        else sum(delta(obs, f) for f in p["den"])
    if den <= 0:
        return None
    return num / den * p.get("scale", 1.0)
