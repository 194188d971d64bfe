"""A kernel's share of its roofline over the traced window: the least
time the chip could take for the bytes the work needs (`costs.py`, keyed
by `bytes`; HBM bandwidth from `peaks.json`: the bytes bound, these
kernels do no arithmetic to speak of) over the device time of the modules
whose jit name starts with `module`."""

from chipbench import xplane


def read(p: dict, obs: dict):
    trace = obs.get("trace")
    if not trace:
        return None
    calls, seconds = xplane.module_seconds(trace, p["module"])
    need = obs["least_bytes"].get(p["bytes"])
    if not calls or not need:
        return None
    return 100.0 * (need / obs["peaks"]["hbm_bytes_per_s"]) / seconds
