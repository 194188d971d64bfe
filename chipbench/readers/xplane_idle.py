"""Share of the traced window in which no operation ran on the device
(averaged over the chips), from the profiler trace and nowhere else."""


def read(p: dict, obs: dict):
    trace = obs.get("trace")
    if not trace or not trace["chips"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / obs["trace_window_s"])
