#!/usr/bin/env python3
"""chipbench/run.py: one cell of BENCHMARK.json, once, on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip: it boots the App and serves it over HTTP,
as `chip_smoke.py` does. The load comes from a child process that never
imports JAX (`loadgen.py`). Every line on stdout is one JSON object; the
last one is the result. Nothing here names a configuration, a traffic mix
or a per-layer metric: a cell is (configuration file, traffic file), the
traffic file names its kind (a module under `mixes/`), and each per-layer
metric of BENCHMARK.json has a file under `layers/` that names its reader
(a module under `readers/`).

`--rehearsal` runs the same control flow at a toy size on whatever device
JAX finds (the CPU here), prints `"rehearsal": true` and exits 1: it can
not be read as a result.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import pickle
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import types

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from chipbench import lib, xplane  # noqa: E402
from chipbench.lib import BenchFailure, say  # noqa: E402

COMPILES = "tempo_jax_jit_compile_total"


def process_age_s() -> float:
    """Seconds since this process was started, from /proc: set-up
    counts from the process's start, not from this module's import."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0)


def run_child(ctx, spec: dict, go=None) -> dict:
    """Run the load generator to its end. With a window (`seconds`), `go`
    is called when the child is ready and returns when the window is to
    start; the child is told on its stdin."""
    spec = dict(spec, port=ctx.port, clients=ctx.traffic["clients"],
                timeout=ctx.traffic.get("timeout_s", 300.0))
    ctx.n_child += 1
    spec_path = os.path.join(ctx.workdir, f"child{ctx.n_child}.spec")
    out_path = os.path.join(ctx.workdir, f"child{ctx.n_child}.out")
    with open(spec_path, "wb") as f:
        pickle.dump(spec, f)
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py"), spec_path, out_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        if spec.get("seconds") is not None:
            if child.stdout.readline().strip() != "ready":
                raise BenchFailure("the load generator did not come up")
            go()
            child.stdin.write("go\n")
            child.stdin.flush()
        rc = child.wait(timeout=(spec.get("seconds") or 0) + 1200)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if rc != 0:
        raise BenchFailure(f"the load generator exited {rc}")
    with open(out_path, "rb") as f:       # written by our own child only
        return pickle.load(f)


def device_report(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use") or 0))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def statistic(spec: dict, judged: dict, seconds: float,
              strict: bool = True) -> float:
    if spec["stat"] == "percentile":
        return lib.percentile(judged["latencies_ms"], spec["q"], strict)
    if spec["stat"] == "rate":
        return judged["units"] / seconds
    raise BenchFailure(f"unknown statistic {spec['stat']!r}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--manifest", default=os.path.join(REPO, "BENCHMARK.json"),
                    help="another manifest of the same form, for cells "
                         "kept out of BENCHMARK.json (chipbench/repro/)")
    ap.add_argument("--keep-trace", default="",
                    help="copy the .xplane.pb to this path")
    args = ap.parse_args()

    with open(args.manifest) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise BenchFailure(f"no cell {args.workload!r} in {args.manifest}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(REPO, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = lib.load_json("traffic", cell["traffic"] + ".json")
    if args.rehearsal:
        config = lib.merged(config, config.get("rehearsal", {}))
        traffic = lib.merged(traffic, traffic.get("rehearsal", {}))

    import tempo_tpu  # noqa: F401  (a bare directory fails here)
    from tempo_tpu import native
    from tempo_tpu.obs.jaxruntime import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not args.rehearsal and (not on_tpu or len(devices) < cell["chips"]):
        print(f"chipbench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s), JAX found {devices}", file=sys.stderr)
        return 2
    peaks = lib.load_json("peaks.json").get(devices[0].device_kind)
    if peaks is None and not args.rehearsal:
        raise BenchFailure(f"no peaks for device kind "
                           f"{devices[0].device_kind!r} in peaks.json")
    if not native.available():
        raise BenchFailure("the native OTLP decoder did not build: the "
                           "pure-Python decoder is not the served path")
    say(platform=devices[0].platform, device_kind=devices[0].device_kind,
        device_count=len(devices), jax=jax.__version__,
        compile_cache_dir=cache_dir, workload=cell["name"], seed=args.seed,
        seconds=args.seconds, trace=args.trace, rehearsal=args.rehearsal)

    # inside the checkout's TMPDIR the driver gives each side; never a
    # fixed path
    workdir = tempfile.mkdtemp(prefix="chipbench-")
    ctx = types.SimpleNamespace(
        args=args, seed=args.seed, cell=cell, config=config, traffic=traffic,
        workdir=workdir, rehearsal=args.rehearsal, n_child=0,
        clock=lambda: round(process_age_s(), 3))
    ctx.run_child = lambda spec, go=None: run_child(ctx, spec, go)
    try:
        return measure(ctx, bench, jax, peaks)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def measure(ctx, bench: dict, jax, peaks) -> int:
    args, cell, traffic = ctx.args, ctx.cell, ctx.traffic
    mix = importlib.import_module("chipbench.mixes." + traffic["kind"]).Mix(ctx)
    mix.setup()
    say(phase="prepared", at_s=ctx.clock())

    obs: dict = {"peaks": peaks, "trace": None}
    marks: dict = {}
    tracing = {"dir": os.path.join(ctx.workdir, "trace"), "window": None}

    def trace_part() -> None:
        """A few seconds of the window under the profiler: traces are
        large and slow the host."""
        plan = traffic["trace"]
        time.sleep(max(marks["t_go"] + plan["start_frac"] * args.seconds
                       - time.monotonic(), 0))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the interpreter's tracer would
        opts.host_tracer_level = 2        # cost the server its lock
        jax.profiler.start_trace(tracing["dir"], profiler_options=opts)
        t0 = time.monotonic()
        time.sleep(min(plan["seconds"], args.seconds / 3))
        t1 = time.monotonic()
        jax.profiler.stop_trace()
        tracing["window"] = (t0, t1)

    def closing_scrape() -> None:
        obs["m1"] = lib.scrape(ctx.port)

    # the closing scrape is taken at the window's end on this side, while
    # the child lets its requests in flight finish
    side = [threading.Timer(args.seconds, closing_scrape)]
    if args.trace:
        side.append(threading.Thread(target=trace_part))

    def go() -> None:
        mix.wait_start()
        obs["m0"] = lib.scrape(ctx.port)
        marks["setup_s"] = process_age_s()
        marks["t_go"] = time.monotonic()
        for t in side:
            t.start()

    res = ctx.run_child(dict(mix.child_spec(), seconds=args.seconds), go)
    for t in side:
        t.join()
    say(phase="window_done", at_s=ctx.clock(),
        child_cpu_s=round(res["cpu_s"], 3), window_s=args.seconds,
        child_cpu_share=round(res["cpu_s"] / args.seconds, 4),
        child_errors=res["errors"][:3])

    judged = mix.judge(res, res["t_go"], args.seconds)
    complaints = list(judged["complaints"])
    compiled = {dict(ls).get("fn", "?"): v - obs["m0"].get((n, ls), 0.0)
                for (n, ls), v in obs["m1"].items() if n == COMPILES
                and v != obs["m0"].get((n, ls), 0.0)}
    if compiled:
        complaints.append(f"jit compilations inside the window: {compiled}")
    lat = sorted(judged["latencies_ms"])
    if lat:
        say(samples=len(lat), p50_ms=lib.percentile(lat, 50),
            p95_ms=lat[int(0.95 * (len(lat) - 1))], max_ms=lat[-1],
            beyond_p90=len(lat) // 10, beyond_p95=len(lat) // 20)

    metrics: dict = {}
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    if not args.trace:
        for name, stat in traffic["report"].items():
            metrics[name] = statistic(stat, judged, args.seconds,
                                      not ctx.rehearsal)
        metrics["setup_s"] = marks["setup_s"]
    device = device_report(jax)
    breakdown = None
    if args.trace:
        path = xplane.find_trace(tracing["dir"])
        if args.keep_trace:
            os.makedirs(os.path.dirname(args.keep_trace) or ".", exist_ok=True)
            shutil.copy(path, args.keep_trace)
        t0, t1 = tracing["window"]
        obs["trace"] = red = xplane.reduce_trace(path)
        obs["trace_window_s"] = t1 - t0
        obs["least_bytes"] = mix.least_bytes(res, t0, t1)
        obs["requests"] = len(judged["latencies_ms"])
        device["busy_s"], device["window_s"] = red["busy_s"], t1 - t0
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        say(traced_modules={k: v for k, v in sorted(
            red["modules"].items(), key=lambda kv: -kv[1][1])[:12]},
            trace_extent_s=red["extent_s"], least_bytes=obs["least_bytes"])
        for m in bench["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            layer = lib.load_json("layers", m["name"] + ".json")
            reader = importlib.import_module(
                "chipbench.readers." + layer["reader"]["kind"])
            value = reader.read(layer["reader"], obs)
            if value is not None:
                metrics[m["name"]] = value
    say(phase="judged", at_s=ctx.clock(), complaints=complaints[:20],
        n_complaints=len(complaints))

    result = {"correct": not complaints, "attempted": judged["attempted"],
              "failed": judged["failed"],
              "metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items()},
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if ctx.rehearsal or device["platform"] != "tpu":
        # a toy-size pass must not read like a result, chip or not
        say(rehearsal=True, would_be=result)
        return 1
    say(**result)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException:
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)      # a wedged dispatch thread must not hold the exit
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
