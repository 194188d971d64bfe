#!/usr/bin/env python3
"""What a clean stop of the durable single binary takes, part by part.

    python3 chipbench/repro/stop_probe.py --seed <n> [--seconds 45] [--rehearsal]

The set-up and a window of `k6-write-wal.steady` through its own mix,
then `App.shutdown` as SIGTERM runs it, with the seconds of each part on
a `TIMED` line: the fleet's checkpoints and the log's truncation
(`fleet.shutdown`), the ingester's flush (`ingester.shutdown`, and each
tenant's `complete_block` inside it), the generator's and the
database's. Where `PERF.md` and `operations/runbook.md` give the stop's
parts at the cell's size (PR 35), this is the script that read them. On
the chip it holds the chip as `run.py` does; `--rehearsal` runs the toy
size on whatever device JAX finds.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import lib, run as bench_run  # noqa: E402
from chipbench.lib import say  # noqa: E402
from chipbench.mixes import otlp_push_wal  # noqa: E402

CELL = "k6-write-wal.steady"


def timed(obj, name: str, label: str) -> None:
    """`obj.name` says how long each call of it took."""
    f = getattr(obj, name)

    @functools.wraps(f)
    def wrapper(*a, **k):
        t0 = time.monotonic()
        try:
            return f(*a, **k)
        finally:
            say(TIMED=label, s=round(time.monotonic() - t0, 3))

    setattr(obj, name, wrapper)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    with open(os.path.join(REPO, next(
            c["file"] for c in bench["configs"]
            if c["name"] == cell["config"]))) as f:
        config = json.load(f)
    traffic = lib.load_json("traffic", cell["traffic"] + ".json")
    if args.rehearsal:
        config = lib.merged(config, config.get("rehearsal", {}))
        traffic = lib.merged(traffic, traffic.get("rehearsal", {}))

    from tempo_tpu.obs.jaxruntime import configure_compile_cache
    configure_compile_cache()
    ctx = types.SimpleNamespace(
        args=args, seed=args.seed, cell=cell, config=config, traffic=traffic,
        workdir=tempfile.mkdtemp(prefix="chipbench-"),
        rehearsal=args.rehearsal, n_child=0,
        clock=lambda: round(bench_run.process_age_s(), 3))
    ctx.run_child = lambda spec, go=None: bench_run.run_child(ctx, spec, go)
    mix = otlp_push_wal.Mix(ctx)
    mix.setup()
    res = ctx.run_child(dict(mix.child_spec(), seconds=args.seconds),
                        lambda: None)
    mix.note(res)
    mix.drain("the window's pushes")
    say(phase="window_done", at_s=ctx.clock(), pushes=len(mix.sent),
        spans=sum(d["n"] for d in mix.sent if "n" in d))
    app = ctx.app
    ctx.srv.shutdown()
    ctx.srv.server_close()
    for part in ("fleet", "ingester", "generator", "db"):
        timed(getattr(app, part), "shutdown", part + ".shutdown")
    for tenant, inst in app.ingester.instances.items():
        timed(inst, "complete_block", "complete_block " + tenant)
    t0 = time.monotonic()
    app.shutdown()
    say(phase="stopped", shutdown_s=round(time.monotonic() - t0, 3))
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)          # as run.py: no thread holds the exit
