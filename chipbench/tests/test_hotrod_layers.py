"""The per-layer metrics of `hotrod-sdk.steady`: three of the service-graph
layer the cell exists for (the expiry's self time, the store's items at the
closing scrape, the edge step's roofline) and seven twins of
`k6-write.steady`'s metrics that say where this cell's time goes. Each
layer file agrees with its `per_layer` entry and reads a number from a
/metrics pair (or a trace) that holds its family and nothing (None, never
0) from one that lacks it: the parent has neither the span
`servicegraphs.expire`, nor the store's gauge, nor the emitted-edge counter
the roofline is fed by. The manifest only grew: one configuration, one
cell, entries that list that cell alone. Not in tier-1:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import costs_edges, lib  # noqa: E402
from chipbench.mixes import otlp_push_hotrod  # noqa: E402
from chipbench.tests import test_yardstick  # noqa: E402

CELL = "hotrod-sdk.steady"
CONFIG = "hotrod-otel-sdk"
PARENT = "7f09636e774b95d0959a5406cda7bfc17a4b0802"
GEN = "generator (registry collect + remote write)"
NEW = {
    # 50 more expiries: 40 clear of 0.2 ms and 10 met of 0.7 ms self time
    "sg_expire_ms.hotrod": (0.3, "push_p50_ms", GEN),
    # the store's items at the closing scrape, both tenants
    "sg_store_items.hotrod": (150_000.0, "ingest_spans_per_s", GEN),
}
ROOFLINE = "edge_update_roofline_pct.hotrod"
TWINS = {n + ".hotrod" for n in (
    "servicegraphs_push_ms", "push_clear_ms", "push_turn_wait_ms",
    "device_idle_pct", "ingester_cut_ms", "cut_columns_pct",
    "host_cpu_us_per_span")}
ALL = set(NEW) | {ROOFLINE} | TWINS


def _exposition(n: int, new: bool = True) -> str:
    """`/metrics` after `n` rounds of 25 pushes, each with one
    `servicegraphs.expire` (with `new`), and the store's gauge at 75,000
    a tenant."""
    out = []
    if new:
        for collect, count, self_s in (("clear", 20, 0.0002),
                                       ("met", 5, 0.0007)):
            labels = f'{{span="servicegraphs.expire",collect="{collect}"}}'
            out.append(f"tempo_span_self_seconds_count{labels} {count * n}")
            out.append(f"tempo_span_self_seconds_sum{labels} "
                       f"{count * n * self_s}")
        for tenant in ("hotrod-a", "hotrod-b"):
            out.append(f'{otlp_push_hotrod.STORE}{{tenant="{tenant}"}} 75000')
            out.append(f'{otlp_push_hotrod.EDGES}{{tenant="{tenant}",'
                       f'kind="completed"}} {9000 * n}')
    out.append('tempo_metrics_generator_spans_received_total'
               f'{{tenant="hotrod-a"}} {12_800 * n}')
    return "\n".join(out) + "\n"


def _pair(a: str, b: str) -> dict:
    return {"m0": lib.parse_exposition(a), "m1": lib.parse_exposition(b)}


def _bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _layer(name: str) -> dict:
    with open(os.path.join(REPO, "chipbench", "layers", name + ".json")) as f:
        return json.load(f)


def _read(name: str, obs: dict):
    reader = _layer(name)["reader"]
    return importlib.import_module(
        "chipbench.readers." + reader["kind"]).read(reader, obs)


def test_the_cell_lists_its_metrics_and_no_other_cell_lists_them():
    mine = {m["name"] for m in _bench()["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine == ALL and len(ALL) == 10
    assert all(m["workloads"] == [CELL] for m in _bench()["per_layer"]
               if m["name"] in mine)


def test_the_manifest_only_grew():
    """Every entry of the parent's manifest is there as it was and in its
    place; what was appended names this cell and no other."""
    try:
        old = json.loads(subprocess.run(
            ["git", "-C", REPO, "show", PARENT + ":BENCHMARK.json"],
            capture_output=True, text=True, check=True).stdout)
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here")
    new = _bench()
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert new[key] == old[key]
    for key in ("configs", "workloads", "per_layer"):
        assert new[key][:len(old[key])] == old[key]
    assert [c["name"] for c in new["configs"][len(old["configs"]):]] == [
        CONFIG]
    added = new["workloads"][len(old["workloads"]):]
    assert [(w["name"], w["config"], w["chips"]) for w in added] == [
        (CELL, CONFIG, 1)]
    added = new["per_layer"][len(old["per_layer"]):]
    assert {m["name"] for m in added} == ALL
    assert all(m["workloads"] == [CELL] for m in added)
    # new files only: nothing the parent's benchmark had is edited
    changed = subprocess.run(
        ["git", "-C", REPO, "diff", "--name-status", PARENT, "--",
         "chipbench"], capture_output=True, text=True, check=True).stdout
    assert all(line.startswith("A") for line in changed.splitlines()), changed


@pytest.mark.parametrize("name", sorted(ALL))
def test_layer_file_agrees_with_the_manifest(name):
    entries = {m["name"]: m for m in _bench()["per_layer"]}
    layer, entry = _layer(name), entries[name]
    assert layer["name"] == name
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        layer["layer"], layer["unit"], layer["moves"])
    twin = name.replace(".hotrod", ".write")
    assert (twin in entries) == (name in TWINS)
    if twin in entries:
        # read as its `.write` twin is, letter for letter
        assert layer["reader"] == _layer(twin)["reader"]
        assert {k: entry[k] for k in entry if k not in ("name", "workloads")} \
            == {k: entries[twin][k] for k in entries[twin]
                if k not in ("name", "workloads")}
    elif name in NEW:
        assert (entry["moves"], entry["layer"]) == NEW[name][1:]
        assert entry["source"] == ("program_span" if layer["reader"][
            "kind"] == "hist_mean" else "program_counter")


@pytest.mark.parametrize("name", sorted(NEW))
def test_layer_reads_a_number_or_nothing(name):
    assert _read(name, _pair(_exposition(1), _exposition(3))) \
        == pytest.approx(NEW[name][0])
    # a /metrics with none of the families: nothing read, nothing raised
    assert _read(name, test_yardstick.obs()) is None
    # the parent: no such span, no such gauge
    assert _read(name, _pair(_exposition(1, new=False),
                             _exposition(3, new=False))) is None


def test_the_store_gauge_reads_zero_when_the_store_is_empty():
    """A state, not a growth: an empty store at the closing scrape is a
    0 that was measured, and the opening scrape is not read."""
    empty = _exposition(3).replace(" 75000", " 0")
    assert _read("sg_store_items.hotrod", _pair(_exposition(1), empty)) \
        == 0.0


def test_sg_expire_is_idle_when_nothing_was_pushed():
    assert _read("sg_expire_ms.hotrod",
                 _pair(_exposition(3), _exposition(3))) is None


def _roofline_obs(seconds: float, least: dict) -> dict:
    trace = {"chips": 1, "busy_s": 0.8,
             "modules": {"jit__fused_update_packed4_impl": [300, 0.5],
                         "jit__edge_update_impl": [400, seconds]}}
    return test_yardstick.obs(trace=trace, trace_window_s=8.0,
                              least_bytes=least,
                              peaks={"hbm_bytes_per_s": 819e9})


def test_edge_roofline_reads_the_edge_step_and_its_bytes():
    """80 B an edge (`costs_edges`); 819 GB/s; the module's seconds."""
    assert costs_edges.edge_update_bytes(1) == 80
    least = {"edge_update": costs_edges.edge_update_bytes(819_000)}
    assert _read(ROOFLINE, _roofline_obs(0.08, least)) == pytest.approx(
        100.0 * 819_000 * 80 / 819e9 / 0.08)
    # nothing where the program has no emitted-edge counter (the parent)
    assert _read(ROOFLINE, _roofline_obs(0.08, {})) is None
    assert _read(ROOFLINE, test_yardstick.obs(trace=None)) is None


def test_the_mix_feeds_the_roofline_from_the_edge_counter():
    """`least_bytes` scales the counter's growth between the two marks to
    the profiler's seconds; no marks (no counter): nothing."""
    mix = otlp_push_hotrod.Mix.__new__(otlp_push_hotrod.Mix)
    mix.edge_marks = [(100.0, 1_000.0), (108.0, 9_000.0)]
    assert mix.least_bytes({}, 100.5, 104.5) == {
        "edge_update": costs_edges.edge_update_bytes(4_000)}
    mix.edge_marks = []
    assert mix.least_bytes({}, 100.5, 104.5) == {}


@pytest.mark.parametrize("name", sorted(TWINS))
def test_a_twin_reads_what_its_write_twin_reads(name):
    assert _layer(name)["reader"] == _layer(
        name.replace(".hotrod", ".write"))["reader"]
