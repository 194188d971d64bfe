"""The per-layer metrics of `tenants-zipf.steady` (PR 33): each layer
file agrees with its `per_layer` entry, reads a number from a /metrics
pair that holds its family, and reads nothing (None, never 0) from one
that lacks it: the parent commit has the page pool's families, the spans
and the one-tenant collect histogram, and not the round histogram that PR
added. The new reader kind `gauge_fill` on canned pairs, and the
manifest: every entry that PR added lists exactly this cell, and no entry
that was there changed. The span `pages.alloc` has NO metric: every
tenant's table is full before the window, so no page is allocated inside
it and a reader of the span would never find anything to read in this
cell. Not in tier-1:

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

from chipbench import lib  # noqa: E402
from chipbench.tests import test_yardstick  # noqa: E402

CELL = "tenants-zipf.steady"
PARENT = "60548fb9e80ad7083a8d7829ffe1728172a068c2"
COLLECT = "tempo_metrics_generator_collect_duration_seconds"
ROUND = "tempo_metrics_generator_collect_round_duration_seconds"
SPANS_IN = "tempo_metrics_generator_spans_received_total"
GATHER = "tempo_pages_gather_overhead_seconds_total"
DD = 'role="traces_spanmetrics_latency/ddsketch",dtype="float32",width="1269"'
CALLS = 'role="traces_spanmetrics_calls_total/values",dtype="float32",width="1"'

# name -> what two rounds of the canned exposition below read
WANT = {
    "sched_enqueue_ms.tenants": 9.0,
    "sched_h2d_ms.tenants": 12.0,
    "push_clear_ms.tenants": 190.0,
    "servicegraphs_push_ms.tenants": 30.0,
    "collect_tick_s.tenants": 0.04,
    "collect_round_s.tenants": 12.0,
    # 2.56 s of gathers over 256 collects
    "pages_gather_ms_per_collect.tenants": 10.0,
    # 458 of the DDSketch arena's 1,023 pages
    "pages_fill_pct.tenants": 100.0 * 458 / 1023,
    "ingester_cut_ms.tenants": 21.0,
    # 1,633 of every 10,000 spans are the first tenant's
    "tenant_hot_share_pct.tenants": 16.33,
}
FROM_THE_TRACE = {"device_idle_pct.tenants",
                  "fused_update_roofline_pct.tenants"}
NEW_AT_THIS_PR = {"collect_round_s.tenants"}
A_STATE = {"pages_fill_pct.tenants"}      # a gauge: read at the close alone


def _exposition(n: int, new: bool = True, pool: bool = True) -> str:
    """`/metrics` after `n` collection rounds over 256 tenants."""
    out = []
    spans = [("sched.h2d", "clear", 0.013, 0.012),
             ("sched.enqueue", "clear", 0.009, 0.009),
             ("servicegraphs.push", "clear", 0.031, 0.03),
             ("servicegraphs.push", "met", 0.3, 0.3),
             ("api.push", "clear", 0.19, 0.005),
             ("api.push", "met", 1.3, 0.04)]
    for span, collect, dur, self_s in spans:
        labels = f'{{span="{span}",collect="{collect}"}}'
        for fam, each in (("tempo_span_duration_seconds", dur),
                          ("tempo_span_self_seconds", self_s)):
            out.append(f"{fam}_count{labels} {10 * n}")
            out.append(f"{fam}_sum{labels} {10 * n * each}")
    cut = "tempo_ingester_cut_duration_seconds"
    out += [f"{COLLECT}_count {256 * n}", f"{COLLECT}_sum {10.24 * n}",
            f"{cut}_count {256 * n}", f"{cut}_sum {5.376 * n}",
            f'{SPANS_IN}{{tenant="t-001"}} {1633 * n}',
            f'{SPANS_IN}{{tenant="t-002"}} {816 * n}',
            f'{SPANS_IN}{{tenant="t-003"}} {544 * n}',
            *(f'{SPANS_IN}{{tenant="t-{i:03d}"}} {1001 * n}'
              for i in range(4, 11))]
    if new:
        out += [f"{ROUND}_count {n}", f"{ROUND}_sum {12.0 * n}"]
    if pool:
        out += [f"{GATHER} {2.56 * n}",
                f"tempo_pages_total{{{DD}}} 1023",
                f"tempo_pages_free{{{DD}}} {1023 - 458}",
                f"tempo_pages_total{{{CALLS}}} 1023",
                f"tempo_pages_free{{{CALLS}}} 500"]
    return "\n".join(out) + "\n"


def _pair(a: str, b: str) -> dict:
    return {"m0": lib.parse_exposition(a), "m1": lib.parse_exposition(b)}


def _layer(name: str) -> dict:
    with open(os.path.join(REPO, "chipbench", "layers", name + ".json")) as f:
        return json.load(f)


def _read(name: str, obs: dict):
    reader = _layer(name)["reader"]
    return importlib.import_module(
        "chipbench.readers." + reader["kind"]).read(reader, obs)


def _bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _entries() -> dict:
    return {m["name"]: m for m in _bench()["per_layer"]}


def test_the_cell_reports_exactly_these():
    mine = {n for n, m in _entries().items() if CELL in m["workloads"]}
    assert mine == set(WANT) | FROM_THE_TRACE


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
        "multitenant-zipf-256"]
    assert [w["name"] for w in new["workloads"][len(old["workloads"]):]] == [
        CELL]
    added = new["per_layer"][len(old["per_layer"]):]
    assert {m["name"] for m in added} == set(WANT) | FROM_THE_TRACE
    assert all(m["workloads"] == [CELL] for m in added)


@pytest.mark.parametrize("name", sorted(set(WANT) | FROM_THE_TRACE))
def test_layer_file_agrees_with_the_manifest(name):
    entries = _entries()
    layer, entry = _layer(name), entries[name]
    assert layer["name"] == name
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        layer["layer"], layer["unit"], layer["moves"])
    assert entry["workloads"] == [CELL]
    assert entry["source"] == ("device_trace" if name in FROM_THE_TRACE
                               else "program_counter")
    # a layer BENCHMARK.json already named, letter for letter
    assert any(m["layer"] == entry["layer"] and CELL not in m["workloads"]
               for m in entries.values())
    twin = name.replace(".tenants", ".write")
    if twin in entries:      # the dense twin, read the same way
        a, b = _layer(twin)["reader"], layer["reader"]
        assert {k for k in a if a[k] != b[k]} <= {"module"}
        assert layer["unit"] == _layer(twin)["unit"]


@pytest.mark.parametrize("name", sorted(WANT))
def test_layer_reads_a_number_or_nothing(name):
    assert _read(name, _pair(_exposition(1), _exposition(3))) \
        == pytest.approx(WANT[name])
    # a /metrics with none of these families: nothing read, nothing raised
    assert _read(name, test_yardstick.obs()) is None
    # nothing happened inside the window
    flat = _read(name, _pair(_exposition(3), _exposition(3)))
    assert flat == pytest.approx(WANT[name]) if name in A_STATE \
        else flat is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_parent_has_the_pool_and_not_the_round(name):
    parent = _pair(_exposition(1, new=False), _exposition(3, new=False))
    if name in NEW_AT_THIS_PR:
        assert _read(name, parent) is None        # left out, never 0
    else:
        assert _read(name, parent) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", ["pages_fill_pct.tenants",
                                  "pages_gather_ms_per_collect.tenants"])
def test_a_run_without_the_pool_reads_nothing(name):
    dense = _pair(_exposition(1, pool=False), _exposition(3, pool=False))
    assert _read(name, dense) is None


def test_no_metric_reads_the_alloc_span():
    """A metric that lists a cell has to be read in every traced run of
    it, and nothing allocates a page inside this cell's window."""
    assert "pages_alloc_ms.tenants" not in _entries()
    assert not os.path.exists(os.path.join(
        REPO, "chipbench", "layers", "pages_alloc_ms.tenants.json"))


def test_gauge_fill():
    from chipbench.readers import gauge_fill

    p = {"free": "tempo_pages_free", "total": "tempo_pages_total",
         "labels": {"role": "traces_spanmetrics_calls_total/values"}}
    obs = _pair(_exposition(1), _exposition(2))
    assert gauge_fill.read(p, obs) == pytest.approx(100.0 * 523 / 1023)
    # no labels: over every arena of the pool
    assert gauge_fill.read(dict(p, labels={}), obs) == pytest.approx(
        100.0 * (458 + 523) / 2046)
    # the closing scrape alone is read
    empty = f"tempo_pages_total{{{DD}}} 1023\ntempo_pages_free{{{DD}}} 1023\n"
    assert gauge_fill.read(dict(p, labels={}),
                           _pair(_exposition(1), empty)) == 0.0
    # a pool of nothing, or no pool
    assert gauge_fill.read(p, _pair("", f"tempo_pages_total{{{CALLS}}} 0\n")) \
        is None
    assert gauge_fill.read(p, _pair("", "")) is None


def test_the_roofline_reads_the_paged_module_alone():
    """`jit__fused_update_paged` matches the page pool's step and neither
    the dense nor the mesh step."""
    from chipbench.readers import xplane_roofline

    p = _layer("fused_update_roofline_pct.tenants")["reader"]
    assert p == {"kind": "xplane_roofline",
                 "module": "jit__fused_update_paged", "bytes": "fused_update"}
    obs = {"peaks": {"hbm_bytes_per_s": 819e9},
           "least_bytes": {"fused_update": 72 * 10_000},
           "trace": {"modules": {
               "jit__fused_update_paged_impl(123)": [10, 0.11],
               "jit__fused_update_packed4_impl(7)": [5, 0.005],
               "jit_step(9)": [40, 0.001]}}}
    got = xplane_roofline.read(p, obs)
    assert got == pytest.approx(100.0 * (720000 / 819e9) / 0.11)
    obs["trace"]["modules"].pop("jit__fused_update_paged_impl(123)")
    assert xplane_roofline.read(p, obs) is None    # the parent: `jit_step`
