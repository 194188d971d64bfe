"""The per-layer metrics of `k6-write-mesh4.steady` (PR 28): each layer
file agrees with its `per_layer` entry, reads a number from a /metrics
pair that holds its family, and reads nothing (None, never 0) from one
that lacks it: the parent commit has the spans and the collect histogram
and none of the three `tempo_mesh_*` families that PR added. The two new
reader kinds on canned pairs. Not in tier-1:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import lib  # noqa: E402
from chipbench.tests import test_yardstick  # noqa: E402

CELL = "k6-write-mesh4.steady"
ROWS, BYTES = "tempo_mesh_shard_rows_total", "tempo_mesh_h2d_bytes_total"
SPANS_IN = "tempo_metrics_generator_spans_received_total"

# name -> what two rounds of the canned exposition below read
WANT = {
    "sched_h2d_ms.mesh4": 12.0,
    "sched_enqueue_ms.mesh4": 9.0,
    "collect_gather_s.mesh4": 0.1,
    "collect_tick_s.mesh4": 22.0,
    "push_clear_ms.mesh4": 190.0,
    # 65,536 link bytes a 1,000 spans
    "mesh_h2d_bytes_per_span.mesh4": 65.536,
    # 890 of 1,000 series rows on shard 0
    "mesh_hot_shard_pct.mesh4": 89.0,
}
FROM_THE_TRACE = {"device_idle_pct.mesh4", "fused_update_roofline_pct.mesh4"}
NEW_AT_THIS_PR = {"mesh_h2d_bytes_per_span.mesh4", "mesh_hot_shard_pct.mesh4"}


def _exposition(n: int, mesh: bool = True) -> str:
    """`/metrics` after `n` rounds of 10 batches of 1,000 spans."""
    out = []
    for span, collect, dur, self_s in (
            ("sched.h2d", "clear", 0.013, 0.012),
            ("sched.enqueue", "clear", 0.009, 0.009),
            ("registry.gather", "met", 0.1, 0.1),
            ("api.push", "clear", 0.19, 0.005),
            ("api.push", "met", 1.3, 0.04)):
        labels = f'{{span="{span}",collect="{collect}"}}'
        for fam, each in (("tempo_span_duration_seconds", dur),
                          ("tempo_span_self_seconds", self_s)):
            out.append(f"{fam}_count{labels} {10 * n}")
            out.append(f"{fam}_sum{labels} {10 * n * each}")
    collect = "tempo_metrics_generator_collect_duration_seconds"
    out += [f"{collect}_count {2 * n}", f"{collect}_sum {44.0 * n}",
            f'{SPANS_IN}{{tenant="k6-a"}} {5000 * n}',
            f'{SPANS_IN}{{tenant="k6-b"}} {5000 * n}']
    if mesh:
        out.append(f"{BYTES} {655360 * n}")
        for plane, rows in (("series", (8900, 1100, 0, 0)),
                            ("sketch", (2500, 2500, 2500, 2500))):
            out += [f'{ROWS}{{plane="{plane}",shard="{i}"}} {r * n}'
                    for i, r in enumerate(rows)]
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


def _entries() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


def test_the_cell_reports_exactly_these():
    mine = {n for n, m in _entries().items() if CELL in m["workloads"]}
    assert mine == set(WANT) | FROM_THE_TRACE


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
    twin = name.replace(".mesh4", ".write")
    if twin in entries:      # the one-chip twin, read the same way
        a, b = _layer(twin)["reader"], layer["reader"]
        assert {k for k in a if a[k] != b[k]} <= {"module"}
        assert entries[twin]["workloads"] == ["k6-write.steady"]


@pytest.mark.parametrize("name", sorted(WANT))
def test_layer_reads_a_number_or_nothing(name):
    assert _read(name, _pair(_exposition(1), _exposition(3))) \
        == pytest.approx(WANT[name])
    # a /metrics with none of these families: nothing read, nothing raised
    assert _read(name, test_yardstick.obs()) is None
    # nothing happened inside the window
    assert _read(name, _pair(_exposition(3), _exposition(3))) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_parent_has_the_spans_and_not_the_mesh_counters(name):
    parent = _pair(_exposition(1, mesh=False), _exposition(3, mesh=False))
    if name in NEW_AT_THIS_PR:
        assert _read(name, parent) is None        # left out, never 0
    else:
        assert _read(name, parent) == pytest.approx(WANT[name])


def test_label_max_share():
    from chipbench.readers import label_max_share

    p = {"family": ROWS, "labels": {"plane": "sketch"}, "by": "shard",
         "scale": 100.0}
    obs = _pair(_exposition(1), _exposition(2))
    assert label_max_share.read(p, obs) == pytest.approx(25.0)   # even
    one = [f'{ROWS}{{plane="series",shard="2"}} {k}\n' for k in (0, 7)]
    assert label_max_share.read(dict(p, labels={"plane": "series"}),
                                _pair(*one)) == pytest.approx(100.0)
    # a label value that appeared inside the window counts from zero
    grew = _pair(f'{ROWS}{{plane="series",shard="0"}} 10\n',
                 f'{ROWS}{{plane="series",shard="0"}} 40\n'
                 f'{ROWS}{{plane="series",shard="1"}} 10\n')
    assert label_max_share.read(dict(p, labels={"plane": "series"}),
                                grew) == pytest.approx(75.0)


def test_present_counter_ratio():
    from chipbench.readers import present_counter_ratio

    p = {"num": [BYTES], "den": [SPANS_IN]}
    # the family is there and did not grow: a measured 0
    flat = _pair(_exposition(1), _exposition(1).replace(
        f'{SPANS_IN}{{tenant="k6-a"}} 5000', f'{SPANS_IN}{{tenant="k6-a"}} 9000'))
    assert present_counter_ratio.read(p, flat) == 0.0
    # the family is not there: nothing, where `counter_ratio` says 0
    from chipbench.readers import counter_ratio
    parent = _pair(_exposition(1, mesh=False), _exposition(2, mesh=False))
    assert present_counter_ratio.read(p, parent) is None
    assert counter_ratio.read(p, parent) == 0.0
