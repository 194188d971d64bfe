"""The three per-layer metrics of live traces kept as columns (PR 31):
the share of spans the live stores took as column slices, the seconds of
one `generator.tick` (the local-blocks cut after a collect), and the self
time of `localblocks.push` a clear push. Each layer file reads a number
from a /metrics pair that holds its family and nothing (None, not 0) from
one that lacks it: the parent commit has the two spans (PR 26) and not
the counter. Not in tier-1:

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

LIVE = "tempo_ingester_live_spans_total"

# name -> (what the canned pair below reads, the end-to-end metric it moves,
#          the metric whose layer it shares letter for letter)
WANT = {
    # 180,000 more spans as columns of 200,000 more in all
    "live_columns_pct.write": (90.0, "ingest_spans_per_s",
                               "ingester_cut_ms.write"),
    # 6 more ticks: 2 clear of 0.3 s and 4 met of 0.6 s
    "generator_tick_s.write": (0.5, "ingest_spans_per_s",
                               "collect_tick_s.write"),
    # clear: 20 more pushes, 0.06 s more self time; the met rows stay out
    "localblocks_push_ms.write": (3.0, "push_p50_ms",
                                  "servicegraphs_push_ms.write"),
}


def _exposition(n: int, counter: bool = True) -> str:
    """`/metrics` after `n` rounds: `localblocks.push` closed 10 n times
    clear (3 ms self each) and 3 n times met (0.7 s), `generator.tick`
    n times clear (0.3 s) and 2 n times met (0.6 s), another span beside
    them, and, with `counter`, 90,000 n spans kept as columns and 10,000 n
    as dicts."""
    out = []
    for span, rows in (
            ("localblocks.push", (("clear", 10, 0.004, 0.003),
                                  ("met", 3, 0.9, 0.7))),
            ("generator.tick", (("clear", 1, 0.3, 0.3),
                                ("met", 2, 0.6, 0.6))),
            ("ingester.push", (("clear", 10, 0.03, 0.026),
                               ("met", 3, 0.9, 0.7)))):
        for collect, count, dur, self_s in rows:
            labels = f'{{span="{span}",collect="{collect}"}}'
            for fam, each in (("tempo_span_duration_seconds", dur),
                              ("tempo_span_self_seconds", self_s)):
                out.append(f"{fam}_count{labels} {count * n}")
                out.append(f"{fam}_sum{labels} {count * n * each}")
    if counter:
        out.append(f"# TYPE {LIVE} counter")
        out.append(f'{LIVE}{{form="columns"}} {90_000 * n}')
        out.append(f'{LIVE}{{form="dicts"}} {10_000 * n}')
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


@pytest.mark.parametrize("name", sorted(WANT))
def test_layer_file_agrees_with_the_manifest(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    layer, entry = _layer(name), entries[name]
    assert layer["name"] == name
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        layer["layer"], layer["unit"], layer["moves"])
    assert entry["moves"] == WANT[name][1]
    assert entry["workloads"] == ["k6-write.steady"]
    assert entry["source"] == "program_counter"
    # a layer BENCHMARK.json already names, letter for letter
    assert entry["layer"] == entries[WANT[name][2]]["layer"]


@pytest.mark.parametrize("name", sorted(WANT))
def test_layer_reads_a_number_or_nothing(name):
    assert _read(name, _pair(_exposition(1), _exposition(3))) \
        == pytest.approx(WANT[name][0])
    # a /metrics with neither family: nothing to read, nothing raised
    assert _read(name, test_yardstick.obs()) is None
    # nothing pushed and no tick inside the window
    assert _read(name, _pair(_exposition(3), _exposition(3))) is None


def test_parent_has_the_spans_and_not_the_counter():
    """The parent commit's /metrics: `generator.tick` and
    `localblocks.push` are there (PR 26), the live-spans counter is not.
    The span metrics read their before, the share reads None, which the
    result line leaves out: never 0."""
    parent = _pair(_exposition(1, counter=False),
                   _exposition(3, counter=False))
    assert _read("generator_tick_s.write", parent) == pytest.approx(0.5)
    assert _read("localblocks_push_ms.write", parent) == pytest.approx(3.0)
    assert _read("live_columns_pct.write", parent) is None


def test_a_deployment_fed_by_dict_routes_reads_zero():
    """Every span through a dict route (Jaeger, Zipkin, the gRPC plane):
    0 %, a number, since the counter is there."""
    only_dicts = [f'{LIVE}{{form="dicts"}} {k}\n' for k in (400, 900)]
    assert _read("live_columns_pct.write", _pair(*only_dicts)) == 0.0
