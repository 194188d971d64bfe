"""The two per-layer metrics of the service-graph emit (PR 27): the self
time of `servicegraphs.push` a clear push, and the share of emits that
took the jitted step. Each layer file reads a number from a /metrics pair
that holds its family and nothing (None, not 0) from one that lacks it:
the parent commit has the span and not the counter. Not in tier-1:

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

EMITS = "tempo_metrics_generator_servicegraphs_emits_total"

# name -> what the canned pair below reads
WANT = {
    # clear: 20 more pushes, 0.1 s more self time; the met rows stay out
    "servicegraphs_push_ms.write": 5.0,
    # 60 more fused emits of 80 more in all
    "servicegraphs_fused_pct.write": 75.0,
}


def _exposition(n: int, counter: bool = True) -> str:
    """`/metrics` after `n` rounds: `servicegraphs.push` closed 10 n times
    clear (5 ms self each) and 3 n times met (0.7 s), another span beside
    it, and, with `counter`, 30 n fused and 10 n family-level emits."""
    out = []
    for span, clear_self in (("servicegraphs.push", 0.005),
                             ("ingester.push", 0.026)):
        for collect, count, dur, self_s in (("clear", 10, 0.009, clear_self),
                                            ("met", 3, 0.9, 0.7)):
            labels = f'{{span="{span}",collect="{collect}"}}'
            for fam, each in (("tempo_span_duration_seconds", dur),
                              ("tempo_span_self_seconds", self_s)):
                out.append(f"{fam}_count{labels} {count * n}")
                out.append(f"{fam}_sum{labels} {count * n * each}")
    if counter:
        out.append(f"# TYPE {EMITS} counter")
        out.append(f'{EMITS}{{path="fused"}} {30 * n}')
        out.append(f'{EMITS}{{path="family"}} {10 * n}')
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
    assert entry["moves"] == "push_p50_ms"
    assert entry["workloads"] == ["k6-write.steady"]
    assert entry["source"] == "program_counter"
    # a layer BENCHMARK.json already names, letter for letter
    assert sum(m["layer"] == entry["layer"] for m in entries.values()) > 2


@pytest.mark.parametrize("name", sorted(WANT))
def test_layer_reads_a_number_or_nothing(name):
    assert _read(name, _pair(_exposition(1), _exposition(3))) \
        == pytest.approx(WANT[name])
    # a /metrics with neither family: nothing to read, nothing raised
    assert _read(name, test_yardstick.obs()) is None
    # nothing pushed inside the window
    assert _read(name, _pair(_exposition(3), _exposition(3))) is None


def test_parent_has_the_span_and_not_the_counter():
    """The parent commit's /metrics: `servicegraphs.push` is there (PR 26),
    the emit counter is not. The span metric reads its before, the share
    reads None, which the result line leaves out: never 0."""
    parent = _pair(_exposition(1, counter=False),
                   _exposition(3, counter=False))
    assert _read("servicegraphs_push_ms.write", parent) == pytest.approx(5.0)
    assert _read("servicegraphs_fused_pct.write", parent) is None


def test_paged_tenants_read_as_no_fused_emit():
    """Every emit through the families (a paged deployment): 0 %, a
    number, since the counter is there."""
    only_family = [f'{EMITS}{{path="family"}} {k}\n' for k in (4, 9)]
    assert _read("servicegraphs_fused_pct.write", _pair(*only_family)) == 0.0
