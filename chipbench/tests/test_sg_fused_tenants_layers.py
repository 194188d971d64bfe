"""`servicegraphs_fused_pct.tenants`: the share of the service-graph emits
of `tenants-zipf.steady` that took one jitted, donating step a push, here
the paged one, against the families' own calls. It is
`servicegraphs_fused_pct.write`'s twin letter for letter but for its name
and cell, reads 100 over a window of `fused` emits and 0 over `family`
ones, and nothing (None, never 0) where no emit grew the counter. Not in
tier-1:

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

NAME, TWIN = "servicegraphs_fused_pct.tenants", "servicegraphs_fused_pct.write"
CELL = "tenants-zipf.steady"
EMITS = "tempo_metrics_generator_servicegraphs_emits_total"


def _layer(name: str) -> dict:
    with open(os.path.join(REPO, "chipbench", "layers", name + ".json")) as f:
        return json.load(f)


def _exposition(fused: int | None, family: int | None) -> str:
    """`/metrics` with the emits counter by path (a path no emit took has
    no row) beside a span family."""
    out = ['tempo_span_self_seconds_count{span="servicegraphs.push",'
           'collect="clear"} 40',
           f"# TYPE {EMITS} counter"]
    for path, n in (("fused", fused), ("family", family)):
        if n is not None:
            out.append(f'{EMITS}{{path="{path}"}} {n}')
    return "\n".join(out) + "\n"


def _pair(a: str, b: str) -> dict:
    return {"m0": lib.parse_exposition(a), "m1": lib.parse_exposition(b)}


def _read(obs: dict):
    reader = _layer(NAME)["reader"]
    return importlib.import_module(
        "chipbench.readers." + reader["kind"]).read(reader, obs)


def test_layer_file_is_the_write_twin_and_agrees_with_the_manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    layer, twin = _layer(NAME), _layer(TWIN)
    assert layer["name"] == NAME
    assert {k: v for k, v in layer.items() if k != "name"} == \
        {k: v for k, v in twin.items() if k != "name"}
    entry = entries[NAME]
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        layer["layer"], layer["unit"], layer["moves"])
    assert {k: v for k, v in entry.items() if k not in ("name", "workloads")} \
        == {k: v for k, v in entries[TWIN].items()
            if k not in ("name", "workloads")}
    assert entry["workloads"] == [CELL]
    assert CELL in {w["name"] for w in bench["workloads"]}


def test_fused_emits_read_100():
    assert _read(_pair(_exposition(1_000, None), _exposition(3_400, None))) \
        == pytest.approx(100.0)


def test_family_emits_read_0():
    """The parent's paged cell: every emit through the families."""
    assert _read(_pair(_exposition(None, 1_000), _exposition(None, 3_400))) \
        == pytest.approx(0.0)


@pytest.mark.parametrize("obs", [
    test_yardstick.obs(),                                    # no family
    _pair(_exposition(5_000, None), _exposition(5_000, None)),  # no emit
], ids=["absent", "flat"])
def test_nothing_to_read_reads_none(obs):
    assert _read(obs) is None
