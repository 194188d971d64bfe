"""The per-layer metric of the cut's route: `cut_columns_pct.write`, the
share of the spans a cut put into a WAL segment that it took from the
chunks of staged pushes. Its layer file agrees with its `per_layer`
entry, reads the share from a /metrics pair that holds
`tempo_ingester_cut_spans_total`, and reads nothing (None, never 0) from
one that lacks the family, as the parent commit's does. Not in tier-1:

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

NAME = "cut_columns_pct.write"
CUT = "tempo_ingester_cut_spans_total"
LIVE = "tempo_ingester_live_spans_total"


def _exposition(n: int, counter: bool = True) -> str:
    """`/metrics` after `n` rounds: the live-spans counter (the parent
    has it) and, with `counter`, 450,000 n spans cut from chunks and
    50,000 n through dicts."""
    out = [f"# TYPE {LIVE} counter",
           f'{LIVE}{{form="columns"}} {500_000 * n}']
    if counter:
        out += [f"# TYPE {CUT} counter",
                f'{CUT}{{route="columns"}} {450_000 * n}',
                f'{CUT}{{route="dicts"}} {50_000 * n}']
    return "\n".join(out) + "\n"


def _pair(a: str, b: str) -> dict:
    return {"m0": lib.parse_exposition(a), "m1": lib.parse_exposition(b)}


def _layer() -> dict:
    with open(os.path.join(REPO, "chipbench", "layers", NAME + ".json")) as f:
        return json.load(f)


def _read(obs: dict):
    reader = _layer()["reader"]
    return importlib.import_module(
        "chipbench.readers." + reader["kind"]).read(reader, obs)


def test_layer_file_agrees_with_the_manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    entries = {m["name"]: m for m in per_layer}
    layer, entry = _layer(), entries[NAME]
    assert set(layer) == {"name", "layer", "unit", "moves", "reader"}
    assert layer["name"] == NAME
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        layer["layer"], layer["unit"], layer["moves"])
    assert (entry["moves"], entry["better"], entry["source"]) == (
        "ingest_spans_per_s", "higher", "program_counter")
    assert entry["workloads"] == ["k6-write.steady"]
    # the layer BENCHMARK.json already names, letter for letter
    assert entry["layer"] == entries["live_columns_pct.write"]["layer"]
    assert layer["reader"] == {"kind": "label_ratio", "family": CUT,
                               "num_labels": {"route": "columns"},
                               "den_labels": {}, "scale": 100.0}
    # appended last
    assert per_layer[-1]["name"] == NAME


def test_reads_the_share_with_the_family():
    assert _read(_pair(_exposition(1), _exposition(3))) \
        == pytest.approx(90.0)


@pytest.mark.parametrize("obs", ["parent", "empty", "no_cut"])
def test_reads_nothing_without_the_family_or_a_cut(obs):
    """The parent's /metrics (no such family), an empty one, and a window
    in which no sweep cut anything: left out, never 0."""
    pair = {"parent": lambda: _pair(_exposition(1, counter=False),
                                    _exposition(3, counter=False)),
            "empty": test_yardstick.obs,
            "no_cut": lambda: _pair(_exposition(3), _exposition(3))}[obs]()
    assert _read(pair) is None


def test_a_cut_of_dict_routes_alone_reads_zero():
    """Every cut span through dicts (Jaeger, Zipkin, the gRPC plane): 0 %,
    a number, since the family is there."""
    only_dicts = [f'{CUT}{{route="dicts"}} {k}\n' for k in (400, 900)]
    assert _read(_pair(*only_dicts)) == 0.0
