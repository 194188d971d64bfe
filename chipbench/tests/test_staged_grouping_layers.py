"""`staged_grouping_pct.write`: the share of the spans the live stores
took into chunks that came grouped by trace from the staging's native
pass, against the stores' own grouping. Its layer file agrees with its
`per_layer` entry and reads a number from a /metrics pair that holds the
chunk counter, and nothing (None, never 0) from one that lacks it: the
parent commit has no such counter. Not in tier-1:

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

NAME = "staged_grouping_pct.write"
CELL = "k6-write.steady"
CHUNKS = "tempo_ingester_chunk_spans_total"


def _exposition(staged: int, own: int | None = None) -> str:
    """`/metrics` with the chunk counter by grouping (no `own` row where
    no store grouped a push itself) beside a span family."""
    out = ['tempo_span_self_seconds_count{span="localblocks.push",'
           'collect="clear"} 40',
           f"# TYPE {CHUNKS} counter",
           f'{CHUNKS}{{grouping="staged"}} {staged}']
    if own is not None:
        out.append(f'{CHUNKS}{{grouping="own"}} {own}')
    return "\n".join(out) + "\n"


def _pair(a: str, b: str) -> dict:
    return {"m0": lib.parse_exposition(a), "m1": lib.parse_exposition(b)}


def _read(obs: dict):
    with open(os.path.join(REPO, "chipbench", "layers", NAME + ".json")) as f:
        reader = json.load(f)["reader"]
    return importlib.import_module(
        "chipbench.readers." + reader["kind"]).read(reader, obs)


def test_layer_file_agrees_with_the_manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    with open(os.path.join(REPO, "chipbench", "layers", NAME + ".json")) as f:
        layer = json.load(f)
    entry = entries[NAME]
    assert layer["name"] == NAME
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        layer["layer"], layer["unit"], layer["moves"])
    assert (entry["moves"], entry["workloads"], entry["source"],
            entry["better"]) == ("ingest_spans_per_s", [CELL],
                                 "program_counter", "higher")
    assert layer["reader"]["kind"] == "label_ratio"
    assert layer["reader"]["family"] == CHUNKS
    # a layer BENCHMARK.json already names, letter for letter
    assert sum(m["layer"] == entry["layer"] for m in entries.values()) > 2
    # appended, last
    assert bench["per_layer"][-1]["name"] == NAME


def test_chunks_of_the_shared_order_only_read_100():
    assert _read(_pair(_exposition(2_000), _exposition(9_002_000))) \
        == pytest.approx(100.0)


def test_a_share_the_stores_grouped_reads_below_100():
    assert _read(_pair(_exposition(2_000, 0), _exposition(6_002_000,
                                                          2_000_000))) \
        == pytest.approx(75.0)


@pytest.mark.parametrize("obs", [
    test_yardstick.obs(),                                  # no family at all
    _pair(_exposition(5_000), _exposition(5_000)),         # nothing pushed
], ids=["absent", "flat"])
def test_nothing_to_read_reads_none(obs):
    assert _read(obs) is None
