"""The per-layer metric of the columnar collection tick (PR 29): the share
of TimeSeries a tick wrote whose label blocks were kept from an earlier
tick. The layer file reads a number from a /metrics pair that holds
`tempo_remote_write_series_encoded_total` and nothing (None, never 0) from
one that lacks it: the parent commit has no such family. Not in tier-1:

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

NAME = "collect_label_reuse_pct.write"
ENCODED = "tempo_remote_write_series_encoded_total"


def _exposition(kept: int, built: int, family: bool = True) -> str:
    """`/metrics` with the sends of the ticks so far and, with `family`,
    the series they wrote by where their label blocks came from."""
    out = ["# TYPE tempo_remote_write_sends_total counter",
           f"tempo_remote_write_sends_total {(kept + built) // 1000}"]
    if family:
        out += [f"# TYPE {ENCODED} counter",
                f'{ENCODED}{{labels="kept"}} {kept}',
                f'{ENCODED}{{labels="built"}} {built}']
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
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    layer, entry = _layer(), entries[NAME]
    assert layer["name"] == NAME
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        layer["layer"], layer["unit"], layer["moves"])
    assert entry["moves"] == "ingest_spans_per_s"
    assert entry["workloads"] == ["k6-write.steady"]
    assert (entry["source"], entry["better"]) == ("program_counter", "higher")
    # the layer of the other collect metrics, letter for letter, and the
    # newest entry of the list: nothing before it moved
    assert entry["layer"] == entries["collect_encode_s.write"]["layer"]
    assert manifest["per_layer"][-1]["name"] == NAME


@pytest.mark.parametrize("before,after,want", [
    # the set-up tick built all 352,665 series of a tenant; the window's
    # four ticks kept every one
    ((0, 352_665), (4 * 352_665, 352_665), 100.0),
    # 1,000 new series met in a window of 9,000 kept
    ((5_000, 5_000), (14_000, 6_000), 90.0),
    # the external labels changed: every block is built again
    ((7_000, 1_000), (7_000, 9_000), 0.0),
], ids=["all-kept", "some-new", "all-built"])
def test_layer_reads_the_kept_share_of_the_windows_series(before, after, want):
    assert _read(_pair(_exposition(*before), _exposition(*after))) \
        == pytest.approx(want)


def test_layer_reads_nothing_where_there_is_nothing_to_read():
    # the parent commit's /metrics: remote write is there, the family not
    parent = _pair(_exposition(0, 1000, family=False),
                   _exposition(4000, 1000, family=False))
    assert _read(parent) is None
    # a /metrics with neither: nothing to read, nothing raised
    assert _read(test_yardstick.obs()) is None
    # no tick ended inside the window
    assert _read(_pair(_exposition(5, 5), _exposition(5, 5))) is None
