"""The per-layer metrics that read the program's spans (PR 26): each layer
file reads a number from a /metrics pair that holds the span families
and nothing from one that lacks them (the parent commit's), and a
recorded v5e trace with the program's annotations reduces to idle gaps
named by program spans. Not in tier-1:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

import glob
import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import lib, xplane  # noqa: E402
from chipbench.tests import test_yardstick  # noqa: E402

SPANS = ["api.push", "distributor.decode", "ingester.push",
         "generator.resolve", "sched.h2d", "sched.enqueue",
         "registry.gather", "registry.format", "remote_write.encode",
         "remote_write.send"]


def _exposition(n: int) -> str:
    """Both span families after `n` rounds: every span closed 10 n times
    clear (4 ms each, 1 ms of it self) and 2 n times met (2 s, 0.5 s)."""
    out = []
    for s in SPANS:
        for collect, count, dur, self_s in (("clear", 10, 0.004, 0.001),
                                            ("met", 2, 2.0, 0.5)):
            labels = f'{{span="{s}",collect="{collect}"}}'
            for fam, each in (("tempo_span_duration_seconds", dur),
                              ("tempo_span_self_seconds", self_s)):
                out.append(f"{fam}_count{labels} {count * n}")
                out.append(f"{fam}_sum{labels} {count * n * each}")
    return "\n".join(out) + "\n"


def _layers() -> list:
    out = []
    for path in sorted(glob.glob(os.path.join(REPO, "chipbench", "layers",
                                              "*.json"))):
        with open(path) as f:
            layer = json.load(f)
        if layer["reader"].get("family", "").startswith("tempo_span_"):
            out.append(layer)
    return out


WANT = {
    "api_push_self_ms.write": 1.0,
    "decode_stage_ms.write": 1.0,
    "ingester_push_ms.write": 1.0,
    "generator_resolve_ms.write": 1.0,
    "push_clear_ms.write": 4.0,
    # both collect values: (10 * 0.001 + 2 * 0.5) / 12
    "sched_h2d_ms.write": 1010.0 / 12,
    "sched_enqueue_ms.write": 1010.0 / 12,
    "collect_gather_s.write": 1.01 / 12,
    "collect_format_s.write": 1.01 / 12,
    "collect_encode_s.write": 1.01 / 12,
    "collect_send_s.write": 1.01 / 12,
    "push_met_collect_pct.write": 100.0 * 2 / 12,
}


def test_every_span_layer_is_in_the_manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert {layer["name"] for layer in _layers()} == set(WANT)
    for layer in _layers():
        e = entries[layer["name"]]
        assert (e["layer"], e["unit"], e["moves"]) == (
            layer["layer"], layer["unit"], layer["moves"])


@pytest.mark.parametrize("layer", _layers(), ids=lambda la: la["name"])
def test_span_layer_reads_a_number_or_nothing(layer):
    reader = importlib.import_module(
        "chipbench.readers." + layer["reader"]["kind"])
    with_spans = {"m0": lib.parse_exposition(_exposition(1)),
                  "m1": lib.parse_exposition(_exposition(3))}
    assert reader.read(layer["reader"], with_spans) == pytest.approx(
        WANT[layer["name"]])
    # the parent commit's /metrics has no such family: nothing to read,
    # and nothing raised
    assert reader.read(layer["reader"], test_yardstick.obs()) is None
    # nor when nothing closed inside the window
    same = {"m0": with_spans["m1"], "m1": with_spans["m1"]}
    assert reader.read(layer["reader"], same) is None


def test_idle_gaps_of_a_recorded_trace_name_program_spans():
    """1.2 s of the write cell on a v5e (PR 26, call 1, seconds 4.2-5.4 of
    the traced window, cut by dropping the events' stats): the program's
    annotations are on the host plane, so the gaps name layers where the
    parent's trace named `PjitFunction(...)`."""
    red = xplane.reduce_trace(os.path.join(
        HERE, "data", "write-1s-v5e-spans.xplane.pb"))
    assert red["chips"] == 1
    assert red["busy_s"] == pytest.approx(0.008584552, abs=1e-9)
    assert xplane.module_seconds(red, "jit__fused_update") == (
        6, pytest.approx(0.006758267))
    assert red["idle_gaps"][0] == ["api.push", pytest.approx(0.434698383)]
    assert len(red["idle_gaps"]) == 10
    assert all("." in name and "(" not in name
               for name, _ in red["idle_gaps"])
