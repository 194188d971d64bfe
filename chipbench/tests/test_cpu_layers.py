"""The per-layer metrics that read a span's CPU clock (PR 38): each layer
file agrees with its `per_layer` entry, reads the expected number from a
/metrics pair that holds `tempo_span_cpu_seconds` (rows for the roots of
a thread's tree alone, as the program renders it: a span under a
same-thread parent reads no CPU clock) and `process_cpu_seconds_total`,
and reads nothing (None, never 0) from the parent's, which has the wall
families and the spans-received counter and neither of the two. Every
CPU metric reads BOTH `collect` values. Each `.tenants` / `.mesh4` file
is its `.write` twin but for the name (and, for
`sched_dispatch_cpu_ms.tenants`, the end-to-end metric it moves, as
`sched_enqueue_ms.tenants` has it).
The manifest only grew: entries appended to `per_layer`, each with its
`workloads`. Not in tier-1:

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

PARENT = "ac5f54644e83535b1e1b172563e127be42a33306"
CELLS = {"write": "k6-write.steady", "tenants": "tenants-zipf.steady",
         "mesh4": "k6-write-mesh4.steady"}
SPANS_IN = "tempo_metrics_generator_spans_received_total"
PROCESS = "process_cpu_seconds_total"

# span -> per close, clear | met: (count a round, duration, CPU); CPU None
# for a span under a same-thread parent: no row in the CPU family
SPANS = {
    "api.push": ((100, 0.040, 0.010), (20, 0.100, 0.016)),
    "distributor.PushSpans": ((100, 0.011, None), (20, 0.020, None)),
    "sched.dispatch": ((90, 0.004, 0.002), (10, 0.014, 0.003)),
    "ingester.cut": ((1, 0.7, 0.6), (3, 3.3, 0.8)),
    "generator.collect": ((0, 0.0, 0.0), (2, 1.2, 0.9)),
    "generator.tick": ((0, 0.0, 0.0), (2, 5.6, 2.0)),
    "remote_write.encode": ((0, 0.0, None), (2, 0.83, None)),
}
SPANS_A_ROUND = 120_000
PROCESS_A_ROUND = 12.0         # CPU seconds the process burns a round


def _root_cpu_a_round() -> float:
    return sum(n * cpu for rows in SPANS.values()
               for n, _, cpu in rows if cpu is not None)


# name stem -> what two rounds of the canned exposition below read
WANT = {
    "host_cpu_us_per_span": 1e6 * PROCESS_A_ROUND / SPANS_A_ROUND,
    "push_cpu_ms": 1000 * (100 * 0.010 + 20 * 0.016) / 120,
    "ingester_cut_cpu_ms": 1000 * (0.6 + 3 * 0.8) / 4,
    "generator_tick_cpu_s": 2.0,
    "sched_dispatch_cpu_ms": 1000 * (90 * 0.002 + 10 * 0.003) / 100,
    "span_cpu_coverage_pct": 100.0 * _root_cpu_a_round() / PROCESS_A_ROUND,
}
NAMES = {
    "write": ["host_cpu_us_per_span", "push_cpu_ms", "ingester_cut_cpu_ms",
              "generator_tick_cpu_s", "sched_dispatch_cpu_ms",
              "span_cpu_coverage_pct"],
    "tenants": ["host_cpu_us_per_span", "push_cpu_ms",
                "sched_dispatch_cpu_ms"],
    "mesh4": ["host_cpu_us_per_span", "sched_dispatch_cpu_ms"],
}
ALL = [f"{stem}.{cell}" for cell, stems in NAMES.items() for stem in stems]
FROM_A_COUNTER = {"host_cpu_us_per_span", "span_cpu_coverage_pct"}
MOVES = {"push_cpu_ms": "push_p50_ms"}
MOVES_IN_A_CELL = {"sched_dispatch_cpu_ms.tenants": "push_p50_ms"}


def _exposition(n: int, cpu: bool = True) -> str:
    """`/metrics` after `n` rounds; `cpu` False: the parent's, with the
    wall families and without this PR's two."""
    out = [f'{SPANS_IN}{{tenant="t-1"}} {SPANS_A_ROUND * n / 2}',
           f'{SPANS_IN}{{tenant="t-2"}} {SPANS_A_ROUND * n / 2}']
    for span, rows in SPANS.items():
        for collect, (count, dur, cpu_s) in zip(("clear", "met"), rows):
            if not count:
                continue
            labels = f'{{span="{span}",collect="{collect}"}}'
            fams = [("tempo_span_duration_seconds", dur),
                    ("tempo_span_self_seconds", dur / 2)]
            if cpu and cpu_s is not None:
                fams.append(("tempo_span_cpu_seconds", cpu_s))
            for fam, each in fams:
                out.append(f'{fam}_bucket{{span="{span}",collect="{collect}"'
                           f',le="+Inf"}} {count * n}')
                out.append(f"{fam}_count{labels} {count * n}")
                out.append(f"{fam}_sum{labels} {count * n * each}")
    if cpu:
        out.append(f"{PROCESS} {30.0 + PROCESS_A_ROUND * n}")
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


@pytest.mark.parametrize("name", ALL)
def test_layer_reads_a_number_or_nothing(name):
    stem = name.rsplit(".", 1)[0]
    assert _read(name, _pair(_exposition(1), _exposition(3))) \
        == pytest.approx(WANT[stem])
    # the parent: spans, wall clocks and the spans-received counter, no
    # CPU clock anywhere. Left out, never 0
    assert _read(name, _pair(_exposition(1, cpu=False),
                             _exposition(3, cpu=False))) is None
    # a /metrics with nothing on it, and a window in which nothing closed
    assert _read(name, _pair("", "")) is None
    assert _read(name, _pair(_exposition(3), _exposition(3))) is None


@pytest.mark.parametrize("name", ALL)
def test_layer_file_agrees_with_the_manifest(name):
    stem, cell = name.rsplit(".", 1)
    entries = _entries()
    layer, entry = _layer(name), entries[name]
    assert set(layer) == {"name", "layer", "unit", "moves", "reader"}
    assert layer["name"] == name
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        layer["layer"], layer["unit"], layer["moves"])
    assert entry["workloads"] == [CELLS[cell]]
    assert entry["moves"] == MOVES_IN_A_CELL.get(
        name, MOVES.get(stem, "ingest_spans_per_s"))
    assert entry["better"] == ("higher" if stem == "span_cpu_coverage_pct"
                               else "lower")
    counter = stem in FROM_A_COUNTER
    assert entry["source"] == ("program_counter" if counter
                               else "program_span")
    assert layer["reader"]["kind"] == ("present_counter_ratio" if counter
                                       else "hist_mean")
    # a layer BENCHMARK.json already named, letter for letter
    assert any(m["layer"] == entry["layer"] and m["name"] not in ALL
               for m in entries.values())
    # both `collect` values: CPU does not swell behind a collect
    assert "collect" not in layer["reader"].get("labels", {})
    # only a thread's root span has rows in the CPU family
    if not counter:
        assert layer["reader"]["family"] == "tempo_span_cpu_seconds"
        assert SPANS[layer["reader"]["labels"]["span"]][1][2] is not None
    # every cell that lists it reports the end-to-end metric it moves
    assert entry["moves"] in {"ingest_spans_per_s", "push_p50_ms"}


@pytest.mark.parametrize("name", [n for n in ALL
                                  if not n.endswith(".write")])
def test_a_twin_is_its_write_file_but_for_the_name(name):
    stem = name.rsplit(".", 1)[0]
    twin, mine = _layer(stem + ".write"), _layer(name)
    differ = {k for k in twin if twin[k] != mine[k]}
    assert differ == ({"name", "moves"} if name in MOVES_IN_A_CELL
                      else {"name"})


def test_the_manifest_only_grew():
    """`BENCHMARK.json` differs from the parent's only by entries
    appended to `per_layer`, each with its `workloads`."""
    try:
        old = json.loads(subprocess.run(
            ["git", "-C", REPO, "show", PARENT + ":BENCHMARK.json"],
            capture_output=True, text=True, check=True).stdout)
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here")
    new = _bench()
    assert {k: v for k, v in new.items() if k != "per_layer"} \
        == {k: v for k, v in old.items() if k != "per_layer"}
    assert new["per_layer"][:len(old["per_layer"])] == old["per_layer"]
    added = new["per_layer"][len(old["per_layer"]):len(old["per_layer"])
                             + len(ALL)]
    assert [m["name"] for m in added] == ALL
    assert all(set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"} for m in added)


def test_no_wal_twin():
    """`tests/test_wal_cell.py` holds that cell's list to PR 35's 24."""
    assert not [n for n, m in _entries().items()
                if "k6-write-wal.steady" in m.get("workloads", [])
                and "_cpu_" in n]
