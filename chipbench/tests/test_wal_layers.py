"""The per-layer metrics of `k6-write-wal.steady` (PR 35): the nine the
issue named and fifteen more twins of `k6-write.steady`'s host metrics
(the review's: with the log on, every host layer of the twin cell runs
and has to be placed). Each layer file agrees with its `per_layer`
entry, reads a number from a /metrics pair that holds its family and
nothing (None, never 0) from one that lacks it. The parent commit has
the log, its four counter families and the span `wal.append`; it lacks
only the span `wal.sync`, so there `wal_sync_ms.wal` reads nothing and
every other metric reads. The manifest only grew: one configuration, one
cell, entries that list that cell alone. Not in tier-1:

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

CELL = "k6-write-wal.steady"
CONFIG = "k6-single-binary-wal"
PARENT = "8aac75d93fc57461ffc7e35d31489353eca7b1d5"
WAL = "generator (ingest WAL)"

# name -> (what two rounds of the canned exposition below read, the
#          end-to-end metric it moves, its layer)
WANT = {
    # 50 more appends: 40 clear of 4 ms and 10 met of 9 ms
    "wal_append_ms.wal": (5.0, "push_p50_ms", WAL),
    # 50 more waits: 40 clear of 1.5 ms and 10 met of 4 ms
    "wal_sync_ms.wal": (2.0, "push_p50_ms", WAL),
    # 50 more appends under 40 more fsyncs: a fifth of them shared one
    "wal_pushes_per_fsync.wal": (1.25, "ingest_spans_per_s", WAL),
    # 6,850,000 more bytes for 50,000 more spans
    "wal_bytes_per_span.wal": (137.0, "ingest_spans_per_s", WAL),
    # clear rows alone: 40 more pushes of 70 ms
    "push_clear_ms.wal": (70.0, "push_p50_ms", None),
    # 4 more sweeps of 0.9 s
    "ingester_cut_ms.wal": (900.0, "ingest_spans_per_s", None),
    # 2 more one-tenant collects of 1.1 s
    "collect_tick_s.wal": (1.1, "ingest_spans_per_s", None),
}
FROM_THE_TRACE = {"device_idle_pct.wal", "fused_update_roofline_pct.wal"}
# read as their `.write` twins are, through `hist_mean`, from spans and
# families the parent has
TWINS = {n + ".wal" for n in (
    "distributor_push_ms", "ingester_push_ms", "generator_resolve_ms",
    "sched_enqueue_ms", "sched_h2d_ms", "api_push_self_ms", "decode_stage_ms",
    "localblocks_push_ms", "servicegraphs_push_ms", "generator_tick_s",
    "cut_lock_held_ms", "collect_gather_s", "collect_format_s",
    "collect_encode_s", "collect_send_s")}
ALL = set(WANT) | FROM_THE_TRACE | TWINS
NEW_AT_THIS_PR = {"wal_sync_ms.wal"}


def _exposition(n: int, sync: bool = True) -> str:
    """`/metrics` after `n` rounds of 25 pushes of 1,000 spans: 20 clear
    and 5 met, each with one `wal.append` and, with `sync`, one `wal.sync`
    inside it; 20 fsyncs; 2 sweeps; one collect."""
    out = []
    spans = [("wal.append", (("clear", 20, 0.004), ("met", 5, 0.009))),
             ("api.push", (("clear", 20, 0.07), ("met", 5, 0.4)))]
    if sync:
        spans.append(("wal.sync", (("clear", 20, 0.0015), ("met", 5, 0.004))))
    for span, rows in spans:
        for collect, count, dur in rows:
            labels = f'{{span="{span}",collect="{collect}"}}'
            for fam in ("tempo_span_duration_seconds",
                        "tempo_span_self_seconds"):
                out.append(f"{fam}_count{labels} {count * n}")
                out.append(f"{fam}_sum{labels} {count * n * dur}")
    out += [f"tempo_wal_appended_batches_total {25 * n}",
            f"tempo_wal_appended_bytes_total {25 * n * 137_000}",
            f"tempo_wal_fsyncs_total {20 * n}",
            "tempo_wal_truncated_segments_total 0",
            f'tempo_metrics_generator_spans_received_total{{tenant="k6-a"}} '
            f"{13_000 * n}",
            f'tempo_metrics_generator_spans_received_total{{tenant="k6-b"}} '
            f"{12_000 * n}",
            f"tempo_ingester_cut_duration_seconds_count {2 * n}",
            f"tempo_ingester_cut_duration_seconds_sum {1.8 * n}",
            f"tempo_metrics_generator_collect_duration_seconds_count {n}",
            f"tempo_metrics_generator_collect_duration_seconds_sum {1.1 * n}"]
    return "\n".join(out) + "\n"


def _pair(a: str, b: str) -> dict:
    return {"m0": lib.parse_exposition(a), "m1": lib.parse_exposition(b)}


def _bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _layer(name: str) -> dict:
    with open(os.path.join(REPO, "chipbench", "layers", name + ".json")) as f:
        return json.load(f)


def _read(name: str, obs: dict):
    reader = _layer(name)["reader"]
    return importlib.import_module(
        "chipbench.readers." + reader["kind"]).read(reader, obs)


def test_the_cell_lists_its_metrics_and_no_other_cell_lists_them():
    mine = {m["name"] for m in _bench()["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine == ALL and len(ALL) == 24
    assert all(m["workloads"] == [CELL] for m in _bench()["per_layer"]
               if m["name"] in mine)


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
        CONFIG]
    added = new["workloads"][len(old["workloads"]):]
    assert [(w["name"], w["config"], w["chips"]) for w in added] == [
        (CELL, CONFIG, 1)]
    added = new["per_layer"][len(old["per_layer"]):]
    assert {m["name"] for m in added} == ALL
    assert all(m["workloads"] == [CELL] for m in added)
    # new files only: nothing the parent's benchmark had is edited
    changed = subprocess.run(
        ["git", "-C", REPO, "diff", "--name-status", PARENT, "--",
         "chipbench"], capture_output=True, text=True, check=True).stdout
    assert all(line.startswith("A") for line in changed.splitlines()), changed


@pytest.mark.parametrize("name", sorted(ALL))
def test_layer_file_agrees_with_the_manifest(name):
    entries = {m["name"]: m for m in _bench()["per_layer"]}
    layer, entry = _layer(name), entries[name]
    assert layer["name"] == name
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        layer["layer"], layer["unit"], layer["moves"])
    twin = name.replace(".wal", ".write")
    assert (twin in entries) == (name not in WANT or WANT[name][2] is None)
    if twin in entries:
        # read as its `.write` twin is, letter for letter
        assert layer["reader"] == _layer(twin)["reader"]
        assert {k: entry[k] for k in entry if k not in ("name", "workloads")} \
            == {k: entries[twin][k] for k in entries[twin]
                if k not in ("name", "workloads")}
    else:
        assert (entry["moves"], entry["layer"]) == WANT[name][1:]
        assert entry["source"] == ("program_span" if layer["reader"][
            "kind"] == "hist_mean" else "program_counter")


@pytest.mark.parametrize("name", sorted(WANT))
def test_layer_reads_a_number_or_nothing(name):
    assert _read(name, _pair(_exposition(1), _exposition(3))) \
        == pytest.approx(WANT[name][0])
    # a /metrics with none of the families: nothing read, nothing raised
    assert _read(name, test_yardstick.obs()) is None
    # nothing pushed inside the window
    assert _read(name, _pair(_exposition(3), _exposition(3))) is None


@pytest.mark.parametrize("name", sorted(TWINS))
def test_a_twin_reads_its_family_or_nothing(name):
    """A mean over the window of the family and labels its file names:
    3 observations of 0.25 before, 8 more of 0.5 inside."""
    reader = _layer(name)["reader"]
    assert reader["kind"] == "hist_mean"
    labels = ",".join(f'{k}="{v}"' for k, v in dict(
        {"collect": "clear"}, **reader.get("labels", {})).items())
    other = labels.replace('="', '="not-')

    def at(count: int, total: float) -> str:
        return "".join(f"{reader['family']}_{what}{{{ls}}} {v}\n"
                       for ls, scale in ((labels, 1), (other, 7))
                       for what, v in (("count", count * scale),
                                       ("sum", total * scale)))

    before, after = at(3, 0.75), at(11, 4.75)
    assert _read(name, _pair(before, after)) == pytest.approx(
        0.5 * reader.get("scale", 1.0))
    assert _read(name, _pair(after, after)) is None
    assert _read(name, test_yardstick.obs()) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_parent_lacks_wal_sync_and_nothing_else(name):
    parent = _pair(_exposition(1, sync=False), _exposition(3, sync=False))
    if name in NEW_AT_THIS_PR:
        assert _read(name, parent) is None         # left out, never 0
    else:
        assert _read(name, parent) == pytest.approx(WANT[name][0])


def test_a_deployment_without_the_log_reads_nothing_of_it():
    """`wal.enabled: false` (the three other cells): the counter families
    are on `/metrics` at 0 and never grow, the spans never open."""
    off = ["tempo_wal_appended_batches_total 0\n"
           "tempo_wal_appended_bytes_total 0\ntempo_wal_fsyncs_total 0\n"
           f"tempo_metrics_generator_spans_received_total {k}\n"
           for k in (1000, 9000)]
    assert _read("wal_pushes_per_fsync.wal", _pair(*off)) is None
    assert _read("wal_append_ms.wal", _pair(*off)) is None
    # bytes over spans is 0 there: a number, since both families are there
    assert _read("wal_bytes_per_span.wal", _pair(*off)) == 0.0


def test_device_metrics_read_the_trace_as_their_twins_do():
    trace = {"chips": 1, "busy_s": 0.48,
             "modules": {"jit__fused_update_packed4_impl": [340, 0.38],
                         "jit__edge_update_impl": [345, 0.1]}}
    o = test_yardstick.obs(trace=trace, trace_window_s=8.0,
                           least_bytes={"fused_update": 819e9 * 0.38e-4},
                           peaks={"hbm_bytes_per_s": 819e9})
    assert _read("device_idle_pct.wal", o) == pytest.approx(94.0)
    assert _read("fused_update_roofline_pct.wal", o) == pytest.approx(0.01)
    assert _read("device_idle_pct.wal", test_yardstick.obs(trace=None)) is None
