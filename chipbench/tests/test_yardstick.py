"""Checks of the yardstick itself. Not in tier-1; run by hand:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import costs, lib, reference, spans, xplane  # noqa: E402
from chipbench.readers import (counter_ratio, hist_mean, xplane_idle,  # noqa: E402
                               xplane_roofline)


# -- the xplane reducer on a recorded trace ---------------------------------

def test_reducer_on_recorded_trace():
    """5 s of the search cell on a v5e (PR 23's leftover, 118 calls of the
    plane's mask kernel): the numbers are fixed by the file."""
    red = xplane.reduce_trace(os.path.join(
        HERE, "data", "search-5s-v5e.xplane.pb"))
    assert red["chips"] == 1
    assert red["busy_s"] == pytest.approx(0.019935911, abs=1e-9)
    assert red["extent_s"] == pytest.approx(4.77546869, abs=1e-6)
    assert red["modules"] == {"jit_fn": [118, pytest.approx(0.019969318)]}
    assert xplane.module_seconds(red, "jit_f") == (
        118, pytest.approx(0.019969318))
    assert xplane.module_seconds(red, "jit_build") == (0, 0)
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) == 10
    assert red["device_ops"][0][1] == pytest.approx(0.00736925)
    # the longest idle gap, by what the host was in
    assert red["idle_gaps"][0] == ["PjitFunction(fn)",
                                   pytest.approx(0.33983796)]
    # busy can never exceed the time the modules ran
    assert red["busy_s"] <= sum(s for _, s in red["modules"].values())


def test_union_and_gaps():
    iv = [(0, 10), (5, 12), (20, 30), (21, 22)]
    assert xplane.union_seconds(iv) == 22
    assert xplane.union_seconds([]) == 0
    assert xplane.gaps(iv, 0, 40) == [(12, 20), (30, 40)]
    assert xplane.gaps([], 3, 4) == [(3, 4)]


# -- percentiles ------------------------------------------------------------

def test_percentile_matches_numpy_and_wants_ten_beyond():
    v = list(np.random.default_rng(0).random(200))
    for q in (50, 90, 95):
        assert lib.percentile(v, q) == pytest.approx(np.percentile(v, q))
    # p95 of 199 samples has 9.95 beyond it: refused; 200 has 10
    with pytest.raises(lib.BenchFailure):
        lib.percentile(v[:199], 95)
    # p90 needs 100 samples, a median none beyond it
    lib.percentile(v[:100], 90)
    with pytest.raises(lib.BenchFailure):
        lib.percentile(v[:99], 90)
    assert lib.percentile([3.0], 50) == 3.0
    with pytest.raises(lib.BenchFailure):
        lib.percentile([], 50)


# -- the declarative readers on a canned /metrics pair ----------------------

M0 = """# HELP x a histogram
x_seconds_count{op="search"} 10
x_seconds_sum{op="search"} 1.0
x_seconds_count{op="metrics"} 4
x_seconds_sum{op="metrics"} 4.0
a_total{class="ingest"} 100
b_total 50
put_bytes_total{site="plane"} 1000
"""
M1 = """x_seconds_count{op="search"} 30
x_seconds_sum{op="search"} 5.0
x_seconds_count{op="metrics"} 4
x_seconds_sum{op="metrics"} 4.0
a_total{class="ingest"} 160
b_total 100
put_bytes_total{site="plane"} 5000
put_bytes_total{site="new"} 1000
"""


def obs(**kv):
    return dict({"m0": lib.parse_exposition(M0),
                 "m1": lib.parse_exposition(M1)}, **kv)


def test_hist_mean():
    assert hist_mean.read({"family": "x_seconds", "scale": 1000.0},
                          obs()) == pytest.approx(200.0)
    assert hist_mean.read({"family": "x_seconds", "labels": {"op": "metrics"}},
                          obs()) is None          # nothing observed
    assert hist_mean.read({"family": "nope"}, obs()) is None


def test_counter_ratio():
    assert counter_ratio.read({"num": ["a_total"], "den": ["b_total"]},
                              obs()) == pytest.approx(1.2)
    assert counter_ratio.read(
        {"num": ["a_total"], "den": ["a_total", "b_total"], "scale": 100.0},
        obs()) == pytest.approx(100 * 60 / 110)
    assert counter_ratio.read({"num": ["put_bytes_total"], "den": "requests"},
                              obs(requests=50)) == pytest.approx(100.0)
    assert counter_ratio.read({"num": ["a_total"], "den": ["nope"]},
                              obs()) is None


def test_xplane_readers():
    trace = {"chips": 1, "busy_s": 0.5, "modules": {"jit_build": [10, 0.4],
                                                    "jit_fn": [3, 0.1]}}
    o = obs(trace=trace, trace_window_s=2.0,
            least_bytes={"plane_grid": 819e9 * 0.002},
            peaks={"hbm_bytes_per_s": 819e9})
    assert xplane_idle.read({}, o) == pytest.approx(75.0)
    assert xplane_roofline.read({"module": "jit_build", "bytes": "plane_grid"},
                                o) == pytest.approx(0.5)
    assert xplane_roofline.read({"module": "jit_nope", "bytes": "plane_grid"},
                                o) is None
    assert xplane_idle.read({}, obs(trace={"chips": 0})) is None
    assert xplane_idle.read({}, obs(trace=None)) is None


def test_costs_count_real_rows_only():
    assert costs.fused_update_bytes(1000) == 72_000
    assert costs.plane_grid_bytes(10**6, 32, 120, False) == 8_015_360
    assert costs.plane_grid_bytes(10**6, 32, 120, True) == 12_983_040


# -- the reference's integer binning ----------------------------------------

def test_step_binning_on_step_edges():
    """Spans exactly on step edges, at an epoch where a float64 second
    has a 238 ns ulp: the span AT an edge belongs to the step it opens,
    the one a nanosecond before to the step it closes."""
    t0_s = 1_790_466_484
    spec = {"blocks": 1, "block_seconds": 120, "services": 2,
            "trace_len": 1, "spans_per_block": 6}
    edge = (t0_s + 60) * 10**9
    start = np.array([t0_s * 10**9, edge - 1, edge, edge + 1,
                      (t0_s + 120) * 10**9 - 1, edge], np.int64)
    cols = {"start_ns": start, "svc": np.array([0, 0, 0, 0, 0, 1]),
            "dur_ns": np.array([1, 2, 3, 4, 1024, 1025], np.int64)}
    data = reference.BlockData(cols, spec, t0_s)
    counts = data.step_hist(t0_s, t0_s + 120, 60).sum(axis=2)
    assert counts.tolist() == [[2, 3], [0, 1]]
    # a window that starts before the data and ends inside it
    counts = data.step_hist(t0_s - 30, t0_s + 90, 30).sum(axis=2)
    assert counts.tolist() == [[0, 1, 1, 2], [0, 0, 0, 1]]
    # log2 buckets: bucket b holds 2^(b-1) < ns <= 2^b
    assert reference.log2_bucket(np.array([1, 2, 3, 4, 1024, 1025])).tolist() \
        == [0, 1, 2, 2, 10, 11]


def test_log2_quantile_is_upstreams_interpolation():
    from tempo_tpu.traceql.engine_metrics import log2_quantile

    rng = np.random.default_rng(1)
    hist = rng.integers(0, 50, (3, 4, reference.HBUCKETS))
    hist[0, 0] = 0
    got = reference.log2_quantile(0.99, hist)
    for i in range(3):
        for j in range(4):
            assert got[i, j] == pytest.approx(
                log2_quantile(0.99, hist[i, j].astype(float)), rel=1e-12)


def test_a_push_is_a_pure_function_of_its_arguments():
    shape = spans.PushShape(8, 125, 5)
    schema = {"services": 32, "names": 96, "vus": 16, "end_jitter_ns": 10**9}
    big = 2**31 + 7                      # the driver's seeds are large
    a = spans.draw_push(big, 1, 9, shape, schema, 1_790_000_000 * 10**9)
    b = spans.draw_push(big, 1, 9, shape, schema, 1_790_000_000 * 10**9)
    assert all(np.array_equal(a[k], b[k]) for k in a if k != "pairs")
    assert spans.encode_push(shape, a) == spans.encode_push(shape, b)
    from tempo_tpu.model.otlp import spans_from_otlp_proto
    got = list(spans_from_otlp_proto(spans.encode_push(shape, a)))
    assert len(got) == 1000
    assert {s["trace_id"] for s in got} == {bytes(t) for t in a["trace_id"]}


# -- the entry point --------------------------------------------------------

def test_run_refuses_to_print_a_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", "k6-write.steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout and '"metrics"' not in p.stdout
    assert "TPU" in p.stderr
