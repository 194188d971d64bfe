"""The deployment `multitenant-zipf-256` at a small size, on the CPU: what
the chip cell `tenants-zipf.steady` rests on.

- 24 tenants under the configuration's law (toy numbers), served over
  HTTP on the PAGED layout, equal the numpy oracle the write cells are
  judged by AND the same pushes served by the DENSE layout, which is the
  layout both other cells run;
- the configuration and the traffic file say what ISSUE 33 lists: the
  law's sums, what is reduced, assumed and guaranteed, the Zipf sequence;
- the cell's judge refuses a run that fell back to the dense layout, in
  which the pool refused a page, in which a sampled tenant's series count
  is not its schema's, or in which a tenant did not receive what was
  acknowledged to it;
- the cell's control flow runs to its end here (`--rehearsal`).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

from chipbench import lib, spans
from chipbench.mixes import otlp_push_tenants as mix

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483659            # the driver's seeds are beyond 32 signed bits
EXACT_SUFFIXES = ("_total", "_count", "_bucket")    # integer-valued families
# the law at a size the CPU holds twice: rank 1 has 1,599 series, rank 2
# 831, ranks 3-4 111, the rest 63; 3,912 in all, 33 of the arena's 63 pages
LAW = {"head_ranks": 2, "names_numerator": 8}
# every push carries the time the test began, and the second App is
# served after the first: on a slow machine (41 s once, beside five other
# workers) the shipped 30 s of slack filtered its last pushes' spans
SMALL = {"schema_law": LAW,
         "tenant_limits": {"generator": {
             "max_active_series": 2048,
             "ingestion_time_range_slack_s": 600.0}}}
PUSH, HEAD_FIRST = (8, 25), (32, 25)     # as the cell's, a fifth the spans
SHAPES = {g: spans.PushShape(g, p, 5) for g, p in (PUSH, HEAD_FIRST)}
SERIES = ("service", "span_name", "span_kind", "status_code")


def _config() -> dict:
    with open(os.path.join(REPO, "chipbench", "configs",
                           "multitenant-zipf-256.json")) as f:
        return json.load(f)


def _traffic() -> dict:
    with open(os.path.join(REPO, "chipbench", "traffic",
                           "tenants-zipf.steady.json")) as f:
        return json.load(f)


# -- paged = oracle = dense, served ----------------------------------------

def _serve(config: dict, workdir: str, schemas: dict, prefill: list,
           zipf: list) -> dict:
    """Boot the configuration as the chip cell does (`lib.boot`); send
    `prefill` in order (it makes every series: two pushes that meet the
    SAME new series at once are a defect of the series table, PERF.md
    section 7) and `zipf` from three threads, both [(tenant index, push
    index, now_ns, groups)]; collect every tenant once. {tenant: (values,
    quantiles)}, what /metrics said and each tenant's layout."""
    os.makedirs(workdir)
    sink = lib.Sink()
    app, srv, port = lib.boot(config, workdir, sink.url)
    tenants, failed = config["tenants"], []
    lock = threading.Lock()

    def client(todo: list) -> None:
        while True:
            with lock:
                if not todo:
                    return
                ti, idx, now_ns, g = todo.pop(0)
            body = spans.encode_push(SHAPES[g], spans.draw_push(
                SEED, ti, idx, SHAPES[g], schemas[tenants[ti]], now_ns))
            status, _ = lib.http_call(port, "POST", "/v1/traces",
                                      tenants[ti], body)
            if status != 200:
                failed.append(status)

    try:
        client(list(prefill))
        todo = list(zipf)
        threads = [threading.Thread(target=client, args=(todo,))
                   for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        assert not failed
        out = {}
        for tenant in tenants:
            samples = lib.get_json(port, "/internal/generator/collect",
                                   tenant, ts_ms=1)["samples"]
            values = {(s["name"], tuple(map(tuple, s["labels"]))): s["value"]
                      for s in samples}
            quantiles = {q: {tuple(map(tuple, e["labels"])): e["value"]
                             for e in lib.get_json(
                                 port, "/internal/generator/quantile",
                                 tenant, q=q)["quantiles"]}
                         for q in (0.5, 0.99)}
            out[tenant] = (values, quantiles)
        out["metrics"] = lib.scrape(port)
        out["layout"] = {t: app.generator.instances[t].state_layout
                         for t in tenants}
        return out
    finally:
        # as `chip_smoke.abandon`: servers and loops stopped without the
        # shutdown flush, so the next App has the process
        srv.shutdown()
        srv.server_close()
        for part in (app, app.ingester, app.generator):
            part._stop.set()
        for t in app.generator._threads:
            t.join(timeout=60)
        app.sched.flush()
        app.db.shutdown()
        sink.srv.shutdown()
        sink.srv.server_close()


def _series(labels) -> str:
    d = dict(labels)
    return "|".join(d.get(k, "") for k in SERIES)


def _by_name(values: dict, name: str) -> dict:
    return {_series(ls): v for (n, ls), v in values.items() if n == name}


def test_served_paged_tenants_equal_the_oracle_and_the_dense_layout(tmp_path):
    config = lib.merged(_config(), SMALL)
    config["tenants"] = tenants = config["tenants"][:24]
    config["yaml_overrides"]["pages"]["arena_slots"] = 16384
    schemas = {t: mix.schema_of(config["schema_law"], i + 1)
               for i, t in enumerate(tenants)}
    now_ns = time.time_ns()
    # every tenant walked to its full table as the cell's prefill walks
    # it (a 32-service tenant's first push has 32 groups: every service is
    # called), then a seeded Zipf sequence
    plans = [mix.fill_plan(schemas[t], [HEAD_FIRST] if schemas[t][
        "services"] > PUSH[0] else [], PUSH) for t in tenants]
    prefill = [(ti, idx, now_ns, g) for ti, plan in enumerate(plans)
               for idx, (g, _) in enumerate(plan)]
    next_idx = [len(plan) for plan in plans]
    zipf = []
    for t, _ in mix.draw_jobs(SEED, 96, tenants, 1.0, SHAPES[8].n):
        ti = tenants.index(t)
        zipf.append((ti, next_idx[ti], now_ns + len(zipf), PUSH[0]))
        next_idx[ti] += 1
    pushes = prefill + zipf

    paged = _serve(config, str(tmp_path / "paged"), schemas, prefill, zipf)
    m = paged["metrics"]
    assert set(paged["layout"].values()) == {"paged"}
    assert lib.metric_sum(m, "tempo_pages_alloc_failures_total") == 0
    assert lib.metric_sum(m, "tempo_pages_total") > 0
    dense_config = lib.merged(config, {"yaml_overrides": {
        "pages": {"enabled": False}}})
    dense = _serve(dense_config, str(tmp_path / "dense"), schemas, prefill,
                   zipf)
    assert set(dense["layout"].values()) == {"dense"}

    for ti, tenant in enumerate(tenants):
        values, quantiles = paged[tenant]
        # the oracle: every acknowledged span counted once by every
        # processor, as the cell's judge holds the chip runs
        cols = [spans.draw_push(SEED, ti, idx, SHAPES[g], schemas[tenant],
                                now) for t, idx, now, g in pushes if t == ti]
        col = {k: np.concatenate([c[k] for c in cols])
               for k in ("svc", "name", "kind", "status", "dur_ns")}
        keys = np.array([
            f"svc-{s:04d}|op-{n:04d}|{spans.KIND_STRS[k]}|"
            f"{spans.STATUS_STRS[st]}" for s, n, k, st in zip(
                col["svc"], col["name"], col["kind"], col["status"])])
        want = dict(zip(*np.unique(keys, return_counts=True)))
        # one tenant's counts never reach another's series: the oracle
        # is over this tenant's pushes alone
        assert _by_name(values, "traces_spanmetrics_calls_total") == want
        assert _by_name(values, "traces_spanmetrics_latency_count") == want
        assert len(want) == mix.table_size(schemas[tenant])
        dur_s = (col["dur_ns"] / 1e9).astype(np.float32)
        lat_sum = sum(_by_name(values, "traces_spanmetrics_latency_sum")
                      .values())
        want_sum = float(dur_s.astype(np.float64).sum())
        # f32 accumulation of f32 durations into per-series sums, summed
        # over series: the k6 cells' bound (chip_smoke's; PR 22 measured
        # 4e-10 to 2e-4 on the chip)
        assert abs(lat_sum - want_sum) <= 1e-4 * want_sum
        edges = sum(v for (n, _), v in values.items()
                    if n == "traces_service_graph_request_total")
        assert edges == sum(c["pairs"] for c in cols)
        if ti < 3:
            # the sketch's 1% (DDSketch, gamma 1.02) at a neighbouring
            # rank: the quantile of a discrete sample sits between two
            # order statistics
            busiest = max(want, key=want.get)
            vals = np.sort(dur_s[keys == busiest].astype(np.float64))
            for q in (0.5, 0.99):
                got_q = {_series(ls): v
                         for ls, v in quantiles[q].items()}[busiest]
                k = int(np.ceil(q * len(vals))) - 1
                assert vals[max(k - 1, 0)] * 0.989 <= got_q \
                    <= vals[min(k + 1, len(vals) - 1)] * 1.011

        # the dense layout, which both other cells run: integer families
        # and quantiles exactly; float sums within 1e-6 (the coalescer
        # forms other batches under other timing, so a series' f32 sum
        # adds the same few terms in another order: a few ulp of 6e-8)
        values_d, quantiles_d = dense[tenant]
        assert values.keys() == values_d.keys() and len(values) > 100
        for key, a in values.items():
            b = values_d[key]
            if key[0].endswith(EXACT_SUFFIXES):
                assert a == b, (key, a, b)
            else:
                assert abs(a - b) <= 1e-6 * max(abs(a), abs(b)), (key, a, b)
        assert quantiles == quantiles_d


# -- the two files, held to the issue --------------------------------------

def test_the_law_sums_to_the_issues_series_and_pages():
    config = _config()
    tenants, law = config["tenants"], config["schema_law"]
    assert len(tenants) == len(set(tenants)) == 256
    sizes = [mix.table_size(mix.schema_of(law, i + 1))
             for i in range(len(tenants))]
    assert sizes[:4] == [18495, 9279, 6207, 4671]     # rank 1: the k6 tenant
    assert sizes[8] == 495 and set(sizes[96:]) == {63}
    assert sum(sizes) == config["series_total"] == 71376
    pages = config["yaml_overrides"]["pages"]
    per_role = sum(-(-n // pages["page_rows"]) for n in sizes)
    assert per_role == config["pages_per_plane_role"] == 458
    # the runbook's rule: active series at page granularity, x 2 for
    # churn, next power of two
    assert pages["arena_slots"] == 262144 \
        == 1 << (2 * per_role * pages["page_rows"] - 1).bit_length()
    assert pages["enabled"] and pages["page_rows"] == 256
    # every tenant's table fits the paged planes' capacity in whole pages
    assert max(sizes) <= 65536


def test_the_configuration_names_what_was_reduced_assumed_and_guaranteed():
    config = _config()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "multitenant-zipf-256")
    assert entry["file"] == "chipbench/configs/multitenant-zipf-256.json"
    assert entry["reduced"] == config["reduced"] == [
        "tenants", "arena_slots", "replicas"]
    assert len(entry["source"]) <= 200
    for word in ("multi-tenancy", "processors", "stress_test_write_path.js"):
        assert word in entry["source"], word
    assert config["yaml_overrides"]["multitenancy_enabled"] is True
    assert config["tenant_limits"]["generator"]["processors"] == [
        "span-metrics", "service-graphs"]
    with open(os.path.join(REPO, "chipbench", "configs",
                           "k6-single-binary.json")) as f:
        k6 = json.load(f)
    assert config["tenant_limits"]["ingestion"] == \
        k6["tenant_limits"]["ingestion"]
    assumed = " ".join(config["assumed"])
    for word in ("Zipf exponent 1.0", "names_i", "services_i", "limits",
                 "two processors", "arena_slots"):
        assert word in assumed, word
    assert config["guarantees"][:4] == k6["guarantees"]
    more = " ".join(config["guarantees"][4:])
    for word in ("paged layout", "refuses no allocation",
                 "another tenant's series", "dense layout's"):
        assert word in more, word
    cell = next(w for w in bench["workloads"]
                if w["name"] == "tenants-zipf.steady")
    assert cell == {"name": "tenants-zipf.steady",
                    "config": "multitenant-zipf-256",
                    "traffic": "tenants-zipf.steady", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "path=family" in cell["why"]
    # one four-chip cell of the benchmark's, and this one is not it
    assert [w["chips"] for w in bench["workloads"]][:3] == [1, 4, 1]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_zipf_sequence_is_a_pure_function_of_the_seed():
    traffic, tenants = _traffic(), _config()["tenants"]
    assert traffic["kind"] == "otlp_push_tenants" and traffic["clients"] == 4
    assert traffic["push"] == [8, 125, 5] and traffic["zipf_s"] == 1.0
    assert traffic["trace"] == {"start_frac": 0.25, "seconds": 8}
    assert set(traffic["report"]) == {"ingest_spans_per_s", "push_p50_ms"}
    assert traffic["sampled_head_ranks"] + traffic[
        "sampled_drawn_tenants"] == 16
    n = traffic["window_jobs"]
    assert n == 20000
    shares = mix.zipf_shares(256, 1.0)
    assert abs(shares[0] - 0.163) < 5e-4 and abs(shares[:8].sum() - 0.444) \
        < 5e-4 and abs(shares[96:].sum() - 0.160) < 5e-4
    for seed in (1, SEED, 3147483651):
        jobs = mix.draw_jobs(seed, n, tenants, 1.0, 1000)
        assert jobs == mix.draw_jobs(seed, n, tenants, 1.0, 1000)
        assert len(jobs) == n and {s for _, s in jobs} == {1000}
        assert abs(sum(t == tenants[0] for t, _ in jobs) / n - 0.163) < 0.01
    assert mix.draw_jobs(1, 64, tenants, 1.0, 1000) \
        != mix.draw_jobs(2, 64, tenants, 1.0, 1000)
    sampled = mix.sampled_tenants(SEED, tenants, 3, 13)
    assert sampled == mix.sampled_tenants(SEED, tenants, 3, 13)
    assert sampled[:3] == tenants[:3] and len(set(sampled)) == 16


def test_the_prefill_walks_every_table_and_races_on_no_new_series():
    config, traffic = _config(), _traffic()
    m = mix.Mix(types.SimpleNamespace(config=config, traffic=traffic))
    m.tenants = config["tenants"]
    m.schemas = {t: mix.schema_of(config["schema_law"], i + 1)
                 for i, t in enumerate(m.tenants)}
    m.shapes = {g * p: (g, p, t) for g, p, t in traffic["warm_shapes"]}
    m.next_idx = {t: 0 for t in m.tenants}
    m.next_idx[m.tenants[0]] = 3                 # the canaries went out
    jobs = m.prefill_jobs()
    assert 300 <= len(jobs) <= 400
    seen = {t: [set(), set()] for t in m.tenants}
    idx = {t: 0 for t in m.tenants}
    canaries = [(t, n) for n in m.shapes for t in m.tenants[:1]]
    last = {}
    for k, (t, n) in enumerate(canaries + jobs):
        series, edges = mix.push_series(m.schemas[t], idx[t],
                                        *m.shapes[n][:2])
        if k >= len(canaries) and t in last and k - last[t][0] < 8:
            # two pushes of one tenant that four clients may hold in
            # flight together name no series that is new to both
            assert not (series - seen[t][0]) & last[t][1], (t, idx[t])
        last[t] = (k, series - seen[t][0])
        seen[t][0] |= series
        seen[t][1] |= edges
        idx[t] += 1
    for t in m.tenants:
        assert len(seen[t][0]) == mix.table_size(m.schemas[t])
        # the window's pushes (1,000 spans, from the next index on) name
        # nothing new: no page is allocated inside the window
        for k in range(idx[t], idx[t] + 8):
            series, edges = mix.push_series(m.schemas[t], k,
                                            *traffic["push"][:2])
            assert series <= seen[t][0] and edges <= seen[t][1]


# -- what the judge refuses ------------------------------------------------

def _judged(fault: str) -> list:
    """The complaints of the judge's two checks over a canned run of two
    tenants (one push each), with one fault planted."""
    config, traffic = _config(), dict(_traffic(), sampled_head_ranks=0)
    tenants = config["tenants"][:2]
    now_ns = 1_700_000_000 * 10**9
    sent = [{"tenant": t, "n": 1000, "idx": 0, "now_ns": now_ns,
             "status": 200, "body": b""} for t in tenants]
    slack = types.SimpleNamespace(spans_filtered_slack=0)
    m = mix.Mix(types.SimpleNamespace(
        config=config, traffic=traffic, seed=SEED,
        sink=types.SimpleNamespace(bodies=[100]),
        app=types.SimpleNamespace(generator=types.SimpleNamespace(
            instances={t: slack for t in tenants}))))
    m.tenants, m.sent = tenants, sent
    m.schemas = {t: mix.schema_of(config["schema_law"], 200 + i)
                 for i, t in enumerate(tenants)}      # 63 series: one push
    m.built = {1000: spans.PushShape(8, 125, 5)}
    m.series_at_go = 2 * 63.0
    metrics = {("tempo_sched_ingest_keep_fraction", ()): 1.0,
               ("tempo_pages_total", (("role", "r"),)): 1023.0,
               ("tempo_pages_free", (("role", "r"),)): 1021.0,
               ("tempo_pages_alloc_failures_total", ()): 0.0,
               (mix.SERIES, ()): 2 * 63.0}
    for t in tenants:
        metrics[(mix.RECEIVED, (("tenant", t),))] = 1000.0
        metrics[(mix.STATE_BYTES, (("layout", "paged"), ("tenant", t)))] = 1e6
    got = {}
    for ti, t in enumerate(tenants):
        c = spans.draw_push(SEED, ti, 0, m.built[1000], m.schemas[t], now_ns)
        dur = ((c["end_ns"] - c["start_ns"]) / 1e9).astype(np.float32)
        got[t] = {"series": 63, "traces_spanmetrics_calls_total": 1000.0,
                  "traces_spanmetrics_latency_count": 1000.0,
                  "traces_spanmetrics_latency_sum":
                      float(dur.astype(np.float64).sum()),
                  "traces_service_graph_request_total": 8.0}
    a, b = tenants
    if fault == "dense":
        del metrics[(mix.STATE_BYTES, (("layout", "paged"), ("tenant", b)))]
        metrics[(mix.STATE_BYTES, (("layout", "dense"), ("tenant", b)))] = 9e7
    elif fault == "refused":
        metrics[("tempo_pages_alloc_failures_total", ())] = 1.0
    elif fault == "series":
        got[a]["series"] = 62
    elif fault == "received":
        metrics[(mix.RECEIVED, (("tenant", b),))] = 999.0
    complaints: list = []
    for t in tenants:
        m.oracle(t, got[t], {}, complaints)
    m.check_served(metrics, {}, complaints)
    return complaints


@pytest.mark.parametrize("fault, says", [
    ("none", None),
    ("dense", "1 tenants on the dense layout"),
    ("refused", "tempo_pages_alloc_failures_total = 1.0"),
    ("series", "62 active series, its schema's table has 63"),
    ("received", "spans_received_total 999 != 1000 acknowledged"),
])
def test_the_judge_refuses(fault, says):
    complaints = _judged(fault)
    if says is None:
        assert complaints == []
    else:
        assert len(complaints) == 1 and says in complaints[0], complaints


# -- the start rule --------------------------------------------------------

def test_the_window_opens_ten_seconds_after_a_whole_round(monkeypatch):
    """On a clock of its own: the round in flight when set-up ended is
    passed over (2 of 4 tenants), the next whole one is taken, and the
    window opens two thirds of the 15 s interval after that round's end,
    so that the one round inside the window begins 3-5 s into it."""
    clock, said = [100.0], []
    tenants = ["a", "b", "c", "d"]

    def count_at(t: float) -> float:
        # 2 collects left of the round in flight, a pause of 15 s, a whole
        # round of one tenant a second from t=118, ended at 122
        return 10 + min(max(int(t - 100), 0), 2) \
            + min(max(int(t - 118), 0), 4)

    def scrape(port):
        clock[0] += 0.3
        return {(mix.COLLECT + "_count", ()): count_at(clock[0]),
                (mix.SERIES, ()): 126.0}

    monkeypatch.setattr(mix, "scrape", scrape)
    monkeypatch.setattr(mix, "say", lambda **kw: said.append(kw))
    monkeypatch.setattr(mix, "time", types.SimpleNamespace(
        monotonic=lambda: clock[0],
        sleep=lambda s: clock.__setitem__(0, clock[0] + s)))
    registry = types.SimpleNamespace(collection_interval_s=15.0)
    m = mix.Mix(types.SimpleNamespace(
        port=0, traffic={"scrape_every_s": 1.0}, clock=lambda: clock[0],
        app=types.SimpleNamespace(cfg=types.SimpleNamespace(
            generator=types.SimpleNamespace(registry=registry)))))
    m.tenants, m.t_ready = tenants, 100.0
    m.wait_start()
    assert [d["phase"] for d in said] == ["round_passed_over", "round_ended"]
    assert (said[0]["collects"], said[0]["of"]) == (2, 4)
    assert m.series_at_go == 126.0
    # the round ended at 122; the scrape that saw it, up to 1.3 s later
    assert 132.0 <= clock[0] <= 133.5
    # the next round begins 15 s after that end: 3.5-5 s into the window
    assert 3.5 <= 137.0 - clock[0] <= 5.0


# -- the cell's control flow, here -----------------------------------------

def test_the_cell_rehearses_to_its_end_on_the_cpu():
    """`run.py --rehearsal`: 12 tenants, `arena_slots` 8,192, a 3 s
    collection interval (the `rehearsal` keys of both files); exits 1 and
    prints `"rehearsal": true`, never a result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)           # one CPU device, as the chip has
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", "tenants-zipf.steady", "--seed", str(SEED),
         "--seconds", "4", "--trace", "0", "--rehearsal"],
        env=env, capture_output=True, text=True, timeout=300)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert proc.returncode == 1, proc.stderr[-2000:]
    last = lines[-1]
    assert last["rehearsal"] is True, proc.stderr[-2000:]
    judged = next(ln for ln in lines if ln.get("phase") == "judged")
    assert judged["complaints"] == []
    would = last["would_be"]
    assert would["correct"] is True and would["failed"] == 0
    assert set(would["metrics"]) == {"ingest_spans_per_s", "push_p50_ms",
                                     "setup_s"}
    rehearsal = _config()["rehearsal"]
    assert len(rehearsal["tenants"]) == 12
    assert rehearsal["yaml_overrides"]["pages"]["arena_slots"] == 8192
    assert rehearsal["yaml_overrides"]["generator"]["registry"][
        "collection_interval_s"] == 3.0
