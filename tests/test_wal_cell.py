"""The deployment `k6-single-binary-wal` at a small size, on the CPU: what
the chip cell `k6-write-wal.steady` rests on.

- the configuration and the traffic file differ from `k6-single-binary`'s
  and `k6-write.steady`'s in the keys ISSUE 35 names and in nothing else;
- the configuration's plain reader (`chipbench/reference_wal.py`, which
  imports nothing of the program) and `wal.decode_record` give equal
  arrays and string tables on seeded records over two rotations, and both
  stop at a torn tail;
- an `App` with `target: all` built from the cell's files serves pushes
  from four threads over HTTP for two tenants, is ABANDONED (no shutdown:
  the kill -9 shape), and a second one over the same directories replays
  to a collect equal to the uninterrupted one's and to the numpy
  oracle's; its clean stop cuts the checkpoints BEFORE the ingester's
  flush and leaves one blob a tenant and no covered segment, and a third
  boot restores from the blobs to the same collect;
- the cell's own run (`mixes/otlp_push_wal.py`, the control flow of
  `run.py` in this process) passes its judge, shows the four `tempo_wal_*`
  families growing on the `/metrics` the harness scrapes and `wal.sync` a
  child of `wal.append`; and the judge complains when a record is cut out
  of a segment, when a frame's byte is flipped and when an acknowledged
  push is missing: three mutations of that one passing run.
"""

from __future__ import annotations

import io
import json
import os
import shutil
import threading
import time
import types
import urllib.parse
import zlib

import numpy as np
import pytest

from chipbench import lib, reference_wal, spans
from chipbench import run as bench_run
from chipbench.mixes import otlp_push_wal
from tempo_tpu.generator import wal as wal_mod
from tempo_tpu.obs.jaxruntime import RUNTIME
from tempo_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483659            # the driver's seeds are beyond 32 signed bits
EXACT_SUFFIXES = ("_total", "_count", "_bucket")    # integer-valued families
SCHEMA = {"services": 8, "names": 4, "vus": 4, "end_jitter_ns": 10**9}
# the cell's push shape, a 1 MB segment (the least the configuration
# admits: ~7 pushes of 137 KB a segment) and a collection loop that stays
# out of the way
SMALL = {"schema": SCHEMA,
         "tenant_limits": {"generator": {"max_active_series": 1024}},
         "yaml_overrides": {"wal": {"segment_max_bytes": 1 << 20},
                            "generator": {"registry": {
                                "collection_interval_s": 3600.0}}}}
SHAPE = spans.PushShape(8, 125, 5)


def _json(*parts: str) -> dict:
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def _config(name: str = "k6-single-binary-wal") -> dict:
    return _json("chipbench", "configs", name + ".json")


# -- the cell's files ------------------------------------------------------

def test_wal_files_differ_from_their_twins_only_where_named():
    base, wal = _config("k6-single-binary"), _config()
    assert base.keys() == wal.keys()
    assert {k for k in base if base[k] != wal[k]} == {
        "name", "source", "deployment", "yaml_overrides", "reduced",
        "device_state", "guarantees", "assumed"}
    over = dict(wal["yaml_overrides"])
    assert over.pop("wal") == {"enabled": True, "fsync": "batch"}
    assert over.pop("fleet") == {"enabled": True}
    assert over.pop("distributor") == {"generator_placement": "tenant"}
    assert over.pop("instance_id")
    assert over == base["yaml_overrides"]
    # the shipped policy, sizes and ages: nothing of the log is tuned
    shipped = wal_mod.IngestWalConfig()
    assert (shipped.fsync, shipped.segment_max_bytes,
            shipped.segment_max_age_s) == ("batch", 64 << 20, 300.0)
    assert wal["assumed"][:len(base["assumed"])] == base["assumed"]
    assert len(wal["assumed"]) == len(base["assumed"]) + 2
    # the k6 deployment's other three guarantees, between the log's two
    # and the clean stop's
    assert wal["guarantees"][2:5] == base["guarantees"][1:]
    assert len(wal["guarantees"]) == 6
    assert wal["reduced"] == ["replicas"] and len(wal["source"]) <= 200
    a = lib.load_json("traffic", "k6-write.steady.json")
    b = lib.load_json("traffic", "k6-write-wal.steady.json")
    assert {k for k in a.keys() | b.keys() if a.get(k) != b.get(k)} == {
        "kind", "wal_sample_records"}
    assert (b["kind"], b["wal_sample_records"]) == ("otlp_push_wal", 64)
    bench = _json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == wal["name"])
    assert (entry["source"], entry["reduced"]) == (wal["source"],
                                                   wal["reduced"])
    cell = next(w for w in bench["workloads"]
                if w["name"] == "k6-write-wal.steady")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        wal["name"], "k6-write-wal.steady", 1)
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".wal")]
    at = bench["per_layer"].index(mine[0])    # one run, as PR 35 added them
    assert len(mine) == 24 and bench["per_layer"][at:at + 24] == mine
    assert all(m["workloads"] == ["k6-write-wal.steady"] for m in mine)


# -- the plain reader against the program's -------------------------------

def _seeded_log(root: str, n: int = 40) -> tuple[str, list]:
    """A tenant's log of `n` seeded records over at least two rotations,
    written by the program's own writer: structured arrays with id
    columns, a vocabulary that grows now and then, a record with no array
    at all. [(meta, arrays)] as handed to the writer."""
    from tempo_tpu.native import STAGE_ATTR_DTYPE, STAGE_REC_DTYPE
    from tempo_tpu.model.interner import StringInterner

    cfg = wal_mod.IngestWalConfig(enabled=True, dir=root, fsync="off",
                                  segment_max_bytes=1 << 20)
    log = wal_mod.GeneratorWal(cfg)
    it, rng, wrote = StringInterner(), np.random.default_rng(SEED), []
    for i in range(n):
        if i % 7 == 0:
            it.intern_many([f"svc-{i:04d}", f"op-{i:04d}", "k6.vu"])
        rows = np.zeros(int(rng.integers(200, 1200)), STAGE_REC_DTYPE)
        rows["span_id"] = rng.integers(0, 256, (len(rows), 8))
        rows["start_ns"] = rng.integers(1, 1 << 62, len(rows))
        rows["name_id"] = rng.integers(0, len(it), len(rows))
        attrs = np.zeros(int(rng.integers(0, 50)), STAGE_ATTR_DTYPE)
        attrs["fval"] = rng.random(len(attrs))
        arrays = {"spans": rows, "sattrs": attrs,
                  "weights": rng.random(len(rows)).astype(np.float32)} \
            if i % 11 else {}
        meta = {"v": 1, "kind": "staged", "ts": float(i), "n": len(rows)}
        log._tw("t/1").append((dict(meta), arrays), interner=it)
        wrote.append((meta, arrays))
    log.close()
    return os.path.join(root, urllib.parse.quote("t/1", safe="")), wrote


def _program_reads(tenant_dir: str) -> list:
    """The program's own reader: [(seq, meta, arrays, strings)] and its
    segment string tables, as `GeneratorWal._replay_segments` builds them."""
    tw = wal_mod._TenantWal(os.path.dirname(tenant_dir), "t/1",
                            wal_mod.IngestWalConfig(), time.time)
    out = []
    for name in tw.segments():
        strings: list = []
        for seq, payload in tw._read_segment(name):
            meta, arrays = wal_mod.decode_record(payload)
            strings = strings + list(meta.get("new_strings") or [])
            out.append((seq, meta, arrays, strings))
    return out


def _same(got: list, want: list) -> None:
    assert [r[0] for r in got] == [r[0] for r in want]
    for (_, meta, arrays, strings), (_, meta_w, arrays_w, strings_w) \
            in zip(got, want):
        assert meta == meta_w and strings == strings_w
        assert arrays.keys() == arrays_w.keys()
        for k in arrays:
            assert arrays[k].dtype == arrays_w[k].dtype, k
            assert arrays[k].tobytes() == arrays_w[k].tobytes(), k


def test_reference_reader_agrees_with_the_programs_over_two_rotations(
        tmp_path):
    tenant_dir, wrote = _seeded_log(str(tmp_path))
    log, faults = reference_wal.read_tenant(tenant_dir)
    assert not faults and len(log) >= 3           # two rotations or more
    got = [rec for _, records in log for rec in records]
    _same(got, _program_reads(tenant_dir))
    # and both equal what went in: every array byte for byte, the delta
    # of a record the strings interned since the one before
    assert len(got) == len(wrote)
    for (seq, meta, arrays, strings), (meta_w, arrays_w) in zip(got, wrote):
        assert {k: meta[k] for k in meta_w} == meta_w
        assert arrays.keys() == arrays_w.keys()
        for k in arrays_w:
            assert arrays[k].tobytes() == arrays_w[k].tobytes()
    assert got[-1][3][:3] == ["svc-0000", "op-0000", "k6.vu"]
    # a segment starts from an empty table and ships the whole vocabulary
    first = [records[0] for _, records in log]
    assert all(r[1]["smark"] == 0 for r in first)
    assert [len(r[3]) for r in first] == sorted(len(r[3]) for r in first)
    # the reader shares nothing with the program
    with open(reference_wal.__file__) as f:
        assert "tempo_tpu" not in "".join(
            ln for ln in f if ln.lstrip().startswith(("import ", "from ")))


@pytest.mark.parametrize("cut", ["mid_payload", "mid_header", "flipped"])
def test_both_readers_stop_at_a_torn_tail(tmp_path, cut):
    tenant_dir, _ = _seeded_log(str(tmp_path), n=13)
    last = os.path.join(tenant_dir, reference_wal.segments(tenant_dir)[-1])
    whole = _program_reads(tenant_dir)
    with open(last, "rb") as f:
        data = f.read()
    if cut == "flipped":                      # a byte of the LAST frame
        data = data[:-10] + bytes([data[-10] ^ 0xFF]) + data[-9:]
    else:
        data = data[:-3000] if cut == "mid_payload" else data + b"TWR1\x01"
    with open(last, "wb") as f:
        f.write(data)
    torn_before = wal_mod.STATS["torn_frames"]
    want = _program_reads(tenant_dir)
    # twice: the writer's own scan for its next seq, and the read
    assert wal_mod.STATS["torn_frames"] == torn_before + 2
    log, faults = reference_wal.read_tenant(tenant_dir)
    _same([rec for _, records in log for rec in records], want)
    assert len(want) == len(whole) - (cut != "mid_header")
    assert len(faults) == 1 and "not the last" not in faults[0]


def test_rotation_beside_a_group_commit_keeps_the_seqs_apart(tmp_path,
                                                            monkeypatch):
    """Six appenders on one tenant's log, `fsync: batch`, a rotation every
    five records: a rotation has to wait out a leader's fsync, which runs
    with the lock released. An appender that read its seq before that
    wait (the program until PR 35) wrote a seq another one had taken
    meanwhile, into the segment that one had opened."""
    real = os.fsync
    monkeypatch.setattr(wal_mod.os, "fsync",
                        lambda fd: (time.sleep(0.002), real(fd))[1])
    log = wal_mod.GeneratorWal(wal_mod.IngestWalConfig(
        enabled=True, dir=str(tmp_path), fsync="batch",
        segment_max_bytes=1 << 20))
    body = {"raw": np.zeros(220_000, np.uint8)}

    def appender(k: int) -> None:
        for i in range(40):
            log._tw("t").append(({"v": 1, "kind": "otlp", "n": 0,
                                  "who": [k, i]}, body))

    threads = [threading.Thread(target=appender, args=(k,)) for k in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    log.close()
    read, faults = reference_wal.read_tenant(os.path.join(str(tmp_path), "t"))
    assert faults == [] and len(read) >= 40
    records = [rec for _, recs in read for rec in recs]
    assert [r[0] for r in records] == list(range(240))
    assert sorted(tuple(r[1]["who"]) for r in records) == [
        (k, i) for k in range(6) for i in range(40)]
    assert log._tw("t").next_seq == 240


# -- the served App, abandoned and replayed --------------------------------

def _boot(workdir: str, sink: lib.Sink, more: dict | None = None):
    config = lib.merged(lib.merged(_config(), SMALL), more or {})
    config["yaml_overrides"]["wal"]["dir"] = os.path.join(workdir, "gwal")
    return lib.boot(config, workdir, sink.url)


def _abandon(app, srv) -> None:
    """The kill -9 shape, as far as one process can give it: servers and
    loops stopped, nothing flushed, checkpointed or closed."""
    srv.shutdown()
    srv.server_close()
    for part in (app, app.ingester, app.generator, app.fleet):
        if part is not None:
            part._stop.set()
    if app.fleet is not None:
        app.fleet._wake.set()
    for t in app.generator._threads:
        t.join(timeout=60)
    app.sched.flush()
    app.db.shutdown()


def _collect(port: int, tenants: list) -> dict:
    out = {}
    for tenant in tenants:
        samples = lib.get_json(port, "/internal/generator/collect", tenant,
                               ts_ms=1)["samples"]
        values = {(s["name"], tuple(map(tuple, s["labels"]))): s["value"]
                  for s in samples}
        quantiles = {q: {tuple(map(tuple, e["labels"])): e["value"]
                         for e in lib.get_json(
                             port, "/internal/generator/quantile", tenant,
                             q=q)["quantiles"]} for q in (0.5, 0.99)}
        out[tenant] = (values, quantiles)
    return out


def _assert_same_state(got: dict, want: dict) -> None:
    for tenant, (values, quantiles) in want.items():
        values_g, quantiles_g = got[tenant]
        assert values_g.keys() == values.keys() and len(values) > 100
        for key, a in values.items():
            b = values_g[key]
            if key[0].endswith(EXACT_SUFFIXES):
                assert a == b, (key, a, b)
            else:
                assert abs(a - b) <= 1e-5 * max(abs(a), abs(b)), (key, a, b)
        assert quantiles_g == quantiles and len(quantiles[0.99]) > 50


def _ckpt_dir(workdir: str, tenant: str) -> str:
    return os.path.join(workdir, "blocks", "fleet-checkpoints", tenant)


def test_abandoned_app_replays_to_the_uninterrupted_collect(tmp_path):
    workdir, sink = str(tmp_path / "w"), lib.Sink()
    os.makedirs(workdir)
    tenants = _config()["tenants"]
    now_ns = time.time_ns()
    pushes = [(ti, idx, now_ns + idx) for idx in range(16) for ti in (0, 1)]
    first, todo = pushes[:8], pushes[8:]
    app, srv, port = _boot(workdir, sink)
    assert app.cfg.target == "all" and app.fleet is not None
    assert app.generator.wal.cfg.fsync == "batch"
    lock, failed = threading.Lock(), []

    def client(todo: list) -> None:
        while True:
            with lock:
                if not todo:
                    return
                ti, idx, stamp = todo.pop(0)
            body = spans.encode_push(SHAPE, spans.draw_push(
                SEED, ti, idx, SHAPE, SCHEMA, stamp))
            status, _ = lib.http_call(port, "POST", "/v1/traces",
                                      tenants[ti], body)
            if status != 200:
                failed.append(status)

    try:
        client(list(first))        # every series made by pushes in turn
        threads = [threading.Thread(target=client, args=(todo,))
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        assert not failed
        uninterrupted = _collect(port, tenants)
    finally:
        _abandon(app, srv)
    gwal = os.path.join(workdir, "gwal")
    assert all(len(reference_wal.segments(os.path.join(gwal, t))) >= 2
               for t in tenants)                       # it rotated
    assert not os.path.isdir(_ckpt_dir(workdir, tenants[0]))

    # the oracle: every acknowledged span counted once
    for ti, tenant in enumerate(tenants):
        cols = [spans.draw_push(SEED, ti, idx, SHAPE, SCHEMA, stamp)
                for t, idx, stamp in pushes if t == ti]
        values = uninterrupted[tenant][0]
        calls = sum(v for (n, _), v in values.items()
                    if n == "traces_spanmetrics_calls_total")
        assert calls == sum(len(c["svc"]) for c in cols) == 16000
        edges = sum(v for (n, _), v in values.items()
                    if n == "traces_service_graph_request_total")
        assert edges == sum(c["pairs"] for c in cols)

    replayed0 = wal_mod.STATS["replayed_batches"]
    app2, srv2, port2 = _boot(workdir, sink)
    try:
        assert wal_mod.STATS["replayed_batches"] - replayed0 == len(pushes)
        assert wal_mod.STATS["dead_letters"] == 0
        _assert_same_state(_collect(port2, tenants), uninterrupted)
        # the clean stop: the checkpoints are cut BEFORE the ingester's
        # flush (minutes at the cell's size), and leave guarantee 4
        seen = {}
        flush_all = app2.ingester.flush_all

        def flush_spy() -> None:
            seen["blobs"] = {t: os.listdir(_ckpt_dir(workdir, t))
                             for t in tenants}
            flush_all()

        app2.ingester.flush_all = flush_spy
        srv2.shutdown()
        srv2.server_close()
        truncated0 = wal_mod.STATS["truncated_segments"]
        app2.shutdown()
    finally:
        srv2.server_close()
    assert all(len(b) == 1 and b[0].endswith(".ckpt")
               for b in seen["blobs"].values()), seen
    for tenant in tenants:
        assert os.listdir(_ckpt_dir(workdir, tenant)) == seen["blobs"][tenant]
        left = os.listdir(os.path.join(gwal, tenant))
        assert left == ["CHECKPOINTED"], left
        with open(os.path.join(gwal, tenant, "CHECKPOINTED")) as f:
            assert int(f.read()) == 15                 # the last record
    assert wal_mod.STATS["truncated_segments"] - truncated0 >= 4

    # a third boot restores from the blobs and replays nothing
    replayed1 = wal_mod.STATS["replayed_batches"]
    app3, srv3, port3 = _boot(workdir, sink)
    try:
        assert wal_mod.STATS["replayed_batches"] == replayed1
        _assert_same_state(_collect(port3, tenants), uninterrupted)
    finally:
        _abandon(app3, srv3)
        sink.srv.shutdown()
        sink.srv.server_close()


# -- the cell's own run, and three mutations of it -------------------------

def _cell_ctx(workdir: str, more: dict | None = None):
    """`run.py`'s context for the cell at rehearsal size."""
    bench = _json("BENCHMARK.json")
    config, traffic = _config(), lib.load_json(
        "traffic", "k6-write-wal.steady.json")
    config = lib.merged(lib.merged(lib.merged(config, config["rehearsal"]), {
        "yaml_overrides": {"wal": {"segment_max_bytes": 4 << 20}}}),
        more or {})
    traffic = lib.merged(lib.merged(traffic, traffic["rehearsal"]),
                         {"prefill_pushes_per_tenant": 8,
                          "wal_sample_records": 12})
    ctx = types.SimpleNamespace(
        args=types.SimpleNamespace(seconds=3.0), seed=SEED, config=config,
        cell=next(w for w in bench["workloads"]
                  if w["name"] == "k6-write-wal.steady"),
        traffic=traffic, workdir=workdir, rehearsal=True, n_child=0,
        clock=lambda: round(bench_run.process_age_s(), 3))
    ctx.run_child = lambda spec, go=None: bench_run.run_child(ctx, spec, go)
    return ctx


@pytest.fixture(scope="module")
def passing_run(tmp_path_factory):
    """The cell at rehearsal size through `run.py`'s own control flow in
    this process (set-up, a 3 s window from the load generator's child,
    the judge), with the log copied aside before the clean stop
    truncates it."""
    workdir = str(tmp_path_factory.mktemp("cell"))
    ctx = _cell_ctx(workdir)
    tracing.reset_span_rows()
    mix = otlp_push_wal.Mix(ctx)
    mix.setup()
    m0 = lib.scrape(ctx.port)
    res = ctx.run_child(dict(mix.child_spec(), seconds=3.0), lambda: None)
    m1 = lib.scrape(ctx.port)
    rows = tracing.span_rows()
    pristine = os.path.join(workdir, "pristine")
    shutil.copytree(mix.wal_dir, pristine)
    judged = mix.judge(res, res["t_go"], 3.0)
    mix.stopper.join(120)             # the judge does not wait for the
    assert not mix.stopper.is_alive()   # ingester's flush; the test does
    ctx.sink.srv.shutdown()
    ctx.sink.srv.server_close()
    return types.SimpleNamespace(
        mix=mix, judged=judged, m0=m0, m1=m1, rows=rows, pristine=pristine,
        workdir=workdir, after=lib.parse_exposition(RUNTIME.render()))


def test_the_cells_run_passes_its_judge(passing_run):
    run = passing_run
    assert run.judged["complaints"] == [] and run.judged["failed"] == 0
    assert run.judged["units"] > 0 and len(run.mix.sent) > 30
    # it rotated, and the sample held the ends of every segment
    assert all(len(reference_wal.segments(os.path.join(run.pristine, t)))
               >= 2 for t in run.mix.tenants)


def test_the_recovery_runs_child_boots_the_cells_deployment(tmp_path):
    """`chip_smoke.py --recover` serves from a child process, so it
    writes as a yaml what `lib.boot` builds in memory: the same `Config`,
    field for field, for the same configuration, workdir and sink."""
    from unittest import mock

    import chip_smoke
    from tempo_tpu.app.config import load_config

    config, url = _config(), "http://127.0.0.1:9/api/v1/push"
    config["yaml_overrides"]["wal"]["dir"] = str(tmp_path / "generator-wal")
    with mock.patch("tempo_tpu.app.app.App") as app_cls, \
            mock.patch("tempo_tpu.app.api.serve"):
        lib.boot(config, str(tmp_path), url)
    (booted,), _ = app_cls.call_args
    assert load_config(chip_smoke.member_yaml(config, str(tmp_path), url)) \
        == booted
    assert booted.wal.enabled and booted.wal.fsync == "batch" \
        and booted.fleet.enabled and booted.generator.remote_write.url == url


def test_wal_families_grow_on_the_scraped_metrics(passing_run):
    """Registered with the process's runtime registry at import of
    `wal.py`: on the `/metrics` a `target: all` process serves, growing."""
    run = passing_run
    obs = {"m0": run.m0, "m1": run.m1}
    pushes = lib.delta(obs, "tempo_distributor_push_duration_seconds_count")
    assert pushes > 5
    assert lib.delta(obs, "tempo_wal_appended_batches_total") == pushes
    assert lib.delta(obs, "tempo_wal_fsyncs_total") >= 1
    per_span = lib.delta(obs, "tempo_wal_appended_bytes_total") / lib.delta(
        obs, "tempo_metrics_generator_spans_received_total")
    assert 88 < per_span < 200         # 88 B a StageRec row, 48 an attribute
    assert lib.metric_sum(run.m1, "tempo_wal_truncated_segments_total") \
        == lib.metric_sum(run.m0, "tempo_wal_truncated_segments_total")
    assert lib.metric_sum(run.after, "tempo_wal_truncated_segments_total") \
        - lib.metric_sum(run.m1, "tempo_wal_truncated_segments_total") >= 4
    assert lib.metric_sum(run.after, "tempo_wal_dead_letters_total") \
        == lib.metric_sum(run.mix.m_boot, "tempo_wal_dead_letters_total")


def test_wal_sync_is_a_child_span_of_wal_append(passing_run):
    rows = passing_run.rows
    names = {name for name, _ in rows}
    assert {"wal.append", "wal.sync", "api.push", "generator.Push"} <= names

    def total(name: str, at: int) -> int:
        return sum(row[at] for (n, _), row in rows.items() if n == name)

    # one wait an append (`fsync: batch`), and the append's self time is
    # its duration less the wait: lock, encode and write
    assert total("wal.sync", 0) == total("wal.append", 0) > 30
    assert total("wal.append", 2) == total("wal.append", 1) \
        - total("wal.sync", 1)
    assert total("wal.sync", 2) == total("wal.sync", 1)


def _cut_record(data: bytes, frames: list) -> bytes:
    at, size = frames[len(frames) // 2]
    return data[:at] + data[at + size:]


def _flip_byte(data: bytes, frames: list) -> bytes:
    at, size = frames[len(frames) // 2]
    mid = at + size // 2
    return data[:mid] + bytes([data[mid] ^ 0x01]) + data[mid + 1:]


def _drop_last(data: bytes, frames: list) -> bytes:
    """The acknowledged push of the segment's last frame never reached
    the disk, and nothing else is wrong with the file."""
    at, _ = frames[-1]
    return data[:at]


@pytest.mark.parametrize("mutate,says", [
    (_cut_record, "follows"),
    (_flip_byte, "the checksum does not hold"),
    (_drop_last, "has no good frame on disk"),
], ids=["record_cut_out", "byte_flipped", "acknowledged_push_missing"])
def test_the_judge_complains_of_a_mutated_log(passing_run, tmp_path, mutate,
                                              says):
    mix = passing_run.mix
    mutated = str(tmp_path / "log")
    shutil.copytree(passing_run.pristine, mutated)
    tenant_dir = os.path.join(mutated, mix.tenants[1])
    name = reference_wal.segments(tenant_dir)[0]      # not the last one
    path = os.path.join(tenant_dir, name)
    with open(path, "rb") as f:
        data = f.read()
    frames, at = [], 0
    while at < len(data):
        _, length, checksum = reference_wal.HEADER.unpack_from(data, at + 4)
        size = 4 + reference_wal.HEADER.size + length
        assert zlib.adler32(data[at + size - length:at + size]) == checksum
        frames.append((at, size))
        at += size
    with open(path, "wb") as f:
        f.write(mutate(data, frames))
    wal_dir, clean, complaints = mix.wal_dir, [], []
    try:
        mix.wal_dir = passing_run.pristine
        mix.check_log(clean)
        mix.wal_dir = mutated
        mix.check_log(complaints)
    finally:
        mix.wal_dir = wal_dir
    assert clean == []
    assert any(says in c and mix.tenants[1] in c for c in complaints), \
        complaints
    # the other tenant's log is whole and draws no complaint
    assert not any(c.startswith(mix.tenants[0]) for c in complaints)


@pytest.mark.parametrize("fsyncs,says", [
    (lambda appends, segments: appends, None),
    (lambda appends, segments: -(-appends // 4), None),
    (lambda appends, segments: -(-appends // 4) - 1, "at the least"),
    (lambda appends, segments: appends + segments + 1, "at the least"),
    (lambda appends, segments: 0, "at the least"),
], ids=["one_an_append", "one_a_round_of_handlers", "rarer_than_batch_can",
        "more_than_appends_and_segments", "none"])
def test_the_judge_bounds_the_fsyncs_from_both_sides(passing_run, fsyncs,
                                                     says):
    """Guarantee 1's fsync part on the counters alone: `batch` under a
    closed loop of four handlers commits at most four appends at once."""
    mix = passing_run.mix
    assert mix.ctx.traffic["clients"] == 4
    logs = {t: [("000000000000.wal", [0])] for t in mix.tenants}
    appends = len(mix.sent)
    m = dict(mix.m_boot)
    for family, grew in ((otlp_push_wal.APPENDS, appends),
                         (otlp_push_wal.FSYNCS, fsyncs(appends, 2))):
        m[(family, ())] = lib.metric_sum(mix.m_boot, family) + grew
    complaints: list = []
    mix.check_counters(m, logs, complaints)
    assert complaints == [] if says is None else \
        len(complaints) == 1 and says in complaints[0], complaints


def test_the_judge_complains_of_a_rarer_flush(tmp_path):
    """The control: the cell's files booted with `fsync: interval`, the
    cell's set-up, the judge's parts on the counters and the log. The
    log is whole (nothing crashed); the policy is not the stated one."""
    ctx = _cell_ctx(str(tmp_path), {"yaml_overrides": {"wal": {
        "fsync": "interval"}}})
    mix = otlp_push_wal.Mix(ctx)
    mix.setup()
    try:
        complaints: list = []
        logs = mix.check_log(complaints)
        assert complaints == []
        mix.check_counters(lib.scrape(ctx.port), logs, complaints)
    finally:
        _abandon(ctx.app, ctx.srv)
        ctx.sink.srv.shutdown()
        ctx.sink.srv.server_close()
    assert any("fsync: interval" in c for c in complaints), complaints


@pytest.mark.parametrize("order,says", [
    ("checkpoints_first", "Ingester.shutdown"),
    ("checkpoints_only", "no part of it"),
    ("flush_first", None),
])
def test_the_judge_holds_guarantee_4_to_a_stop_that_comes_that_far(
        tmp_path, monkeypatch, order, says):
    """The judge runs `App.shutdown` whole and reads the disk until the
    stop is past the fleet's part. A stop that is past it, or has ended,
    and left no blob is complained of at once; one that flushes the
    ingester before it checkpoints (the order before PR 35) is reported
    as not coming that far, and the run exits under it."""
    release = threading.Event()

    class Fleet:
        def shutdown(self) -> None:
            pass                             # cuts nothing

    class Ingester:
        def shutdown(self) -> None:
            release.wait(30)                 # the flush: minutes

    class App:
        fleet, ingester = Fleet(), Ingester()

        def checkpoints_first(self) -> None:
            self.fleet.shutdown()
            self.ingester.shutdown()

        def checkpoints_only(self) -> None:
            self.fleet.shutdown()

        def flush_first(self) -> None:
            if self.ingester:
                self.ingester.shutdown()
            if self.fleet is not None:
                self.fleet.shutdown()

    App.shutdown = getattr(App, order)
    App.shutdown.__code__ = App.shutdown.__code__.replace(
        co_qualname=App.__qualname__ + ".shutdown")
    monkeypatch.setattr(otlp_push_wal, "STOP_WITHIN_S", 20.0)
    mix = otlp_push_wal.Mix(types.SimpleNamespace(
        workdir=str(tmp_path), clock=lambda: 0.0, app=App(),
        config={"yaml_overrides": {"wal": {}}},
        srv=types.SimpleNamespace(shutdown=lambda: None,
                                  server_close=lambda: None)))
    mix.tenants = ["k6-a", "k6-b"]
    mix.place_log()
    complaints: list = []
    t0 = time.monotonic()
    try:
        mix.check_clean_stop({t: [("000000000000.wal", [0, 1])]
                              for t in mix.tenants}, complaints)
    finally:
        release.set()
        mix.stopper.join(30)
    assert time.monotonic() - t0 < 5         # not the 20 s of patience
    assert len(complaints) == (2 if says else 0), complaints
    assert all("0 checkpoint blobs" in c and says in c for c in complaints)


def test_the_programs_stop_checkpoints_before_it_flushes():
    from tempo_tpu.app.app import App

    assert not otlp_push_wal.flushes_first(App.shutdown)


def test_clean_stop_faults_are_seen(passing_run, tmp_path):
    """Guarantee 4 as the judge reads it from the disk: no blob, two
    blobs, a covered segment left behind, a watermark short of the log."""
    run, mix = passing_run, passing_run.mix
    logs = {t: [(n, [r[0] for r in recs]) for n, recs in
                reference_wal.read_tenant(os.path.join(run.pristine, t))[0]]
            for t in mix.tenants}
    assert mix.stop_faults(logs) == []            # as the run left it
    tenant = mix.tenants[0]
    blobs_at = _ckpt_dir(run.workdir, tenant)
    (blob,) = os.listdir(blobs_at)
    kept = os.path.join(mix.wal_dir, tenant, "000000000000.wal")
    try:
        shutil.copy(os.path.join(run.pristine, tenant, "000000000000.wal"),
                    kept)
        assert any("not truncated" in f for f in mix.stop_faults(logs))
        os.unlink(kept)
        shutil.copy(os.path.join(blobs_at, blob),
                    os.path.join(blobs_at, "9" + blob[1:]))
        assert any("2 checkpoint blobs" in f for f in mix.stop_faults(logs))
        os.unlink(os.path.join(blobs_at, "9" + blob[1:]))
        longer = dict(logs, **{tenant: logs[tenant] + [("x.wal", [10**6])]})
        assert any("watermark" in f for f in mix.stop_faults(longer))
        os.rename(os.path.join(blobs_at, blob), str(tmp_path / blob))
        assert any("0 checkpoint blobs" in f for f in mix.stop_faults(logs))
        os.rename(str(tmp_path / blob), os.path.join(blobs_at, blob))
    finally:
        if os.path.exists(kept):
            os.unlink(kept)
    with open(os.path.join(blobs_at, blob), "rb") as f, \
            np.load(io.BytesIO(f.read()), allow_pickle=False) as z:
        marks = json.loads(z["__meta__"].tobytes())["wal"]
    assert list(marks) == ["generator/" + _config()["yaml_overrides"][
        "instance_id"]]
