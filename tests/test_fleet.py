"""Generator fleet: placement, checkpoint/restore, drain/handoff.

The multi-host protocol is exercised in-process where possible (two
Generators + controllers over one KVStore — fast, deterministic) and
with ONE real child process for the worker/reap plumbing. Bit-identity
contract: count-kind samples (calls/size counters, histogram buckets
and counts, DDSketch grids) restore and merge EXACTLY; float sums are
f32-add-order class (the same tolerance the mesh/shard combines carry).
"""

from __future__ import annotations

import numpy as np
import pytest

from tempo_tpu.backend.mem import MemBackend
from tempo_tpu.fleet import STATS, FleetConfig
from tempo_tpu.fleet import checkpoint as ck
from tempo_tpu.fleet.controller import FleetController
from tempo_tpu.fleet.placement import TenantPlacement, tenant_token
from tempo_tpu.generator.generator import Generator
from tempo_tpu.generator.instance import GeneratorConfig, GeneratorInstance
from tempo_tpu.generator.processors.spanmetrics import SpanMetricsConfig
from tempo_tpu.model.span_batch import SpanBatchBuilder
from tempo_tpu.registry import RegistryOverrides
from tempo_tpu.ring import KVStore, Lifecycler, Ring

NOW = 1700000000.0


def _cfg(sketch: str = "both", max_series: int = 1024,
         moments_k: int = 12) -> GeneratorConfig:
    return GeneratorConfig(
        processors=("span-metrics",),
        registry=RegistryOverrides(max_active_series=max_series),
        spanmetrics=SpanMetricsConfig(sketch=sketch, moments_k=moments_k))


def _inst(tenant="t1", **kw) -> GeneratorInstance:
    return GeneratorInstance(tenant, _cfg(**kw), now=lambda: NOW)


def _spans(seed: int, n: int = 40) -> list[dict]:
    rng = np.random.default_rng(seed)
    return [dict(trace_id=rng.bytes(16), span_id=rng.bytes(8),
                 name=f"op-{i % 5}", service=f"svc-{i % 3}", kind=2,
                 status_code=int(i % 7 == 0) * 2,
                 start_unix_nano=int(NOW * 1e9),
                 end_unix_nano=int(NOW * 1e9) + int(rng.integers(1, 5e8)))
            for i in range(n)]


def _push(inst: GeneratorInstance, seed: int, n: int = 40) -> None:
    b = SpanBatchBuilder(inst.registry.interner)
    for s in _spans(seed, n):
        b.append(**s)
    inst.push_batch(b.build())


def _samples(inst: GeneratorInstance) -> dict:
    return {(s.name, s.labels): s.value
            for s in inst.registry.collect(ts_ms=1)
            if not s.is_stale_marker}


def _assert_merge_equal(got: dict, want: dict) -> None:
    """Count kinds bit-identical; float sums within f32-add-order."""
    assert set(got) == set(want)
    for k, v in want.items():
        if k[0].endswith("_sum"):
            assert got[k] == pytest.approx(v, rel=1e-5)
        else:
            assert got[k] == v, k


# ---------------------------------------------------------------------------
# checkpoint round trips
# ---------------------------------------------------------------------------


def test_checkpoint_restore_roundtrip_bit_identical():
    """Fresh-instance restore is add-to-zero: collect() and the dd
    quantile surface round-trip bit-identically through the blob."""
    a = _inst()
    _push(a, 1)
    blob = ck.snapshot_instance(a)
    b = _inst()
    stats = ck.restore_instance(b, blob)
    assert stats["dropped"] == 0 and stats["series"] > 0
    assert _samples(b) == _samples(a)
    pa = a.processors["span-metrics"]
    pb = b.processors["span-metrics"]
    assert pb.quantile(0.99) == pa.quantile(0.99)


def test_checkpoint_restore_through_backend_objects():
    """The storage-layout helpers: write → list → read → delete."""
    be = MemBackend()
    a = _inst("te/nant")                 # path-hostile tenant name
    _push(a, 2)
    blob = ck.snapshot_instance(a)
    name = ck.checkpoint_name(NOW, "gen-a")
    ck.write_checkpoint(be, "fleet-checkpoints", "te/nant", blob, name)
    listed = ck.list_checkpoints(be, "fleet-checkpoints")
    assert listed == {"te/nant": [name]}
    got = ck.read_checkpoint(be, "fleet-checkpoints", "te/nant", name)
    b = _inst("te/nant")
    ck.restore_instance(b, got)
    assert _samples(b) == _samples(a)
    ck.delete_checkpoint(be, "fleet-checkpoints", "te/nant", name)
    assert ck.list_checkpoints(be, "fleet-checkpoints") == {}


def test_checkpoint_restore_roundtrip_paged_and_cross_layout():
    """Paged tenants snapshot backed pages only; the blob is layout-
    neutral (paged → paged AND paged → dense restores bit-identically),
    and dropping the paged instance releases its pages to the pool."""
    from tempo_tpu.registry import pages as pgs

    pool = pgs.PagePool(pgs.PagePoolConfig(enabled=True, page_rows=64,
                                           arena_slots=4096))
    with pgs.use(pool):
        a = _inst("pt")
        assert a.state_layout == "paged"
        _push(a, 3)
        blob = ck.snapshot_instance(a)
        b = _inst("pt")
        ck.restore_instance(b, blob)
        assert _samples(b) == _samples(a)
        assert b.processors["span-metrics"].quantile(0.9) == \
            a.processors["span-metrics"].quantile(0.9)
        want = _samples(a)
    dense = _inst("pt")
    ck.restore_instance(dense, blob)
    assert dense.state_layout == "dense"
    assert _samples(dense) == want


def test_restore_merges_inflight_deltas_like_oracle():
    """The handoff window: receiver already took fresh spans, then
    merges the mover's checkpoint — equals an uninterrupted oracle
    (count kinds exactly; sums to f32 add order; dd quantiles exact)."""
    a = _inst()
    _push(a, 1)
    blob = ck.snapshot_instance(a)
    b = _inst()
    _push(b, 2)                          # in-flight deltas land FIRST
    ck.restore_instance(b, blob)         # then the moved state merges
    oracle = _inst()
    _push(oracle, 1)
    _push(oracle, 2)
    _assert_merge_equal(_samples(b), _samples(oracle))


def test_restore_rejects_mismatched_sketch_meta():
    """The ValueError-guarded merge checks refuse a checkpoint cut
    under different moments parameters BEFORE any row merges."""
    a = _inst(moments_k=8)
    _push(a, 1)
    blob = ck.snapshot_instance(a)
    b = _inst(moments_k=12)
    with pytest.raises(ValueError):
        b.processors["span-metrics"].sketch_meta_check(
            ck._decode(blob)[0]["spanmetrics"])
    # the full restore path refuses on the overrides fingerprint first
    with pytest.raises(ck.CheckpointMismatch):
        ck.restore_instance(b, blob)
    assert _samples(b) == {}             # nothing merged


def test_restore_rejects_changed_label_layout():
    cfg = _cfg()
    cfg.spanmetrics = SpanMetricsConfig(sketch="both",
                                        dimensions=("http.status",))
    a = GeneratorInstance("t1", cfg, now=lambda: NOW)
    _push(a, 1)
    blob = ck.snapshot_instance(a)
    with pytest.raises(ck.CheckpointMismatch):
        ck.restore_instance(_inst(), blob)


# digests `overrides_fingerprint` gave `_inst()` at the last commit that
# had the compact-state tier (485649c), by its `compact_state` value
_FP_BEFORE_REMOVAL = {False: "2ac5926c598bfdd2", True: "d55b705f673e24cb"}


@pytest.mark.parametrize("compact_state", [False, True])
def test_checkpoint_cut_before_the_compact_tier_was_removed(compact_state):
    """A checkpoint the old writer cut with `compact_state: false`
    carries the digest this build computes, and restores bit-identically;
    one cut with `true` is refused, by name, before any row is written."""
    a = _inst()
    _push(a, 1)
    meta, arrays = ck._decode(ck.snapshot_instance(a))
    assert meta["fingerprint"] == _FP_BEFORE_REMOVAL[False]
    meta["fingerprint"] = _FP_BEFORE_REMOVAL[compact_state]
    blob = ck._encode(meta, arrays)
    b = _inst()
    if compact_state:
        with pytest.raises(ck.CheckpointMismatch, match="compact_state"):
            ck.restore_instance(b, blob)
        assert _samples(b) == {}
    else:
        ck.restore_instance(b, blob)
        assert _samples(b) == _samples(a)


# ---------------------------------------------------------------------------
# placement + controller handoff (in-process fleet over one KVStore)
# ---------------------------------------------------------------------------


def _member(kv, be, iid):
    g = Generator(_cfg(), instance_id=iid, now=lambda: NOW)
    ring = Ring(kv=kv, key="generator", replication_factor=1,
                now=lambda: NOW)
    lc = Lifecycler(kv, iid, key="generator", now=lambda: NOW)
    fc = FleetController(g, ring, iid, be, be,
                         cfg=FleetConfig(enabled=True), now=lambda: NOW)
    return g, ring, lc, fc


def test_placement_agrees_across_members_and_spills_over():
    kv = KVStore()
    be = MemBackend()
    ga, ra, la, _ = _member(kv, be, "gen-a")
    gb, rb, lb, _ = _member(kv, be, "gen-b")
    pa = TenantPlacement(ra, "gen-a")
    pb = TenantPlacement(rb, "gen-b")
    tenants = [f"t{i}" for i in range(50)]
    for t in tenants:
        assert pa.owner(t).id == pb.owner(t).id          # views agree
    owned_a = {t for t in tenants if pa.owns(t)}
    owned_b = {t for t in tenants if pb.owns(t)}
    assert owned_a | owned_b == set(tenants)
    assert not (owned_a & owned_b)
    assert owned_a and owned_b                           # both got a share
    # spillover: a's descriptor goes stale → b owns everything
    la.leave()
    assert all(pb.owner(t).id == "gen-b" for t in tenants)
    assert tenant_token("t1") == tenant_token("t1")      # deterministic


def test_controller_handoff_and_restore_zero_loss():
    """Owner leaves → its controller drains + checkpoints + drops; the
    survivor's tick restores; post-handoff state (with fresh in-flight
    deltas) equals the uninterrupted oracle on count kinds exactly."""
    kv = KVStore()
    be = MemBackend()
    ga, ra, la, fa = _member(kv, be, "gen-a")
    gb, rb, lb, fb = _member(kv, be, "gen-b")
    tenant = "handoff-tenant"
    owner_is_a = TenantPlacement(ra, "gen-a").owns(tenant)
    g_own, lc_own, fc_own = (ga, la, fa) if owner_is_a else (gb, lb, fb)
    g_other, fc_other = (gb, fb) if owner_is_a else (ga, fa)

    g_own.push_spans(tenant, _spans(1))
    restores0 = STATS["restores"]
    lc_own.leave()
    fc_own.tick()                        # loss: drain + checkpoint + drop
    assert tenant not in g_own.tenants()
    fc_other.tick()                      # gain: restore + consume blob
    assert tenant in g_other.tenants()
    assert STATS["restores"] == restores0 + 1
    assert ck.list_checkpoints(be, "fleet-checkpoints") == {}  # consumed
    g_other.push_spans(tenant, _spans(2))   # post-handoff traffic

    oracle = Generator(_cfg(), instance_id="oracle", now=lambda: NOW)
    oracle.push_spans(tenant, _spans(1))
    oracle.push_spans(tenant, _spans(2))
    _assert_merge_equal(_samples(g_other.instance(tenant)),
                        _samples(oracle.instance(tenant)))
    # dd quantiles ride integer grids: bit-identical post-handoff
    assert g_other.instance(tenant).processors["span-metrics"] \
        .quantile(0.99) == \
        oracle.instance(tenant).processors["span-metrics"].quantile(0.99)
    st = fc_other.status()
    assert st["held_tenants"] == 1 and st["owned_tenants"] == 1


def test_shutdown_checkpoint_then_boot_restore():
    """Single-host restart without data loss: shutdown cuts blobs for
    every held tenant; a fresh controller with the same identity
    restores them on its boot tick."""
    kv = KVStore()
    be = MemBackend()
    g1, r1, lc1, fc1 = _member(kv, be, "gen-solo")
    g1.push_spans("ta", _spans(4))
    g1.push_spans("tb", _spans(5))
    want_a = _samples(g1.instance("ta"))
    want_b = _samples(g1.instance("tb"))
    fc1.shutdown()                       # writes shutdown checkpoints
    assert set(ck.list_checkpoints(be, "fleet-checkpoints")) == \
        {"ta", "tb"}
    # "restart": same identity, fresh generator, same backend + KV
    g2, r2, lc2, fc2 = _member(kv, be, "gen-solo")
    fc2.tick()
    assert _samples(g2.instance("ta")) == want_a
    assert _samples(g2.instance("tb")) == want_b
    assert ck.list_checkpoints(be, "fleet-checkpoints") == {}


def test_quarantine_on_poison_checkpoint():
    """An incompatible blob is skipped loudly and kept in the store —
    never deleted, never retried forever, never half-merged."""
    kv = KVStore()
    be = MemBackend()
    poison_src = _inst("tq", moments_k=8)
    _push(poison_src, 1)
    blob = ck.snapshot_instance(poison_src)
    name = ck.checkpoint_name(NOW, "gen-old")
    ck.write_checkpoint(be, "fleet-checkpoints", "tq", blob, name)
    g, r, lc, fc = _member(kv, be, "gen-q")   # moments_k=12 fleet
    fc.tick()
    assert _samples(g.instance("tq")) == {}   # nothing merged
    assert ck.list_checkpoints(be, "fleet-checkpoints") == {"tq": [name]}
    assert fc.status()["quarantined_checkpoints"] == [f"tq/{name}"]
    fc.tick()                                  # stays quarantined, no churn
    assert fc.status()["quarantined_checkpoints"] == [f"tq/{name}"]


def test_checkpoint_ships_only_referenced_strings():
    """The blob carries the strings the checkpointed keys reference, not
    the whole interner table — dead strings from churned series must not
    grow blobs and receiving interners monotonically across handoffs."""
    a = _inst()
    _push(a, 1)
    a.registry.interner.intern_many(
        [f"dead-string-{i}" for i in range(500)])
    blob = ck.snapshot_instance(a)
    meta, _arrays = ck._decode(blob)
    assert not any(s.startswith("dead-string-") for s in meta["strings"])
    b = _inst()
    ck.restore_instance(b, blob)
    assert _samples(b) == _samples(a)


def test_consumed_marker_prevents_replay():
    """A blob carrying a store-side consumed marker (a crashed deleter,
    or a peer whose stale ring view already merged it) is deleted
    WITHOUT restoring — a scatter-add replay would double-count every
    count-kind series."""
    kv = KVStore()
    be = MemBackend()
    src = _inst("tm")
    _push(src, 3)
    blob = ck.snapshot_instance(src)
    name = ck.checkpoint_name(NOW, "gen-dead")
    ck.write_checkpoint(be, "fleet-checkpoints", "tm", blob, name)
    ck.mark_consumed(be, "fleet-checkpoints", "tm", name)
    assert ck.is_consumed(be, "fleet-checkpoints", "tm", name)
    # markers are invisible to the blob listing
    assert ck.list_checkpoints(be, "fleet-checkpoints") == {"tm": [name]}
    g, _r, _lc, fc = _member(kv, be, "gen-m")
    restores0 = STATS["restores"]
    fc.tick()
    assert _samples(g.instance("tm")) == {}          # NOT merged
    assert STATS["restores"] == restores0
    assert ck.list_checkpoints(be, "fleet-checkpoints") == {}  # cleaned
    assert not ck.is_consumed(be, "fleet-checkpoints", "tm", name)


def test_remove_instance_releases_pool_pages():
    from tempo_tpu.registry import pages as pgs

    pool = pgs.PagePool(pgs.PagePoolConfig(enabled=True, page_rows=64,
                                           arena_slots=4096))
    with pgs.use(pool):
        g = Generator(_cfg(), instance_id="gen-p", now=lambda: NOW)
        g.push_spans("pp", _spans(6))
        assert g.instance("pp").state_layout == "paged"
        free_before = pool.free_pages()
        assert g.remove_instance("pp") is not None
        assert g.tenants() == []
        assert pool.free_pages() > free_before
        assert pool.free_pages() == pool.total_pages()


# ---------------------------------------------------------------------------
# real child process: worker spawn/reap plumbing (conftest fixture)
# ---------------------------------------------------------------------------


def test_fleet_worker_process_spawn_and_reap(fleet_procs, tmp_path):
    """One real fleet member process: comes up ready, serves /status
    with the fleet + rings blocks, dies cleanly on terminate. The
    fixture guarantees the reap even if the asserts fail."""
    import json
    import socket
    import urllib.request

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cfg = tmp_path / "member.yaml"
    cfg.write_text(f"""
target: metrics-generator
server: {{http_listen_port: {port}}}
ring_kv_url: local
storage:
  backend: local
  local_path: {tmp_path}/blocks
  wal_path: {tmp_path}/wal
fleet: {{enabled: true, rebalance_interval_s: 0.5}}
distributor: {{generator_placement: tenant}}
generator:
  processors: [span-metrics]
  spanmetrics: {{sketch: moments}}
""")
    p = fleet_procs(["--config", str(cfg)])
    assert p.ready["port"] == port
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/status",
                                timeout=10) as r:
        st = json.loads(r.read())
    assert st["fleet"] is not None
    assert st["fleet"]["instance"].startswith("generator")
    assert "generator" in st["rings"]
    members = st["rings"]["generator"]["members"]
    assert len(members) == 1 and members[0]["ownership_ratio"] == 1.0
    p.terminate()
    assert p.wait(timeout=15) is not None


def test_sigkill_restart_replays_wal_bit_identically(fleet_procs,
                                                     tmp_path):
    """The SIGKILL variant of the worker handoff test: kill -9 a member
    (no drain, no shutdown checkpoint), restart it over the same dirs,
    and assert the ingest-WAL replay restores every ACKED push —
    collect() and quantile() bit-identical to an uninterrupted in-process
    oracle fed the same payloads."""
    import json
    import socket
    import urllib.request

    import numpy as np

    from tempo_tpu.generator.generator import Generator
    from tempo_tpu.model.otlp import encode_spans_otlp
    from tempo_tpu.overrides import Overrides
    from tempo_tpu.overrides.limits import Limits
    from tempo_tpu.rpc import RemoteGeneratorClient

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cfg = tmp_path / "member.yaml"
    cfg.write_text(f"""
target: metrics-generator
server: {{http_listen_port: {port}}}
ring_kv_url: local
usage_stats_enabled: false
storage:
  backend: local
  local_path: {tmp_path}/blocks
  wal_path: {tmp_path}/wal
wal: {{enabled: true, dir: {tmp_path}/gwal}}
fleet: {{enabled: true, rebalance_interval_s: 5.0}}
distributor: {{generator_placement: tenant}}
generator:
  processors: [span-metrics]
overrides_defaults:
  generator:
    processors: [span-metrics]
    max_active_series: 2048
    ingestion_time_range_slack_s: 0.0
    collection_interval_s: 3600.0
    sketch: dd
""")
    rng = np.random.default_rng(11)
    now_ns = int(NOW * 1e9)
    payloads = [encode_spans_otlp([
        dict(trace_id=rng.bytes(16), span_id=rng.bytes(8),
             name=f"op-{i % 4}", service=f"svc-{i % 3}", kind=2,
             status_code=0, start_unix_nano=now_ns,
             end_unix_nano=now_ns + int(rng.integers(1, 5e8)))
        for i in range(24)]) for _ in range(3)]

    p = fleet_procs(["--config", str(cfg)])
    client = RemoteGeneratorClient(f"http://127.0.0.1:{port}",
                                   timeout_s=30.0)
    for pl in payloads:
        assert client.push_otlp("t1", pl) == 24
    p.kill()                             # SIGKILL: nothing drains
    assert p.wait(timeout=10) is not None

    p2 = fleet_procs(["--config", str(cfg)])   # same dirs, same WAL
    req = urllib.request.Request(
        f"http://127.0.0.1:{p2.ready['port']}"
        "/internal/generator/collect?ts_ms=1",
        headers={"X-Scope-OrgID": "t1"})
    doc = json.loads(urllib.request.urlopen(req, timeout=30).read())
    got = {(s["name"], tuple(tuple(kv) for kv in s["labels"])):
           s["value"] for s in doc["samples"]}
    req = urllib.request.Request(
        f"http://127.0.0.1:{p2.ready['port']}"
        "/internal/generator/quantile?q=0.99",
        headers={"X-Scope-OrgID": "t1"})
    qdoc = json.loads(urllib.request.urlopen(req, timeout=30).read())
    got_q = {tuple(tuple(kv) for kv in e["labels"]): e["value"]
             for e in qdoc["quantiles"]}

    lim = Limits()
    lim.generator.processors = ("span-metrics",)
    lim.generator.max_active_series = 2048
    lim.generator.ingestion_time_range_slack_s = 0.0
    lim.generator.collection_interval_s = 3600.0
    lim.generator.sketch = "dd"
    oracle = Generator(GeneratorConfig(), instance_id="oracle",
                       overrides=Overrides(defaults=lim))
    for pl in payloads:
        oracle.push_otlp("t1", pl)
    inst = oracle.instance("t1")
    inst.drain()
    want = {(s.name, tuple(s.labels)): s.value
            for s in inst.registry.collect(ts_ms=1)
            if not s.is_stale_marker}
    _assert_merge_equal(got, want)
    want_q = {tuple(k): v for k, v in
              inst.processors["span-metrics"].quantile(0.99).items()}
    assert got_q == want_q


def test_kv_only_worker(fleet_procs):
    """The standalone /kv CAS server speaks the RemoteKVStore wire."""
    from tempo_tpu.ring.kv import RemoteKVStore

    p = fleet_procs(["--kv-only"])
    kv = RemoteKVStore(f"http://127.0.0.1:{p.ready['port']}",
                       poll_interval_s=0.05)
    try:
        assert kv.get("nope") is None
        kv.cas("k", lambda cur: {"v": (cur or {}).get("v", 0) + 1})
        kv.cas("k", lambda cur: {"v": cur["v"] + 1})
        assert kv.get("k") == {"v": 2}
        kv.delete("k")
        assert kv.get("k") is None
        # a Lifecycler round-trips ring descs through it
        lc = Lifecycler(kv, "gen-remote", n_tokens=8, now=lambda: NOW)
        ring = Ring(kv=kv, key="ring", replication_factor=1,
                    now=lambda: NOW)
        assert ring.owner_of("x").id == "gen-remote"
        lc.leave()
        assert kv.get("ring") == {}
    finally:
        kv.shutdown()
