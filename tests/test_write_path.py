"""Write path: distributor → ring RF3 → ingester → WAL → block → flush."""

from __future__ import annotations

import numpy as np
import pytest

from tempo_tpu.backend.mem import MemBackend
from tempo_tpu.distributor import Distributor, DistributorConfig
from tempo_tpu.distributor.distributor import (
    REASON_INVALID_TRACE_ID,
    RateLimited,
)
from tempo_tpu.ingester import Ingester, IngesterConfig
from tempo_tpu.ingester.instance import InstanceConfig
from tempo_tpu.overrides import Overrides
from tempo_tpu.ring import ACTIVE, InstanceDesc, Ring
from tempo_tpu.ring.ring import _instance_tokens


def mkspan(tid: bytes, sid: bytes, name="op", svc="svc", t0=10**18,
           dur=1_000_000, **kw):
    return {"trace_id": tid, "span_id": sid, "name": name, "service": svc,
            "start_unix_nano": t0, "end_unix_nano": t0 + dur, **kw}


def make_clock():
    t = [1000.0]
    def now():
        return t[0]
    return t, now


@pytest.fixture
def rig(tmp_path):
    """3 ingesters on a ring + 1 distributor, manual clock."""
    t, now = make_clock()
    cfg = IngesterConfig(
        instance=InstanceConfig(trace_idle_s=2.0, trace_live_s=10.0,
                                max_block_duration_s=30.0))
    backend = MemBackend()
    ring = Ring(replication_factor=3, now=now)
    ingesters = {}
    for i in range(3):
        ing = Ingester(str(tmp_path / f"ing{i}"), flush_writer=backend,
                       cfg=cfg, now=now, instance_id=f"ing-{i}")
        ingesters[f"ing-{i}"] = ing
        ring.register(InstanceDesc(id=f"ing-{i}", state=ACTIVE,
                                   tokens=_instance_tokens(f"ing-{i}", 64),
                                   heartbeat_ts=now()))
    dist = Distributor(ring, ingesters, cfg=DistributorConfig(rf=3), now=now)
    return t, now, backend, ring, ingesters, dist


def test_rf3_replication(rig):
    t, now, backend, ring, ingesters, dist = rig
    spans = [mkspan(bytes([i]) * 16, bytes([j]) * 8)
             for i in range(1, 11) for j in range(1, 4)]
    errs = dist.push_spans("t1", spans)
    assert errs == {}
    # every trace lands on all 3 ingesters (RF3 over 3 instances)
    for ing in ingesters.values():
        inst = ing.instance("t1")
        assert len(inst.live) == 10
    # spans grouped per trace
    inst = ingesters["ing-0"].instance("t1")
    assert len(inst.live.view().spans_of(bytes([1]) * 16)) == 3


def test_invalid_trace_id_discarded(rig):
    *_, dist = rig
    errs = dist.push_spans("t1", [mkspan(b"", b"\x01" * 8)])
    assert errs[REASON_INVALID_TRACE_ID] == 1


def test_rate_limit(rig):
    t, now, backend, ring, ingesters, dist = rig
    dist.overrides = Overrides()
    dist.overrides.set_tenant_patch(
        "t1", {"ingestion": {"rate_limit_bytes": 100, "burst_size_bytes": 300}})
    spans = [mkspan(bytes([i]) * 16, b"\x01" * 8) for i in range(1, 9)]
    with pytest.raises(RateLimited):
        dist.push_spans("t1", spans)   # ~1600B > 300B burst
    # refill after time passes
    t[0] += 10.0
    assert dist.push_spans("t1", spans[:1]) == {}


def test_quorum_survives_one_ingester_down(rig):
    t, now, backend, ring, ingesters, dist = rig

    class Down:
        def push(self, tenant, traces):
            raise RuntimeError("down")

    dist.ingester_clients = dict(ingesters)
    dist.ingester_clients["ing-1"] = Down()
    errs = dist.push_spans("t1", [mkspan(b"\x05" * 16, b"\x01" * 8)])
    assert errs == {}
    assert dist.metrics["traces_pushed_total"] == 1


def test_cut_complete_flush_cycle(rig, tmp_path):
    t, now, backend, ring, ingesters, dist = rig
    spans = [mkspan(bytes([i]) * 16, bytes([j]) * 8)
             for i in range(1, 6) for j in range(1, 3)]
    dist.push_spans("t1", spans)
    ing = ingesters["ing-0"]
    # nothing idle yet
    ing.sweep_instance("t1")
    assert ing.instance("t1").head is None
    # idle out the traces → head block
    t[0] += 5.0
    ing.sweep_instance("t1")
    inst = ing.instance("t1")
    assert len(inst.live) == 0
    assert inst.head is not None
    # age the block → seal + complete + flush
    t[0] += 31.0
    ing.sweep_instance("t1")
    assert inst.head is None
    n = ing.flush_tick()
    assert n >= 1
    ing.flush_tick()
    assert len(inst.complete) == 1
    meta = next(iter(inst.complete.values())).meta
    assert meta.total_objects == 5
    # flushed to object storage: meta + data present
    from tempo_tpu.backend.meta import read_block_meta
    m2 = read_block_meta(backend, meta.block_id, "t1")
    assert m2.total_objects == 5


def test_find_trace_spans_all_stages(rig):
    t, now, backend, ring, ingesters, dist = rig
    tid = b"\x07" * 16
    dist.push_spans("t1", [mkspan(tid, b"\x01" * 8)])
    ing = ingesters["ing-0"]
    inst = ing.instance("t1")
    assert inst.find_trace_by_id(tid) is not None          # live
    t[0] += 5.0
    ing.sweep_instance("t1")
    assert inst.find_trace_by_id(tid) is not None          # head WAL
    t[0] += 31.0
    ing.sweep_instance("t1")
    ing.flush_tick(); ing.flush_tick()
    spans = inst.find_trace_by_id(tid)                     # complete block
    assert spans is not None and len(spans) == 1
    assert inst.find_trace_by_id(b"\xff" * 16) is None


def test_wal_replay_after_crash(tmp_path):
    t, now = make_clock()
    backend = MemBackend()
    cfg = IngesterConfig(instance=InstanceConfig(trace_idle_s=1.0))
    ing = Ingester(str(tmp_path / "ing"), flush_writer=backend, cfg=cfg,
                   now=now, instance_id="ing-0")
    tid = b"\x09" * 16
    ing.push("t1", [(tid, [mkspan(tid, b"\x01" * 8)])])
    t[0] += 2.0
    ing.instance("t1").cut_complete_traces()   # data in WAL, then "crash"
    del ing
    ing2 = Ingester(str(tmp_path / "ing"), flush_writer=backend, cfg=cfg,
                    now=now, instance_id="ing-0")
    # replay queued the WAL block for completion
    assert ing2.instance("t1").find_trace_by_id(tid) is not None
    ing2.flush_all()
    from tempo_tpu.backend.raw import blocks as list_blocks
    assert len(list_blocks(backend, "t1")) == 1


def test_one_wal_segment_per_cut_sweep(tmp_path):
    """A sweep appends every trace it cut as ONE fsynced segment (the
    reference appends each and flushes the head block once); a segment
    per trace cost milliseconds per trace. Nothing of the sweep is lost:
    the completed block holds every trace, spans merged per trace."""
    t, now = make_clock()
    ing = Ingester(str(tmp_path / "ing"), flush_writer=MemBackend(),
                   cfg=IngesterConfig(instance=InstanceConfig(trace_idle_s=1.0)),
                   now=now, instance_id="ing-0")
    tids = [bytes([i]) * 16 for i in range(1, 9)]
    ing.push("t1", [(tid, [mkspan(tid, b"\x01" * 8), mkspan(tid, b"\x02" * 8)])
                    for tid in tids])
    inst = ing.instance("t1")
    assert inst.cut_complete_traces(immediate=True) == len(tids)
    assert len(inst.head.segments()) == 1
    assert inst.head.spans_appended == 2 * len(tids)
    ing.push("t1", [(tids[0], [mkspan(tids[0], b"\x03" * 8)])])
    assert inst.cut_complete_traces(immediate=True) == 1
    assert len(inst.head.segments()) == 2
    got = dict(inst.head.complete())
    assert sorted(got) == tids
    assert [len(got[tid]) for tid in tids] == [3] + [2] * 7


def test_shutdown_flushes_everything(rig):
    t, now, backend, ring, ingesters, dist = rig
    dist.push_spans("t1", [mkspan(bytes([i]) * 16, b"\x01" * 8)
                           for i in range(1, 4)])
    for ing in ingesters.values():
        ing.shutdown()
    from tempo_tpu.backend.raw import blocks as list_blocks
    assert len(list_blocks(backend, "t1")) == 3  # one block per ingester


def test_push_error_counted_once_across_replicas(rig):
    """A trace rejected by all RF replicas is ONE discarded trace."""
    t, now, backend, ring, ingesters, dist = rig
    for ing in ingesters.values():
        ing.overrides.set_tenant_patch(
            "t1", {"read": {"max_bytes_per_trace": 10}})
    errs = dist.push_spans("t1", [mkspan(b"\x01" * 16, b"\x01" * 8)])
    assert errs == {"trace_too_large": 1}
    assert dist.discarded["trace_too_large"] == 1


def test_replay_dedupes_wal_handles(tmp_path):
    """Restart with both a WAL block and a local complete block must not
    leave duplicate WALBlock handles that crash reads after completion."""
    t, now = make_clock()
    backend = MemBackend()
    cfg = IngesterConfig(instance=InstanceConfig(trace_idle_s=1.0))
    ing = Ingester(str(tmp_path / "i"), flush_writer=backend, cfg=cfg,
                   now=now, instance_id="ing-0")
    tid1, tid2 = b"\x01" * 16, b"\x02" * 16
    ing.push("t1", [(tid1, [mkspan(tid1, b"\x01" * 8)])])
    t[0] += 2.0
    ing.sweep_instance("t1")
    sealed = ing.instance("t1").cut_block_if_ready(immediate=True)
    ing.instance("t1").complete_block(sealed)          # one local complete block
    ing.push("t1", [(tid2, [mkspan(tid2, b"\x02" * 8)])])
    t[0] += 2.0
    ing.instance("t1").cut_complete_traces()           # one WAL block, then crash
    del ing
    ing2 = Ingester(str(tmp_path / "i"), flush_writer=backend, cfg=cfg,
                    now=now, instance_id="ing-0")
    inst = ing2.instance("t1")
    ids = [b.block_id for b in inst.completing]
    assert len(ids) == len(set(ids))                   # no duplicate handles
    ing2.flush_all()
    # both traces survive, reads don't crash on cleared WAL dirs
    assert inst.find_trace_by_id(tid1) is not None
    assert inst.find_trace_by_id(tid2) is not None


def test_generator_tee(rig):
    t, now, backend, ring, ingesters, dist = rig

    class CapturingGen:
        """Tee protocol: OTLP bytes on the wire (PushOTLP), decoded here to
        count what arrived."""
        def __init__(self):
            self.spans = []
        def push_otlp(self, tenant, data):
            from tempo_tpu.model.otlp import spans_from_otlp_proto
            got = list(spans_from_otlp_proto(data))
            self.spans.extend(got)
            return len(got)

    gens = {"gen-0": CapturingGen(), "gen-1": CapturingGen()}
    gring = Ring(replication_factor=1, now=now)
    for gid in gens:
        gring.register(InstanceDesc(id=gid, state=ACTIVE,
                                    tokens=_instance_tokens(gid, 64),
                                    heartbeat_ts=now()))
    dist.generator_ring = gring
    dist.generator_clients = gens
    dist.overrides.set_tenant_patch(
        "t1", {"generator": {"processors": ["span-metrics"]}})
    spans = [mkspan(bytes([i]) * 16, b"\x01" * 8) for i in range(1, 21)]
    dist.push_spans("t1", spans)
    total = sum(len(g.spans) for g in gens.values())
    assert total == 20          # RF1: each span at exactly one generator
    assert all(len(g.spans) > 0 for g in gens.values())  # spread over both


def test_generator_tee_raw_otlp_slicing(rig):
    """An OTLP receiver hands the raw payload to push_spans; the tee must
    forward raw wire slices (no re-encode) partitioned per generator, with
    content identical to the decoded spans."""
    import numpy as np

    from tempo_tpu import native
    from tempo_tpu.model.otlp import encode_spans_otlp, spans_from_otlp_proto

    t, now, backend, ring, ingesters, dist = rig

    class CapturingGen:
        def __init__(self):
            self.spans = []
        def push_otlp(self, tenant, data):
            got = list(spans_from_otlp_proto(data))
            self.spans.extend(got)
            return len(got)

    gens = {"gen-0": CapturingGen(), "gen-1": CapturingGen()}
    gring = Ring(replication_factor=1, now=now)
    for gid in gens:
        gring.register(InstanceDesc(id=gid, state=ACTIVE,
                                    tokens=_instance_tokens(gid, 64),
                                    heartbeat_ts=now()))
    dist.generator_ring = gring
    dist.generator_clients = gens
    dist.overrides.set_tenant_patch(
        "t1", {"generator": {"processors": ["span-metrics"]}})

    src = [mkspan(bytes([i]) * 16, b"\x01" * 8,
                  attrs={"http.status_code": 200 + i},
                  res_attrs={"service.name": f"svc-{i % 3}"})
           for i in range(1, 21)]
    raw = encode_spans_otlp(src)
    decoded = list(spans_from_otlp_proto(raw))
    assert len(decoded) == 20
    dist.push_spans("t1", decoded, raw_otlp=raw)

    got = sorted((s["trace_id"], s) for g in gens.values() for s in g.spans)
    want = sorted((s["trace_id"], s) for s in decoded)
    assert len(got) == 20
    for (gt, gs), (wt, ws) in zip(got, want):
        assert gt == wt
        assert gs == ws          # full span dict round-trips the slice
    if native.available():
        assert all(len(g.spans) > 0 for g in gens.values())


def test_columnar_push_matches_dict_path(rig):
    """distributor.push_otlp (no span dicts in the distributor) must land
    the same traces, reasons, and usage as push_spans over the same
    payload — including RF3 replication content at every ingester."""
    import numpy as np

    from tempo_tpu import native
    from tempo_tpu.model.otlp import encode_spans_otlp, spans_from_otlp_proto

    if not native.available():
        import pytest
        pytest.skip("native scanner required")

    t, now, backend, ring, ingesters, dist = rig
    src = []
    for i in range(1, 16):
        src.append(mkspan(bytes([i]) * 16, bytes([i]) * 8,
                          name=f"cp-{i % 3}",
                          attrs={"http.status_code": 200 + i},
                          res_attrs={"service.name": f"cs-{i % 2}"}))
    # two spans of one trace in different resources + an invalid-id span
    src.append(mkspan(bytes([1]) * 16, b"\xaa" * 8, name="cp-x",
                      res_attrs={"service.name": "cs-1"}))
    raw = encode_spans_otlp(src) + encode_spans_otlp(
        [{**mkspan(b"", b"\x01" * 8), "trace_id": b""}])

    errs = dist.push_otlp("t1", raw)
    assert errs.get("invalid_trace_id") == 1
    # every ingester holds every valid trace (RF3, 3 members)
    for i in range(1, 16):
        held = sum(1 for ing in ingesters.values()
                   if ing.find_trace_by_id("t1", bytes([i]) * 16))
        assert held == 3, (i, held)
    # the multi-resource trace carries both spans everywhere
    for ing in ingesters.values():
        spans = ing.find_trace_by_id("t1", bytes([1]) * 16)
        assert {s["span_id"] for s in spans} == {bytes([1]) * 8, b"\xaa" * 8}
    # the invalid-id span was DISCARDED, not replicated (regression: the
    # full-coverage raw-payload fast path must not bypass validation)
    for ing in ingesters.values():
        assert not ing.find_trace_by_id("t1", b"")
    # usage attribution by service matches the dict path's labels
    snap = dist.usage.prometheus_text()
    assert 'service="cs-0"' in snap and 'service="cs-1"' in snap
    # metrics counters moved
    assert dist.metrics["spans_received_total"] >= 17
    assert dist.dataquality.snapshot() is not None

    # parity of ingester CONTENT vs the dict path on a fresh rig tenant
    decoded = list(spans_from_otlp_proto(raw))
    errs2 = dist.push_spans("t2", decoded)
    assert errs2.get("invalid_trace_id") == 1
    for i in range(1, 16):
        a = next(ing.find_trace_by_id("t1", bytes([i]) * 16)
                 for ing in ingesters.values())
        b = next(ing.find_trace_by_id("t2", bytes([i]) * 16)
                 for ing in ingesters.values())
        ka = sorted((s["span_id"], s["name"]) for s in a)
        kb = sorted((s["span_id"], s["name"]) for s in b)
        assert ka == kb


def test_kafka_receiver_consumes_topic(rig):
    """Kafka receiver (shim.go:165-171 "kafka"): OTLP payloads produced
    to a topic by an external pipeline are consumed into the distributor;
    offsets commit after the push (at-least-once)."""
    from tempo_tpu.distributor.receiver_kafka import (KafkaReceiver,
                                                      KafkaReceiverConfig)
    from tempo_tpu.ingest.bus import Bus
    from tempo_tpu.model.otlp import encode_spans_otlp

    t, now, backend, ring, ingesters, dist = rig
    bus = Bus(n_partitions=2)
    spans = [mkspan(bytes([40]) * 16, bytes([1]) * 8, name="kr-op",
                    res_attrs={"service.name": "kr-svc"})]
    bus.produce(0, "t1", encode_spans_otlp(spans))
    bus.produce(1, "t1", encode_spans_otlp(
        [mkspan(bytes([41]) * 16, bytes([2]) * 8, name="kr-op2")]))
    rx = KafkaReceiver(bus, dist, KafkaReceiverConfig(partitions=(0, 1)))
    assert rx.run_once() == 2
    held = sum(1 for ing in ingesters.values()
               if ing.find_trace_by_id("t1", bytes([40]) * 16))
    assert held == 3                       # RF3 replication applied
    assert bus.committed(rx.cfg.group, 0) == 1
    assert bus.committed(rx.cfg.group, 1) == 1
    assert rx.run_once() == 0              # nothing new: offsets held


def test_forwarder_filter_policies(rig):
    """pkg/spanfilter-shaped per-tenant policies on the forwarder tee
    (the OTTL-filter analog): regex include + strict exclude."""
    from tempo_tpu.distributor.forwarder import (Forwarder,
                                                 ForwarderConfig)

    t, now, backend, ring, ingesters, dist = rig
    got: list = []
    fwd = Forwarder(
        ForwarderConfig(
            name="f1",
            filter_policies=[{
                "include": {"match_type": "regex",
                            "attributes": [{"key": "span.name",
                                            "value": "keep-.*"}]},
                "exclude": {"match_type": "strict",
                            "attributes": [{"key": "span.kind",
                                            "value": "SPAN_KIND_CLIENT"}]},
            }]),
        sink=got.extend)
    dist.forwarders.register("t1", fwd)
    spans = [
        mkspan(bytes([50]) * 16, bytes([1]) * 8, name="keep-a", kind=2),
        mkspan(bytes([51]) * 16, bytes([2]) * 8, name="keep-b", kind=3),
        mkspan(bytes([52]) * 16, bytes([3]) * 8, name="drop-c", kind=2),
    ]
    dist.push_spans("t1", spans)
    fwd.flush()
    fwd.shutdown()
    names = sorted(s["name"] for s in got)
    assert names == ["keep-a"], names      # regex kept keep-*, CLIENT excluded
