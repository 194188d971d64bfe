"""Pushes go through the distributor one at a time (`utils/turn.py`).

- the turn alone: waiters are served in arrival order, a nested entry
  does not wait for itself, a turn given up early goes to the next waiter
  and is not taken again, a wait for a lock another thread holds gives
  the turn up first, and the span `distributor.turn` is the wait and
  nothing else;
- a bare `Distributor`: `push_otlp`'s fall-back into `push_spans` does
  not deadlock, and a push that raises gives the turn back;
- a bare `Distributor` whose ingester or generator client is not of its
  process (a `target: distributor` process): the turn is given up before
  the send, so four pushes overlap a slow round trip and a replica that
  hangs holds up no other tenant; a client of the process keeps it;
- the served App (`k6-single-binary`, small): eight clients over HTTP
  never stand two in `_push_staged` together with their turn, every push
  is acknowledged and the collected counts are exact; a 400, a 429 and a 500 each give
  the turn back;
- a tenant's log alone: under `fsync: batch` and `fsync: interval` an
  append starts its fsync with the turn given up;
- the served durable App (`k6-single-binary-wal`, small) with a slow
  fsync: the turn is not held through the wait for the fsync, so four
  pushes at once share fsyncs and finish well before four in a row
  do, and each 2xx still follows an fsync that covers its record.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import pytest

from chipbench import lib, spans
from tempo_tpu.generator import wal as wal_mod
from tempo_tpu.utils import tracing
from tempo_tpu.utils import turn as turn_mod
from tempo_tpu.utils.turn import Turn
from tests.test_wal_cell import SCHEMA, SHAPE, SMALL, _abandon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483693
WAIT_S = 30.0


def _threads(n: int, body) -> list:
    ts = [threading.Thread(target=body, args=(k,), daemon=True)
          for k in range(n)]
    for t in ts:
        t.start()
    return ts


def _join(ts: list) -> None:
    for t in ts:
        t.join(WAIT_S)
        assert not t.is_alive()


def _until(cond) -> None:
    end = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < end
        time.sleep(0.001)


# -- the turn alone --------------------------------------------------------

def test_waiters_are_served_in_arrival_order():
    turn, order = Turn(), []

    def waiter(k: int) -> None:
        with turn.served("t"):
            order.append(k)

    with turn.served("t"):
        ts = []
        for k in range(6):      # each is in the queue before the next comes
            ts += _threads(1, lambda _k, k=k: waiter(k))
            _until(lambda: len(turn._waiting) == k + 1)
    _join(ts)
    assert order == list(range(6))
    assert not turn._taken and not turn._waiting


def test_a_nested_entry_does_not_wait_for_itself():
    turn = Turn()
    with turn.served("t"):
        with turn.served("t"):
            assert turn._taken
        assert turn._taken          # the inner block gave nothing back
    assert not turn._taken


def test_a_turn_given_up_goes_to_the_next_and_is_not_taken_again():
    turn, inside, leave = Turn(), threading.Event(), threading.Event()

    def second(_k: int) -> None:
        with turn.served("t"):
            inside.set()
            assert leave.wait(WAIT_S)

    with turn.served("t"):
        ts = _threads(1, second)
        _until(lambda: len(turn._waiting) == 1)
        turn_mod.give_up()
        assert inside.wait(WAIT_S)      # beside this block, not after it
        turn_mod.give_up()              # nothing left to give: no effect
        assert turn._taken
    assert turn._taken                  # the first block's end took nothing
    leave.set()
    _join(ts)
    assert not turn._taken
    turn_mod.give_up()                  # outside any turn: no effect


def test_a_wait_for_a_held_lock_gives_the_turn_up_first():
    turn, lock = Turn(), threading.RLock()
    with turn.served("t"):
        with turn_mod.waiting_for(lock), turn_mod.waiting_for(lock):
            assert turn_mod._mine.holds is turn     # free, or its own
    entered, leave = threading.Event(), threading.Event()

    def holder(_k: int) -> None:
        with lock:
            entered.set()
            assert leave.wait(WAIT_S)

    def second(_k: int) -> None:
        with turn.served("t"):
            leave.set()             # only the next turn lets the lock go

    hs = _threads(1, holder)
    assert entered.wait(WAIT_S)
    with turn.served("t"):
        ts = _threads(1, second)
        _until(lambda: len(turn._waiting) == 1)
        with turn_mod.waiting_for(lock):    # held through the wait, this
            assert turn_mod._mine.holds is None         # would never end
    _join(hs + ts)
    assert not turn._taken


def test_the_span_is_the_wait_and_nothing_else():
    turn, held = Turn(), threading.Event()

    def holder(_k: int) -> None:
        with turn.served("t"):
            held.set()
            time.sleep(0.2)

    ts = _threads(1, holder)
    assert held.wait(WAIT_S)
    with turn.served("t"):
        time.sleep(0.3)
    _join(ts)
    count, dur_ns = tracing.span_rows()[("distributor.turn", "clear")][:2]
    assert count == 2                   # one a turn, waited for or not
    assert 0.1e9 < dur_ns < 0.29e9      # the 0.2 s wait, not the 0.3 s push


def test_sixteen_threads_never_share_a_turn():
    """More threads than cores, the interpreter switching every 0.1 ms:
    a read-modify-write inside the turn loses no update, and a thread
    that gave its turn up early is outside it."""
    turn, rounds = Turn(), 150
    count, inside, most = [0], [0], [0]

    def body(k: int) -> None:
        for i in range(rounds):
            with turn.served("t"):
                inside[0] += 1
                most[0] = max(most[0], inside[0])
                seen = count[0]
                time.sleep(0)           # hands the interpreter over
                count[0] = seen + 1
                inside[0] -= 1
                if (i + k) % 3 == 0:
                    turn_mod.give_up()
                    time.sleep(0)       # beside the next turn, not in it

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        _join(_threads(16, body))
    finally:
        sys.setswitchinterval(before)
    assert count[0] == 16 * rounds and most[0] == 1
    assert not turn._taken and not turn._waiting


# -- a bare Distributor ----------------------------------------------------

class _NullIng:
    def push(self, tenant, traces):
        return [None] * len(traces)

    def push_otlp(self, tenant, payload):
        return {}


def _mini_distributor(patch: dict):
    from tempo_tpu.distributor import Distributor
    from tempo_tpu.overrides import Overrides
    from tempo_tpu.ring import ACTIVE, InstanceDesc, Ring
    from tempo_tpu.ring.ring import _instance_tokens

    ring = Ring(replication_factor=1)
    ring.register(InstanceDesc(id="i0", state=ACTIVE,
                               tokens=_instance_tokens("i0", 64),
                               heartbeat_ts=time.time()))
    ov = Overrides()
    ov.set_tenant_patch("t", {"ingestion": dict(
        patch, rate_limit_bytes=1 << 40, burst_size_bytes=1 << 40)})
    return Distributor(ring, {"i0": _NullIng()}, overrides=ov)


def _payload(idx: int = 0, tenant_idx: int = 0) -> bytes:
    return spans.encode_push(SHAPE, spans.draw_push(
        SEED, tenant_idx, idx, SHAPE, SCHEMA, time.time_ns() + idx))


def test_the_fall_back_into_push_spans_does_not_wait_for_itself():
    # attribute truncation needs span dicts: `push_otlp` decodes them and
    # calls `push_spans`, both doors of the same turn
    d = _mini_distributor({"max_attribute_bytes": 64})
    seen = []
    real = d._push_spans
    d._push_spans = lambda *a: (seen.append(d.turn._taken), real(*a))[1]
    ts = _threads(1, lambda _k: seen.append(d.push_otlp("t", _payload())))
    _join(ts)
    assert seen == [True, {}] and not d.turn._taken
    assert d.metrics["spans_received_total"] == SHAPE.n


def test_a_push_that_raises_gives_the_turn_back():
    d = _mini_distributor({})

    def broken(tenant, lim, sz, n_spans):
        raise KeyError("an admission that raises")

    d._admit = broken
    for _ in range(2):              # the second would wait for ever
        with pytest.raises(KeyError):
            d.push_otlp("t", _payload())
        assert not d.turn._taken


# -- a Distributor whose clients are not of its process ---------------------

SEND_S = 0.3


class _FarClient:
    """What `rpc.py` and `grpcplane/client.py` are to a distributor: a
    send is a wait for the network. `gate` stands in for a round trip
    that never comes back (their timeout is 30 s)."""

    def __init__(self) -> None:
        self.holds, self.gate, self.in_send = [], {}, threading.Event()

    def push(self, tenant, traces):
        self.push_otlp(tenant, b"")
        return [None] * len(traces)

    def push_otlp(self, tenant, payload):
        self.holds.append(getattr(turn_mod._mine, "holds", None))
        self.in_send.set()
        gate = self.gate.get(tenant)
        if gate is None:
            time.sleep(SEND_S)
        else:
            assert gate.wait(WAIT_S)
        return {}


class _NearClient(_FarClient):
    in_process = True


def _far_distributor(far: str, client):
    """One replica of each ring; `far` says which of the two is `client`,
    the other answers at once from this process."""
    from tempo_tpu.ring import ACTIVE, InstanceDesc, Ring
    from tempo_tpu.ring.ring import _instance_tokens

    d = _mini_distributor({})
    for tenant in ("t", "u"):
        d.overrides.set_tenant_patch(tenant, {
            "ingestion": {"rate_limit_bytes": 1 << 40,
                          "burst_size_bytes": 1 << 40},
            "generator": {"processors": ["span-metrics"]}})
    near = _NullIng()
    near.in_process = True
    d.ingester_clients = {"i0": client if far == "ingester" else near}
    d.generator_ring = Ring(replication_factor=1)
    d.generator_ring.register(InstanceDesc(
        id="g0", state=ACTIVE, tokens=_instance_tokens("g0", 64),
        heartbeat_ts=time.time()))
    d.generator_clients = {"g0": client if far == "generator" else near}
    return d


@pytest.mark.parametrize("far", ["ingester", "generator"])
def test_four_pushes_overlap_a_send_that_leaves_the_process(far):
    client = _FarClient()
    d = _far_distributor(far, client)
    t0 = time.monotonic()
    _join(_threads(4, lambda k: d.push_otlp("t", _payload(k))))
    together = time.monotonic() - t0
    # held through the send they would take 4 x SEND_S, one after another
    assert SEND_S <= together < 2.5 * SEND_S
    assert client.holds == [None] * 4 and not d.turn._taken
    assert d.metrics["spans_received_total"] == 4 * SHAPE.n \
        and d.metrics["push_failures_total"] == 0


@pytest.mark.parametrize("far", ["ingester", "generator"])
def test_a_replica_that_hangs_holds_up_no_other_tenant(far):
    client = _FarClient()
    client.gate["t"] = threading.Event()        # tenant t's send hangs
    d = _far_distributor(far, client)
    hung = _threads(1, lambda _k: d.push_otlp("t", _payload(0)))
    assert client.in_send.wait(WAIT_S)
    t0 = time.monotonic()
    for k in range(3):
        assert d.push_otlp("u", _payload(1 + k, 1)) == {}
    assert time.monotonic() - t0 < 3 * SEND_S + 1.0
    assert hung[0].is_alive() and not d.turn._taken
    client.gate["t"].set()
    _join(hung)
    assert client.holds == [None] * 4


def test_a_client_of_the_process_is_pushed_to_inside_the_turn():
    client = _NearClient()
    client.gate["t"] = threading.Event()
    client.gate["t"].set()
    d = _far_distributor("ingester", client)
    _join(_threads(3, lambda k: d.push_otlp("t", _payload(k))))
    assert client.holds == [d.turn] * 3 and not d.turn._taken


def test_only_the_service_objects_are_of_the_process():
    from tempo_tpu.generator.generator import Generator
    from tempo_tpu.grpcplane import GrpcGeneratorClient, GrpcIngesterClient
    from tempo_tpu.ingester.ingester import Ingester
    from tempo_tpu.rpc import RemoteGeneratorClient, RemoteIngesterClient

    assert Ingester.in_process and Generator.in_process
    for far in (RemoteIngesterClient, RemoteGeneratorClient,
                GrpcIngesterClient, GrpcGeneratorClient):
        assert not getattr(far, "in_process", False)


def test_the_tee_gives_the_turn_up_before_it_pauses(monkeypatch):
    # tenant placement: an owner that refuses is asked again after a
    # pause, and the pause is no wait of the interpreter's
    from tempo_tpu.distributor import distributor as dist_mod

    d = _far_distributor("ingester", _NearClient())
    d.cfg.generator_placement = "tenant"
    slept = []
    monkeypatch.setattr(dist_mod.time, "sleep", lambda s: slept.append(
        getattr(turn_mod._mine, "holds", None)))

    def refuse(inst, items):
        raise ConnectionRefusedError("the owner is gone")

    def in_a_turn(_k: int) -> None:
        with d.turn.served("t"):
            d._send_generator_tee("t", None, 1, refuse)

    _join(_threads(1, in_a_turn))
    assert slept == [None] and d.metrics["push_failures_total"] == 1
    assert d.metrics["push_retries_total"] == 2 and not d.turn._taken


# -- the served App --------------------------------------------------------

def _config(name: str) -> dict:
    with open(os.path.join(REPO, "chipbench", "configs", name + ".json")) as f:
        return lib.merged(json.load(f), SMALL)


@pytest.fixture
def served(tmp_path):
    config, sink = _config("k6-single-binary"), lib.Sink()
    app, srv, port = lib.boot(config, str(tmp_path), sink.url)
    yield app, port, config["tenants"]
    _abandon(app, srv)


def _post(port: int, tenant: str, body: bytes) -> int:
    return lib.http_call(port, "POST", "/v1/traces", tenant, body,
                         timeout=WAIT_S)[0]


def _calls(port: int, tenant: str) -> float:
    samples = lib.get_json(port, "/internal/generator/collect", tenant,
                           ts_ms=1)["samples"]
    return sum(s["value"] for s in samples
               if s["name"] == "traces_spanmetrics_calls_total")


def test_eight_clients_never_stand_two_in_the_push_together(served,
                                                            monkeypatch):
    app, port, tenants = served
    dist = app.distributor
    real, mu, inside, most = dist._push_staged, threading.Lock(), [0], [0]
    mine, real_give_up = threading.local(), turn_mod.give_up

    def leave() -> None:
        if getattr(mine, "counted", False):
            mine.counted = False
            with mu:
                inside[0] -= 1

    def give_up() -> None:      # a push that waits for a lock held through
        leave()                 # device work is beside the turn from there
        real_give_up()

    def probe(*a):
        assert turn_mod._mine.holds is dist.turn
        with mu:
            inside[0] += 1
            most[0] = max(most[0], inside[0])
        mine.counted = True
        try:
            time.sleep(0.002)       # hand the interpreter to whoever waits
            return real(*a)
        finally:
            leave()

    monkeypatch.setattr(turn_mod, "give_up", give_up)
    dist._push_staged = probe
    per, statuses = 6, []

    def client(k: int) -> None:
        for i in range(per):
            statuses.append(_post(port, tenants[k % 2],
                                  _payload(k * per + i, k % 2)))

    _join(_threads(8, client))
    assert statuses == [200] * (8 * per)
    assert most[0] == 1 and not dist.turn._taken and not dist.turn._waiting
    app.sched.flush()
    for tenant in tenants:
        assert _calls(port, tenant) == 4 * per * SHAPE.n
    rows = tracing.span_rows()
    assert rows[("distributor.turn", "clear")][0] == 8 * per
    assert rows[("distributor.PushSpans", "clear")][0] == 8 * per


@pytest.mark.parametrize("way_out", [400, 429, 500])
def test_every_way_out_gives_the_turn_back(served, monkeypatch, way_out):
    app, port, tenants = served
    dist, tenant = app.distributor, tenants[0]
    assert _post(port, tenant, _payload(0)) == 200
    with monkeypatch.context() as m:
        body = _payload(1)
        if way_out == 400:
            body = body[:-1]                        # a torn payload
        elif way_out == 429:
            m.setattr(dist.backpressure, "retry_after", lambda: 2.0)
        else:
            def broken(*a):
                raise RuntimeError("a target that raises")
            m.setattr(dist, "_push_staged", broken)
        assert _post(port, tenant, body) == way_out
    assert not dist.turn._taken
    t0 = time.monotonic()
    assert _post(port, tenant, _payload(2)) == 200
    assert time.monotonic() - t0 < 1.0
    app.sched.flush()
    assert _calls(port, tenant) == 2 * SHAPE.n


# -- the tenant's log alone: no fsync inside the turn -----------------------

@pytest.mark.parametrize("fsync", ["batch", "interval"])
def test_an_append_fsyncs_with_the_turn_given_up(tmp_path, monkeypatch,
                                                 fsync):
    cfg = wal_mod.IngestWalConfig(enabled=True, dir=str(tmp_path),
                                  fsync=fsync, fsync_interval_s=0.0)
    tw = wal_mod._TenantWal(str(tmp_path), "t", cfg, time.time)
    turn, held, real_fsync = Turn(), [], os.fsync

    def fsync_(fd):
        held.append(getattr(turn_mod._mine, "holds", None))
        return real_fsync(fd)

    tw.append(b"the record that opens the segment")     # once a segment
    monkeypatch.setattr(wal_mod.os, "fsync", fsync_)
    for _ in range(2):
        with turn.served("t"):
            assert turn_mod._mine.holds is turn
            tw.append(b"a record")
    assert held == [None, None] and not turn._taken


# -- the served durable App, a slow fsync ----------------------------------

FSYNC_S = 0.25


def test_the_turn_is_not_held_through_the_fsync(tmp_path, monkeypatch):
    config, sink = _config("k6-single-binary-wal"), lib.Sink()
    config["yaml_overrides"]["wal"]["dir"] = str(tmp_path / "gwal")
    app, srv, port = lib.boot(config, str(tmp_path), sink.url)
    try:
        tenant = config["tenants"][0]
        assert app.generator.wal.cfg.fsync == "batch"
        assert _post(port, tenant, _payload(0)) == 200      # shapes warm
        real_fsync, real_sync_to = os.fsync, wal_mod._TenantWal._sync_to
        covered, held = [], []

        def slow_fsync(fd):
            time.sleep(FSYNC_S)
            return real_fsync(fd)

        def sync_to(tw, ticket):
            # the push has its turn no longer when it starts this wait
            held.append(getattr(turn_mod._mine, "holds", None))
            real_sync_to(tw, ticket)
            assert tw._synced >= ticket     # an fsync that covers it
            covered.append(time.monotonic())

        monkeypatch.setattr(wal_mod.os, "fsync", slow_fsync)
        monkeypatch.setattr(wal_mod._TenantWal, "_sync_to", sync_to)

        t0 = time.monotonic()
        assert _post(port, tenant, _payload(1)) == 200
        alone = time.monotonic() - t0
        assert alone >= FSYNC_S
        acked, stats0 = [], dict(wal_mod.STATS)

        def client(k: int) -> None:
            assert _post(port, tenant, _payload(2 + k)) == 200
            acked.append(time.monotonic())

        t0 = time.monotonic()
        _join(_threads(4, client))
        together = time.monotonic() - t0
        appended = wal_mod.STATS["appended_batches"] - \
            stats0["appended_batches"]
        fsyncs = wal_mod.STATS["fsyncs"] - stats0["fsyncs"]
        # held through the wait, no two pushes could be in the log at
        # once: four fsyncs one after the other, as four pushes in a row
        # pay them (timed beside the same neighbours as the four at once)
        assert appended == 4 and fsyncs < 4         # pushes a fsync > 1
        t0 = time.monotonic()
        for k in range(4):
            assert _post(port, tenant, _payload(6 + k)) == 200
        in_a_row = time.monotonic() - t0
        assert in_a_row >= 4 * FSYNC_S and together < 0.9 * in_a_row
        assert held == [None] * 9 and len(covered) == 9
        # each 2xx follows the end of its own record's wait: whichever
        # push a wait was, the k-th 2xx cannot precede the k-th wait's end
        assert all(a >= c for a, c in zip(sorted(acked),
                                          sorted(covered[1:5])))
        assert not app.distributor.turn._taken
    finally:
        _abandon(app, srv)
