"""The service-graph half-edge store as arrays, paired in one call a push
(`HalfIndex.pair`, native or its dict fallback), against the dict of
objects and the deque it replaced (`tests/sg_legacy.py`), one Python step
a half. Random push scripts over a few trace and span ids, so that keys
meet, repeat and come back, must leave every edge family's state, the
interner and the store's counters bit-identical to the reference's."""

import numpy as np
import pytest

from sg_legacy import LegacyServiceGraphs
from tempo_tpu import native
from tempo_tpu.generator.processors.servicegraphs import (
    HALVES,
    ServiceGraphsConfig,
    ServiceGraphsProcessor,
)
from tempo_tpu.model.span_batch import (
    KIND_CLIENT,
    KIND_CONSUMER,
    KIND_INTERNAL,
    KIND_PRODUCER,
    KIND_SERVER,
    STATUS_ERROR,
    SpanBatchBuilder,
)
from tempo_tpu.registry import ManagedRegistry

ROUTES = [True, False]
ROUTE_IDS = ["native", "dict"]
KINDS = (KIND_CLIENT, KIND_SERVER, KIND_PRODUCER, KIND_CONSUMER,
         KIND_INTERNAL)
PEERS = ({}, {}, {"db.system": "mysql"}, {"peer.service": "billing"},
         {"net.peer.name": "redis", "db.name": "cache"},
         {"peer.service": ""})


class Clock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _span(trace: int, sid: int, parent: int | None, kind: int, service: str,
          status: int = 0, dur_ns: int = 10**8, start: int = 10**18,
          attrs: dict | None = None) -> dict:
    return dict(trace_id=bytes([trace]) * 16, span_id=bytes([sid]) * 8,
                parent_span_id=b"" if parent is None else bytes([parent]) * 8,
                name="op", service=service, kind=kind, status_code=status,
                start_unix_nano=start, end_unix_nano=start + dur_ns,
                attrs=attrs or {})


def _random_script(seed: int, wait_s: float) -> list:
    """(clock step, spans) a push. Three traces and six span ids, so pairs
    meet inside a push and across pushes, a key comes three times, a side
    repeats, and a key is taken again after its match; clock steps land
    before, at and after `wait_s`, and now and then step back."""
    r = np.random.default_rng(seed)
    steps = (0.0, 0.5, wait_s - 0.5, wait_s, wait_s + 0.5, 2 * wait_s, -0.5)
    script = []
    for _ in range(int(r.integers(20, 36))):
        spans = []
        for _ in range(int(r.integers(0, 24))):
            parent = None if r.random() < 0.25 else int(r.integers(1, 7))
            spans.append(_span(
                int(r.integers(1, 4)), int(r.integers(1, 7)), parent,
                KINDS[int(r.integers(len(KINDS)))],
                f"svc-{int(r.integers(4))}",
                STATUS_ERROR if r.random() < 0.2 else 0,
                int(r.integers(1, 5 * 10**9)),
                10**18 + int(r.integers(-10**9, 10**9)),
                PEERS[int(r.integers(len(PEERS)))]))
        script.append((float(steps[int(r.integers(len(steps)))]), spans))
    return script


def _run(script, cfg: ServiceGraphsConfig, make):
    clock = Clock()
    reg = ManagedRegistry(now=clock)
    p = make(reg, cfg)
    for step, spans in script:
        clock.t += step
        b = SpanBatchBuilder(interner=reg.interner)
        for sp in spans:
            b.append(**sp)
        p.push_batch(b.build())
    return reg, p


def _state(reg, p) -> dict:
    out = {"interner": reg.interner.snapshot(),
           "counters": (p.expired, p.dropped, dict(p.edges),
                        p.store_items()),
           "samples": sorted((s.name, s.labels, s.value)
                             for s in reg.collect(7))}
    for fam in p._families:
        st = fam.state
        for field in ("values", "bucket_counts", "counts", "sums"):
            if hasattr(st, field):
                out[(fam.name, field)] = np.asarray(getattr(st, field))
    return out


def _assert_identical(got: dict, want: dict) -> None:
    assert got.keys() == want.keys()
    for k in want:
        if isinstance(want[k], np.ndarray):
            np.testing.assert_array_equal(got[k], want[k], err_msg=str(k))
        else:
            assert got[k] == want[k], k


def _differential(script, cfg: ServiceGraphsConfig, use_native: bool):
    want = _state(*_run(script, cfg, LegacyServiceGraphs))
    before = {r: HALVES.value((r,)) for r in ("native", "dict")}
    reg, p = _run(script, cfg, lambda reg, c: ServiceGraphsProcessor(
        reg, c, use_native=use_native))
    route = "native" if use_native and native.available() else "dict"
    assert p._route == route
    grew = {r: HALVES.value((r,)) - v for r, v in before.items()}
    assert grew[{"native": "dict", "dict": "native"}[route]] == 0
    _assert_identical(_state(reg, p), want)
    return want, grew[route]


@pytest.mark.parametrize("use_native", ROUTES, ids=ROUTE_IDS)
@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("max_items,wait_s,messaging", [
    (10_000, 5.0, False),
    (10_000, 5.0, True),
    (6, 5.0, True),            # a full store: drops, same-side put-backs
    (10_000, 0.0, False),      # every unmatched half expires in its push
], ids=["plain", "messaging", "full", "nowait"])
def test_random_scripts_match_the_per_half_loop(
        use_native, seed, max_items, wait_s, messaging):
    cfg = ServiceGraphsConfig(
        wait_s=wait_s, max_items=max_items,
        enable_messaging_system_latency_histogram=messaging)
    want, halves = _differential(_random_script(seed, wait_s), cfg,
                                 use_native)
    expired, dropped, edges, _ = want["counters"]
    assert edges["completed"] > 0 and halves > 0
    if max_items < 100:
        assert dropped > 0
    if wait_s:
        assert expired > 0


def _handmade() -> list:
    """Each case once, in order: a pair inside a push; a client three times
    under one key (the third replaces the second, which replaced the
    first); a server whose client comes a push later; a key taken again
    after its match, whose older ring entry comes due while the new half
    waits (it is queued again); a root server and a peer-named client that
    expire into virtual nodes; a PRODUCER/CONSUMER pair; a clock that steps
    back; and an idle stretch that expires the rest."""
    c, s, p, q = KIND_CLIENT, KIND_SERVER, KIND_PRODUCER, KIND_CONSUMER
    return [
        (0.0, [_span(1, 1, None, c, "web"), _span(1, 2, 1, s, "api")]),
        (0.0, [_span(2, 3, None, c, "web", dur_ns=1),
               _span(2, 3, None, c, "web", dur_ns=2),
               _span(2, 3, None, c, "web", dur_ns=3)]),
        (1.0, [_span(2, 4, 3, s, "api", status=STATUS_ERROR)]),
        (0.0, [_span(3, 5, None, c, "web")]),
        (2.0, [_span(3, 6, 5, s, "db")]),
        (1.0, [_span(3, 5, None, c, "web2")]),          # key taken again
        (0.0, [_span(1, 7, None, s, "api"),              # root server
               _span(1, 8, 7, c, "api",
                     attrs={"db.system": "postgres"}),
               _span(2, 9, None, p, "queue-in", start=10**18),
               _span(2, 10, 9, q, "queue-out", start=10**18 + 5000)]),
        (2.5, []),                                        # first entry due
        (-1.0, [_span(1, 11, 2, s, "late")]),
        (20.0, [_span(3, 12, None, KIND_INTERNAL, "tick")]),
    ]


@pytest.mark.parametrize("use_native", ROUTES, ids=ROUTE_IDS)
@pytest.mark.parametrize("messaging", [False, True],
                         ids=["plain", "messaging"])
def test_each_case_once_matches_the_per_half_loop(use_native, messaging):
    cfg = ServiceGraphsConfig(
        wait_s=5.0, enable_messaging_system_latency_histogram=messaging)
    want, halves = _differential(_handmade(), cfg, use_native)
    expired, dropped, edges, items = want["counters"]
    assert (edges, dropped, items) == (
        {"completed": 4, "virtual": 2}, 0, 0)
    assert expired == 4 and halves == 14
    assert "user" in want["interner"] and "postgres" in want["interner"]


# -- the pairing call ------------------------------------------------------


def _pairs(idx, keys, sides, max_items, fresh_from=0):
    """`HalfIndex.pair` over a batch in which walk row r is batch row
    2 r + 1 (the even rows are other spans) and carries key keys[r]: the
    trace id, then the span id of a client or the parent id of a server;
    the other id is random. The call hands back those keys and each row's
    root flag."""
    n, sides = len(keys), np.asarray(sides, bool)
    rng = np.random.default_rng(n)
    trace, span, parent = (rng.integers(0, 256, (2 * n + 1, w), np.uint8)
                           for w in (16, 8, 8))
    rows = 2 * np.arange(n) + 1
    trace[rows] = keys[:, :16]
    span[rows[sides]] = keys[sides, 16:]
    parent[rows[~sides]] = keys[~sides, 16:]
    fresh = np.arange(fresh_from, fresh_from + n, dtype=np.int64)
    got, out, matched, prev, root, taken = idx.pair(
        trace, span, parent, rows, sides, max_items, fresh)
    np.testing.assert_array_equal(got.view(np.uint8).reshape(-1, 24), keys)
    np.testing.assert_array_equal(root, ~parent[rows].any(axis=1))
    return out, matched, prev, taken


@pytest.mark.parametrize("use_native", ROUTES, ids=ROUTE_IDS)
def test_pair_keys_are_all_24_bytes(use_native):
    """Keys that differ in one byte only, the last of the span id among
    them, are distinct halves; a key that is all zero bytes is a key."""
    idx = native.HalfIndex(use_native=use_native)
    keys = np.zeros((25, 24), np.uint8)
    for b in range(24):
        keys[b + 1, b] = 1
    out, matched, prev, taken = _pairs(idx, keys, np.ones(25, bool), 100)
    assert taken == 25 and not matched.any() and (prev == -1).all()
    np.testing.assert_array_equal(out, np.arange(25))
    assert len(idx) == 25
    # the server side of each: matched one for one, in order
    out, matched, prev, taken = _pairs(idx, keys[::-1], np.zeros(25, bool),
                                       100, 100)
    assert taken == 0 and matched.all()
    np.testing.assert_array_equal(out, np.arange(25)[::-1])
    assert len(idx) == 0
    ids = [np.zeros((2, w), np.uint8) for w in (16, 8, 8)]
    with pytest.raises(ValueError):       # a trace id is 16 bytes
        idx.pair(np.zeros((2, 17), np.uint8), *ids[1:], np.arange(2),
                 np.ones(2, bool), 9, np.arange(2))
    with pytest.raises(ValueError):       # a row past the batch
        idx.pair(*ids, np.array([0, 2]), np.ones(2, bool), 9, np.arange(2))


@pytest.mark.parametrize("use_native", ROUTES, ids=ROUTE_IDS)
def test_pair_one_call_walks_rows_in_order(use_native):
    """Inside one call: a pair meets, a same-side row replaces (prev),
    the full store drops a row and keeps the half it would replace, and a
    matched key's tombstone takes a new half under the same key."""
    idx = native.HalfIndex(use_native=use_native)
    a, b, c, d = (np.full((1, 24), v, np.uint8) for v in (1, 2, 3, 4))
    keys = np.concatenate([a, a, b, b, a, d, b, c])
    sides = np.array([1, 0, 1, 1, 0, 1, 1, 1], bool)
    out, matched, prev, taken = _pairs(idx, keys, sides, 2, 10)
    # a: client waits (10), server meets it; b: client waits (11), a
    # second client replaces it (12); a: server waits (13) in the
    # matched key's tombstone, beside b; then the store holds two: d is
    # dropped, b's third client too (b's second stays), c too
    np.testing.assert_array_equal(out, [10, 10, 11, 12, 13, -1, -1, -1])
    np.testing.assert_array_equal(matched, [0, 1, 0, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(prev, [-1, -1, -1, 11, -1, -1, -1, -1])
    assert taken == 4 and len(idx) == 2
    np.testing.assert_array_equal(
        idx.lookup(np.concatenate([a, b, c, d])),
        [2 * 13 + 0, 2 * 12 + 1, -1, -1])


def test_pair_native_and_dict_agree_through_rehash_and_tombstones():
    """Many halves in few calls: the table grows (rehash) and fills with
    the tombstones of matched keys; both routes give the same outputs
    call for call."""
    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(11)
    pool = rng.integers(0, 256, (30_000, 24), dtype=np.uint8)
    idxs = [native.HalfIndex(use_native=u) for u in (True, False)]
    assert [i.native for i in idxs] == [True, False]
    base = 0
    for call in range(8):
        n = int(rng.integers(5_000, 40_000))
        keys = pool[rng.integers(0, len(pool), n)]
        sides = rng.random(n) < 0.5
        max_items = 50_000 if call != 5 else 100   # one call meets a full store
        got = [_pairs(i, keys, sides, max_items, base) for i in idxs]
        for g_native, g_dict in zip(*got):
            np.testing.assert_array_equal(g_native, g_dict)
        base += got[0][3]
        assert len(idxs[0]) == len(idxs[1]) > 0
        np.testing.assert_array_equal(idxs[0].lookup(pool),
                                      idxs[1].lookup(pool))


def test_first_svals_native_and_numpy_agree():
    """The peer attribute a row names: the first key of `kids` (in order)
    the row carries with a string value, at that key's first column, as
    `SpanBatch.attr_sval_column` reads it key by key."""
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 6, (400, 5)).astype(np.int32)
    svals = rng.integers(-1, 50, (400, 5)).astype(np.int32)
    rows = rng.permutation(400)[:300]
    kids = [4, 2, 5]

    def first(r):
        for kid in kids:
            hit = np.flatnonzero(keys[r] == kid)
            if hit.size and svals[r, hit[0]] != -1:
                return svals[r, hit[0]]
        return -1

    want = [first(r) for r in rows]
    for use_native in ROUTES:
        np.testing.assert_array_equal(
            native.first_svals(keys, svals, rows, kids, use_native), want)
        assert native.first_svals(keys, svals, rows[:0], kids,
                                  use_native).shape == (0,)
        assert (native.first_svals(keys, svals, rows, [], use_native)
                == -1).all()


@pytest.mark.parametrize("use_native", ROUTES, ids=ROUTE_IDS)
def test_a_queued_again_entry_expires_the_key_s_next_half(use_native):
    """A ring entry names a key, not a half. An entry that came due while
    its key's next half waited is queued again under that half's time;
    when that half is matched and the key taken a third time behind a
    clock that stepped back, the queued-again entry expires the third half
    before its own entry comes up, so the server that follows waits
    instead of pairing."""
    c, s, i = KIND_CLIENT, KIND_SERVER, KIND_INTERNAL
    tick = [_span(9, 9, None, i, "tick")]
    script = [
        (0.0, [_span(4, 1, None, c, "web")]),            # K: due 1005
        (2.0, [_span(4, 1, None, c, "web")]),            # K again: 1007
        (1.0, [_span(5, 1, None, c, "web")]),            # Y: 1008
        (3.0, tick),                 # K's first entry due, queued again
        (0.0, [_span(4, 2, 1, s, "api")]),               # K matched
        (1.5, tick),                 # K's second entry: gone; Y blocks
        (0.0, [_span(6, 1, None, c, "web")]),            # Z: 1012.5
        (-1.0, [_span(4, 1, None, c, "web3")]),          # K: 1011.5
        (5.0, tick),                 # Y, then the queued-again entry
        (0.0, [_span(4, 2, 1, s, "api3")]),
    ]
    want, _ = _differential(script, ServiceGraphsConfig(wait_s=5.0),
                            use_native)
    expired, dropped, edges, items = want["counters"]
    assert (expired, edges["completed"], items) == (2, 1, 2)
