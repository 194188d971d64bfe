"""A sweep holds `TenantInstance.lock` only to take the cut traces and to
withdraw them: with `WALBlock.append_table` stopped on events, pushes go
on, the reads find a cut trace whole at every point of the sweep, the
head block is not sealed under a write, and a failed write leaves
nothing published."""

from __future__ import annotations

import threading

import pytest

from tempo_tpu.block.live_columns import ColumnSource
from tempo_tpu.block.wal import WALBlock
from tempo_tpu.model.interner import StringInterner
from tests.test_live_columns import _instance, _k6_spans, _stage

WAIT_S = 30.0


class _HeldWrite:
    """`WALBlock.append_table`, stopped twice: `entered` is set when a
    sweep reaches the write, which waits for `start`; `written` is set
    when the segment is on disk, and the call returns after `finish`
    (for the instance the write has not ended, for a reader the trace is
    in `cutting` and in the segment: the state between the write's end
    and the sweep's second hold of the lock)."""

    def __init__(self, monkeypatch) -> None:
        self.entered, self.start = threading.Event(), threading.Event()
        self.written, self.finish = threading.Event(), threading.Event()
        self.fail: Exception | None = None
        real = WALBlock.append_table

        def held(block, table):
            self.entered.set()
            assert self.start.wait(WAIT_S)
            if self.fail is not None:
                raise self.fail
            real(block, table)
            self.written.set()
            assert self.finish.wait(WAIT_S)

        monkeypatch.setattr(WALBlock, "append_table", held)

    def release(self) -> None:
        self.start.set()
        self.finish.set()


@pytest.fixture
def write(monkeypatch):
    w = _HeldWrite(monkeypatch)
    yield w
    w.release()      # no thread of a failed test stays parked


def _run(fn, *args) -> tuple[threading.Thread, list]:
    """`fn(*args)` on a thread; its result (or what it raised) lands in
    the list."""
    out: list = []

    def body() -> None:
        try:
            out.append(fn(*args))
        except Exception as e:      # handed to the test's thread
            out.append(e)

    t = threading.Thread(target=body, daemon=True)
    t.start()
    return t, out


def _done(t: threading.Thread) -> bool:
    t.join(WAIT_S)
    return not t.is_alive()


def _push(inst, spans, it) -> dict:
    staged = _stage(spans, it)
    return inst.push_columns(ColumnSource(staged.batch()[0], staged),
                             staged.view().row_indices())


def _by_trace(spans) -> dict[bytes, set]:
    out: dict[bytes, set] = {}
    for s in spans:
        out.setdefault(s["trace_id"], set()).add(s["span_id"])
    return out


def _assert_reads_whole(inst, want: dict[bytes, set]) -> None:
    """Both reads return every trace of `want` with each span id once."""
    tid = next(iter(want))
    got = [s["span_id"] for s in inst.find_trace_by_id(tid)]
    assert len(got) == len(set(got)) and set(got) == want[tid]
    recent = dict(inst.all_recent_traces())
    assert set(recent) == set(want)
    for t, spans in recent.items():
        ids = [s["span_id"] for s in spans]
        assert len(ids) == len(set(ids)) and set(ids) == want[t]


def test_a_push_returns_while_the_sweeps_write_is_in_flight(tmp_path, write):
    it = StringInterner()
    inst = _instance(tmp_path, "a")
    assert _push(inst, _k6_spans(1, groups=2, per=50), it) == {}
    sweep, cut = _run(inst.cut_complete_traces, True)
    assert write.entered.wait(WAIT_S)
    assert inst.sweep_lock.locked() and len(inst.cutting) == 20
    push, refused = _run(_push, inst, _k6_spans(2, groups=2, per=50), it)
    assert _done(push) and refused == [{}]
    assert len(inst.live) == 20          # the new traces, not the cut ones
    write.release()
    assert _done(sweep) and cut == [20]
    assert inst.cutting is None and not inst.sweep_lock.locked()


@pytest.mark.parametrize("route", ["columns", "dicts"])
def test_reads_find_a_cut_trace_whole_all_through_the_sweep(
        route, tmp_path, write):
    it = StringInterner()
    inst = _instance(tmp_path, "b")
    spans = _k6_spans(3, groups=2, per=50)
    want = _by_trace(spans)
    if route == "columns":
        assert _push(inst, spans, it) == {}
    else:
        for tid in want:
            assert inst.push_trace(
                tid, [s for s in spans if s["trace_id"] == tid]) is None
    _assert_reads_whole(inst, want)                  # live
    sweep, _ = _run(inst.cut_complete_traces, True)
    assert write.entered.wait(WAIT_S)
    assert not len(inst.live) and not inst.head.segments()
    _assert_reads_whole(inst, want)                  # in `cutting` alone
    write.start.set()
    assert write.written.wait(WAIT_S)
    assert inst.cutting and inst.head.segments() == ["0000000.parquet"]
    _assert_reads_whole(inst, want)                  # in both
    write.finish.set()
    assert _done(sweep)
    assert inst.cutting is None
    _assert_reads_whole(inst, want)                  # in the segment alone


def test_sealing_the_head_block_waits_for_the_sweep(tmp_path, write):
    it = StringInterner()
    inst = _instance(tmp_path, "c")
    spans = _k6_spans(4, groups=2, per=50)
    _push(inst, spans, it)
    sweep, _ = _run(inst.cut_complete_traces, True)
    assert write.entered.wait(WAIT_S)
    seal, sealed = _run(inst.cut_block_if_ready, True)
    seal.join(0.2)
    assert seal.is_alive() and inst.head is not None and not sealed
    write.release()
    assert _done(sweep) and _done(seal)
    assert inst.head is None and inst.completing == sealed
    assert sealed[0].segments() == ["0000000.parquet"]
    assert _by_trace(sealed[0].iter_spans()) == _by_trace(spans)


def test_a_write_that_raises_leaves_nothing_published(tmp_path, write):
    it = StringInterner()
    inst = _instance(tmp_path, "d")
    _push(inst, _k6_spans(5, groups=2, per=50), it)
    write.fail = OSError("disk full")
    write.release()
    with pytest.raises(OSError, match="disk full"):
        inst.cut_complete_traces(immediate=True)
    assert inst.cutting is None and not inst.sweep_lock.locked()
    assert not len(inst.live) and not inst.head.segments()
    # the next sweep works, into the same head block
    write.fail = None
    spans = _k6_spans(6, groups=2, per=50)
    assert _push(inst, spans, it) == {}
    assert inst.cut_complete_traces(immediate=True) == 20
    assert inst.head.segments() == ["0000000.parquet"]
    _assert_reads_whole(inst, _by_trace(spans))


def test_spans_pushed_for_a_trace_being_cut_read_back_merged(tmp_path, write):
    it = StringInterner()
    inst = _instance(tmp_path, "e")
    first = _k6_spans(7, groups=2, per=50)
    # the same trace ids (they come from the seed), other span ids
    later = [dict(s, span_id=(int.from_bytes(s["span_id"], "big") + 1)
                  .to_bytes(8, "big")) for s in first]
    want = {t: ids | _by_trace(later)[t]
            for t, ids in _by_trace(first).items()}
    assert all(len(ids) == 10 for ids in want.values())
    _push(inst, first, it)
    sweep, _ = _run(inst.cut_complete_traces, True)
    assert write.entered.wait(WAIT_S)
    assert _push(inst, later, it) == {}
    assert len(inst.live) == len(inst.cutting) == 20     # new live traces
    _assert_reads_whole(inst, want)          # live + cutting
    write.start.set()
    assert write.written.wait(WAIT_S)
    _assert_reads_whole(inst, want)          # live + cutting + segment
    write.finish.set()
    assert _done(sweep)
    _assert_reads_whole(inst, want)          # live + segment
    assert inst.cut_complete_traces(immediate=True) == 20
    assert len(inst.head.segments()) == 2
    _assert_reads_whole(inst, want)          # two segments
