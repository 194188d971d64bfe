"""The turn: pushes go through the distributor one at a time.

The interpreter runs one thread. Four request threads inside the push
path together hand it back and forth at every numpy call, `ctypes` call,
lock and launch, and each of them then queues for it again: on the
chip's host a push of 1,000 spans that takes 9 ms alone took 69 ms
beside three others (`PERF.md` section 5, PR 37). So a request thread
reads its body as before (sockets overlap), then waits for its turn,
runs the whole push alone, and gives the turn up when only a wait that
is not the interpreter's (the log's fsync) or the reply is left.

Width is one, in code: there is no key for it. Waiters are served in
arrival order (a bare `threading.Lock` hands over in no order).

Every wait on the push path, in the order a push meets them. A wait
that is not the interpreter's GIVES the turn UP before it starts
(`give_up`; never taken again, so what follows runs beside the next
push, as all of it did before there was a turn). Check a blocking call
that is new on this path against this list, and add it here:

- the body read and the reply (`app/api.py`): outside the turn;
- the wait for the turn itself: the span `distributor.turn`;
- `_admit`, decode/stage, usage, data quality, the forwarders' `offer`
  (`put_nowait`): the interpreter's, short locks: HELD. A 429 therefore
  waits its place in line behind the pushes that came before it;
- the ingest bus (`_push_spans`, `produce_traces`; Kafka waits for its
  brokers): GIVEN UP, the produce is the last thing that push does;
- a send to an ingester or generator client that is not a service object
  of this process (`Distributor._client`; HTTP or gRPC, 30 s timeout):
  GIVEN UP before the first such send;
- the in-process ingester's `TenantInstance.lock` (a cut holds it while
  it takes its traces) and the local-blocks and WAL tenant locks: HELD;
- the scheduler's `submit_rows`: never blocks (sheds inline): HELD;
- the one-processor span-metrics `IngestPipeline.acquire`, which waits
  for the device's oldest batch when `depth` are staged ahead: HELD
  (no cell drives it; its stall is counted on `/metrics`);
- a registry's `state_lock` where another thread has it (held through a
  launch or the collector's gathers): GIVEN UP (`waiting_for`);
- a cold compile of a step inside the push's own thread: HELD (~23 s a
  shape on an empty cache holds up every tenant: `operations/runbook.md`);
- the generator WAL's fsync (`generator/wal.py`: `wal.sync` under
  `fsync: batch`, the due fsync under `fsync: interval`): GIVEN UP; a
  segment's rotation (once a segment): HELD;
- the tenant-placement tee's pause before it asks a refusing owner
  again (`_send_generator_tee`): GIVEN UP.
"""

from __future__ import annotations

import collections
import contextlib
import threading

from tempo_tpu.utils import tracing

# what THIS thread has of a turn: `inside` the block (so that a nested
# entry, `push_otlp`'s fall-back into `push_spans`, does not wait for
# itself) and the turn it still `holds` (None once given up early)
_mine = threading.local()


class Turn:
    """A mutex that hands over in arrival order."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._taken = False
        self._waiting: collections.deque[threading.Lock] = \
            collections.deque()

    def _take(self) -> None:
        with self._mu:
            if not self._taken:
                self._taken = True
                return
            gate = threading.Lock()
            gate.acquire()
            self._waiting.append(gate)
        gate.acquire()          # opened by `_give`: the turn comes taken

    def _give(self) -> None:
        with self._mu:
            if self._waiting:
                self._waiting.popleft().release()
            else:
                self._taken = False

    @contextlib.contextmanager
    def served(self, tenant: str):
        """Wait for the turn (the span `distributor.turn` is that wait
        and nothing else), run the block in it, give it back on every
        way out unless the block gave it up already."""
        if getattr(_mine, "inside", False):
            yield
            return
        with tracing.span_for_tenant("distributor.turn", tenant):
            self._take()
        _mine.inside, _mine.holds = True, self
        try:
            yield
        finally:
            _mine.inside = False
            give_up()


def give_up() -> None:
    """Give this thread's turn to the next push, if it holds one; it is
    not taken again. Called where a push starts a wait that is not the
    interpreter's, so that the turn is never held through it: the
    module's docstring lists them."""
    turn = getattr(_mine, "holds", None)
    if turn is not None:
        _mine.holds = None
        turn._give()


@contextlib.contextmanager
def waiting_for(lock):
    """Hold `lock` for the block; where another thread has it, give this
    thread's turn up before waiting. For a lock that is held through
    device work: a registry's `state_lock` (on the paged layout the
    pool's lock, every tenant's) is held by the scheduler's thread
    through a launch and by the collector through its gathers, tens of
    milliseconds each behind a busy device, and a push that waited for
    it inside its turn held up every tenant's pushes
    (`tenants-zipf.steady`, `PERF.md` section 6, PR 37)."""
    if not lock.acquire(blocking=False):
        give_up()
        lock.acquire()
    try:
        yield
    finally:
        lock.release()
