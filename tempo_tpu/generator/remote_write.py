"""Prometheus remote write: WriteRequest encoding + snappy framing + HTTP.

The output half of the reference's per-tenant generator storage
(`modules/generator/storage/instance.go:60-127`): collected samples are
encoded as a `prometheus.WriteRequest` protobuf (remote-write 1.0 schema),
snappy block-compressed, and POSTed with per-tenant headers. We encode the
proto directly with the wire codec in tempo_tpu.model.proto_wire, so no
generated code or vendored schema is needed.

Snappy note: the environment ships no snappy binding, so we emit a *valid*
snappy block stream using only literal chunks (the format permits arbitrary
literal/copy interleaving; all-literals is legal, just uncompressed-size).
Any compliant decoder (Prometheus/Mimir) accepts it.

WriteRequest field numbers (public prometheus/prompb/remote.proto + types.proto):
  WriteRequest{ repeated TimeSeries timeseries = 1; repeated MetricMetadata metadata = 3 }
  TimeSeries { repeated Label labels = 1; repeated Sample samples = 2;
               repeated Exemplar exemplars = 3; repeated Histogram histograms = 4 }
  Label      { string name = 1; string value = 2 }
  Sample     { double value = 1; int64 timestamp = 2 }
  Exemplar   { repeated Label labels = 1; double value = 2; int64 timestamp = 3 }
  Histogram  { uint64 count_int = 1; double sum = 3; sint32 schema = 4;
               double zero_threshold = 5; uint64 zero_count_int = 6;
               repeated BucketSpan positive_spans = 11;
               repeated sint64 positive_deltas = 12; int64 timestamp = 15 }
  BucketSpan { sint32 offset = 1; uint32 length = 2 }
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time
import urllib.error
import urllib.request
from typing import Iterable, Sequence

import numpy as np

from tempo_tpu.model import proto_wire as pw
from tempo_tpu.registry.registry import FamilyColumns
from tempo_tpu.registry.series import Exemplar, Sample
from tempo_tpu.utils import tracing

MAX_LITERAL = (1 << 32) - 1

# process-wide delivery counters across every RemoteWriteClient (one per
# tenant instance), rendered by the RUNTIME registry families below —
# retry storms and dead endpoints must be visible on /metrics, not just
# in per-client attributes nobody scrapes
_RW_LOCK = threading.Lock()
_RW_RETRIES: dict[str, int] = {}      # cause -> count
_RW_STATS = {"sends": 0, "failed": 0}
# TimeSeries written, by where their label blocks came from: `kept` from
# a family's LabelBlocks, `built` where a block had to be encoded
_RW_SERIES = {"kept": 0, "built": 0}


def _note_retry(cause: str) -> None:
    with _RW_LOCK:
        _RW_RETRIES[cause] = _RW_RETRIES.get(cause, 0) + 1


def snappy_compress(data: bytes) -> bytes:
    """Snappy block-format framing using literal chunks only."""
    out = bytearray(pw.enc_varint(len(data)))
    pos, n = 0, len(data)
    while pos < n:
        chunk = data[pos: pos + 65536]
        ln = len(chunk)
        if ln <= 60:
            out.append((ln - 1) << 2)
        elif ln <= 256:
            out.append(60 << 2)
            out.append(ln - 1)
        else:
            out.append(61 << 2)
            out += (ln - 1).to_bytes(2, "little")
        out += chunk
        pos += ln
    return bytes(out)


def _enc_label(name: str, value: str) -> bytes:
    return pw.enc_field_str(1, name) + pw.enc_field_str(2, value)


def _enc_pair(name: str, value: str) -> bytes:
    """One `repeated Label labels = 1` entry of a TimeSeries."""
    return pw.enc_field_msg(1, _enc_label(name, value))


def _enc_labels(labels: Sequence[tuple[str, str]]) -> bytes:
    return b"".join(_enc_pair(n, v) for n, v in sorted(labels))


def _enc_exemplar(ex: Exemplar) -> bytes:
    return pw.enc_field_msg(
        3, pw.enc_field_msg(1, _enc_label("trace_id", ex.trace_id_hex))
        + pw.enc_field_double(2, ex.value) + pw.enc_field_varint(3, ex.ts_ms))


def _zigzag(v: int) -> int:
    return (v << 1) ^ (v >> 63) if v < 0 else v << 1


def encode_native_histogram(log2_counts: np.ndarray, total: float, zeros: float,
                            sum_: float, ts_ms: int, offset: int = 0) -> bytes:
    """Encode a log2-bucket row as a schema-0 native histogram.

    Our log2 bucket b>0 covers [2^(b-1-offset), 2^(b-offset)); Prometheus
    schema-0 index i covers (2^(i-1), 2^i], so i = b - offset. Contiguous
    nonzero runs become BucketSpans with delta-encoded counts.
    """
    nz = np.flatnonzero(log2_counts[1:])  # skip zero-bucket; b = idx+1
    spans = b""
    deltas = b""
    prev_count = 0
    prev_idx = None
    run_start = None
    run_len = 0

    def flush_span(start, length, prev_end):
        offset = start - (prev_end if prev_end is not None else 0)
        return pw.enc_field_msg(11, pw.enc_field_varint(1, _zigzag(offset))
                                + pw.enc_field_varint(2, length))

    prev_end = None
    for idx in nz.tolist():
        i = idx + 1 - offset  # prometheus index = b - offset where b = idx+1
        if run_start is None:
            run_start, run_len = i, 1
        elif i == run_start + run_len:
            run_len += 1
        else:
            spans += flush_span(run_start, run_len, prev_end)
            prev_end = run_start + run_len
            run_start, run_len = i, 1
        c = int(log2_counts[idx + 1])
        deltas += pw.enc_field_varint(12, _zigzag(c - prev_count))
        prev_count = c
    if run_start is not None:
        spans += flush_span(run_start, run_len, prev_end)
    body = (
        pw.enc_field_varint(1, int(total))
        + pw.enc_field_double(3, float(sum_))
        + pw.enc_field_varint(4, _zigzag(0))      # schema 0
        + pw.enc_field_double(5, 1e-128)          # zero threshold
        + pw.enc_field_varint(6, int(zeros))
        + spans + deltas
        + pw.enc_field_varint(15, ts_ms)
    )
    return body


def encode_write_request(samples: Iterable[Sample],
                         native_histograms: Iterable[tuple] = (),
                         ts_ms: int | None = None) -> bytes:
    """samples → WriteRequest bytes. Stale markers become NaN samples (the
    Prometheus staleness convention the reference relies on)."""
    out = bytearray()
    for s in samples:
        ts = s.ts_ms if ts_ms is None else ts_ms
        body = _enc_labels(s.labels) + pw.enc_field_msg(
            2, pw.enc_field_double(1, s.value) + pw.enc_field_varint(2, ts))
        if s.exemplar is not None:
            body += _enc_exemplar(s.exemplar)
        out += pw.enc_field_msg(1, body)
    for labels, log2_counts, sum_, count, zeros, ts, *rest in native_histograms:
        offset = rest[0] if rest else 0
        body = _enc_labels(labels) + pw.enc_field_msg(
            4, encode_native_histogram(log2_counts, count, zeros, sum_, ts, offset))
        out += pw.enc_field_msg(1, body)
    return bytes(out)


def _objects(items: list) -> np.ndarray:
    """[n] object array of the bytes in `items`, as they are (a fixed-width
    string dtype on the way would strip trailing NUL bytes)."""
    out = np.empty(len(items), object)
    out[:] = items
    return out


_LEN = np.frompyfunc(len, 1, 1)


class LabelBlocks:
    """One family's encoded `repeated Label` bytes, a series' block built
    on first sight and kept until the registry evicts its slot (`drop`).

    A block is the slot's sorted pairs (its label values, the tenant's
    external labels, `__name__`), split where `le` sorts in: a TimeSeries'
    labels are then `before + [le pair] + after`. `key` is everything a
    block is built from besides the slot's own values; the encoder makes a
    new store when it differs."""

    def __init__(self, key: tuple):
        self.key = key
        # slot -> (the slot's label-id row the block was built from,
        # before, after). An eviction can fall anywhere between a tick's
        # read of a slot's labels and its store, and the slot's next
        # series must not inherit the block: a kept block counts only
        # while the slot still holds the row it was built from
        self._blocks: dict[int, tuple[bytes, bytes, bytes]] = {}

    def drop(self, slots: np.ndarray) -> None:
        for slot in slots.tolist():
            self._blocks.pop(slot, None)

    def get(self, fam, slots: np.ndarray) -> tuple[list, int]:
        """([S] (before, after) blocks, how many had to be built)."""
        slot_list = slots.tolist()
        keys = fam.table.slot_keys[slots]
        rows = [key.tobytes() for key in keys]
        got = [self._blocks.get(s) for s in slot_list]
        miss = [i for i, (block, row) in enumerate(zip(got, rows))
                if block is None or block[0] != row]
        if miss:
            for i, block in zip(miss, _build_blocks(fam, keys[miss])):
                got[i] = self._blocks[slot_list[i]] = (rows[i], *block)
        return [block[1:] for block in got], len(miss)


def _build_blocks(fam, keys: np.ndarray) -> list[tuple[bytes, bytes]]:
    """`_MetricBase.labels_of`'s pairs for slots holding the label-id rows
    `keys`, encoded a label column at a time: each distinct value of a
    column is encoded once."""
    fixed = {**fam.registry.overrides.external_labels, "__name__": fam.name}
    column = {name: j for j, name in enumerate(fam.label_names)}
    before = after = _objects([b""] * len(keys))
    for name in sorted(column.keys() | fixed.keys()):
        if name in fixed:
            pairs = _objects([_enc_pair(name, fixed[name])])
        else:
            ids, inverse = np.unique(keys[:, column[name]],
                                     return_inverse=True)
            pairs = _objects([
                _enc_pair(name, value)
                for value in fam.registry.interner.lookup_many(ids)])[inverse]
        if name < "le":
            before = before + pairs
        else:
            after = after + pairs
    return list(zip(before.tolist(), after.tolist()))


def _family_blocks(fam) -> "LabelBlocks | None":
    """The family's store, made anew where its label names or the tenant's
    external labels are no longer what the blocks were built with. None
    where a label is itself named `le`: `sorted()` then orders that pair
    against every bucket's by VALUE, and no split holds for all edges."""
    external = fam.registry.overrides.external_labels
    if "le" in external or "le" in fam.label_names:
        return None
    key = (fam.name, fam.label_names, tuple(sorted(external.items())))
    if fam.label_blocks is None or fam.label_blocks.key != key:
        fam.label_blocks = LabelBlocks(key)
    return fam.label_blocks


def _encode_family(cols: FamilyColumns) -> tuple[bytes, int]:
    """(one family's TimeSeries, how many found their label blocks kept).
    Byte for byte `encode_write_request(cols.samples())`."""
    n_slots, n_kinds = cols.values.shape
    blocks = _family_blocks(cols.family) if n_slots else None
    if blocks is None:
        return encode_write_request(cols.samples()), 0
    got, n_built = blocks.get(cols.family, cols.slots)
    before, after = (_objects(list(side)) for side in zip(*got))
    le = _objects([b"" if le is None else _enc_pair("le", le)
                   for _, le in cols.kinds])
    # Sample{value, timestamp}: the same bytes but for the double
    stamp = pw.enc_field_varint(2, cols.ts_ms)
    sample = np.empty((n_slots * n_kinds, 11 + len(stamp)), np.uint8)
    sample[:, :3] = 0x12, 9 + len(stamp), 0x09
    sample[:, 3:11] = cols.values.astype("<f8").reshape(-1, 1).view(np.uint8)
    sample[:, 11:] = np.frombuffer(stamp, np.uint8)
    # one exemplar a slot a tick, whichever of its series carry it
    exemplar = _objects([b"" if ex is None else _enc_exemplar(ex)
                         for ex in cols.exemplars])
    body = (_LEN(before) + _LEN(after))[:, None] + _LEN(le)[None, :] \
        + sample.shape[1] + _LEN(exemplar)[:, None] * cols.carries
    sizes, which = np.unique(body.astype(np.int64).reshape(-1),
                             return_inverse=True)
    head = _objects([b"\x0a" + pw.enc_varint(n) for n in sizes.tolist()])
    series = np.empty((n_slots, n_kinds, 6), object)
    series[:, :, 0] = head[which].reshape(n_slots, n_kinds)
    series[:, :, 1] = before[:, None]
    series[:, :, 2] = le[None, :]
    series[:, :, 3] = after[:, None]
    series[:, :, 4] = _objects(
        sample.view(f"V{sample.shape[1]}").reshape(-1).tolist()
    ).reshape(n_slots, n_kinds)
    series[:, :, 5] = np.where(cols.carries, exemplar[:, None], _objects([b""]))
    return (b"".join(series.reshape(-1).tolist())
            + encode_write_request(cols.stale_samples()),
            (n_slots - n_built) * n_kinds)


def encode_columns(columns: Sequence[FamilyColumns],
                   native_histograms: Iterable[tuple] = ()) -> bytes:
    """A collection tick → WriteRequest bytes, the bytes of
    `encode_write_request(samples of the columns, native_histograms)`."""
    parts, kept, total = [], 0, 0
    for cols in columns:
        part, n_kept = _encode_family(cols)
        parts.append(part)
        kept += n_kept
        total += cols.n_series
    native_histograms = list(native_histograms)
    parts.append(encode_write_request((), native_histograms))
    with _RW_LOCK:
        _RW_SERIES["kept"] += kept
        _RW_SERIES["built"] += total - kept + len(native_histograms)
    return b"".join(parts)


@dataclasses.dataclass
class RemoteWriteConfig:
    url: str = ""
    headers: dict = dataclasses.field(default_factory=dict)
    timeout_s: float = 30.0
    retries: int = 3
    backoff_s: float = 0.5
    # TOTAL backoff sleep budget per send() call: send runs inline on
    # the shared collection thread, so the stall one tenant's backend
    # can inflict per tick must be bounded regardless of how many
    # retries remain or what Retry-After it advertises (a hostile
    # header cannot buy more than the remaining budget; once spent,
    # remaining retries are abandoned and the send fails)
    max_backoff_total_s: float = 15.0
    send_native_histograms: bool = False  # reference toggle (config_util.go)


class RemoteWriteClient:
    """POSTs snappy-framed WriteRequests with retry/backoff.

    Plays the role of the prometheus agent-WAL remote-write queue in the
    reference (deliberately without the on-disk WAL — the reference wipes it
    on every restart anyway, `storage/instance.go:66-70,135-146`; our
    delivery buffer is in-memory with bounded retry).
    """

    def __init__(self, cfg: RemoteWriteConfig):
        self.cfg = cfg
        self.sent_bytes = 0
        self.sent_samples = 0
        self.failed_sends = 0
        self.retried_sends = 0
        # injectable for tests: retry pacing must be assertable without
        # real sleeps, and jitter without seeding the global RNG
        self._sleep = time.sleep
        self._rng = random.Random()

    @staticmethod
    def _retry_after_s(e: urllib.error.HTTPError) -> "float | None":
        """Seconds advertised by a 429/503 Retry-After header (delta
        form only — the HTTP-date form is ignored rather than parsed
        wrong)."""
        try:
            v = e.headers.get("Retry-After") if e.headers else None
            return float(v) if v is not None else None
        except (TypeError, ValueError):
            return None

    def _backoff(self, attempt_delay: float,
                 retry_after: "float | None") -> float:
        """Full-jitter exponential backoff (sleep ~ U(0, delay)): a fleet
        of generators retrying the same dead endpoint never synchronizes
        into a thundering herd. A server-advertised Retry-After raises
        the floor — we honor it, plus jitter ON TOP so the fleet doesn't
        all return at exactly the advertised second. The caller clamps
        the result to its remaining per-send budget."""
        sleep_s = self._rng.uniform(0.0, attempt_delay)
        if retry_after is not None and retry_after > 0:
            sleep_s = retry_after + self._rng.uniform(
                0.0, max(retry_after * 0.1, self.cfg.backoff_s))
        return sleep_s

    def send(self, columns: Sequence[FamilyColumns],
             native_histograms: Sequence[tuple] = ()) -> bool:
        n_samples = sum(cols.n_series for cols in columns)
        if not self.cfg.url or (not n_samples and not native_histograms):
            return True
        with tracing.span("remote_write.encode", n_samples=n_samples):
            payload = snappy_compress(
                encode_columns(columns, native_histograms))
        with tracing.span("remote_write.send", n_bytes=len(payload)):
            return self._post(payload, n_samples)

    def _post(self, payload: bytes, n_samples: int) -> bool:
        """One remote-write request, retries and their sleeps included."""
        req = urllib.request.Request(self.cfg.url, data=payload, method="POST")
        req.add_header("Content-Encoding", "snappy")
        req.add_header("Content-Type", "application/x-protobuf")
        req.add_header("X-Prometheus-Remote-Write-Version", "0.1.0")
        req.add_header("User-Agent", "tempo-tpu-remote-write/0.1")
        for k, v in self.cfg.headers.items():
            req.add_header(k, v)
        delay = self.cfg.backoff_s
        budget = self.cfg.max_backoff_total_s   # total sleep per send()
        for attempt in range(self.cfg.retries + 1):
            retry_after = None
            cause = None
            try:
                with urllib.request.urlopen(req, timeout=self.cfg.timeout_s) as resp:
                    if 200 <= resp.status < 300:
                        self.sent_bytes += len(payload)
                        self.sent_samples += n_samples
                        with _RW_LOCK:
                            _RW_STATS["sends"] += 1
                        return True
            except urllib.error.HTTPError as e:
                if e.code == 429 or e.code >= 500:
                    # retryable per prometheus remote-write rules; 429
                    # and 503 commonly advertise Retry-After
                    cause = "http_429" if e.code == 429 else "http_5xx"
                    retry_after = self._retry_after_s(e)
                else:
                    break  # other 4xx: non-retryable
            except (urllib.error.URLError, OSError):
                cause = "network"
            if attempt < self.cfg.retries:
                sleep_s = min(self._backoff(delay, retry_after), budget)
                if sleep_s <= 0:
                    break      # budget spent: abandon remaining retries
                budget -= sleep_s
                self.retried_sends += 1
                _note_retry(cause or "unknown")
                self._sleep(sleep_s)
                delay *= 2
        self.failed_sends += 1
        with _RW_LOCK:
            _RW_STATS["failed"] += 1
        return False


# RUNTIME registry families (process-wide, next to the sched/jit ones):
# the per-client attributes above stay the store, these render them
from tempo_tpu.obs.jaxruntime import RUNTIME  # noqa: E402

def _retries_family() -> list:
    # the lock covers the iteration too: a sender inserting a new cause
    # key mid-scrape would otherwise blow up the /metrics render
    with _RW_LOCK:
        return [((c,), float(v)) for c, v in _RW_RETRIES.items()]


RUNTIME.counter_func(
    "tempo_remote_write_retries_total", _retries_family,
    help="Remote-write attempts retried after a retryable failure, by "
         "cause (429 vs 5xx vs network) — sustained growth means the "
         "metrics backend is rejecting or unreachable",
    labels=("cause",))
RUNTIME.counter_func(
    "tempo_remote_write_sends_total",
    lambda: [((), float(_RW_STATS["sends"]))],
    help="Remote-write requests delivered (2xx)")
RUNTIME.counter_func(
    "tempo_remote_write_series_encoded_total",
    lambda: [((k,), float(v)) for k, v in _RW_SERIES.items()],
    help="TimeSeries written to remote-write payloads, by whether their "
         "label blocks were kept from an earlier tick or had to be built "
         "(a series' first tick, after an eviction, after the tenant's "
         "external labels changed)",
    labels=("labels",))
RUNTIME.counter_func(
    "tempo_remote_write_failed_sends_total",
    lambda: [((), float(_RW_STATS["failed"]))],
    help="Remote-write requests dropped after exhausting retries "
         "(samples LOST to the metrics backend)")
