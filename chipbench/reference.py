"""The plain reference: numpy over the generated columns, integers only.

Counts and timestamps are `np.int64` nanoseconds and steps come from
floor division: at 1.79e9 s a float64 second has a 238 ns ulp, so a
reference that bins with floats would itself be off by one span at a
step edge. Every query of the read traffic starts and ends on a whole
second, so the spans are binned once into whole seconds and a query is a
sum over seconds, exact.

Semantics, as upstream Tempo has them:
- TraceQL metrics bin a span by its START time into step
  floor((start - query start) / step); a span outside [start, start +
  steps x step) is not counted.
- `quantile_over_time(duration, q)` is `Log2Quantile`: spans counted into
  power-of-two buckets (bucket b holds 2^(b-1) < ns <= 2^b), the bucket
  that holds the q-th span found, linear interpolation inside it.
- search returns a trace when the spans that match, taken together,
  overlap the window: max(end) >= start and min(start) < end; never more
  than `limit` traces.
"""

from __future__ import annotations

import json

import numpy as np

HBUCKETS = 64
_POW2 = np.int64(1) << np.arange(63, dtype=np.int64)


def log2_bucket(dur_ns: np.ndarray) -> np.ndarray:
    """ceil(log2(ns)) in integers: the first power of two >= ns."""
    return np.searchsorted(_POW2, np.maximum(dur_ns, 1), side="left")


def log2_quantile(q: float, hist: np.ndarray) -> np.ndarray:
    """[..., HBUCKETS] counts -> quantile in seconds (0 where empty)."""
    h = hist.astype(np.float64)
    cum = np.cumsum(h, axis=-1)
    total = cum[..., -1]
    target = np.maximum(q * total, 1e-12)
    b = np.minimum((cum < target[..., None]).sum(axis=-1), HBUCKETS - 1)
    prev = np.where(b > 0, np.take_along_axis(
        cum, np.maximum(b - 1, 0)[..., None], -1)[..., 0], 0.0)
    inb = np.take_along_axis(h, b[..., None], -1)[..., 0]
    frac = np.where(inb > 0, (target - prev) / np.maximum(inb, 1e-300), 0.0)
    lo = np.where(b == 0, 0.0, np.exp2(b - 1.0))
    hi = np.exp2(b.astype(np.float64))
    return np.where(total > 0, (lo + (hi - lo) * frac) / 1e9, 0.0)


class BlockData:
    """The data set's columns, and the answers to the read traffic."""

    def __init__(self, cols: dict, spec: dict, t0_s: int) -> None:
        self.c, self.spec, self.t0_s = cols, spec, t0_s
        self.seconds = spec["blocks"] * spec["block_seconds"]
        self._hist = self._by_vu = self._errors = None

    # -- metrics -----------------------------------------------------------

    def _cum_hist(self) -> np.ndarray:
        """[services, seconds + 1, HBUCKETS] int64: spans that started
        before each whole second of the data, by service and bucket."""
        if self._hist is None:
            c, S = self.c, self.spec["services"]
            sec = c["start_ns"] // 10**9 - self.t0_s
            flat = (c["svc"] * self.seconds + sec) * HBUCKETS \
                + log2_bucket(c["dur_ns"])
            h = np.bincount(flat, minlength=S * self.seconds * HBUCKETS
                            ).reshape(S, self.seconds, HBUCKETS)
            self._hist = np.zeros((S, self.seconds + 1, HBUCKETS), np.int64)
            np.cumsum(h, axis=1, out=self._hist[:, 1:])
        return self._hist

    def step_hist(self, start_s: int, end_s: int, step_s: int) -> np.ndarray:
        """[services, steps, HBUCKETS] counts of the query's step grid."""
        n_steps = max(-(-(end_s - start_s) // step_s), 1)
        edges = np.clip(start_s - self.t0_s + np.arange(n_steps + 1) * step_s,
                        0, self.seconds)
        cum = self._cum_hist()
        return cum[:, edges[1:]] - cum[:, edges[:-1]]

    # -- search ------------------------------------------------------------

    def _index(self, key: str, mask=None):
        """Per value of column `key`: rows sorted by start time."""
        c = self.c
        rows = np.flatnonzero(mask) if mask is not None else np.arange(
            len(c[key]))
        order = rows[np.lexsort((c["start_ns"][rows], c[key][rows]))]
        bounds = np.searchsorted(c[key][order], np.arange(
            int(c[key].max()) + 2))
        return order, bounds

    def matching_traces(self, rows: np.ndarray, start_ns: int, end_ns: int
                        ) -> set:
        """Trace ids whose matching spans `rows`, taken together, overlap
        the window."""
        c = self.c
        if len(rows) == 0:
            return set()
        tkey = c["trace_key"][rows]
        order = np.argsort(tkey, kind="stable")
        rows, tkey = rows[order], tkey[order]
        first = np.concatenate([[0], np.flatnonzero(np.diff(tkey)) + 1])
        t0 = np.minimum.reduceat(c["start_ns"][rows], first)
        t1 = np.maximum.reduceat(c["start_ns"][rows] + c["dur_ns"][rows],
                                 first)
        ok = (t1 >= start_ns) & (t0 < end_ns)
        return {bytes(t).hex() for t in c["trace_id"][rows[first[ok]]]}

    def search_vu(self, vu: int, x_ms: int, start_s: int, end_s: int) -> set:
        if self._by_vu is None:
            self._by_vu = self._index("vu")
        order, bounds = self._by_vu
        rows = order[bounds[vu]:bounds[vu + 1]]
        rows = rows[self.c["dur_ns"][rows] > x_ms * 10**6]
        return self.matching_traces(rows, start_s * 10**9, end_s * 10**9)

    def search_errors(self, svc: int, start_s: int, end_s: int) -> set:
        if self._errors is None:
            self._errors = self._index("svc", self.c["status"] == 2)
        order, bounds = self._errors
        rows = order[bounds[svc]:bounds[svc + 1]]
        return self.matching_traces(rows, start_s * 10**9, end_s * 10**9)

    def trace_spans(self, block: int, trace_no: int) -> tuple[str, dict]:
        """(hex id, {span id hex: (start, end, name)}) of a trace."""
        T, per = self.spec["trace_len"], self.spec["spans_per_block"]
        lo = block * per + trace_no * T
        c = self.c
        return bytes(c["trace_id"][lo]).hex(), {
            int(c["span_id"][r]).to_bytes(8, "little").hex(): (
                int(c["start_ns"][r]), int(c["start_ns"][r] + c["dur_ns"][r]),
                f"op-{int(c['name'][r]):04d}") for r in range(lo, lo + T)}


# -- checks of one served answer; each returns a complaint or None ----------

QUANTILE_RTOL = 1e-9   # the same float64 formula evaluated in another order


def _label(series: dict, key: str):
    for lab in series["labels"]:
        if lab["key"] == key:
            return next(iter(lab["value"].values()))
    return None


def _series_by_service(body: bytes, n_steps: int):
    got = {}
    for s in json.loads(body)["series"]:
        name = _label(s, "resource.service.name")
        vals = [p["value"] for p in s["samples"]]
        if name is None or len(vals) != n_steps:
            return None, f"series {s['labels']} has {len(vals)} steps, " \
                         f"{n_steps} asked"
        got[name] = np.asarray([0.0 if v is None else float(v) for v in vals])
    return got, None


def check_rate(data: BlockData, r: dict, body: bytes):
    want = data.step_hist(r["start"], r["end"], r["step"]).sum(axis=2)
    got, err = _series_by_service(body, want.shape[1])
    if err:
        return err
    for svc in range(want.shape[0]):
        name = f"svc-{svc:04d}"
        have = got.pop(name, np.zeros(want.shape[1]))
        counts = have * r["step"]
        bad = np.flatnonzero(np.abs(counts - want[svc]) > 1e-6)
        if len(bad):
            i = int(bad[0])
            return (f"rate of {name}: step {i} counts {counts[i]}, "
                    f"reference {int(want[svc, i])} ({len(bad)} steps off)")
    return f"series nobody pushed: {sorted(got)[:3]}" if got else None


def check_quantile(data: BlockData, r: dict, body: bytes):
    hist = data.step_hist(r["start"], r["end"], r["step"])
    want = log2_quantile(r["quantile"], hist)
    got, err = _series_by_service(body, want.shape[1])
    if err:
        return err
    for svc in range(want.shape[0]):
        name = f"svc-{svc:04d}"
        have = got.pop(name, np.zeros(want.shape[1]))
        bad = np.flatnonzero(~np.isclose(have, want[svc], rtol=QUANTILE_RTOL,
                                         atol=0.0))
        if len(bad):
            i = int(bad[0])
            return (f"p{r['quantile']} of {name}: step {i} is {have[i]}, "
                    f"reference {want[svc, i]} ({len(bad)} steps off)")
    return f"series nobody pushed: {sorted(got)[:3]}" if got else None


def _check_ids(got_ids: list, want: set, limit: int, what: str):
    got = set(got_ids)
    if len(got) != len(got_ids):
        return f"{what}: a trace came back twice"
    if len(want) < limit:
        if got != want:
            return (f"{what}: {len(got)} traces, reference {len(want)}: "
                    f"{sorted(got ^ want)[:3]}")
    elif len(got) != limit or not got <= want:
        # at the limit upstream promises any `limit` matching traces
        return (f"{what}: {len(got)} traces of which "
                f"{len(got - want)} do not match; {len(want)} match, "
                f"limit {limit}")
    return None


def _search_ids(body: bytes) -> list:
    return [t["traceID"].rjust(32, "0") for t in json.loads(body)["traces"]]


def check_search_vu(data: BlockData, r: dict, body: bytes):
    want = data.search_vu(r["vu"], r["x_ms"], r["start"], r["end"])
    return _check_ids(_search_ids(body), want, r["limit"],
                      f"vu-{r['vu']:02d} over {r['x_ms']} ms")


def check_search_errors(data: BlockData, r: dict, body: bytes):
    want = data.search_errors(r["svc"], r["start"], r["end"])
    return _check_ids(_search_ids(body), want, r["limit"],
                      f"errors of svc-{r['svc']:04d}")


def check_trace_by_id(data: BlockData, r: dict, body: bytes):
    hexid, want = data.trace_spans(r["block"], r["trace_no"])
    got = {s["span_id"]: (int(s["start_unix_nano"]), int(s["end_unix_nano"]),
                          s["name"]) for s in json.loads(body)["spans"]}
    if got != want:
        return f"trace {hexid}: {len(got)} spans came back, pushed {len(want)}"
    return None
