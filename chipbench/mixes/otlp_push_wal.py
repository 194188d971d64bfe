"""Traffic kind `otlp_push_wal`: `otlp_push` on the durable single binary.

The same closed-loop writers and the same oracle over the collected
state. The configuration turns the generator's ingest log on (`fsync:
batch`) beside a single-member fleet, so the judge also holds the run to
what that deployment guarantees, as far as a run can show it:

1. from `/metrics` since boot: an append for every acknowledged push, an
   fsync for no more than every append and rotation, no dead letter;
2. the log on disk, read by the configuration's own plain reader
   (`chipbench/reference_wal.py`): frames whole and contiguous, one
   record an acknowledged push, and a seeded sample of records equal,
   span for span, to what `spans.draw_push` made for those pushes;
3. last, with nothing left to read from the server: the App is stopped
   as SIGTERM stops it (`App.shutdown`, whole), and within 60 s the
   disk holds one checkpoint blob a tenant and no segment that the
   blob's watermark wholly covers.

`wal.dir` is placed in the run's workdir before the boot: `lib.boot`
moves the storage there and not the log, and a log left at its default
(relative to the cwd) would be REPLAYED by the next run's boot.
"""

from __future__ import annotations

import dis
import io
import json
import os
import statistics
import sys
import threading
import time
import urllib.parse

import numpy as np

from chipbench import reference_wal, spans
from chipbench.lib import metric_sum, parse_exposition, say, scrape
from chipbench.mixes import otlp_push
from chipbench.mixes.otlp_push import acked

APPENDS = "tempo_wal_appended_batches_total"
FSYNCS = "tempo_wal_fsyncs_total"
DEAD = "tempo_wal_dead_letters_total"
STOP_WITHIN_S = 60.0
PROBE_BYTES, PROBE_ROUNDS = 100_000, 20


def filesystem_of(path: str) -> dict:
    """The mount that holds `path`, from /proc/mounts: the longest mount
    point that is a prefix of it."""
    path, best = os.path.realpath(path), ("", "?", "?")
    try:
        with open("/proc/mounts") as f:
            for line in f:
                dev, at, kind = line.split()[:3]
                at = at.replace("\\040", " ")
                if (path == at or path.startswith(at.rstrip("/") + "/")) \
                        and len(at) > len(best[0]):
                    best = (at, kind, dev)
    except OSError:
        pass
    return {"mount": best[0], "fstype": best[1], "device": best[2]}


def fsync_probe_ms(workdir: str) -> float:
    """Median of PROBE_ROUNDS appends of PROBE_BYTES, each followed by an
    fsync, in the workdir: what one record of the log costs this disk."""
    path, block, took = os.path.join(workdir, "fsync.probe"), \
        bytes(PROBE_BYTES), []
    with open(path, "ab", buffering=0) as f:
        for _ in range(PROBE_ROUNDS):
            f.write(block)
            t0 = time.perf_counter()
            os.fsync(f.fileno())
            took.append((time.perf_counter() - t0) * 1e3)
    os.unlink(path)
    return statistics.median(took)


def span_rows(service, name, kind, status, dur_ns) -> list:
    """The spans of a push as a sorted list: a multiset."""
    return sorted(zip(map(str, service), map(str, name), map(int, kind),
                      map(int, status), map(int, dur_ns)))


def flushes_first(shutdown) -> bool:
    """Whether this `App.shutdown` flushes the ingester before the fleet
    cuts its checkpoints, from the order of its calls
    `self.<part>.shutdown()` in its code."""
    names = [i.argval for i in dis.get_instructions(shutdown)
             if i.opname == "LOAD_ATTR"]
    calls = [a for a, b in zip(names, names[1:]) if b == "shutdown"]
    return "ingester" in calls and "fleet" in calls \
        and calls.index("ingester") < calls.index("fleet")


class Mix(otlp_push.Mix):
    def place_log(self) -> None:
        """`wal.dir` into the workdir; the checkpoints lie where
        `lib.boot` moves the storage (`fleet.checkpoint_prefix` is the
        shipped one)."""
        ctx = self.ctx
        self.wal_dir = os.path.join(ctx.workdir, "generator-wal")
        self.blobs_dir = os.path.join(ctx.workdir, "blocks",
                                      "fleet-checkpoints")
        ctx.config["yaml_overrides"]["wal"]["dir"] = self.wal_dir

    def setup(self) -> None:
        from tempo_tpu.obs.jaxruntime import RUNTIME

        ctx = self.ctx
        self.place_log()
        # the log's counters are the process's, not the App's: what they
        # read before the boot is not this run's
        self.m_boot = parse_exposition(RUNTIME.render())
        super().setup()
        say(phase="disk", at_s=ctx.clock(), workdir=ctx.workdir,
            **filesystem_of(ctx.workdir),
            fsync_probe_ms=fsync_probe_ms(ctx.workdir),
            wal=ctx.app.generator.wal is not None
            and ctx.app.generator.wal.cfg.fsync,
            fleet=ctx.app.fleet is not None)

    # -- judging -----------------------------------------------------------

    def judge(self, res: dict, t_go: float, seconds: float) -> dict:
        judged = super().judge(res, t_go, seconds)
        complaints, t0 = judged["complaints"], time.monotonic()
        logs = self.check_log(complaints)
        self.check_counters(scrape(self.ctx.port), logs, complaints)
        say(phase="log_read", at_s=self.ctx.clock(),
            log_s=round(time.monotonic() - t0, 3))
        self.check_clean_stop(logs, complaints)
        return judged

    def since_boot(self, m: dict, family: str) -> float:
        return metric_sum(m, family) - metric_sum(self.m_boot, family)

    def check_counters(self, m: dict, logs: dict, complaints: list) -> None:
        """Part 1. The fsync policy is held from both sides: booted as
        `batch`, no more fsyncs than appends and segments, and no fewer
        than a closed loop of `clients` handlers can make of them (one
        commit covers at most one append a handler; `interval` makes
        tens of fsyncs for thousands of appends)."""
        n_acked = sum(acked(d) for d in self.sent)
        appends, fsyncs, dead = (self.since_boot(m, f)
                                 for f in (APPENDS, FSYNCS, DEAD))
        segments = sum(len(log) for log in logs.values())
        log = self.ctx.app.generator.wal
        policy = log.cfg.fsync if log is not None else "no log"
        if policy != "batch":
            complaints.append(f"the log was booted with fsync: {policy}, "
                              "the configuration states batch")
        if not n_acked <= appends <= len(self.sent):
            complaints.append(f"{APPENDS} grew by {appends:g}: "
                              f"{n_acked} pushes were acknowledged of "
                              f"{len(self.sent)} sent")
        least = appends / self.ctx.traffic["clients"]
        if not max(least, 1) <= fsyncs <= appends + segments:
            complaints.append(f"{FSYNCS} grew by {fsyncs:g} for "
                              f"{appends:g} appends and {segments} segments: "
                              f"batch makes {least:g} at the least")
        if dead:
            complaints.append(f"{DEAD} = {dead:g}")
        say(wal={"appends": appends, "fsyncs": fsyncs, "segments": segments,
                 "acknowledged": n_acked, "dead_letters": dead,
                 "fsync": policy})

    def draw(self, ti: int, d: dict) -> dict:
        return spans.draw_push(self.ctx.seed, ti, d["idx"],
                               self.built[d["n"]], self.schema, d["now_ns"])

    def tenant_dir(self, tenant: str) -> str:
        return os.path.join(self.wal_dir, urllib.parse.quote(tenant, safe=""))

    def check_log(self, complaints: list, in_flight: int = 0) -> dict:
        """Part 2. {tenant: [(segment, [seq, ...])]} of what was read.
        `in_flight`: the pushes that never returned (a server killed
        under them, `chip_smoke.py --recover`), which may be in the log
        and cannot be drawn again; the cell has none."""
        out, strangers = {}, []
        for ti, tenant in enumerate(self.tenants):
            log, faults = reference_wal.read_tenant(self.tenant_dir(tenant))
            # a server killed mid-write may leave its last frame torn
            complaints += [f"{tenant}: log: {f}" for f in faults
                           if not in_flight or any(w in f for w in (
                               "not the last", "follows", "first record"))]
            out[tenant] = [(name, [r[0] for r in records])
                           for name, records in log]
            mine = [d for d in self.sent if d.get("tenant") == tenant]
            # least span id of a push -> the push
            drawn = {int(self.draw(ti, d)["span_id"].min()): d for d in mine}
            seen: dict = {}
            records = [(name, rec) for name, recs in log for rec in recs]
            for name, (seq, meta, arrays, _) in records:
                key = int(np.ascontiguousarray(arrays["spans"]["span_id"])
                          .view("<i8").min()) \
                    if meta.get("kind") == "staged" and meta.get("n") else None
                if key not in drawn:
                    strangers.append(f"{tenant}: record {seq} of {name} "
                                     f"({meta.get('kind')}, n="
                                     f"{meta.get('n')}) is no push sent")
                    continue
                d = drawn[key]
                if key in seen:
                    complaints.append(f"{tenant}: push #{d['idx']} is in the "
                                      f"log twice: records {seen[key]} and "
                                      f"{seq}")
                seen[key] = seq
                if meta["n"] != d["n"] or len(arrays["spans"]) != d["n"]:
                    complaints.append(f"{tenant}: record {seq} holds "
                                      f"{meta['n']} spans, push #{d['idx']} "
                                      f"had {d['n']}")
            for key, d in drawn.items():
                # a push without its 2xx may be in the log; never an
                # acknowledged one out of it
                if acked(d) and key not in seen:
                    complaints.append(f"{tenant}: acknowledged push "
                                      f"#{d['idx']} has no good frame on "
                                      "disk")
            self.check_sample(ti, tenant, log, drawn, complaints)
        if len(strangers) > in_flight:
            complaints += strangers
        say(wal_log={t: {"segments": len(log),
                         "records": sum(len(s) for _, s in log)}
                     for t, log in out.items()})
        return out

    def check_sample(self, ti: int, tenant: str, log: list, drawn: dict,
                     complaints: list) -> None:
        """A seeded sample of the tenant's records, the first and the
        last of every segment among them, span for span."""
        at = [(si, ri) for si, (_, recs) in enumerate(log)
              for ri in range(len(recs))]
        must = {(si, ri) for si, (_, recs) in enumerate(log) if recs
                for ri in (0, len(recs) - 1)}
        rest = [p for p in at if p not in must]
        rng = np.random.default_rng([self.ctx.seed, 13, ti])
        more = max(self.ctx.traffic["wal_sample_records"] - len(must), 0)
        picked = sorted(must) + [rest[int(i)] for i in rng.choice(
            len(rest), size=min(more, len(rest)), replace=False)]
        for si, ri in picked:
            name, (seq, meta, arrays, strings) = log[si][0], log[si][1][ri]
            if meta.get("kind") != "staged":
                continue                    # complained of above
            got = reference_wal.span_columns(arrays, strings)
            d = drawn.get(int(got["span_id"].min()))
            if d is None:
                continue
            c = self.draw(ti, d)
            want = span_rows([f"svc-{s:04d}" for s in c["svc"]],
                             [f"op-{n:04d}" for n in c["name"]],
                             c["kind"], c["status"], c["dur_ns"])
            if span_rows(got["service"], got["name"], got["kind"],
                         got["status"], got["end_ns"] - got["start_ns"]) \
                    != want:
                complaints.append(f"{tenant}: record {seq} of {name} "
                                  f"differs from push #{d['idx']} as sent")
        say(wal_sampled={tenant: len(picked)})

    def check_clean_stop(self, logs: dict, complaints: list) -> None:
        """Part 3: what SIGTERM does (`fleet/worker.py`: `App.shutdown`,
        whole, nothing of it patched), and guarantee 4 read from the
        disk until it holds, or the stop is past the fleet's part (the
        checkpoints and the truncation: nothing that guarantee 4 reads
        changes after it), or STOP_WITHIN_S have passed. What the disk
        lacks then is a complaint. The ingester's flush comes after the
        fleet's part and at the cell's size takes minutes (every live
        trace completed to a block: PERF.md section 7): the run does not
        wait for it, says which part the stop is in, and exits under it.
        A program that flushes BEFORE it checkpoints (the order before
        PR 35, read off `App.shutdown`'s code) comes to its checkpoints
        in no run's time: the line says `guarantee_4: null` and part 3
        holds nothing there."""
        ctx, t0 = self.ctx, time.monotonic()
        flush_first = flushes_first(type(ctx.app).shutdown)
        flush = type(ctx.app.ingester).__qualname__ + ".shutdown"

        def stop() -> None:
            ctx.srv.shutdown()
            ctx.srv.server_close()
            ctx.app.shutdown()

        self.stopper = threading.Thread(target=stop, daemon=True)
        self.stopper.start()
        while True:
            ended = not self.stopper.is_alive()
            part = self.stop_is_in()            # before the disk is read
            faults = self.stop_faults(logs)
            if not faults or flush_first or ended or part == flush \
                    or time.monotonic() - t0 > STOP_WITHIN_S:
                break
            time.sleep(0.25)
        say(phase="stopped", at_s=ctx.clock(),
            guarantee_4=None if faults and flush_first else not faults,
            guarantee_4_s=round(time.monotonic() - t0, 3),
            flushes_first=flush_first, stop_is_in=part, app_stopped=ended)
        if not flush_first:
            complaints += [f"{time.monotonic() - t0:.1f} s into the stop, "
                           f"in {part or 'no part of it'}: {f}"
                           for f in faults]

    def stop_is_in(self) -> str:
        """The part of `App.shutdown` the stopping thread is in, read
        off its stack: the function `App.shutdown` has called."""
        frame, chain = sys._current_frames().get(self.stopper.ident), []
        while frame is not None:
            chain.append(frame.f_code.co_qualname)
            frame = frame.f_back
        whole = type(self.ctx.app).__qualname__ + ".shutdown"
        return chain[chain.index(whole) - 1] if whole in chain[1:] else ""

    def stop_faults(self, logs: dict) -> list:
        """What the disk lacks, now, of guarantee 4."""
        out = []
        for tenant in self.tenants:
            blobs_at = os.path.join(self.blobs_dir,
                                    urllib.parse.quote(tenant, safe=""))
            blobs = sorted(n for n in (os.listdir(blobs_at) if os.path.isdir(
                blobs_at) else []) if n.endswith(".ckpt"))
            if len(blobs) != 1:
                out.append(f"{tenant}: the clean stop left {len(blobs)} "
                           "checkpoint blobs, not one")
                continue
            try:
                with open(os.path.join(blobs_at, blobs[0]), "rb") as f, \
                        np.load(io.BytesIO(f.read()), allow_pickle=False) as z:
                    marks = json.loads(z["__meta__"].tobytes())["wal"]
            except (OSError, ValueError, KeyError) as e:
                out.append(f"{tenant}: the blob {blobs[0]} does not read: "
                           f"{type(e).__name__}: {e}")
                continue
            covered = max((int(v[1]) for v in marks.values()), default=-1)
            last = max((s for _, seqs in logs[tenant] for s in seqs),
                       default=-1)
            if covered < last:
                out.append(f"{tenant}: the blob's watermark is {covered}, "
                           f"the log reached {last}")
            # a segment holds [its name's seq, the next one's): left
            # behind while every record of it is at or below the mark
            left = reference_wal.segments(self.tenant_dir(tenant))
            firsts = [int(n.split(".")[0]) for n in left] + [last + 1]
            kept = [n for n, nxt in zip(left, firsts[1:])
                    if nxt - 1 <= covered]
            if kept:
                out.append(f"{tenant}: segments {kept} are wholly under "
                           f"the watermark {covered} and were not truncated")
        return out
