"""Traffic kind `otlp_push`: closed-loop OTLP writers, as k6 virtual users.

Set-up boots the App, sends one canary of each push shape the coalescer
can form (each landing alone, as `chip_smoke.phase_warm`), prefills every
tenant to its full series table, and starts the window right after a
collection tick that began with all of that in place has ended. The
judge is `chip_smoke.phase_collect`'s oracle over everything acknowledged
since boot, with the quiescence rule and the discard identity of ISSUE 24.
"""

from __future__ import annotations

import json
import time

import numpy as np

from chipbench import costs, spans
from chipbench.loadgen import send_push
from chipbench.lib import (BenchFailure, Sink, boot, get_json, http_call,
                           metric_sum, say, scrape)

COLLECT = "tempo_metrics_generator_collect_duration_seconds"


def acked(d: dict) -> bool:
    return 200 <= d["status"] < 300 and not d["body"]


class Mix:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.sent: list[dict] = []          # every push since boot

    # -- set-up ------------------------------------------------------------

    def setup(self) -> None:
        ctx, tr = self.ctx, self.ctx.traffic
        self.tenants = ctx.config["tenants"]
        self.schema = ctx.config["schema"]
        self.shapes = {g * p: (g, p, t) for g, p, t in tr["warm_shapes"]}
        self.n_push = tr["push"][0] * tr["push"][1]
        ctx.sink = Sink()
        ctx.app, ctx.srv, ctx.port = boot(ctx.config, ctx.workdir,
                                          ctx.sink.url)
        say(phase="booted", at_s=ctx.clock())
        self.next_idx = {t: 0 for t in self.tenants}
        self.built = {n: spans.PushShape(*gpt)
                      for n, gpt in self.shapes.items()}
        for n in self.shapes:                       # the canaries
            for tenant in self.tenants:
                idx = self.next_idx[tenant]
                self.next_idx[tenant] = idx + 1
                self.sent.append(send_push(
                    ctx.port, ctx.seed, self.tenants, tenant, idx, n,
                    self.built[n], self.schema, 600.0))
                self.drain("a canary push")
        say(phase="canaries", at_s=ctx.clock())
        # prefill from a push index on which the walk over a service's
        # series starts a fresh visit (see spans.draw_push)
        per = self.schema["services"] // tr["push"][0]
        for t in self.tenants:
            self.next_idx[t] = -(-self.next_idx[t] // per) * per
        jobs = [(t, self.n_push) for _ in range(tr["prefill_pushes_per_tenant"])
                for t in self.tenants]
        res = ctx.run_child(dict(self.child_spec(), jobs=jobs, seconds=None))
        self.note(res)
        self.drain("the prefill")
        self.t_ready = time.monotonic()
        say(phase="prefilled", at_s=ctx.clock(), pushes=len(self.sent))

    def drain(self, what: str) -> None:
        deadline = time.monotonic() + 900
        while self.ctx.app.sched.pending():
            if time.monotonic() > deadline:
                raise BenchFailure(f"scheduler did not drain {what}")
            time.sleep(0.02)

    def note(self, res: dict) -> None:
        self.sent += res["done"]
        for d in res["done"]:
            if "idx" in d:
                self.next_idx[d["tenant"]] = max(self.next_idx[d["tenant"]],
                                                 d["idx"] + 1)

    def child_spec(self) -> dict:
        return {"kind": "push", "seed": self.ctx.seed,
                "tenants": list(self.tenants), "schema": self.schema,
                "shapes": self.shapes, "n_spans": self.n_push,
                "next_idx": dict(self.next_idx)}

    def wait_start(self) -> None:
        """Return right after the end of a collection tick that began
        after set-up was complete: that tick was a full-size collect, so
        the window's own ticks are warm, and every window starts at the
        same phase of the collection loop. A tick has ended when the
        collect histogram's count is a multiple of the tenants again; its
        length is the growth of the histogram's sum."""
        ctx, n = self.ctx, len(self.tenants)
        m = scrape(ctx.port)
        count0, sum0 = (metric_sum(m, COLLECT + "_count"),
                        metric_sum(m, COLLECT + "_sum"))
        even_sum = sum0 if count0 % n == 0 else None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            time.sleep(0.25)
            m = scrape(ctx.port)
            count, total = (metric_sum(m, COLLECT + "_count"),
                            metric_sum(m, COLLECT + "_sum"))
            if count == count0:
                continue
            now, count0 = time.monotonic(), count
            if count % n:
                continue
            length = total - even_sum if even_sum is not None else None
            even_sum = total
            if length is not None and now - length >= self.t_ready - 0.5:
                say(phase="tick_ended", at_s=ctx.clock(),
                    tick_s=round(length, 3))
                return
        raise BenchFailure("no collection tick ended within 300 s")

    # -- judging -----------------------------------------------------------

    def collect_sums(self, tenant: str) -> dict:
        samples = get_json(self.ctx.port, "/internal/generator/collect",
                           tenant, ts_ms=int(time.time() * 1000))["samples"]
        out = {"series": 0}
        for s in samples:
            out[s["name"]] = out.get(s["name"], 0.0) + s["value"]
            out["series"] += s["name"] == "traces_spanmetrics_calls_total"
        return out

    def quiescent_collect(self) -> dict:
        """Per tenant, collects a collection interval apart until two
        successive ones agree."""
        interval = self.ctx.app.cfg.generator.registry.collection_interval_s
        last = {t: self.collect_sums(t) for t in self.tenants}
        for round_no in range(1, 6):
            time.sleep(interval)
            now = {t: self.collect_sums(t) for t in self.tenants}
            say(phase="collected", round=round_no, at_s=self.ctx.clock(),
                agree=now == last)
            if now == last:
                return now
            last = now
        raise BenchFailure("the collected state never came to rest")

    def judge(self, res: dict, t_go: float, seconds: float) -> dict:
        ctx, tr = self.ctx, self.ctx.traffic
        self.note(res)
        self.drain("the window's pushes")
        complaints: list[str] = []
        failed = sum(not acked(d) for d in res["done"])
        for d in self.sent:
            if not acked(d):
                complaints.append(f"push {d.get('tenant')}#{d.get('idx')} -> "
                                  f"{d['status']} {d['body'][:120]!r}"
                                  f"{d.get('error', '')}")
        got = self.quiescent_collect()
        m = scrape(ctx.port)
        discarded = {dict(ls).get("reason", "?"): v for (name, ls), v
                     in m.items() if name == "tempo_discarded_spans_total"
                     and v}
        keep = metric_sum(m, "tempo_sched_ingest_keep_fraction")
        if keep != 1.0:
            complaints.append(f"overload sampling armed: keep fraction {keep}")
        for name in ("tempo_sched_dispatch_errors_total",
                     "tempo_distributor_push_failures_total",
                     "tempo_remote_write_failed_sends_total"):
            if metric_sum(m, name):
                complaints.append(f"{name} = {metric_sum(m, name)}")
        if not ctx.sink.bodies or not max(ctx.sink.bodies):
            complaints.append("no remote-write body reached the sink")
        report, lost = {}, sum(discarded.values())
        for ti, tenant in enumerate(self.tenants):
            cols = [spans.draw_push(ctx.seed, ti, d["idx"], self.built[d["n"]],
                                    self.schema, d["now_ns"])
                    for d in self.sent if acked(d) and d["tenant"] == tenant]
            col = {k: np.concatenate([c[k] for c in cols]) for k in (
                "svc", "name", "kind", "status", "start_ns", "end_ns")}
            n_acked = len(col["svc"])
            inst = ctx.app.generator.instances[tenant]
            slack = int(inst.spans_filtered_slack)
            lost += slack
            # the discard identity: every acknowledged span is counted
            # once or is owned by a discard counter. The distributor's
            # discards carry no tenant label, so they are allowed to
            # either tenant; with none (every run so far) this is exact
            want = n_acked - slack
            calls = got[tenant].get("traces_spanmetrics_calls_total", 0.0)
            count = got[tenant].get("traces_spanmetrics_latency_count", 0.0)
            received = metric_sum(
                m, "tempo_metrics_generator_spans_received_total",
                tenant=tenant)
            for what, v in (("calls_total", calls), ("latency_count", count)):
                if not want - sum(discarded.values()) <= v <= want:
                    complaints.append(
                        f"{tenant}: {what} {v} != {n_acked} acknowledged - "
                        f"{slack} outside the slack window - discarded "
                        f"{discarded}; the generator received {received}")
            dur_s = ((col["end_ns"] - col["start_ns"]) / 1e9).astype(
                np.float32)
            want_sum = float(dur_s.astype(np.float64).sum())
            lat_sum = got[tenant].get("traces_spanmetrics_latency_sum", 0.0)
            rel = abs(lat_sum - want_sum) / want_sum
            if rel > tr["latency_sum_rtol"] and not lost:
                complaints.append(f"{tenant}: latency_sum {lat_sum} vs f64 "
                                  f"oracle {want_sum} (rel {rel:.3g})")
            pairs = sum(c["pairs"] for c in cols)
            edges = got[tenant].get("traces_service_graph_request_total", 0.0)
            if edges != pairs and not lost:
                complaints.append(f"{tenant}: service graph counted {edges} "
                                  f"edges, {pairs} acknowledged")
            if got[tenant]["series"] < tr["min_series"]:
                complaints.append(f"{tenant}: {got[tenant]['series']} active "
                                  f"series < {tr['min_series']}")
            worst = self.check_sketch(tenant, col, dur_s, complaints)
            report[tenant] = {
                "acknowledged": n_acked, "calls_total": calls,
                "spans_received": received, "slack_filtered": slack,
                "series_active": got[tenant]["series"], "edges": pairs,
                "latency_sum_rel_err": rel,
                "sketch_worst_rel_err_vs_rank": worst}
        self.readback(res, complaints)
        if lost:
            # spans the program discarded under a reason: their pushes
            # count as failed, as many as the spans fill
            failed += -(-int(lost) // self.n_push)
        say(oracle=report, discarded=discarded,
            remote_write_requests=len(ctx.sink.bodies))
        in_window = [d for d in res["done"] if acked(d)
                     and t_go <= d["t1"] <= t_go + seconds]
        return {"attempted": len(res["done"]), "failed": failed,
                "complaints": complaints,
                "latencies_ms": [(d["t1"] - d["t0"]) * 1e3 for d in in_window],
                # counted only where the collected counters bear them out
                "units": 0 if any("calls_total" in c for c in complaints)
                else sum(d["n"] for d in in_window)}

    def check_sketch(self, tenant, col, dur_s, complaints) -> float:
        """p50 and p99 of the busiest series: within `sketch_rel_err` of
        a value at a neighbouring rank (`chip_smoke.phase_collect`)."""
        tr = self.ctx.traffic
        code = ((col["svc"] * 10000 + col["name"]) * 10 + col["kind"]) * 10 \
            + col["status"]
        uniq, inv, cnt = np.unique(code, return_inverse=True,
                                   return_counts=True)
        worst, err = 0.0, tr["sketch_rel_err"]
        for q in (0.5, 0.99):
            got = {}
            for e in get_json(self.ctx.port, "/internal/generator/quantile",
                              tenant, q=q)["quantiles"]:
                d = dict(e["labels"])
                got[(d["service"], d["span_name"], d["span_kind"],
                     d["status_code"])] = e["value"]
            for i in np.argsort(-cnt)[:tr["sketch_probes"]]:
                c = int(uniq[i])
                key = (f"svc-{c // 1000000:04d}", f"op-{c // 100 % 10000:04d}",
                       spans.KIND_STRS[c // 10 % 10], spans.STATUS_STRS[c % 10])
                vals = np.sort(dur_s[inv == i].astype(np.float64))
                k = int(np.ceil(q * len(vals))) - 1
                lo = vals[max(k - 1, 0)] * (1 - 1.1 * err)
                hi = vals[min(k + 1, len(vals) - 1)] * (1 + 1.1 * err)
                if key not in got or not lo <= got[key] <= hi:
                    complaints.append(f"{tenant}: q{q} of {key} = "
                                      f"{got.get(key)} outside [{lo}, {hi}] "
                                      f"({len(vals)} spans)")
                else:
                    worst = max(worst, abs(got[key] - vals[k]) / vals[k])
        return worst

    def readback(self, res: dict, complaints: list) -> None:
        """A seeded sample of the window's acknowledged traces, whole."""
        ctx = self.ctx
        ok = [d for d in res["done"] if acked(d)]
        if not ok:
            return
        rng = np.random.default_rng([ctx.seed, 11])
        n_read = 0
        for d in (ok[int(i)] for i in rng.choice(
                len(ok), size=min(ctx.traffic["readback_traces"], len(ok)),
                replace=False)):
            c = spans.draw_push(ctx.seed, self.tenants.index(d["tenant"]),
                                d["idx"], self.built[d["n"]], self.schema,
                                d["now_ns"])
            row = int(rng.integers(len(c["svc"])))
            rows = np.flatnonzero((c["trace_id"] == c["trace_id"][row]
                                   ).all(axis=1))
            hexid = bytes(c["trace_id"][row]).hex()
            want = {int(c["span_id"][r]).to_bytes(8, "little").hex(): (
                int(c["start_ns"][r]), int(c["end_ns"][r]),
                f"op-{int(c['name'][r]):04d}") for r in rows}
            status, body = http_call(ctx.port, "GET", "/api/traces/" + hexid,
                                     d["tenant"])
            got = {} if status != 200 else {
                s["span_id"]: (int(s["start_unix_nano"]),
                               int(s["end_unix_nano"]), s["name"])
                for s in json.loads(body)["spans"]}
            n_read += 1
            if got != want:
                complaints.append(f"{d['tenant']}: trace {hexid} read back "
                                  f"as {len(got)} spans ({status}), "
                                  f"acknowledged {len(want)}")
        say(traces_read_back=n_read, at_s=ctx.clock())

    def least_bytes(self, res: dict, lo: float, hi: float) -> dict:
        n = sum(d["n"] for d in res["done"] if acked(d)
                and lo <= (d["t0"] + d["t1"]) / 2 <= hi)
        return {"fused_update": costs.fused_update_bytes(n)}
