"""Traffic kind `otlp_push_hotrod`: HotROD requests that every service
exports on its own, in OpenTelemetry SDK batches (`loadgen_hotrod.py`).

What differs from `otlp_push`:

- the load comes from `loadgen_hotrod.py`, which keeps one export queue a
  (tenant, service) and holds a seeded share of the batches past the
  generator's slack; it sends from its start, and the window opens after
  `warm_s` of that traffic and a collection tick, so that held batches
  arrive inside the window as they would in steady state;
- set-up refuses a program that does not run the tenant's service-graph
  overrides (`sg_*`): the deployment is its store size, its wait, its
  buckets and its peer attributes, and a program that ignores them cannot
  run it (it exits 1 at set-up, never with a result);
- the canaries are call pairs, both halves in one push, so that each
  warms exactly one shape of the service-graph step;
- after the window the judge waits out the store (`sg_wait_s` + 1 s),
  sends one in-slack span a tenant so that every pending half expires
  (expiry runs inside a tenant's own pushes), collects, and holds the
  service graph to `reference_hotrod.py` exactly, the span metrics to the
  k6 oracle's rules, the slack discards to the held batches, the store to
  empty and undropped, and four traces a tenant to reading back whole.
"""

from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
import threading
import time

import numpy as np

from chipbench import costs_edges, reference_hotrod
from chipbench import loadgen_hotrod as lg
from chipbench.lib import (HERE, BenchFailure, Sink, boot, get_json,
                           http_call, metric_sum, say, scrape)
from chipbench.mixes import otlp_push
from chipbench.mixes.otlp_push import acked

RECEIVED = "tempo_metrics_generator_spans_received_total"
DROPPED = "tempo_metrics_generator_processor_service_graphs_dropped_spans"
STORE = "tempo_metrics_generator_servicegraphs_store_items"
EDGES = "tempo_metrics_generator_servicegraphs_edges_total"
SG = "traces_service_graph_request"
SM_CALLS = "traces_spanmetrics_calls_total"
SM_COUNT = "traces_spanmetrics_latency_count"
SM_SUM = "traces_spanmetrics_latency_sum"


def run_child(ctx, spec: dict, go=None) -> dict:
    """`run.py::run_child` with this mix's load generator, which sends from
    its start and says `ready` after `warm_s` of traffic."""
    spec = dict(spec, port=ctx.port, clients=ctx.traffic["clients"],
                timeout=ctx.traffic.get("timeout_s", 300.0))
    ctx.n_child += 1
    spec_path = os.path.join(ctx.workdir, f"child{ctx.n_child}.spec")
    out_path = os.path.join(ctx.workdir, f"child{ctx.n_child}.out")
    with open(spec_path, "wb") as f:
        pickle.dump(spec, f)
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen_hotrod.py"), spec_path,
         out_path], stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        if child.stdout.readline().strip() != "ready":
            raise BenchFailure("the load generator did not come up")
        go()
        child.stdin.write("go\n")
        child.stdin.flush()
        rc = child.wait(timeout=spec["seconds"] + 1200)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if rc != 0:
        raise BenchFailure(f"the load generator exited {rc}")
    with open(out_path, "rb") as f:       # written by our own child only
        return pickle.load(f)


def samples_by_key(samples: list) -> dict:
    """/internal/generator/collect's samples as {(name, labels): value}."""
    return {(s["name"], tuple(sorted(map(tuple, s["labels"])))): s["value"]
            for s in samples}


class Mix(otlp_push.Mix):

    # -- set-up ------------------------------------------------------------

    def setup(self) -> None:
        ctx, tr, cfg = self.ctx, self.ctx.traffic, self.ctx.config
        ctx.run_child = lambda spec, go=None: run_child(ctx, spec, go)
        self.tenants = cfg["tenants"]
        self.h = lg.Hotrod(cfg["schema"], extra=(tr["flush_span"],))
        self.sg = cfg["tenant_limits"]["generator"]
        ctx.sink = Sink()
        ctx.app, ctx.srv, ctx.port = boot(cfg, ctx.workdir, ctx.sink.url)
        say(phase="booted", at_s=ctx.clock())
        self.check_overrides()
        self.pushes: list[dict] = []        # canaries and flushes
        client, server = tr["canary_templates"]
        for k, pairs in enumerate(tr["canary_pairs"]):
            now_ns = time.time_ns()
            groups = lg.pair_columns(self.h, ctx.seed, k, pairs, client,
                                     server, now_ns)
            self.post(self.tenants[0], groups, kind="canary", key=k,
                      pairs=pairs, now_ns=now_ns)
            self.drain("a canary push")
        say(phase="canaries", at_s=ctx.clock(), pushes=len(self.pushes))

    def check_overrides(self) -> None:
        """The tenant's `sg_*` overrides are what its service-graph
        processor runs with."""
        want = {"max_items": self.sg["sg_max_items"],
                "wait_s": self.sg["sg_wait_s"],
                "histogram_buckets": tuple(self.sg["sg_histogram_buckets"]),
                "peer_attributes": tuple(self.sg["sg_peer_attributes"])}
        for tenant in self.tenants:
            proc = self.ctx.app.generator.instance(tenant).processors.get(
                "service-graphs")
            got = {k: getattr(getattr(proc, "cfg", None), k, None)
                   for k in want}
            got = {k: tuple(v) if isinstance(v, (list, tuple)) else v
                   for k, v in got.items()}
            if got != want:
                raise BenchFailure(
                    f"{tenant}: the service-graph processor runs with "
                    f"{got}, the tenant's overrides say {want}: this "
                    "program does not apply the sg_* overrides")

    def post(self, tenant: str, groups: list, **rec) -> dict:
        d = lg.post(self.ctx.port, tenant, lg.encode(self.h, groups), 600.0)
        d.update(rec, n=sum(len(c["tmpl"]) for _, c in groups))
        self.pushes.append(d)
        return d

    def child_spec(self) -> dict:
        tr, ctx = self.ctx.traffic, self.ctx
        return {"seed": ctx.seed, "tenants": list(self.tenants),
                "schema": ctx.config["schema"],
                **{k: tr[k] for k in ("batch_spans", "max_age_s",
                                      "late_share", "hold_s",
                                      "chunk_requests", "warm_s")}}

    def wait_start(self) -> None:
        """After `warm_s` of traffic, the end of a collection tick that
        began after it; in a traced run, two scrapes of the emitted-edge
        counter around the profiler's seconds (the roofline's work)."""
        self.t_ready = time.monotonic()
        super().wait_start()
        self.edge_marks: list = []
        if getattr(self.ctx.args, "trace", 0):
            plan, t_go = self.ctx.traffic["trace"], time.monotonic()
            at = t_go + plan["start_frac"] * self.ctx.args.seconds
            span = min(plan["seconds"], self.ctx.args.seconds / 3)
            threading.Thread(target=self.mark_edges, args=(at, at + span),
                             daemon=True).start()

    def mark_edges(self, *when: float) -> None:
        for t in when:
            time.sleep(max(t - time.monotonic(), 0.0))
            m = scrape(self.ctx.port)
            if any(name == EDGES for name, _ in m):
                self.edge_marks.append((time.monotonic(),
                                        metric_sum(m, EDGES)))

    def least_bytes(self, res: dict, lo: float, hi: float) -> dict:
        """The edges the step took in the traced seconds: the emitted-edge
        counter's growth between the two marks, over their span, times the
        profiler's span. Nothing where the program has no such counter."""
        marks = getattr(self, "edge_marks", [])
        if len(marks) < 2 or marks[1][0] <= marks[0][0]:
            return {}
        rate = (marks[1][1] - marks[0][1]) / (marks[1][0] - marks[0][0])
        return {"edge_update": costs_edges.edge_update_bytes(
            int(rate * (hi - lo)))}

    # -- judging -----------------------------------------------------------

    def collect_sums(self, tenant: str) -> dict:
        return samples_by_key(get_json(
            self.ctx.port, "/internal/generator/collect", tenant,
            ts_ms=int(time.time() * 1000))["samples"])

    def flush(self, res: dict) -> None:
        """Wait out the store, then one in-slack span a tenant: its push
        expires every half the tenant still holds."""
        ctx, tr = self.ctx, self.ctx.traffic
        last = max([d["t1"] for d in res["done"]] + [time.monotonic()])
        time.sleep(max(last + self.sg["sg_wait_s"] + tr["flush_after_wait_s"]
                       - time.monotonic(), 0.0))
        for ti, tenant in enumerate(self.tenants):
            now_ns = time.time_ns()
            self.post(tenant, lg.single_columns(self.h, ctx.seed, ti,
                                                len(self.h.templates) - 1,
                                                now_ns),
                      kind="flush", key=ti, now_ns=now_ns)
        self.drain("the flush pushes")

    def columns(self, ti: int, res: dict) -> dict:
        """Every span sent to tenant `ti`, drawn again, with `acked` and
        `in_slack` (acknowledged and not held) flags and `push` (index
        into `self.sent`)."""
        ctx = self.ctx
        stamps = res["chunks"][ti]
        n_req = ctx.traffic["chunk_requests"]
        streams = [[] for _ in self.h.services]
        for ci, now_ns in enumerate(stamps):
            chunk = lg.draw_chunk(self.h, ctx.seed, ti, ci, n_req, now_ns)
            for s in range(len(self.h.services)):
                streams[s].append(lg.service_rows(self.h, chunk, s))
        streams = [lg.cat(parts) for parts in streams]
        client, server = ctx.traffic["canary_templates"]
        parts, flags = [], []
        for k, d in enumerate(self.sent):
            if d.get("tenant") != self.tenants[ti]:
                continue
            if d["kind"] == "batch":
                groups = [(d["svc"], lg.take_rows(streams[d["svc"]], d["lo"],
                                                  d["hi"]))]
            elif d["kind"] == "canary":
                groups = lg.pair_columns(self.h, ctx.seed, d["key"],
                                         d["pairs"], client, server,
                                         d["now_ns"])
            else:
                groups = lg.single_columns(self.h, ctx.seed, d["key"],
                                           len(self.h.templates) - 1,
                                           d["now_ns"])
            for _, c in groups:
                parts.append(c)
                n = len(c["tmpl"])
                flags.append((np.full(n, acked(d)),
                              np.full(n, acked(d) and not d.get("hold_s")),
                              np.full(n, k)))
        cols = lg.cat(parts)
        cols["acked"], cols["in_slack"], cols["push"] = (
            np.concatenate(f) for f in zip(*flags))
        return cols

    def judge(self, res: dict, t_go: float, seconds: float) -> dict:
        ctx, tr = self.ctx, self.ctx.traffic
        self.drain("the window's pushes")
        for d in res["done"]:
            d["tenant"] = self.tenants[d["ti"]]
        self.flush(res)
        self.sent = self.pushes + res["done"]
        complaints: list[str] = []
        for d in self.sent:
            if not acked(d):
                complaints.append(f"push {d.get('tenant')} {d['kind']} -> "
                                  f"{d['status']} {d['body'][:120]!r}"
                                  f"{d.get('error', '')}")
        got = self.quiescent_collect()
        m = scrape(ctx.port)
        discarded = {dict(ls).get("reason", "?"): v for (name, ls), v
                     in m.items() if name == "tempo_discarded_spans_total"
                     and v}
        self.check_served(m, discarded, complaints)
        report, late_total = {}, 0
        for ti, tenant in enumerate(self.tenants):
            cols = self.columns(ti, res)
            report[tenant], late = self.oracle(tenant, cols, got[tenant], m,
                                               complaints)
            late_total += late
            self.readback(tenant, cols, complaints)
            del cols
        if discarded.get("outside_slack", 0.0) != late_total:
            complaints.append(f"outside_slack discards "
                              f"{discarded.get('outside_slack', 0.0):g} != "
                              f"{late_total} spans of held pushes")
        lost = sum(v for r, v in discarded.items() if r != "outside_slack") \
            + metric_sum(m, DROPPED)
        in_window = [d for d in res["done"] if acked(d)
                     and t_go <= d["t1"] <= t_go + seconds]
        failed = sum(not acked(d) for d in res["done"]
                     if t_go <= d["t1"] <= t_go + seconds)
        if lost:
            failed += -(-int(lost) // tr["batch_spans"])
        say(oracle=report, discarded=discarded,
            remote_write_requests=len(ctx.sink.bodies),
            pushes_in_window=len(in_window),
            held_in_window=sum(bool(d["hold_s"]) for d in in_window))
        # counted only where the collected counters bear them out
        borne_out = not any(w in c for c in complaints for w in (
            "spans_received", "outside_slack", "calls_total"))
        return {"attempted": len(in_window) + failed, "failed": failed,
                "complaints": complaints,
                "latencies_ms": [(d["t1"] - d["t0"]) * 1e3
                                 for d in in_window],
                "units": sum(d["n"] for d in in_window) if borne_out else 0}

    def check_served(self, m: dict, discarded: dict, complaints: list) -> None:
        keep = metric_sum(m, "tempo_sched_ingest_keep_fraction")
        if keep != 1.0:
            complaints.append(f"overload sampling armed: keep fraction {keep}")
        for name in ("tempo_sched_dispatch_errors_total",
                     "tempo_distributor_push_failures_total",
                     "tempo_remote_write_failed_sends_total"):
            if metric_sum(m, name):
                complaints.append(f"{name} = {metric_sum(m, name)}")
        for reason, v in discarded.items():
            if reason != "outside_slack":
                complaints.append(f"{v:g} spans discarded as {reason}")
        if not self.ctx.sink.bodies or not max(self.ctx.sink.bodies):
            complaints.append("no remote-write body reached the sink")
        present = {name for name, _ in m}
        for family in (DROPPED, STORE, EDGES):
            if family not in present:
                complaints.append(f"{family} is not on /metrics")
        if metric_sum(m, DROPPED):
            complaints.append(f"the service-graph store dropped "
                              f"{metric_sum(m, DROPPED):g} spans")
        if metric_sum(m, STORE):
            complaints.append(f"{metric_sum(m, STORE):g} halves still in the "
                              "service-graph store after the flush")

    def oracle(self, tenant: str, cols: dict, got: dict, m: dict,
               complaints: list) -> tuple:
        """One tenant against the reference: received and slack counts,
        every service-graph series exactly (sums to `latency_sum_rtol`),
        every span-metrics series' count exactly and the latency sum to
        `latency_sum_rtol`, and the sketch probes."""
        ctx, tr = self.ctx, self.ctx.traffic
        acked_n = int(cols["acked"].sum())
        late = acked_n - int(cols["in_slack"].sum())
        received = metric_sum(m, RECEIVED, tenant=tenant)
        if received != acked_n:
            complaints.append(f"{tenant}: spans_received_total {received:g} "
                              f"!= {acked_n} acknowledged")
        slack = int(ctx.app.generator.instances[tenant].spans_filtered_slack)
        if slack != late:
            complaints.append(f"{tenant}: outside_slack {slack} != {late} "
                              "spans of held pushes acknowledged")
        templates, services = self.h.templates, self.h.services
        want = reference_hotrod.service_graph(
            cols, templates, services, self.sg["sg_peer_attributes"],
            self.sg["sg_histogram_buckets"])
        sg_worst = self.check_graph(tenant, want, got, complaints)
        durs = reference_hotrod.span_metrics(cols, templates, services)
        sm = {}
        for (name, ls), v in got.items():
            if name in (SM_CALLS, SM_COUNT, SM_SUM):
                d = dict(ls)
                key = (d.get("service"), d.get("span_name"),
                       d.get("span_kind"), d.get("status_code"))
                sm.setdefault(key, {})[name] = v
        for key in sorted(set(sm) | set(durs), key=str):
            n = len(durs.get(key, ()))
            for name in (SM_CALLS, SM_COUNT):
                if sm.get(key, {}).get(name, 0.0) != n:
                    complaints.append(f"{tenant}: {name}{key} = "
                                      f"{sm.get(key, {}).get(name)}, {n} "
                                      "in-slack spans acknowledged")
        want_sum = sum(float(v.astype(np.float64).sum())
                       for v in durs.values())
        lat_sum = sum(s.get(SM_SUM, 0.0) for s in sm.values())
        rel = abs(lat_sum - want_sum) / want_sum
        if rel > tr["latency_sum_rtol"]:
            complaints.append(f"{tenant}: latency_sum {lat_sum} vs f64 "
                              f"oracle {want_sum} (rel {rel:.3g})")
        sketch = self.check_sketch(tenant, durs, complaints)
        e = reference_hotrod.edges_of(cols, templates, services,
                                      self.sg["sg_peer_attributes"])
        gap = self.pair_gap_s(cols, *e["pairs"])
        if gap >= self.sg["sg_wait_s"]:
            complaints.append(f"{tenant}: the two halves of a call reached "
                              f"the server up to {gap:.3f} s apart, not "
                              f"inside sg_wait_s {self.sg['sg_wait_s']}: "
                              "the reference pairs what the store may not")
        return ({"acknowledged": acked_n, "held": late,
                 "spans_received": received, "slack_filtered": slack,
                 "edge_series": len(want), "edges_completed": e["completed"],
                 "edges_virtual": e["virtual"],
                 "halves_unpaired": e["lone_halves"],
                 "pair_gap_s": gap,
                 "graph_sum_rel_err": sg_worst, "latency_sum_rel_err": rel,
                 "sketch_worst_rel_err_vs_rank": sketch}, late)

    def pair_gap_s(self, cols: dict, c_rows, s_rows) -> float:
        """The most time that can have passed between the server taking
        the first half of a call and the second: from the earlier push's
        send to the later push's answer."""
        if not len(c_rows):
            return 0.0
        t0 = np.asarray([d["t0"] for d in self.sent])
        t1 = np.asarray([d["t1"] for d in self.sent])
        pc, ps = cols["push"][c_rows], cols["push"][s_rows]
        # two halves of one push (the canaries) pair inside that push
        return float(np.where(pc == ps, 0.0, np.maximum(t1[pc], t1[ps])
                              - np.minimum(t0[pc], t0[ps])).max())

    def check_graph(self, tenant: str, want: dict, got: dict,
                    complaints: list) -> float:
        edges = self.sg["sg_histogram_buckets"]
        series: dict = {}
        for (name, ls), v in got.items():
            if not name.startswith(SG):
                continue
            d = dict(ls)
            key = (d.get("client"), d.get("server"),
                   d.get("connection_type", ""))
            series.setdefault(key, {})[(name, d.get("le"))] = v
        if set(series) != set(want):
            complaints.append(f"{tenant}: service-graph series "
                              f"{sorted(set(series) ^ set(want))[:6]} "
                              "differ from the reference's")
        les = [float(e) for e in edges] + [float("inf")]
        worst, sums = 0.0, {}
        for key in sorted(set(series) & set(want)):
            s, w = series[key], want[key]
            have = {"total": s.get((SG + "_total", None)),
                    "failed": s.get((SG + "_failed_total", None), 0.0)}
            bad = [k for k in have if have[k] != w[k]]
            for side in ("client", "server"):
                fam = f"{SG}_{side}_seconds"
                buckets = {float(le): v for (n, le), v in s.items()
                           if n == fam + "_bucket"}
                cum = np.cumsum(w[side + "_buckets"]).tolist()
                got_cum = [buckets.get(le) for le in les]
                if got_cum != cum or len(buckets) != len(les):
                    bad.append(f"{side} buckets {got_cum} != {cum}")
                if s.get((fam + "_count", None)) != w["total"]:
                    bad.append(f"{side} count")
                a, b = sums.get(side, (0.0, 0.0))
                sums[side] = (a + s.get((fam + "_sum", None), 0.0),
                              b + w[side + "_sum"])
            if bad:
                complaints.append(f"{tenant}: service graph {key}: {bad[:3]}")
        for side, (a, b) in sums.items():
            rel = abs(a - b) / b if b else abs(a)
            worst = max(worst, rel)
            if rel > self.ctx.traffic["latency_sum_rtol"]:
                complaints.append(f"{tenant}: service-graph {side} seconds "
                                  f"sum {a} vs f64 reference {b} "
                                  f"(rel {rel:.3g})")
        return worst

    def check_sketch(self, tenant: str, durs: dict, complaints: list) -> float:
        """p50 and p99 of the busiest series: within `sketch_rel_err` of a
        value at a neighbouring rank (as `otlp_push.check_sketch`)."""
        tr = self.ctx.traffic
        worst, err = 0.0, tr["sketch_rel_err"]
        busiest = sorted(durs, key=lambda k: -len(durs[k]))[
            :tr["sketch_probes"]]
        for q in (0.5, 0.99):
            got = {}
            for e in get_json(self.ctx.port, "/internal/generator/quantile",
                              tenant, q=q)["quantiles"]:
                d = dict(e["labels"])
                got[(d.get("service"), d.get("span_name"), d.get("span_kind"),
                     d.get("status_code"))] = e["value"]
            for key in busiest:
                vals = np.sort(durs[key].astype(np.float64))
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

    def readback(self, tenant: str, cols: dict, complaints: list) -> None:
        """Seeded traces of the tenant, whole by id: `readback_traces` of
        in-slack batches and `readback_split_traces` that a held push
        split over an ingester cut."""
        ctx, tr = self.ctx, self.ctx.traffic
        rng = np.random.default_rng([ctx.seed, 11, self.tenants.index(tenant)])
        held = np.flatnonzero(cols["acked"] & ~cols["in_slack"])
        clear = np.flatnonzero(cols["in_slack"])
        picks = []
        for rows, n, what in ((clear, tr["readback_traces"], "in-slack"),
                              (held, tr["readback_split_traces"], "held")):
            if len(rows) < n:
                complaints.append(f"{tenant}: {len(rows)} {what} spans to "
                                  f"read back, {n} traces wanted")
                continue
            picks += rng.choice(rows, size=n, replace=False).tolist()
        tids = cols["trace_id"].view("V16").ravel()
        for r in picks:
            rows = np.flatnonzero((tids == tids[r]) & cols["acked"])
            hexid = bytes(cols["trace_id"][r]).hex()
            want = {int(cols["span_id"][i]).to_bytes(8, "little").hex(): (
                int(cols["start_ns"][i]), int(cols["end_ns"][i]),
                self.h.templates[int(cols["tmpl"][i])]["name"]) for i in rows}
            status, body = http_call(ctx.port, "GET", "/api/traces/" + hexid,
                                     tenant)
            got = {} if status != 200 else {
                s["span_id"]: (int(s["start_unix_nano"]),
                               int(s["end_unix_nano"]), s["name"])
                for s in json.loads(body)["spans"]}
            if got != want:
                complaints.append(f"{tenant}: trace {hexid} read back as "
                                  f"{len(got)} spans ({status}), "
                                  f"acknowledged {len(want)}")
        say(traces_read_back=len(picks), tenant=tenant, at_s=ctx.clock())
