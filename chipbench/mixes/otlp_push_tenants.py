"""Traffic kind `otlp_push_tenants`: `otlp_push` from many tenants, each
with a schema of its own, on the paged layout.

The same closed-loop writers and the same numpy oracle. What differs:

- every tenant has its own schema, by the configuration's `schema_law`
  (services and names fall with the tenant's rank), so the load comes
  from `loadgen_tenants.py`, which picks the schema by tenant;
- a push's tenant is drawn from the seed with probability 1/rank^s; the
  drawn list is the child's `jobs`, in the window too;
- set-up sends the canaries to ONE tenant (the arenas and the compiled
  steps are every tenant's), then walks every tenant's series table to
  its end with the fewest pushes, a round of one push a tenant at a time;
- the window starts right after a collection ROUND that began with all
  of that in place;
- the judge holds EVERY tenant to the counters of one `/metrics` scrape
  and a seeded sample of them to `otlp_push`'s whole oracle, and adds
  that the run was served from the page pool alone.

Every family read here is exported by the parent of the PR that brought
the cell too, so both sides are held to the same rules.
"""

from __future__ import annotations

import os
import pickle
import subprocess
import sys
import time

import numpy as np

from chipbench import spans
from chipbench.loadgen import send_push
from chipbench.lib import (HERE, BenchFailure, Sink, boot, metric_sum, say,
                           scrape)
from chipbench.mixes import otlp_push
from chipbench.mixes.otlp_push import COLLECT, acked

SERIES = "tempo_metrics_generator_registry_active_series"
RECEIVED = "tempo_metrics_generator_spans_received_total"
STATE_BYTES = "tempo_registry_state_bytes"
N_COMBO = len(spans.KINDS) * 3           # series a (service, name)


def schema_of(law: dict, rank: int) -> dict:
    """The schema of the tenant of rank `rank` (1 = the largest)."""
    return {"services": law["head_services"] if rank <= law["head_ranks"]
            else law["tail_services"],
            "names": max(1, law["names_numerator"] // rank),
            "vus": law["vus"], "end_jitter_ns": law["end_jitter_ns"]}


def table_size(schema: dict) -> int:
    """A tenant's full span-metrics table: every (service, name, kind,
    status), and the call series: every service calls (CLIENT) and every
    service but the first is called (SERVER)."""
    return schema["services"] * schema["names"] * N_COMBO \
        + 2 * schema["services"] - 1


def push_series(schema: dict, idx: int, groups: int, per: int) -> tuple:
    """What push `idx` of `groups` x `per` spans names, as two sets: its
    span-metrics series and its service-graph edges. What
    `spans.draw_push` draws, which depends on no seed."""
    S, names = schema["services"], schema["names"]
    n_combo = names * N_COMBO
    svc = (idx * groups + np.arange(groups)) % S
    combo = (idx * groups // S * per + np.arange(per)[None, :]
             + svc[:, None] * 7) % n_combo
    code = svc[:, None] * (n_combo + 2) + combo
    g = np.arange(groups)
    srv_g = np.minimum(g + 1, groups - 1)
    srv_p = np.where(g < groups - 1, per - 2, per - 3)
    code[g, per - 1] = svc * (n_combo + 2) + n_combo            # CLIENT
    code[srv_g, srv_p] = svc[srv_g] * (n_combo + 2) + n_combo + 1  # SERVER
    return set(code.ravel().tolist()), set(zip(svc.tolist(),
                                               svc[srv_g].tolist()))


def fill_plan(schema: dict, first: list, push: tuple) -> list:
    """The fewest pushes that walk a tenant's tables to their end: the
    shapes in `first` (already sent, or to be sent first), then `push`
    until every span-metrics series was named and every edge that pushes
    of that shape draw was seen. [(groups, per)] from push index 0."""
    want = table_size(schema)
    want_edges = set().union(*(push_series(schema, k, *push)[1] for k in
                               range(-(-schema["services"] // push[0]))))
    plan, seen, edges = [], set(), set()
    while len(seen) < want or not want_edges <= edges:
        if len(plan) > 400:
            raise BenchFailure(f"no walk fills {schema}: {len(seen)}/{want}")
        shape = first[len(plan)] if len(plan) < len(first) else push
        series, drawn = push_series(schema, len(plan), *shape)
        seen, edges = seen | series, edges | drawn
        plan.append(shape)
    return plan


def zipf_shares(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def draw_jobs(seed: int, count: int, tenants: list, s: float,
              n_spans: int) -> list:
    """The window's `count` (tenant, spans) jobs: each push's tenant drawn
    independently with probability 1/rank^s. A pure function of its
    arguments."""
    rng = np.random.default_rng([seed, 17])
    idx = rng.choice(len(tenants), size=count,
                     p=zipf_shares(len(tenants), s))
    return [(tenants[i], n_spans) for i in idx.tolist()]


def sampled_tenants(seed: int, tenants: list, heads: int, drawn: int) -> list:
    """The tenants held to the whole oracle: the `heads` largest and
    `drawn` of the others, from the seed."""
    heads = min(heads, len(tenants))
    rng = np.random.default_rng([seed, 19])
    rest = heads + rng.choice(len(tenants) - heads, replace=False,
                              size=min(drawn, len(tenants) - heads))
    return [tenants[i] for i in list(range(heads)) + sorted(rest.tolist())]


def by_label(m: dict, family: str, label: str, **fixed) -> dict:
    """{value of `label`: sample} over one family's rows."""
    want = set(fixed.items())
    out: dict = {}
    for (name, ls), v in m.items():
        if name == family and want <= set(ls):
            key = dict(ls).get(label, "")
            out[key] = out.get(key, 0.0) + v
    return out


def run_child(ctx, spec: dict, go=None) -> dict:
    """`run.py::run_child` with this mix's load generator: the child
    needs a schema a tenant, which `loadgen.send_push` cannot be told."""
    spec = dict(spec, port=ctx.port, clients=ctx.traffic["clients"],
                timeout=ctx.traffic.get("timeout_s", 300.0))
    ctx.n_child += 1
    spec_path = os.path.join(ctx.workdir, f"child{ctx.n_child}.spec")
    out_path = os.path.join(ctx.workdir, f"child{ctx.n_child}.out")
    with open(spec_path, "wb") as f:
        pickle.dump(spec, f)
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen_tenants.py"), spec_path,
         out_path], stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        if spec.get("seconds") is not None:
            if child.stdout.readline().strip() != "ready":
                raise BenchFailure("the load generator did not come up")
            go()
            child.stdin.write("go\n")
            child.stdin.flush()
        rc = child.wait(timeout=(spec.get("seconds") or 0) + 1200)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if rc != 0:
        raise BenchFailure(f"the load generator exited {rc}")
    with open(out_path, "rb") as f:       # written by our own child only
        return pickle.load(f)


class Mix(otlp_push.Mix):

    # -- set-up ------------------------------------------------------------

    def setup(self) -> None:
        ctx, tr = self.ctx, self.ctx.traffic
        ctx.run_child = lambda spec, go=None: run_child(ctx, spec, go)
        self.tenants = ctx.config["tenants"]
        law = ctx.config["schema_law"]
        self.schemas = {t: schema_of(law, i + 1)
                        for i, t in enumerate(self.tenants)}
        self.shapes = {g * p: (g, p, t) for g, p, t in tr["warm_shapes"]}
        self.n_push = tr["push"][0] * tr["push"][1]
        ctx.sink = Sink()
        ctx.app, ctx.srv, ctx.port = boot(ctx.config, ctx.workdir,
                                          ctx.sink.url)
        say(phase="booted", at_s=ctx.clock())
        self.next_idx = {t: 0 for t in self.tenants}
        self.built = {n: spans.PushShape(*gpt)
                      for n, gpt in self.shapes.items()}
        first = self.tenants[0]   # the arenas and the steps are everyone's
        for n in self.shapes:
            idx = self.next_idx[first]
            self.next_idx[first] = idx + 1
            self.sent.append(send_push(
                ctx.port, ctx.seed, self.tenants, first, idx, n,
                self.built[n], self.schemas[first], 600.0))
            self.drain("a canary push")
        say(phase="canaries", at_s=ctx.clock())
        jobs = self.prefill_jobs()
        res = ctx.run_child(dict(self.spec(jobs), seconds=None))
        self.note(res)
        self.drain("the prefill")
        self.t_ready = time.monotonic()
        say(phase="prefilled", at_s=ctx.clock(), pushes=len(self.sent),
            prefill_pushes=len(jobs))

    def prefill_jobs(self) -> list:
        """Every tenant to its full table, a round of one push a tenant at
        a time: two pushes of one tenant that are in flight together are
        consecutive ones, which walk different services, so no two meet
        the same new series (`registry/series.py::_lookup_native` would
        lose rows)."""
        tr = self.ctx.traffic
        push = tuple(tr["push"][:2])
        canaries = [gpt[:2] for gpt in self.shapes.values()]
        head = tuple(tr["head_first_push"][:2])
        todo = {}
        for i, t in enumerate(self.tenants):
            schema = self.schemas[t]
            first = canaries if i == 0 else \
                [head] if schema["services"] > push[0] else []
            todo[t] = fill_plan(schema, first, push)[self.next_idx[t]:]
        jobs = []
        for k in range(max(map(len, todo.values()))):
            jobs += [(t, g * p) for t, plan in todo.items()
                     for g, p in plan[k:k + 1]]
        return jobs

    def spec(self, jobs: list) -> dict:
        return {"kind": "push", "seed": self.ctx.seed,
                "tenants": list(self.tenants), "schemas": self.schemas,
                "shapes": self.shapes, "next_idx": dict(self.next_idx),
                "jobs": jobs}

    def child_spec(self) -> dict:
        """The window's jobs are the seeded Zipf sequence, longer than any
        window consumes (the judge checks that it did not run out)."""
        tr = self.ctx.traffic
        return self.spec(draw_jobs(self.ctx.seed, tr["window_jobs"],
                                   self.tenants, tr["zipf_s"], self.n_push))

    def wait_start(self) -> None:
        """Return two thirds of a collection interval after the end of a
        collection round that began after set-up was complete and walked
        every tenant: that round compiled every paged gather a collect of
        these tenants takes, and every window starts at the same phase of
        the collection loop.
        `otlp_push` knows a round by the collect count being a multiple of
        the tenants, which holds only where every round since boot walked
        all of them; here they appear over several. The collector sleeps
        a collection interval between rounds, so a round is the growth of
        the count between two pauses of a third of it, and it was whole if
        it grew by every tenant. `/metrics` carries per-tenant families
        for every tenant and is parsed on the server's interpreter: it is
        scraped no more often than `scrape_every_s`."""
        ctx, n = self.ctx, len(self.tenants)
        every = ctx.traffic["scrape_every_s"]
        quiet = ctx.app.cfg.generator.registry.collection_interval_s / 3
        count0 = metric_sum(scrape(ctx.port), COLLECT + "_count")
        burst = None            # (began after, count before it, last growth)
        now = time.monotonic()
        deadline = now + 300
        while now < deadline:
            time.sleep(every)
            m, before_scrape = scrape(ctx.port), now
            count, now = metric_sum(m, COLLECT + "_count"), time.monotonic()
            if count != count0:
                burst = (burst[0] if burst else before_scrape,
                         burst[1] if burst else count0, now)
                count0 = count
                continue
            if burst is None or now - burst[2] < quiet:
                continue
            (began, before, last), burst = burst, None
            if count - before == n and began >= self.t_ready:
                self.series_at_go = metric_sum(m, SERIES)
                say(phase="round_ended", at_s=ctx.clock(),
                    round_s=round(last - began, 3),
                    active_series=self.series_at_go)
                # the collector pauses a whole interval after a round:
                # go two thirds into the pause, so that the window's one
                # round, twice its interval long under load, has ended
                # by the closing scrape that reads it
                time.sleep(max(last + 2 * quiet - time.monotonic(), 0))
                return
            # the round in flight when set-up ended, as a rule; one run of
            # 26 on the chip waited two rounds more, cause not seen
            say(phase="round_passed_over", at_s=ctx.clock(),
                collects=count - before, of=n,
                began_after_ready_s=round(began - self.t_ready, 3),
                burst_s=round(last - began, 3))
        raise BenchFailure("no whole collection round ended within 300 s")

    # -- judging -----------------------------------------------------------

    def quiescent_collect(self) -> dict:
        """`otlp_push`'s rule over the sampled tenants only."""
        every, self.tenants = self.tenants, self.sampled
        try:
            return super().quiescent_collect()
        finally:
            self.tenants = every

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
        if len(res["done"]) >= tr["window_jobs"]:
            complaints.append(f"the window's {tr['window_jobs']} jobs ran out")
        self.sampled = sampled_tenants(ctx.seed, self.tenants,
                                       tr["sampled_head_ranks"],
                                       tr["sampled_drawn_tenants"])
        got = self.quiescent_collect()
        m = scrape(ctx.port)
        discarded = {dict(ls).get("reason", "?"): v for (name, ls), v
                     in m.items() if name == "tempo_discarded_spans_total"
                     and v}
        report = {t: self.oracle(t, got[t], discarded, complaints)
                  for t in self.sampled}
        lost = sum(discarded.values()) \
            + sum(r["slack_filtered"] for r in report.values())
        pages = self.check_served(m, discarded, complaints)
        say(phase="oracle_done", at_s=ctx.clock(), tenants=len(report))
        # `otlp_push.readback` draws under the one `self.schema`: it is
        # handed the largest tenant's pushes and that tenant's schema
        self.schema = self.schemas[self.sampled[0]]
        self.readback(dict(res, done=[d for d in res["done"] if d.get(
            "tenant") == self.sampled[0]]), complaints)
        if lost:
            # spans the program discarded under a reason: their pushes
            # count as failed, as many as the spans fill
            failed += -(-int(lost) // self.n_push)
        say(oracle=report, discarded=discarded, pages=pages,
            remote_write_requests=len(ctx.sink.bodies))
        in_window = [d for d in res["done"] if acked(d)
                     and t_go <= d["t1"] <= t_go + seconds]
        return {"attempted": len(res["done"]), "failed": failed,
                "complaints": complaints,
                "latencies_ms": [(d["t1"] - d["t0"]) * 1e3 for d in in_window],
                # counted only where the collected counters bear them out
                "units": 0 if any("calls_total" in c or "spans_received" in c
                                  for c in complaints)
                else sum(d["n"] for d in in_window)}

    def check_served(self, m: dict, discarded: dict, complaints: list) -> dict:
        """ALL tenants, from one scrape: the generator received what was
        acknowledged to each; nothing sampled, failed or refused; every
        tenant's state on the paged layout and none on the dense one; no
        new series since the window's start; the sink received bodies."""
        keep = metric_sum(m, "tempo_sched_ingest_keep_fraction")
        if keep != 1.0:
            complaints.append(f"overload sampling armed: keep fraction {keep}")
        for name in ("tempo_sched_dispatch_errors_total",
                     "tempo_distributor_push_failures_total",
                     "tempo_remote_write_failed_sends_total",
                     "tempo_pages_alloc_failures_total"):
            if metric_sum(m, name):
                complaints.append(f"{name} = {metric_sum(m, name)}")
        if not self.ctx.sink.bodies or not max(self.ctx.sink.bodies):
            complaints.append("no remote-write body reached the sink")
        n_acked: dict = {}
        for d in self.sent:
            if acked(d):
                n_acked[d["tenant"]] = n_acked.get(d["tenant"], 0) + d["n"]
        # the distributor's discards carry no tenant label: with none
        # (every run so far) the identity is exact
        gone = sum(v for r, v in discarded.items() if r != "outside_slack")
        received = by_label(m, RECEIVED, "tenant")
        wrong = [t for t in self.tenants if not n_acked.get(t, 0) - gone
                 <= received.get(t, 0.0) <= n_acked.get(t, 0)]
        for t in wrong[:8]:
            complaints.append(f"{t}: spans_received_total "
                              f"{received.get(t, 0.0):g} != "
                              f"{n_acked.get(t, 0)} acknowledged")
        if len(wrong) > 8:
            complaints.append(f"spans_received_total: {len(wrong)} tenants "
                              "did not receive what was acknowledged to them")
        paged = by_label(m, STATE_BYTES, "tenant", layout="paged")
        dense = by_label(m, STATE_BYTES, "tenant", layout="dense")
        unbacked = [t for t in self.tenants if not paged.get(t)]
        if dense or unbacked or not metric_sum(m, "tempo_pages_total"):
            complaints.append(f"{len(dense)} tenants on the dense layout, "
                              f"{len(unbacked)} hold no page "
                              f"({(sorted(dense) + unbacked)[:4]})")
        series = metric_sum(m, SERIES)
        if series != self.series_at_go:
            complaints.append(f"{series - self.series_at_go:g} series were "
                              f"new inside the window ({series:g} active)")
        return {"tenants_paged": len(paged) - len(unbacked),
                "tenants_received": len(received), "tenants_wrong": len(wrong),
                "active_series": series,
                "pages_free": metric_sum(m, "tempo_pages_free"),
                "pages_total": metric_sum(m, "tempo_pages_total")}

    def oracle(self, tenant: str, got: dict, discarded: dict,
               complaints: list) -> dict:
        """`otlp_push.judge`'s body for one tenant, under the tenant's own
        schema: float64 numpy over the re-drawn columns of everything
        acknowledged to it since boot. The series count is held EQUAL to
        the schema's table."""
        ctx, tr = self.ctx, self.ctx.traffic
        ti, schema = self.tenants.index(tenant), self.schemas[tenant]
        cols = [spans.draw_push(ctx.seed, ti, d["idx"], self.built[d["n"]],
                                schema, d["now_ns"])
                for d in self.sent if acked(d) and d["tenant"] == tenant]
        col = {k: np.concatenate([c[k] for c in cols]) for k in (
            "svc", "name", "kind", "status", "start_ns", "end_ns")}
        n_acked = len(col["svc"])
        slack = int(ctx.app.generator.instances[tenant].spans_filtered_slack)
        gone = sum(discarded.values())
        lost = slack + gone
        want = n_acked - slack
        calls = got.get("traces_spanmetrics_calls_total", 0.0)
        count = got.get("traces_spanmetrics_latency_count", 0.0)
        for what, v in (("calls_total", calls), ("latency_count", count)):
            if not want - gone <= v <= want:
                complaints.append(
                    f"{tenant}: {what} {v} != {n_acked} acknowledged - "
                    f"{slack} outside the slack window - discarded "
                    f"{discarded}")
        dur_s = ((col["end_ns"] - col["start_ns"]) / 1e9).astype(np.float32)
        want_sum = float(dur_s.astype(np.float64).sum())
        lat_sum = got.get("traces_spanmetrics_latency_sum", 0.0)
        rel = abs(lat_sum - want_sum) / want_sum
        if rel > tr["latency_sum_rtol"] and not lost:
            complaints.append(f"{tenant}: latency_sum {lat_sum} vs f64 "
                              f"oracle {want_sum} (rel {rel:.3g})")
        pairs = sum(c["pairs"] for c in cols)
        edges = got.get("traces_service_graph_request_total", 0.0)
        if edges != pairs and not lost:
            complaints.append(f"{tenant}: service graph counted {edges} "
                              f"edges, {pairs} acknowledged")
        if got["series"] != table_size(schema):
            complaints.append(f"{tenant}: {got['series']} active series, its "
                              f"schema's table has {table_size(schema)}")
        worst = None
        if ti < tr["sampled_head_ranks"]:
            worst = self.check_sketch(tenant, col, dur_s, complaints)
        return {"acknowledged": n_acked, "calls_total": calls,
                "slack_filtered": slack, "series_active": got["series"],
                "edges": pairs, "latency_sum_rel_err": rel,
                "sketch_worst_rel_err_vs_rank": worst}
