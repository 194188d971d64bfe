"""Traffic kind `traceql_read`: closed-loop reads of backend blocks.

The configuration's data set (blocks x spans, written straight to the
backend from the seed) is made here; the traffic file's templates are
expanded into a plan of requests by one general generator: a fixed plan
seed draws the SET of requests (template, window, step, offsets, values),
and `--seed` only permutes their order, so every seed sends the same work.
"""

from __future__ import annotations

import time
import urllib.parse

import numpy as np

from chipbench import costs, reference, spans
from chipbench.lib import BenchFailure, Sink, boot, http_call, say


def _block_table(c: dict, spec: dict):
    """Block columns -> the arrow table `block/writer.py` stores."""
    import pyarrow as pa

    from tempo_tpu.block import schema as bs

    n, T = len(c["span_id"]), spec["trace_len"]
    pos = c["pos"]

    def strings(codes, fmt, count):
        return pa.DictionaryArray.from_arrays(
            pa.array(codes.astype(np.int32)),
            pa.array([fmt % i for i in range(count)])).dictionary_decode()

    def fixed(a, width):
        return pa.FixedSizeBinaryArray.from_buffers(
            pa.binary(width), n, [None, pa.py_buffer(
                np.ascontiguousarray(a).tobytes())])

    def lists(values, typ):
        if values is None:
            return pa.ListArray.from_arrays(
                pa.array(np.zeros(n + 1, np.int32)), pa.array([], typ))
        return pa.ListArray.from_arrays(
            pa.array(np.arange(n + 1, dtype=np.int32)), values)

    arrays = {
        "trace_id": fixed(c["trace_id"], 16),
        "trace_idx": pa.array((np.arange(n) // T).astype(np.int32)),
        "span_id": fixed(c["span_id"].astype("<i8").view(np.uint8), 8),
        "parent_span_id": fixed(c["parent"].astype("<i8").view(np.uint8), 8),
        "parent_row": pa.array(np.where(pos == 0, -1, 0).astype(np.int32)),
        # a root and T-1 children: root (1, 2T), child k (2k, 2k+1)
        "nested_left": pa.array(np.where(pos == 0, 1, 2 * pos).astype(np.int32)),
        "nested_right": pa.array(
            np.where(pos == 0, 2 * T, 2 * pos + 1).astype(np.int32)),
        "is_root": pa.array(pos == 0),
        "name": strings(c["name"], "op-%04d", spec["names"]),
        "service": strings(c["svc"], "svc-%04d", spec["services"]),
        "kind": pa.array(c["kind"]), "status_code": pa.array(c["status"]),
        "status_message": strings(np.zeros(n, np.int32), "%.0s", 1),
        "start_unix_nano": pa.array(c["start_ns"]),
        "duration_ns": pa.array(c["dur_ns"]),
        "sattr_str_keys": lists(strings(np.zeros(n, np.int32), "k6.vu%.0s", 1),
                                None),
        "sattr_str_vals": lists(strings(c["vu"], "vu-%02d", spec["vus"]), None),
    }
    schema = bs.block_schema(())
    for f in schema:
        if f.name not in arrays:
            arrays[f.name] = lists(None, f.type.value_type)
    return pa.Table.from_arrays([arrays[f.name] for f in schema],
                                schema=schema)


def make_dataset(ctx) -> reference.BlockData:
    """Write the configuration's blocks to the backend; keep the columns."""
    from tempo_tpu.block.writer import write_block_from_table

    spec, tenant = ctx.config["dataset"], ctx.config["tenants"][0]
    span_s = spec["blocks"] * spec["block_seconds"]
    # the data ends `age_s` before now, on a whole minute: behind the
    # frontend's backend cutoff and the ingesters' window, so every read
    # goes to these blocks
    t0_s = (int(time.time()) - spec["age_s"] - span_s) // 60 * 60
    cols, sizes = [], []
    for b in range(spec["blocks"]):
        c = spans.draw_block(ctx.seed, b, spec,
                             (t0_s + b * spec["block_seconds"]) * 10**9)
        tids = [bytes(t) for t in c["trace_id"][::spec["trace_len"]]]
        meta = write_block_from_table(
            ctx.app.db.w, tenant, _block_table(c, spec), tids,
            row_group_rows=ctx.app.db.cfg.row_group_rows,
            replication_factor=1)
        ctx.app.db.blocklist.update(tenant, add=[meta])
        sizes.append(meta.size_bytes)
        cols.append(c)
    keys = ("trace_id", "span_id", "svc", "name", "status", "vu", "start_ns",
            "dur_ns")
    allc = {k: np.concatenate([c[k] for c in cols]) for k in keys}
    allc["trace_key"] = np.arange(len(allc["svc"])) // spec["trace_len"]
    say(dataset_blocks=spec["blocks"], spans=len(allc["svc"]),
        block_bytes=sizes, data_start_s=t0_s, data_end_s=t0_s + span_s)
    return reference.BlockData(allc, spec, t0_s)


def plan_requests(traffic: dict, spec: dict, t0_s: int, seed: int) -> list:
    """The traffic file's templates -> a list of requests (dicts with a
    `path`). The SET comes from `plan_seed`, the order from `seed`."""
    rng = np.random.default_rng(traffic["plan_seed"])
    tpl = traffic["templates"]
    w = np.asarray([t["weight"] for t in tpl], float)
    data_end = t0_s + spec["blocks"] * spec["block_seconds"]
    out, seen = [], set()
    while len(out) < traffic["planned"]:
        t = tpl[int(rng.choice(len(tpl), p=w / w.sum()))]
        r = {"template": t["name"], "check": t["check"]}
        for k, v in t.get("const", {}).items():
            r[k] = v
        for k, choices in t.get("choose", {}).items():
            r[k] = choices[int(rng.integers(len(choices)))]
        for k, (lo, hi) in t.get("draw", {}).items():
            r[k] = int(rng.integers(lo, hi))
        key = tuple(sorted((k, str(v)) for k, v in r.items()))
        if key in seen:                 # every request of a run is distinct
            continue
        seen.add(key)
        out.append(r)
    order = np.random.default_rng([seed, 99]).permutation(len(out))
    return [finish_request(out[i], spec, data_end, tpl) for i in order]


def finish_request(r: dict, spec: dict, data_end: int, tpl: list) -> dict:
    t = next(t for t in tpl if t["name"] == r["template"])
    if "window_s" in r:
        r["end"] = data_end - r.get("end_offset_s", 0)
        r["start"] = r["end"] - r["window_s"]
    if "trace" in r:                    # a position in the data, not an id
        r["block"] = r["trace"] % spec["blocks"]
        r["trace_no"] = r["trace"] // spec["blocks"] * 37 % (
            spec["spans_per_block"] // spec["trace_len"])
    r["tpl"] = t
    return r


def request_path(r: dict, data: reference.BlockData) -> str:
    t = r["tpl"]
    vals = dict(r)
    if "trace" in r:
        vals["trace_id"] = data.trace_spans(r["block"], r["trace_no"])[0]
    path = t["path"].format(**vals)
    params = {k: (v.format(**vals) if isinstance(v, str) else v)
              for k, v in t.get("params", {}).items()}
    for k in t.get("pass", ()):
        params[k] = r[k]
    return path + ("?" + urllib.parse.urlencode(params) if params else "")


class Mix:
    def __init__(self, ctx) -> None:
        self.ctx = ctx

    def setup(self) -> None:
        ctx = self.ctx
        ctx.sink = Sink()
        ctx.app, ctx.srv, ctx.port = boot(ctx.config, ctx.workdir,
                                          ctx.sink.url)
        say(phase="booted", at_s=ctx.clock())
        self.data = make_dataset(ctx)
        say(phase="dataset", at_s=ctx.clock())
        spec = ctx.config["dataset"]
        self.plan = plan_requests(ctx.traffic, spec, self.data.t0_s, ctx.seed)
        self.paths = [request_path(r, self.data) for r in self.plan]
        # warm-up: each shape of each template once, from the END of the
        # plan (requests the window will not reach)
        shape_keys = ("template", "window_s", "step")
        seen = set()
        for r, path in zip(reversed(self.plan), reversed(self.paths)):
            key = tuple(r.get(k) for k in shape_keys)
            if key in seen:
                continue
            seen.add(key)
            t0 = time.monotonic()
            status, body = http_call(ctx.port, "GET", path,
                                     ctx.config["tenants"][0])
            if status != 200:
                raise BenchFailure(f"warm-up {path} -> {status}: "
                                   f"{body[:200]!r}")
            say(warm_up=key, wall_s=round(time.monotonic() - t0, 3))

    def child_spec(self) -> dict:
        return {"kind": "get", "paths": self.paths,
                "tenant": self.ctx.config["tenants"][0]}

    def wait_start(self) -> None:
        pass

    def judge(self, res: dict, t_go: float, seconds: float) -> dict:
        ctx = self.ctx
        failed, complaints, lat, kinds = 0, [], [], {}
        answers = {}
        for d in res["done"]:
            r = self.plan[d["k"]] if "k" in d else None
            if r is None or d["status"] != 200:
                failed += 1
                complaints.append(f"{d.get('error') or d['status']}: "
                                  f"{self.paths[d['k']] if r else d}")
                continue
            why = getattr(reference, "check_" + r["check"])(
                self.data, r, d["body"])
            if why is not None:
                failed += 1
                complaints.append(f"{self.paths[d['k']]}: {why}")
                continue
            answers[d["k"]] = d["body"]
            if t_go <= d["t1"] <= t_go + seconds:
                lat.append((d["t1"] - d["t0"]) * 1e3)
                kinds[r["template"]] = kinds.get(r["template"], 0) + 1
        if len(res["done"]) >= len(self.plan):
            complaints.append("the plan ran out before the window did")
        complaints += self.second_reference(res, answers)
        say(reads_by_template=kinds)
        return {"attempted": len(res["done"]), "failed": failed,
                "complaints": complaints, "latencies_ms": lat,
                "units": len(lat)}

    def second_reference(self, res: dict, answers: dict) -> list:
        """A seeded sample of the served answers against the program's own
        host engine (`device_plane=False`) over the same blocks."""
        import json

        from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig
        from tempo_tpu.traceql.engine_metrics import (QueryRangeRequest,
                                                      SeriesCombiner,
                                                      metrics_kind)

        ctx, out = self.ctx, []
        tenant = ctx.config["tenants"][0]
        n = ctx.traffic.get("host_engine_sample", 0)
        ks = sorted(k for k in answers if "q" in self.plan[k]["tpl"].get(
            "params", {}) and self.plan[k].get("window_s", 10**9) <= 3600)
        if not ks or not n:
            return out
        pick = np.random.default_rng([ctx.seed, 5]).choice(
            ks, size=min(n, len(ks)), replace=False)
        db = TempoDB(ctx.app.db.r, ctx.app.db.w,
                     TempoDBConfig(device_plane=False))
        db.blocklist.update(tenant, add=ctx.app.db.blocklist.metas(tenant))
        t0 = time.monotonic()
        for k in pick:
            r = self.plan[int(k)]
            q = r["tpl"]["params"]["q"].format(**r)
            served = json.loads(answers[int(k)])
            if "step" in r:
                req = QueryRangeRequest(query=q, start_ns=r["start"] * 10**9,
                                        end_ns=r["end"] * 10**9,
                                        step_ns=r["step"] * 10**9)
                comb = SeriesCombiner(metrics_kind(q), req.n_steps)
                comb.add_all(db.query_range(tenant, req))
                host = {dict(ts.labels)["resource.service.name"]:
                        np.asarray(ts.samples, float)
                        for ts in comb.final(req)}
                got = {reference._label(s, "resource.service.name"):
                       np.asarray([float(p["value"] or 0.0)
                                   for p in s["samples"]])
                       for s in served["series"]}
                bad = [name for name in set(host) | set(got)
                       if name not in host or name not in got
                       or not np.allclose(host[name], got[name],
                                          rtol=reference.QUANTILE_RTOL, atol=0)]
            else:
                host = {md.trace_id.rjust(32, "0") for md in db.search(
                    tenant, q, limit=r["limit"], start_s=float(r["start"]),
                    end_s=float(r["end"]))}
                got = {t["traceID"].rjust(32, "0") for t in served["traces"]}
                bad = sorted(host ^ got) if len(host) < r["limit"] else []
            if bad:
                out.append(f"host engine disagrees with the served answer "
                           f"on {self.paths[int(k)]}: {bad[:3]}")
        say(host_engine_checked=len(pick), wall_s=round(
            time.monotonic() - t0, 3), disagreements=len(out))
        return out

    def least_bytes(self, res: dict, lo: float, hi: float) -> dict:
        """Bytes the plane grid needs for the metrics reads whose middle
        fell inside [lo, hi]: per block the query touches, the resident
        columns read once and the grid written once."""
        spec = self.ctx.config["dataset"]
        total = 0
        for d in res["done"]:
            r = self.plan[d["k"]] if "k" in d else None
            if r is None or "step" not in r \
                    or not lo <= (d["t0"] + d["t1"]) / 2 <= hi:
                continue
            first = max((r["start"] - self.data.t0_s) // spec["block_seconds"],
                        0)
            last = min((r["end"] - 1 - self.data.t0_s)
                       // spec["block_seconds"], spec["blocks"] - 1)
            total += max(last - first + 1, 0) * costs.plane_grid_bytes(
                rows=spec["spans_per_block"], groups=spec["services"],
                steps=r["window_s"] // r["step"],
                hist=r["check"] == "quantile")
        return {"plane_grid": total}
