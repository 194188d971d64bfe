"""The plain reference of `hotrod-otel-sdk`: what the service-graph and
span-metrics processors must collect from the spans that were sent.

Imports nothing of the program. It is handed the columns of every
acknowledged span (the judge draws them again from the seed) with a flag
`in_slack` (False for the spans of a held push, which the generator's
slack filter discards), and the configuration's span templates, peer
attributes and bucket edges. Upstream's rules (grafana/tempo
`modules/generator/processor/servicegraphs`, docs "Service graphs"):

- a CLIENT (or PRODUCER) span is one half of an edge keyed by (trace id,
  its span id); a SERVER (or CONSUMER) span is the other half, keyed by
  (trace id, its parent span id). Two halves of one key are an edge
  (client service, server service, ""), failed if either side errored;
- a half left alone when the store expires (the judge's flush pushes make
  that every half left) is a virtual-node edge or nothing: a ROOT server
  span (no parent) is called by client "user"; a client span that carries
  a peer attribute (the first of `peer_attributes` it has) calls a server
  named by that attribute's value; any other half names no edge. The
  missing side observes 0 s;
- latencies are observed in seconds into classic histograms whose value
  the program holds as float32: a bucket is the number of edges below the
  value (upper bounds inclusive), both in float32, as the program's step
  compares them.
"""

from __future__ import annotations

import numpy as np

KIND = {"internal": 1, "server": 2, "client": 3, "producer": 4,
        "consumer": 5}
KIND_STRS = {1: "SPAN_KIND_INTERNAL", 2: "SPAN_KIND_SERVER",
             3: "SPAN_KIND_CLIENT", 4: "SPAN_KIND_PRODUCER",
             5: "SPAN_KIND_CONSUMER"}
STATUS_STRS = {0: "STATUS_CODE_UNSET", 1: "STATUS_CODE_OK",
               2: "STATUS_CODE_ERROR"}
ERROR = 2


def _keys(trace_id: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """(trace id, 8-byte id) as one 24-byte key a row."""
    raw = np.concatenate([np.ascontiguousarray(trace_id, np.uint8),
                          np.ascontiguousarray(ids, "<i8").view(np.uint8)
                          .reshape(-1, 8)], axis=1)
    return np.ascontiguousarray(raw).view("V24").ravel()


def seconds_f32(dur_ns: np.ndarray) -> np.ndarray:
    return (dur_ns / 1e9).astype(np.float32)


def bucket_of(v32: np.ndarray, edges: list) -> np.ndarray:
    e32 = np.asarray(edges, np.float32)
    return (v32[:, None] > e32[None, :]).sum(axis=1)


def edges_of(cols: dict, templates: list, services: list,
             peer_attributes: list) -> dict:
    """Every edge the in-slack spans make, as columns: client, server and
    connection type names, failed, client and server duration (ns)."""
    t_kind = np.asarray([KIND[t["kind"]] for t in templates])
    t_svc = np.asarray([services.index(t["service"]) for t in templates])
    t_peer = []
    for t in templates:
        attrs = t.get("attributes", {})
        t_peer.append(next((attrs[a] for a in peer_attributes if a in attrs),
                           ""))
    kind, ok = t_kind[cols["tmpl"]], cols["in_slack"]
    cli = np.flatnonzero(ok & ((kind == 3) | (kind == 4)))
    srv = np.flatnonzero(ok & ((kind == 2) | (kind == 5)))
    ck = _keys(cols["trace_id"][cli], cols["span_id"][cli])
    sk = _keys(cols["trace_id"][srv], cols["parent"][srv])
    if len(np.unique(ck)) != len(ck) or len(np.unique(sk)) != len(sk):
        raise ValueError("two halves on one side of a key")
    _, ci, si = np.intersect1d(ck, sk, assume_unique=True,
                               return_indices=True)
    c_rows, s_rows = cli[ci], srv[si]
    lone_srv = np.setdiff1d(srv, s_rows)
    lone_cli = np.setdiff1d(cli, c_rows)
    dur = cols["end_ns"] - cols["start_ns"]
    err = cols["status"] == ERROR
    svc_name = np.asarray(services, object)[t_svc[cols["tmpl"]]]
    peer = np.asarray(t_peer, object)[cols["tmpl"]]
    roots = lone_srv[cols["parent"][lone_srv] == 0]
    peers = lone_cli[peer[lone_cli] != ""]
    zero = lambda a: np.zeros(len(a), np.int64)      # noqa: E731
    parts = [
        (svc_name[c_rows], svc_name[s_rows], "", err[c_rows] | err[s_rows],
         dur[c_rows], dur[s_rows]),
        (np.full(len(roots), "user", object), svc_name[roots],
         "virtual_node", err[roots], zero(roots), dur[roots]),
        (svc_name[peers], peer[peers], "virtual_node", err[peers],
         dur[peers], zero(peers)),
    ]
    out = {k: [] for k in ("client", "server", "conn", "failed", "cdur",
                           "sdur")}
    for client, server, conn, failed, cdur, sdur in parts:
        out["client"].append(np.asarray(client, object))
        out["server"].append(np.asarray(server, object))
        out["conn"].append(np.full(len(client), conn, object))
        out["failed"].append(np.asarray(failed, bool))
        out["cdur"].append(np.asarray(cdur, np.int64))
        out["sdur"].append(np.asarray(sdur, np.int64))
    out = {k: np.concatenate(v) for k, v in out.items()}
    out["pairs"] = (c_rows, s_rows)
    out["lone_halves"] = len(lone_srv) + len(lone_cli)
    out["completed"] = len(c_rows)
    out["virtual"] = len(roots) + len(peers)
    return out


def service_graph(cols: dict, templates: list, services: list,
                  peer_attributes: list, edges_s: list) -> dict:
    """{(client, server, connection_type): {total, failed, client_buckets,
    client_sum, server_buckets, server_sum}}, buckets per bucket (not
    cumulative), `len(edges_s) + 1` of them, sums float64 of the float32
    seconds."""
    e = edges_of(cols, templates, services, peer_attributes)
    label = np.asarray([f"{c}\x1f{s}\x1f{n}" for c, s, n in
                        zip(e["client"], e["server"], e["conn"])])
    uniq, inv = np.unique(label, return_inverse=True)
    nb = len(edges_s) + 1
    out = {}
    sides = {}
    for side in ("cdur", "sdur"):
        v32 = seconds_f32(e[side])
        b = bucket_of(v32, edges_s)
        sides[side] = (np.bincount(inv * nb + b, minlength=len(uniq) * nb)
                       .reshape(len(uniq), nb),
                       np.bincount(inv, weights=v32.astype(np.float64),
                                   minlength=len(uniq)))
    total = np.bincount(inv, minlength=len(uniq))
    failed = np.bincount(inv, weights=e["failed"], minlength=len(uniq))
    for i, key in enumerate(uniq.tolist()):
        out[tuple(key.split("\x1f"))] = {
            "total": int(total[i]), "failed": int(failed[i]),
            "client_buckets": sides["cdur"][0][i],
            "client_sum": float(sides["cdur"][1][i]),
            "server_buckets": sides["sdur"][0][i],
            "server_sum": float(sides["sdur"][1][i])}
    return out


def span_metrics(cols: dict, templates: list, services: list) -> dict:
    """{(service, span_name, span_kind, status_code): float32 seconds of
    every in-slack span of that series}."""
    ok = np.flatnonzero(cols["in_slack"])
    tmpl, status = cols["tmpl"][ok], cols["status"][ok]
    v32 = seconds_f32(cols["end_ns"][ok] - cols["start_ns"][ok])
    out = {}
    for t in np.unique(tmpl).tolist():
        for st in np.unique(status[tmpl == t]).tolist():
            rows = (tmpl == t) & (status == st)
            tp = templates[t]
            out[(tp["service"], tp["name"], KIND_STRS[KIND[tp["kind"]]],
                 STATUS_STRS[st])] = v32[rows]
    return out
