"""What every part of the harness shares: lines of output, HTTP, the
`/metrics` scrape, the percentile rule, the served App.

`boot()` and `Sink` are copies of `chip_smoke.py`'s (sound, PR 22), with
the configuration's overrides read from its file instead of constants.
"""

from __future__ import annotations

import http.client
import json
import math
import os
import re
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, HTTPServer

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class BenchFailure(Exception):
    """The run cannot end in a result."""


def say(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


# -- HTTP ------------------------------------------------------------------

def http_call(port: int, method: str, path: str, tenant: str = "",
              body: bytes | None = None, timeout: float = 600.0
              ) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        headers = {"X-Scope-OrgID": tenant} if tenant else {}
        if body is not None:
            headers["Content-Type"] = "application/x-protobuf"
        conn.request(method, path, body=body, headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def get_json(port: int, path: str, tenant: str = "", **params):
    if params:
        path += "?" + urllib.parse.urlencode(params)
    status, body = http_call(port, "GET", path, tenant)
    if status != 200:
        raise BenchFailure(f"GET {path} -> {status}: {body[:300]!r}")
    return json.loads(body)


_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_exposition(text: str) -> dict:
    """Prometheus text -> {(sample name, ((label, value), ...)): float}."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _SAMPLE.match(line)
        if m is None:
            continue
        name, labels, value = m.groups()
        key = (name, tuple(sorted(_LABEL.findall(labels or ""))))
        out[key] = float(value)
    return out


def scrape(port: int) -> dict:
    status, body = http_call(port, "GET", "/metrics")
    if status != 200:
        raise BenchFailure(f"/metrics -> {status}")
    return parse_exposition(body.decode())


def metric_sum(m: dict, name: str, **labels) -> float:
    want = set(labels.items())
    return sum(v for (n, ls), v in m.items() if n == name and want <= set(ls))


def delta(obs: dict, name: str, **labels) -> float:
    """Growth of a counter family over the window."""
    return metric_sum(obs["m1"], name, **labels) \
        - metric_sum(obs["m0"], name, **labels)


# -- statistics ------------------------------------------------------------

BEYOND = 10      # samples a percentile needs beyond it (choosing-metrics 1)


def percentile(values, q: float, strict: bool = True) -> float:
    """The q-th percentile by linear interpolation between order
    statistics (numpy's default), refused where fewer than BEYOND samples
    lie beyond it: a tail read off two or three requests is noise."""
    v = sorted(values)
    beyond = len(v) * (100.0 - q) / 100.0
    if strict and beyond < BEYOND and q > 50:
        raise BenchFailure(f"p{q:g} of {len(v)} samples has "
                           f"{beyond:.1f} beyond it, needs {BEYOND}")
    if not v:
        raise BenchFailure(f"p{q:g} of no samples")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


# -- the served App --------------------------------------------------------

class Sink:
    """The loopback remote-write receiver."""

    def __init__(self) -> None:
        sink = self
        self.bodies: list[int] = []

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0) or 0)
                sink.bodies.append(len(self.rfile.read(n)))
                self.send_response(200)
                self.send_header("Content-Length", "0")
                self.end_headers()

            def log_message(self, *a):
                pass

        self.srv = HTTPServer(("127.0.0.1", 0), Handler)
        self.url = f"http://127.0.0.1:{self.srv.server_address[1]}/api/v1/push"
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()


def merged(base: dict, over: dict) -> dict:
    """`base` with `over` laid on top, group by group; neither is changed."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def boot(config: dict, workdir: str, sink_url: str):
    """The single-binary App of the configuration's example yaml, moved
    into `workdir`, remote-writing to the loopback sink, serving on a free
    port of this process."""
    import yaml

    from tempo_tpu.app.api import serve
    from tempo_tpu.app.app import App
    from tempo_tpu.app.config import load_config

    limits_path = os.path.join(workdir, "overrides.yaml")
    with open(limits_path, "w") as f:
        yaml.safe_dump({"overrides": {t: config["tenant_limits"]
                                      for t in config["tenants"]}}, f)
    moved = {
        "server": {"http_listen_port": 0},
        "storage": {"local_path": os.path.join(workdir, "blocks"),
                    "wal_path": os.path.join(workdir, "wal")},
        "per_tenant_override_config": limits_path,
        "generator": {
            "remote_write": {"url": sink_url},
            "localblocks": {"data_dir": os.path.join(workdir, "localblocks")}},
        "usage_stats_enabled": False,
    }
    cfg = load_config(os.path.join(REPO, config["example_yaml"]),
                      overrides=merged(moved, config.get("yaml_overrides", {})))
    app = App(cfg)
    app.start_loops()
    srv = serve(app, block=False)
    return app, srv, srv.server_address[1]
