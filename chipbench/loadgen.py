"""The load generator: a child process that never imports JAX.

    python chipbench/loadgen.py <spec.pkl> <out.pkl>

`spec` (pickled by run.py, which is the only writer) names the port, the
number of closed-loop clients, and either a list of jobs to drain or a
window in seconds. A client sends its next request only when the last one
was answered. With a window, the child waits for a line on stdin, runs
for `seconds`, lets requests in flight finish, and stops. Times are
`time.monotonic()`, which parent and child share on one machine.

Two request kinds: `push` (an OTLP payload drawn from the seed and
stamped with the wall clock at send time, as spans must end inside the
generator's slack window) and `get` (a path prepared by the parent; the
body is kept for the parent to judge).
"""

from __future__ import annotations

import os
import pickle
import resource
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import spans  # noqa: E402
from chipbench.lib import http_call  # noqa: E402


def send_push(port: int, seed: int, tenants: list, tenant: str, idx: int,
              n: int, shape: spans.PushShape, schema: dict,
              timeout: float) -> dict:
    """Draw, stamp, encode and POST one push; the record of it is all the
    judge needs to draw the same spans again."""
    now_ns = time.time_ns()
    payload = spans.encode_push(shape, spans.draw_push(
        seed, tenants.index(tenant), idx, shape, schema, now_ns))
    t0 = time.monotonic()
    status, body = http_call(port, "POST", "/v1/traces", tenant, payload,
                             timeout)
    return {"tenant": tenant, "n": n, "idx": idx, "now_ns": now_ns,
            "t0": t0, "t1": time.monotonic(), "status": status,
            "body": body if body not in (b"", b"{}") else b""}


class Source:
    """Hands each client its next request, under one lock."""

    def __init__(self, spec: dict) -> None:
        self.spec = spec
        self.lock = threading.Lock()
        self.k = 0
        if spec["kind"] == "push":
            self.shapes = {n: spans.PushShape(*gpt)
                           for n, gpt in spec["shapes"].items()}
            self.next_idx = dict(spec["next_idx"])   # tenant -> push index

    def take(self):
        spec = self.spec
        with self.lock:
            k, self.k = self.k, self.k + 1
            if spec["kind"] == "get":
                return (k,) if k < len(spec["paths"]) else None
            if spec.get("jobs") is not None:
                if k >= len(spec["jobs"]):
                    return None
                tenant, n = spec["jobs"][k]
            else:                                  # tenants alternating
                tenant = spec["tenants"][k % len(spec["tenants"])]
                n = spec["n_spans"]
            idx = self.next_idx[tenant]
            self.next_idx[tenant] = idx + 1
            return tenant, n, idx

    def send(self, job) -> dict:
        spec = self.spec
        if spec["kind"] == "get":
            t0 = time.monotonic()
            status, body = http_call(spec["port"], "GET",
                                     spec["paths"][job[0]], spec["tenant"],
                                     None, spec["timeout"])
            return {"k": job[0], "t0": t0, "t1": time.monotonic(),
                    "status": status, "body": body}
        tenant, n, idx = job
        return send_push(spec["port"], spec["seed"], spec["tenants"], tenant,
                         idx, n, self.shapes[n], spec["schema"],
                         spec["timeout"])


def main() -> int:
    with open(sys.argv[1], "rb") as f:
        spec = pickle.load(f)
    src = Source(spec)
    done: list[dict] = []
    errors: list[str] = []
    deadline = [float("inf")]

    def client() -> None:
        while time.monotonic() < deadline[0]:
            job = src.take()
            if job is None:
                return
            try:
                done.append(src.send(job))
            except Exception as e:     # a dead socket is a failed request
                errors.append(f"{type(e).__name__}: {e}")
                done.append({"job": job, "status": -1, "t0": 0.0, "t1": 0.0,
                             "body": b"", "error": str(e)})

    threads = [threading.Thread(target=client) for _ in range(spec["clients"])]
    if spec.get("seconds") is not None:
        print("ready", flush=True)
        sys.stdin.readline()                       # the parent's "go"
        deadline[0] = time.monotonic() + spec["seconds"]
    t_go = time.monotonic()
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    with open(sys.argv[2], "wb") as f:
        pickle.dump({"t_go": t_go, "t_end": time.monotonic(), "done": done,
                     "errors": errors,
                     "cpu_s": (ru1.ru_utime - ru0.ru_utime
                               + ru1.ru_stime - ru0.ru_stime)}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
