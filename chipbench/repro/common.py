"""Boot the `blockread-1tenant` App on whatever device JAX finds and put
hand-made spans into one backend block (an hour old, so that reads go to
the backend)."""

import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import lib  # noqa: E402

TENANT = "reads"
HOUR_AGO_S = (int(time.time()) - 3600) // 60 * 60      # a whole minute


def serve_block(spans: list[dict]):
    """`spans`: dicts with trace_id, span_id, start_unix_nano,
    end_unix_nano (ints and bytes), the rest filled in here."""
    from tempo_tpu.block.schema import spans_by_trace
    from tempo_tpu.obs.jaxruntime import configure_compile_cache

    configure_compile_cache()
    config = lib.load_json("configs", "blockread-1tenant.json")
    app, _srv, port = lib.boot(config, tempfile.mkdtemp(prefix="repro-"),
                               lib.Sink().url)
    full = [dict({"name": "op", "service": "svc", "kind": 1, "status_code": 0,
                  "parent_span_id": b"", "attrs": {}, "res_attrs": {}}, **s)
            for s in spans]
    app.db.write_block(TENANT, spans_by_trace(full), replication_factor=1)
    return app, port


def get(port: int, path: str, **params) -> dict:
    return lib.get_json(port, path, TENANT, **params)


def verdict(ok: bool, what: str) -> None:
    print(json.dumps({"defect_shown": not ok, "what": what}), flush=True)
    sys.stdout.flush()
    os._exit(0 if not ok else 1)     # exit 0 while the defect is there
