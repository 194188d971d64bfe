"""`loadgen.py` for tenants that each have a schema of their own.

    python chipbench/loadgen_tenants.py <spec.pkl> <out.pkl>

The same child (closed-loop clients, a list of jobs or a window, never
JAX); the one difference is that a push is drawn under
`spec["schemas"][tenant]` and not under the one `spec["schema"]`.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import loadgen  # noqa: E402


class Source(loadgen.Source):
    def send(self, job) -> dict:
        tenant, n, idx = job
        spec = self.spec
        return loadgen.send_push(spec["port"], spec["seed"], spec["tenants"],
                                 tenant, idx, n, self.shapes[n],
                                 spec["schemas"][tenant], spec["timeout"])


if __name__ == "__main__":
    loadgen.Source = Source
    sys.exit(loadgen.main())
