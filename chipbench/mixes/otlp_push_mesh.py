"""Traffic kind `otlp_push_mesh`: `otlp_push` on a serving mesh.

The same closed-loop writers and the same oracle. The judge adds that the
run was served by the mesh the configuration asks for: a four-chip cell
that fell back to one device would read like a result. Every family read
here is exported by the parent of the PR that brought the cell too, so
both sides are held to it; the gauge that PR added is read where it is.
"""

from __future__ import annotations

from chipbench.lib import metric_sum, scrape
from chipbench.mixes import otlp_push

OCCUPANCY = "tempo_sched_batch_occupancy_ratio_count"
KERNEL = "spanmetrics_fused_update"


class Mix(otlp_push.Mix):
    @staticmethod
    def batches(m: dict, shard: str) -> float:
        """Span-metrics batches the scheduler dispatched since boot: a
        mesh batch carries its 'data' shard under `shard` ("0" with the
        data axis at 1), a one-device batch `shard=""`."""
        return metric_sum(m, OCCUPANCY, kernel=KERNEL, shard=shard)

    def wait_start(self) -> None:
        super().wait_start()
        self.one_device_at_go = self.batches(scrape(self.ctx.port), "")

    def judge(self, res: dict, t_go: float, seconds: float) -> dict:
        judged = super().judge(res, t_go, seconds)
        want = self.ctx.config["yaml_overrides"]["mesh"]
        m, complaints = scrape(self.ctx.port), judged["complaints"]
        for family, key in (("tempo_mesh_devices", "devices"),
                            ("tempo_mesh_series_shards", "series_shards")):
            if metric_sum(m, family) != want[key]:
                complaints.append(f"{family} = {metric_sum(m, family)}, the "
                                  f"configuration asks for {want[key]}")
        grown = self.batches(m, "") - self.one_device_at_go
        if grown:
            complaints.append(f"{grown:g} span-metrics batches of the window "
                              "took the one-device route")
        if not self.batches(m, "0"):
            complaints.append("no span-metrics batch took the mesh route")
        unplaced = metric_sum(m, "tempo_mesh_unplaced_processors")
        if unplaced:     # absent at a parent without the gauge: reads 0
            complaints.append(f"{unplaced:g} span-metrics processors stayed "
                              "on one device")
        return judged
