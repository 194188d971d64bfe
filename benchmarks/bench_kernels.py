"""Device-kernel shootout: XLA scatter vs Pallas MXU one-hot matmul,
plus the paged-fused line (composed scatters vs the Pallas ragged-page
kernel on the packed [roles, bucket] coalescer shape).

Runs on a TPU only (`chiprun -- python -u benchmarks/bench_kernels.py`)
and fails anywhere else: a kernel timing from another backend is not a
kernel timing. Prints one JSON line per formulation. Today both Pallas
kernels are refused by the v5e compiler (tests/test_chip_compile.py), so
the run stops at the first Pallas arm with Mosaic's message; interpret-
mode parity lives in tests/test_pallas_kernels.py, not here.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EDGES = (0.002, 0.004, 0.008, 0.016, 0.032, 0.064, 0.128, 0.256,
         0.512, 1.024, 2.048, 4.096)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from tempo_tpu.ops.pallas_kernels import (
        fused_spanmetrics_matmul,
        fused_spanmetrics_scatter,
    )

    n_spans, n_series = 262144, 4096
    rng = np.random.default_rng(0)
    slots = jnp.asarray(rng.integers(0, n_series, n_spans), jnp.int32)
    dur = jnp.asarray(rng.lognormal(-3, 1.5, n_spans), jnp.float32)
    sizes = jnp.asarray(rng.integers(100, 5000, n_spans), jnp.float32)
    w = jnp.ones((n_spans,), jnp.float32)

    if jax.devices()[0].platform != "tpu":
        raise SystemExit(f"bench_kernels: needs a TPU, JAX sees "
                         f"{jax.devices()}")

    def bench(name, fn, iters=20):
        out = fn()
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out)
        dt = (time.time() - t0) / iters
        print(json.dumps({
            "metric": f"fused_state_delta_{name}",
            "value": round(n_spans / dt, 1),
            "unit": "spans/s",
            "platform": jax.devices()[0].platform,
        }))
        return out

    scatter = jax.jit(lambda: fused_spanmetrics_scatter(
        slots, dur, sizes, w, n_series=n_series, edges=EDGES))
    a = bench("xla_scatter", scatter)

    matmul = jax.jit(lambda: fused_spanmetrics_matmul(
        slots, dur, sizes, w, n_series=n_series, edges=EDGES,
        block=1024))
    b = bench("pallas_mxu_matmul", matmul)

    # obs instrumentation cost on the same kernel: instrumented_jit's
    # per-call compile-cache probe + a kernel_timer histogram observation
    # — what production dispatch sites (device_scan, spanmetrics) pay.
    # Alternating pairs + per-arm median so machine noise cancels out of
    # a delta that is micro-seconds against a multi-ms kernel.
    import statistics

    from tempo_tpu.obs.jaxruntime import instrumented_jit, kernel_timer

    scatter_obs = instrumented_jit(
        lambda: fused_spanmetrics_scatter(
            slots, dur, sizes, w, n_series=n_series, edges=EDGES),
        name="bench_xla_scatter")

    def obs_call():
        with kernel_timer("bench_xla_scatter"):
            return scatter_obs()

    def one(fn) -> float:
        t0 = time.time()
        jax.block_until_ready(fn())
        return time.time() - t0

    one(scatter)
    one(obs_call)                       # warm the instrumented trace
    plain, instr = [], []
    for _ in range(10):
        plain.append(one(scatter))
        instr.append(one(obs_call))
    dt_plain, dt_obs = statistics.median(plain), statistics.median(instr)
    print(json.dumps({
        "metric": "fused_state_delta_xla_scatter_instrumented",
        "value": round(n_spans / dt_obs, 1),
        "unit": "spans/s",
        "platform": jax.devices()[0].platform,
    }))
    print(json.dumps({
        "metric": "obs_kernel_instrumentation_overhead_pct",
        "value": round((dt_obs - dt_plain) / dt_plain * 100, 3),
        "unit": "%",
    }))

    # request-scoped query-stats accumulation on the same dispatch: an
    # active QueryStats scope recording device-scan stage + kernel wall
    # nanos per call (what tempodb's fused drain pays per grid fetch) vs
    # the no-scope None-check path — the <3% read-path budget twin of
    # the obs overhead line above.
    from tempo_tpu.obs import querystats

    def qstats_call():
        with querystats.stage("device_scan"):
            out = scatter()
        t0 = time.perf_counter_ns()
        jax.block_until_ready(out)
        querystats.add(kernel_wall_ns=time.perf_counter_ns() - t0)
        return out

    # alternating pairs + per-arm median, like the obs arm above — the
    # delta is micro-seconds against a multi-hundred-µs kernel, so phase
    # drift would swamp a split measurement
    with querystats.scope():
        one(qstats_call)                # warm
        plain_q, instr_q = [], []
        for _ in range(10):
            plain_q.append(one(scatter))
            instr_q.append(one(qstats_call))
    pct = (statistics.median(instr_q) - statistics.median(plain_q)) \
        / statistics.median(plain_q) * 100
    print(json.dumps({
        "metric": "query_stats_kernel_instrumentation_overhead_pct",
        "value": round(pct, 3),
        "unit": "%",
    }))
    print(json.dumps({"check": "query_stats_overhead_under_3pct",
                      "ok": bool(pct < 3.0)}))

    # f32 accumulation order differs (matmul vs sorted scatter): ~1e-3 rel
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                               atol=1e-3)
    print(json.dumps({"check": "outputs_match", "ok": True}))

    # device-scheduler amortization on the same fused kernel: many
    # 256-row caller batches coalesced into padded pow-2 dispatches vs
    # one dispatch per caller (tempo_tpu/sched; ISSUE 3 bench line)
    from tempo_tpu.sched import DeviceScheduler, SchedConfig

    small = 256
    n_jobs = 256
    srng = np.random.default_rng(1)
    jobs = [(srng.integers(0, n_series, small).astype(np.int32),
             srng.lognormal(-3, 1.5, small).astype(np.float32),
             srng.integers(100, 5000, small).astype(np.float32),
             np.ones(small, np.float32)) for _ in range(n_jobs)]

    def small_step(slots, dur, sizes, w):
        return fused_spanmetrics_scatter(slots, dur, sizes, w,
                                         n_series=n_series, edges=EDGES)

    from tempo_tpu.sched import bucket_rows

    sstep = jax.jit(small_step)
    # deterministic warmup: the 256-row direct shape plus every pow-2
    # bucket the coalescer can produce for this load (chunk sizes are
    # timing-dependent multiples of 256)
    for b in sorted({small} | {bucket_rows(r)
                               for r in range(small, 16384 + 1, small)}):
        jax.block_until_ready(sstep(
            jnp.full((b,), -1, jnp.int32), jnp.zeros(b, jnp.float32),
            jnp.zeros(b, jnp.float32), jnp.zeros(b, jnp.float32)))
    t0 = time.time()
    outs = [sstep(*map(jnp.asarray, j)) for j in jobs]
    jax.block_until_ready(outs)
    dt_direct = time.time() - t0

    acc = []
    sc = DeviceScheduler(SchedConfig(batch_window_ms=20.0),
                         start_worker=True)
    for j in jobs:                                             # warm buckets
        sc.submit_rows("bench_kernels_sched", "m", j, small,
                       lambda *a: acc.append(sstep(*a)))
    sc.flush()
    jax.block_until_ready(acc)
    acc.clear()
    t0 = time.time()
    for j in jobs:
        sc.submit_rows("bench_kernels_sched", "m", j, small,
                       lambda *a: acc.append(sstep(*a)))
    sc.flush()
    jax.block_until_ready(acc)
    dt_sched = time.time() - t0
    sc.stop()
    print(json.dumps({
        "metric": "sched_dispatch_amortization",
        "value": round(dt_direct / dt_sched, 2) if dt_sched else 0.0,
        "unit": "x_vs_direct_256row_calls",
        "extra": {
            "batch_occupancy": round(
                sc.mean_occupancy("bench_kernels_sched"), 3),
            "batches": sc.batches_total.get("bench_kernels_sched", 0),
            "jobs_coalesced": sc.coalesced_total.get(
                "bench_kernels_sched", 0),
            "padding_waste_bytes": sc.padding_waste_bytes.get(
                "bench_kernels_sched", 0),
        },
        "platform": jax.devices()[0].platform,
    }))

    # paged fused family update (ISSUE 11): composed XLA scatters vs the
    # single-pass Pallas ragged-page kernel on the coalescer's packed
    # [roles, bucket] shape. The composed path re-gathers the page-table
    # indirection once PER ROLE (7 scatters here: calls, latency
    # sum/count, size, latency grid, dd grid, dd zeros); the Pallas
    # kernel walks the stacked tables once per span block. Gate:
    # pallas >= 2x.
    import statistics as _st

    from tempo_tpu.ops import pages as op_pages

    page_rows, cap = 256, 4096
    lpages = cap // page_rows
    n_phys = lpages + 2                  # + trash page + slack
    gamma_pf, nb_pf = 1.05, 512
    rows = n_phys * page_rows
    n_hist = len(EDGES) + 1

    def pf_arenas():
        # distinct buffers: the step donates every arena (a shared
        # zeros buffer would be donated twice and XLA rejects it)
        return tuple(jnp.zeros(rows, jnp.float32) for _ in range(4)) + (
            jnp.zeros((rows, n_hist), jnp.float32),
            jnp.zeros(rows, jnp.float32),
            jnp.zeros((rows, nb_pf), jnp.float32))

    # every logical page backed (phys 0 = reserved trash)
    table = jnp.asarray(np.arange(1, lpages + 1, dtype=np.int32))
    tabs = (table,) * 7
    prng = np.random.default_rng(3)

    def pf_mat(bucket):
        m = np.empty((4, bucket), np.float32)
        m[0] = prng.integers(0, cap, bucket)
        m[1] = prng.lognormal(-3, 1.5, bucket)
        m[2] = prng.integers(100, 5000, bucket)
        m[3] = 1.0
        return m

    def pf_arm(kernel, buckets, iters=10):
        step = op_pages.fused_step(
            EDGES, gamma_pf, 1e-6, cap, page_rows.bit_length() - 1,
            packed=True, kernel=kernel)
        out = {}
        for bucket in buckets:
            mats = [jnp.asarray(pf_mat(bucket)) for _ in range(3)]
            arenas = pf_arenas()
            arenas = step(*arenas, *tabs, mats[0])       # warm trace
            times = []
            for _ in range(3):
                t0 = time.time()
                for i in range(iters):
                    arenas = step(*arenas, *tabs, mats[i % 3])
                jax.block_until_ready(arenas[0])
                times.append((time.time() - t0) / iters)
            out[bucket] = bucket / _st.median(times)
        return out

    pf_buckets = (256, 4096, 65536)
    xla_rates = pf_arm("xla", pf_buckets)
    extra = {f"xla_{b}_spans_per_sec": round(r, 1)
             for b, r in xla_rates.items()}
    pal_rates = pf_arm("pallas", pf_buckets)
    extra.update({f"pallas_{b}_spans_per_sec": round(r, 1)
                  for b, r in pal_rates.items()})
    speedup = min(pal_rates[b] / xla_rates[b] for b in pf_buckets)
    print(json.dumps({"metric": "paged_fused",
                      "value": round(speedup, 2),
                      "unit": "x_pallas_vs_composed_scatter",
                      "extra": extra, "platform": "tpu"}))
    print(json.dumps({"check": "paged_fused_pallas_2x",
                      "ok": bool(speedup >= 2.0)}))


if __name__ == "__main__":
    sys.exit(main())
