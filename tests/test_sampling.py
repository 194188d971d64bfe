"""Graceful-overload sampling: determinism, the pressure controller,
Horvitz-Thompson weights, the staged-path wiring, and the satellite
regressions (limiter bucket eviction, remote-write retry behavior)."""

from __future__ import annotations

import time

import numpy as np
import pytest

from tempo_tpu import native, sched
from tempo_tpu.distributor.sampler import (SpanSampler, _DurationSketch,
                                           trace_hash_u01)
from tempo_tpu.overrides.limits import SamplingLimits
from tempo_tpu.sched import SchedConfig, fraction_for_pressure


def _recs(n: int, seed: int = 0, err_every: int = 0,
          dur_ns: int = 1_000_000, tail_every: int = 0,
          tail_dur_ns: int = 10_000_000_000) -> np.ndarray:
    """Synthetic StageRec rows: distinct trace ids, optional error and
    latency-tail stripes."""
    rng = np.random.default_rng(seed)
    recs = np.zeros(n, native.STAGE_REC_DTYPE)
    recs["trace_id"] = rng.integers(0, 256, (n, 16), dtype=np.uint8)
    recs["tid_len"] = 16
    recs["start_ns"] = 1_000_000_000
    recs["end_ns"] = 1_000_000_000 + dur_ns
    if err_every:
        recs["status_code"][::err_every] = 2
    if tail_every:
        recs["end_ns"][1::tail_every] = 1_000_000_000 + tail_dur_ns
    return recs


def _policy(**kw) -> SamplingLimits:
    # tail disarmed by default: most tests want the pure-hash decision
    base = dict(tail_min_spans=1 << 30)
    base.update(kw)
    return SamplingLimits(**base)


# -- the deterministic hash ------------------------------------------------


def test_trace_hash_pure_function_of_id_bytes():
    recs = _recs(512, seed=1)
    u1 = trace_hash_u01(recs["trace_id"])
    u2 = trace_hash_u01(recs["trace_id"].copy())
    assert np.array_equal(u1, u2)
    # order invariance: the variate belongs to the ID, not the row
    perm = np.random.default_rng(2).permutation(512)
    assert np.array_equal(trace_hash_u01(recs["trace_id"][perm]), u1[perm])


def test_trace_hash_roughly_uniform():
    tids = np.random.default_rng(3).integers(0, 256, (200_000, 16),
                                             dtype=np.uint8)
    u = trace_hash_u01(tids)
    assert 0.49 < u.mean() < 0.51
    for f in (0.1, 0.25, 0.5):
        assert abs((u < f).mean() - f) < 0.01


def test_keep_monotone_in_fraction():
    """Raising the keep-fraction only ADDS spans — the property that
    makes a moving controller stable (a trace never flaps out)."""
    recs = _recs(4096, seed=4)
    valid = np.ones(4096, bool)
    pol = _policy(keep_errors=False)
    s = SpanSampler(fraction_source=lambda: 0.5)
    k_lo, _ = s.sample("t", recs, valid, 0.2, pol)
    k_hi, _ = s.sample("t", recs, valid, 0.6, pol)
    assert not (k_lo & ~k_hi).any()


def test_sampler_decisions_agree_across_replicas():
    """keep/drop is a pure function of (trace id, policy): two fresh
    sampler instances — think two distributor replicas, or a replayed
    retry — make identical decisions for identical inputs."""
    recs = _recs(2048, seed=5, err_every=7)
    valid = np.ones(2048, bool)
    pol = _policy()
    ka, wa = SpanSampler().sample("a", recs, valid, 0.3, pol)
    kb, wb = SpanSampler().sample("b", recs, valid, 0.3, pol)
    assert np.array_equal(ka, kb)
    assert np.array_equal(wa, wb)
    # same trace id appearing in a different payload: same decision
    recs2 = np.concatenate([recs[1024:], recs[:1024]])
    kc, _ = SpanSampler().sample("c", recs2, valid, 0.3, pol)
    assert np.array_equal(kc, np.concatenate([ka[1024:], ka[:1024]]))


# -- forced keeps and weights ----------------------------------------------


def test_error_spans_always_kept_exactly():
    recs = _recs(1000, seed=6, err_every=5)
    valid = np.ones(1000, bool)
    keep, w = SpanSampler().sample("t", recs, valid, 0.01, _policy())
    errs = recs["status_code"] == 2
    assert keep[errs].all()
    assert np.allclose(w[errs], 1.0)     # exact, never upscaled


def test_latency_tail_always_kept_once_armed():
    recs = _recs(2000, seed=7, tail_every=100)
    valid = np.ones(2000, bool)
    pol = _policy(tail_min_spans=100, tail_quantile=0.99, keep_errors=False)
    s = SpanSampler()
    for _ in range(5):
        s.observe("t", recs)             # warm the duration sketch
    keep, w = s.sample("t", recs, valid, 0.01, pol)
    tail = recs["end_ns"].astype(np.int64) - recs["start_ns"].astype(np.int64)
    tail = tail > 1_000_000_000          # the 10s stripe
    assert keep[tail].all()
    assert np.allclose(w[tail], 1.0)


def test_horvitz_thompson_weights_recover_true_rate():
    recs = _recs(40_000, seed=8, err_every=10)
    valid = np.ones(len(recs), bool)
    frac = 0.25
    keep, w = SpanSampler().sample("t", recs, valid, frac, _policy())
    est = float(w[keep].sum())
    assert abs(est - len(recs)) / len(recs) < 0.02
    # hash-kept spans carry exactly 1/frac
    hash_kept = keep & (recs["status_code"] != 2)
    assert np.allclose(w[hash_kept], 1.0 / frac)


def test_duration_sketch_quantile():
    sk = _DurationSketch()
    durs = np.concatenate([np.full(9900, 0.01), np.full(100, 10.0)])
    sk.record(durs)
    q99 = sk.quantile(0.99)
    assert 0.005 < q99 < 0.05            # p99 sits in the body's bucket
    assert sk.quantile(0.999) > 1.0      # p99.9 reaches the 10s stripe
    # out-of-range q from a misconfigured tenant policy must clamp, not
    # crash the push path
    assert sk.quantile(1.5) == sk.quantile(1.0)
    assert sk.quantile(-0.5) == sk.quantile(0.0)


# -- the pressure controller -----------------------------------------------


def test_fraction_for_pressure_control_law():
    assert fraction_for_pressure(0.0, 0.5, 0.05) == 1.0
    assert fraction_for_pressure(0.5, 0.5, 0.05) == 1.0
    assert fraction_for_pressure(1.0, 0.5, 0.05) == pytest.approx(0.05)
    mid = fraction_for_pressure(0.75, 0.5, 0.05)
    assert 0.05 < mid < 1.0
    # monotone non-increasing in pressure
    fs = [fraction_for_pressure(p, 0.5, 0.05)
          for p in np.linspace(0, 1.2, 25)]
    assert all(a >= b for a, b in zip(fs, fs[1:]))


def test_scheduler_keep_fraction_tracks_pressure(forced_sched_saturation):
    sc = forced_sched_saturation(0.0)
    assert sc.keep_fraction() == 1.0                 # exactly off
    assert sched.ingest_keep_fraction() == 1.0
    sc.forced_pressure = 0.8
    f = sched.ingest_keep_fraction()
    assert 0.05 <= f < 1.0
    sc.forced_pressure = 0.0
    assert sched.ingest_keep_fraction() == 1.0       # snaps fully off


def test_keep_fraction_smoothing_ramps_and_snaps_back(
        forced_sched_saturation):
    t = [0.0]
    sc = forced_sched_saturation(0.0, SchedConfig(sampling_smoothing_s=1.0))
    sc.now = lambda: t[0]
    assert sc.keep_fraction() == 1.0
    sc.forced_pressure = 1.0
    t[0] += 0.1
    f1 = sc.keep_fraction()
    assert f1 > sc.cfg.sampling_min_fraction        # ramping, not a step
    t[0] += 30.0
    f2 = sc.keep_fraction()
    assert f2 == pytest.approx(sc.cfg.sampling_min_fraction, abs=1e-6)
    sc.forced_pressure = 0.0
    t[0] += 30.0
    assert sc.keep_fraction() == 1.0                 # exact recovery


def test_control_pressure_includes_inflight_jobs():
    """The controller's pressure must not collapse to zero while the
    worker chews a popped backlog — in-flight ingest jobs count."""
    from tempo_tpu.sched import DeviceScheduler

    sc = DeviceScheduler(SchedConfig(max_queue_ingest=10,
                                     sampling_smoothing_s=0.0),
                         start_worker=False)
    mid_dispatch: list[float] = []

    def dispatch(arr):
        mid_dispatch.append(sc.control_pressure())

    for _ in range(4):
        sc.submit_rows("k", "mk", (np.zeros(2, np.float32),), 2, dispatch)
    assert sc.control_pressure() == pytest.approx(0.4)
    sc.drain_once(force=True)
    # during the dispatch the queue was empty but 4 jobs were in flight
    assert mid_dispatch and mid_dispatch[0] == pytest.approx(0.4)
    assert sc.control_pressure() == 0.0


def test_effective_fraction_floor_and_optout():
    s = SpanSampler(fraction_source=lambda: 0.1)
    assert s.effective_fraction("t", _policy(floor=0.4)) == 0.4
    assert s.effective_fraction("t", _policy(floor=0.0)) == \
        pytest.approx(0.1)
    assert s.effective_fraction("t", _policy(enabled=False)) == 1.0
    s2 = SpanSampler(fraction_source=lambda: 1.0)
    assert s2.effective_fraction("t", _policy(floor=0.4)) == 1.0


def test_sampler_idle_tenant_eviction():
    t = [0.0]
    s = SpanSampler(now=lambda: t[0])
    for i in range(50):
        s.observe(f"ten-{i}", _recs(4, seed=i))
    assert s.tenants() == 50
    t[0] = SpanSampler.IDLE_TTL_S + 1.0
    s._next_sweep = 0.0
    s.observe("fresh", _recs(4))
    assert s.tenants() == 1


# -- satellite: rate-limiter bucket eviction --------------------------------


def test_rate_limiter_buckets_bounded_under_tenant_churn():
    from tempo_tpu.distributor.limiter import RateLimiter

    t = [0.0]
    rl = RateLimiter(now=lambda: t[0], idle_ttl_s=60.0, max_buckets=100)
    for i in range(5000):
        t[0] += 0.001
        rl.allow(f"churn-{i}", 10, 1000.0, 1000.0)
    assert len(rl._buckets) <= 100 + 1   # max-size trim holds under churn
    # TTL pass: idle buckets vanish, an active one survives
    t[0] += 30.0
    rl.allow("keepalive", 10, 1000.0, 1000.0)
    t[0] += 45.0                          # idle > 60s for the churn set
    rl._next_sweep = 0.0
    rl.allow("keepalive", 10, 1000.0, 1000.0)
    assert set(rl._buckets) == {"keepalive"}


def test_rate_limiter_churn_cannot_launder_spent_burst():
    """An attacker churning ephemeral tenant ids must not force the trim
    to evict a DRAINED bucket (recreation would regrant a full burst):
    refilled buckets are evicted first, unrefilled ones survive."""
    from tempo_tpu.distributor.limiter import RateLimiter

    t = [0.0]
    rl = RateLimiter(now=lambda: t[0], idle_ttl_s=1e6, max_buckets=50)
    # tenant A drains its whole burst at a trickle refill rate
    assert rl.allow("A", 1000, 1.0, 1000.0)
    # churn: fast-refill ephemeral tenants blow past the cap repeatedly
    for i in range(500):
        t[0] += 0.01
        rl.allow(f"churn-{i}", 1, 1e6, 1000.0)
    # A's bucket was the oldest, but unrefilled → survived every trim
    t[0] += 1.0
    assert not rl.allow("A", 1000, 1.0, 1000.0)


def test_rate_limiter_eviction_is_lossless():
    """An evicted-idle bucket refills to burst anyway: recreation admits
    exactly what a kept bucket would have."""
    from tempo_tpu.distributor.limiter import RateLimiter

    t = [0.0]
    kept = RateLimiter(now=lambda: t[0], idle_ttl_s=1e9)
    evicted = RateLimiter(now=lambda: t[0], idle_ttl_s=10.0)
    for rl in (kept, evicted):
        assert rl.allow("t", 900, 100.0, 1000.0)
    t[0] = 20.0
    evicted._next_sweep = 0.0
    evicted.allow("other", 1, 100.0, 1000.0)   # triggers the sweep
    assert "t" not in evicted._buckets
    for rl in (kept, evicted):
        assert rl.allow("t", 1000, 100.0, 1000.0)   # both refilled to burst
        assert not rl.allow("t", 500, 100.0, 1000.0)


# -- satellite: remote-write retry behavior ---------------------------------


def _one_series():
    """A collection tick of one series: what `send` takes."""
    from tempo_tpu.registry import ManagedRegistry

    reg = ManagedRegistry("t")
    reg.new_counter("m", ("a",)).inc(("b",))
    return reg.collect_columns(0)


def test_remote_write_honors_retry_after(faulty_remote_write):
    from tempo_tpu.generator.remote_write import (RemoteWriteClient,
                                                  RemoteWriteConfig)

    srv = faulty_remote_write
    srv.script.append((429, {"Retry-After": "0.05"}))
    c = RemoteWriteClient(RemoteWriteConfig(url=srv.url, retries=2,
                                            backoff_s=0.01))
    sleeps: list[float] = []
    c._sleep = sleeps.append
    ok = c.send(_one_series())
    assert ok
    assert len(srv.requests) == 2
    assert c.retried_sends == 1 and c.failed_sends == 0
    assert sleeps and sleeps[0] >= 0.05        # Retry-After is the floor


def test_remote_write_full_jitter_backoff(faulty_remote_write):
    """Without Retry-After, sleeps are U(0, base·2^attempt): bounded
    above by the exponential envelope and not all identical (the
    anti-synchronization property)."""
    import random

    from tempo_tpu.generator.remote_write import (RemoteWriteClient,
                                                  RemoteWriteConfig)

    srv = faulty_remote_write
    for _ in range(3):
        srv.script.append((503, {}))
    c = RemoteWriteClient(RemoteWriteConfig(url=srv.url, retries=3,
                                            backoff_s=0.5))
    c._rng = random.Random(42)
    sleeps: list[float] = []
    c._sleep = sleeps.append
    ok = c.send(_one_series())
    assert ok and len(sleeps) == 3
    for i, s in enumerate(sleeps):
        assert 0.0 <= s <= 0.5 * (2 ** i)
    assert len({round(s, 6) for s in sleeps}) > 1


def test_remote_write_non_retryable_4xx_fails_fast(faulty_remote_write):
    from tempo_tpu.generator.remote_write import (RemoteWriteClient,
                                                  RemoteWriteConfig)

    srv = faulty_remote_write
    srv.script.append((400, {}))
    c = RemoteWriteClient(RemoteWriteConfig(url=srv.url, retries=3,
                                            backoff_s=0.01))
    c._sleep = lambda s: None
    ok = c.send(_one_series())
    assert not ok
    assert len(srv.requests) == 1             # no retry on a client error
    assert c.failed_sends == 1 and c.retried_sends == 0


def test_remote_write_obs_families_register():
    from tempo_tpu.obs.jaxruntime import RUNTIME
    import tempo_tpu.generator.remote_write  # noqa: F401 — registers

    text = RUNTIME.render()
    for fam in ("tempo_remote_write_retries_total",
                "tempo_remote_write_sends_total",
                "tempo_remote_write_series_encoded_total",
                "tempo_remote_write_failed_sends_total"):
        assert fam in text
