"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Multi-chip sharding paths (tempo_tpu.parallel) are exercised without TPU
hardware via xla_force_host_platform_device_count, mirroring how the
reference tests multi-node behavior with in-memory fakes (SURVEY.md §4.2).
Must run before the first `import jax` anywhere in the test session.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite runs on virtual CPU devices
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402  (after env setup, before any test imports)

# Persistent compile cache, placed by the program's own rule (the
# JAX_COMPILATION_CACHE_DIR variable wins; else <checkout>/.jax_cache):
# XLA:CPU compiles cost ~1s each and dominate the suite.
from tempo_tpu.obs.jaxruntime import configure_compile_cache  # noqa: E402

configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_device_scheduler():
    """The device scheduler (tempo_tpu.sched) is process-wide state that
    App construction configures; drop it between tests so standalone
    processors (which assert on device state right after a push) never
    inherit async dispatch from an earlier App-based test."""
    yield
    from tempo_tpu import sched

    sched.reset()
    # the device page pool is process-wide the same way: an App-based
    # test leaving it configured would silently page every later test's
    # registries
    from tempo_tpu.registry import pages

    pages.reset()
    # the TraceQL quantile query tier follows the spanmetrics sketch
    # config at App build; reset so a moments-tier App doesn't leak
    # moment grids into later tests' evaluators
    from tempo_tpu.ops import moments

    moments.set_query_tier("log2")
    # the materialized-view tier is process-wide the same way: an
    # App-based test leaving it configured would silently stream every
    # later test's generator pushes into stale grids (and serve its
    # frontend reads from them)
    from tempo_tpu import matview

    matview.reset()
    # the fault-injection registry is process-wide and module-flag
    # gated; a test (or an App built with faults armed) must never
    # leak injected failures into later tests
    from tempo_tpu.utils import faults

    faults.reset()
    # the installed self-tracer is process-wide; a test that installs a
    # SelfTracer (loopback App, propagation tests) must never leave it
    # live — later tests would emit spans into a dead sink and trip the
    # suppression/reserved-tenant guards in surprising places
    from tempo_tpu.utils import tracing

    tracing.install(tracing.Tracer())
    # the span rows behind tempo_span_*_seconds are process-wide too
    tracing.reset_span_rows()
    # trace-analytics operational counters and the dataquality orphan
    # tally are process-wide callback-family state (monotonic by
    # design); reset so per-test assertions on late/cycle/orphan counts
    # never see an earlier test's cuts
    from tempo_tpu.generator.processors import traceanalytics
    from tempo_tpu.utils import dataquality

    traceanalytics.reset_counters()
    dataquality.reset_orphan_spans()


# ---------------------------------------------------------------------------
# tier-1 runtime guard
# ---------------------------------------------------------------------------
#
# The tier-1 suite runs under a hard 870s budget (ROADMAP verify line),
# already pressured by the soak/pages/dryrun tests. Every test added
# AFTER this guard landed must keep its call phase under the budget
# below; the modules listed were grandfathered at introduction. A new test
# file — or any moments-tier test — that exceeds the budget fails the
# whole suite, so slow tests surface in the PR that adds them instead
# of silently eating the shared budget. Opt out (local debugging only)
# with TEMPO_TEST_NO_TIME_GUARD=1.

_RUNTIME_BUDGET_S = 10.0
# explicit, per-test budget exceptions — each must say WHY. The point
# of the guard is surfacing slow tests in the PR that adds them; an
# entry here is that surfacing, not an escape hatch.
_BUDGET_OVERRIDES = {
    # two REAL fleet-worker process boots (~4s of jax+App init each,
    # irreducible) around a SIGKILL: the ingest-WAL crash-recovery
    # contract cannot be exercised in-process. 40 s, not 25, since the
    # workers' compile cache starts empty in a fresh checkout (see the
    # cold-cache block below): 14.5 and 16.1 s read cold, 10.4 warm
    "tests/test_fleet.py::test_sigkill_restart_replays_wal_bit_identically":
        40.0,
    # compiles the structure kernel at three EXTRA pad shapes on purpose
    # (the invariance under test is exactly that recompilation at a new
    # pow-2 pad cannot change results); ~5s of XLA compile per shape
    "tests/test_traceanalytics.py::test_structure_padding_invariance":
        30.0,
    # tests/test_chip_compile.py: each asks the TPU compiler (installed
    # here, no chip attached) for one real-width program of the served
    # path. XLA:TPU takes ~30 s (single-threaded) over the dense step's
    # 83 MB DDSketch plane whatever the batch size and ~10 s over its
    # four-way sharded twin, seen at 46 s / 17 s beside five busy workers.
    "tests/test_chip_compile.py::test_dense_fused_update_compiles": 120.0,
    "tests/test_chip_compile.py::test_serving_mesh_step_compiles": 60.0,
    # the service-graph step: XLA:TPU takes ~19 s over two [65536, 15]
    # histogram planes for a 16-row scatter (19.8 s read alone)
    "tests/test_chip_compile.py::test_servicegraphs_edge_update_compiles":
        60.0,
    # the read plane's grid over a 1M-span block: ~3 s alone, 6 s seen
    # beside five busy workers (the other compile tests stay under 3 s)
    "tests/test_chip_compile.py::test_read_plane_metrics_grid_compiles": 30.0,
    # tests/test_tenants_cell.py: two served Apps one after the other (24
    # tenants on the paged layout, then the same pushes on the dense one:
    # 12 s of boots and 2 x 24 collects with quantiles), 24 s read warm;
    # and the cell's `run.py --rehearsal` as a child process, whose 3 s
    # collection interval the set-up and the judge each wait out twice,
    # 30 s read warm. Both compile on an empty cache beside five workers
    "tests/test_tenants_cell.py::"
    "test_served_paged_tenants_equal_the_oracle_and_the_dense_layout": 90.0,
    "tests/test_tenants_cell.py::"
    "test_the_cell_rehearses_to_its_end_on_the_cpu": 120.0,
    # compiles the moments zeroing and update kernels on an empty cache:
    # 10.1 s read cold beside five workers and the rehearsal's child
    # process above (PR 33's whole run), under 10 s before it
    "tests/test_moments.py::test_moments_zero_slots_resets_to_empty": 25.0,
    # The suite's compile cache now lives inside the checkout (PR 22:
    # the program reads and writes nothing around its checkout), so the
    # driver's fresh checkout starts with it EMPTY and whichever guarded
    # test first touches a kernel pays that kernel's XLA:CPU compile,
    # beside five other workers doing the same. These are the guarded
    # tests that read 5 s or more on an empty cache under the driver's
    # `-n 6 --dist loadfile` (cold reading / the same run with the cache
    # warm); the budget is ~2.5x the cold reading. A test that needs
    # more than its line here got slower, it did not get colder.
    # device compaction merges at several block-split shapes: 25.9 / 9.8
    "tests/test_compact.py::test_device_compaction_block_split_parity": 60.0,
    # 12.9 / 7.6
    "tests/test_compact.py::test_backfill_skips_done_and_respects_limit":
        30.0,
    # 6.5 / 2.6
    "tests/test_compact.py::test_sidecar_merge_and_cardinality": 20.0,
    # 5.2 / 3.7
    "tests/test_compact.py::test_sidecar_fold_rate_matches_rescan_exactly":
        20.0,
    # every moments-kind grid of the fuzz grammar compiles here: 12.8 / 1.7
    "tests/test_plane_fuzz.py::test_fuzz_moments_tier_query_range_parity":
        30.0,
    # 5.6 / 1.9
    "tests/test_traceanalytics.py::test_processor_known_topology_attribution":
        20.0,
    # real fleet-worker processes, each of which boots an App and
    # compiles its kernels from the same empty cache: 10.7 / 2.3,
    # 9.5 / 1.1 and 5.3 / 3.8
    "tests/test_fleet.py::test_shutdown_checkpoint_then_boot_restore": 25.0,
    "tests/test_fleet.py::test_controller_handoff_and_restore_zero_loss":
        25.0,
    "tests/test_fleet.py::test_fleet_worker_process_spawn_and_reap": 20.0,
    # TWO served Apps one after the other (the mesh deployment and its
    # one-device twin, the same OTLP stream over HTTP into each, a collect
    # and two quantile reads a tenant): the comparison between them is
    # the test. 7.1 alone, cold or warm
    "tests/test_mesh_cell.py::"
    "test_served_mesh_app_equals_the_oracle_and_the_one_device_app": 30.0,
}
_GRANDFATHERED_MODULES = frozenset({
    "test_app.py", "test_aux.py", "test_backend.py",
    "test_block.py", "test_cli.py",
    "test_db.py", "test_device_scan.py", "test_devtime.py",
    "test_engine.py", "test_frontend_features.py", "test_generator.py",
    "test_grpc.py", "test_ingest_bus.py", "test_ingest_fuzz.py",
    "test_ingest_pipeline.py", "test_localblocks.py",
    "test_mesh_serving.py", "test_microservices.py", "test_model.py",
    "test_multichip_dryrun.py", "test_native.py", "test_obs.py",
    "test_otlp_batch.py", "test_overload_smoke.py", "test_pages.py",
    "test_parallel.py", "test_plane_arith.py",
    "test_plane_fuzz.py", "test_query_stats.py", "test_read_path.py",
    "test_read_plane.py", "test_registry.py", "test_ring.py",
    "test_sampling.py", "test_sched.py", "test_sketches.py",
    "test_traceql.py", "test_write_path.py",
})
_runtime_offenders: list = []


def pytest_runtest_logreport(report):
    if report.when != "call" or os.environ.get("TEMPO_TEST_NO_TIME_GUARD"):
        return
    module = report.nodeid.split("::", 1)[0].rsplit("/", 1)[-1]
    guarded = module not in _GRANDFATHERED_MODULES \
        or "moments" in report.nodeid
    budget = _BUDGET_OVERRIDES.get(report.nodeid.split("[", 1)[0],
                                   _RUNTIME_BUDGET_S)
    if guarded and report.duration > budget:
        _runtime_offenders.append((report.nodeid, report.duration))


def pytest_terminal_summary(terminalreporter):
    if _runtime_offenders:
        terminalreporter.section("tier-1 runtime guard")
        for nodeid, dur in _runtime_offenders:
            terminalreporter.write_line(
                f"FAILED budget: {nodeid} took {dur:.1f}s "
                f"(> {_RUNTIME_BUDGET_S:.0f}s per new test — the 870s "
                "tier-1 budget is shared; mark it slow or shrink it)")


def pytest_sessionfinish(session, exitstatus):
    if _runtime_offenders and session.exitstatus == 0:
        session.exitstatus = 1


# ---------------------------------------------------------------------------
# fleet child processes — spawned AND reliably reaped (no orphans on
# test failure; every fleet test stays under the 10s tier-1 guard)
# ---------------------------------------------------------------------------


@pytest.fixture
def fleet_procs():
    """Factory spawning `python -m tempo_tpu.fleet.worker ...` children.

    `spawn(args, env=...)` blocks until the worker prints its JSON ready
    line (or dies — surfaced with its stderr tail) and returns the
    Popen with `.ready` (the parsed line) attached. EVERY spawned child
    is reaped on teardown regardless of test outcome: SIGTERM, bounded
    wait, SIGKILL fallback — a failing test must not leak generator
    processes into the rest of the suite. The lifecycle itself lives in
    `tempo_tpu.fleet.worker.{spawn_worker,reap_workers}`."""
    from tempo_tpu.fleet.worker import reap_workers, spawn_worker

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs: list = []

    def spawn(args, env=None, wait_ready_s=60.0):
        e = dict(env or {})
        e.setdefault("JAX_PLATFORMS", "cpu")
        p = spawn_worker(args, env=e, wait_ready_s=wait_ready_s,
                         cwd=repo_root)
        procs.append(p)
        return p

    yield spawn

    reap_workers(procs, term_wait_s=8.0)


# ---------------------------------------------------------------------------
# fault injection — shared overload / retry-storm test helpers
# ---------------------------------------------------------------------------


def make_pressure_scheduler(pressure: float = 0.0, cfg=None):
    """A real DeviceScheduler whose live-ingest queue FILL is forced to
    `pressure` (0..1+): the keep-fraction controller, IngestBackpressure,
    and /status all read the injected value through the normal depth()
    surface, so overload tests exercise the genuine escalation path
    (full stream → sampled → 429) without racing a worker thread.
    Mutate `.forced_pressure` to ramp. Worker is NOT started."""
    from tempo_tpu.sched import DeviceScheduler, PRIO_INGEST, SchedConfig

    class _PressureScheduler(DeviceScheduler):
        def __init__(self):
            # pipeline_depth=0: the decode-ahead ring bounds in-flight
            # jobs and there is NO worker here to land them — a third
            # push would block in pipeline.acquire for its full timeout.
            # smoothing 0: tests assert on the raw control law.
            super().__init__(
                cfg or SchedConfig(sampling_smoothing_s=0.0,
                                   pipeline_depth=0),
                start_worker=False)
            self.forced_pressure = pressure

        def depth(self, prio):
            if prio == PRIO_INGEST:
                return int(round(self.forced_pressure * self._limit(prio)))
            return super().depth(prio)

    return _PressureScheduler()


@pytest.fixture
def forced_sched_saturation():
    """Factory fixture: install a forced-pressure scheduler as THE
    process scheduler for the test. `arm(pressure)` returns it; ramp by
    assigning `.forced_pressure`. Uninstalled on teardown."""
    from tempo_tpu import sched

    cms = []

    def arm(pressure: float = 1.0, cfg=None):
        sc = make_pressure_scheduler(pressure, cfg)
        cm = sched.use(sc)
        cm.__enter__()
        cms.append(cm)
        return sc

    yield arm
    for cm in reversed(cms):
        cm.__exit__(None, None, None)


@pytest.fixture
def faulty_remote_write():
    """A loopback HTTP endpoint with a scripted response sequence —
    the failing / Retry-After-emitting remote-write backend. Append
    `(status, headers)` tuples to `.script` (empty script → 200);
    received requests accumulate in `.requests`."""
    import threading
    from http.server import BaseHTTPRequestHandler, HTTPServer

    class _Handler(BaseHTTPRequestHandler):
        def do_POST(self):
            srv = self.server
            n = int(self.headers.get("Content-Length", 0) or 0)
            body = self.rfile.read(n)
            srv.requests.append({"path": self.path, "n_bytes": len(body),
                                 "headers": dict(self.headers)})
            status, headers = (srv.script.pop(0) if srv.script
                               else (200, {}))
            self.send_response(status)
            for k, v in headers.items():
                self.send_header(k, str(v))
            self.send_header("Content-Length", "0")
            self.end_headers()

        def log_message(self, *a):     # keep pytest output clean
            pass

    srv = HTTPServer(("127.0.0.1", 0), _Handler)
    srv.script = []
    srv.requests = []
    srv.url = f"http://127.0.0.1:{srv.server_address[1]}/api/v1/push"
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv
    srv.shutdown()
    srv.server_close()
    t.join(timeout=2)
