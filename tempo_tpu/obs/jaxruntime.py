"""JAX/TPU runtime self-metrics: jit compiles, transfers, kernel walls.

One PROCESS-WIDE registry (`RUNTIME`), distinct from the per-App
registry: jit compilation caches, device transfers, and kernel dispatch
are process-level facts shared by every App in the process (tests boot
several), so their counters live here and `/metrics` renders them as an
`extra` registry alongside the App's own families.

Nothing in this module imports jax at import time — `instrumented_jit`
defers the import to first use so CPU-only unit tests of the registry
never pay (or require) a jax initialization.
"""

from __future__ import annotations

import contextlib
import os
import time

from tempo_tpu.obs.registry import Registry, exponential_buckets

RUNTIME = Registry()

JIT_COMPILES = RUNTIME.counter(
    "tempo_jax_jit_compile_total",
    "Number of XLA compilations per instrumented jitted function "
    "(cache-miss traces; steady state should be flat)",
    labels=("fn",))
JIT_COMPILE_SECONDS = RUNTIME.counter(
    "tempo_jax_jit_compile_seconds_total",
    "Wall seconds spent inside calls that triggered an XLA compilation, "
    "per instrumented jitted function",
    labels=("fn",))
DEVICE_PUT_BYTES = RUNTIME.counter(
    "tempo_jax_device_put_bytes_total",
    "Bytes uploaded host-to-device, by call site",
    labels=("site",))
KERNEL_SECONDS = RUNTIME.histogram(
    "tempo_jax_kernel_duration_seconds",
    "Device kernel wall time measured around block_until_ready at the "
    "ops/sketches result-fetch sites, per kernel",
    labels=("kernel",),
    buckets=exponential_buckets(1e-5, 4.0, 12))


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Place JAX's persistent compile cache before the first jit; returns
    the directory in use. Where JAX_COMPILATION_CACHE_DIR is set JAX reads
    it itself and no other directory is set here. Otherwise ONE fixed
    directory inside the checkout: the path is part of every cache key,
    so one built from a temp name, pid or time would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def instrumented_jit(fn, *, name: str | None = None, **jit_kwargs):
    """`jax.jit` wrapper that detects per-call compile-cache growth and
    records compile count + wall seconds under the `fn` label: any growth
    of the jitted callable's `_cache_size()` during a call means at least
    one fresh trace+compile."""
    import jax

    jfn = jax.jit(fn, **jit_kwargs)
    label = name or getattr(fn, "__name__", "jit")

    def wrapper(*args, **kwargs):
        before = jfn._cache_size()
        t0 = time.perf_counter()
        out = jfn(*args, **kwargs)
        grown = jfn._cache_size() - before
        if grown > 0:
            JIT_COMPILES.inc(grown, (label,))
            JIT_COMPILE_SECONDS.inc(time.perf_counter() - t0, (label,))
        return out

    wrapper.__name__ = getattr(fn, "__name__", "jit")
    wrapper._jit = jfn          # escape hatch: .lower() etc.
    return wrapper


def record_device_put(nbytes: int, site: str) -> None:
    DEVICE_PUT_BYTES.inc(int(nbytes), (site,))


@contextlib.contextmanager
def kernel_timer(kernel: str):
    """Time a device-synchronizing region (a block_until_ready / result
    fetch) into the kernel wall-time histogram."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        KERNEL_SECONDS.observe(time.perf_counter() - t0, (kernel,))


__all__ = ["RUNTIME", "configure_compile_cache", "instrumented_jit",
           "record_device_put", "kernel_timer", "JIT_COMPILES", "JIT_COMPILE_SECONDS",
           "DEVICE_PUT_BYTES", "KERNEL_SECONDS"]
