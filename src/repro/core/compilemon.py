"""Process-wide compile-stall monitor (retrace telemetry).

The serving engine's whole value proposition is steady per-flush latency
under ragged, skewed traffic -- and a silent ``jit`` retrace is the
single biggest way to lose it: one unlucky batch shape and a flush that
normally takes ~1 ms stalls for hundreds while XLA recompiles.  The
ROADMAP calls recompiles "the biggest untracked latency source today";
this module makes them *tracked*.

It hangs one listener on ``jax.monitoring`` (the same event stream
``jax.log_compiles`` prints from) and accumulates two counters:

  * ``n_compiles``  -- backend compilations observed (one per retrace;
    the ``/jax/core/compile/backend_compile_duration`` event);
  * ``stall_secs``  -- wall-clock spent tracing + lowering + compiling
    (trace, MLIR-lowering and backend-compile duration events summed),
    i.e. the latency the process paid to compilation.

``jax.monitoring`` has no per-listener removal, so the listener is
installed once per process (idempotent ``install()``) and consumers
read *deltas*: ``snapshot()`` before and after a region attributes its
compile stalls::

    from repro.core import compilemon
    compilemon.install()
    before = compilemon.snapshot()
    run_flush()
    d = compilemon.since(before)        # CompileDelta(n_compiles, stall_ms)

``serve.SessionEngine`` wraps every flush this way and reports the
deltas in its schema-v1 telemetry (``n_retraces`` /
``compile_stall_ms`` per flush row and lifetime totals);
``benchmarks/serving_session.py`` asserts the steady-state count is 0
after the AOT bucket warmup.

Interleaving contract (pinned by ``tests/test_obs.py``)
  The counters are PROCESS-GLOBAL and MONOTONE; a snapshot/since pair
  carries no identity, only two readings.  Three consequences callers
  must design around:

  * **Overlap double-counts.**  Two regions whose snapshot/since
    windows overlap in time BOTH count any compile landing in the
    overlap -- region deltas are not a partition of the total, and
    summing them over overlapping regions over-reports.  Nested
    regions are the common case: the outer delta always INCLUDES the
    inner's.  Use ``repro.obs.region()`` when composition matters: it
    keeps a thread-local region stack and reports an ``exclusive``
    delta per region (children subtracted) alongside the raw
    ``inclusive`` one.
  * **Attribution is per-window, not per-cause.**  A concurrent thread
    compiling inside the window is counted too (the serving engine is
    single-threaded on the flush path, so in practice its deltas are
    its own).
  * **Reads are atomic, windows are not.**  ``snapshot()`` itself is
    lock-consistent (n_compiles and stall_secs from the same instant),
    but nothing orders it against compiles in flight on other threads.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from pathlib import Path

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_STALL_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, git-ignored and inside the checkout: the cache key includes the
# path, so a directory that moved between runs would never hit
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_lock = threading.Lock()
_installed = False
_n_compiles = 0
_stall_secs = 0.0


@dataclasses.dataclass(frozen=True)
class CompileSnapshot:
    """Monotone counters at one instant (see ``snapshot``)."""

    n_compiles: int
    stall_secs: float


@dataclasses.dataclass(frozen=True)
class CompileDelta:
    """Compiles + stall time attributed to one region (see ``since``)."""

    n_compiles: int
    stall_ms: float


def _listener(event: str, duration_secs: float, **_kw) -> None:
    global _n_compiles, _stall_secs
    if event not in _STALL_EVENTS:
        return
    with _lock:
        if event == _COMPILE_EVENT:
            _n_compiles += 1
        _stall_secs += float(duration_secs)


def install() -> None:
    """Register the monitoring listener (idempotent, process-global).
    ``jax.monitoring`` listeners cannot be individually removed, so this
    never registers twice."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_listener)


def snapshot() -> CompileSnapshot:
    """Current monotone counters (0 until ``install()`` has run and a
    compile has happened)."""
    with _lock:
        return CompileSnapshot(_n_compiles, _stall_secs)


def since(before: CompileSnapshot) -> CompileDelta:
    """Compiles and stall milliseconds accumulated after ``before``."""
    now = snapshot()
    return CompileDelta(
        n_compiles=now.n_compiles - before.n_compiles,
        stall_ms=round((now.stall_secs - before.stall_secs) * 1e3, 3))


def use_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point
    (``chip_smoke.py``, ``python -m benchmarks.run``) and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and this sets no other; otherwise the cache goes to
    ``DEFAULT_CACHE_DIR``.  The test suite never calls this: the cache
    stays off there."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        import jax
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
