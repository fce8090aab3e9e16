#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a deployment (``bench/configs/``) and
a traffic mix (``bench/traffic/``).  This process holds the chip: it
builds the ``SessionEngine`` with its lane state on the device and every
scan program compiled ahead of time, puts a ``SessionService`` in front
of it and starts the load generator (``bench/loadgen.py``) as a child
that never touches the chip.  Set-up ends when every session is open;
then the generator offers the mix open loop for ``--seconds``, and
afterwards the sampled answers are compared with the numpy reference.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` runs
with the span tracer and the JAX profiler on and prints its per-layer
metrics.  The last line of standard output is the result's JSON; the
numbers compared for ``correct`` are the last lines of standard error.
Exits 2, with no result, where JAX finds no TPU or too few chips.
``--rate`` (requests/s, for the knee sweep) and ``--control`` (a lower
precision or shortcut in the program's place) are for measuring the
benchmark itself; its cells never use them.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse                      # noqa: E402
import importlib.util                # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import subprocess                    # noqa: E402
import sys                           # noqa: E402
from pathlib import Path             # noqa: E402

import numpy as np                   # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import workload as wl                # noqa: E402

OUT_DIR = ROOT / ".bench_out"        # traces live here until reduced
CACHE_DIR = ROOT / ".jax_cache"      # fixed: the path is in the cache key


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def use_compile_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program of the cell is cached, the small ones too, so that
    # only a cell's first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def build_engine(cfg: dict, chips: int, traced: bool):
    from repro.apps import histo, hll
    from repro.core.distributed import lane_mesh
    from repro.serve import SessionEngine

    if cfg["app"] == "histo":
        spec = histo.make_spec(cfg["bins"], cfg["key_domain"], cfg["num_pri"])
    elif cfg["app"] == "hll":
        spec = hll.make_spec(cfg["p"], cfg["num_pri"])
    else:
        raise ValueError(f"unknown app {cfg['app']!r}")
    return SessionEngine(
        spec, num_pri=cfg["num_pri"], num_sec=cfg["num_sec"],
        chunk_size=cfg["chunk"], primary_slots=cfg["primary_slots"],
        secondary_slots=cfg["secondary_slots"],
        aot_buckets=cfg["aot_buckets"], mesh=lane_mesh(chips),
        obs=None if traced else False)


def backlog_tuples(engine) -> int:
    return int(sum(engine.tenant_loads()[1].values()))


def session_totals(engine) -> dict:
    out = {"chunks": 0, "sec_chunks": 0}
    for sid in list(engine.sessions):
        st = engine.session_stats(sid)
        out["chunks"] += st["chunks_flushed"]
        out["sec_chunks"] += st["sec_lane_flushes"]
    return out


def telemetry(engine) -> dict:
    """The engine's telemetry record, read while its worker may be
    appending a row (a copy of a deque that changes raises; read again)."""
    while True:
        try:
            return engine.telemetry_record(validate=False)
        except RuntimeError:
            time.sleep(0.001)


class Child:
    """The load generator process and its line protocol."""

    def __init__(self, argv, env):
        self.proc = subprocess.Popen(argv, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, env=env,
                                     text=True, bufsize=1)

    def expect(self, tag: str) -> dict:
        for line in self.proc.stdout:
            if line.startswith(tag + " "):
                return json.loads(line[len(tag) + 1:])
            sys.stderr.write(line)
        raise RuntimeError(f"load generator exited (code "
                           f"{self.proc.wait()}) before {tag}")

    def send(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.stdin.close()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


def load_reader(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sleep_until(t: float) -> None:
    time.sleep(max(t - time.monotonic(), 0.0))


def run_cell(cell: wl.Cell, seed: int, seconds: float, trace: bool,
             rate=None, control=None) -> dict:
    """One run of ``cell``; returns the result dict (last stdout line)."""
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.core import compilemon
    from repro.serve import SessionService, ServiceConfig

    cfg = cell.config
    spec = {"config": cfg, "traffic": cell.traffic,
            "rate": cell.rate if rate is None else rate}
    argv = [sys.executable, str(BENCH / "loadgen.py"), "--root", str(ROOT),
            "--cell", json.dumps(spec), "--seed", str(seed),
            "--seconds", str(seconds)]
    if control is not None:
        argv += ["--control", control]
    # the generator makes its schedule while this process warms up
    child = Child(argv, dict(os.environ, JAX_PLATFORMS="cpu"))
    svc = None
    try:
        use_compile_cache()
        devices = jax.devices()[:cell.chips]
        engine = build_engine(cfg, cell.chips, trace)
        engine.warmup(dtype=np.int32, feat_shape=(2,))
        aot = telemetry(engine)["extra"]["aot"]
        log(f"engine: {engine.num_lanes} lanes, {engine.state_bytes} B of "
            f"lane state, warm-up {aot['warmup_ms']:.0f} ms "
            f"({aot['warmup_compiles']} compiles)")
        svc = SessionService(engine, ServiceConfig(**cfg["service"]))
        host, port = svc.start()
        child.send(f"CONNECT {host} {port}")
        trace_dir = OUT_DIR / f"trace-{cell.name}"
        ready = child.expect("READY")
        if not ready["warm_ok"]:
            raise RuntimeError("the warm traffic of set-up failed")
        snap = compilemon.snapshot()
        # the prelude of the mix runs before the window, in set-up
        t0 = time.monotonic() + 0.5 + float(cell.traffic["prelude_s"])
        setup_s = t0 - T_START
        child.send(f"GO {t0!r}")
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            sleep_until(t0 - 2.0)
            opts = jax.profiler.ProfileOptions()
            # host TraceMe events label the idle gaps; the Python tracer
            # would record every call of the service and slow it down
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        sleep_until(t0)
        if trace:
            engine.obs.tracer.clear()
        b0 = backlog_tuples(engine)
        tot0 = session_totals(engine)
        rows0 = telemetry(engine)["extra"]["telemetry"]["rows_total"]
        if trace:
            with jax.profiler.TraceAnnotation("bench.window"):
                sleep_until(t0 + seconds)
        else:
            sleep_until(t0 + seconds)
        b1 = backlog_tuples(engine)
        rows1 = telemetry(engine)["extra"]["telemetry"]["rows_total"]
        if trace:
            jax.profiler.stop_trace()
        window = child.expect("WINDOW")
        compiles = compilemon.since(snap).n_compiles
        tot1 = session_totals(engine)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        spans = engine.obs.tracer.events() if trace else []
        child.send("VERIFY")
        verdict = child.expect("RESULT")
    finally:
        child.stop()
        if svc is not None:
            svc.stop()
    rec = telemetry(engine)
    first = rec["extra"]["telemetry"]["rows_total"] - len(rec["rows"])
    rows = rec["rows"][max(rows0 - first, 0):rows1 - first]
    del engine, svc
    trace_red = None
    if trace:
        import trace_reduce
        trace_red = trace_reduce.reduce_dir(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = {
        "seconds": seconds, "config": cfg, "traffic": cell.traffic,
        "chips": cell.chips, "device_kind": devices[0].device_kind,
        "setup_s": setup_s, "window": window, "backlog_growth": b1 - b0,
        "rows": rows, "num_lanes": cfg["primary_slots"]
        + cfg["secondary_slots"],
        "chunks": tot1["chunks"] - tot0["chunks"],
        "sec_chunks": tot1["sec_chunks"] - tot0["sec_chunks"],
        "spans": spans, "trace": trace_red,
    }
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_reader("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = {
        "mismatched_cells": [verdict["mismatched_cells"], 0],
        "missing_answers": [verdict["missing"] + verdict["wrong_shape"], 0],
        "never_answered": [window["never_answered"], 0],
        "window_compiles": [compiles, 0],
    }
    correct = (all(v <= lim for v, lim in checks.values())
               and verdict["compared"] > 0)
    lat = {k: _pcts(window[k]) for k in ("append_lat_ms", "query_lat_ms",
                                         "prelude_query_lat_ms", "late_ms")}
    log(f"window: {window['attempted']} requests ({window['appends']} "
        f"appends, {window['queries']} queries), failed {window['failed']} "
        f"{window['errors']}, in flight at close "
        f"{window['inflight_at_close']}, last answer at "
        f"{window['last_answer_s']:.2f} s, backlog {b0} -> {b1} tuples, "
        f"{len(rows)} flushes, p50 latency first/last fifth "
        f"{window['lat_p50_first_last_fifth_ms']} ms, answered in window "
        f"{window['answered_in_window']}")
    for k, v in lat.items():
        log(f"{k}: {v}")
    for scope in ("engine", "session"):
        rs = [r for r in rows if r["scope"] == scope]
        if rs:
            ms = np.array([r["flush_ms"] for r in rs], float)
            wd = np.array([r["lane_width"] for r in rs], float)
            log(f"{scope} flushes: {len(rs)}, flush_ms mean {ms.mean():.1f} "
                f"max {ms.max():.1f} sum {ms.sum():.0f}, width mean "
                f"{wd.mean():.2f} max {wd.max():.0f}, tuples "
                f"{sum(r['tuples'] for r in rs)}, chunks "
                f"{sum(r['chunks'] for r in rs)}, sec granted max "
                f"{max(r['sec_granted'] for r in rs)}")
    log(f"compared {verdict['compared']} answers "
        f"({verdict['not_comparable']} after an append of unknown fate)")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device}
    if trace_red is not None:
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
        result["breakdown"] = trace_red["breakdown"]
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def _pcts(xs) -> dict:
    if not xs:
        return {"n": 0}
    a = np.asarray(xs, float)
    return {"n": len(a), "p50": float(np.percentile(a, 50)),
            "p95": float(np.percentile(a, 95)),
            "p99": float(np.percentile(a, 99)), "max": float(a.max())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override the mix's requests/s (knee sweep)")
    ap.add_argument("--control", choices=("int16", "stale_tail"),
                    default=None, help="compare a control, not the program")
    args = ap.parse_args(argv)
    cell = wl.resolve(args.workload, ROOT)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is missing",
              file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      rate=args.rate, control=args.control)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
