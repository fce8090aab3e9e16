"""Continuous-batching session serving under mixed-skew multi-tenant load
(DESIGN.md §8, §9).

Drives ``serve.SessionEngine`` the way a datacenter front-end would:
T tenants with different Zipf skews (and a deliberately hot tenant
appending several times more data, so the backlog scheduler has real
skew to chase) stream ragged appends over multiple rounds; every round
each tenant issues a mid-stream ``query``.

The rounds alternate the query's flush tier so the latency-tiering
claim is measured head-to-head on identical load: ``scope="engine"``
rounds pay the pre-tiering cost (the first query of the round flushes
EVERY tenant's backlog over every lane), ``scope="session"`` rounds
flush only the queried tenant's lane group.  The headline reports both
p99s and their ratio; the per-session tier must win (asserted).

On a multi-device jax (``XLA_FLAGS=--xla_force_host_platform_device_count=4``)
the engine runs distributed: the slot lanes are sharded over a ``lanes``
mesh axis (primary slots are padded up so the lanes split evenly), and
the report carries the device count and lanes-per-device columns.

The engine runs with ``aot_buckets=`` enabled: ``warmup()`` pre-compiles
the whole bucket table before any traffic, and the bench ASSERTS that
the timed rounds observe ZERO retraces (``core.compilemon`` around the
timed window) -- ragged Zipf-1.5 appends and all.  The headline carries
``n_retraces_steady`` / ``compile_stall_ms_steady``, and the embedded
engine telemetry has the per-flush ``n_retraces`` / ``compile_stall_ms``
columns.

Reports sustained tuples/sec and p50/p99 query latency per tier,
verifies every tenant's final buffers bit-exactly against the numpy
oracle, and embeds the engine's own per-flush telemetry record.

A second **session-storm phase** measures batched admission (the
memcached request-path scenario): ``storms`` bursts of
``storm_sessions`` brand-new tenants each arrive in ONE
``open_batch`` call with chunk-straddling first appends.  The phase
ASSERTS in-bench that every storm runs O(width buckets) scan
dispatches (not one per session) and -- on the warmed table -- that
``n_retraces_admit == 0``; the headline carries ``admit_p99_ms`` and
``n_retraces_admit``, and a sample of each burst is verified
bit-exact against the oracle.

Both phases run fully instrumented through one shared ``repro.obs``
bundle (DESIGN.md §11, docs/observability.md): the storm engine is a
``DurableSessionEngine`` over a throwaway WAL directory so the trace
carries ``wal.append`` and ``ckpt.save`` spans next to the flush and
admission spans.  The bench ASSERTS in-bench that (a) the measured
observability overhead -- interleaved obs-on/obs-off round pairs over
identical load, best-round estimator, one retry for CI-runner stalls
-- stays under ``obs_overhead_bound`` percent, (b) the Prometheus
exposition round-trips through ``obs.parse_prometheus``, and (c) the
exported Perfetto trace is non-empty and contains the
flush/admission/WAL span families.  It exports
``serving_session.prom`` (Prometheus text), ``serving_session_trace.json``
(Chrome/Perfetto ``trace_event`` JSON) and ``serving_session_obs.json``
(the snapshot ``python -m repro.obs.report`` renders) next to the
bench record, and the headline carries ``obs_overhead_pct``.

    PYTHONPATH=src python -m benchmarks.serving_session
"""
from __future__ import annotations

import json
import shutil
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

from benchmarks.common import (RESULTS_DIR, bench_record, print_table,
                               save_record)
from repro import obs as obs_lib
from repro.apps import histo
from repro.core import compilemon
from repro.core.distributed import lane_mesh
from repro.data.zipf import zipf_tuples
from repro.obs import parse_prometheus, report as obs_report
from repro.serve import DurableSessionEngine, SessionEngine

ALPHAS = (0.0, 0.8, 1.5, 2.0)
HOT_TENANT = 3            # the alpha=2.0 tenant appends hot_factor x data


def run(n_tuples: int = 1 << 15, rounds: int = 5, chunk: int = 2048,
        num_pri: int = 16, num_sec: int = 8, primary_slots: int = 4,
        secondary_slots: int = 2, hot_factor: int = 4, chips: int = 1,
        aot_buckets: int = 8, storm_sessions: int = 1024,
        storms: int = 3, storm_chunk: int = 256,
        obs_overhead_bound: float = 5.0,
        export_dir: Optional[str] = None):
    if rounds < 3:
        raise ValueError("rounds must be >= 3: one warm-up pass plus at "
                         "least one timed round per flush tier")
    mesh = lane_mesh(chips)
    if mesh is not None:
        # shard_map splits the lanes axis evenly: pad primary slots up
        primary_slots += -(primary_slots + secondary_slots) % chips
    spec = histo.make_spec(512, 1 << 20, num_pri)
    # one shared bundle across both phases: the serving engine and the
    # storm engine emit into the same registry/trace, so the exports
    # show the whole run on one timeline
    obs = obs_lib.Observability()
    eng = SessionEngine(spec, num_pri=num_pri, num_sec=num_sec,
                        chunk_size=chunk, primary_slots=primary_slots,
                        secondary_slots=secondary_slots, mesh=mesh,
                        aot_buckets=aot_buckets, obs=obs)
    aot_info = (eng.warmup(dtype=np.int32, feat_shape=(2,))
                if aot_buckets is not None else None)
    devices = eng.num_lanes // eng.lanes_per_device
    rng = np.random.default_rng(11)
    tenants = list(range(len(ALPHAS)))
    sids = {t: eng.open(tenant=f"zipf{ALPHAS[t]}") for t in tenants}
    appended = {t: [] for t in tenants}
    lat_ms = {"engine": {t: [] for t in tenants},
              "session": {t: [] for t in tenants}}

    def one_round(r, scope, timed: bool):
        total = 0
        for t in tenants:
            n = n_tuples // rounds * (hot_factor if t == HOT_TENANT else 1)
            n += int(rng.integers(1, chunk))          # ragged on purpose
            data = zipf_tuples(n, 1 << 20, ALPHAS[t], seed=100 * r + t)
            eng.append(sids[t], data)
            appended[t].append(data)
            total += n
        for t in tenants:                 # backlog pending: the query
            t0 = time.perf_counter()      # pays its tier's flush cost
            eng.query(sids[t], scope=scope)
            if timed:
                lat_ms[scope][t].append((time.perf_counter() - t0) * 1e3)
        return total

    # warm-up rounds: the engine-scope pass grants the hot tenant its
    # secondary lanes before timing, the session-scope pass exercises the
    # granted lane-group shapes.  With ``aot_buckets`` every flush shape
    # already sits in the warmed bucket table, so these rounds settle the
    # SCHEDULER, not the compiler; run them twice so a ragged width
    # straddling a power-of-two boundary is covered on the plain-jit
    # path (aot_buckets=None) too.
    for w in range(2):
        one_round(rounds + 2 * w, "engine", timed=False)
        one_round(rounds + 2 * w + 1, "session", timed=False)
    pre = compilemon.snapshot()
    t0 = time.perf_counter()
    tuples_timed = sum(
        one_round(r, ("engine", "session")[r % 2], timed=True)
        for r in range(1, rounds))
    seconds = time.perf_counter() - t0
    steady = compilemon.since(pre)
    tput = tuples_timed / seconds

    # per-session flush must answer exactly what a full flush answers
    snap_sess = eng.query(sids[HOT_TENANT], scope="session")
    snap_full = eng.query(sids[HOT_TENANT], scope="engine")
    np.testing.assert_array_equal(np.asarray(snap_sess),
                                  np.asarray(snap_full))

    # ------------------------------------------- observability overhead
    # The <obs_overhead_bound>% acceptance claim, measured in-bench:
    # identical-shape rounds run with the shared bundle toggled on/off,
    # interleaved in pairs whose order alternates so clock drift
    # cancels.  Each state is summarized by its BEST round (max
    # tuples/sec), which is robust to a one-off CI-runner stall landing
    # in a single round; a measurement over the bound gets one full
    # retry (taking the min of the two estimates) before it fails the
    # bench.  Rounds still append real data (recorded in ``appended``),
    # so the bit-exact oracle check below covers them too.
    def obs_round(r):
        t0 = time.perf_counter()
        n = one_round(r, "engine", timed=False)
        return n / (time.perf_counter() - t0)

    def measure_overhead(base):
        tput_by_state = {True: [], False: []}
        for k in range(3):
            for j, state in enumerate((bool(k % 2), not k % 2)):
                obs.enabled = state
                tput_by_state[state].append(obs_round(base + 2 * k + j))
        obs.enabled = True
        on, off = max(tput_by_state[True]), max(tput_by_state[False])
        return round((off - on) / off * 100.0, 2)

    obs_overhead_pct = measure_overhead(1000)
    if obs_overhead_pct >= obs_overhead_bound:
        obs_overhead_pct = min(obs_overhead_pct, measure_overhead(2000))
    print(f"observability overhead: {obs_overhead_pct:+.2f}% "
          f"(bound {obs_overhead_bound:.1f}%)")
    assert obs_overhead_pct < obs_overhead_bound, (
        f"obs-on throughput trails obs-off by {obs_overhead_pct:.2f}% "
        f">= {obs_overhead_bound:.1f}% even after a retry; the "
        "instrumentation hot path regressed")

    def pct(v, q):
        return round(float(np.percentile(v, q)), 2) if len(v) else None

    rows = []
    for t in tenants:
        merged, stats = eng.close(sids[t])
        keys = np.concatenate([d[:, 0] for d in appended[t]])
        np.testing.assert_array_equal(          # acceptance: bit-exact
            np.asarray(merged), histo.oracle(keys, 512, 1 << 20, num_pri))
        rows.append({
            "tenant": f"zipf{ALPHAS[t]}" + (" (hot)" if t == HOT_TENANT else ""),
            "alpha": ALPHAS[t],
            "tuples": int(stats["tuples_flushed"]),
            "queries": int(stats["queries"]),
            "sec_lane_chunks": int(stats["sec_lane_flushes"]),
            "q_p99_ms_full": pct(lat_ms["engine"][t], 99),
            "q_p99_ms_session": pct(lat_ms["session"][t], 99),
        })
    lat_full = np.concatenate([lat_ms["engine"][t] for t in tenants])
    lat_sess = np.concatenate([lat_ms["session"][t] for t in tenants])
    p99_full, p99_sess = pct(lat_full, 99), pct(lat_sess, 99)
    telemetry = eng.telemetry_record()
    title = (f"Session serving: {len(tenants)} mixed-skew tenants, "
             f"{eng.primary_slots}P+{secondary_slots}S slots, "
             f"{devices} device(s) x {eng.lanes_per_device} lanes "
             f"({num_pri}P/{num_sec}S PEs, chunk {chunk})")
    print_table(title, rows)
    print(f"sustained: {tput:,.0f} tuples/s; steady-state retraces "
          f"{steady.n_compiles} ({steady.stall_ms:.1f} ms compile stall "
          "inside the timed rounds)")
    # the tentpole claim: a warmed bucket table means the timed rounds
    # -- ragged Zipf appends, both flush tiers, queries and all -- never
    # hit the compiler.  One retrace here is the multi-hundred-ms stall
    # class the AOT path exists to kill, so it fails the bench.
    if aot_buckets is not None:
        assert steady.n_compiles == 0, (
            f"{steady.n_compiles} retrace(s) ({steady.stall_ms:.1f} ms) "
            "during the timed rounds despite aot_buckets="
            f"{aot_buckets}; the bucket table has a hole")
    # the hot tenant is what the backlog scheduler exists for: it must
    # actually receive secondary lanes under mixed-skew load
    assert rows[HOT_TENANT]["sec_lane_chunks"] > 0, rows[HOT_TENANT]
    # the latency-tiering headline: scanning only the queried session's
    # lanes must beat flushing the whole engine at the tail.  A tier
    # with no timed samples has no p99 (pct() returns None) -- skip the
    # headline instead of formatting None.  A fresh jit compile landing
    # inside one timed query can spike either tier by hundreds of ms on
    # a loaded CI runner; when the raw comparison fails, retry with each
    # tier's single worst sample (the compile spike) dropped before
    # declaring a regression.
    if p99_full is None or p99_sess is None:
        print("query-latency headline skipped: a flush tier recorded no "
              f"timed queries (full={p99_full}, session={p99_sess})")
        speedup = None
    else:
        speedup = round(p99_full / p99_sess, 2)
        print(f"query p99 full-flush {p99_full:.2f} ms vs per-session "
              f"{p99_sess:.2f} ms ({speedup:.2f}x)")
        if not p99_sess < p99_full:
            assert pct(np.sort(lat_sess)[:-1], 99) < \
                pct(np.sort(lat_full)[:-1], 99), (p99_sess, p99_full)

    # ------------------------------------------------ session-storm phase
    # A dedicated wide engine (one primary slot per storm session, no
    # secondary tier -- admission is the thing under test) absorbs
    # ``storms`` bursts of ``storm_sessions`` brand-new tenants, each
    # burst ONE open_batch call with 1..3-chunk first appends (ragged,
    # chunk-straddling).  Between bursts every session closes, so each
    # storm re-admits a cold full house through the same buckets.
    storm_num_pri = 8
    if mesh is not None:
        storm_sessions += -storm_sessions % chips
    storm_spec = histo.make_spec(512, 1 << 20, storm_num_pri)
    storm_aot = 2 if aot_buckets is not None else None
    # durable on purpose: open_batch dispatches through the virtual
    # open/append, so every admitted session WAL-logs -- the shared
    # trace gets ``wal.append`` (and, after the storms, ``ckpt.save``)
    # spans on the same timeline as the admission spans.  The WAL
    # directory is throwaway; checkpoint_every=0 keeps the admission
    # timing free of background checkpoints.
    storm_dir = tempfile.mkdtemp(prefix="serving_session_storm_")
    storm_eng = DurableSessionEngine(
        storm_spec, directory=storm_dir, checkpoint_every=0,
        num_pri=storm_num_pri, num_sec=2, chunk_size=storm_chunk,
        primary_slots=storm_sessions, secondary_slots=0, mesh=mesh,
        aot_buckets=storm_aot, obs=obs)
    if storm_aot is not None:
        storm_eng.warmup(dtype=np.int64, feat_shape=(2,))
    srng = np.random.default_rng(7)
    sample = sorted({0, storm_sessions // 2, storm_sessions - 1})
    admit_ms, dispatches = [], []
    pre_storm = compilemon.snapshot()
    for s in range(storms):
        firsts = []
        for i in range(storm_sessions):
            n = storm_chunk * (1 + (i + s) % 3) + \
                int(srng.integers(0, storm_chunk))
            keys = srng.integers(0, 1 << 20, size=n)
            firsts.append(np.stack([keys, np.ones_like(keys)], axis=1))
        sids = storm_eng.open_batch(
            [f"burst{s}.{i}" for i in range(storm_sessions)], first=firsts)
        row = storm_eng._telemetry[-1]
        assert row["scope"] == "admit" and \
            row["n_admitted"] == storm_sessions, row
        admit_ms.append(row["admit_ms"])
        dispatches.append(row["n_scan_dispatches"])
        # the tentpole claim, asserted in-bench: the widest first append
        # is 3 chunks, so the whole storm runs in <= ceil(3/W) pow2
        # segments -- O(buckets) scan dispatches, NOT one per session
        assert row["n_scan_dispatches"] <= 2 < storm_sessions, row
        for i in sample:              # bit-exact spot check per burst
            np.testing.assert_array_equal(
                np.asarray(storm_eng.query(sids[i], scope="session")),
                histo.oracle(firsts[i][:, 0], 512, 1 << 20, storm_num_pri))
        for sid in sids:              # drain: next burst re-admits cold
            storm_eng.close(sid)
    storm_delta = compilemon.since(pre_storm)
    storm_totals = storm_eng.telemetry_record(
        validate=False)["extra"]["totals"]
    n_retraces_admit = int(storm_totals["n_retraces_admit"])
    admit_p99 = pct(admit_ms, 99)
    print(f"storm phase: {storms} x {storm_sessions}-session open_batch; "
          f"admit p99 {admit_p99:.2f} ms, {max(dispatches)} scan "
          f"dispatch(es)/storm, {n_retraces_admit} admission retrace(s)")
    if storm_aot is not None:
        # warmed admission buckets: a storm must never hit the compiler
        assert n_retraces_admit == 0, storm_totals
        assert storm_delta.n_compiles == 0, (
            f"{storm_delta.n_compiles} retrace(s) "
            f"({storm_delta.stall_ms:.1f} ms) inside the storm phase "
            f"despite aot_buckets={storm_aot}")

    # --------------------------------------------- observability exports
    # checkpoint AFTER the zero-retrace window closes: the lane gather
    # may legitimately compile a fresh shape
    storm_eng.checkpoint(block=True)
    storm_eng.shutdown()
    shutil.rmtree(storm_dir, ignore_errors=True)
    out_dir = Path(export_dir) if export_dir is not None else RESULTS_DIR
    out_dir.mkdir(parents=True, exist_ok=True)
    prom_text = obs.registry.prometheus_text()
    (out_dir / "serving_session.prom").write_text(prom_text)
    obs.tracer.write(out_dir / "serving_session_trace.json",
                     process_name="benchmarks.serving_session")
    obs_snapshot = {"metrics": obs.registry.snapshot(),
                    "telemetry": telemetry}
    (out_dir / "serving_session_obs.json").write_text(
        json.dumps(obs_snapshot, indent=2, default=float))
    # acceptance, in-bench: the exposition round-trips through the
    # strict parser, the trace is non-empty and carries the flush /
    # admission / WAL / checkpoint span families, and the operator
    # report renders from the exported snapshot
    prom_samples = parse_prometheus(prom_text)
    assert prom_samples, "empty Prometheus exposition"
    sample_names = {name for name, _, _ in prom_samples}
    for required in ("flush_latency_ms_count", "admit_latency_ms_count",
                     "wal_records_total", "checkpoints_total"):
        assert required in sample_names, (required, sorted(sample_names))
    span_names = obs.tracer.span_names()
    missing = {"engine.flush", "engine.admit_storm", "wal.append",
               "ckpt.save"} - span_names
    assert not missing, f"trace is missing span families: {missing}"
    n_trace_events = len(obs.tracer.events())
    assert n_trace_events > 0, "empty trace export"
    health = obs_report.render(obs_snapshot)
    assert "engine health report" in health, health[:200]
    print(f"observability: {len(prom_samples)} Prometheus samples, "
          f"{n_trace_events} trace events "
          f"({len(span_names)} span names) -> {out_dir}/"
          "serving_session{.prom,_trace.json,_obs.json}")
    return bench_record(
        "serving_session", title, rows,
        extra={
            "headline": {
                "tuples_per_sec": round(tput, 1),
                "query_p99_ms_full": p99_full,
                "query_p99_ms_session": p99_sess,
                "p99_session_speedup": speedup,
                "n_retraces_steady": int(steady.n_compiles),
                "compile_stall_ms_steady": round(steady.stall_ms, 3),
                "admit_p99_ms": admit_p99,
                "n_retraces_admit": n_retraces_admit,
                "obs_overhead_pct": obs_overhead_pct,
                "devices": devices,
            },
            "config": {
                "devices": devices,
                "lanes_per_device": eng.lanes_per_device,
                "primary_slots": eng.primary_slots,
                "secondary_slots": secondary_slots,
                "aot_buckets": aot_buckets,
                "query_p50_ms_full": pct(lat_full, 50),
                "query_p50_ms_session": pct(lat_sess, 50),
                "storm_sessions": storm_sessions,
                "storms": storms,
                "storm_chunk": storm_chunk,
                "admit_p50_ms": pct(admit_ms, 50),
                "admit_scan_dispatches_max": int(max(dispatches)),
            },
            "storm_telemetry_totals": storm_totals,
            "obs": {
                "overhead_pct": obs_overhead_pct,
                "overhead_bound_pct": obs_overhead_bound,
                "prom_samples": len(prom_samples),
                "trace_events": n_trace_events,
                "trace_dropped": int(obs.tracer.dropped),
                "span_names": sorted(span_names),
                "export_dir": str(out_dir),
            },
            "aot": aot_info,
            "timed_tuples": int(tuples_timed),
            "timed_seconds": round(seconds, 4),
            "telemetry": telemetry,
        })


if __name__ == "__main__":
    save_record(run())
