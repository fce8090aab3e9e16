"""Observability layer (DESIGN.md §11, docs/observability.md): metrics
registry semantics + Prometheus round-trip, span tracer ring/export,
the pinned ``core.compilemon`` interleaving contract and the composable
``obs.region()`` attribution built on top of it, engine-level
instrumentation (shared bundles, obs-off equivalence, the telemetry
ring), the incremental ``telemetry_record(validate=True)`` scaling fix,
and recovery observability (replay counters + spans)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:         # benchmarks/ is a repo-root package
    sys.path.insert(0, str(REPO))

from repro import obs as obs_lib
from repro.apps import histo
from repro.core import compilemon
from repro.obs import (DEFAULT_MS_BUCKETS, MetricsRegistry, Observability,
                       SpanTracer, parse_prometheus)
from repro.serve import DurableSessionEngine, SessionEngine

from tests.conftest import SMALL_CHUNK, SMALL_M

BINS, DOMAIN = 64, 1 << 16


def _oracle(keys: np.ndarray) -> np.ndarray:
    return histo.oracle(np.asarray(keys), BINS, DOMAIN, SMALL_M)


def _engine(spec, **kw):
    kw.setdefault("primary_slots", 2)
    kw.setdefault("secondary_slots", 1)
    return SessionEngine(spec, num_pri=SMALL_M, num_sec=2,
                         chunk_size=SMALL_CHUNK, **kw)


# -------------------------------------------------------- MetricsRegistry
class TestMetricsRegistry:
    def test_counter_gauge_histogram_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("flushes_total", "flushes", labels=("scope",))
        c.inc(scope="engine")
        c.inc(2, scope="session")
        assert c.value(scope="engine") == 1.0
        assert c.value(scope="session") == 2.0
        g = reg.gauge("backlog_depth", labels=("tenant",))
        g.set(5, tenant="a")
        g.add(-2, tenant="a")
        assert g.value(tenant="a") == 3.0
        h = reg.histogram("flush_latency_ms", buckets=(1.0, 10.0))
        for v in (0.5, 3.0, 99.0):
            h.observe(v)
        assert h.count() == 3
        assert h.sum() == pytest.approx(102.5)
        # one observation per band: <=1, <=10, +Inf
        assert h.samples[()]["counts"] == [1, 1, 1]

    def test_counters_are_monotone(self):
        c = MetricsRegistry().counter("n")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_label_schema_enforced(self):
        c = MetricsRegistry().counter("n", labels=("tenant",))
        with pytest.raises(ValueError):
            c.inc()                          # missing label
        with pytest.raises(ValueError):
            c.inc(tenant="a", lane="x")      # undeclared label

    def test_reregistration_returns_same_family(self):
        reg = MetricsRegistry()
        a = reg.counter("n", labels=("x",))
        assert reg.counter("n", labels=("x",)) is a
        with pytest.raises(ValueError):
            reg.gauge("n", labels=("x",))    # kind mismatch
        with pytest.raises(ValueError):
            reg.counter("n", labels=("y",))  # label-schema mismatch

    def test_disabled_registry_is_a_noop(self):
        reg = MetricsRegistry(enabled=False)
        c, g = reg.counter("c"), reg.gauge("g")
        h = reg.histogram("h")
        c.inc(), g.set(4.0), h.observe(1.0)
        assert c.value() == 0.0 and g.value() == 0.0 and h.count() == 0

    def test_prometheus_round_trip(self):
        """The bench's acceptance check, pinned as a unit: every sample
        (label escaping included) survives text exposition -> parse."""
        reg = MetricsRegistry()
        reg.counter("wal_records_total", "records",
                    labels=("type",)).inc(3, type='we"ird\\ten\nant')
        reg.gauge("lane_occupancy", labels=("lane",)).set(1, lane="7")
        h = reg.histogram("flush_latency_ms", "flush", buckets=(1.0, 5.0))
        h.observe(0.4), h.observe(4.0), h.observe(50.0)
        samples = parse_prometheus(reg.prometheus_text())
        got = {(n, tuple(sorted(lb.items()))): v for n, lb, v in samples}
        assert got[("wal_records_total",
                    (("type", 'we"ird\\ten\nant'),))] == 3.0
        assert got[("lane_occupancy", (("lane", "7"),))] == 1.0
        # histogram expands cumulatively with the implicit +Inf bucket
        assert got[("flush_latency_ms_bucket", (("le", "1.0"),))] == 1.0
        assert got[("flush_latency_ms_bucket", (("le", "5.0"),))] == 2.0
        assert got[("flush_latency_ms_bucket", (("le", "+Inf"),))] == 3.0
        assert got[("flush_latency_ms_count", ())] == 3.0
        assert got[("flush_latency_ms_sum", ())] == pytest.approx(54.4)

    def test_parser_is_strict(self):
        with pytest.raises(ValueError):
            parse_prometheus("this is not a sample !!\n")
        with pytest.raises(ValueError):
            parse_prometheus("name not_a_number\n")

    def test_snapshot_is_schema_v1(self):
        reg = MetricsRegistry()
        reg.counter("c", labels=("k",)).inc(k="v")
        reg.histogram("h", buckets=(1.0,)).observe(2.0)
        snap = reg.snapshot(validate=True)    # validate_record importable
        assert snap["schema_version"] == 1
        assert {r["metric"] for r in snap["rows"]} == \
            {"c", "h_sum", "h_count"}
        assert snap["extra"]["histograms"]["h"]["buckets"] == [1.0]

    def test_default_buckets_cover_latency_range(self):
        assert DEFAULT_MS_BUCKETS[0] <= 0.1
        assert DEFAULT_MS_BUCKETS[-1] >= 10000.0
        assert list(DEFAULT_MS_BUCKETS) == sorted(DEFAULT_MS_BUCKETS)


# ------------------------------------------------------------- SpanTracer
class TestSpanTracer:
    def test_nested_spans_and_args(self):
        tr = SpanTracer()
        with tr.span("engine.flush", cat="engine", scope="engine") as sp:
            with tr.span("scan.segment", cat="scan", width=4):
                pass
            sp.set(tuples=128)
        evs = tr.events()
        assert [e["name"] for e in evs] == ["scan.segment", "engine.flush"]
        flush = evs[1]
        assert flush["ph"] == "X" and flush["dur"] >= 1
        assert flush["args"] == {"scope": "engine", "tuples": 128}
        # containment: the child span lies inside the parent's window
        child = evs[0]
        assert flush["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= flush["ts"] + flush["dur"]

    def test_ring_cap_counts_drops(self):
        tr = SpanTracer(cap=4)
        for i in range(10):
            tr.instant(f"e{i}")
        assert len(tr.events()) == 4
        assert tr.dropped == 6
        assert tr.to_trace_events()["otherData"]["dropped_events"] == 6

    def test_disabled_records_nothing(self):
        tr = SpanTracer(enabled=False)
        with tr.span("x") as sp:
            sp.set(a=1)                       # the null span accepts set()
        tr.instant("y")
        assert tr.events() == [] and tr.dropped == 0

    def test_write_perfetto_json(self, tmp_path):
        tr = SpanTracer()
        with tr.span("engine.flush", n=np.int64(7)):   # numpy arg rides
            pass
        p = tmp_path / "trace.json"
        tr.write(p, process_name="unit")
        doc = json.loads(p.read_text())
        assert doc["displayTimeUnit"] == "ms"
        meta, ev = doc["traceEvents"]
        assert meta["ph"] == "M" and meta["args"]["name"] == "unit"
        assert ev["name"] == "engine.flush" and ev["args"]["n"] == 7


# ------------------------------------- compilemon contract + obs.region()
def _fresh_compile():
    """Force exactly one backend compile: a brand-new function object
    never hits the jit cache."""
    import jax
    jax.jit(lambda x: x * 2 + 1)(np.arange(17, dtype=np.int32))


class TestCompileAttribution:
    def test_overlapping_windows_both_count(self):
        """The pinned ``core.compilemon`` interleaving contract: the
        counters are process-global and carry no identity, so two
        snapshot/since windows overlapping one compile BOTH count it --
        summing overlapping deltas over-reports, by design."""
        compilemon.install()
        outer = compilemon.snapshot()
        inner = compilemon.snapshot()
        _fresh_compile()
        d_inner = compilemon.since(inner)
        d_outer = compilemon.since(outer)
        assert d_inner.n_compiles >= 1
        assert d_outer.n_compiles >= d_inner.n_compiles
        total = compilemon.since(outer).n_compiles
        assert d_outer.n_compiles + d_inner.n_compiles > total

    def test_region_exclusive_subtracts_children(self):
        """``obs.region()`` is the composition fix: nested scopes report
        an exclusive delta, so each compile is attributed once per
        nesting level."""
        with obs_lib.region("outer") as outer:
            with obs_lib.region("inner") as r:
                _fresh_compile()
        assert r.inclusive.n_compiles >= 1
        assert r.exclusive.n_compiles == r.inclusive.n_compiles
        assert outer.inclusive.n_compiles >= r.inclusive.n_compiles
        # everything inside `outer` happened inside `inner`
        assert outer.exclusive.n_compiles == \
            outer.inclusive.n_compiles - r.inclusive.n_compiles
        assert outer.exclusive.stall_ms == pytest.approx(
            outer.inclusive.stall_ms - r.inclusive.stall_ms, abs=1e-2)

    def test_region_siblings_partition(self):
        with obs_lib.region("parent") as parent:
            with obs_lib.region("a") as a:
                _fresh_compile()
            with obs_lib.region("b") as b:
                pass
        assert a.inclusive.n_compiles >= 1
        assert b.inclusive.n_compiles == 0
        assert parent.exclusive.n_compiles == (
            parent.inclusive.n_compiles
            - a.inclusive.n_compiles - b.inclusive.n_compiles)


class TestPersistentCache:
    """``compilemon.use_persistent_cache``: the entry points' one place
    for the compile cache.  ``jax.config.update`` is replaced by a
    recorder, so the suite's own cache stays off."""

    @pytest.fixture
    def updates(self, monkeypatch):
        import jax
        seen = []
        monkeypatch.setattr(jax.config, "update",
                            lambda name, value: seen.append((name, value)))
        return seen

    def test_env_directory_wins_and_nothing_else_is_set(self, monkeypatch,
                                                        updates, tmp_path):
        monkeypatch.setenv(compilemon.CACHE_ENV, str(tmp_path))
        assert compilemon.use_persistent_cache() == str(tmp_path)
        assert updates == []

    def test_fixed_ignored_directory_in_the_checkout(self, monkeypatch,
                                                     updates):
        from pathlib import Path
        monkeypatch.delenv(compilemon.CACHE_ENV, raising=False)
        repo = Path(__file__).resolve().parent.parent
        path = compilemon.use_persistent_cache()
        assert path == str(repo / ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", path)]
        ignored = (repo / ".gitignore").read_text().splitlines()
        assert ".jax_cache/" in ignored


# ---------------------------------------------------- Observability bundle
class TestObservabilityBundle:
    def test_resolve(self):
        shared = Observability()
        assert obs_lib.resolve(shared) is shared
        assert obs_lib.resolve(None).enabled
        assert not obs_lib.resolve(False).enabled
        assert obs_lib.resolve(True).enabled

    def test_enabled_flips_registry_and_tracer(self):
        o = Observability()
        o.enabled = False
        assert not o.registry.enabled and not o.tracer.enabled
        o.registry.counter("c").inc()
        with o.span("s"):
            pass
        assert o.registry.counter("c").value() == 0.0
        assert o.tracer.events() == []
        o.enabled = True
        assert o.registry.enabled and o.tracer.enabled


# ------------------------------------------------- engine instrumentation
class TestEngineObservability:
    def test_flush_metrics_and_spans(self, small_spec, zipf_dataset):
        obs = Observability()
        eng = _engine(small_spec, obs=obs)
        assert eng.obs is obs                 # shared bundle, not a copy
        sid = eng.open(tenant="a")
        data = zipf_dataset(2 * SMALL_CHUNK + 17, DOMAIN, 1.5)
        eng.append(sid, data)
        eng.query(sid, scope="engine")
        eng.query(sid, scope="session")
        merged, _ = eng.close(sid)
        np.testing.assert_array_equal(merged, _oracle(data[:, 0]))
        reg = obs.registry
        assert reg.get("sessions_opened_total").value() == 1.0
        assert reg.get("flushes_total").value(scope="engine") >= 1.0
        assert reg.get("flushes_total").value(scope="session") >= 1.0
        assert reg.get("queries_total").value(scope="engine") == 1.0
        assert reg.get("flush_latency_ms").count(scope="engine") >= 1
        # registry emission is derived from the same rows, so the
        # counter agrees with the telemetry lifetime totals exactly
        totals = eng.telemetry_record(validate=False)["extra"]["totals"]
        assert reg.get("tuples_flushed_total").value() == \
            totals["tuples_flushed"]
        names = obs.tracer.span_names()
        assert {"engine.flush", "engine.flush_session", "scan.segment",
                "scan.pack", "scan.h2d", "scan.run", "flush.stripe",
                "merge.snapshot"} <= names
        # appends are counted (appends_total), not timed
        assert "engine.append" not in names
        assert reg.get("appends_total").value() == 1.0

    def test_obs_off_is_bit_exact_and_silent(self, small_spec,
                                             zipf_dataset):
        data = zipf_dataset(3 * SMALL_CHUNK + 5, DOMAIN, 2.0)
        merged = {}
        for on in (True, False):
            obs = Observability(enabled=on)
            eng = _engine(small_spec, obs=obs)
            sid = eng.open(tenant="t")
            eng.append(sid, data)
            merged[on], _ = eng.close(sid)
            if not on:
                assert obs.tracer.events() == []
                assert all(not f.samples for f in obs.registry.families())
        np.testing.assert_array_equal(merged[True], merged[False])

    def test_storm_metrics(self, small_spec, zipf_dataset):
        obs = Observability()
        eng = _engine(small_spec, primary_slots=4, secondary_slots=0,
                      obs=obs)
        firsts = [zipf_dataset(SMALL_CHUNK + 9 * i, DOMAIN, 1.5,
                               seed=50 + i) for i in range(3)]
        eng.open_batch([f"s{i}" for i in range(3)], first=firsts)
        assert obs.registry.get("storms_total").value() == 1.0
        assert obs.registry.get("storm_admitted_total").value() == 3.0
        assert obs.registry.get("admit_latency_ms").count() == 1
        assert {"engine.admit_storm", "admit.lane_init"} <= \
            obs.tracer.span_names()

    def test_telemetry_ring_caps_and_reports_drops(self, small_spec,
                                                   zipf_dataset):
        eng = _engine(small_spec, telemetry_cap=4)
        sid = eng.open(tenant="a")
        for i in range(6):
            eng.append(sid, zipf_dataset(SMALL_CHUNK, DOMAIN, 1.5,
                                         seed=i))
            eng.query(sid, scope="engine")    # one flush row per round
        rec = eng.telemetry_record()
        tele = rec["extra"]["telemetry"]
        assert len(rec["rows"]) == 4 and tele["cap"] == 4
        assert tele["rows_total"] == 6 and tele["dropped_rows"] == 2
        assert eng.obs.registry.get(
            "telemetry_dropped_rows_total").value() == 2.0
        # the retained tail is the NEWEST rows, oldest dropped first:
        # 4 contiguous flush ids ending at the engine's latest
        ids = [r["flush"] for r in rec["rows"]]
        assert ids == list(range(ids[-1] - 3, ids[-1] + 1))

    def test_telemetry_cap_validation(self, small_spec):
        with pytest.raises(ValueError):
            _engine(small_spec, telemetry_cap=0)
        eng = _engine(small_spec, telemetry_cap=None)   # unbounded opt-out
        assert eng._telemetry.maxlen is None

    def test_validate_is_incremental(self, small_spec, zipf_dataset,
                                     monkeypatch):
        """The O(n^2) regression fix: repeated
        ``telemetry_record(validate=True)`` calls must validate each row
        ONCE, not re-validate the whole ring every call."""
        import benchmarks.common as common
        seen = []
        orig = common.validate_record

        def counting(rec):
            seen.append(len(rec.get("rows", ())))
            return orig(rec)

        monkeypatch.setattr(common, "validate_record", counting)
        eng = _engine(small_spec)
        sid = eng.open(tenant="a")

        def rounds(n, base):
            for i in range(n):
                eng.append(sid, zipf_dataset(SMALL_CHUNK, DOMAIN, 1.5,
                                             seed=base + i))
                eng.query(sid, scope="engine")

        rounds(3, 0)
        eng.telemetry_record(validate=True)
        rounds(3, 10)
        eng.telemetry_record(validate=True)
        eng.telemetry_record(validate=True)
        assert seen == [3, 3, 0]      # new rows only; third call validates 0
        # and the validated slice really is schema-clean end to end
        orig(eng.telemetry_record(validate=False))

    def test_flush_row_bit_compat(self, small_spec, zipf_dataset):
        """Existing telemetry columns survive the registry-backed
        emission path; the one NEW column is ``flush_ms``."""
        eng = _engine(small_spec)
        sid = eng.open(tenant="a")
        eng.append(sid, zipf_dataset(SMALL_CHUNK + 3, DOMAIN, 1.5))
        eng.flush()
        row = list(eng._telemetry)[-1]
        assert {"flush", "scope", "active_sessions", "queued_sessions",
                "tuples", "chunks", "lane_width", "sec_granted",
                "slot_reschedules", "backlog_tuples", "slot_occupancy",
                "n_retraces", "compile_stall_ms", "flush_ms"} <= set(row)
        assert row["flush_ms"] is None or row["flush_ms"] >= 0.0


# ------------------------------------------------- scan step timing
_STEPS = ("scan.pack", "scan.h2d", "scan.run")


def _profiled_flush(eng, sid, trace_dir):
    """One forced engine-wide flush under the JAX profiler; returns the
    host plane's events per thread line, as ``(name, start, end)``."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(trace_dir))
    try:
        eng.flush(force=(sid,))
    finally:
        jax.profiler.stop_trace()
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    return [[(e.name, e.start_ns, e.end_ns) for e in ln.events]
            for pl in pd.planes if pl.name.startswith("/host:")
            for ln in pl.lines]


class TestScanStepTiming:
    @pytest.mark.parametrize("on", [True, False])
    def test_spans_nest_in_the_profiler(self, small_spec, zipf_dataset,
                                        tmp_path, on):
        """With the tracer on, ``engine.flush`` > ``scan.segment`` >
        ``scan.pack``, ``scan.h2d``, ``scan.run`` land in the profiler's
        host plane, nested and in order on one thread; with it off, no
        span is annotated."""
        eng = _engine(small_spec, aot_buckets=1, secondary_slots=0,
                      obs=Observability(enabled=on))
        sid = eng.open(tenant="a")
        eng.append(sid, zipf_dataset(2 * SMALL_CHUNK + 5, DOMAIN, 1.5))
        lines = _profiled_flush(eng, sid, tmp_path)
        ours = {"engine.flush", "scan.segment", "flush.stripe", *_STEPS}
        found = [[e for e in ln if e[0] in ours] for ln in lines]
        found = [ln for ln in found if ln]
        if not on:
            assert found == []
            return
        assert len(found) == 1                      # one thread
        evs = sorted(found[0], key=lambda e: (e[1], -e[2]))
        flush = [e for e in evs if e[0] == "engine.flush"]
        segs = [e for e in evs if e[0] == "scan.segment"]
        assert len(flush) == 1 and len(segs) == 3   # 3 chunks, width 1
        f0, f1 = flush[0][1:]
        for s0, s1 in (e[1:] for e in segs):
            assert f0 <= s0 and s1 <= f1
            steps = [e for e in evs
                     if e[0] in _STEPS and s0 <= e[1] and e[2] <= s1]
            assert [e[0] for e in steps] == list(_STEPS)
            assert all(a[2] <= b[1] for a, b in zip(steps, steps[1:]))

    @pytest.mark.parametrize("on", [True, False])
    def test_step_columns_in_both_scopes(self, small_spec, zipf_dataset,
                                         on):
        """Every flush row carries its scan steps' times and segment
        count, tracing on or off; engine rows count the forced
        sessions."""
        eng = _engine(small_spec, aot_buckets=1, primary_slots=3,
                      obs=Observability(enabled=on))
        sids = [eng.open(tenant=t) for t in "abc"]
        for i, sid in enumerate(sids):
            eng.append(sid, zipf_dataset((2 + i) * SMALL_CHUNK + 7, DOMAIN,
                                         1.5, seed=i))
        eng.flush(force=sids[:2])
        eng.append(sids[2], zipf_dataset(3 * SMALL_CHUNK, DOMAIN, 0.8))
        eng.query(sids[2], scope="session")
        rows = list(eng._telemetry)
        assert [r["scope"] for r in rows] == ["engine", "session"]
        assert rows[0]["forced_sessions"] == 2
        assert "forced_sessions" not in rows[1]
        for r in rows:
            steps = [r["pack_ms"], r["h2d_ms"], r["run_ms"]]
            assert min(steps) >= 0.0
            # aot_buckets=1: one segment per chunk of the widest lane
            assert r["segments"] == r["lane_width"] >= 2
            # each column is rounded to 1 us
            assert sum(steps) <= r["flush_ms"] + 0.002

    def test_storm_row_counts_its_segments(self, small_spec, zipf_dataset):
        eng = _engine(small_spec, aot_buckets=1, primary_slots=4,
                      secondary_slots=0)
        eng.open_batch(["a", "b"], first=[
            zipf_dataset(2 * SMALL_CHUNK, DOMAIN, 1.5, seed=1),
            zipf_dataset(SMALL_CHUNK + 3, DOMAIN, 1.5, seed=2)])
        row = list(eng._telemetry)[-1]
        assert row["scope"] == "admit"
        assert row["segments"] == row["n_scan_dispatches"] == 2
        assert min(row["pack_ms"], row["h2d_ms"], row["run_ms"]) >= 0.0

    def test_every_segment_gets_its_span(self, small_spec, zipf_dataset):
        """A flush of 20 one-chunk segments emits 20 ``scan.segment``
        spans, each with its three steps; no aggregate span."""
        obs = Observability()
        eng = _engine(small_spec, aot_buckets=1, secondary_slots=0,
                      obs=obs)
        sid = eng.open(tenant="a")
        eng.append(sid, zipf_dataset(20 * SMALL_CHUNK, DOMAIN, 1.5))
        obs.tracer.clear()
        eng.flush()
        names = [e["name"] for e in obs.tracer.events()]
        assert names.count("scan.segment") == 20
        for step in _STEPS:
            assert names.count(step) == 20
        assert "scan.segments" not in names
        assert list(eng._telemetry)[-1]["segments"] == 20

    @pytest.mark.parametrize("mesh", [False, True], ids=["local", "mesh1"])
    @pytest.mark.parametrize("aot", [None, 1])
    def test_explicit_copy_is_bit_exact(self, small_spec, zipf_dataset,
                                        mesh, aot):
        """Answers through every flush path (storm, engine-wide,
        per-session) equal the oracle with the batch copied to the
        device before the scan, local and on a mesh of one."""
        import jax
        m = jax.make_mesh((1,), ("lanes",)) if mesh else None
        eng = _engine(small_spec, aot_buckets=aot, primary_slots=3,
                      mesh=m)
        data = [zipf_dataset(n, DOMAIN, a, seed=k) for k, (n, a) in
                enumerate([(3 * SMALL_CHUNK + 11, 2.0),
                           (SMALL_CHUNK + 1, 0.8), (2 * SMALL_CHUNK, 1.5)])]
        sids = eng.open_batch(["a", "b", "c"], first=data)
        more = zipf_dataset(2 * SMALL_CHUNK + 9, DOMAIN, 2.0, seed=7)
        eng.append(sids[0], more)
        eng.flush(force=sids[:2])
        np.testing.assert_array_equal(
            np.asarray(eng.query(sids[0], scope="session")),
            _oracle(np.concatenate([data[0][:, 0], more[:, 0]])))
        for sid, d in zip(sids[1:], data[1:]):
            merged, _ = eng.close(sid)
            np.testing.assert_array_equal(merged, _oracle(d[:, 0]))


# ------------------------------------------------- recovery observability
class TestRecoveryObservability:
    def test_recovery_counters_and_spans(self, small_spec, zipf_dataset,
                                         tmp_path):
        data = zipf_dataset(2 * SMALL_CHUNK + 31, DOMAIN, 1.5)
        tail = zipf_dataset(SMALL_CHUNK + 7, DOMAIN, 1.5, seed=9)
        eng = DurableSessionEngine(
            small_spec, directory=tmp_path, num_pri=SMALL_M, num_sec=2,
            chunk_size=SMALL_CHUNK, primary_slots=2, secondary_slots=1,
            checkpoint_every=0)
        sid = eng.open(tenant="a")
        eng.append(sid, data)
        eng.flush()
        eng.checkpoint(block=True)
        assert eng.obs.registry.get("checkpoints_total").value() == 1.0
        assert eng.obs.registry.get("checkpoint_save_ms").count() == 1
        assert "ckpt.save" in eng.obs.tracer.span_names()
        eng.append(sid, tail)      # WAL tail only -- replayed on recovery
        eng._mgr.wait()
        # crash: abandon the engine object, then recover with a fresh
        # bundle wired through the recover() overrides
        obs2 = Observability()
        eng2 = SessionEngine.recover(small_spec, tmp_path, obs=obs2)
        assert eng2.obs is obs2
        info = eng2.recovery_info
        assert info["replayed_records"] >= 1
        reg2 = obs2.registry
        assert reg2.get("recovery_replay_records_total").value() == \
            info["replayed_records"]
        assert reg2.get("recovery_replay_tuples_total").value() == \
            info["replayed_tuples"]
        assert {"recover", "ckpt.restore", "recover.replay"} <= \
            obs2.tracer.span_names()
        sid2 = {s.tenant: i for i, s in eng2.sessions.items()
                if not s.closed}["a"]
        np.testing.assert_array_equal(
            np.asarray(eng2.query(sid2, scope="session")),
            _oracle(np.concatenate([data[:, 0], tail[:, 0]])))
        eng2.shutdown()

    def test_wal_metrics(self, tmp_path):
        from repro.serve import WriteAheadLog
        obs = Observability()
        wal = WriteAheadLog(tmp_path, sync=True, obs=obs)
        wal.log("a", {"t": "open", "sid": 0, "tenant": "a"})
        wal.log("a", {"t": "app", "sid": 0},
                np.arange(8, dtype=np.int32).tobytes())
        wal.close()
        reg = obs.registry
        assert reg.get("wal_records_total").value(type="open") == 1.0
        assert reg.get("wal_records_total").value(type="app") == 1.0
        assert reg.get("wal_bytes_total").value() > 0
        assert reg.get("wal_append_ms").count() == 2
        assert reg.get("wal_fsync_ms").count() == 2   # sync=True
        assert "wal.append" in obs.tracer.span_names()
