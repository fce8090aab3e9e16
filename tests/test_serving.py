"""Serving-layer suite (DESIGN.md §8, §9): StreamEngine batch formation /
padding isolation, SessionEngine bit-exactness vs the one-shot executor
(uniform + Zipf 1.5, ragged appends), the tenant-level skew scheduler's
slot-allocation properties, the per-session flush tier, and the engine
on an explicit one-device mesh, against the oracles (multi-device runs
live in tests/test_distributed.py)."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:         # benchmarks/ is a repo-root package
    sys.path.insert(0, str(REPO))

from repro.apps import histo, hll
from repro.core import make_executor
from repro.data.pipeline import chunk_stream
from repro.serve import SessionEngine, StreamEngine

from tests.conftest import SMALL_CHUNK, SMALL_M

BINS, DOMAIN = 64, 1 << 16


def _oracle(keys: np.ndarray) -> np.ndarray:
    return histo.oracle(np.asarray(keys), BINS, DOMAIN, SMALL_M)


HLL_P = 8            # 256 registers, 32 per PE at SMALL_M


def _hll_oracle(keys: np.ndarray) -> np.ndarray:
    return hll.oracle(np.asarray(keys), HLL_P, SMALL_M)


class _App:
    """One app under the app-agnostic bit-exact tests: its spec and its
    numpy oracle over the key column."""

    def __init__(self, spec, oracle):
        self.spec, self.oracle = spec, oracle


@pytest.fixture(scope="module", params=["histo", "hll"])
def app(request, small_spec):
    """HISTO (``add`` combine) and HLL (``max`` combine) in turn."""
    if request.param == "histo":
        return _App(small_spec, _oracle)
    return _App(hll.make_spec(HLL_P, SMALL_M), _hll_oracle)


def _solo(spec, data: np.ndarray) -> np.ndarray:
    """One-shot executor on the identical tuple stream (masked tail)."""
    ts = chunk_stream(np.asarray(data), SMALL_CHUNK, pad_tail=True)
    run = make_executor(spec, SMALL_M, 2, SMALL_CHUNK)
    merged, _ = run(jnp.asarray(ts.body), mask=jnp.asarray(ts.mask))
    return np.asarray(merged)


# ----------------------------------------------------------- StreamEngine
class TestStreamEngine:
    def _engine(self, small_spec, **kw):
        kw.setdefault("max_streams", 4)
        return StreamEngine(small_spec, num_pri=SMALL_M, num_sec=2,
                            chunk_size=SMALL_CHUNK, **kw)

    def test_mixed_chunk_counts_no_hol_blocking(self, small_spec,
                                                zipf_dataset):
        """A long stream at the head must not force short streams behind
        it into their own tiny batches: the largest compatible group is
        picked first, and every result stays exact."""
        eng = self._engine(small_spec)
        long = zipf_dataset(4 * SMALL_CHUNK, DOMAIN, 1.5, seed=1)
        shorts = [zipf_dataset(SMALL_CHUNK, DOMAIN, a, seed=2 + i)
                  for i, a in enumerate((0.0, 1.0, 2.0))]
        rid_long = eng.submit(long)
        rid_short = [eng.submit(s) for s in shorts]
        # largest group (the three 1-chunk streams) batches before the head
        batch = eng._next_batch()
        assert {r.rid for r in batch} == set(rid_short)
        assert [r.rid for r in eng.pending] == [rid_long]
        eng.pending = batch + eng.pending          # restore, then run all
        out = eng.flush()
        assert not eng.pending
        np.testing.assert_array_equal(out[rid_long][0], _oracle(long[:, 0]))
        for rid, s in zip(rid_short, shorts):
            np.testing.assert_array_equal(out[rid][0], _oracle(s[:, 0]))

    def test_pad_lane_isolation(self, small_spec, zipf_dataset):
        """A partially filled batch pads with masked zero lanes; the lone
        tenant's result must equal running alone (nothing replayed, no
        cross-lane effects)."""
        data = zipf_dataset(2 * SMALL_CHUNK, DOMAIN, 2.0)
        eng = self._engine(small_spec)
        rid = eng.submit(data)
        merged, stats = eng.flush()[rid]
        np.testing.assert_array_equal(merged, _oracle(data[:, 0]))
        np.testing.assert_array_equal(merged, _solo(small_spec, data))
        # per-request stats are the tenant's own (2 chunks scanned)
        assert stats.modeled_cycles.shape == (2,)

    def test_ragged_submit(self, small_spec, zipf_dataset):
        """Stream lengths no longer need to be chunk multiples: the tail
        rides the pipeline's masked-chunk path end-to-end."""
        data = zipf_dataset(SMALL_CHUNK + 123, DOMAIN, 1.5)
        eng = self._engine(small_spec)
        rid = eng.submit(data)
        merged, _ = eng.flush()[rid]
        np.testing.assert_array_equal(merged, _oracle(data[:, 0]))

    def test_flush_order_independence(self, small_spec, zipf_dataset):
        """Submission order never changes any tenant's result."""
        datasets = [zipf_dataset(SMALL_CHUNK * (1 + i % 2), DOMAIN,
                                 0.5 * i, seed=10 + i) for i in range(5)]
        for order in (range(5), reversed(range(5))):
            eng = self._engine(small_spec)
            rids = {i: eng.submit(datasets[i]) for i in order}
            out = eng.flush()
            for i, rid in rids.items():
                np.testing.assert_array_equal(
                    out[rid][0], _oracle(datasets[i][:, 0]))


# ---------------------------------------------------------- SessionEngine
def _session_engine(spec, **kw):
    kw.setdefault("primary_slots", 2)
    kw.setdefault("secondary_slots", 2)
    return SessionEngine(spec, num_pri=SMALL_M, num_sec=2,
                         chunk_size=SMALL_CHUNK, **kw)


class TestSessionEngine:
    @pytest.mark.parametrize("alpha", [0.0, 1.5])
    @pytest.mark.parametrize("ragged", [False, True])
    def test_bit_exact_vs_one_shot(self, app, zipf_dataset, alpha,
                                   ragged):
        """Acceptance: SessionEngine == one-shot executor on the same
        tuples, for any append chunking, with and without ragged tails."""
        n = 6 * SMALL_CHUNK + (137 if ragged else 0)
        data = zipf_dataset(n, DOMAIN, alpha)
        eng = _session_engine(app.spec)
        sid = eng.open()
        rng = np.random.default_rng(0)
        i = 0
        while i < n:                     # arbitrary-length appends
            step = int(rng.integers(1, SMALL_CHUNK + 200))
            eng.append(sid, data[i:i + step])
            i += step
            if rng.random() < 0.5:
                eng.flush()
        merged, _ = eng.close(sid)
        np.testing.assert_array_equal(merged, _solo(app.spec, data))
        np.testing.assert_array_equal(merged, app.oracle(data[:, 0]))

    def test_ragged_append_equivalence(self, app, zipf_dataset):
        """Any partition of the same stream into appends yields identical
        merged buffers (mid-stream queries included)."""
        data = zipf_dataset(3 * SMALL_CHUNK + 41, DOMAIN, 1.5)
        results = []
        for cuts in ([len(data)], [100, 1, 333, len(data) - 434],
                     [SMALL_CHUNK] * 3 + [41]):
            eng = _session_engine(app.spec)
            sid = eng.open()
            i = 0
            for c in cuts:
                eng.append(sid, data[i:i + c])
                i += c
            assert i == len(data)
            snap = eng.query(sid)        # mid-stream snapshot is complete
            np.testing.assert_array_equal(snap, app.oracle(data[:, 0]))
            merged, _ = eng.close(sid)
            results.append(merged)
        for r in results[1:]:
            np.testing.assert_array_equal(r, results[0])

    def test_query_is_non_destructive(self, small_spec, zipf_dataset):
        """The stream continues after a query; the final result covers
        everything ever appended exactly once."""
        a = zipf_dataset(2 * SMALL_CHUNK + 7, DOMAIN, 1.5, seed=1)
        b = zipf_dataset(SMALL_CHUNK + 99, DOMAIN, 0.0, seed=2)
        eng = _session_engine(small_spec)
        sid = eng.open()
        eng.append(sid, a)
        np.testing.assert_array_equal(eng.query(sid), _oracle(a[:, 0]))
        np.testing.assert_array_equal(eng.query(sid), _oracle(a[:, 0]))
        eng.append(sid, b)
        merged, _ = eng.close(sid)
        np.testing.assert_array_equal(
            merged, _oracle(np.concatenate([a[:, 0], b[:, 0]])))

    def test_tenant_isolation_and_slot_recycling(self, small_spec,
                                                 zipf_dataset):
        """More sessions than primary slots: queued sessions admit as
        slots free, and every tenant's result is its own."""
        datasets = {t: zipf_dataset(SMALL_CHUNK * 2 + 13 * t, DOMAIN,
                                    0.7 * t, seed=t) for t in range(4)}
        eng = _session_engine(small_spec, primary_slots=2)
        sids = {t: eng.open(tenant=f"t{t}") for t in range(4)}
        assert sum(eng.sessions[s].slot is not None
                   for s in sids.values()) == 2
        for t in range(4):
            eng.append(sids[t], datasets[t])
        for t in range(4):               # closing frees slots -> admits
            merged, _ = eng.close(sids[t])
            np.testing.assert_array_equal(merged,
                                          _oracle(datasets[t][:, 0]))

    def test_queued_session_never_answers_empty(self, small_spec,
                                                zipf_dataset):
        """A session waiting for a slot must error on query (nothing has
        run) and refuse to close while holding data -- never silently
        return empty buffers or drop tuples."""
        eng = _session_engine(small_spec, primary_slots=1)
        a, b = eng.open(), eng.open()
        data = zipf_dataset(500, DOMAIN, 1.5)
        eng.append(b, data)
        with pytest.raises(RuntimeError, match="queued"):
            eng.query(b)
        with pytest.raises(RuntimeError, match="refusing to discard"):
            eng.close(b)
        eng.close(a)                     # frees the slot -> b admitted
        merged, _ = eng.close(b)
        np.testing.assert_array_equal(merged, _oracle(data[:, 0]))
        # an EMPTY queued session may close gracefully
        eng2 = _session_engine(small_spec, primary_slots=1)
        eng2.open()
        sid = eng2.open()
        merged, stats = eng2.close(sid)
        assert merged.sum() == 0 and stats["tuples_appended"] == 0

    def test_padding_chunks_leave_carry_untouched(self, small_spec,
                                                  zipf_dataset):
        """Batch-width padding (fully masked chunks) must not advance the
        profiling window, fire the mode machine, or inflate load stats --
        a padded chunk is bit-identical to an absent one."""
        from repro.core import make_resumable_executor
        res = make_resumable_executor(small_spec, SMALL_M, 2, SMALL_CHUNK,
                                      profile_chunks=2)
        state = res.init_state()
        dead = jnp.zeros((3, SMALL_CHUNK, 2), jnp.int32)
        state, stats = res.run_chunks(
            state, dead, jnp.zeros((3, SMALL_CHUNK), bool))
        assert int(state.chunks_in_mode) == 0       # still pre-profile
        assert int(state.mode) == 0                 # PROFILE
        assert np.asarray(stats.max_load).max() == 0  # sentinel dropped
        # a real ragged tail reports only its live tuples as load
        data = zipf_dataset(SMALL_CHUNK + 57, DOMAIN, 0.0)
        ts = chunk_stream(data, SMALL_CHUNK, pad_tail=True)
        state, stats = res.run_chunks(state, jnp.asarray(ts.body),
                                      jnp.asarray(ts.mask))
        assert int(np.asarray(stats.max_load)[-1]) <= 57
        np.testing.assert_array_equal(res.merge_state(state),
                                      _oracle(data[:, 0]))

    def test_closed_session_rejects_use(self, small_spec, zipf_dataset):
        eng = _session_engine(small_spec)
        sid = eng.open()
        eng.append(sid, zipf_dataset(64, DOMAIN, 0.0))
        eng.close(sid)
        # closed and never-opened sids both get a descriptive ValueError
        # (naming the sid and the engine state), not a bare KeyError
        with pytest.raises(ValueError, match=f"session {sid}.*closed"):
            eng.append(sid, zipf_dataset(64, DOMAIN, 0.0))
        with pytest.raises(ValueError,
                           match=f"unknown session id {sid + 999}"):
            eng.query(sid + 999)
        with pytest.raises(ValueError, match="unknown session id"):
            eng.close(sid + 999)
        with pytest.raises(ValueError, match="open\\(\\)/open_batch\\(\\)"):
            eng.append(sid + 999, zipf_dataset(4, DOMAIN, 0.0))

    def test_tuned_plan_config(self, small_spec, zipf_dataset):
        """tuned=TunedPlan resolves the engine shape through the core's
        single resolution path (and conflicting num_pri is rejected)."""
        from repro.tune import SearchSpace, autotune
        sample = zipf_dataset(4096, DOMAIN, 1.5)
        tuned = autotune(small_spec, sample,
                         space=SearchSpace(m_candidates=(SMALL_M,),
                                           chunk_sizes=(SMALL_CHUNK,)),
                         tolerance=0.1)
        eng = SessionEngine(small_spec, tuned=tuned, primary_slots=2,
                            secondary_slots=1)
        assert (eng.num_pri, eng.num_sec, eng.chunk_size) == \
            (SMALL_M, tuned.num_sec, SMALL_CHUNK)
        sid = eng.open()
        eng.append(sid, sample)
        merged, _ = eng.close(sid)
        np.testing.assert_array_equal(merged, _oracle(sample[:, 0]))
        with pytest.raises(ValueError, match="conflicts"):
            SessionEngine(small_spec, tuned=tuned, num_pri=SMALL_M + 1)

    def test_telemetry_record_schema(self, small_spec, zipf_dataset):
        """Per-flush telemetry validates against the benchmark schema and
        counts what actually ran."""
        from benchmarks.common import validate_record
        eng = _session_engine(small_spec)
        sid = eng.open()
        eng.append(sid, zipf_dataset(3 * SMALL_CHUNK, DOMAIN, 1.5))
        eng.flush()
        eng.close(sid)
        rec = validate_record(eng.telemetry_record())
        assert rec["rows"] and rec["rows"][0]["tuples"] == 3 * SMALL_CHUNK
        assert rec["extra"]["totals"]["sessions_opened"] == 1


# --------------------------------------- per-session flush (latency tier)
class TestPerSessionFlush:
    def test_query_scopes_identical_results(self, app, zipf_dataset):
        """Acceptance: the per-session flush tier returns results
        identical to the engine-wide flush, for every tenant, with
        pending backlog on BOTH."""
        datasets = {t: zipf_dataset(2 * SMALL_CHUNK + 31 * t, DOMAIN,
                                    0.7 * t, seed=t) for t in range(2)}
        snaps = {}
        for scope in ("session", "engine"):
            eng = _session_engine(app.spec)
            sids = {t: eng.open() for t in datasets}
            for t, d in datasets.items():
                eng.append(sids[t], d)
            snaps[scope] = {t: eng.query(sids[t], scope=scope)
                            for t in datasets}
        for t, d in datasets.items():
            np.testing.assert_array_equal(snaps["session"][t],
                                          snaps["engine"][t])
            np.testing.assert_array_equal(snaps["session"][t],
                                          app.oracle(d[:, 0]))

    def test_session_flush_leaves_other_backlogs(self, small_spec,
                                                 zipf_dataset):
        """flush_session touches ONLY the target session: the other
        tenant's backlog stays buffered (that is the p99 win), and its
        eventual answer is still exact."""
        a, b = zipf_dataset(2 * SMALL_CHUNK, DOMAIN, 1.5, seed=1), \
            zipf_dataset(3 * SMALL_CHUNK + 17, DOMAIN, 0.0, seed=2)
        eng = _session_engine(small_spec)
        sa, sb = eng.open(), eng.open()
        eng.append(sa, a)
        eng.append(sb, b)
        eng.flush_session(sa)
        assert eng.sessions[sb].backlog_tuples == len(b)   # untouched
        assert eng.sessions[sa].backlog_tuples == 0
        np.testing.assert_array_equal(eng.query(sa), _oracle(a[:, 0]))
        np.testing.assert_array_equal(eng.query(sb), _oracle(b[:, 0]))

    def test_session_flush_uses_granted_lanes(self, small_spec,
                                              zipf_dataset):
        """A hot session's per-session flush stripes across its granted
        secondary lanes (the scan shortens) and stays exact across
        engine-wide flushes that may re-grant."""
        eng = _session_engine(small_spec, primary_slots=2,
                              secondary_slots=2)
        hot, cold = eng.open(), eng.open()
        d_hot = zipf_dataset(6 * SMALL_CHUNK + 13, DOMAIN, 1.5, seed=3)
        eng.append(hot, d_hot)
        eng.flush()                      # grants secondaries to hot
        assert eng._lane_group(eng.sessions[hot].slot) != \
            [eng.sessions[hot].slot]
        more = zipf_dataset(4 * SMALL_CHUNK + 7, DOMAIN, 1.5, seed=4)
        eng.append(hot, more)
        np.testing.assert_array_equal(
            eng.query(hot),
            _oracle(np.concatenate([d_hot[:, 0], more[:, 0]])))
        assert eng.sessions[hot].stats.sec_lane_flushes > 0
        merged, _ = eng.close(hot)
        np.testing.assert_array_equal(
            merged, _oracle(np.concatenate([d_hot[:, 0], more[:, 0]])))
        eng.close(cold)

    def test_queued_session_flush_raises(self, small_spec, zipf_dataset):
        eng = _session_engine(small_spec, primary_slots=1)
        admitted = eng.open()
        queued = eng.open()
        with pytest.raises(RuntimeError, match="queued"):
            eng.flush_session(queued)
        with pytest.raises(ValueError, match="scope"):
            eng.query(admitted, scope="bogus")

    def test_telemetry_rows_tag_scope(self, small_spec, zipf_dataset):
        eng = _session_engine(small_spec)
        sid = eng.open()
        eng.append(sid, zipf_dataset(2 * SMALL_CHUNK, DOMAIN, 1.5))
        eng.flush()
        eng.query(sid)
        rows = eng.telemetry_record()["rows"]
        assert rows[0]["scope"] == "engine"
        assert rows[-1]["scope"] == "session"


# ----------------------------------- the engine on a one-device lanes mesh
def _drive_scenario(eng, datasets, rng_seed=0):
    """Ragged appends + interleaved flush/query/close; returns every
    answer keyed by name, for bit-exact engine comparisons."""
    rng = np.random.default_rng(rng_seed)
    sids = {t: eng.open(tenant=f"t{t}") for t in datasets}
    answers = {}
    for t, data in datasets.items():
        i = 0
        while i < len(data):
            step = int(rng.integers(1, SMALL_CHUNK + 99))
            eng.append(sids[t], data[i:i + step])
            i += step
            if rng.random() < 0.3:
                eng.flush()
    eng.flush()
    for t in datasets:
        answers[f"q{t}"] = eng.query(sids[t])
    for t in datasets:
        merged, stats = eng.close(sids[t])
        answers[f"c{t}"] = merged
    return answers


class TestSessionEngineMesh1:
    """Acceptance: the engine on a caller's one-device ``lanes`` mesh
    (``jax.make_mesh`` gives it Explicit axes) answers every query and
    close exactly as the app's oracle does."""

    def _mesh(self):
        return jax.make_mesh((1,), ("lanes",))

    def test_scenario_bit_exact_vs_unsharded(self, app,
                                             zipf_dataset):
        datasets = {t: zipf_dataset(3 * SMALL_CHUNK + 41 * t, DOMAIN,
                                    (0.0, 1.5)[t % 2], seed=t)
                    for t in range(3)}
        got = _drive_scenario(
            _session_engine(app.spec, primary_slots=3,
                            mesh=self._mesh()), datasets)
        assert sorted(got) == sorted(f"{k}{t}" for k in "qc"
                                     for t in datasets)
        for t, d in datasets.items():      # every append precedes them
            for k in "qc":
                np.testing.assert_array_equal(np.asarray(got[f"{k}{t}"]),
                                              app.oracle(d[:, 0]))

    def test_regrant_folds_bit_exact(self, app, zipf_dataset):
        """Alternating hot tenants force secondary re-grants (the
        collective §IV-B fold path) on the meshed engine; every close
        equals the oracle over its tenant's appends."""
        eng = _session_engine(app.spec, mesh=self._mesh())
        d = {t: np.zeros((0, 2), np.int32) for t in range(2)}
        sids = {t: eng.open() for t in range(2)}
        for r in range(5):
            hot = r % 2
            for t in range(2):
                n = (5 if t == hot else 1) * SMALL_CHUNK + 7 * r
                batch = zipf_dataset(n, DOMAIN, 1.5, seed=10 * r + t)
                d[t] = np.concatenate([d[t], batch])
                eng.append(sids[t], batch)
            eng.flush()
        assert eng._slot_reschedules > 0
        for t in range(2):
            np.testing.assert_array_equal(
                np.asarray(eng.close(sids[t])[0]), app.oracle(d[t][:, 0]))

    def test_mesh_validation(self, small_spec):
        with pytest.raises(ValueError, match="axis"):
            _session_engine(small_spec,
                            mesh=jax.make_mesh((1,), ("pe",)))
        eng = _session_engine(small_spec, mesh=self._mesh())
        assert eng.lanes_per_device == eng.num_lanes
        rec = eng.telemetry_record()
        assert rec["extra"]["config"]["mesh_devices"] == 1


# ------------------------------------------- tenant-level skew scheduling
class TestTenantSkewScheduling:
    def test_hot_session_takes_all_lanes(self, small_spec):
        eng = _session_engine(small_spec, primary_slots=3,
                              secondary_slots=2)
        a = eng.plan_secondary(np.array([40.0, 2.0, 2.0], np.float32))
        assert a.tolist() == [0, 0]      # greedy max-backlog splitting

    def test_uniform_backlog_spreads_lanes(self, small_spec):
        eng = _session_engine(small_spec, primary_slots=4,
                              secondary_slots=3)
        a = eng.plan_secondary(np.full(4, 10.0, np.float32))
        assert len(set(a.tolist())) == 3     # three different slots helped

    def test_small_backlog_gets_no_helper(self, small_spec):
        eng = _session_engine(small_spec, primary_slots=2,
                              secondary_slots=2, min_grant_chunks=2)
        a = eng.plan_secondary(np.array([1.0, 0.0], np.float32))
        assert a.tolist() == [-1, -1]    # 1 chunk cannot be split

    def test_slot_allocation_property(self, small_spec):
        """Fig. 5 property, lifted: the hottest session's post-grant
        share never exceeds the no-grant maximum, and grants only go to
        sessions at/above min_grant_chunks."""
        rng = np.random.default_rng(3)
        eng = _session_engine(small_spec, primary_slots=6,
                              secondary_slots=4)
        for _ in range(20):
            backlog = rng.integers(0, 50, size=6).astype(np.float32)
            a = eng.plan_secondary(backlog)
            granted = a[a >= 0]
            assert all(backlog[g] >= eng.min_grant_chunks for g in granted)
            shares = np.ones(6)
            np.add.at(shares, granted, 1.0)
            if backlog.max() >= eng.min_grant_chunks:
                assert (backlog / shares).max() <= backlog.max() + 1e-6

    def test_regrants_keep_exactness(self, app, zipf_dataset):
        """Secondary lanes migrate between tenants across flushes (the
        lifted merge-before-reassign); results stay exact for both."""
        eng = _session_engine(app.spec, primary_slots=2,
                              secondary_slots=2)
        d = {t: np.zeros((0, 2), np.int32) for t in range(2)}
        sids = {t: eng.open() for t in range(2)}
        rng = np.random.default_rng(9)
        for r in range(6):               # alternate who is hot
            hot = r % 2
            for t in range(2):
                n = (6 if t == hot else 1) * SMALL_CHUNK \
                    + int(rng.integers(0, 50))
                batch = zipf_dataset(n, DOMAIN, 1.5, seed=10 * r + t)
                d[t] = np.concatenate([d[t], batch])
                eng.append(sids[t], batch)
            eng.flush()
        assert eng._slot_reschedules > 0     # grants really moved
        for t in range(2):
            merged, stats = eng.close(sids[t])
            np.testing.assert_array_equal(merged, app.oracle(d[t][:, 0]))
            if t == 0:
                assert stats["sec_lane_flushes"] > 0

    def test_non_decomposable_rejects_secondary(self):
        from repro.apps import dp
        spec = dp.make_spec(3, SMALL_M, capacity_per_pe=1024)
        with pytest.raises(ValueError, match="secondary_slots=0"):
            _session_engine(spec, secondary_slots=1)


# --------------------------------------------------- AOT bucketed flush
class TestAOTBuckets:
    """DESIGN.md §8 AOT shape buckets: a bucketed engine must answer
    bit-exactly like the plain-jit engine in every mode, and a warmed
    engine must never retrace on the flush path -- however ragged the
    appends, and across bucket (width and lane-group) boundaries."""

    def _datasets(self, zipf_dataset, n=3):
        # sizes straddle the width-2 segment boundary on purpose:
        # 1..5-chunk backlogs, ragged tails, mixed skew
        return {t: zipf_dataset((2 + t) * SMALL_CHUNK + 41 * t + 7, DOMAIN,
                                (0.0, 1.5)[t % 2], seed=t)
                for t in range(n)}

    def test_bit_exact_vs_unbucketed_local(self, app, zipf_dataset):
        """Acceptance: same ragged multi-tenant scenario through the
        plain-jit and the aot_buckets=2 engine (width chopping active:
        backlogs run to 5+ chunks) -- every query/close answer
        identical, and exact vs the oracle."""
        datasets = self._datasets(zipf_dataset)
        want = _drive_scenario(
            _session_engine(app.spec, primary_slots=3), datasets)
        got = _drive_scenario(
            _session_engine(app.spec, primary_slots=3, aot_buckets=2),
            datasets)
        assert got.keys() == want.keys()
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))
        for t, d in datasets.items():
            np.testing.assert_array_equal(np.asarray(got[f"c{t}"]),
                                          app.oracle(d[:, 0]))

    def test_bit_exact_vs_unbucketed_mesh_of_1(self, app,
                                               zipf_dataset):
        """Acceptance: the bucketed engine on a caller's one-device mesh
        (warmup lowers the lane table's scan) answers every query and
        close exactly as the oracle does."""
        datasets = self._datasets(zipf_dataset, n=2)
        got = _drive_scenario(
            _session_engine(app.spec, aot_buckets=4,
                            mesh=jax.make_mesh((1,), ("lanes",))),
            datasets)
        assert len(got) == 2 * len(datasets)
        for t, d in datasets.items():
            for k in "qc":
                np.testing.assert_array_equal(np.asarray(got[f"{k}{t}"]),
                                              app.oracle(d[:, 0]))

    def test_zero_retraces_after_warmup(self, small_spec, zipf_dataset):
        """Regression: after the (append-triggered) warmup, a ragged
        multi-tenant scenario -- widths crossing the W=2 bucket cap,
        active-lane counts crossing lane buckets, both flush tiers --
        records ZERO retraces in the per-flush telemetry."""
        eng = _session_engine(small_spec, aot_buckets=2)
        sid = eng.open()
        eng.append(sid, zipf_dataset(8, DOMAIN, 1.5))  # triggers warmup
        eng.close(sid)
        aot = eng.telemetry_record()["extra"]["aot"]
        assert aot is not None and aot["widths"] == [1, 2]
        assert aot["lane_buckets"] == [1, 2, 4]      # the one family
        assert aot["warmup_compiles"] > 0
        n0 = len(eng.telemetry_record()["rows"])
        _drive_scenario(eng, self._datasets(zipf_dataset, n=2))
        rec = eng.telemetry_record()
        steady = rec["rows"][n0:]
        assert steady, "scenario recorded no flushes"
        bad = [r for r in steady if r["n_retraces"]]
        assert not bad, bad
        assert {r["scan_lanes"] for r in steady} <= {0, 1, 2, 4}
        # width chopping: a >W-chunk backlog flushes in one go, still
        # compile-free (W-wide segments through the bucket table)
        wide = zipf_dataset(5 * SMALL_CHUNK + 9, DOMAIN, 1.5, seed=99)
        sid2 = eng.open()
        eng.append(sid2, wide)
        merged, _ = eng.close(sid2)
        np.testing.assert_array_equal(np.asarray(merged),
                                      _oracle(wide[:, 0]))
        rec = eng.telemetry_record()
        assert rec["rows"][-1]["lane_width"] > 2
        assert rec["rows"][-1]["n_retraces"] == 0
        assert rec["extra"]["totals"]["n_retraces"] == 0
        assert rec["extra"]["totals"]["compile_stall_ms"] == 0.0
        assert rec["extra"]["config"]["aot_buckets"] == 2

    def test_group_padding_leaves_other_sessions_untouched(
            self, app, zipf_dataset):
        """A per-session flush whose lane group rounds UP to a bucket
        pads with another session's lane carrying all-masked chunks;
        both sessions must stay exact (the padded lane's state rides
        through the scan bit-identically)."""
        eng = _session_engine(app.spec, primary_slots=2,
                              secondary_slots=3, aot_buckets=2)
        sids = [eng.open(), eng.open()]
        d0 = zipf_dataset(10 * SMALL_CHUNK + 13, DOMAIN, 1.5, seed=1)
        d1 = zipf_dataset(6 * SMALL_CHUNK + 7, DOMAIN, 1.5, seed=2)
        eng.append(sids[0], d0)
        eng.append(sids[1], d1)
        eng.flush()                       # grants settle: 2 + 1 split
        s0 = eng.sessions[sids[0]]
        if len(eng._lane_group(s0.slot)) == 3:   # group 3 -> bucket 4:
            tail0 = zipf_dataset(2 * SMALL_CHUNK + 99, DOMAIN, 1.5, seed=3)
            eng.append(sids[0], tail0)           # the padded-lane path
            eng.flush_session(sids[0])
            d0 = np.concatenate([d0, tail0])
        np.testing.assert_array_equal(
            np.asarray(eng.query(sids[0])), app.oracle(d0[:, 0]))
        np.testing.assert_array_equal(
            np.asarray(eng.query(sids[1])), app.oracle(d1[:, 0]))

    def test_warmup_validation_and_knobs(self, small_spec):
        with pytest.raises(ValueError, match="aot_buckets"):
            _session_engine(small_spec, aot_buckets=0)
        with pytest.raises(RuntimeError, match="aot_buckets"):
            _session_engine(small_spec).warmup()
        eng = _session_engine(small_spec, aot_buckets=3)  # pow2-ceiled
        assert eng._aot_widths == (1, 2, 4)
        with pytest.raises(RuntimeError, match="tuple shape"):
            eng.warmup()
        info = eng.warmup(dtype=np.int32, feat_shape=(2,))
        assert info["n_executables"] == len(eng._aot) > 0
        with pytest.raises(ValueError, match="dtype"):
            eng.warmup(dtype=np.float32)

    def test_backlog_consumes_without_recopy(self, small_spec):
        """Satellite: a flush that leaves a sub-chunk remainder advances
        ``backlog_off`` inside the appended array instead of rebuilding
        the backlog -- repeated small appends stay O(taken)."""
        eng = _session_engine(small_spec)
        sid = eng.open()
        n = SMALL_CHUNK + 100
        keys = (np.arange(n, dtype=np.int32) * 7) % DOMAIN
        eng.append(sid, np.stack([keys, np.ones_like(keys)], axis=1))
        eng.flush()                  # one full chunk runs, 100 stay
        s = eng.sessions[sid]
        assert s.backlog_tuples == 100
        assert len(s.backlog) == 1 and s.backlog_off == SMALL_CHUNK
        pend = s.pending_arrays()
        assert len(pend) == 1 and len(pend[0]) == 100
        np.testing.assert_array_equal(
            np.asarray(eng.query(sid)), _oracle(keys))


# ------------------------------------------------------- batched admission
class TestBatchedAdmission:
    """ISSUE 7 tentpole: ``open_batch`` packs a session storm into ONE
    batched lane-init + one pow2-bucketed scan segment -- it must be
    bit-exact vs serial ``open``+``append`` admission (on the default
    device and on a caller's one-device mesh),
    keep the FIFO overflow contract, and absorb a storm on a warmed
    engine with ZERO retraces."""

    def _storm_data(self, zipf_dataset, n):
        # first appends straddle the chunk boundary: some admit-flushable
        # (>= 1 chunk), some sub-chunk (stay host-buffered), one None
        sizes = [2 * SMALL_CHUNK + 17, SMALL_CHUNK, 73,
                 3 * SMALL_CHUNK, SMALL_CHUNK + 1]
        out = []
        for i in range(n):
            if i == n - 1:
                out.append(None)
            else:
                out.append(zipf_dataset(sizes[i % len(sizes)], DOMAIN,
                                        (0.0, 1.5)[i % 2], seed=50 + i))
        return out

    def _finish(self, eng, sids, firsts, tails):
        """Drain the storm: late appends + close everything (queued
        sessions admit FIFO as slots free), returning answers by sid."""
        for sid, tail in zip(sids, tails):
            eng.append(sid, tail)
        answers = {}
        for sid, first in zip(sids, firsts):
            merged, _ = eng.close(sid)
            answers[sid] = np.asarray(merged)
        return answers

    @pytest.mark.parametrize("mode", ["local", "mesh1"])
    def test_bit_exact_vs_serial_admission(self, app, zipf_dataset,
                                           mode):
        """Acceptance: the SAME over-capacity storm (7 sessions, 3
        primary slots) through open_batch and through serial
        open+append gives identical sids, identical slot/queue state,
        and bit-exact answers -- on the default device and on a caller's
        one-device mesh."""
        kw = dict(primary_slots=3, secondary_slots=1, aot_buckets=2)
        if mode == "mesh1":
            kw["mesh"] = jax.make_mesh((1,), ("lanes",))
        firsts = self._storm_data(zipf_dataset, 7)
        tenants = [f"t{i}" for i in range(7)]
        tails = [zipf_dataset(SMALL_CHUNK + 31 * i, DOMAIN, 1.0,
                              seed=100 + i) for i in range(7)]

        batch = _session_engine(app.spec, **kw)
        sids_b = batch.open_batch(tenants, first=firsts)
        serial = _session_engine(app.spec, **kw)
        sids_s = []
        for t, f in zip(tenants, firsts):
            sid = serial.open(t)
            sids_s.append(sid)
            if f is not None:
                serial.append(sid, f)
        assert sids_b == sids_s
        assert batch._slot_sid == serial._slot_sid
        assert list(batch._queue) == list(serial._queue)
        assert sorted(batch._free_slots) == sorted(serial._free_slots)
        got = self._finish(batch, sids_b, firsts, tails)
        want = self._finish(serial, sids_s, firsts, tails)
        for sid in want:
            np.testing.assert_array_equal(got[sid], want[sid])
        # ... and both equal the oracle on the full per-session stream
        for sid, first, tail in zip(sids_b, firsts, tails):
            keys = (tail[:, 0] if first is None
                    else np.concatenate([first[:, 0], tail[:, 0]]))
            np.testing.assert_array_equal(got[sid], app.oracle(keys))

    def test_fifo_overflow_and_drain_deterministic(self, small_spec):
        """Satellite: the waitlist is STRICTLY FIFO by open/open_batch
        call order, and a freed slot always goes to the queue front --
        admitted into the lowest-numbered free slot (never dict/set
        iteration order)."""
        eng = _session_engine(small_spec, primary_slots=2,
                              secondary_slots=0)
        sids = eng.open_batch([f"t{i}" for i in range(5)])
        assert sids == [0, 1, 2, 3, 4]
        assert eng._slot_sid == [0, 1]
        assert list(eng._queue) == [2, 3, 4]
        eng.close(sids[1])                 # frees slot 1 -> sid 2 admits
        assert eng._slot_sid == [0, 2]
        assert list(eng._queue) == [3, 4]
        eng.close(sids[0])                 # frees slot 0 -> sid 3 admits
        assert eng._slot_sid == [3, 2]
        assert list(eng._queue) == [4]
        eng.close(sids[2])                 # frees slot 1 -> sid 4 admits
        assert eng._slot_sid == [3, 4]
        eng.close(sids[3])                 # queue empty: slot 0 stays free
        late = eng.open("late")            # ... and the next open takes it
        assert eng._slot_sid == [late, 4]
        assert not eng._queue
        # interleaved single opens keep global FIFO order with the batch
        eng2 = _session_engine(small_spec, primary_slots=1,
                               secondary_slots=0)
        a = eng2.open("a")
        mid = eng2.open_batch(["b", "c"])
        d = eng2.open("d")
        order = []
        for sid in [a, *mid, d]:
            assert eng2.sessions[sid].slot == (0 if sid == a else None)
        for _ in range(4):
            front = eng2._slot_sid[0]
            order.append(front)
            eng2.close(front)
        assert order == [a, *mid, d]

    def test_open_batch_validation(self, small_spec, zipf_dataset):
        eng = _session_engine(small_spec)
        with pytest.raises(ValueError, match="first-append"):
            eng.open_batch(["a", "b"], first=[None])
        # empty storm is a no-op that still records an admit row
        assert eng.open_batch([]) == []
        row = eng.telemetry_record()["rows"][-1]
        assert row["scope"] == "admit" and row["n_admitted"] == 0

    def test_zero_retrace_storm_and_telemetry(self, small_spec,
                                              zipf_dataset):
        """Acceptance: a warmed engine absorbs an over-capacity storm
        with zero retraces, one admit scan dispatch per width bucket,
        and the storm totals/row columns land in the schema-v1 record."""
        eng = _session_engine(small_spec, primary_slots=4,
                              secondary_slots=1, aot_buckets=2)
        eng.warmup(dtype=np.int64, feat_shape=(2,))
        firsts = self._storm_data(zipf_dataset, 6)
        sids = eng.open_batch([f"t{i}" for i in range(6)], first=firsts)
        rec = eng.telemetry_record()
        row = rec["rows"][-1]
        assert row["scope"] == "admit"
        assert row["n_admitted"] == 4 and row["n_queued_batch"] == 2
        assert row["n_retraces"] == 0
        # O(buckets): the widest admitted backlog is 3 chunks -> at most
        # ceil(3 / W=2) = 2 pow2 segments, NOT one dispatch per session
        assert 1 <= row["n_scan_dispatches"] <= 2
        assert row["admit_ms"] > 0
        totals = rec["extra"]["totals"]
        assert totals["storms"] == 1
        assert totals["batch_admitted"] == 4
        assert totals["n_retraces_admit"] == 0
        assert totals["n_retraces"] == 0
        assert totals["admit_stall_ms"] >= row["admit_ms"]
        # a second storm after a drain is also compile-free
        for sid in sids:
            eng.close(sid)
        eng.open_batch(["x", "y", "z"],
                       first=self._storm_data(zipf_dataset, 3))
        totals = eng.telemetry_record()["extra"]["totals"]
        assert totals["storms"] == 2 and totals["n_retraces_admit"] == 0

    def test_unknown_and_closed_sid_messages(self, small_spec,
                                             zipf_dataset):
        """Satellite: bad sids raise ValueError naming the sid and the
        engine state (issued/open/queued counts), not a bare KeyError."""
        eng = _session_engine(small_spec)
        sid = eng.open()
        with pytest.raises(ValueError, match=r"issued 1 sid\(s\), 1 open"):
            eng.query(sid + 7)
        eng.close(sid)
        with pytest.raises(ValueError, match="closed sid cannot be reused"):
            eng.append(sid, zipf_dataset(4, DOMAIN, 0.0))


class TestWarmupTableCompleteness:
    """Satellite: every (lane bucket, width) shape the engine can LEGALLY
    produce -- pow2 scan segments, the one lane-bucket family that the
    engine-wide, per-session and admission scans share -- is in the
    compiled table, and nothing else is; the zero-retrace asserts above
    cannot pass vacuously against an empty table."""

    @pytest.mark.parametrize("primary_slots,secondary_slots,aot_buckets",
                             [(2, 2, 2), (3, 1, 4), (5, 0, 1), (1, 3, 8)])
    def test_table_covers_exactly_the_legal_shapes(
            self, small_spec, primary_slots, secondary_slots, aot_buckets):
        eng = _session_engine(small_spec, primary_slots=primary_slots,
                              secondary_slots=secondary_slots,
                              aot_buckets=aot_buckets)
        eng.warmup(dtype=np.int64, feat_shape=(2,))
        widths = eng._aot_widths
        # legal widths: _segments only ever yields pow2 widths <= cap
        assert widths == tuple(sorted(widths))
        assert all(w & (w - 1) == 0 for w in widths)
        # every lane count a flush can present: 1..num_lanes active lanes
        buckets = {eng._lane_bucket(n) for n in range(1, 1 + eng.num_lanes)}
        assert buckets == set(eng._lane_buckets)
        assert max(buckets) == eng.num_lanes
        legal = {(b, w) for b in buckets for w in widths}
        assert set(eng._aot) == legal
        assert eng._aot_info["n_executables"] == len(legal)
        # the info dict advertises the same bucket family
        assert set(eng._aot_info["lane_buckets"]) == buckets
        # every layout of every active-lane set lands in the family
        for n in range(1, 1 + eng.num_lanes):
            rows, idx, b = eng._bucket_rows(list(range(n)))
            assert b in buckets and len(idx) == len(rows) == b
            assert sorted(r for r in rows if r >= 0) == list(range(n))
            assert len(set(idx.tolist())) == b

    def test_every_segment_width_hits_the_table(self, small_spec):
        """Property: for ANY backlog width 1..6*W and ANY lane bucket the
        pow2 segments ``_segments`` yields are all present as (bucket,
        width) keys -- no legal flush can fall through to a fresh
        trace."""
        eng = _session_engine(small_spec, aot_buckets=2)
        eng.warmup(dtype=np.int64, feat_shape=(2,))
        cap = eng._aot_widths[-1]
        for wmax in range(1, 6 * cap + 1):
            segs = list(eng._segments([list(range(wmax))]))
            assert sum(w for _, w in segs) >= wmax
            for b in eng._lane_buckets:
                for _, w in segs:
                    assert (b, w) in eng._aot, (wmax, b, w)


# ------------------------------- HLL through the active-lane flush paths
def _hll_many_tenant_run(mesh=None):
    """HyperLogLog (the ``max`` combine) over 12 tenants on 16 lanes, few
    of them active per flush, through every flush path: an
    ``open_batch`` storm with first appends, engine-wide flushes of a
    hot and a warm tenant (the hot one alternating, so secondary lanes
    are re-granted and folded with ``max``), forced engine-wide flushes
    and per-session flushes behind ``query``, then every close.  Asserts
    each answer register for register against ``apps/hll.oracle``, that
    each engine-wide flush scanned fewer lanes than the table holds, and
    that nothing compiled after the warmup.  Importable by a child
    process that holds a multi-device CPU mesh."""
    from repro.data import zipf
    eng = SessionEngine(hll.make_spec(HLL_P, SMALL_M), num_pri=SMALL_M,
                        num_sec=2, chunk_size=SMALL_CHUNK, primary_slots=14,
                        secondary_slots=2, aot_buckets=2, mesh=mesh)
    keys = {t: np.zeros(0, np.int32) for t in range(12)}

    def feed(t, n, seed):
        d = zipf.zipf_tuples(n, DOMAIN, 1.5, seed=seed)
        keys[t] = np.concatenate([keys[t], d[:, 0]])
        return d

    def check(t, got):
        np.testing.assert_array_equal(np.asarray(got), _hll_oracle(keys[t]))

    firsts = [None] * 12
    for t, n in ((0, 2 * SMALL_CHUNK + 5), (5, 77), (9, SMALL_CHUNK)):
        firsts[t] = feed(t, n, 100 + t)
    sids = eng.open_batch([f"t{t}" for t in range(12)], first=firsts)
    n0 = len(eng.telemetry_record()["rows"])
    for r in range(6):
        hot, warm = (0, 3)[r % 2], 6 + r % 3
        eng.append(sids[hot], feed(hot, 5 * SMALL_CHUNK + 13 * r, 10 * r))
        eng.append(sids[warm], feed(warm, SMALL_CHUNK + 7, 10 * r + 1))
        eng.flush()
        if r % 2:
            check(hot, eng.query(sids[hot]))          # per-session tier
        else:
            eng.append(sids[5], feed(5, 31 + r, 10 * r + 2))
            eng.flush(force=(sids[hot], sids[5]))     # engine-wide, forced
            check(5, eng._snapshot(eng.sessions[sids[5]]))
            check(hot, eng._snapshot(eng.sessions[sids[hot]]))
    assert eng.slot_reschedules > 0                   # max folds ran
    rows = eng.telemetry_record()["rows"][n0:]
    engine_rows = [r for r in rows if r["scope"] == "engine"
                   and r["scan_lanes"]]
    assert engine_rows
    n_dev = eng.num_lanes // eng.lanes_per_device
    for row in engine_rows:
        assert row["active_lanes"] <= row["scan_lanes"] < eng.num_lanes
        assert row["scan_lanes"] % n_dev == 0
        assert row["scan_lanes"] // n_dev in eng._lane_buckets
    assert not [r for r in rows if r["n_retraces"]]
    sec = 0
    for t in range(12):
        merged, stats = eng.close(sids[t])
        check(t, merged)
        sec += stats["sec_lane_flushes"]
    assert sec > 0
    return {"engine_flushes": len(engine_rows),
            "scan_lanes": sorted({r["scan_lanes"] for r in engine_rows})}


class TestHLLActiveLaneFlush:
    """HLL through the engine's three flush paths with few active lanes,
    register for register against the oracle: on the default device, on
    a caller's one-device mesh (Explicit axes), and on a four-device CPU
    mesh in a child process (each device gathers its own active
    lanes)."""

    def test_one_device(self):
        out = _hll_many_tenant_run()
        assert out["engine_flushes"] >= 6

    def test_mesh_of_1(self):
        _hll_many_tenant_run(jax.make_mesh((1,), ("lanes",)))

    def test_four_device_mesh(self, cpu_mesh_env):
        import subprocess
        code = (
            "import sys; sys.path[:0] = [%r, %r]\n"
            "from repro.core.distributed import lane_mesh\n"
            "from tests.test_serving import _hll_many_tenant_run\n"
            "print('RESULT', _hll_many_tenant_run(lane_mesh(4)))\n"
            % (str(REPO), str(REPO / "src")))
        env = dict(cpu_mesh_env,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=600,
                           cwd=str(REPO))
        assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
        assert "RESULT" in r.stdout
