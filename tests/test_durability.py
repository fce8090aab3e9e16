"""Durability subsystem (DESIGN.md §10, docs/durability.md): WAL framing
and torn-tail tolerance, checkpoint + WAL-tail replay crash-exactness
(local and mesh-of-1; the 8-fake-device SIGKILL run is the slow
subprocess test at the bottom), corrupt-checkpoint fallback, and the
PreemptionGuard drain path.

The in-process "crash" is abandoning the engine object without any
flush/close/shutdown: the WAL flushes every record to the OS as it is
logged and checkpoints are atomic, so the on-disk state at abandonment
is byte-identical to a SIGKILL at the same program point (real SIGKILLs
run in ``examples/crash_recovery.py`` + CI, where a child process kills
itself mid-stream)."""
from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent

from repro.apps import histo
from repro.serve import (DurableSessionEngine, EnginePreempted,
                         SessionEngine, WriteAheadLog)
from repro.train.ft import PreemptionGuard

from tests.conftest import SMALL_CHUNK, SMALL_M

BINS, DOMAIN = 64, 1 << 16


def _oracle(keys: np.ndarray) -> np.ndarray:
    return histo.oracle(np.asarray(keys), BINS, DOMAIN, SMALL_M)


def _engine(spec, directory, **kw):
    kw.setdefault("primary_slots", 3)
    kw.setdefault("secondary_slots", 2)
    kw.setdefault("checkpoint_every", 2)
    return DurableSessionEngine(spec, directory=directory, num_pri=SMALL_M,
                                num_sec=2, chunk_size=SMALL_CHUNK, **kw)


def _drive_pre_crash(eng, zipf_dataset, tenants=3, rounds=3, hot=0):
    """Deterministic multi-tenant pre-crash load: ragged Zipf-1.5
    appends with a hot tenant (so secondary grants are active), an
    engine-wide flush per round (auto-checkpoint at flush 2 with the
    default checkpoint_every=2), then an UN-flushed, un-checkpointed
    ragged tail -- the WAL-tail replay has real work to do.  Returns the
    per-tenant appended batches."""
    sids = {t: eng.open(f"t{t}") for t in range(tenants)}
    appended = {t: [] for t in sids}
    for r in range(rounds):
        for t in sids:
            n = (5 if t == hot else 1) * SMALL_CHUNK + 37 * r + 11 * t
            b = zipf_dataset(n, DOMAIN, 1.5, seed=100 * r + t)
            eng.append(sids[t], b)
            appended[t].append(b)
        eng.flush()
    for t in sids:
        b = zipf_dataset(SMALL_CHUNK + 13 * t + 7, DOMAIN, 1.5, seed=900 + t)
        eng.append(sids[t], b)
        appended[t].append(b)
    eng._mgr.wait()       # async checkpoint fully on disk before the crash
    return sids, appended


def _tenant_sids(eng):
    return {s.tenant: sid for sid, s in eng.sessions.items() if not s.closed}


# ------------------------------------------------------------------- WAL
class TestWriteAheadLog:
    def test_roundtrip_global_order_and_seq_resume(self, tmp_path):
        wal = WriteAheadLog(tmp_path)
        payload = np.arange(7, dtype=np.int32).tobytes()
        wal.log("a", {"t": "open", "sid": 0, "tenant": "a"})
        wal.log("b", {"t": "open", "sid": 1, "tenant": "b"})
        wal.log("a", {"t": "app", "sid": 0, "dtype": "int32",
                      "shape": [7]}, payload)
        wal.log("b", {"t": "close", "sid": 1})
        wal.close()
        # records from BOTH tenant files merge back into total order
        wal2 = WriteAheadLog(tmp_path)
        recs = wal2.replay()
        assert [m["seq"] for m, _ in recs] == [1, 2, 3, 4]
        assert [m["t"] for m, _ in recs] == ["open", "open", "app", "close"]
        assert recs[2][1] == payload
        assert wal2.seq == 5          # continues where the writer stopped
        assert len(list(tmp_path.glob("*.wal"))) == 2

    def test_torn_tail_tolerated_and_repaired(self, tmp_path):
        wal = WriteAheadLog(tmp_path)
        wal.log("a", {"t": "open", "sid": 0, "tenant": "a"})
        wal.log("a", {"t": "app", "sid": 0, "dtype": "int32",
                      "shape": [2]}, b"\x01\x00\x00\x00\x02\x00\x00\x00")
        wal.close()
        p = next(tmp_path.glob("*.wal"))
        good = p.stat().st_size
        with open(p, "ab") as f:       # a frame cut mid-write by the crash
            f.write(b"\x99" * 11)
        wal2 = WriteAheadLog(tmp_path)     # reopen repairs the torn tail
        assert len(wal2.replay()) == 2
        assert p.stat().st_size == good
        wal2.log("a", {"t": "close", "sid": 0})    # appends stay readable
        wal2.close()
        assert [m["t"] for m, _ in WriteAheadLog(tmp_path).replay()] == \
            ["open", "app", "close"]

    def test_torn_header_truncates_to_empty_and_recovers(self, tmp_path):
        """A crash that tears the 8-byte magic itself (brand-new tenant
        file) must not zero-pad into a permanently unreadable header:
        reopen truncates to empty and the next append rewrites the
        magic, so acknowledged post-repair records stay readable."""
        wal = WriteAheadLog(tmp_path)
        wal.log("a", {"t": "open", "sid": 0, "tenant": "a"})
        wal.close()
        p = next(tmp_path.glob("*.wal"))
        p.write_bytes(p.read_bytes()[:4])      # torn mid-magic
        wal2 = WriteAheadLog(tmp_path)
        assert p.stat().st_size == 0           # header wiped, not padded
        s = wal2.log("a", {"t": "open", "sid": 0, "tenant": "a"})
        wal2.close()
        recs = WriteAheadLog(tmp_path).replay()
        assert [m["seq"] for m, _ in recs] == [s]

    def test_watermark_filters_and_gc_drops_prefix(self, tmp_path):
        wal = WriteAheadLog(tmp_path)
        wal.log("a", {"t": "open", "sid": 0, "tenant": "a"})
        wal.log("a", {"t": "app", "sid": 0, "dtype": "int32", "shape": [0]})
        wm = wal.seq - 1
        wal.watermark(step=1, upto=wm)
        s3 = wal.log("a", {"t": "app", "sid": 0, "dtype": "int32",
                           "shape": [0]})
        assert [m["seq"] for m, _ in wal.replay(after_seq=wm)] == [s3]
        wal.gc(wm)
        assert [m["seq"] for m, _ in wal.replay()] == [s3]
        wal.close()


# -------------------------------------------------------- crash recovery
class TestCrashRecovery:
    def test_crash_exact_local(self, small_spec, zipf_dataset, tmp_path):
        """Acceptance: abandon the engine mid-stream (secondary grants
        active, ragged un-checkpointed tail) -> recover -> every query
        equals the uninterrupted oracle, only the WAL tail replayed,
        and the stream continues to an exact close."""
        eng = _engine(small_spec, tmp_path)
        sids, appended = _drive_pre_crash(eng, zipf_dataset)
        assert eng._slot_reschedules >= 0 and \
            (eng._sec_assign >= 0).any()          # grants really active
        total = sum(len(b) for bs in appended.values() for b in bs)

        eng2 = SessionEngine.recover(small_spec, tmp_path)
        info = eng2.recovery_info
        assert info["checkpoint_step"] is not None
        assert 0 < info["replayed_tuples"] < total
        assert info["replay_anomalies"] == 0
        by_tenant = _tenant_sids(eng2)
        for t in sids:
            keys = np.concatenate([b[:, 0] for b in appended[t]])
            np.testing.assert_array_equal(
                np.asarray(eng2.query(by_tenant[f"t{t}"])), _oracle(keys))
        # the recovered engine keeps serving durably: more appends, close
        for t in sids:
            b = zipf_dataset(2 * SMALL_CHUNK + 5 * t, DOMAIN, 1.5,
                             seed=500 + t)
            eng2.append(by_tenant[f"t{t}"], b)
            appended[t].append(b)
        eng2.flush()
        for t in sids:
            keys = np.concatenate([b[:, 0] for b in appended[t]])
            merged, _ = eng2.close(by_tenant[f"t{t}"])
            np.testing.assert_array_equal(np.asarray(merged), _oracle(keys))
        eng2.shutdown()

    def test_recovered_answers_match_uninterrupted_engine(
            self, small_spec, zipf_dataset, tmp_path):
        """Crash-exactness vs a live engine, not just the oracle: the
        recovered engine and an identically-driven uninterrupted durable
        engine return identical query answers and session metadata."""
        eng = _engine(small_spec, tmp_path / "crashed")
        sids, appended = _drive_pre_crash(eng, zipf_dataset)
        ref = _engine(small_spec, tmp_path / "reference")
        _drive_pre_crash(ref, zipf_dataset)

        eng2 = SessionEngine.recover(small_spec, tmp_path / "crashed")
        by_tenant, ref_by = _tenant_sids(eng2), _tenant_sids(ref)
        assert by_tenant == ref_by
        for t in sids:
            np.testing.assert_array_equal(
                np.asarray(eng2.query(by_tenant[f"t{t}"])),
                np.asarray(ref.query(ref_by[f"t{t}"])))
            assert (eng2.sessions[by_tenant[f"t{t}"]].tenant
                    == ref.sessions[ref_by[f"t{t}"]].tenant)
        eng2.shutdown()
        ref.shutdown()

    def test_crash_exact_mesh_of_1(self, small_spec, zipf_dataset,
                                   tmp_path):
        """Acceptance: the same kill-and-recover scenario through the
        lane-sharded engine -- the restore scatters the checkpointed
        lanes back with put_lanes and re-pins them to the mesh sharding
        (multi-device SIGKILL runs live in the slow test below)."""
        mesh = jax.make_mesh((1,), ("lanes",))
        eng = _engine(small_spec, tmp_path, primary_slots=2, mesh=mesh)
        sids, appended = _drive_pre_crash(eng, zipf_dataset, tenants=2)
        total = sum(len(b) for bs in appended.values() for b in bs)
        eng2 = SessionEngine.recover(small_spec, tmp_path, mesh=mesh)
        assert eng2._sharded is not None
        assert 0 < eng2.recovery_info["replayed_tuples"] < total
        by_tenant = _tenant_sids(eng2)
        for t in sids:
            keys = np.concatenate([b[:, 0] for b in appended[t]])
            np.testing.assert_array_equal(
                np.asarray(eng2.query(by_tenant[f"t{t}"])), _oracle(keys))
        eng2.shutdown()

    def test_checkpoint_is_mesh_elastic(self, small_spec, zipf_dataset,
                                        tmp_path):
        """A checkpoint taken by a LOCAL engine restores onto a meshed
        one (the lanes-stacked state is mesh-agnostic on disk)."""
        eng = _engine(small_spec, tmp_path, primary_slots=2)
        sids, appended = _drive_pre_crash(eng, zipf_dataset, tenants=2)
        mesh = jax.make_mesh((1,), ("lanes",))
        eng2 = SessionEngine.recover(small_spec, tmp_path, mesh=mesh)
        by_tenant = _tenant_sids(eng2)
        for t in sids:
            keys = np.concatenate([b[:, 0] for b in appended[t]])
            np.testing.assert_array_equal(
                np.asarray(eng2.query(by_tenant[f"t{t}"])), _oracle(keys))
        eng2.shutdown()

    def test_recover_without_checkpoint_replays_everything(
            self, small_spec, zipf_dataset, tmp_path):
        """WAL-only recovery (crash before the first checkpoint): the
        full stream replays and answers stay exact."""
        eng = _engine(small_spec, tmp_path, checkpoint_every=0)
        data = zipf_dataset(2 * SMALL_CHUNK + 41, DOMAIN, 1.5)
        sid = eng.open("solo")
        eng.append(sid, data)
        eng.flush()
        eng2 = SessionEngine.recover(small_spec, tmp_path)
        assert eng2.recovery_info["checkpoint_step"] is None
        assert eng2.recovery_info["replayed_tuples"] == len(data)
        np.testing.assert_array_equal(
            np.asarray(eng2.query(_tenant_sids(eng2)["solo"])),
            _oracle(data[:, 0]))
        eng2.shutdown()

    def test_corrupt_latest_checkpoint_falls_back(self, small_spec,
                                                  zipf_dataset, tmp_path):
        """A truncated newest checkpoint (torn by disk damage) is
        skipped; recovery restores the previous one and replays the
        correspondingly longer WAL tail -- answers still exact."""
        eng = _engine(small_spec, tmp_path, checkpoint_every=0)
        sid = eng.open("solo")
        chunks = []
        for r in range(3):
            b = zipf_dataset(2 * SMALL_CHUNK + 19 * r, DOMAIN, 1.5,
                             seed=40 + r)
            eng.append(sid, b)
            chunks.append(b)
            eng.flush()
            eng.checkpoint(block=True)
        steps = eng._mgr.steps()
        assert len(steps) == 3
        leaf = tmp_path / "ckpt" / f"step_{steps[-1]}" / "leaf_0.npy"
        leaf.write_bytes(leaf.read_bytes()[:10])
        with pytest.warns(UserWarning, match="skipping unreadable"):
            eng2 = SessionEngine.recover(small_spec, tmp_path)
        assert eng2.recovery_info["checkpoint_step"] == steps[-2]
        assert eng2.recovery_info["replayed_tuples"] == len(chunks[-1])
        keys = np.concatenate([b[:, 0] for b in chunks])
        np.testing.assert_array_equal(
            np.asarray(eng2.query(_tenant_sids(eng2)["solo"])),
            _oracle(keys))
        eng2.shutdown()

    def test_all_checkpoints_corrupt_refuses_wal_only_recovery(
            self, small_spec, zipf_dataset, tmp_path):
        """When checkpoints EXIST but none restores cleanly, recovery
        must refuse rather than silently replay a WAL whose prefix may
        have been GC'd past their watermarks (short answers)."""
        eng = _engine(small_spec, tmp_path, checkpoint_every=0)
        sid = eng.open("solo")
        eng.append(sid, zipf_dataset(2 * SMALL_CHUNK, DOMAIN, 1.5))
        eng.flush()
        eng.checkpoint(block=True)
        for step_dir in (tmp_path / "ckpt").glob("step_*"):
            (step_dir / "leaf_0.npy").write_bytes(b"garbage")
        with pytest.warns(UserWarning, match="skipping unreadable"):
            with pytest.raises(RuntimeError, match="WAL-only"):
                SessionEngine.recover(small_spec, tmp_path)

    def test_wal_gc_runs_in_steady_state(self, small_spec, zipf_dataset,
                                         tmp_path):
        """WAL records covered by the oldest KEPT checkpoint are dropped
        by the ordinary async checkpoint cadence (no drain needed), so
        the log tracks the tail instead of the engine's lifetime -- and
        recovery after GC is still exact."""
        eng = _engine(small_spec, tmp_path, checkpoint_every=1, keep=1)
        sid = eng.open("solo")
        chunks = []
        for r in range(4):
            b = zipf_dataset(2 * SMALL_CHUNK + 19 * r, DOMAIN, 1.5,
                             seed=60 + r)
            eng.append(sid, b)
            chunks.append(b)
            eng.flush()                  # ckpt every flush, keep=1
        eng._mgr.wait()
        replayable = eng._wal.replay()   # post-GC: early appends dropped
        assert all(m["seq"] > 2 for m, _ in replayable)
        assert len(replayable) < 1 + len(chunks)
        eng2 = SessionEngine.recover(small_spec, tmp_path)
        keys = np.concatenate([b[:, 0] for b in chunks])
        np.testing.assert_array_equal(
            np.asarray(eng2.query(_tenant_sids(eng2)["solo"])),
            _oracle(keys))
        eng2.shutdown()

    def test_queued_and_empty_sessions_survive(self, small_spec,
                                               zipf_dataset, tmp_path):
        """The scheduler state recovers too: a queued session (with
        data) is still queued and admits when the slot frees; a session
        whose only append was EMPTY (the zero-tuple edge that feeds the
        WAL-replay path) answers all-zero buffers."""
        eng = _engine(small_spec, tmp_path, primary_slots=1,
                      secondary_slots=0)
        a = eng.open("first")
        b = eng.open("waiting")
        c_data = zipf_dataset(SMALL_CHUNK + 9, DOMAIN, 1.5, seed=7)
        eng.append(b, c_data)
        empty = eng.open("empty")
        eng.append(empty, np.zeros((0, 2), np.int32))
        eng.flush()
        eng.checkpoint(block=True)

        eng2 = SessionEngine.recover(small_spec, tmp_path)
        by_tenant = _tenant_sids(eng2)
        assert eng2.sessions[by_tenant["waiting"]].slot is None
        with pytest.raises(RuntimeError, match="queued"):
            eng2.query(by_tenant["waiting"])
        eng2.close(by_tenant["first"])       # frees the slot -> admits b
        np.testing.assert_array_equal(
            np.asarray(eng2.query(by_tenant["waiting"])),
            _oracle(c_data[:, 0]))
        merged, stats = eng2.close(by_tenant["waiting"])
        eng2.close(by_tenant["empty"])
        assert stats["tuples_appended"] == len(c_data)
        eng2.shutdown()

    def test_fresh_engine_refuses_stale_dir(self, small_spec,
                                            zipf_dataset, tmp_path):
        eng = _engine(small_spec, tmp_path)
        sid = eng.open()
        eng.append(sid, zipf_dataset(64, DOMAIN, 0.0))
        eng.shutdown()
        with pytest.raises(ValueError, match="recover"):
            _engine(small_spec, tmp_path)
        eng2 = _engine(small_spec, tmp_path, overwrite=True)  # explicit wipe
        assert eng2._wal.replay() == []
        eng2.shutdown()


# ------------------------------------------------------ preemption drain
class TestPreemptionDrain:
    def test_drain_then_recover_with_empty_tail(self, small_spec,
                                                zipf_dataset, tmp_path):
        guard = PreemptionGuard(signals=())      # triggered manually
        eng = _engine(small_spec, tmp_path, guard=guard)
        sid = eng.open("alpha")
        data = zipf_dataset(3 * SMALL_CHUNK + 7, DOMAIN, 1.5)
        eng.append(sid, data)
        guard.trigger()                          # the SIGTERM moment
        with pytest.raises(EnginePreempted):
            eng.append(sid, data)
        assert eng.drained
        # reads stay available on the drained engine -- BOTH query
        # scopes (engine scope routes through flush) -- writes refuse
        np.testing.assert_array_equal(np.asarray(eng.query(sid)),
                                      _oracle(data[:, 0]))
        np.testing.assert_array_equal(
            np.asarray(eng.query(sid, scope="engine")),
            _oracle(data[:, 0]))
        with pytest.raises(EnginePreempted):
            eng.open("beta")
        with pytest.raises(EnginePreempted):
            eng.append(sid, data)
        # the drain checkpoint covers everything: replay tail is EMPTY
        eng2 = SessionEngine.recover(small_spec, tmp_path)
        assert eng2.recovery_info["replayed_records"] == 0
        np.testing.assert_array_equal(
            np.asarray(eng2.query(_tenant_sids(eng2)["alpha"])),
            _oracle(data[:, 0]))
        eng2.shutdown()


# --------------------------------------------------- durable == plain
class TestDurableMatchesPlain:
    def test_no_crash_answers_identical(self, small_spec, zipf_dataset,
                                        tmp_path):
        """The WAL/checkpoint wrappers must be answer-invisible: a
        durable engine and a plain SessionEngine driven identically
        return identical queries, closes and telemetry totals."""
        engines = {
            "plain": SessionEngine(small_spec, num_pri=SMALL_M, num_sec=2,
                                   chunk_size=SMALL_CHUNK, primary_slots=2,
                                   secondary_slots=2),
            "durable": _engine(small_spec, tmp_path, primary_slots=2),
        }
        answers = {}
        for name, eng in engines.items():
            sids = {t: eng.open(f"t{t}") for t in range(2)}
            out = []
            for r in range(3):
                for t in sids:
                    eng.append(sids[t], zipf_dataset(
                        (4 if t == 0 else 1) * SMALL_CHUNK + 31 * r,
                        DOMAIN, 1.5, seed=10 * r + t))
                eng.flush()
                out.append(np.asarray(eng.query(sids[0])))
            for t in sids:
                out.append(np.asarray(eng.close(sids[t])[0]))
            answers[name] = (out, eng.telemetry_record(validate=False)
                             ["extra"]["totals"]["tuples_flushed"])
        for got, want in zip(*[answers[n][0] for n in ("durable", "plain")]):
            np.testing.assert_array_equal(got, want)
        assert answers["durable"][1] == answers["plain"][1]
        engines["durable"].shutdown()


# ----------------------------------------------- SIGKILL subprocess run
@pytest.mark.slow
def test_crash_recovery_example_sigkill_multi_device(cpu_mesh_env,
                                                     tmp_path):
    """Acceptance: a REAL SIGKILL mid-stream on the 8-fake-device meshed
    engine (the example's child process kills itself past the last
    checkpoint), recovered by the example's parent and verified
    bit-exact vs the uninterrupted oracle, WAL-tail-only replay
    asserted."""
    r = subprocess.run(
        [sys.executable, str(REPO / "examples" / "crash_recovery.py"),
         "--chips", "8", str(tmp_path / "durable")],
        env=cpu_mesh_env, capture_output=True, text=True, timeout=560,
        cwd=str(REPO))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK child SIGKILLed mid-stream" in r.stdout
    assert "OK WAL tail only" in r.stdout
    assert "OK recovered answers oracle-exact" in r.stdout
    assert "OK post-recovery stream + close oracle-exact" in r.stdout


class TestRecoverAOTBuckets:
    def test_recover_lands_in_same_buckets_zero_retraces(
            self, small_spec, zipf_dataset, tmp_path):
        """Acceptance: an ``aot_buckets=`` engine's knob round-trips
        through config.json, ``recover`` re-warms the bucket table from
        the checkpoint's dtype/shape BEFORE the WAL tail replays, the
        recovered answers stay crash-exact, and post-recover queries
        record zero retraces."""
        import json
        eng = _engine(small_spec, tmp_path, aot_buckets=2)
        sids, appended = _drive_pre_crash(eng, zipf_dataset, tenants=2)
        eng.shutdown()                       # abandon == SIGKILL on disk
        cfg = json.loads((tmp_path / "config.json").read_text())
        assert cfg["engine_kw"]["aot_buckets"] == 2

        eng2 = SessionEngine.recover(small_spec, tmp_path)
        rec = eng2.telemetry_record(validate=False)
        assert rec["extra"]["config"]["aot_buckets"] == 2
        assert rec["extra"]["aot"] is not None   # warmup really ran
        n0 = len(rec["rows"])
        by_tenant = _tenant_sids(eng2)
        for t in sids:
            keys = np.concatenate([b[:, 0] for b in appended[t]])
            np.testing.assert_array_equal(
                np.asarray(eng2.query(by_tenant[f"t{t}"])), _oracle(keys))
        steady = eng2.telemetry_record(validate=False)["rows"][n0:]
        assert steady and all(r["n_retraces"] == 0 for r in steady), steady
        eng2.shutdown()

    def test_recover_plain_engine_stays_unbucketed(
            self, small_spec, zipf_dataset, tmp_path):
        """No knob, no buckets: recovery of a plain durable engine keeps
        the plain jit path (aot config None, no warmup info)."""
        eng = _engine(small_spec, tmp_path)
        _drive_pre_crash(eng, zipf_dataset, tenants=2)
        eng.shutdown()
        eng2 = SessionEngine.recover(small_spec, tmp_path)
        rec = eng2.telemetry_record(validate=False)
        assert rec["extra"]["config"]["aot_buckets"] is None
        assert rec["extra"]["aot"] is None
        eng2.shutdown()


# ------------------------------------------------------ storms x recovery
class TestStormRecovery:
    def test_sigkill_mid_storm_wal_tail_replays_rest(
            self, small_spec, zipf_dataset, tmp_path):
        """ISSUE 7 satellite: crash with a storm half-admitted -- the
        checkpoint covers the pre-storm state, the WAL tail holds the
        whole ``open_batch`` (logged as its constituent opens/appends),
        and recovery replays it into the SAME admission buckets: queue
        order FIFO-preserved, answers oracle-exact, zero retraces on the
        re-warmed engine."""
        eng = _engine(small_spec, tmp_path, primary_slots=3,
                      secondary_slots=1, aot_buckets=2, checkpoint_every=0)
        warm_data = zipf_dataset(2 * SMALL_CHUNK + 31, DOMAIN, 1.5, seed=1)
        warm = eng.open("warm")
        eng.append(warm, warm_data)
        eng.flush()
        eng.checkpoint(block=True)          # storm below is NOT covered

        # over-capacity storm: 2 admit (slots 1,2), 3 queue behind them
        tenants = [f"s{i}" for i in range(5)]
        firsts = [zipf_dataset(SMALL_CHUNK * (1 + i % 3) + 17 * i, DOMAIN,
                               (0.0, 1.5)[i % 2], seed=10 + i)
                  for i in range(4)] + [None]
        sids = eng.open_batch(tenants, first=firsts)
        assert [eng.sessions[s].slot is not None for s in sids] == \
            [True, True, False, False, False]
        crashed_queue = list(eng._queue)
        assert eng.telemetry_record(validate=False)["extra"]["totals"][
            "n_retraces_admit"] == 0
        # abandon WITHOUT shutdown/checkpoint == SIGKILL here (see module
        # docstring); the WAL has the storm, no checkpoint does

        eng2 = SessionEngine.recover(small_spec, tmp_path)
        info = eng2.recovery_info
        assert info["checkpoint_step"] is not None
        assert info["replay_anomalies"] == 0
        assert info["replayed_tuples"] == sum(
            len(f) for f in firsts if f is not None)
        by_tenant = _tenant_sids(eng2)
        assert list(eng2._queue) == \
            [by_tenant[t] for t in tenants[2:]] == crashed_queue
        n0 = len(eng2.telemetry_record(validate=False)["rows"])
        for i in (0, 1):                    # the half that was admitted
            np.testing.assert_array_equal(
                np.asarray(eng2.query(by_tenant[tenants[i]])),
                _oracle(firsts[i][:, 0]))
        np.testing.assert_array_equal(
            np.asarray(eng2.query(by_tenant["warm"])),
            _oracle(warm_data[:, 0]))
        # drain FIFO: closing admitted sessions admits the queued rest
        for t in ("warm", *tenants[:2]):
            eng2.close(by_tenant[t])
        for i in (2, 3):
            assert eng2.sessions[by_tenant[tenants[i]]].slot is not None
            np.testing.assert_array_equal(
                np.asarray(eng2.query(by_tenant[tenants[i]])),
                _oracle(firsts[i][:, 0]))
        # the replayed storm landed in the pre-warmed buckets: every
        # post-recover flush row is compile-free
        steady = eng2.telemetry_record(validate=False)["rows"][n0:]
        assert steady and all(r["n_retraces"] == 0 for r in steady), steady
        eng2.shutdown()
