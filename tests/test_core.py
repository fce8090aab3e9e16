"""Core architecture tests: mapper (Fig. 4), scheduler (Fig. 5), merger,
analyzer (Eq. 2), Eq. 1 tuning, and end-to-end executor equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DittoSpec, analyze_skew, apply_schedule,
                        buffer_capacity_fraction, init_plan, make_executor,
                        make_static_plan, merge_buffers, occurrence_rank,
                        post_plan_max_load, redirect, schedule_secpes,
                        secpes_for_workload, tune_pe_counts, workload_hist)
from repro.core import mapper, profiler
from repro.core.types import PROFILE_MODE, RUN_MODE


# ---------------------------------------------------------------- Fig. 4
class TestMapper:
    def test_fig4_table_update(self):
        """Paper Fig. 4a/4b walkthrough: 4 PriPEs, 3 SecPEs, plan
        {Sec4->Pri2, Sec5->Pri2, Sec6->Pri0}."""
        plan0 = init_plan(4, 3)
        np.testing.assert_array_equal(np.asarray(plan0.counter), [1, 1, 1, 1])
        np.testing.assert_array_equal(np.asarray(plan0.table),
                                      [[0] * 4, [1] * 4, [2] * 4, [3] * 4])
        plan = apply_schedule(plan0, jnp.array([2, 2, 0], jnp.int32))
        np.testing.assert_array_equal(np.asarray(plan.counter), [2, 1, 3, 1])
        tab = np.asarray(plan.table)
        assert tab[0].tolist() == [0, 6, 0, 0]
        assert tab[2].tolist() == [2, 4, 5, 2]
        assert tab[1].tolist() == [1, 1, 1, 1]
        assert tab[3].tolist() == [3, 3, 3, 3]

    def test_fig4c_round_robin_sequence(self):
        """Fig. 4c: dst=0 alternates 0,6; dst=2 cycles 2,4,5."""
        plan = apply_schedule(init_plan(4, 3), jnp.array([2, 2, 0], jnp.int32))
        dst = jnp.array([0, 0, 0, 0, 2, 2, 2, 2, 2, 2], jnp.int32)
        rank, _ = occurrence_rank(dst, 4, jnp.zeros(4, jnp.int32))
        eff = redirect(plan, dst, rank)
        assert np.asarray(eff).tolist() == [0, 6, 0, 6, 2, 4, 5, 2, 4, 5]

    def test_round_robin_continues_across_chunks(self):
        plan = apply_schedule(init_plan(2, 1), jnp.array([0], jnp.int32))
        base = jnp.zeros(2, jnp.int32)
        seq = []
        for _ in range(3):
            dst = jnp.array([0, 0, 0], jnp.int32)
            rank, base = occurrence_rank(dst, 2, base)
            seq += np.asarray(redirect(plan, dst, rank)).tolist()
        assert seq == [0, 2, 0, 2, 0, 2, 0, 2, 0]

    def test_unassigned_secs_ignored(self):
        plan = apply_schedule(init_plan(4, 3), jnp.array([1, -1, -1], jnp.int32))
        assert np.asarray(plan.counter).tolist() == [1, 2, 1, 1]
        assert np.asarray(plan.table)[1].tolist() == [1, 4, 1, 1]


# ---------------------------------------------------------------- Fig. 5
class TestScheduler:
    def test_fig5_greedy_max_splitting(self):
        """PriPE 2 is maximal for the first two iterations -> divided to
        one-third; the third SecPE helps the next-hottest PriPE."""
        w = jnp.array([150, 32, 400, 16], jnp.float32)
        a = schedule_secpes(w, 3)
        assert np.asarray(a).tolist() == [2, 2, 0]

    def test_uniform_workload_spreads(self):
        a = np.asarray(schedule_secpes(jnp.ones(4) * 100.0, 3))
        assert len(set(a.tolist())) == 3  # three different PEs helped

    def test_oblivious_bound(self):
        """X = M-1 handles the worst case: all tuples to one PriPE."""
        m = 16
        w = jnp.zeros(m).at[3].set(1e6)
        a = schedule_secpes(w, m - 1)
        assert np.asarray(a == 3).all()
        assert float(post_plan_max_load(w, a)) == pytest.approx(1e6 / m)

    def test_post_plan_max_load_le_baseline(self):
        rng = np.random.default_rng(0)
        for _ in range(5):
            w = jnp.asarray(rng.integers(0, 1000, size=16).astype(np.float32))
            for x in (0, 3, 15):
                a = schedule_secpes(w, x)
                assert float(post_plan_max_load(w, a)) <= float(w.max()) + 1e-6


# ---------------------------------------------------------------- merger
class TestMerger:
    def test_add_merge(self):
        bufs = jnp.arange(5 * 4, dtype=jnp.int32).reshape(5, 4)  # 3 pri + 2 sec
        a = jnp.array([0, 2], jnp.int32)
        out = np.asarray(merge_buffers(bufs, a, 3, "add"))
        exp = np.asarray(bufs[:3]).copy()
        exp[0] += np.asarray(bufs[3])
        exp[2] += np.asarray(bufs[4])
        np.testing.assert_array_equal(out, exp)

    def test_max_merge_with_idle_sec(self):
        bufs = jnp.array([[1, 5], [7, 2], [9, 9], [0, 8]], jnp.int32)  # 2 pri
        a = jnp.array([1, -1], jnp.int32)
        out = np.asarray(merge_buffers(bufs, a, 2, "max"))
        np.testing.assert_array_equal(out, [[1, 5], [9, 9]])

    def test_no_secs(self):
        bufs = jnp.ones((3, 4), jnp.int32)
        out = merge_buffers(bufs, jnp.zeros((0,), jnp.int32), 3, "add")
        np.testing.assert_array_equal(np.asarray(out), np.ones((3, 4)))


# ---------------------------------------------------------------- Eq. 2 / Eq. 1
class TestAnalyzer:
    def test_uniform_needs_no_secpes(self):
        dst = jnp.arange(16000, dtype=jnp.int32) % 16
        assert analyze_skew(dst, 16, tolerance=0.01) == 0

    def test_extreme_skew_needs_m_minus_1(self):
        dst = jnp.zeros(16000, jnp.int32)
        assert analyze_skew(dst, 16, tolerance=0.01) == 15

    def test_moderate_skew_between(self):
        # half the tuples to PE 0, rest uniform
        dst = np.concatenate([np.zeros(8000), np.arange(8000) % 16])
        x = analyze_skew(jnp.asarray(dst, jnp.int32), 16, tolerance=0.01)
        assert 0 < x < 15
        # the guarantee: post-plan max load <= uniform load (within T)
        w = workload_hist(jnp.asarray(dst, jnp.int32), 16)
        a = schedule_secpes(w, int(x))
        assert float(post_plan_max_load(w, a)) <= float(w.sum()) / 16 * 1.35

    def test_eq1_histo_example(self):
        """Paper §II: 8 tuples/cycle, II_pe = 2 -> 16 PriPEs."""
        n_pre, n_pri, w = tune_pe_counts(64, 8, 1, 2)
        assert (n_pre, n_pri, w) == (8, 16, 8)

    def test_capacity_fraction(self):
        assert buffer_capacity_fraction(16, 0) == 1.0
        assert buffer_capacity_fraction(16, 15) == pytest.approx(16 / 31)


# ---------------------------------------------------------------- profiler
class TestProfiler:
    def test_partial_hists_merge_to_global(self):
        dst = jnp.asarray(np.random.default_rng(1).integers(0, 16, 256), jnp.int32)
        parts = profiler.partial_hists(dst, 16, 8)
        assert parts.shape == (8, 16)
        np.testing.assert_array_equal(
            np.asarray(profiler.merge_partials(parts)),
            np.asarray(workload_hist(dst, 16)))


# ------------------------------------------------------- end-to-end executor
def _histo_spec(bins_per_pe: int):
    def pre(chunk, num_pri):
        key = chunk[:, 0]
        h = key  # identity hash keeps the oracle trivial
        dst = (h % num_pri).astype(jnp.int32)
        idx = (h // num_pri % bins_per_pe).astype(jnp.int32)
        return dst, idx, jnp.ones_like(key, jnp.int32)

    return DittoSpec(
        name="histo-test", pre=pre,
        init_buffer=lambda n: jnp.zeros((n, bins_per_pe), jnp.int32),
        combine="add")


def _oracle_hist(keys: np.ndarray, num_pri: int, bins_per_pe: int) -> np.ndarray:
    dst = keys % num_pri
    idx = keys // num_pri % bins_per_pe
    out = np.zeros((num_pri, bins_per_pe), np.int64)
    np.add.at(out, (dst, idx), 1)
    return out


class TestExecutor:
    M, B, C = 8, 32, 256

    def _data(self, skewed: bool, n=2048):
        rng = np.random.default_rng(42)
        if skewed:
            keys = np.minimum(rng.zipf(2.0, size=n) - 1, self.M * self.B - 1)
        else:
            keys = rng.integers(0, self.M * self.B, size=n)
        return np.stack([keys, keys], axis=1).astype(np.int32)

    @pytest.mark.parametrize("num_sec", [0, 3, 7])
    @pytest.mark.parametrize("skewed", [False, True])
    def test_equivalence_runtime_plan(self, num_sec, skewed):
        spec = _histo_spec(self.B)
        run = make_executor(spec, self.M, num_sec, self.C, profile_chunks=2)
        tuples = self._data(skewed).reshape(-1, self.C, 2)
        merged, stats = run(jnp.asarray(tuples))
        oracle = _oracle_hist(self._data(skewed)[:, 0], self.M, self.B)
        np.testing.assert_array_equal(np.asarray(merged), oracle)
        assert int(np.asarray(merged).sum()) == tuples.shape[0] * tuples.shape[1]

    def test_equivalence_static_plan(self):
        spec = _histo_spec(self.B)
        data = self._data(True)
        w = workload_hist(jnp.asarray(data[:, 0] % self.M, jnp.int32), self.M)
        plan = make_static_plan(self.M, 7, w)
        run = make_executor(spec, self.M, 7, self.C, static_plan=True)
        merged, stats = run(jnp.asarray(data.reshape(-1, self.C, 2)), plan)
        np.testing.assert_array_equal(np.asarray(merged),
                                      _oracle_hist(data[:, 0], self.M, self.B))

    def test_skew_reduces_max_load_with_plan(self):
        """The architecture's whole point: SecPEs flatten the max PE load."""
        spec = _histo_spec(self.B)
        data = self._data(True)
        chunks = jnp.asarray(data.reshape(-1, self.C, 2))
        run0 = make_executor(spec, self.M, 0, self.C, profile_chunks=1)
        run7 = make_executor(spec, self.M, 7, self.C, profile_chunks=1)
        _, s0 = run0(chunks)
        _, s7 = run7(chunks)
        # after the first (profiling) chunk, plans are live
        assert float(s7.max_load[1:].mean()) < float(s0.max_load[1:].mean())

    def test_modes_progress(self):
        spec = _histo_spec(self.B)
        run = make_executor(spec, self.M, 3, self.C, profile_chunks=2)
        _, stats = run(jnp.asarray(self._data(False).reshape(-1, self.C, 2)))
        modes = np.asarray(stats.mode)
        assert modes[0] == PROFILE_MODE and modes[1] == PROFILE_MODE
        assert (modes[2:] == RUN_MODE).all()

    @pytest.mark.parametrize("skewed", [False, True])
    def test_masked_ragged_stream_equivalence(self, skewed):
        """A ragged stream through the validity-mask path == the oracle:
        padded tuples touch no buffer, histogram or round-robin state."""
        from repro.data.pipeline import chunk_stream
        spec = _histo_spec(self.B)
        data = self._data(skewed, n=2048 + 117)
        ts = chunk_stream(data, self.C, pad_tail=True)
        run = make_executor(spec, self.M, 3, self.C, profile_chunks=2)
        merged, stats = run(jnp.asarray(ts.body), mask=jnp.asarray(ts.mask))
        np.testing.assert_array_equal(
            np.asarray(merged), _oracle_hist(data[:, 0], self.M, self.B))
        # the masked tail chunk's workload counts only the real tuples
        assert int(np.asarray(stats.workload)[-1].sum()) == 2048 + 117 - 2048

    def test_masked_ragged_custom_pe_update(self):
        """The mask sentinel must be dropped by CUSTOM pe_updates too (the
        DP cursor-append writes via jnp .at, which normalizes negative
        indices -- hence the OOB-high sentinel): tight capacity, ragged
        stream, no spurious writes anywhere."""
        from repro.apps import dp
        from repro.data.pipeline import chunk_stream
        spec = dp.make_spec(2, 4, capacity_per_pe=8)
        rng = np.random.default_rng(2)
        data = rng.integers(0, 100, size=(2 * 8 + 5, 2)).astype(np.int32)
        ts = chunk_stream(data, 8, pad_tail=True)
        run = make_executor(spec, 4, 0, 8)
        bufs, _ = run(jnp.asarray(ts.body), mask=jnp.asarray(ts.mask))
        parts = dp.partitions_from_buffers(bufs, 4)
        for p, (a, b) in enumerate(zip(parts, dp.oracle(data, 2))):
            assert a.shape == b.shape and dp.multiset_equal(a, b), p
        cur = np.asarray(bufs.cursor)
        assert int(cur.sum()) == len(data)
        tag = np.asarray(bufs.dst_part)
        for pe in range(4):              # nothing written past any cursor
            assert (tag[pe, cur[pe]:] == -1).all()

    def test_resumable_matches_one_shot(self):
        """Suspend/resume across run_chunks calls == one lax.scan, and
        merge_state snapshots are non-destructive (DESIGN.md §8)."""
        from repro.core import make_resumable_executor
        spec = _histo_spec(self.B)
        data = self._data(True)
        chunks = jnp.asarray(data.reshape(-1, self.C, 2))
        one_shot, _ = make_executor(spec, self.M, 3, self.C,
                                    profile_chunks=2)(chunks)
        res = make_resumable_executor(spec, self.M, 3, self.C,
                                      profile_chunks=2)
        state = res.init_state()
        for lo, hi in ((0, 3), (3, 4), (4, 8)):
            state, _ = res.run_chunks(state, chunks[lo:hi])
            res.merge_state(state)           # mid-stream query, no effect
        np.testing.assert_array_equal(np.asarray(res.merge_state(state)),
                                      np.asarray(one_shot))

    def test_resumable_with_plan_runs_static(self):
        from repro.core import make_resumable_executor, with_plan
        spec = _histo_spec(self.B)
        data = self._data(True)
        w = workload_hist(jnp.asarray(data[:, 0] % self.M, jnp.int32), self.M)
        plan = make_static_plan(self.M, 7, w)
        res = make_resumable_executor(spec, self.M, 7, self.C)
        state = with_plan(res.init_state(), plan)
        state, stats = res.run_chunks(state,
                                      jnp.asarray(data.reshape(-1, self.C, 2)))
        assert (np.asarray(stats.mode) == RUN_MODE).all()
        np.testing.assert_array_equal(
            np.asarray(res.merge_state(state)),
            _oracle_hist(data[:, 0], self.M, self.B))

    def test_empty_stream_is_exact_noop(self):
        """The chunk_stream empty-stream contract end-to-end: a
        zero-chunk (body [0, C, ...]) stream scans as a no-op -- fresh
        buffers, zero tuples -- so WAL-replay-style callers never
        special-case 'nothing appended'."""
        from repro.data.pipeline import chunk_stream
        spec = _histo_spec(self.B)
        ts = chunk_stream(np.zeros((0, 2), np.int32), self.C, pad_tail=True)
        run = make_executor(spec, self.M, 3, self.C)
        merged, stats = run(jnp.asarray(ts.body), mask=jnp.asarray(ts.mask))
        assert int(np.asarray(merged).sum()) == 0
        assert np.asarray(stats.max_load).shape == (0,)

    def test_reschedule_on_evolving_skew(self):
        """Shift the hot key range mid-stream; the monitor must fire and the
        result must still be exact (merge-before-reassign correctness)."""
        spec = _histo_spec(self.B)
        rng = np.random.default_rng(7)
        n = 16 * self.C
        hot_a = rng.integers(0, 2, size=n) * 0          # all key 0   (pe 0)
        hot_b = np.full(n, 3, np.int64)                 # all key 3   (pe 3)
        keys = np.concatenate([hot_a, hot_b])
        data = np.stack([keys, keys], 1).astype(np.int32)
        run = make_executor(spec, self.M, 7, self.C, profile_chunks=1,
                            threshold=0.5)
        merged, stats = run(jnp.asarray(data.reshape(-1, self.C, 2)))
        np.testing.assert_array_equal(np.asarray(merged),
                                      _oracle_hist(keys, self.M, self.B))
        assert bool(np.asarray(stats.rescheduled).any())


# -------------------------------------- lane gather/scatter primitives
class TestLanePrimitives:
    """Direct round-trip coverage for ``stack_states`` / ``take_lanes``
    / ``put_lanes`` -- the SessionEngine's per-session-flush resume unit
    AND the durability snapshot unit (DESIGN.md §9, §10), previously
    exercised only through the engine."""

    L, M, X, C = 4, 8, 2, 64

    def _setup(self):
        from repro.core import executor as E
        spec = _histo_spec(16)
        res = E.make_resumable_executor(spec, self.M, self.X, self.C)
        return E, res

    def _advanced(self, E, res, seed=0):
        """A lanes-stacked state advanced with per-lane-distinct data."""
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, self.M * 16, size=(self.L, 2, self.C))
        chunks = jnp.asarray(np.stack([keys, keys], axis=-1), jnp.int32)
        states = E.stack_states(res.init_state(), self.L)
        states, _ = jax.jit(jax.vmap(res.scan_chunks))(states, chunks, None)
        return states, chunks

    def test_stack_states_broadcasts_every_leaf(self):
        E, res = self._setup()
        fresh = res.init_state()
        stacked = E.stack_states(fresh, self.L)
        for leaf, f in zip(jax.tree.leaves(stacked), jax.tree.leaves(fresh)):
            assert leaf.shape == (self.L,) + np.asarray(f).shape
            for ln in range(self.L):
                np.testing.assert_array_equal(np.asarray(leaf[ln]),
                                              np.asarray(f))

    def test_take_permuted_then_put_is_identity(self):
        """take(idx) gathers exactly the named lanes IN idx ORDER, and
        put(idx, take(idx)) reconstructs the original state bit-for-bit
        for any permutation."""
        E, res = self._setup()
        states, _ = self._advanced(E, res)
        for perm in ([3, 1, 0, 2], [2, 0], [1]):
            idx = jnp.asarray(perm, jnp.int32)
            sub = E.take_lanes(states, idx)
            for leaf, full in zip(jax.tree.leaves(sub),
                                  jax.tree.leaves(states)):
                for k, ln in enumerate(perm):
                    np.testing.assert_array_equal(np.asarray(leaf[k]),
                                                  np.asarray(full[ln]))
            back = E.put_lanes(states, idx, sub)
            for got, want in zip(jax.tree.leaves(back),
                                 jax.tree.leaves(states)):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))

    def test_subset_advance_equals_masked_lanes(self):
        """Advancing a gathered lane SUBSET and scattering it back must
        equal the all-lanes scan in which the untouched lanes ran
        fully-masked padding chunks (the mask no-op guarantee): the two
        suspend/resume shapes cannot drift."""
        E, res = self._setup()
        states, _ = self._advanced(E, res)
        rng = np.random.default_rng(3)
        keys = rng.integers(0, self.M * 16, size=(2, 1, self.C))
        more = jnp.asarray(np.stack([keys, keys], axis=-1), jnp.int32)
        idx = jnp.asarray([1, 3], jnp.int32)

        sub = E.take_lanes(states, idx)
        sub, _ = jax.jit(jax.vmap(res.scan_chunks))(
            sub, more, jnp.ones((2, 1, self.C), bool))
        got = E.put_lanes(states, idx, sub)

        full_chunks = jnp.zeros((self.L, 1, self.C, 2), jnp.int32)
        full_chunks = full_chunks.at[idx].set(more)
        full_mask = jnp.zeros((self.L, 1, self.C), bool).at[idx].set(True)
        want, _ = jax.jit(jax.vmap(res.scan_chunks))(states, full_chunks,
                                                     full_mask)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_take_put_roundtrip_on_mesh_of_1(self):
        """The same round-trip through the lane table on a one-device
        mesh (``core.distributed.make_lane_table``): scan, gather a
        permuted subset, scatter it back -- the per-session flush and
        checkpoint-restore path.  Every lane's snapshot then equals a
        one-shot ``make_executor`` run over that lane's chunks."""
        from repro.core import distributed as D
        E, res = self._setup()
        table = D.make_lane_table(res, jax.make_mesh((1,), ("lanes",)),
                                  self.L)
        rng = np.random.default_rng(5)
        keys = rng.integers(0, self.M * 16, size=(self.L, 2, self.C))
        chunks = jnp.asarray(np.stack([keys, keys], axis=-1), jnp.int32)
        states, _ = table.scan(table.init_states(), chunks,
                               jnp.ones((self.L, 2, self.C), bool))
        idx = jax.device_put(np.asarray([2, 0], np.int32), table.sharding)
        back = table.scatter(states, idx, table.gather(states, idx))
        run = E.make_executor(res.spec, self.M, self.X, self.C)
        for ln in range(self.L):
            want, _ = run(chunks[ln])
            np.testing.assert_array_equal(
                np.asarray(table.snapshot(back, ln)), np.asarray(want))


# ------------------------------------------------------- input pipeline
class TestChunkStreamContract:
    def test_empty_stream_pad_tail(self):
        """chunk_stream(pad_tail=True) on a ZERO-tuple stream: zero
        chunks, empty mask, num_tuples == 0 (not one all-masked chunk)."""
        from repro.data.pipeline import chunk_stream
        ts = chunk_stream(np.zeros((0, 2), np.int32), 8, pad_tail=True)
        assert ts.body.shape == (0, 8, 2)
        assert ts.mask.shape == (0, 8)
        assert ts.tail is None and ts.num_tuples == 0

    def test_empty_stream_legacy_shape(self):
        from repro.data.pipeline import chunk_stream
        ts = chunk_stream(np.zeros((0,), np.int64), 8, pad_tail=False)
        assert ts.body.shape == (0, 8)
        assert ts.tail is None and ts.num_tuples == 0

    def test_ragged_and_exact_multiples(self):
        from repro.data.pipeline import chunk_stream
        data = np.arange(20, dtype=np.int32)
        ts = chunk_stream(data, 8, pad_tail=True)
        assert ts.body.shape == (3, 8) and ts.num_tuples == 20
        assert ts.mask[-1].tolist() == [True] * 4 + [False] * 4
        exact = chunk_stream(data[:16], 8, pad_tail=True)
        assert exact.body.shape == (2, 8) and exact.mask.all()
