"""Cross-device Ditto (core/distributed.py): shard_map + all_to_all for
the routed dataflow, and the lane-sharded serving executor
(make_lane_sharded_executor, DESIGN.md §9).

Multi-device execution needs its own process (pytest's jax is pinned to
1 CPU device), so the heavy tests drive the examples under 8 host
devices in a subprocess; the mesh-of-1 degenerate case (which must be
bit-exact vs the unsharded path) runs in-process.
"""
from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent

from tests.conftest import SMALL_CHUNK, SMALL_M


@pytest.mark.slow
def test_distributed_ditto_example_exact_and_skew_robust(cpu_mesh_env):
    r = subprocess.run(
        [sys.executable, str(REPO / "examples" / "distributed_ditto.py")],
        env=cpu_mesh_env,
        capture_output=True, text=True, timeout=560, cwd=str(REPO))
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    # uniform: both variants exact, no drops
    assert out.count("(oracle-exact)") >= 2
    lines = [l for l in out.splitlines() if l.strip().startswith("2.0")]
    x0 = next(l for l in lines if "X=0" in l)
    x2 = next(l for l in lines if "X=2" in l)
    drops0 = int(x0.split()[3])
    drops2 = int(x2.split()[3])
    load0 = int(x0.split()[2])
    load2 = int(x2.split()[2])
    # the paper's claim at cluster scale: once the plan is in, the skewed
    # stream fits the uniform capacity (no post-plan drops, lower max
    # receive load); without SecPEs it drops heavily
    assert drops0 > 1000
    assert drops2 == 0
    assert load2 < load0


@pytest.mark.slow
def test_distributed_sessions_example_multi_device(cpu_mesh_env):
    """Acceptance: on 8 fake devices one engine serves 12 sessions with
    2 lanes/device (more than one device's lane budget), Zipf 1.5 with
    ragged appends, bit-exact vs the single-device engine AND the
    oracle, with cross-device §IV-B lane folds actually happening."""
    r = subprocess.run(
        [sys.executable, str(REPO / "examples" / "distributed_sessions.py")],
        env=cpu_mesh_env,
        capture_output=True, text=True, timeout=560, cwd=str(REPO))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK bit-exact vs single-device engine" in r.stdout
    assert "OK oracle-exact" in r.stdout
    assert "slot re-grants" in r.stdout


# ------------------------------------------------ lane-sharded executor
class TestShardedLaneExecutor:
    """Mesh-of-1 ShardedLaneExecutor ops vs their local (vmap / indexed)
    equivalents: the degenerate sharding must be bit-exact, because the
    multi-device runs in the subprocess tests above rely on the same
    code path."""

    NUM_LANES = 4

    def _build(self, small_spec):
        from repro.core import distributed as D
        from repro.core import executor as E
        res = E.make_resumable_executor(small_spec, SMALL_M, 2, SMALL_CHUNK)
        mesh = jax.make_mesh((1,), ("lanes",))
        return res, D.make_lane_sharded_executor(res, mesh, self.NUM_LANES)

    def _chunks(self, zipf_dataset):
        data = np.stack([
            zipf_dataset(2 * SMALL_CHUNK, 1 << 16, 0.5 * ln, seed=ln)
            .reshape(2, SMALL_CHUNK, 2) for ln in range(self.NUM_LANES)])
        mask = np.ones(data.shape[:3], bool)
        mask[1, 1, 40:] = False            # one ragged lane
        return jnp.asarray(data), jnp.asarray(mask)

    def test_run_lanes_matches_local_vmap(self, small_spec, zipf_dataset):
        from repro.core import executor as E
        res, sh = self._build(small_spec)
        chunks, mask = self._chunks(zipf_dataset)
        got_states, got_stats = sh.run_lanes(sh.init_states(), chunks, mask)
        want_states, want_stats = jax.jit(jax.vmap(res.scan_chunks))(
            E.stack_states(res.init_state(), self.NUM_LANES), chunks, mask)
        for g, w in zip(jax.tree.leaves(got_states),
                        jax.tree.leaves(want_states)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        np.testing.assert_array_equal(np.asarray(got_stats.max_load),
                                      np.asarray(want_stats.max_load))

    def test_merge_and_reset_match_indexed(self, small_spec, zipf_dataset):
        res, sh = self._build(small_spec)
        chunks, mask = self._chunks(zipf_dataset)
        states, _ = sh.run_lanes(sh.init_states(), chunks, mask)
        for i in range(self.NUM_LANES):
            want = res.merge_state(
                jax.tree.map(lambda x: x[i], states))
            np.testing.assert_array_equal(
                np.asarray(sh.merge_lane(states, i)), np.asarray(want))
        reset = sh.reset_lane(states, 2)
        fresh = res.init_state()
        for leaf, f in zip(jax.tree.leaves(reset), jax.tree.leaves(fresh)):
            np.testing.assert_array_equal(np.asarray(leaf)[2], np.asarray(f))
        # other lanes untouched
        np.testing.assert_array_equal(np.asarray(reset.buffers)[0],
                                      np.asarray(states.buffers)[0])

    def test_fold_lane_is_merge_before_reassign(self, small_spec,
                                                zipf_dataset):
        """fold(src, dst) == add src's merged contribution into dst's
        primary region, then reset src -- the §IV-B collective."""
        res, sh = self._build(small_spec)
        chunks, mask = self._chunks(zipf_dataset)
        states, _ = sh.run_lanes(sh.init_states(), chunks, mask)
        src, dst = 3, 0
        contrib = np.asarray(res.merge_state(
            jax.tree.map(lambda x: x[src], states)))
        folded = sh.fold_lane(states, src, dst)
        want = np.array(states.buffers[dst])
        want[:SMALL_M] = want[:SMALL_M] + contrib
        np.testing.assert_array_equal(np.asarray(folded.buffers)[dst], want)
        np.testing.assert_array_equal(
            np.asarray(folded.buffers)[src],
            np.asarray(res.init_state().buffers))
        # the fold conserves tuples: total merged mass is unchanged
        total = sum(np.asarray(sh.merge_lane(folded, i)).sum()
                    for i in range(self.NUM_LANES))
        total0 = sum(np.asarray(sh.merge_lane(states, i)).sum()
                     for i in range(self.NUM_LANES))
        assert total == total0

    def test_lane_mesh_takes_an_explicit_chip_count(self):
        """One chip means the unsharded engine; a count beyond the
        visible devices is refused instead of silently shrunk."""
        from repro.core import distributed as D
        assert D.lane_mesh(1) is None
        with pytest.raises(ValueError):
            D.lane_mesh(len(jax.devices()) + 1)
        with pytest.raises(ValueError):
            D.lane_mesh(0)

    def test_missing_axis_and_lane_split(self, small_spec):
        from repro.core import distributed as D
        from repro.core import executor as E
        res = E.make_resumable_executor(small_spec, SMALL_M, 2, SMALL_CHUNK)
        mesh = jax.make_mesh((1,), ("pe",))
        with pytest.raises(KeyError):
            D.make_lane_sharded_executor(res, mesh, 4, axis="lanes")
        sh = D.make_lane_sharded_executor(
            res, jax.make_mesh((1,), ("lanes",)), 4)
        assert sh.lanes_per_device == 4
