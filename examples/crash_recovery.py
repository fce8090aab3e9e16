"""Crash-restart smoke: SIGKILL a durable SessionEngine mid-stream,
recover it, and verify every answer against the uninterrupted oracle
(DESIGN.md §10, docs/durability.md).

    PYTHONPATH=src python examples/crash_recovery.py [--chips N] [workdir]

The script is its own harness: the parent re-runs this file with
``--child``, and the CHILD process drives a ``serve.DurableSessionEngine``
(Zipf-1.5 tenants, one deliberately hot so secondary-lane grants are
active, ragged appends, auto-checkpoint every 2 flushes) and then sends
itself SIGKILL at a fixed point PAST the last checkpoint -- a real
process death with un-checkpointed WAL tail on disk.  The parent then

  1. asserts the child actually died by SIGKILL,
  2. recovers the engine from the same directory
     (``SessionEngine.recover``) and asserts only the WAL *tail*
     replayed (replayed tuples < the full stream),
  3. asserts every open session's ``query()`` is bit-exact vs the numpy
     oracle over everything the child appended before dying,
  4. keeps streaming post-recovery and closes every session, again
     oracle-exact.

Multi-device: with ``--chips N`` (N in {2,4,8}; on CPU under
``XLA_FLAGS=--xla_force_host_platform_device_count=N``, CI uses 4) both
processes run the engine with the slot lanes split over a ``lanes``
mesh axis of N devices, so the recovery restores each device's share
through the lane table's scatter.  The parent touches JAX
only after the child is gone: one process at a time holds a chip.
"""
import argparse
import os
import signal
import subprocess
import sys
import tempfile

import numpy as np

PRE_ROUNDS, POST_ROUNDS, TENANTS = 3, 2, 6
NUM_PRI, NUM_SEC, CHUNK = 8, 2, 256
BINS, DOMAIN = 64, 1 << 16
PRIMARY_SLOTS, SECONDARY_SLOTS = 6, 2    # 8 lanes: shards over 1/2/4/8 devs
HOT = 0


def batch(r: int, t: int) -> np.ndarray:
    """The deterministic (round, tenant) append -- parent and child
    derive the identical stream from seeds alone."""
    from repro.data.zipf import zipf_tuples
    n = (5 if t == HOT else 1) * CHUNK + (37 * r + 11 * t) % CHUNK + 1
    return zipf_tuples(n, DOMAIN, 1.5, seed=1000 * r + t)


def make_engine(dirpath: str, recovering: bool, chips: int):
    from repro.apps import histo
    from repro.core.distributed import lane_mesh
    from repro.serve import DurableSessionEngine, SessionEngine
    mesh = lane_mesh(chips)
    spec = histo.make_spec(BINS, DOMAIN, NUM_PRI)
    if recovering:
        return spec, SessionEngine.recover(spec, dirpath, mesh=mesh)
    return spec, DurableSessionEngine(
        spec, directory=dirpath, num_pri=NUM_PRI, num_sec=NUM_SEC,
        chunk_size=CHUNK, primary_slots=PRIMARY_SLOTS,
        secondary_slots=SECONDARY_SLOTS, checkpoint_every=2, mesh=mesh)


def child(dirpath: str, chips: int):
    _, eng = make_engine(dirpath, recovering=False, chips=chips)
    sids = {t: eng.open(f"t{t}") for t in range(TENANTS)}
    for r in range(PRE_ROUNDS):
        for t in sids:
            eng.append(sids[t], batch(r, t))
        eng.flush()          # auto-checkpoint fires at flush 2
    for t in sids:           # the un-checkpointed ragged tail
        eng.append(sids[t], batch(PRE_ROUNDS, t))
    eng._mgr.wait()          # the flush-2 checkpoint is fully on disk
    os.kill(os.getpid(), signal.SIGKILL)     # mid-stream, no cleanup


def main(workdir, chips: int):
    workdir = workdir or tempfile.mkdtemp(prefix="crash_recovery_")
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--chips", str(chips), workdir],
        env=os.environ.copy(), timeout=560)
    assert r.returncode == -signal.SIGKILL, \
        f"child exited {r.returncode}, expected SIGKILL"
    print("OK child SIGKILLed mid-stream")

    from repro.apps import histo
    spec, eng = make_engine(workdir, recovering=True, chips=chips)
    print(f"recovering across {eng.num_lanes // eng.lanes_per_device} "
          f"device(s) x {eng.lanes_per_device} lanes")
    appended = {t: [batch(r, t) for r in range(PRE_ROUNDS + 1)]
                for t in range(TENANTS)}
    total = sum(len(b) for bs in appended.values() for b in bs)
    info = eng.recovery_info
    assert 0 < info["replayed_tuples"] < total, info
    print(f"OK WAL tail only: replayed {info['replayed_tuples']}/{total} "
          f"tuples ({info['replayed_records']} records past checkpoint "
          f"step {info['checkpoint_step']})")

    sids = {s.tenant: sid for sid, s in eng.sessions.items() if not s.closed}
    for t in range(TENANTS):
        keys = np.concatenate([b[:, 0] for b in appended[t]])
        np.testing.assert_array_equal(
            np.asarray(eng.query(sids[f"t{t}"])),
            histo.oracle(keys, BINS, DOMAIN, NUM_PRI))
    print(f"OK recovered answers oracle-exact ({TENANTS} sessions, "
          "Zipf 1.5, ragged appends)")

    for r in range(PRE_ROUNDS + 1, PRE_ROUNDS + 1 + POST_ROUNDS):
        for t in range(TENANTS):
            b = batch(r, t)
            eng.append(sids[f"t{t}"], b)
            appended[t].append(b)
        eng.flush()
    for t in range(TENANTS):
        keys = np.concatenate([b[:, 0] for b in appended[t]])
        merged, stats = eng.close(sids[f"t{t}"])
        np.testing.assert_array_equal(
            np.asarray(merged), histo.oracle(keys, BINS, DOMAIN, NUM_PRI))
        if t == HOT:
            assert stats["sec_lane_flushes"] > 0, \
                "hot tenant never used a granted secondary lane"
    print("OK post-recovery stream + close oracle-exact "
          f"({POST_ROUNDS} more rounds)")
    eng.shutdown()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("workdir", nargs="?", default=None)
    ap.add_argument("--chips", type=int, default=1,
                    help="shard the lanes over this many devices")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        child(args.workdir, args.chips)
    else:
        main(args.workdir, args.chips)
