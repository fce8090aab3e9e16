"""The engine updates its lane table in place (docs/serving.md, "The lane
table").  The scatter of every flush, the fold of a secondary regrant
and the group reset of close and admission take the table donated, so
their executables alias the table into their output and copy no
``buffers`` leaf, on one device and on a four-device CPU mesh; and an
engine driven through every path that updates the table, a checkpoint
and a recovery included, answers bit for bit as a plain NumPy fold of
its appends, never touching a table it donated."""
from __future__ import annotations

import re
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent

from repro.apps import histo, hll
from repro.data import zipf
from repro.serve import DurableSessionEngine, SessionEngine

from tests.conftest import SMALL_CHUNK, SMALL_M

BINS, DOMAIN, HLL_P = 64, 1 << 16, 8

APPS = {
    "histo": (lambda: histo.make_spec(BINS, DOMAIN, SMALL_M),
              lambda keys: histo.oracle(keys, BINS, DOMAIN, SMALL_M)),
    "hll": (lambda: hll.make_spec(HLL_P, SMALL_M),
            lambda keys: hll.oracle(keys, HLL_P, SMALL_M)),
}


def _aliased_params(hlo_text: str) -> set:
    """Parameter numbers the module's ``input_output_alias`` maps into
    its output."""
    head = hlo_text.split("\n", 1)[0]
    block = head.partition("input_output_alias=")[2]
    block = block.partition("entry_computation_layout")[0]
    return {int(p) for p in re.findall(r"\}:\s*\((\d+),", block)}


def _copy_shapes(hlo_text: str) -> list:
    """Result shapes of the module's ``copy`` ops, as dim tuples."""
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"= \w+\[([\d,]*)\]\S* copy\(", hlo_text)]


def check_in_place(app: str, mesh=None) -> dict:
    """Build a small engine of ``app`` (``mesh=`` optional), warm it up
    and check each lane-table update program of the largest lane
    bucket: no ``copy`` of the ``buffers`` leaf (its per-device shape)
    and every table leaf aliased into the output.  Importable by a
    child process that holds a multi-device CPU mesh."""
    eng = SessionEngine(APPS[app][0](), num_pri=SMALL_M, num_sec=2,
                        chunk_size=SMALL_CHUNK, primary_slots=12,
                        secondary_slots=4, aot_buckets=1, mesh=mesh)
    info = eng.warmup(dtype=np.int32, feat_shape=(2,))
    m = 2 * len(eng._lane_buckets) + 1       # scatter + reset per bucket, fold
    assert info["table_in_place"] == f"{m} of {m}", info
    states, table = eng._states, eng._table
    n_leaves = len(jax.tree.leaves(states))
    buffers = (eng.lanes_per_device, *states.buffers.shape[1:])
    b = eng._lane_buckets[-1]
    idx = jax.device_put(
        np.tile(np.arange(b, dtype=np.int32), table.num_devices),
        table.sharding)
    programs = {
        "scatter": (table.scatter, (idx, table.gather(states, idx))),
        "reset": (table.reset, (idx,)),
        "fold": (table.fold, (eng.primary_slots, 0)),
    }
    for name, (program, args) in programs.items():
        text = program.lower(states, *args).compile().as_text()
        assert buffers not in _copy_shapes(text), (app, name, buffers)
        assert _aliased_params(text) >= set(range(n_leaves)), (app, name)
    return {"app": app, "table_in_place": info["table_in_place"]}


@pytest.mark.parametrize("app", sorted(APPS))
def test_update_programs_alias_the_table(app):
    check_in_place(app)


def test_update_programs_alias_the_table_four_device_mesh(cpu_mesh_env):
    code = (
        "import sys; sys.path[:0] = [%r, %r]\n"
        "from repro.core.distributed import lane_mesh\n"
        "from tests.test_lane_table_in_place import check_in_place\n"
        "for app in ('histo', 'hll'):\n"
        "    print('RESULT', check_in_place(app, lane_mesh(4)))\n"
        % (str(REPO), str(REPO / "src")))
    env = dict(cpu_mesh_env,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=str(REPO))
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert r.stdout.count("RESULT") == 2, r.stdout


@pytest.mark.parametrize("app", sorted(APPS))
def test_every_table_update_bit_exact(app, tmp_path):
    """Appends, storm admission, engine-wide flushes (the hot tenant
    alternating, so secondary lanes are re-granted and folded), queries
    through both flush tiers, a close and a reopen into its slot, a
    second ``warmup()``, a blocking checkpoint, more appends, then a
    recovery from disk and every close: each answer equals the oracle,
    and each flush donated the table it started from."""
    spec, oracle = APPS[app][0](), APPS[app][1]
    eng = DurableSessionEngine(
        spec, directory=tmp_path, num_pri=SMALL_M, num_sec=2,
        chunk_size=SMALL_CHUNK, primary_slots=4, secondary_slots=2,
        aot_buckets=1, checkpoint_every=0)
    keys = {}

    def feed(t, n, seed):
        d = zipf.zipf_tuples(n, DOMAIN, 1.5, seed=seed)
        keys[t] = np.concatenate([keys.get(t, np.zeros(0, np.int32)),
                                  d[:, 0]])
        return d

    def check(t, got):
        np.testing.assert_array_equal(np.asarray(got), oracle(keys[t]))

    tenants = ["t0", "t1", "t2"]
    firsts = [feed(t, 2 * SMALL_CHUNK + 9 * i, 10 + i)
              for i, t in enumerate(tenants)]
    sid = dict(zip(tenants, eng.open_batch(tenants, first=firsts)))
    for r in range(4):
        hot = tenants[r % 2]
        eng.append(sid[hot], feed(hot, 5 * SMALL_CHUNK + 13 * r, 100 + r))
        eng.append(sid["t2"], feed("t2", SMALL_CHUNK + 7 * r, 200 + r))
        before = eng._states
        eng.flush()
        assert before.buffers.is_deleted()        # donated, not copied
        if r % 2:
            check(hot, eng.query(sid[hot]))                   # session tier
        else:
            check("t2", eng.query(sid["t2"], scope="engine"))  # engine tier
    assert eng.slot_reschedules > 0                           # folds ran
    merged, _ = eng.close(sid.pop("t1"))                      # group reset
    check("t1", merged)
    sid["t3"] = eng.open("t3")
    eng.append(sid["t3"], feed("t3", 3 * SMALL_CHUNK + 1, 300))
    eng.warmup()                                              # second warmup
    eng.flush()
    check("t3", eng.query(sid["t3"]))
    eng.checkpoint(block=True)
    for i, t in enumerate(sid):
        eng.append(sid[t], feed(t, SMALL_CHUNK + 11 * i, 400 + i))
    rows = eng.telemetry_record(validate=False)["rows"]
    assert all(r["table_updates"] >= 1 for r in rows if r["scan_lanes"])
    eng.shutdown()                         # abandoned: recovery replays

    eng2 = SessionEngine.recover(spec, tmp_path)
    by_tenant = {s.tenant: k for k, s in eng2.sessions.items()
                 if not s.closed}
    assert sorted(by_tenant) == sorted(sid)
    for t in sorted(sid):
        check(t, eng2.query(by_tenant[t]))
        merged, _ = eng2.close(by_tenant[t])
        check(t, merged)
    eng2.shutdown()
