"""Compile the main path's Pallas kernels for a TPU v5e without one.

The TPU compiler is installed next to JAX, so these tests compile for a
described ``v5e:2x2`` chip (nothing runs): ``route_accumulate`` for add
and max in int32 and float32 and ``cms_update`` at the widths the
serving smoke uses, plus one vmapped histogram lane-scan step the way
``SessionEngine`` builds it and one HyperLogLog lane bucket of the
hll-16k deployment (gather, scan, scatter), and the engine's lane-table
update programs at both deployments' sizes, on one chip and on the
2x2 mesh, written in place.  Interpret-mode tests cannot see what these
catch: Mosaic refusing a layout, an int32 matmul, too much memory, or a
relayout of the whole table around a scatter.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every pytest
worker imports this file.
"""
from __future__ import annotations

import functools
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.apps import histo, hll
from repro.core import distributed as core_distributed
from repro.core import executor as core_executor
from repro.kernels import dispatch
from repro.kernels.cms_update import cms_update
from repro.kernels.route_accumulate import route_accumulate

V5E_HBM = 16 << 30
T = 4096                         # tuples per chunk
CELLS = 20 * 16384               # smoke histogram lane: (M + X) x bins/M


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        from jax.experimental import topologies
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("combine", ["add", "max"])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_route_accumulate_compiles(one_chip, combine, dtype):
    idx = jax.ShapeDtypeStruct((T,), jnp.int32, sharding=one_chip)
    val = jax.ShapeDtypeStruct((T,), dtype, sharding=one_chip)
    _compile(lambda i, v: route_accumulate(i, v, CELLS, combine), idx, val)


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_cms_update_compiles(one_chip, dtype):
    eff = jax.ShapeDtypeStruct((T,), jnp.int32, sharding=one_chip)
    cols = jax.ShapeDtypeStruct((T, 4), jnp.int32, sharding=one_chip)
    val = jax.ShapeDtypeStruct((T,), dtype, sharding=one_chip)
    _compile(lambda e, c, v: cms_update(e, c, v, 20, 4, 16384),
             eff, cols, val)


def test_histo_lane_scan_compiles(one_chip):
    """One flush step of the smoke's histogram engine, 8 lanes wide:
    ``jit(ResumableExecutor.scan_lanes)`` as ``SessionEngine`` runs it,
    with the native kernel pinned (on a CPU host the dispatcher would
    resolve to ``jnp``)."""
    lanes = 8
    spec = histo.make_spec(262144, 1 << 22, 16)
    res = core_executor.make_resumable_executor(
        spec, 16, 4, T, kernel_backend=dispatch.PALLAS)
    states = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: core_executor.stack_states(res.init_state(),
                                                          lanes)))
    chunks = jax.ShapeDtypeStruct((lanes, 1, T, 2), jnp.int32,
                                  sharding=one_chip)
    mask = jax.ShapeDtypeStruct((lanes, 1, T), jnp.bool_, sharding=one_chip)
    mem = _compile(res.scan_lanes, states, chunks, mask).memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM


def test_hll_lane_bucket_compiles(one_chip):
    """One engine-wide flush of the hll-16k deployment, the way the
    active-lane flush runs it: an 8-lane bucket gathered out of the
    16,448-lane table of p = 14 registers, scanned with the native
    ``max`` kernel, scattered back -- each program within one chip."""
    lanes, bucket = 16448, 8
    spec = hll.make_spec(14, 16)
    res = core_executor.make_resumable_executor(
        spec, 16, 4, T, kernel_backend=dispatch.PALLAS)

    def states(n):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip),
            jax.eval_shape(lambda: core_executor.stack_states(
                res.init_state(), n)))

    table, sub = states(lanes), states(bucket)
    idx = jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip)
    chunks = jax.ShapeDtypeStruct((bucket, 1, T, 2), jnp.int32,
                                  sharding=one_chip)
    mask = jax.ShapeDtypeStruct((bucket, 1, T), jnp.bool_,
                                sharding=one_chip)
    compiled = [
        jax.jit(core_executor.take_lanes).lower(table, idx).compile(),
        _compile(res.scan_lanes, sub, chunks, mask),
        jax.jit(core_executor.put_lanes).lower(table, idx, sub).compile()]
    for c in compiled:
        mem = c.memory_analysis()
        assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) < V5E_HBM


# the deployments of bench/configs: (spec, lanes)
DEPLOYMENTS = {
    "histo-1k": (lambda: histo.make_spec(262144, 1 << 22, 16), 1088),
    "hll-16k": (lambda: hll.make_spec(14, 16), 16448),
}


def _table(res, lanes, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(lambda: core_executor.stack_states(res.init_state(),
                                                          lanes)))


def _assert_in_place(compiled, buffers_shape, table_bytes):
    """The donated table is aliased into the output, and no op copies or
    re-lays out its ``buffers`` leaf: the scratch is a few lanes' worth,
    not a table's."""
    text = compiled.as_text()
    dims = ",".join(str(d) for d in buffers_shape)
    relaid = re.findall(rf"= s32\[{dims}\]\S* (?:copy|fusion|transpose)\(",
                        text)
    assert not relaid, relaid
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= table_bytes
    assert mem.temp_size_in_bytes < table_bytes // 100


@pytest.mark.parametrize("deployment", sorted(DEPLOYMENTS))
def test_lane_table_updates_in_place(one_chip, deployment):
    """``SessionEngine``'s scatter (``executor.put_lanes``), group reset
    (``executor.reset_lanes``) and secondary fold, donated as the engine
    binds them, write the 1.36-1.43-GB table of each deployment in place
    on one chip: an 8-lane bucket, not a copy of the table."""
    make_spec, lanes = DEPLOYMENTS[deployment]
    spec = make_spec()
    res = core_executor.make_resumable_executor(
        spec, 16, 4, T, kernel_backend=dispatch.PALLAS)
    table = _table(res, lanes, one_chip)
    sub = _table(res, 8, one_chip)
    idx = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    lane = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    fresh = res.init_state()
    engine = types.SimpleNamespace(_res=res, spec=spec, num_pri=16,
                                   _fresh=fresh)
    from repro.serve import SessionEngine
    fold = functools.partial(SessionEngine._fold_lane_impl, engine)
    table_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(table))
    donated = functools.partial(jax.jit, donate_argnums=0)
    for fn, args in (
            (core_executor.put_lanes, (idx, sub)),
            (lambda s, i: core_executor.reset_lanes(s, i, fresh), (idx,)),
            (fold, (lane, lane))):
        _assert_in_place(donated(fn).lower(table, *args).compile(),
                         table.buffers.shape, table_bytes)


def test_lane_table_updates_in_place_mesh(topo):
    """The same on the 2x2 mesh of the four-chip engine: the
    ``ShardedLaneExecutor``'s ``put_local`` and ``fold_lane`` and the
    engine's lane-sharded group reset, each donated, write each chip's
    quarter of the histo-1k table in place."""
    spec = histo.make_spec(262144, 1 << 22, 16)
    res = core_executor.make_resumable_executor(
        spec, 16, 4, T, kernel_backend=dispatch.PALLAS)
    lanes, n_dev = 1088, 4
    mesh = Mesh(np.array(topo.devices[:n_dev]), ("lanes",))
    sh = core_distributed.make_lane_sharded_executor(res, mesh, lanes)
    replicated = NamedSharding(sh.mesh, PartitionSpec())
    table = _table(res, lanes, sh.lane_sharding)
    sub = _table(res, 8 * n_dev, sh.lane_sharding)
    idx = jax.ShapeDtypeStruct((8 * n_dev,), jnp.int32,
                               sharding=sh.lane_sharding)
    group = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=replicated)
    lane = jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)
    fresh = res.init_state()
    local_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(table)) // n_dev
    local_buffers = (lanes // n_dev, *table.buffers.shape[1:])
    donated = functools.partial(jax.jit, donate_argnums=0)
    for fn, args in (
            (sh.put_local, (idx, sub)),
            (sh.fold_lane, (lane, lane)),
            (lambda s, i: core_executor.reset_lanes(s, i, fresh), (group,))):
        compiled = donated(fn).lower(table, *args).compile()
        assert "all-gather" not in compiled.as_text()
        _assert_in_place(compiled, local_buffers, local_bytes)
