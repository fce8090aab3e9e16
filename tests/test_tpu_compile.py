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

import re

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


def _one_chip_mesh(topo):
    return Mesh(np.array(topo.devices[:1]), ("lanes",))


def test_hll_lane_bucket_compiles(topo):
    """One engine-wide flush of the hll-16k deployment, the way the
    active-lane flush runs it: an 8-lane bucket gathered out of the
    16,448-lane table of p = 14 registers, scanned with the native
    ``max`` kernel, scattered back -- the lane table's programs on one
    chip, each within its memory."""
    lanes, bucket = 16448, 8
    spec = hll.make_spec(14, 16)
    res = core_executor.make_resumable_executor(
        spec, 16, 4, T, kernel_backend=dispatch.PALLAS)
    table = core_distributed.make_lane_table(res, _one_chip_mesh(topo),
                                             lanes)
    on = table.sharding
    full, sub = _table(res, lanes, on), _table(res, bucket, on)
    idx = jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=on)
    chunks = jax.ShapeDtypeStruct((bucket, 1, T, 2), jnp.int32, sharding=on)
    mask = jax.ShapeDtypeStruct((bucket, 1, T), jnp.bool_, sharding=on)
    compiled = [table.gather.lower(full, idx).compile(),
                table.scan.lower(sub, chunks, mask).compile(),
                table.scatter.lower(full, idx, sub).compile()]
    assert "tpu_custom_call" in compiled[1].as_text()
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


def _check_updates_in_place(make_spec, lanes, mesh):
    """The lane table's scatter, reset and fold of an 8-lane-per-device
    bucket, donated as built, each write every device's share of the
    table in place, with no collective but the fold's psum."""
    res = core_executor.make_resumable_executor(
        make_spec(), 16, 4, T, kernel_backend=dispatch.PALLAS)
    table = core_distributed.make_lane_table(res, mesh, lanes)
    n_dev = table.num_devices
    replicated = NamedSharding(table.mesh, PartitionSpec())
    full = _table(res, lanes, table.sharding)
    sub = _table(res, 8 * n_dev, table.sharding)
    idx = jax.ShapeDtypeStruct((8 * n_dev,), jnp.int32,
                               sharding=table.sharding)
    lane = jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)
    local_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(full)) // n_dev
    local_buffers = (lanes // n_dev, *full.buffers.shape[1:])
    for program, args in ((table.scatter, (idx, sub)),
                          (table.reset, (idx,)),
                          (table.fold, (lane, lane))):
        compiled = program.lower(full, *args).compile()
        assert "all-gather" not in compiled.as_text()
        _assert_in_place(compiled, local_buffers, local_bytes)


@pytest.mark.parametrize("deployment", sorted(DEPLOYMENTS))
def test_lane_table_updates_in_place(topo, deployment):
    """The engine's scatter, group reset and secondary fold write the
    1.36-1.43-GB table of each deployment in place on one chip: an
    8-lane bucket, not a copy of the table."""
    make_spec, lanes = DEPLOYMENTS[deployment]
    _check_updates_in_place(make_spec, lanes, _one_chip_mesh(topo))


def test_lane_table_updates_in_place_mesh(topo):
    """The same on the 2x2 mesh of the four-chip engine: the same
    programs write each chip's quarter of the histo-1k table in
    place."""
    make_spec, lanes = DEPLOYMENTS["histo-1k"]
    _check_updates_in_place(make_spec, lanes,
                            Mesh(np.array(topo.devices[:4]), ("lanes",)))

