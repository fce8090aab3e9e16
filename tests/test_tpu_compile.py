"""Compile the main path's Pallas kernels for a TPU v5e without one.

The TPU compiler is installed next to JAX, so these tests compile for a
described ``v5e:2x2`` chip (nothing runs): ``route_accumulate`` for add
and max in int32 and float32 and ``cms_update`` at the widths the
serving smoke uses, plus one vmapped histogram lane-scan step the way
``SessionEngine`` builds it and one HyperLogLog lane bucket of the
hll-16k deployment (gather, scan, scatter).  Interpret-mode tests
cannot see what these catch: Mosaic refusing a layout, an int32 matmul
or too much memory.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every pytest
worker imports this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps import histo, hll
from repro.core import executor as core_executor
from repro.kernels import dispatch
from repro.kernels.cms_update import cms_update
from repro.kernels.route_accumulate import route_accumulate

V5E_HBM = 16 << 30
T = 4096                         # tuples per chunk
CELLS = 20 * 16384               # smoke histogram lane: (M + X) x bins/M


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


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
