"""The skew-oblivious data-routing architecture ACROSS devices.

core/executor.py realizes the paper within one logical device (PEs =
buffer partitions).  This module is the cluster-scale version: one PE =
one mesh shard along the 'pe' axis, private buffer = that shard's HBM,
and the combiner/decoder/filter channel network = `jax.lax.all_to_all`
inside `shard_map`.  The Ditto pieces map 1:1:

  PrePE        each shard computes <dst, idx, value> for ITS slice of the
               stream (producers are sharded too, like the paper's N
               PrePEs feeding the routing network)
  mapper       per-producer round-robin redirect (the paper gives each
               mapper its own table+counter; no global coordination)
  routing      fixed-capacity all_to_all: producer p packs a [P, cap]
               send buffer by destination shard; one collective delivers
               every tuple to its designated PE
  PriPE/SecPE  each shard scatter-accumulates its received tuples into
               its private buffer partition (kernels/route_accumulate
               semantics)
  profiler     per-chunk receive-load histogram returned to the host;
               plan generation between chunks = the paper's CPU
               re-enqueue (scheduler.schedule_secpes)
  merger       SecPE shadow buffers are summed/maxed into their PriPEs
               from the plan at stream end

THE capacity trade (the paper's BRAM story at cluster scale): without a
plan, the all_to_all send buffer must be provisioned for the WORST-CASE
per-PE load (all tuples to one shard) or tuples drop; with X secondary
shards scheduled to the hot PEs, the same drop rate is reached with
near-uniform capacity -- measured by tests/test_distributed.py and
examples/distributed_ditto.py.

This module also hosts the SERVING-layer lift of the same mapping
(DESIGN.md §9): ``make_lane_sharded_executor`` shards the slot *lanes*
of ``serve.SessionEngine`` -- each lane a full resumable executor carry
-- along a mesh ``lanes`` axis, so one engine serves
``P x lanes_per_device`` tenants.  The §IV-B shadow-buffer merge of a
re-granted lane becomes a ``psum`` collective over the lanes axis (the
re-granted lane and its old owner's primary lane may live on different
devices).  Full mapping table + worked example: docs/distributed.md.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import executor as core_executor
from repro.core import mapper as core_mapper
from repro.core import scheduler as core_scheduler
from repro.core.types import DittoSpec, RoutePlan


def lane_mesh(chips: int):
    """The ``lanes`` mesh over the first ``chips`` devices, for
    ``serve.SessionEngine(mesh=...)``; ``None`` (the unsharded engine)
    for one chip.  Entry points take the chip count as an argument: it
    is never inferred from how many devices happen to be visible."""
    devices = jax.devices()
    if not 1 <= chips <= len(devices):
        raise ValueError(f"chips={chips}: JAX sees {len(devices)} device(s)")
    if chips == 1:
        return None
    return jax.make_mesh((chips,), ("lanes",), devices=devices[:chips])


def _auto_axes(mesh):
    """``mesh`` with every axis of type Auto.  Both lifts index sharded
    arrays with plain gathers and scatters (``executor.take_lanes`` /
    ``put_lanes``, the merger loop of ``run_stream``), which needs the
    compiler to propagate shardings; ``jax.make_mesh`` makes Explicit
    axes, under which such a gather is refused."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_distributed_executor(spec: DittoSpec, mesh, num_pri: int,
                              num_sec: int, *, capacity: int,
                              axis: str = "pe"):
    """Build the shard_map chunk step.

    The mesh `axis` size is the physical shard count; num_pri + num_sec
    <= mesh size (inactive shards receive nothing).  Returns
    ``chunk_fn(tuples, buffers, plan) -> (buffers, stats)`` operating on
    GLOBAL arrays: tuples [P*T_loc, 2] sharded over `axis`, buffers
    [P, *local] sharded over `axis`.  ``capacity`` is the per-(producer,
    destination) all_to_all budget -- tuples beyond it drop (counted).
    """
    mesh = _auto_axes(mesh)
    num_pe = dict(mesh.shape)[axis]          # physical shards
    assert num_pri + num_sec <= num_pe

    def step(tuples_loc, buffers_loc, table, counter):
        # local views: tuples_loc [T_loc, 2]; buffers_loc [1, *local]
        dst, idx, value = spec.pre(tuples_loc, num_pri)

        # --- per-producer mapper (paper Fig. 4): RR over the slot group
        plan = RoutePlan(assignment=jnp.zeros((num_sec,), jnp.int32),
                         table=table, counter=counter)
        rank, _ = core_mapper.occurrence_rank(
            dst, num_pri, jnp.zeros((num_pri,), jnp.int32))
        eff = core_mapper.redirect(plan, dst, rank)          # [T_loc]

        # --- pack the [P, cap] send buffer (capacity slotting per dest)
        oh = jax.nn.one_hot(eff, num_pe, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - oh,
                                  eff[:, None], axis=1)[:, 0]
        keep = pos < capacity
        dropped = jnp.sum(~keep)
        cell = jnp.where(keep, eff * capacity + pos, num_pe * capacity)
        payload = jnp.stack([idx, value], axis=1)            # [T_loc, 2]
        send = jnp.full((num_pe * capacity + 1, 2), -1, jnp.int32) \
            .at[cell].set(payload)[:-1].reshape(num_pe, capacity, 2)

        # --- the routing network: one all_to_all delivers everything
        recv = jax.lax.all_to_all(send, axis, 0, 0)          # [P, cap, 2]
        recv = recv.reshape(-1, 2)                           # [P*cap, 2]

        # --- PriPE/SecPE private-buffer update (add/max semantics)
        r_idx, r_val = recv[:, 0], recv[:, 1]
        valid = r_idx >= 0
        r_idx = jnp.where(valid, r_idx, 0)
        r_val = jnp.where(valid, r_val, 0 if spec.combine == "add"
                          else jnp.iinfo(jnp.int32).min)
        buf = buffers_loc.reshape(buffers_loc.shape[-1:]
                                  if buffers_loc.ndim == 2
                                  else buffers_loc.shape[1:])
        flat = buf.reshape(-1)
        flat = (flat.at[r_idx].add(r_val) if spec.combine == "add"
                else flat.at[r_idx].max(r_val))
        new_buf = flat.reshape(buf.shape)

        # --- profiler: my receive load + designated-load histogram share
        my_load = jnp.sum(valid)
        workload = jnp.zeros((num_pri,), jnp.int32).at[dst].add(1)
        workload = jax.lax.psum(workload, axis)              # global hist
        return (new_buf[None], my_load[None], dropped[None], workload)

    pspec = P(axis)
    return jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspec, pspec, P(), P()),
        out_specs=(pspec, pspec, pspec, P())))


def run_stream(spec: DittoSpec, mesh, tuples, num_pri: int, num_sec: int,
               *, capacity: int, axis: str = "pe",
               profile_chunks: int = 1):
    """Host-driven streaming loop (the paper's CPU side): run chunks,
    profile, generate the SecPE plan between chunks, merge at the end.

    tuples: [num_chunks, P*T_loc, 2].  Returns (merged buffers [num_pri,
    local], stats dict)."""
    num_pe = dict(mesh.shape)[axis]
    chunk_fn = make_distributed_executor(spec, mesh, num_pri, num_sec,
                                         capacity=capacity, axis=axis)
    buffers = spec.init_buffer(num_pe)
    plan = core_mapper.init_plan(num_pri, num_sec)
    hist = jnp.zeros((num_pri,), jnp.int32)
    assignment = jnp.full((num_sec,), -1, jnp.int32)
    loads, drops = [], []       # per chunk; plan active from profile_chunks
    for c, chunk in enumerate(tuples):
        buffers, load, dropped, workload = chunk_fn(
            jnp.asarray(chunk), buffers, plan.table, plan.counter)
        loads.append(int(jnp.max(load)))
        drops.append(int(jnp.sum(dropped)))
        hist = hist + workload
        if c + 1 == profile_chunks and num_sec:
            # the paper's re-enqueue: plan from the profiling window
            assignment = core_scheduler.schedule_secpes(hist, num_sec)
            plan = core_mapper.apply_schedule(
                core_mapper.init_plan(num_pri, num_sec), assignment)
    # merger: fold SecPE shadow buffers into their PriPEs
    merged = buffers[:num_pri]
    for j in range(num_sec):
        tgt = int(assignment[j])
        if tgt >= 0:
            if spec.combine == "add":
                merged = merged.at[tgt].add(buffers[num_pri + j])
            else:
                merged = merged.at[tgt].max(buffers[num_pri + j])
    pc = profile_chunks
    stats = {"max_load": max(loads),
             "max_load_postplan": max(loads[pc:]) if loads[pc:] else None,
             "dropped": sum(drops),
             "dropped_postplan": sum(drops[pc:]),
             "assignment": assignment}
    return merged, stats


# ---------------------------------------------------------------------------
# Lane-sharded serving executor (DESIGN.md §9): slot lanes across devices
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedLaneExecutor:
    """A lanes-stacked ``ResumableExecutor`` sharded across a mesh axis.

    Where ``make_distributed_executor`` maps one *PE* to one shard (the
    routed dataflow inside a single stream), this maps one *slot lane*
    -- a whole per-session executor carry -- to a mesh-shard slice, the
    serving-layer lift: P devices x lanes_per_device lanes, each lane an
    independent ``ExecState`` advanced by the vmapped chunk scan of its
    local shard.  No collective is needed on the flush path (lanes are
    independent streams); the collectives live in the slot re-scheduling
    path, where the §IV-B shadow-buffer merge crosses devices:

      run_lanes(states, chunks, mask)  one shard_map'd step: every shard
                                       vmaps the chunk scan over its
                                       local lanes (zero communication)
      fold_lane(states, src, dst)      merge-before-reassign as a
                                       collective: src's merged buffers
                                       are masked out locally, psum'd
                                       over the lanes axis, combined
                                       (add/max) into dst's primary
                                       region on dst's shard, and src is
                                       reset to fresh on its shard
      merge_lane(states, i)            replicated merged snapshot of one
                                       lane (the query path), same
                                       mask + psum selection
      reset_lane(states, i)            fresh-lane reset on i's shard

    ``num_lanes`` must divide evenly over the mesh axis (shard_map's
    even-split contract); ``serve.SessionEngine`` surfaces the
    divisibility requirement at construction.  A mesh of size 1 degenerates to the
    single-device engine bit-exactly: the vmap body is identical and the
    psum/selection collectives are identities over a 1-sized axis.
    """

    res: core_executor.ResumableExecutor
    mesh: object
    num_lanes: int
    axis: str
    lanes_per_device: int
    lane_sharding: NamedSharding
    run_lanes: Callable = dataclasses.field(repr=False)
    fold_lane: Optional[Callable] = dataclasses.field(repr=False)
    merge_lane: Callable = dataclasses.field(repr=False)
    reset_lane: Callable = dataclasses.field(repr=False)

    def init_states(self):
        """Fresh lanes-stacked ``ExecState``, device_put to the lane
        sharding (leaf axis 0 split over the mesh's lanes axis)."""
        stacked = core_executor.stack_states(self.res.init_state(),
                                             self.num_lanes)
        return jax.device_put(stacked, self.lane_sharding)

    def shard_states(self, states):
        """Re-pin a lanes-stacked state to the lane sharding (after a
        host-side or cross-shard edit, e.g. ``executor.put_lanes``)."""
        return jax.device_put(states, self.lane_sharding)

    def to_group_device(self, tree):
        """Move a gathered lane group (``executor.take_lanes``) onto the
        mesh's first device, where the per-session tier scans it: a
        Pallas kernel cannot be partitioned automatically, so a program
        that holds one runs on one device or under ``shard_map``."""
        return jax.device_put(tree, self.mesh.devices.flat[0])

    def replicate(self, tree):
        """Replicate a lane group over the mesh, ready to be scattered
        back into the lane-sharded state (``executor.put_lanes``)."""
        return jax.device_put(tree, NamedSharding(self.mesh, P()))


def make_lane_sharded_executor(res: core_executor.ResumableExecutor, mesh,
                               num_lanes: int, *,
                               axis: str = "lanes") -> ShardedLaneExecutor:
    """Build the shard_map'd lane operations for ``num_lanes`` slot lanes
    of ``res`` split over ``mesh``'s ``axis``.  See ShardedLaneExecutor."""
    num_dev = dict(mesh.shape)[axis]
    if num_lanes % num_dev:
        raise ValueError(
            f"num_lanes={num_lanes} must be divisible by the mesh's "
            f"'{axis}' axis size {num_dev} (shard_map splits the lanes "
            "axis evenly); pad primary/secondary slots up")
    lanes_per_device = num_lanes // num_dev
    mesh = _auto_axes(mesh)
    pspec = P(axis)
    sharding = NamedSharding(mesh, pspec)
    fresh = res.init_state()

    def local_ids():
        """Global lane ids of this shard's local slice."""
        return (jax.lax.axis_index(axis) * lanes_per_device
                + jnp.arange(lanes_per_device, dtype=jnp.int32))

    def select(tree, sel):
        """Zero out every local lane but ``sel``'s, then drop the lane
        axis by summation: at most one local lane matches, so this
        extracts it exactly (adding zeros is exact for int and float
        alike); shards owning no match produce an all-zero pytree."""
        def leaf(x):
            selb = sel.reshape(sel.shape + (1,) * (x.ndim - 1))
            return jnp.where(selb, x, jnp.zeros((), x.dtype)).sum(axis=0)
        return jax.tree.map(leaf, tree)

    def merge_selected(states, sel):
        """Merged snapshot of the ONE globally selected lane, computed
        with a single per-shard merge: select the lane's ExecState
        locally, merge it once, zero the result on non-owner shards
        (whose selected state is all-zero garbage), and let the caller
        psum.  Exact for any dtype -- only the owner contributes."""
        merged = res.merge_state_raw(select(states, sel))
        own = sel.any()
        return jax.tree.map(
            lambda x: jnp.where(own, x, jnp.zeros((), x.dtype)), merged)

    def set_lane(states, sel, value):
        """Overwrite the local lanes matching ``sel`` with ``value`` (a
        single-lane pytree, broadcast over the selector)."""
        def leaf(x, v):
            selb = sel.reshape(sel.shape + (1,) * (x.ndim - 1))
            return jnp.where(selb, v, x)
        return jax.tree.map(leaf, states, value)

    def _run(states, chunks, mask):
        return jax.vmap(res.scan_chunks)(states, chunks, mask)

    run_lanes = jax.jit(jax.shard_map(
        _run, mesh=mesh, in_specs=(pspec, pspec, pspec),
        out_specs=(pspec, pspec)))

    def _merge(states, i):
        picked = merge_selected(states, local_ids() == i)
        return jax.tree.map(lambda x: jax.lax.psum(x, axis), picked)

    merge_lane = jax.jit(jax.shard_map(
        _merge, mesh=mesh, in_specs=(pspec, P()), out_specs=P()))

    def _reset(states, i):
        return set_lane(states, local_ids() == i, fresh)

    reset_lane = jax.jit(jax.shard_map(
        _reset, mesh=mesh, in_specs=(pspec, P()), out_specs=pspec))

    fold_lane = None
    if res.spec.merge is None:        # decomposable buffers only (add/max)
        def _fold(states, src, dst):
            gid = local_ids()
            # src's merged contribution, delivered to every shard: the
            # §IV-B merge-before-reassign expressed as a collective
            contrib = jax.lax.psum(merge_selected(states, gid == src), axis)
            own = (gid == dst).reshape((-1,) + (1,) * contrib.ndim)
            bufs = states.buffers                    # [L, M+X, *local]
            m = res.num_pri
            if res.spec.combine == "add":
                bufs = bufs.at[:, :m].add(jnp.where(own, contrib, 0))
            else:
                neutral = (jnp.iinfo(bufs.dtype).min
                           if jnp.issubdtype(bufs.dtype, jnp.integer)
                           else -jnp.inf)
                bufs = bufs.at[:, :m].max(jnp.where(own, contrib, neutral))
            states = dataclasses.replace(states, buffers=bufs)
            return set_lane(states, gid == src, fresh)

        fold_lane = jax.jit(jax.shard_map(
            _fold, mesh=mesh, in_specs=(pspec, P(), P()), out_specs=pspec))

    return ShardedLaneExecutor(
        res=res, mesh=mesh, num_lanes=num_lanes, axis=axis,
        lanes_per_device=lanes_per_device, lane_sharding=sharding,
        run_lanes=run_lanes, fold_lane=fold_lane, merge_lane=merge_lane,
        reset_lane=reset_lane)
