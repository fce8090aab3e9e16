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
(DESIGN.md §9): ``make_lane_table`` splits the slot *lanes* of
``serve.SessionEngine`` -- each lane a full resumable executor carry --
over a mesh ``lanes`` axis, so one engine serves ``P x
lanes_per_device`` tenants; one device is the mesh of one.  The §IV-B
shadow-buffer merge of a re-granted lane becomes a ``psum`` collective
over the lanes axis (the re-granted lane and its old owner's primary
lane may live on different devices).  Full mapping table + worked
example: docs/distributed.md.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import executor as core_executor
from repro.core import mapper as core_mapper
from repro.core import scheduler as core_scheduler
from repro.core.types import DittoSpec, RoutePlan


def lane_mesh(chips: int):
    """The ``lanes`` mesh over the first ``chips`` devices, for
    ``serve.SessionEngine(mesh=...)``; ``None`` for one chip, which the
    engine takes as the one-device mesh over the default device.  Entry
    points take the chip count as an argument: it is never inferred
    from how many devices happen to be visible."""
    devices = jax.devices()
    if not 1 <= chips <= len(devices):
        raise ValueError(f"chips={chips}: JAX sees {len(devices)} device(s)")
    if chips == 1:
        return None
    return jax.make_mesh((chips,), ("lanes",), devices=devices[:chips])


def _auto_axes(mesh):
    """``mesh`` with every axis of type Auto.  Both lifts index arrays
    with plain gathers and scatters (``executor.take_lanes`` /
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
# The serving engine's lane table (DESIGN.md §9): slot lanes across devices
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LaneTable:
    """The lane table of ``serve.SessionEngine``: ``num_lanes`` slot
    lanes, each a whole ``ExecState`` of ``res``, stacked on a leading
    axis that is split over the mesh's ``lanes`` axis -- one device
    holding the whole table, or P devices x ``lanes_per_device`` lanes.

    Where ``make_distributed_executor`` maps one *PE* to one shard (the
    routed dataflow inside a single stream), this maps one *slot lane* to
    a shard slice.  Each operation is one body over a device's slice,
    lifted by ``shard_map``; the same programs run on one device and on
    many.  Lane ids are per device for the bucket operations and global
    for the one-lane ones:

      gather(states, idx)        the lane bucket of a flush: each device
                                 takes its own local lanes ``idx`` (an
                                 equal count per device; no lane crosses
                                 devices) -- ``executor.take_lanes``
      scatter(states, idx, sub)  its inverse, the table donated --
                                 ``executor.put_lanes``
      scan(sub, chunks, mask)    each device vmaps the chunk scan over its
                                 bucket rows (no communication) --
                                 ``ResumableExecutor.scan_lanes``
      snapshot(states, i)        replicated merged snapshot of global lane
                                 ``i`` (the query path): its owner reads
                                 the one lane, the others give zeros, and
                                 a psum over the axis combines them
      fold(states, src, dst)     §IV-B merge-before-reassign, the table
                                 donated: src's snapshot (as above) is
                                 combined (add/max) into dst's primary
                                 region on dst's device and src is reset
                                 on its own; ``None`` for apps whose
                                 buffers do not combine (``spec.merge``)
      reset(states, idx)         lanes ``idx`` (local, ``-1`` pads last)
                                 to fresh state, the table donated --
                                 ``executor.reset_lanes``

    The donated programs write the touched lanes in place: the caller
    must rebind its table to their result.  ``num_lanes`` must divide
    evenly over the mesh axis (shard_map's even-split contract).
    """

    res: core_executor.ResumableExecutor
    mesh: object
    num_lanes: int
    lanes_per_device: int
    sharding: NamedSharding
    gather: Callable = dataclasses.field(repr=False)
    scatter: Callable = dataclasses.field(repr=False)
    scan: Callable = dataclasses.field(repr=False)
    snapshot: Callable = dataclasses.field(repr=False)
    fold: Optional[Callable] = dataclasses.field(repr=False)
    reset: Callable = dataclasses.field(repr=False)

    @property
    def num_devices(self) -> int:
        return self.num_lanes // self.lanes_per_device

    def init_states(self):
        """Fresh lanes-stacked ``ExecState`` on the lane sharding."""
        stacked = core_executor.stack_states(self.res.init_state(),
                                             self.num_lanes)
        return jax.device_put(stacked, self.sharding)


def make_lane_table(res: core_executor.ResumableExecutor, mesh,
                    num_lanes: int) -> LaneTable:
    """Build the lane operations for ``num_lanes`` slot lanes of ``res``
    split over ``mesh``'s ``lanes`` axis; ``mesh=None`` is a one-device
    mesh over the default device.  See LaneTable."""
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()[:1]), ("lanes",))
    if "lanes" not in dict(mesh.shape):
        raise ValueError(f"mesh has no 'lanes' axis; mesh axes: "
                         f"{tuple(dict(mesh.shape))}")
    num_dev = dict(mesh.shape)["lanes"]
    if num_lanes % num_dev:
        raise ValueError(
            f"num_lanes={num_lanes} must be divisible by the mesh's "
            f"'lanes' axis size {num_dev} (shard_map splits the lanes "
            "axis evenly); pad primary/secondary slots up")
    lpd = num_lanes // num_dev
    mesh = _auto_axes(mesh)
    lanes, one = P("lanes"), P()
    fresh = res.init_state()

    def lift(body, in_specs, out_specs, donate=False):
        # explicit output shardings: on one device the compiler may hand
        # a leaf back replicated, and a table that changed sharding
        # would miss the jit caches that ``warmup()`` filled
        out = jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                           out_specs, is_leaf=lambda x: isinstance(x, P))
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs),
                       out_shardings=out,
                       donate_argnums=0 if donate else ())

    def local(i):
        """Local index of global lane ``i`` on this device (0 where the
        device does not hold it) and whether it holds it."""
        base = jax.lax.axis_index("lanes") * lpd
        own = (i >= base) & (i < base + lpd)
        return jnp.where(own, i - base, 0), own

    def lane(x, k):
        return jax.lax.dynamic_index_in_dim(x, k, 0, keepdims=False)

    def take(states, i):
        """Global lane ``i``'s state, read by dynamic index (a lane of
        this device where it does not own ``i``), its local index and
        whether this device owns it."""
        k, own = local(i)
        return jax.tree.map(lambda x: lane(x, k), states), k, own

    def merged(one_lane, own):
        """The merged snapshot of ``one_lane`` from its owner, zeros
        from every other device, summed over the axis: exact for any
        dtype, since only the owner contributes."""
        return jax.tree.map(
            lambda x: jax.lax.psum(jnp.where(own, x, jnp.zeros((), x.dtype)),
                                   "lanes"),
            res.merge_state_raw(one_lane))

    def snapshot(states, i):
        one_lane, _, own = take(states, i)
        return merged(one_lane, own)

    def reset(states, idx):
        return core_executor.reset_lanes(states, idx, fresh)

    fold = None
    if res.spec.merge is None:        # decomposable buffers only (add/max)
        def _fold(states, src, dst):
            src_lane, s, own_s = take(states, src)
            contrib = merged(src_lane, own_s)
            # each write touches one lane (unchanged off its owner), so
            # XLA updates the donated table in place
            d, own = local(dst)
            bufs = states.buffers                    # [L, M+X, *local]
            row = bufs[d, :res.num_pri]
            comb = (row + contrib if res.spec.combine == "add"
                    else jnp.maximum(row, contrib))
            bufs = bufs.at[d, :res.num_pri].set(jnp.where(own, comb, row))
            states = dataclasses.replace(states, buffers=bufs)
            # src's lane as it stands now: where this device does not
            # own src, its local index 0 may be dst's, just updated
            now = dataclasses.replace(src_lane, buffers=lane(bufs, s))
            return jax.tree.map(
                lambda x, f, v: jax.lax.dynamic_update_index_in_dim(
                    x, jnp.where(own_s, f, v), s, 0),
                states, fresh, now)

        fold = lift(_fold, (lanes, one, one), lanes, donate=True)

    return LaneTable(
        res=res, mesh=mesh, num_lanes=num_lanes, lanes_per_device=lpd,
        sharding=NamedSharding(mesh, lanes),
        gather=lift(core_executor.take_lanes, (lanes, lanes), lanes),
        scatter=lift(core_executor.put_lanes, (lanes, lanes, lanes), lanes,
                     donate=True),
        scan=lift(res.scan_lanes, (lanes, lanes, lanes), (lanes, lanes)),
        snapshot=lift(snapshot, (lanes, one), one),
        fold=fold,
        reset=lift(reset, (lanes, lanes), lanes, donate=True))
