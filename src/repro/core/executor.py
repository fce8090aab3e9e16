"""The streaming executor: chunks in, merged buffers out.

This is the JAX realization of the full architecture in paper Fig. 3:

  chunk -> PrePEs (spec.pre) -> data routing (mapper.redirect) ->
  PriPEs/SecPEs (pe_update on partitioned buffers) -> merger

driven by a `lax.scan` over fixed-size chunks (a chunk is the paper's
profiling window / channel beat).  The runtime profiler + scheduler live in
the scan carry, so plan generation and SecPE re-scheduling happen *between
chunks without interrupting PriPEs*, mirroring §IV-B: on a re-schedule the
SecPE shadow buffers are merged into their PriPEs and reset before the next
plan re-assigns them.

Two execution shapes share the same chunk step (``_build_chunk_step``):

  * ``make_executor`` -- the one-shot closure (init -> scan -> merge), the
    shape every benchmark and test uses;
  * ``make_resumable_executor`` -- ``ExecState`` as a first-class
    input/output that survives across calls, for serving layers that
    suspend a stream mid-flight and resume it later
    (``serve.SessionEngine``, DESIGN.md §8).  ``merge_state`` is a
    non-destructive snapshot: SecPE shadow buffers stay intact so the
    stream continues after a mid-stream query.

Both accept an optional per-tuple **validity mask** alongside each chunk
(the ragged-tail path of ``data.pipeline.chunk_stream``): masked-out
tuples are routed to sentinel PEs that every kernel backend drops, so
they touch no buffer, no profiler histogram and no round-robin counter --
a padded chunk is bit-identical to a shorter one.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import mapper, merger, perfmodel, profiler, scheduler
from repro.core.types import PROFILE_MODE, RUN_MODE, DittoSpec, ExecStats, RoutePlan
from repro.kernels import dispatch as K

Array = jax.Array


def default_pe_update(buffers: Array, eff: Array, idx: Array, value: Array,
                      combine: str, backend: Optional[str] = None) -> Array:
    """PriPE/SecPE buffer update, routed through the kernel backend
    dispatcher: jnp scatter on CPU, the route_accumulate one-hot kernel on
    TPU/GPU (kernels/dispatch.pe_buffer_update)."""
    return K.pe_buffer_update(buffers, eff, idx, value, combine,
                              backend=backend)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ExecState:
    buffers: Any
    plan: RoutePlan
    rr_base: Array
    mode: Array
    profile_hist: Array
    chunks_in_mode: Array
    monitor: profiler.MonitorState
    reschedules: Array


def init_state(spec: DittoSpec, num_pri: int, num_sec: int) -> ExecState:
    buffers = spec.init_buffer(num_pri + num_sec)
    return ExecState(
        buffers=buffers,
        plan=mapper.init_plan(num_pri, num_sec),
        rr_base=jnp.zeros((num_pri,), jnp.int32),
        mode=jnp.int32(PROFILE_MODE),
        profile_hist=jnp.zeros((num_pri,), jnp.int32),
        chunks_in_mode=jnp.int32(0),
        monitor=profiler.MonitorState.fresh(),
        reschedules=jnp.int32(0),
    )


def with_plan(state: ExecState, plan: RoutePlan) -> ExecState:
    """Seed a state with a pre-made plan and start it in RUN mode."""
    return dataclasses.replace(state, plan=plan, mode=jnp.int32(RUN_MODE))


def _resolve_config(num_pri, num_sec, chunk_size, mem_width_tuples,
                    kernel_backend, who: str):
    """Normalize (num_pri | TunedPlan, ...) into explicit executor knobs."""
    if hasattr(num_pri, "executor_kwargs"):
        tuned = num_pri.executor_kwargs()
        num_pri = tuned["num_pri"]
        if num_sec is None:
            num_sec = tuned["num_sec"]
        if chunk_size is None:
            chunk_size = tuned["chunk_size"]
        if mem_width_tuples is None:
            mem_width_tuples = tuned["mem_width_tuples"]
        if kernel_backend is None:
            kernel_backend = tuned["kernel_backend"]
    if num_sec is None or chunk_size is None:
        raise TypeError(f"{who} needs (num_pri, num_sec, chunk_size) "
                        "or a TunedPlan in place of num_pri")
    if mem_width_tuples is None:
        mem_width_tuples = 8
    return num_pri, num_sec, chunk_size, mem_width_tuples, kernel_backend


def _build_chunk_step(spec: DittoSpec, num_pri: int, num_sec: int,
                      chunk_size: int, *, profile_chunks: int,
                      threshold: float, mem_width_tuples: int,
                      static_plan: bool, pe_update) -> Callable:
    """The lax.scan body shared by every executor shape.

    The scanned xs is ``(chunk, mask)`` where ``mask`` is either ``None``
    (dense chunk, the common case -- None has no pytree leaves, so the same
    scan handles it) or a bool[chunk_size] validity mask.  Masked-out
    tuples are routed to out-of-bounds-high sentinel ids (dst -> M,
    eff -> M+X) that the histogram / round-robin / kernel scatters all
    drop, so they are bit-exact no-ops on every backend.
    """
    num_pe = num_pri + num_sec

    def chunk_step(state: ExecState, xs):
        chunk, mask = xs
        # `live` gates every carry update that counts chunks: a FULLY
        # masked chunk (batch-width padding) must leave the profiling
        # window, monitor EMA and mode machine exactly as it found them.
        live = None if mask is None else mask.any()
        dst, idx, value = spec.pre(chunk, num_pri)
        if mask is not None:
            # dst sentinel M: out-of-range for the workload hist scatter
            # (dropped) and for the occurrence-rank one-hot (no match, so
            # rr_base never advances on padding).
            dst = jnp.where(mask, dst, jnp.int32(num_pri))
        workload = profiler.workload_hist(dst, num_pri)

        # --- data routing: designated PE -> effective PE (mapper, Fig. 4c)
        rank, rr_base = mapper.occurrence_rank(dst, num_pri, state.rr_base)
        eff = mapper.redirect(state.plan, dst, rank)
        if mask is not None:
            # eff sentinel num_pe (out-of-bounds HIGH, never -1: jnp .at[]
            # normalizes negative indices onto the last PE): dropped by
            # every realization -- jnp scatters drop OOB updates, the
            # kernel layer's valid checks reject eff >= num_pe, and the
            # one-hot row matches (DP cursor-append, Pallas cms) match
            # nothing.
            eff = jnp.where(mask, eff, jnp.int32(num_pe))

        # --- PriPE/SecPE buffer updates
        buffers = pe_update(state.buffers, eff, idx, value)

        # --- port-limited cycle model for the monitor + stats
        eff_load = jnp.zeros((num_pe,), jnp.int32).at[eff].add(1)
        max_load = eff_load.max()
        cycles = perfmodel.chunk_cycles(chunk_size, max_load,
                                        mem_width_tuples, spec.ii_pe)

        if static_plan:
            stats = ExecStats(max_load=max_load, modeled_cycles=cycles,
                              mode=jnp.int32(RUN_MODE),
                              rescheduled=jnp.bool_(False), workload=workload)
            return dataclasses.replace(state, buffers=buffers, rr_base=rr_base), stats

        # --- runtime profiler: PROFILE mode accumulates the workload hist
        in_profile = state.mode == PROFILE_MODE
        profile_hist = jnp.where(in_profile, state.profile_hist + workload,
                                 state.profile_hist)
        chunks_in_mode = state.chunks_in_mode + \
            (1 if live is None else live.astype(jnp.int32))

        # PROFILE -> RUN: generate + apply the SecPE scheduling plan (Fig. 5)
        plan_ready = jnp.logical_and(in_profile, chunks_in_mode >= profile_chunks)
        if live is not None:
            plan_ready = jnp.logical_and(plan_ready, live)
        assignment = scheduler.schedule_secpes(profile_hist, num_sec)
        new_plan = mapper.apply_schedule(state.plan, assignment)
        post_load = scheduler.post_plan_max_load(
            profile_hist.astype(jnp.float32) / jnp.maximum(chunks_in_mode, 1),
            assignment)
        ref_cycles = perfmodel.chunk_cycles(chunk_size, post_load,
                                            mem_width_tuples, spec.ii_pe)

        def pick(new, old):
            return jax.tree.map(lambda a, b: jnp.where(plan_ready, a, b), new, old)

        plan = pick(new_plan, state.plan)
        monitor = pick(
            profiler.MonitorState(ref_cycles=ref_cycles, ema_cycles=jnp.float32(0.0)),
            state.monitor)
        mode = jnp.where(plan_ready, RUN_MODE, state.mode).astype(jnp.int32)
        chunks_in_mode = jnp.where(plan_ready, 0, chunks_in_mode)

        # RUN mode: throughput monitoring -> re-schedule trigger (§IV-B)
        in_run = mode == RUN_MODE
        monitor_on = jnp.logical_and(in_run, ~plan_ready)
        if live is not None:
            monitor_on = jnp.logical_and(monitor_on, live)
        monitor = jax.tree.map(
            lambda upd, old: jnp.where(monitor_on, upd, old),
            profiler.monitor_update(monitor, cycles), monitor)
        fire = jnp.logical_and(
            jnp.logical_and(in_run, ~plan_ready),
            profiler.should_reschedule(monitor, jnp.float32(threshold)))
        if live is not None:
            fire = jnp.logical_and(fire, live)

        def do_reschedule(bufs):
            merged = merger.merge_buffers(bufs, plan.assignment, num_pri, spec.combine)
            bufs = bufs.at[:num_pri].set(merged)
            return merger.reset_sec_buffers(bufs, num_pri, spec.combine)

        if spec.merge is None:
            buffers = jax.lax.cond(fire, do_reschedule, lambda b: b, buffers)
        # else: non-decomposable apps keep per-PE regions; threshold=0.0
        # (enforced above) makes `fire` statically False, and tracing
        # merge_buffers on their custom buffer pytree would be invalid.
        plan = jax.tree.map(
            lambda fresh, cur: jnp.where(fire, fresh, cur),
            mapper.init_plan(num_pri, num_sec), plan)
        mode = jnp.where(fire, PROFILE_MODE, mode).astype(jnp.int32)
        profile_hist = jnp.where(fire, 0, profile_hist)
        chunks_in_mode = jnp.where(fire, 0, chunks_in_mode)
        monitor = jax.tree.map(
            lambda fresh, cur: jnp.where(fire, fresh, cur),
            profiler.MonitorState.fresh(), monitor)

        stats = ExecStats(max_load=max_load, modeled_cycles=cycles, mode=state.mode,
                          rescheduled=fire, workload=workload)
        new_state = ExecState(buffers=buffers, plan=plan, rr_base=rr_base,
                              mode=mode, profile_hist=profile_hist,
                              chunks_in_mode=chunks_in_mode, monitor=monitor,
                              reschedules=state.reschedules + fire.astype(jnp.int32))
        return new_state, stats

    return chunk_step


def _merge_state(spec: DittoSpec, num_pri: int, state: ExecState):
    """Merged-buffer snapshot of a state (non-destructive: SecPE shadow
    buffers are left intact, so the stream can keep running afterwards)."""
    if spec.merge is not None:
        return spec.merge(state.buffers, state.plan)
    return merger.merge_buffers(state.buffers, state.plan.assignment,
                                num_pri, spec.combine)


def make_executor(
    spec: DittoSpec,
    num_pri: Any,
    num_sec: Optional[int] = None,
    chunk_size: Optional[int] = None,
    *,
    profile_chunks: int = 1,
    threshold: float = 0.0,
    mem_width_tuples: Optional[int] = None,
    static_plan: bool = False,
    kernel_backend: Optional[str] = None,
) -> Callable[..., tuple[Any, ExecStats]]:
    """Build the jitted streaming executor.

    Args:
      spec: application specification (Listing-2 analogue).
      num_pri/num_sec: M PriPEs and X SecPEs (the generated variant).
        ``num_pri`` alternatively accepts a ``repro.tune.TunedPlan`` (any
        object with ``executor_kwargs()``), which supplies num_pri/num_sec/
        chunk_size/mem_width_tuples/kernel_backend in one bundle; any of
        those passed explicitly (e.g. ``chunk_size=8192``) override the
        plan's value.  Pass ``tuned.route_plan`` to the returned fn to
        start in RUN mode under the tuned static plan.
      chunk_size: tuples per chunk (= profiling window granularity).
      profile_chunks: chunks of profiling before a plan is generated.
      threshold: throughput-drop fraction that triggers re-scheduling
        (0.0 disables re-scheduling, the paper's escape hatch).
      mem_width_tuples: tuples the memory interface feeds per cycle
        (Eq. 1 W); default 8.
      static_plan: skip runtime profiling; caller passes a pre-made plan
        (used by tests and by the offline path once a plan is known).
      kernel_backend: pin the PE-update kernel realization ('jnp' |
        'interpret' | 'pallas'); None = auto per jax.default_backend().
        Only applies to the default pe_update (custom spec.pe_update
        callables pick their own backend).

    Returns fn(tuples, [plan], [mask]) -> (merged_buffers, ExecStats).
      ``tuples`` is [num_chunks, chunk_size, ...]; the leading axis is
      scanned.  ``mask`` is an optional bool[num_chunks, chunk_size]
      validity mask (the padded-tail path of data.pipeline.chunk_stream);
      masked-out tuples are exact no-ops.
    """
    res = make_resumable_executor(
        spec, num_pri, num_sec, chunk_size, profile_chunks=profile_chunks,
        threshold=threshold, mem_width_tuples=mem_width_tuples,
        static_plan=static_plan, kernel_backend=kernel_backend,
        _who="make_executor")

    @jax.jit
    def run(tuples, plan: Optional[RoutePlan] = None,
            mask: Optional[Array] = None):
        state = res.init_state()
        if plan is not None:
            state = with_plan(state, plan)
        state, stats = res.scan_chunks(state, tuples, mask)
        return _merge_state(spec, res.num_pri, state), stats

    return run


@dataclasses.dataclass(frozen=True)
class ResumableExecutor:
    """A streaming executor whose scan carry is caller-owned.

    The serving layer's suspend/resume primitive (DESIGN.md §8): hold an
    ``ExecState`` per tenant stream, feed chunk batches as they arrive
    (``run_chunks``), snapshot merged buffers mid-stream without
    disturbing the SecPE shadow buffers (``merge_state``), and keep
    going.  ``step`` is the raw un-jitted scan body ``(state, (chunk,
    mask)) -> (state, stats)`` for callers that compose their own scans
    or vmaps (e.g. the slot-stacked SessionEngine);
    ``merge_state_raw`` is the un-jitted snapshot for the same purpose
    (the lane table's snapshot and fold, under ``shard_map``).
    """

    spec: DittoSpec
    num_pri: int
    num_sec: int
    chunk_size: int
    step: Callable = dataclasses.field(repr=False)
    run_chunks: Callable = dataclasses.field(repr=False)
    merge_state: Callable = dataclasses.field(repr=False)
    merge_state_raw: Callable = dataclasses.field(repr=False)

    def init_state(self) -> ExecState:
        return init_state(self.spec, self.num_pri, self.num_sec)

    def scan_chunks(self, state: ExecState, chunks, mask=None):
        """Un-jitted run_chunks (for embedding under an outer jit/vmap)."""
        return jax.lax.scan(self.step, state, (chunks, mask))

    def scan_lanes(self, states: ExecState, chunks, mask=None):
        """Un-jitted vmapped scan over a leading lanes axis: a
        lanes-stacked ``ExecState`` (see ``stack_states``) advances by
        ``chunks[lane, k]`` per lane in one batched scan.

        This is the **lowerable entry point** of the serving layer's hot
        path: ``core.distributed.make_lane_table`` lifts it over the
        mesh's lanes axis and ``serve.SessionEngine``, with
        ``aot_buckets=`` enabled, AOT-lowers and compiles one executable
        per (lane count, scan width) shape bucket at warmup
        (``jit(scan_lanes).lower(...).compile()``), so ragged traffic
        never retraces on the flush path."""
        return jax.vmap(self.scan_chunks)(states, chunks, mask)


def make_resumable_executor(
    spec: DittoSpec,
    num_pri: Any,
    num_sec: Optional[int] = None,
    chunk_size: Optional[int] = None,
    *,
    profile_chunks: int = 1,
    threshold: float = 0.0,
    mem_width_tuples: Optional[int] = None,
    static_plan: bool = False,
    kernel_backend: Optional[str] = None,
    _who: str = "make_resumable_executor",
) -> ResumableExecutor:
    """The suspend/resume shape of ``make_executor`` (same knobs).

    Usage:
        res = make_resumable_executor(spec, 16, 4, 4096)
        state = res.init_state()                    # or with_plan(state, p)
        state, stats = res.run_chunks(state, chunks_a)       # flush 1
        snapshot = res.merge_state(state)                    # query
        state, stats = res.run_chunks(state, chunks_b, mask) # flush 2 (ragged)

    ``run_chunks``/``merge_state`` are jitted; ``merge_state`` never
    mutates: the same state keeps accumulating after a query.
    """
    (num_pri, num_sec, chunk_size, mem_width_tuples,
     kernel_backend) = _resolve_config(num_pri, num_sec, chunk_size,
                                       mem_width_tuples, kernel_backend,
                                       _who)
    if spec.merge is not None and threshold > 0.0:
        raise ValueError(
            f"{spec.name}: non-decomposable applications keep per-PE output "
            "regions and cannot re-merge mid-stream; use threshold=0.0")
    # observability hook on the one factory funnel every executor build
    # goes through (make_executor / multistream / the serving engines all
    # land here).  Lazy import: repro.obs imports repro.core at module
    # scope, so the reverse edge must stay inside the function.
    from repro import obs as obs_lib
    obs = obs_lib.get_default()
    obs.registry.counter(
        "executor_builds_total",
        "executor factory calls, by entry point",
        labels=("kind",)).inc(kind=_who)
    with obs.span("executor.build", cat="build", kind=_who, app=spec.name,
                  num_pri=num_pri, num_sec=num_sec, chunk_size=chunk_size):
        pe_update = spec.pe_update or partial(default_pe_update,
                                              combine=spec.combine,
                                              backend=kernel_backend)
        step = _build_chunk_step(
            spec, num_pri, num_sec, chunk_size, profile_chunks=profile_chunks,
            threshold=threshold, mem_width_tuples=mem_width_tuples,
            static_plan=static_plan, pe_update=pe_update)

    @jax.jit
    def run_chunks(state, chunks, mask=None):
        return jax.lax.scan(step, state, (chunks, mask))

    def merge_state_raw(state):
        return _merge_state(spec, num_pri, state)

    return ResumableExecutor(spec=spec, num_pri=num_pri, num_sec=num_sec,
                             chunk_size=chunk_size, step=step,
                             run_chunks=run_chunks,
                             merge_state=jax.jit(merge_state_raw),
                             merge_state_raw=merge_state_raw)


def stack_states(state: ExecState, num_lanes: int) -> ExecState:
    """Broadcast one ``ExecState`` into a lanes-stacked pytree: every leaf
    gains a leading ``[num_lanes]`` axis.  This is the slot-lane state of
    ``serve.SessionEngine``, whose axis 0 is split over a mesh's ``lanes``
    axis (``core.distributed.make_lane_table``, DESIGN.md §9)."""
    return jax.tree.map(lambda x: jnp.stack([x] * num_lanes), state)


def take_lanes(states: ExecState, idx) -> ExecState:
    """Gather lane sub-states ``idx`` (int array) out of a lanes-stacked
    ``ExecState`` -- an ``ExecState`` is an ordinary pytree of arrays,
    so suspending a lane and resuming it later is this gather plus the
    ``put_lanes`` scatter.  ``core.distributed.make_lane_table`` runs
    both on each device's slice of the serving engine's lane table
    (DESIGN.md §9)."""
    return jax.tree.map(lambda x: x[idx], states)


def put_lanes(states: ExecState, idx, sub: ExecState) -> ExecState:
    """Scatter lane sub-states back into a lanes-stacked ``ExecState``
    (inverse of ``take_lanes``; the ids ``idx`` are distinct)."""
    return _write_lanes(states, idx,
                        lambda k: jax.tree.map(lambda s: s[k], sub))


def reset_lanes(states: ExecState, idx, fresh: ExecState) -> ExecState:
    """Overwrite lanes ``idx`` of a lanes-stacked ``ExecState`` with the
    one-lane state ``fresh`` (an id may repeat; ids ``-1`` are pads,
    placed after every real id, and write nothing)."""
    return _write_lanes(states, idx, lambda k: fresh,
                        count=jnp.sum(idx >= 0))


def _write_lanes(states: ExecState, idx, lane, count=None) -> ExecState:
    """Write ``lane(k)`` (a one-lane state) into lane ``idx[k]`` for each
    ``k < count`` (default: every ``k``), one dynamic update per lane.
    A table the caller donates is then written in place whatever its
    device layout: a multi-lane scatter makes the TPU lay the whole
    table out lane-major and back (two copies of it) around the write."""
    def write(k, st):
        return jax.tree.map(
            lambda x, v: jax.lax.dynamic_update_index_in_dim(
                x, v.astype(x.dtype), idx[k], 0), st, lane(k))
    return jax.lax.fori_loop(0, len(idx) if count is None else count,
                             write, states)


def make_multistream_executor(
    spec: DittoSpec,
    num_pri: Any,
    num_sec: Optional[int] = None,
    chunk_size: Optional[int] = None,
    **kw,
) -> Callable[..., tuple[Any, ExecStats]]:
    """Vmapped multi-stream executor: S independent chunk streams in one
    scan.  ``num_pri`` accepts a TunedPlan exactly like ``make_executor``
    (per-tenant route plans go in as the stacked ``plans`` argument; see
    ``stack_plans``).

    The single-stream executor is vmapped over a leading streams axis, so
    every stream carries its OWN profiler/scheduler state (plan, mode,
    monitor, reschedule counter) while the per-chunk work of all streams
    fuses into one batched ``lax.scan`` -- the serving shape for many
    concurrent skewed workloads (one tenant per stream).

    Returns fn(tuples, [plans], [mask]) -> (merged_buffers, ExecStats):
      tuples: [num_streams, num_chunks, chunk_size, ...]
      plans:  optional RoutePlan pytree with leading [num_streams] axis
              (e.g. from stacking make_static_plan outputs); when given,
              every stream starts in RUN mode under its own plan.
      mask:   optional bool[num_streams, num_chunks, chunk_size] validity
              mask -- ragged streams and padded batch lanes ride through
              as exact no-ops (serve.StreamEngine's pad-lane isolation).
    Outputs gain the same leading [num_streams] axis and are bit-identical
    to running each stream alone (integer apps; float apps up to the usual
    reduction-order caveats, which vmap does not change).
    """
    run = make_executor(spec, num_pri, num_sec, chunk_size, **kw)
    free = jax.jit(jax.vmap(lambda t: run(t)))
    planned = jax.jit(jax.vmap(lambda t, p: run(t, p)))
    free_masked = jax.jit(jax.vmap(lambda t, m: run(t, mask=m)))
    planned_masked = jax.jit(jax.vmap(lambda t, p, m: run(t, p, mask=m)))

    def run_streams(tuples, plans: Optional[RoutePlan] = None, mask=None):
        if plans is None:
            return free(tuples) if mask is None else free_masked(tuples, mask)
        if mask is None:
            return planned(tuples, plans)
        return planned_masked(tuples, plans, mask)

    return run_streams


def make_static_plan(num_pri: int, num_sec: int, workload) -> RoutePlan:
    """Offline path: plan from a sampled workload distribution (the skew
    analyzer's sample doubles as the profiling window)."""
    assignment = scheduler.schedule_secpes(jnp.asarray(workload), num_sec)
    return mapper.apply_schedule(mapper.init_plan(num_pri, num_sec), assignment)


def stack_plans(plans) -> RoutePlan:
    """Stack per-stream RoutePlans into the leading-[num_streams] pytree the
    multi-stream executor takes (per-tenant plans in serve.StreamEngine).
    All plans must share (num_pri, num_sec)."""
    plans = list(plans)
    if not plans:
        raise ValueError("stack_plans needs at least one plan")
    shapes = {(p.num_pri, p.num_sec) for p in plans}
    if len(shapes) != 1:
        raise ValueError(f"plans disagree on (num_pri, num_sec): {shapes}")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *plans)
