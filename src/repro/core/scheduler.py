"""SecPE scheduling-plan generation (paper §IV-C3, Fig. 5).

The runtime profiler assigns a SecPE to the PriPE whose workload is maximal
and recalculates the workload distribution assuming the original workload is
evenly shared with the attached SecPEs; repeated until all SecPEs are
scheduled.  Scheduling-plan generation is off the critical path, so the paper
executes it serially -- we keep the identical serial greedy under a
`lax.fori_loop` (validated against the paper's Fig. 5 walkthrough).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def schedule_secpes(workload: jax.Array, num_sec: int, *,
                    min_load=None) -> jax.Array:
    """Greedy max-load splitting.

    Args:
      workload: int/float[M] per-PriPE tuple counts from the profiler.
      num_sec:  X, the number of schedulable SecPEs.
      min_load: when given, grants to PriPEs whose workload is below this
        floor are suppressed to -1 (idle SecPE).  The paper always
        schedules every SecPE (helping a balanced PriPE is harmless at
        PE granularity), but the lifted schedulers -- tenant-level slot
        grants in ``serve.SessionEngine``, cross-device lane grants in
        the distributed engine -- pay a real merge on every re-grant, so
        a helper that cannot shorten the scan (backlog below
        ``min_grant_chunks``) is net negative there.

    Returns:
      assignment: int32[X] with assignment[j] = PriPE id SecPE j shadows
      (or -1 where ``min_load`` suppressed the grant).
    """
    m = workload.shape[0]
    if num_sec == 0:
        return jnp.zeros((0,), jnp.int32)
    w = workload.astype(jnp.float32)
    # the carry is built *_like(w) so that it varies over the same mesh
    # axes as the workload when traced under shard_map: a constant carry
    # would change type in the loop body
    shares = jnp.ones_like(w)                   # 1 + #SecPEs attached
    assignment = jnp.full_like(w, -1, shape=(num_sec,), dtype=jnp.int32)

    def body(j, carry):
        shares, assignment = carry
        eff = w / shares
        p = jnp.argmax(eff).astype(jnp.int32)
        shares = shares.at[p].add(1.0)
        assignment = assignment.at[j].set(p)
        return shares, assignment

    _, assignment = jax.lax.fori_loop(0, num_sec, body, (shares, assignment))
    if min_load is not None:
        hot = w[jnp.clip(assignment, 0, m - 1)] >= min_load
        assignment = jnp.where(hot, assignment, -1)
    return assignment


def post_plan_max_load(workload: jax.Array, assignment: jax.Array) -> jax.Array:
    """Max effective per-PE load after the plan divides hot PriPEs' work.

    Used by the throughput monitor and the perf model: PriPE p with s_p
    attached SecPEs absorbs workload[p] / (1 + s_p).
    """
    m = workload.shape[0]
    num_sec = assignment.shape[0]
    onehot = (assignment[:, None] == jnp.arange(m)[None, :]).astype(jnp.float32)
    shares = 1.0 + onehot.sum(axis=0)
    return jnp.max(workload.astype(jnp.float32) / shares)


def plan_summary(workload, assignment) -> dict:
    """Host-side observability summary of one scheduling plan.

    Pure numpy (no trace, no device sync beyond reading the inputs) --
    the serving engine calls this per flush to feed its metrics
    registry (``sched_n_granted`` / ``sched_post_plan_max_load``
    gauges, docs/observability.md), so it must never jit or allocate on
    device.

    Returns ``n_granted`` (assignments != -1), ``max_load_before`` (the
    hottest PriPE's raw workload) and ``max_load_after`` (the paper's
    post-plan balance metric: hottest workload / (1 + attached SecPEs),
    matching ``post_plan_max_load``).
    """
    w = np.asarray(workload, np.float32)
    a = np.asarray(assignment, np.int64)
    granted = a[a >= 0]
    shares = np.ones(len(w), np.float32)
    np.add.at(shares, granted, 1.0)
    return {
        "n_granted": int(len(granted)),
        "max_load_before": float(w.max()) if len(w) else 0.0,
        "max_load_after": float((w / shares).max()) if len(w) else 0.0,
    }


# ---------------------------------------------------------------------------
# Eq. 2 lifted to admission time (PR 9, DESIGN.md §12)
# ---------------------------------------------------------------------------

def admission_score(backlog, occupancy) -> np.ndarray:
    """Per-tenant Eq. 2 effective load at admission time.

    ``schedule_secpes`` is the paper's balancing move inside the engine:
    the hottest PriPE gets the next helper, with effective load
    ``workload / (1 + shares)``.  The admission controller is the same
    move pointed the other way -- the next free primary slot goes to the
    COLDEST tenant, where a tenant's effective load is the work it has
    already parked on the engine:

        eff_t = occupancy_t + backlog_t / (1 + occupancy_t)

    ``occupancy_t`` (slots the tenant already holds) dominates so one
    tenant's storm cannot FIFO-hog the slot table, and the queued
    backlog is divided across the tenant's resident slots exactly like
    Eq. 2 divides a PriPE's workload across its attached SecPEs.

    Args:
      backlog:   int/float[T] per-tenant queued tuples (or any work
        proxy) not yet resident in a slot.
      occupancy: int/float[T] per-tenant primary slots currently held.

    Returns:
      float64[T] scores; LOWER admits first.  Pure numpy -- admission
      runs on the request path of the network service, so it must never
      trace or touch the device.
    """
    b = np.asarray(backlog, np.float64)
    o = np.asarray(occupancy, np.float64)
    if b.shape != o.shape:
        raise ValueError(f"backlog shape {b.shape} != occupancy "
                         f"shape {o.shape}")
    return o + b / (1.0 + o)


def plan_admission(backlog, occupancy, free_slots: int,
                   pending) -> np.ndarray:
    """Greedy Eq. 2 admission plan: which pending opens get the free
    slots, and in what order.

    Mirrors the serial greedy of ``schedule_secpes``: each round picks
    the argmin of ``admission_score`` among tenants with a pending open
    (first-arrived wins ties, preserving FIFO among equals), charges
    that tenant one slot of occupancy, and recomputes.  Never admits
    more than ``free_slots`` (capacity is a hard bound).

    Args:
      backlog:    int/float[T] per-tenant queued work (see
        ``admission_score``).
      occupancy:  int/float[T] per-tenant slots held; mutated copies are
        used internally, the input is untouched.
      free_slots: number of primary slots currently free.
      pending:    int[K] tenant index of each queued open request, in
        arrival order.

    Returns:
      int64[A] indices into ``pending`` in admission order, A =
      min(K, free_slots).
    """
    occ = np.asarray(occupancy, np.float64).copy()
    b = np.asarray(backlog, np.float64)
    pend = np.asarray(pending, np.int64)
    if len(pend) and (pend.min() < 0 or pend.max() >= len(occ)):
        raise ValueError(f"pending tenant ids must be in [0, {len(occ)}); "
                         f"got range [{pend.min()}, {pend.max()}]")
    todo = list(range(len(pend)))
    admitted: list = []
    for _ in range(max(0, int(free_slots))):
        if not todo:
            break
        scores = admission_score(b, occ)
        # argmin over the still-pending entries; np.argmin returns the
        # FIRST minimum, i.e. the earliest arrival among score ties.
        k = int(np.argmin(scores[pend[todo]]))
        i = todo.pop(k)
        occ[pend[i]] += 1.0
        admitted.append(i)
    return np.asarray(admitted, np.int64)
