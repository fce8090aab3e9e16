"""The mapper module (paper §IV-C2, Fig. 4).

The mappers execute the SecPE scheduling plan: a two-dimensional mapping
table with M rows and X+1 columns plus a one-dimensional counter array with
M entries.  Workload redirecting looks the table up in a round-robin manner
with the counter indicating the boundary.

The FPGA implementation updates one `SecPE ID -> PriPE ID` pair per cycle for
timing; here the same sequential semantics run under `lax.fori_loop` (the
result is bit-identical, verified against the paper's Fig. 4 example in
tests/test_core_mapper.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.types import RoutePlan


def init_plan(num_pri: int, num_sec: int) -> RoutePlan:
    """Initial mapping table/counter: row p is filled with PriPE id p and the
    counter is one -- every tuple routes to its designated PriPE."""
    table = jnp.tile(
        jnp.arange(num_pri, dtype=jnp.int32)[:, None], (1, num_sec + 1)
    )
    counter = jnp.ones((num_pri,), dtype=jnp.int32)
    assignment = jnp.full((num_sec,), -1, dtype=jnp.int32)
    return RoutePlan(assignment=assignment, table=table, counter=counter)


def apply_schedule(plan: RoutePlan, assignment: jax.Array) -> RoutePlan:
    """Mapping-table updating (Fig. 4b).

    ``assignment`` is the scheduler's array of "SecPE j -> PriPE assignment[j]"
    pairs (-1 = unassigned).  For each pair, write the SecPE's global id
    (M + j) to the next free slot of the row (using the counter value as the
    write index) and increase the counter by one.
    """
    num_pri = plan.num_pri
    fresh = init_plan(num_pri, plan.num_sec)
    if plan.num_sec == 0:
        return fresh
    # the carry starts *_like(assignment) so that, under shard_map, it
    # varies over the same mesh axes as the plan the loop writes into it
    table = fresh.table + jnp.zeros_like(assignment, jnp.int32,
                                         shape=fresh.table.shape)
    counter = jnp.ones_like(assignment, jnp.int32, shape=fresh.counter.shape)

    def body(j, carry):
        table, counter = carry
        p = assignment[j]
        valid = p >= 0
        p_safe = jnp.where(valid, p, 0)
        slot = counter[p_safe]
        sec_id = jnp.int32(num_pri + j)
        new_row_val = jnp.where(valid, sec_id, table[p_safe, slot])
        table = table.at[p_safe, slot].set(new_row_val)
        counter = counter.at[p_safe].add(jnp.where(valid, 1, 0).astype(jnp.int32))
        return table, counter

    table, counter = jax.lax.fori_loop(0, plan.num_sec, body, (table, counter))
    return RoutePlan(assignment=assignment.astype(jnp.int32), table=table, counter=counter)


def occurrence_rank(dst: jax.Array, num_pri: int, base: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Round-robin position of each tuple within its PriPE's stream.

    The FPGA mappers advance one table column per redirected tuple; the
    vectorized equivalent is the *occurrence rank*: tuple i destined to PriPE
    p gets rank = base[p] + #{j < i : dst[j] == p}.  Returns (rank, new_base).

    O(T*M) one-hot prefix sum -- M is small (<=64) by construction.
    """
    onehot = (dst[:, None] == jnp.arange(num_pri, dtype=dst.dtype)[None, :])
    onehot = onehot.astype(jnp.int32)
    # exclusive prefix count of own destination
    incl = jnp.cumsum(onehot, axis=0)
    excl = incl - onehot
    rank = base[dst] + jnp.take_along_axis(excl, dst[:, None].astype(jnp.int32), axis=1)[:, 0]
    new_base = base + incl[-1]
    return rank, new_base


def redirect(plan: RoutePlan, dst: jax.Array, rank: jax.Array) -> jax.Array:
    """Workload redirecting (Fig. 4c): effective PE id for each tuple.

    eff = table[dst, rank mod counter[dst]] -- round robin across the PriPE
    and its assigned SecPEs, with the counter as the boundary.
    """
    width = plan.counter[dst]
    slot = jnp.remainder(rank, width)
    return plan.table[dst, slot]
