"""Pallas TPU kernel: scatter-accumulate via a one-hot compare/select/reduce.

The PE private-buffer update (paper Listing 1 / §IV-C1) is a scatter: BRAM
ports absorb one tuple per cycle.  TPUs have no BRAM ports -- random scatter
into VMEM is serialized and slow.  The TPU-native adaptation (DESIGN.md §2)
converts the scatter into a dense one-hot reduction on the VPU, exact for
every dtype (the v5e MXU takes no int32 operands):

    out[b] (+|max)= reduce_t  value[t] if flat_idx[t] == b else neutral

Grid: (bins // BB, T // TT); the tuple axis is the *last* (sequential) grid
dimension so the output block stays resident in VMEM across the reduction
(the standard Pallas revisiting-reduction pattern).  Tuples arrive
lane-dense (``[T / 128, 128]`` blocks, so a vmapped batch of lanes costs
no padding), bins sit on lanes (a ``[1, BB]`` output row, BB a multiple
of 128): the in-kernel loop transposes 1024 tuples at a time, compares
them 8 at a time against the BB bins and folds the hits into an
``[8, BB]`` accumulator, which is reduced over its sublanes once per
tile.  The work is tuples x bins.  Out-of-range indices (padding, -1)
match no bin and are dropped -- exactly the ref.py semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SUB = 8        # tuples per compare: one vreg of sublanes
_ROWS = 8       # [8, 128] tuple rows transposed per loop iteration


def _neutral(combine: str, dtype):
    if combine == "add":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.array(jnp.iinfo(dtype).min, dtype)
    return jnp.array(-jnp.inf, dtype)


def _kernel(idx_ref, val_ref, out_ref, *, combine: str, block_bins: int):
    dtype = out_ref.dtype
    neutral = _neutral(combine, dtype)
    fold = jnp.add if combine == "add" else jnp.maximum

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, neutral, dtype)

    bins = (jax.lax.broadcasted_iota(jnp.int32, (_SUB, block_bins), 1)
            + pl.program_id(0) * block_bins)

    def body(s, acc):
        r0 = pl.multiple_of(s * _ROWS, _ROWS)
        # tuples arrive lane-dense; the transpose puts 8 of them on the
        # sublanes of each [8, 1] column that is compared against the bins
        idx = idx_ref[pl.ds(r0, _ROWS), :].T          # [128, 8]
        val = val_ref[pl.ds(r0, _ROWS), :].T
        for c in range(_ROWS):
            for g in range(0, 128, _SUB):
                hit = idx[g:g + _SUB, c:c + 1] == bins    # [8, BB]
                acc = fold(acc, jnp.where(hit, val[g:g + _SUB, c:c + 1],
                                          neutral))
        return acc

    acc = jax.lax.fori_loop(0, idx_ref.shape[0] // _ROWS, body,
                            jnp.full((_SUB, block_bins), neutral, dtype))
    if combine == "add":
        out_ref[...] += jnp.sum(acc, axis=0, keepdims=True)
    else:
        out_ref[...] = jnp.maximum(out_ref[...],
                                   jnp.max(acc, axis=0, keepdims=True))


@functools.partial(jax.jit, static_argnames=("num_bins", "combine",
                                             "block_bins", "block_t",
                                             "interpret"))
def route_accumulate(flat_idx: jax.Array, value: jax.Array, num_bins: int,
                     combine: str = "add", *, block_bins: int = 2048,
                     block_t: int = 4096, interpret: bool = False) -> jax.Array:
    """Scatter-accumulate with padding to block multiples.  See module doc.

    flat_idx: [T] int32 (invalid/padding entries < 0 or >= num_bins).
    value:    [T] int32/float32.
    Returns [num_bins] accumulated buffer (add: zeros init; max: neutral
    replaced by 0 to match ref.py's zeros-init .at[].max semantics).
    """
    t = flat_idx.shape[0]
    step = _ROWS * 128
    bb = min(_round_up(block_bins, 128), _round_up(num_bins, 128))
    tt = _round_up(min(block_t, t), step)
    nb = _round_up(num_bins, bb)
    tp = _round_up(t, tt)
    idx = jnp.full((tp,), -1, jnp.int32).at[:t].set(
        flat_idx.astype(jnp.int32)).reshape(tp // 128, 128)
    val = jnp.zeros((tp,), value.dtype).at[:t].set(value).reshape(
        tp // 128, 128)

    out = pl.pallas_call(
        functools.partial(_kernel, combine=combine, block_bins=bb),
        name="route_accumulate",
        grid=(nb // bb, tp // tt),
        in_specs=[
            pl.BlockSpec((tt // 128, 128), lambda i, j: (j, 0)),
            pl.BlockSpec((tt // 128, 128), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bb), lambda i, j: (0, i)),
        # inside shard_map (the lane mesh) the output varies over the
        # same mesh axes as the tuples
        out_shape=jax.ShapeDtypeStruct(
            (1, nb), value.dtype,
            vma=jax.typeof(idx).vma | jax.typeof(val).vma),
        interpret=interpret,
    )(idx, val)
    out = out[0, :num_bins]
    if combine == "max":
        # ref semantics: zeros-initialized buffer -> result is max(0, values)
        out = jnp.maximum(out, jnp.zeros((), value.dtype))
    return out


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m
