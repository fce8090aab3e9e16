"""Pallas TPU kernel: count-min sketch update (HHD's hot loop).

The FPGA PE updates D BRAM banks per tuple in parallel (one per sketch row).
TPU adaptation: the whole [num_pe * depth, width] sketch-row space is updated
per tuple tile with two one-hot factors contracted on the MXU:

    out[r, w] += sum_t value[t] * [eff[t]*D + d(r) == r] * [cols[t, d(r)] == w]

realized as  rows_onehot.T @ (cols_onehot * value)  per depth level d --
a [R, TT] x [TT, WB] contraction, with the d loop unrolled statically
(D <= 4).  Tuples sit on sublanes (``[T, 1]`` / ``[T, D]`` blocks).

Exactness.  The v5e MXU takes no int32 operands, so integer values are
contracted as their four bytes, each a bf16 integer in [0, 255], with f32
accumulation: a tile's per-cell byte sum is at most 255 * TT < 2**24
(``block_t`` is capped at 2**16 to keep it so), hence exact in f32 and
after the conversion back to int32.  The planes recombine by wrapping int32
shifts and adds, which is the two's-complement sum -- bit-exact against the
int32 scatter-add for ANY int32 values.  Float values contract in f32 at
``Precision.HIGHEST`` (sum order may differ from a scatter's).

Grid: (width // WB, T // TT); tuple axis last (sequential reduction, output
block resident).  All R = num_pe * depth rows stay in the block: R is small
by construction (M <= 64, D <= 4 -> R <= 256 sublanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

MAX_BLOCK_T = 1 << 16          # 255 * 2**16 < 2**24: byte sums exact in f32
_TUPLES = (((0,), (0,)), ((), ()))     # contract the tuple axis of both


def _kernel(eff_ref, cols_ref, val_ref, out_ref, *, depth: int,
            block_w: int, rows: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    eff = eff_ref[...]                      # [TT, 1]
    val = val_ref[...]                      # [TT, 1]
    tt = eff.shape[0]
    dtype = out_ref.dtype
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (tt, rows), 1)
    col_iota = (jax.lax.broadcasted_iota(jnp.int32, (tt, block_w), 1)
                + pl.program_id(0) * block_w)
    acc = out_ref[...]
    for d in range(depth):
        row_hit = (eff * depth + d) == row_iota       # [TT, R]; eff<0: none
        col_hit = cols_ref[:, d:d + 1] == col_iota    # [TT, WB]
        if jnp.issubdtype(dtype, jnp.integer):
            lhs = row_hit.astype(jnp.bfloat16)
            for k in range(4):
                byte = ((val >> (8 * k)) & 0xFF).astype(jnp.float32)
                rhs = jnp.where(col_hit, byte, 0.0).astype(jnp.bfloat16)
                part = jax.lax.dot_general(lhs, rhs, _TUPLES,
                                           preferred_element_type=jnp.float32)
                acc = acc + (part.astype(jnp.int32) << (8 * k))
        else:
            rhs = jnp.where(col_hit, val, jnp.zeros((), dtype))
            acc = acc + jax.lax.dot_general(
                row_hit.astype(dtype), rhs, _TUPLES,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=dtype)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("num_pe", "depth", "width",
                                             "block_w", "block_t", "interpret"))
def cms_update(eff: jax.Array, cols: jax.Array, value: jax.Array,
               num_pe: int, depth: int, width: int, *, block_w: int = 512,
               block_t: int = 512, interpret: bool = False) -> jax.Array:
    """CMS update -> [num_pe, depth, width].  eff<0 entries are padding."""
    if block_t > MAX_BLOCK_T:
        raise ValueError(f"block_t={block_t} > {MAX_BLOCK_T}: integer byte "
                         "sums would no longer be exact in f32")
    t = eff.shape[0]
    rows = num_pe * depth
    wb = min(block_w, _round_up(width, 128))
    tt = _round_up(min(block_t, t), 8)
    wp = _round_up(width, wb)
    tp = _round_up(t, tt)
    eff_p = jnp.full((tp, 1), -1, jnp.int32).at[:t, 0].set(
        eff.astype(jnp.int32))
    cols_p = jnp.zeros((tp, depth), jnp.int32).at[:t].set(cols.astype(jnp.int32))
    val_p = jnp.zeros((tp, 1), value.dtype).at[:t, 0].set(value)

    out = pl.pallas_call(
        functools.partial(_kernel, depth=depth, block_w=wb, rows=rows),
        name="cms_update",
        grid=(wp // wb, tp // tt),
        in_specs=[
            pl.BlockSpec((tt, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((tt, depth), lambda i, j: (j, 0)),
            pl.BlockSpec((tt, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((rows, wb), lambda i, j: (0, i)),
        # inside shard_map (the lane mesh) the output varies over the
        # same mesh axes as the tuples
        out_shape=jax.ShapeDtypeStruct(
            (rows, wp), value.dtype,
            vma=(jax.typeof(eff_p).vma | jax.typeof(cols_p).vma
                 | jax.typeof(val_p).vma)),
        interpret=interpret,
    )(eff_p, cols_p, val_p)
    return out[:, :width].reshape(num_pe, depth, width)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m
