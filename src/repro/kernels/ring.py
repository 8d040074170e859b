"""Pallas TPU kernel: fused per-chunk |A Bᵀ| row-sum accumulation.

This is the compute body of BOTH similarity epilogues (DESIGN.md §7.4):
at each of the p ring steps a device holds one (m/p)×c chunk of the
normalized matrix V and folds its contribution into the running
marginal sums, d += Σ_j |V_local · chunkᵀ|_{:,j}; the allgather
epilogue is the degenerate single-chunk call (b = the gathered full V,
acc = None — the schedule the retired similarity.py kernel hard-coded
with a partials buffer).  The m×m similarity tile never touches HBM,
and the accumulator rides through the kernel so each step is a single
fused matmul→|·|→row-reduce→add with no jnp epilogue.

Grid: (i, j) over (bl × bc) tiles, j innermost.  The (block_i, 1) output
block is revisited across j (classic accumulation schedule): j == 0
initializes it from the carried-in accumulator, later steps add their
tile's row-sums.  Operands stay in their native dtype (fp32 or bf16
under the mixed-precision policy); the dot and the accumulator are fp32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .vma import interpret_mode, out_struct


def _abs_rowsum_kernel(a_ref, b_ref, acc_ref, o_ref, *, j_dim: int,
                       precision=None):
    """Shared body; j_dim names the grid position of the innermost
    (accumulation) axis — 1 unbatched, 2 when a leading request axis is
    prepended to the grid (DESIGN.md §7.6).  Refs arrive with their
    leading block dims collapsed to the (block_i|block_j, c) tiles."""
    a = a_ref[...].reshape(a_ref.shape[-2:])  # (block_i, c), native dtype
    b = b_ref[...].reshape(b_ref.shape[-2:])  # (block_j, c)
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32)
    partial = jnp.sum(jnp.abs(s), axis=1)[:, None]
    partial = partial.reshape(o_ref.shape)

    @pl.when(pl.program_id(j_dim) == 0)
    def _init():
        o_ref[...] = acc_ref[...] + partial

    @pl.when(pl.program_id(j_dim) > 0)
    def _accumulate():
        o_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret",
                                             "precision"))
def abs_rowsum(a: jax.Array, b: jax.Array,
               acc: Optional[jax.Array] = None, *,
               block_i: int = 128, block_j: int = 128,
               interpret: bool = False, precision=None) -> jax.Array:
    """acc + row-sums of |a @ bᵀ| — the ring-step epilogue, fused.

    a: (bl, c) — this device's rows of V (fixed across ring steps).
    b: (bc, c) — the circulating chunk of V.
    acc: (bl,) fp32 running sums, or None for zeros (first step).
    Request-batched form (DESIGN.md §7.6): a (B, bl, c), b (B, bc, c),
    acc (B, bl) — requests never mix (block-diagonal in the similarity
    tile), so the grid grows a leading B axis instead of flattening.
    Zero-padding rows of `b` contribute |0| = 0, which is exactly how the
    parallel caller pads the slice dimension to even shards.
    `precision` (a jax.lax.Precision, static) is the precision policy's
    contraction precision.
    """
    batched = a.ndim == 3
    nb = a.shape[0] if batched else 1
    bl, c = a.shape[-2:]
    bc = b.shape[-2]
    acc_shape = (nb, bl) if batched else (bl,)
    acc = jnp.zeros(acc_shape, jnp.float32) if acc is None \
        else acc.astype(jnp.float32)
    block_i = min(block_i, bl)
    block_j = min(block_j, bc)
    ip = pl.cdiv(bl, block_i) * block_i
    jp = pl.cdiv(bc, block_j) * block_j
    zero2 = ((0, 0),) if batched else ()
    if ip != bl:
        a = jnp.pad(a, zero2 + ((0, ip - bl), (0, 0)))
        acc = jnp.pad(acc, zero2 + ((0, ip - bl),))
    if jp != bc:
        b = jnp.pad(b, zero2 + ((0, jp - bc), (0, 0)))

    if batched:
        grid = (nb, ip // block_i, jp // block_j)
        in_specs = [
            pl.BlockSpec((1, block_i, c), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_j, c), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, block_i, 1), lambda g, i, j: (g, i, 0)),
        ]
        out_specs = pl.BlockSpec((1, block_i, 1), lambda g, i, j: (g, i, 0))
        out_shape = (nb, ip, 1)
        j_dim = 2
    else:
        grid = (ip // block_i, jp // block_j)
        in_specs = [
            pl.BlockSpec((block_i, c), lambda i, j: (i, 0)),
            pl.BlockSpec((block_j, c), lambda i, j: (j, 0)),
            pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
        ]
        out_specs = pl.BlockSpec((block_i, 1), lambda i, j: (i, 0))
        out_shape = (ip, 1)
        j_dim = 1

    out = pl.pallas_call(
        functools.partial(_abs_rowsum_kernel, j_dim=j_dim,
                          precision=precision),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_struct(out_shape, jnp.float32, a, b, acc),
        interpret=interpret_mode(interpret, a, b, acc),
    )(a, b, acc[..., None])
    return out[..., :bl, 0]
