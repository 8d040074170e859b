"""Pallas TPU kernel: batched slice covariance C_i = T_iᵀT_i.

This is the paper-faithful hot spot (Alg. 1 line 1): every slice's gram
matrix, batched over the slices a device owns.  The kernel tiles the
(c × c) output into VMEM blocks and marches over the contraction (row)
dimension, accumulating on the MXU in fp32.

Grid: (b, ci, cj, rk) — rk innermost so the output block (ci, cj) stays
resident in VMEM across the whole contraction (classic matmul schedule).
Block sizes default to 128/256 — MXU-aligned (multiples of 128 on the
lane dim) and small enough that 3 blocks (two inputs + acc) fit VMEM:
  2·(block_r × block_c)·4B + block_c²·4B ≈ 2·128KiB + 256KiB ≪ 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .vma import interpret_mode, out_struct


def _gram_kernel(t1_ref, t2_ref, o_ref, *, precision=None):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = t1_ref[0]  # (block_r, block_ci), native operand dtype (fp32 or bf16)
    b = t2_ref[0]  # (block_r, block_cj)
    o_ref[0, :, :] += jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())),  # contract rows: aᵀ·b
        precision=precision, preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit,
                   static_argnames=("block_r", "block_c", "out_dtype",
                                    "interpret", "precision"))
def batched_gram(slices: jax.Array, *, block_r: int = 256, block_c: int = 128,
                 out_dtype=None, interpret: bool = False,
                 precision=None) -> jax.Array:
    """(b, r, c) → (b, c, c), accumulated in fp32.

    Contractions run in the input's operand dtype (bf16 inputs → bf16 MXU
    passes under the mixed-precision policy) with fp32 accumulation.
    out_dtype: result dtype; defaults to the input dtype.  The adaptive
    eigensolver requests fp32 so bf16-operand grams keep their fp32
    accumulation downstream.
    """
    b, r, c = slices.shape
    block_r = min(block_r, r)
    block_c = min(block_c, c)
    # pad r and c to block multiples; zero rows/cols add zero contributions
    rp = pl.cdiv(r, block_r) * block_r
    cp = pl.cdiv(c, block_c) * block_c
    if (rp, cp) != (r, c):
        slices = jnp.pad(slices, ((0, 0), (0, rp - r), (0, cp - c)))
    grid = (b, cp // block_c, cp // block_c, rp // block_r)

    out = pl.pallas_call(
        functools.partial(_gram_kernel, precision=precision),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_r, block_c),
                         lambda bi, ci, cj, rk: (bi, rk, ci)),
            pl.BlockSpec((1, block_r, block_c),
                         lambda bi, ci, cj, rk: (bi, rk, cj)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_c),
                               lambda bi, ci, cj, rk: (bi, ci, cj)),
        out_shape=out_struct((b, cp, cp), jnp.float32, slices),
        interpret=interpret_mode(interpret, slices),
    )(slices, slices)
    return out[:, :c, :c].astype(out_dtype or slices.dtype)
