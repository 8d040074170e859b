"""Pallas TPU kernel: fused matrix-free power iteration, r-tiled.

The beyond-paper eigensolver (DESIGN.md §7.1) iterates v ← Tᵀ(T v)
without forming the gram matrix.  Expressed in plain jnp, each iteration
re-reads the slice T from HBM (2·r·c·4 B per iteration, arithmetic
intensity ≈ 1 MAC/byte — hopelessly memory-bound).  This kernel keeps the
iteration state (v and the w = Tᵀ(T v) accumulator) VMEM-resident and
streams the slice through VMEM in r-tiles:

  grid  = (b, n_steps, nr)  — slice × sweep × r-tile, r-tile innermost
  block = (block_r × c) tile of T; v/w/λ blocks are indexed by slice
          only, so they stay resident across the whole (sweep, tile)
          subgrid (same revisiting trick as the gram kernel).

For slices that fit VMEM (nr == 1) the T block index is constant across
sweeps, so Pallas fetches the slice from HBM exactly once — the original
whole-slice-resident schedule falls out as the special case.  For
paper-scale r (1000+) the slice streams tile-by-tile each sweep instead
of requiring whole-slice residency (DESIGN.md §7.3).

Per r-tile and sweep, two MXU contractions in the *operand dtype of the
input* (fp32, or bf16 under the mixed-precision policy) with fp32
accumulation:   tv_tile = v Tᵏᵀ   then   w += tv_tile Tᵏ.  `precision`
(a jax.lax.Precision, static) is the precision policy's contraction
precision, passed through to both dots.
After the last tile of a sweep, w is normalized into v in fp32.

Three entry points share the kernel body:

* power_iterate      — n_iters sweeps + a trailing λ = ‖T v‖² pass.
* power_iterate_chunk — k sweeps; additionally emits the fp32 Rayleigh
  quotient λ = vᵀw and residual ‖w − λv‖ measured at the final sweep
  (reusing that sweep's matvec), the inputs of the adaptive convergence
  gate (DESIGN.md §7.3).
* power_matvec       — ONE unnormalized sweep, returning the raw fp32
  accumulator w = Tᵀ(T v).  The building block of the inner-sharded
  solver (DESIGN.md §7.5): when each device holds only a row-block of
  T, the caller must lax.psum the partial w over the inner mesh axis
  *before* normalizing, so normalization cannot live in the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .vma import interpret_mode, out_struct


def _power_kernel(t_ref, v0_ref, lam_ref, v_ref, resid_ref, w_ref, *,
                  n_upd: int, nr: int, lambda_pass: bool, emit_gate: bool,
                  normalize: bool = True, precision=None):
    # Every per-slice ref carries a unit middle dim — v/w are (1, 1, c)
    # blocks of (b, 1, c) arrays, λ/resid (1, 1, 1) blocks of (b, 1, 1)
    # — so each block's last two dims equal the array's, the tiling rule
    # Mosaic enforces on the chip.
    it = pl.program_id(1)
    rk = pl.program_id(2)

    @pl.when((it == 0) & (rk == 0))
    def _init():
        v_ref[0] = v0_ref[0].astype(jnp.float32)
        lam_ref[0] = jnp.zeros((1, 1), jnp.float32)
        resid_ref[0] = jnp.zeros((1, 1), jnp.float32)

    @pl.when(rk == 0)
    def _zero_w():
        w_ref[0] = jnp.zeros((1, w_ref.shape[-1]), jnp.float32)

    t = t_ref[0]                                   # (block_r, c), native dtype
    v = v_ref[0]                                   # (1, c) fp32 state
    tv = jax.lax.dot_general(v.astype(t.dtype), t, (((1,), (1,)), ((), ())),
                             precision=precision,
                             preferred_element_type=jnp.float32)  # (1, block_r)

    if lambda_pass:
        # trailing sweep: accumulate λ = ‖T v‖² instead of updating v
        @pl.when(it == n_upd)
        def _lam():
            lam_ref[0] += jnp.sum(tv * tv, axis=1, keepdims=True)

    @pl.when(it < n_upd)
    def _accum():
        w_ref[0] += jax.lax.dot_general(
            tv.astype(t.dtype), t, (((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)    # (1, c)

    if emit_gate:
        # Rayleigh quotient and residual at the final sweep, from the
        # completed fp32 accumulator w = C v, *before* normalization.
        @pl.when((it == n_upd - 1) & (rk == nr - 1))
        def _gate():
            w = w_ref[0]
            lam = jnp.sum(w * v, axis=1, keepdims=True)
            lam_ref[0] = lam
            resid_ref[0] = jnp.sqrt(
                jnp.sum((w - lam * v) ** 2, axis=1, keepdims=True))

    if normalize:
        @pl.when((it < n_upd) & (rk == nr - 1))
        def _update():
            w = w_ref[0]
            nrm = jnp.sqrt(jnp.sum(w * w, axis=1, keepdims=True)) + 1e-30
            v_ref[0] = w / nrm


def _call(slices, v0, n_upd, *, lambda_pass, emit_gate, block_r, interpret,
          normalize=True, precision=None):
    # Request-batched inputs (B, b, r, c) flatten into the grid's slice
    # dim — one launch at (B·b, sweep, r_tile), the fused form the
    # serving path relies on (DESIGN.md §7.6) — and unflatten on exit.
    lead = slices.shape[:-3]
    if lead:
        bb = lead + (slices.shape[-3],)
        lam, v, resid, w = _call(
            slices.reshape((-1,) + slices.shape[-2:]),
            v0.reshape((-1, v0.shape[-1])), n_upd,
            lambda_pass=lambda_pass, emit_gate=emit_gate, block_r=block_r,
            interpret=interpret, normalize=normalize, precision=precision)
        return (lam.reshape(bb), v.reshape(bb + v.shape[1:]),
                resid.reshape(bb), w.reshape(bb + w.shape[1:]))
    b, r, c = slices.shape
    block_r = min(block_r, r)
    rp = pl.cdiv(r, block_r) * block_r
    if rp != r:  # zero rows contribute nothing to Tᵀ(T v) or ‖T v‖²
        slices = jnp.pad(slices, ((0, 0), (0, rp - r), (0, 0)))
    nr = rp // block_r
    n_steps = n_upd + (1 if lambda_pass else 0)
    v0 = v0.reshape(b, 1, c)
    vec = pl.BlockSpec((1, 1, c), lambda i, it, rk: (i, 0, 0))
    scalar = pl.BlockSpec((1, 1, 1), lambda i, it, rk: (i, 0, 0))

    lam, v, resid, w = pl.pallas_call(
        functools.partial(_power_kernel, n_upd=n_upd, nr=nr,
                          lambda_pass=lambda_pass, emit_gate=emit_gate,
                          normalize=normalize, precision=precision),
        grid=(b, n_steps, nr),
        in_specs=[
            pl.BlockSpec((1, block_r, c), lambda i, it, rk: (i, rk, 0)),
            vec,
        ],
        out_specs=[scalar, vec, scalar, vec],   # λ, v, resid, w scratch
        out_shape=[out_struct(sh, jnp.float32, slices, v0)
                   for sh in ((b, 1, 1), (b, 1, c), (b, 1, 1), (b, 1, c))],
        interpret=interpret_mode(interpret, slices, v0),
    )(slices, v0)
    return lam[:, 0, 0], v[:, 0], resid[:, 0, 0], w[:, 0]


@functools.partial(jax.jit, static_argnames=("n_iters", "block_r",
                                             "interpret", "precision"))
def power_iterate(slices: jax.Array, v0: jax.Array, n_iters: int, *,
                  block_r: int = 256, interpret: bool = False,
                  precision=None):
    """Fused power iteration.  slices: (b, r, c), v0: (b, c); a leading
    request dim (B, b, …) flattens into the grid and unflattens on exit.

    Returns (lam (b,) fp32, v (b, c) fp32) — bit-comparable to
    ref.power_iterate up to fp32 reduction order.  λ is computed with the
    input's operand dtype and fp32 accumulation.
    """
    lam, v, _, _ = _call(slices, v0, n_iters, lambda_pass=True,
                         emit_gate=False, block_r=block_r,
                         interpret=interpret, precision=precision)
    return lam, v


@functools.partial(jax.jit, static_argnames=("k", "block_r", "interpret",
                                             "precision"))
def power_iterate_chunk(slices: jax.Array, v: jax.Array, k: int, *,
                        block_r: int = 256, interpret: bool = False,
                        precision=None):
    """k fused sweeps from state v; emits the convergence-gate measurements.

    Returns (v_new (b, c) fp32, lam (b,) fp32, resid (b,) fp32) with
    λ = vᵀ(C v) and resid = ‖C v − λ v‖ taken at the k-th sweep's
    pre-normalization iterate (the same probe the jnp adaptive path uses).
    """
    lam, v_new, resid, _ = _call(slices, v, k, lambda_pass=False,
                                 emit_gate=True, block_r=block_r,
                                 interpret=interpret, precision=precision)
    return v_new, lam, resid


@functools.partial(jax.jit, static_argnames=("block_r", "interpret",
                                             "precision"))
def power_matvec(slices: jax.Array, v: jax.Array, *,
                 block_r: int = 256, interpret: bool = False,
                 precision=None):
    """One unnormalized r-tiled sweep: w = Tᵀ(T v), fp32 accumulator.

    slices: (b, r, c) — typically a row-block of each slice on an
    inner-sharded mesh; v: (b, c) fp32.  Returns w (b, c) fp32 with NO
    normalization applied — inner-sharded callers psum partial w over
    the mesh axis first, then normalize (core/power_iter._run_adaptive
    drives the sweep loop and the convergence gate).
    """
    _, _, _, w = _call(slices, v, 1, lambda_pass=False, emit_gate=False,
                       normalize=False, block_r=block_r, interpret=interpret,
                       precision=precision)
    return w
