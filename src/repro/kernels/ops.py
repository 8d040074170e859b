"""jit'd public wrappers for the Pallas kernels.

Dispatch policy: on TPU the kernels run compiled (`interpret=False`); on
CPU (the test platform) they run in interpret mode, which executes the
kernel body in Python per grid step — bit-faithful to the TPU dataflow
but slow, so the big-tensor paths (core MSC, models) only route through
kernels when `MSCConfig.use_kernels` / `ModelConfig.use_pallas` is set
(tests and kernel benches); the dry-run lowers the jnp path.  Any other
backend is an error: the kernels are written for the TPU, and silently
interpreting them there would hide that the device path never ran.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import gram as _gram
from . import power_iter as _pi
from . import ring as _ring
from . import ref


@functools.cache
def _interpret_default() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for the TPU and interpret on the "
            f"CPU; backend {backend!r} is neither")
    return backend == "cpu"


def batched_gram(slices: jax.Array, *, interpret: bool | None = None,
                 block_r: int = 256, block_c: int = 128,
                 out_dtype=None, precision=None) -> jax.Array:
    """Pallas batched slice covariance C_i = T_iᵀT_i (see gram.py).

    A leading request dim (B, b, r, c) flattens into the kernel's slice
    grid axis and unflattens on exit (DESIGN.md §7.6)."""
    interpret = _interpret_default() if interpret is None else interpret
    lead = slices.shape[:-3]
    if lead:
        flat = batched_gram(slices.reshape((-1,) + slices.shape[-2:]),
                            interpret=interpret, block_r=block_r,
                            block_c=block_c, out_dtype=out_dtype,
                            precision=precision)
        return flat.reshape(lead + (slices.shape[-3],) + flat.shape[1:])
    return _gram.batched_gram(slices, block_r=block_r, block_c=block_c,
                              out_dtype=out_dtype, interpret=interpret,
                              precision=precision)


def abs_rowsum(a: jax.Array, b: jax.Array, acc=None, *,
               block_i: int = 128, block_j: int = 128,
               interpret: bool | None = None, precision=None) -> jax.Array:
    """Fused accumulation acc + Σ|a bᵀ| row-sums (see ring.py).

    The single epilogue kernel: the ring epilogue calls it once per
    circulating chunk with the running accumulator, the allgather
    epilogue once with the full gathered V and acc=None (the schedule
    that the retired similarity.py kernel hard-coded).  block_i/block_j
    tile the output grid (clamped to the operand extents inside the
    kernel); every block shape is bit-identical — the autotuner only
    changes which one compiles fastest."""
    interpret = _interpret_default() if interpret is None else interpret
    return _ring.abs_rowsum(a, b, acc, block_i=block_i, block_j=block_j,
                            interpret=interpret, precision=precision)


def build_chunk_fn(slices: jax.Array, k: int, *, precision: str = "fp32",
                   inner_axis=None, block_r: int = 256,
                   interpret: bool | None = None):
    """Kernel-path gate-chunk body (DESIGN.md §7.7): chunk_fn(v) ->
    (v_new, lam, resid), k fused sweeps + the gate probe — the Pallas
    analogue of `core.power_iter.make_chunk_probe`, shared by the
    in-jit gated loop below and the chunk-resumable serving path.  With
    inner_axis set the fusion drops to one `power_matvec` per sweep so
    the caller's psum can complete w before normalization."""
    from repro.core.power_iter import (_mark_varying, _psum_inner,
                                       compute_dtype, dot_precision,
                                       make_chunk_probe)

    interpret = _interpret_default() if interpret is None else interpret
    s = slices.astype(compute_dtype(precision))
    prec = dot_precision(precision)
    if inner_axis is not None:
        def matvec(v):
            w = _pi.power_matvec(s, _mark_varying(v, inner_axis),
                                 block_r=block_r, interpret=interpret,
                                 precision=prec)
            return _psum_inner(w, inner_axis)

        return make_chunk_probe(matvec, k)

    def chunk_fn(v):
        return _pi.power_iterate_chunk(s, v, k, block_r=block_r,
                                       interpret=interpret, precision=prec)

    return chunk_fn


def power_iterate_matrix_free(slices: jax.Array, n_iters: int = 60,
                              tol: float = 0.0, check_every: int = 6,
                              precision: str = "fp32", vary_axes=None,
                              axis_name=None, inner_axis=None,
                              c_valid=None, *, block_r: int = 256,
                              interpret: bool | None = None):
    """Fused r-tiled power iteration (see power_iter.py), adaptive-capable.

    Matches repro.core.power_iter's deterministic init and convergence
    gate so the kernel path is drop-in for MSCConfig.use_kernels=True:
    when tol > 0, the kernel runs in check_every-sweep chunks inside a
    lax.while_loop, each chunk emitting the fp32 Rayleigh quotient and
    residual that feed the shared λ-weighted gate (pmax-reduced over
    axis_name under shard_map — same lockstep exit as the jnp path).

    Axis-aware path (DESIGN.md §7.5): with inner_axis set, each device
    holds only a row-block of every slice, so multi-sweep fusion is
    impossible — each sweep needs a cross-device psum of the partial
    w = Tᵀ(T v) before normalization.  The dispatch drops to one fused
    r-tiled `power_matvec` kernel launch per sweep, with the shared jnp
    driver (`_run_adaptive`) supplying the psum, normalization, and the
    lockstep gate.  c_valid masks the deterministic init under column
    padding, exactly like the jnp path.

    Request batching (DESIGN.md §7.6): slices (B, b, r, c) flattens into
    one fused launch at grid (B·b, sweep, r_tile); the gated paths share
    the per-request verdict/freeze driver (`_gated_loop`) with the jnp
    solver, so iters comes back per request.

    Returns (lam (..., b), v (..., b, c), iters with the request shape);
    λ is always a final fp32 Rayleigh quotient, regardless of the
    operand precision policy.
    """
    from repro.core.power_iter import (_gated_loop, _init_vectors,
                                       _mark_varying, _psum_inner,
                                       _run_adaptive, compute_dtype,
                                       dot_precision, rayleigh_fp32)

    interpret = _interpret_default() if interpret is None else interpret
    c = slices.shape[-1]
    s = slices.astype(compute_dtype(precision))
    prec = dot_precision(precision)
    v0 = _mark_varying(_init_vectors(slices.shape[:-2], c, jnp.float32,
                                    c_valid), vary_axes)

    if inner_axis is not None:
        def matvec(v):
            w = _pi.power_matvec(s, _mark_varying(v, inner_axis),
                                 block_r=block_r, interpret=interpret,
                                 precision=prec)
            return _psum_inner(w, inner_axis)

        v, iters = _run_adaptive(matvec, v0, n_iters, tol, check_every,
                                 axis_name, vary_axes)
        return rayleigh_fp32(slices, v, inner_axis), v, iters

    if tol <= 0.0:
        lam, v = _pi.power_iterate(s, v0, n_iters, block_r=block_r,
                                   interpret=interpret, precision=prec)
        if precision != "fp32":
            lam = rayleigh_fp32(slices, v)
        return lam, v, jnp.full(slices.shape[:-3], n_iters, jnp.int32)

    k = max(1, min(check_every, n_iters))
    chunk_fn = build_chunk_fn(slices, k, precision=precision,
                              block_r=block_r, interpret=interpret)
    v, iters = _gated_loop(chunk_fn, v0, n_iters, k, tol, axis_name,
                           vary_axes)
    return rayleigh_fp32(slices, v), v, iters


def flash_attention(q, k, v, *, causal=True, scale=None, q_offset=0,
                    window=None, softcap=None, interpret: bool | None = None,
                    block_q: int = 128, block_k: int = 512):
    """Fused flash attention (see flash_attention.py)."""
    interpret = _interpret_default() if interpret is None else interpret
    return _fa.flash_attention(
        q, k, v, causal=causal, scale=scale, q_offset=q_offset,
        window=window, softcap=softcap, block_q=block_q, block_k=block_k,
        interpret=interpret)
