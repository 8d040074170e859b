"""Sequential MSC reference (paper Alg. 1) — the single-device oracle.

This is the ground truth every parallel schedule must match bit-for-bit
(up to collective reduction order).  It is also the version used for the
paper's sequential-baseline timings in benchmarks/fig6_data_scaling.py.

Layout convention: for mode j we build a `slices` array of shape
(m_j, r_j, c_j) whose i-th entry is the paper's slice T_i (a matrix); the
per-slice covariance is C_i = T_iᵀT_i of shape (c_j, c_j).  Our `V` is
stored row-major — row i is the paper's column λ̃_i ṽ_i — so the paper's
C = |VᵀV| becomes |V Vᵀ| here.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .extraction import extract_cluster
from .power_iter import compute_dtype, dot_precision, top_eigenpairs
from .types import ModeResult, MSCConfig, MSCResult

# Transpositions taking T (m1,m2,m3) to (m_j, r_j, c_j) slice-major form.
MODE_PERMS = ((0, 1, 2), (1, 0, 2), (2, 0, 1))


def mode_slices(tensor: jax.Array, mode: int) -> jax.Array:
    """(m_j, r_j, c_j) slice-major view of the tensor for mode j∈{0,1,2}."""
    return jnp.transpose(tensor, MODE_PERMS[mode])


def normalized_eigrows(
    slices: jax.Array,
    cfg: MSCConfig,
    valid_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Rows λ̃_i ṽ_i of the normalized matrix V (paper's columns).

    Returns (V (m, c), lambdas (m,), power_iters_run ()).  Padded slices
    (valid_mask False) get zero rows and are excluded from the λ_max
    normalization, which is always performed in fp32.
    """
    lam, vec, p_iters = top_eigenpairs(slices, cfg)
    if valid_mask is not None:
        lam = jnp.where(valid_mask, lam, 0.0)
    lam_max = jnp.maximum(jnp.max(lam), 1e-30)
    v_rows = (lam / lam_max)[:, None] * vec
    if valid_mask is not None:
        v_rows = jnp.where(valid_mask[:, None], v_rows, 0.0)
    return v_rows, lam, p_iters


def similarity_matrix(v_rows: jax.Array, precision: str = "fp32") -> jax.Array:
    """C = |V Vᵀ| (paper's C = |VᵀV| in our row-major storage)."""
    dt = compute_dtype(precision)
    prod = jnp.einsum("ic,jc->ij", v_rows.astype(dt), v_rows.astype(dt),
                      precision=dot_precision(precision),
                      preferred_element_type=jnp.float32)
    return jnp.abs(prod)


def marginal_sums(v_rows: jax.Array, valid_mask: Optional[jax.Array] = None,
                  precision: str = "fp32") -> jax.Array:
    """d_i = Σ_j c_ij.  Padded columns contribute zero rows in V already."""
    c = similarity_matrix(v_rows, precision)
    if valid_mask is not None:
        c = jnp.where(valid_mask[None, :], c, 0.0)
    return jnp.sum(c, axis=1)


def cluster_mode_slices(
    slices: jax.Array,
    cfg: MSCConfig,
    valid_mask: Optional[jax.Array] = None,
) -> ModeResult:
    """Cluster one mode given its slice-major tensor (m, r, c)."""
    v_rows, lam, p_iters = normalized_eigrows(slices, cfg, valid_mask)
    d = marginal_sums(v_rows, valid_mask, cfg.precision)
    mask, n_iters = extract_cluster(
        d, cfg.epsilon, valid_mask, cfg.max_extraction_iters
    )
    return ModeResult(mask=mask, d=d, lambdas=lam, n_iters=n_iters,
                      power_iters_run=p_iters)


@partial(jax.jit, static_argnames=("cfg",))
def msc_sequential(tensor: jax.Array, cfg: MSCConfig) -> MSCResult:
    """Full MSC (paper Alg. 1): cluster all three modes of `tensor`."""
    modes = tuple(
        cluster_mode_slices(mode_slices(tensor, j), cfg) for j in range(3)
    )
    return MSCResult(modes=modes)


@partial(jax.jit, static_argnames=("cfg",))
def msc_similarity_matrices(tensor: jax.Array, cfg: MSCConfig):
    """Per-mode similarity matrices C (for the paper's sim metric, Eq. 6)."""
    out = []
    for j in range(3):
        v_rows, _, _ = normalized_eigrows(mode_slices(tensor, j), cfg)
        out.append(similarity_matrix(v_rows, cfg.precision))
    return tuple(out)
