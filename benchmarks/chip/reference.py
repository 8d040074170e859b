"""Plain Multi-Slice Clustering, the yardstick the served masks are held to.

Written from the method's description (arXiv 2309.17383, Alg. 1, with the
adaptive gate of the configurations' `power_tol`), one tensor and one mode
at a time, in plain `jax.numpy` and numpy.  It imports nothing of the
system under test and takes nothing it made.

For mode j the tensor is read as m_j slices T_i (r × c).  Each slice's top
eigenpair of T_iᵀT_i comes from matrix-free power iteration
v ← Tᵀ(T v) / ‖·‖ from the start vector 1 + 0.01·sin(1.37·k + 0.3).  The
sweeps run in chunks of `power_check_every`; the last sweep of a chunk is
the probe: with w = Tᵀ(T v), λ = w·v and the residual ‖w − λ v‖, the solve
stops once max_i (residual_i / max(λ_i, 1))·λ_i ≤ power_tol · max_i λ_i,
or at `power_iters` sweeps.  Then λ_i = ‖T_i v_i‖², the rows
V_i = (λ_i / max λ) v_i, d = row sums of |V Vᵀ|, and the cluster is the
part of d above its largest gap, trimmed while its spread exceeds
l·ε/2 + √log(max(m − l, 2)) (Theorem II.1).

Every contraction runs at the configurations' stated fp32 precision
(`jax.lax.Precision.HIGHEST`), or with `passes=3` as three bf16 passes
with float32 accumulation (hi·hi + hi·lo + lo·hi, the TPU's HIGH): the
control, which the comparison has to fail.  The three passes are spelled
out, so the control is the same arithmetic on every backend.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# tensor axes read as (slice, row, column) for each mode
MODE_PERMS = ((0, 1, 2), (1, 0, 2), (2, 0, 1))
_EPS = 1e-30


def start_vector(c: int) -> jax.Array:
    v = 1.0 + 0.01 * jnp.sin(1.37 * jnp.arange(c, dtype=jnp.float32) + 0.3)
    return v / jnp.linalg.norm(v)


def _normalize(v):
    return v / (jnp.linalg.norm(v, axis=-1, keepdims=True) + _EPS)


def _dot(spec, a, b, passes):
    if passes is None:
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if passes != 3:
        raise ValueError(f"passes must be None or 3, got {passes}")

    def split(x):
        # reduce_precision, not a round trip through bfloat16: the TPU's
        # compiler may keep a fused bfloat16 intermediate at float32
        # (excess precision), which makes the low part 0 and the three
        # passes one
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return (hi.astype(jnp.bfloat16),
                (x - hi).astype(jnp.bfloat16))

    (ah, al), (bh, bl) = split(a), split(b)

    def one(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)

    return one(ah, bh) + (one(ah, bl) + one(al, bh))


def _sweep(slices, v, passes):
    tv = _dot("brc,bc->br", slices, v, passes)
    return _dot("brc,br->bc", slices, tv, passes)


@partial(jax.jit, static_argnames=("k", "tol", "passes"))
def _chunk(slices, v, *, k, tol, passes):
    """k sweeps; returns (v, whether the gate fires at the last one)."""
    for _ in range(k - 1):
        v = _normalize(_sweep(slices, v, passes))
    w = _sweep(slices, v, passes)
    lam = jnp.sum(w * v, axis=-1)
    resid = jnp.linalg.norm(w - lam[:, None] * v, axis=-1)
    weighted = jnp.max(resid / jnp.maximum(lam, 1.0) * lam)
    fired = weighted <= tol * jnp.maximum(jnp.max(lam), _EPS)
    return _normalize(w), fired


@partial(jax.jit, static_argnames=("passes",))
def _similarity(slices, v, *, passes):
    """(d, λ) from the final iterates."""
    tv = _dot("brc,bc->br", slices, v, passes)
    lam = jnp.sum(tv * tv, axis=-1)
    rows = (lam / jnp.maximum(jnp.max(lam), _EPS))[:, None] * v
    sim = _dot("ic,jc->ij", rows, rows, passes)
    return jnp.sum(jnp.abs(sim), axis=1), lam


def extract(d: np.ndarray, epsilon: float) -> np.ndarray:
    """Cluster mask from d: largest-gap split, then Theorem II.1 trim."""
    d = np.asarray(d, np.float32)
    m = d.shape[0]
    order = np.argsort(-d, kind="stable")
    ds = d[order]
    k = int(np.argmax(ds[:-1] - ds[1:])) if m > 1 else 0
    mask = d >= ds[k]
    eps = np.float32(epsilon)
    while True:
        l = np.float32(mask.sum())
        bound = (l * eps / np.float32(2.0)
                 + np.sqrt(np.log(max(np.float32(m) - l, np.float32(2.0)))))
        spread = d[mask].max() - d[mask].min()
        if not (spread > bound and l > 1):
            return mask
        mask[np.argmin(np.where(mask, d, np.inf))] = False


def solve(tensor, cfg: dict, passes=None):
    """Plain MSC of one tensor: per mode a dict of mask, d, lambdas and
    sweeps.  `cfg` holds epsilon, power_iters, power_tol and
    power_check_every; `passes=3` computes the control."""
    t = jnp.asarray(tensor, jnp.float32)
    cap = int(cfg["power_iters"])
    k = max(1, min(int(cfg["power_check_every"]), cap))
    out = []
    for perm in MODE_PERMS:
        slices = jnp.transpose(t, perm)
        v = jnp.broadcast_to(start_vector(slices.shape[2]),
                             (slices.shape[0], slices.shape[2]))
        sweeps = 0
        while sweeps < cap:
            v, fired = _chunk(slices, v, k=k, tol=float(cfg["power_tol"]),
                              passes=passes)
            sweeps += k
            if bool(fired):
                break
        d, lam = (np.asarray(x) for x in _similarity(slices, v,
                                                      passes=passes))
        out.append({"mask": extract(d, float(cfg["epsilon"])), "d": d,
                    "lambdas": lam, "sweeps": sweeps})
        del slices
    return out
