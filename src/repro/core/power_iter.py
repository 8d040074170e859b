"""Batched top-eigenpair extraction for slice covariances (paper §III-C).

For each slice T_i (r × c) the paper extracts the top eigenpair of
C_i = T_iᵀT_i with power iteration.  Two paths:

* explicit gram (paper-faithful): form C_i once (r·c² MACs) then iterate
  v ← C_i v (c² per iteration).  This is what the reference MPI code does.
* matrix-free (beyond-paper): iterate v ← T_iᵀ(T_i v) (2·r·c per
  iteration) and never materialize C_i.  For the paper's 1000³ tensors
  this trades 10⁹ one-time MACs per slice for 2·10⁶ per iteration — a
  ~8× FLOP reduction at 60 iterations — and drops the c×c temporary,
  which is what matters for VMEM residency on TPU.

All slices on a device are processed as one batched einsum so the MXU
sees large matmuls rather than a per-slice loop.

Adaptive convergence gating (DESIGN.md §7.3): when `tol > 0` the fixed
trip count becomes a *cap*.  Every `check_every` sweeps the solver
measures the λ-weighted Rayleigh residual

    max_i  (‖C_i v_i − λ_i v_i‖ / max(λ_i, 1)) · λ_i / λ_max

and exits once it drops below `tol`.  The λ/λ_max weighting matches how
eigenvectors actually enter MSC: row i of the normalized matrix V is
(λ_i/λ_max)·v_i, so an unconverged direction in a small-λ noise slice
perturbs the similarity sums proportionally less.  High-gap planted
slices converge in ~10 sweeps; the weighting keeps slow Wishart noise
slices from pinning every solve at the cap.  Both reductions are exact
maxima, so the parallel schedules reproduce them with `lax.pmax` over
the group axis (all group members take the same trip count — the
lockstep contract of tests/test_msc_parallel.py).

Mixed precision (DESIGN.md §7.3): `precision="bf16_fp32"` runs the
T v / Tᵀ(T v) einsums with bf16 operands and fp32 accumulation
(`preferred_element_type`); normalization, the convergence gate, and the
final Rayleigh quotient stay in fp32.

Inner-axis sharding (DESIGN.md §7.5): when `inner_axis` names a mesh
axis, each device holds only a (b, r/q, c) row-block of its slices and
every contraction over r becomes a local partial + `lax.psum` over the
inner axis — T v, Tᵀ(T v), the explicit gram, and the final Rayleigh
quotient ‖T v‖².  v, λ, and the convergence gate then live replicated
across the inner axis (the eigenvector dim c is never sharded), so the
lockstep-exit contract over the slice axes is unchanged.  `c_valid`
masks the deterministic start vector to the first c_valid entries when a
relayout had to zero-pad the column dim: padded columns stay exactly
zero through every matvec and norm, making the padded run bit-identical
to the unpadded one.  It may be a static int (the relayout paths) or a
traced array (the serving path's per-request column bounds).

Request batching (DESIGN.md §7.6): every solver is rank-polymorphic in
a leading request dim — slices (B, b, r, c) runs B independent MSC
requests through one set of fused contractions.  The convergence gate
then issues one verdict *per request* (maxima reduce over the slice dim
only): a converged request's iterate freezes and its counter stops,
while the while_loop exits on the batch-max (all requests done) so the
lockstep contract over the mesh is preserved.  `iters` comes back with
the request shape — per-request realized sweeps, not the batch max.

Resumable solves (DESIGN.md §7.7): the gated loop's carry is the
explicit `SolveState` pytree (iterate, λ, residual, per-request counter
and verdict) and one gate chunk is the explicit `step_chunk` transition
on it.  The in-jit adaptive solvers run `step_chunk` under a
lax.while_loop (`_gated_loop`); the continuous serving engine instead
persists SolveState on device between dispatches and drives the SAME
transition from the host — one chunk-step executable per call — so a
request can be evicted/refilled at any chunk boundary with iterates
bit-identical to the uninterrupted solve.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

PRECISIONS = ("fp32", "bf16_fp32")


def compute_dtype(precision: str):
    """Operand dtype of the precision policy ("fp32" | "bf16_fp32")."""
    if precision == "fp32":
        return jnp.float32
    if precision == "bf16_fp32":
        return jnp.bfloat16
    raise ValueError(f"unknown precision {precision!r}; expected {PRECISIONS}")


# Contraction precision per policy (None = the backend default).  On a
# TPU an fp32 dot at the default precision runs as one bf16 pass, which
# would make "fp32" nearly the bf16_fp32 program; HIGHEST keeps it fp32.
# The CPU computes fp32 dots in fp32 either way.
DOT_PRECISION = {"fp32": jax.lax.Precision.HIGHEST, "bf16_fp32": None}


def dot_precision(precision: str):
    """`jax.lax.Precision` of the policy's contractions, passed to every
    einsum and kernel dot of the MSC path."""
    compute_dtype(precision)
    return DOT_PRECISION[precision]


def _init_vectors(batch, dim: int, dtype=jnp.float32,
                  c_valid=None) -> jax.Array:
    """Deterministic start vectors with guaranteed overlap with any
    non-negative planted direction: ones + a fixed low-amplitude
    perturbation (breaks ties/orthogonal starts without a PRNG key).

    batch: an int (the slice count) or a tuple of leading dims —
    (B, b) for the request-batched solvers.

    c_valid: when the column dim was zero-padded (dim > true c), mask
    the init to the first c_valid entries and normalize over them — the
    resulting iterates are bit-identical to the unpadded solve (padded
    columns are zero in T, so they stay exactly zero forever).  Accepts
    a scalar (static or traced) or a per-request array broadcastable
    against the batch dims (e.g. (B, 1) for batch=(B, b)): the serving
    engine's buckets pad every request to one shape, so each request
    masks to its own true column count."""
    shape = (batch,) if isinstance(batch, int) else tuple(batch)
    pert = 0.01 * jnp.sin(1.37 * jnp.arange(dim, dtype=dtype) + 0.3)
    v0 = jnp.ones((dim,), dtype) + pert
    if c_valid is not None:
        cv = jnp.asarray(c_valid)
        v0 = jnp.where(jnp.arange(dim) < cv[..., None], v0, 0.0)
    v0 = v0 / jnp.linalg.norm(v0, axis=-1, keepdims=True)
    return jnp.broadcast_to(v0, (*shape, dim))


def _normalize(v, eps=1e-30):
    return v / (jnp.linalg.norm(v, axis=-1, keepdims=True) + eps)


def merge_warm_start(v0: jax.Array, warm_v: jax.Array,
                     use_warm: jax.Array) -> jax.Array:
    """Per-request warm-start selection for the serving admission path
    (DESIGN.md §7.10): request b's start iterates are `warm_v[b]` — a
    cached near-converged eigenvector set — where `use_warm[b]`, else
    the deterministic `_init_vectors` start `v0[b]`.

    `warm_v` rows are re-normalized defensively (cached iterates are
    already unit, but persistence round-trips and column re-padding
    must not be able to feed the gate an off-scale vector); all-zero
    padded rows stay exactly zero, preserving the padded-slice
    invariants the chunk step relies on.  Traced-shape only — this runs
    inside the refill executable, so warm admissions recompile nothing.
    """
    w = _normalize(jnp.asarray(warm_v, v0.dtype))
    u = jnp.asarray(use_warm).reshape(
        (-1,) + (1,) * (v0.ndim - 1))
    return jnp.where(u, w, v0)


def predict_remaining_sweeps(iter_hist, current: int, *, cap: int,
                             check_every: int = 1) -> float:
    """Expected remaining power-iteration sweeps of a request that has
    already run `current` sweeps, under the empirical sweep histogram
    (the serving engine's §7.11 `_sweep_hist` of realized max-mode
    sweeps).

    The conditional-tail estimate E[S − current | S > current] captures
    the heavy tail the SLO scheduler cares about: realized sweeps are
    bimodal (planted-gap requests gate in a chunk or two, near-noise
    requests run toward the cap), so the longer a request has already
    run, the *larger* its expected remaining work — which is exactly why
    the preemption policy targets the longest-running slot.  A request
    that has outlived every histogram entry is predicted to run to the
    `cap` (the near-noise worst case); an empty histogram predicts one
    more gate chunk.  Host-side pure function — policy only, never part
    of any compiled program.
    """
    cur = max(0, int(current))
    tail = [int(s) for s in iter_hist if int(s) > cur]
    if tail:
        return sum(tail) / len(tail) - cur
    if any(int(s) <= cur for s in iter_hist):
        # ran past everything ever observed: assume a cap-runner
        return float(max(cap - cur, check_every))
    return float(max(1, check_every))


def _mark_varying(v, vary_axes):
    """Mark the loop-carry init as device-varying inside shard_map.

    shard_map's vma tracking requires the loop carry to keep the same
    varying-axes type as the body output; the deterministic init is
    replicated, so callers running under shard_map pass their mesh axes."""
    if vary_axes:
        axes = (vary_axes,) if isinstance(vary_axes, str) else tuple(vary_axes)
        return jax.lax.pcast(v, axes, to="varying")
    return v


def _psum_inner(x, inner_axis):
    """All-reduce a partial contraction over the inner (row-shard) axis.

    The identity when inner_axis is None.  Outputs are replicated over
    the inner axis — the replication ladder's step *down* (its step up
    is `_mark_varying(x, inner_axis)` on the way into a contraction)."""
    return jax.lax.psum(x, inner_axis) if inner_axis is not None else x


def convergence_gate(lam: jax.Array, resid: jax.Array, tol: float,
                     axis_name=None) -> jax.Array:
    """True once every slice's λ-weighted residual is below tol.

    lam: (..., b) Rayleigh quotients; resid: (..., b) ‖C v − λ v‖ per
    slice.  Maxima reduce over the slice dim only, so any leading
    request dims get one independent verdict each.  Under shard_map,
    axis_name reduces both maxima over the group axis so all devices
    reach the same verdict (collective-safe lockstep exit).
    """
    weighted = jnp.max(resid / jnp.maximum(lam, 1.0) * lam, axis=-1)
    lam_max = jnp.max(lam, axis=-1)
    if axis_name is not None:
        weighted = jax.lax.pmax(weighted, axis_name)
        lam_max = jax.lax.pmax(lam_max, axis_name)
    return weighted <= tol * jnp.maximum(lam_max, 1e-30)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SolveState:
    """Resumable eigensolver carry (DESIGN.md §7.7).

    One gate chunk (`step_chunk`) maps SolveState → SolveState; the
    leading dims of every field are independent requests.  Fields:

      v:     (..., b, c) current unit iterates (frozen once done)
      lam:   (..., b)    Rayleigh quotients at the last gate probe
      resid: (..., b)    ‖C v − λ v‖ at the last gate probe
      iters: (...)       realized sweeps per request (int32)
      done:  (...)       per-request gate verdict (bool)

    A request stops advancing once `done` fires OR `iters` reaches the
    cap (`exhausted`); its fields then pass through every further
    step_chunk untouched, which is what makes host-driven chunking
    bit-identical to the uninterrupted in-jit while_loop.
    """

    v: jax.Array
    lam: jax.Array
    resid: jax.Array
    iters: jax.Array
    done: jax.Array

    def tree_flatten(self):
        return (self.v, self.lam, self.resid, self.iters, self.done), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def exhausted(self, n_iters: int) -> jax.Array:
        """Per-request 'will never advance again' — converged or capped."""
        return self.done | (self.iters >= n_iters)


def init_solve_state(v0: jax.Array, vary_axes=None) -> SolveState:
    """Fresh SolveState from (pre-marked) start vectors v0 (..., b, c)."""
    gshape, b = v0.shape[:-2], v0.shape[-2]

    def mk(shape, dtype):
        return _mark_varying(jnp.zeros(shape, dtype), vary_axes)

    return SolveState(v=v0, lam=mk(gshape + (b,), jnp.float32),
                      resid=mk(gshape + (b,), jnp.float32),
                      iters=mk(gshape, jnp.int32), done=mk(gshape, bool))


def step_chunk(chunk_fn, state: SolveState, *, k: int, n_iters: int,
               tol: float, axis_name=None) -> SolveState:
    """One gate chunk: advance every unfinished request by k sweeps.

    chunk_fn(v) -> (v_new, lam, resid): k sweeps from v with the gate
    probe measured at the final sweep; v is (..., b, c), lam/resid
    (..., b).  Each request gets its own gate verdict; a finished
    request's fields pass through untouched (the carried v keeps the
    converged state — bit-identical to running that request alone) and
    its counter stops.  The chunk body itself always computes on the
    full batch (fixed shapes, lockstep collectives); `active` only
    masks the state update.
    """
    active = ~state.done & (state.iters < n_iters)
    v_new, lam, resid = chunk_fn(state.v)
    fired = convergence_gate(lam, resid, tol, axis_name)
    return SolveState(
        v=jnp.where(active[..., None, None], v_new, state.v),
        lam=jnp.where(active[..., None], lam, state.lam),
        resid=jnp.where(active[..., None], resid, state.resid),
        iters=jnp.where(active, state.iters + k, state.iters),
        done=state.done | (active & fired))


def _gated_loop(chunk_fn, v, n_iters: int, k: int, tol: float,
                axis_name, vary_axes):
    """Lockstep-gated chunked while_loop shared by the jnp and kernel
    paths: `step_chunk` driven to quiescence in one jit.  The loop exits
    on the batch-max (all requests done or capped) so every device still
    takes the same trip count.  Returns (v, iters) with iters shaped
    like the request dims (scalar for the unbatched solvers).
    """
    def cond(state):
        return jnp.any(~state.exhausted(n_iters))

    def body(state):
        return step_chunk(chunk_fn, state, k=k, n_iters=n_iters, tol=tol,
                          axis_name=axis_name)

    state = jax.lax.while_loop(cond, body, init_solve_state(v, vary_axes))
    return state.v, state.iters


def make_chunk_probe(matvec, k: int):
    """chunk_fn(v) -> (v_new, lam, resid): k matvec sweeps with the gate
    probe reusing the final sweep — the einsum-path gate-chunk body,
    shared by the in-jit gated loop and the chunk-resumable serving path
    (one definition ⇒ identical numerics between the two).

    matvec(v) must return the *unnormalized* image C v in fp32.
    """
    def step(_, v):
        return _normalize(matvec(v))

    def chunk_fn(v):
        v = jax.lax.fori_loop(0, k - 1, step, v)
        # final sweep of the chunk doubles as the residual probe: w = C v
        # is both the convergence measurement and the next iterate.
        w = matvec(v)
        lam = jnp.sum(w * v, axis=-1)  # Rayleigh quotient (v is unit)
        resid = jnp.linalg.norm(w - lam[..., None] * v, axis=-1)
        return _normalize(w), lam, resid

    return chunk_fn


def _run_adaptive(matvec, v, n_iters: int, tol: float, check_every: int,
                  axis_name, vary_axes):
    """Shared driver: fixed fori_loop when tol<=0, gated while_loop else.

    matvec(v) must return the *unnormalized* image C v in fp32.
    Returns (v, iters_run).  With tol>0 the cap rounds up to a multiple
    of check_every (identical semantics to the chunked kernel path).
    """
    if tol <= 0.0:
        def step(_, v):
            return _normalize(matvec(v))

        v = jax.lax.fori_loop(0, n_iters, step, v)
        return v, jnp.full(v.shape[:-2], n_iters, jnp.int32)

    k = max(1, min(check_every, n_iters))
    return _gated_loop(make_chunk_probe(matvec, k), v, n_iters, k, tol,
                       axis_name, vary_axes)


def matvec_matrix_free(slices: jax.Array, precision: str = "fp32",
                       inner_axis=None, overlap: bool = False):
    """matvec(v) = Tᵀ(T v) closure over `slices` — precision-policy
    operands, fp32 accumulation, partials psum'd over `inner_axis`.

    overlap=True double-buffers the inner reduction (DESIGN.md §7.11):
    the slice batch splits in half and each half psums independently,
    so half B's local contractions have no data dependence on half A's
    psum and the scheduler hides one reduction under the other half's
    T·v.  Bit-preserving — psum is elementwise per slice, and the
    halves concatenate back in order — so the engine can flip it per
    bucket from the roofline model without touching results.  Needs an
    inner axis and ≥ 2 local slices; degenerates to the fused form
    otherwise.
    """
    dt = compute_dtype(precision)
    prec = dot_precision(precision)
    s = slices.astype(dt)
    b = slices.shape[-3]
    split = bool(overlap) and inner_axis is not None and b >= 2

    def _local(sh, vh):
        tv = jnp.einsum("...rc,...c->...r", sh, vh.astype(dt),
                        precision=prec, preferred_element_type=jnp.float32)
        return jnp.einsum("...rc,...r->...c", sh, tv.astype(dt),
                          precision=prec,
                          preferred_element_type=jnp.float32)

    def matvec(v):
        vb = _mark_varying(v, inner_axis)
        if not split:
            return _psum_inner(_local(s, vb), inner_axis)
        h = b // 2
        wa = _psum_inner(_local(s[..., :h, :, :], vb[..., :h, :]),
                         inner_axis)
        wb = _psum_inner(_local(s[..., h:, :, :], vb[..., h:, :]),
                         inner_axis)
        return jnp.concatenate([wa, wb], axis=-2)

    return matvec


def rayleigh_fp32(slices: jax.Array, v: jax.Array, inner_axis=None):
    """λ = ‖T v‖² per slice, always fp32 — the final Rayleigh quotient
    every solver reports regardless of the operand precision policy."""
    tv = jnp.einsum("...rc,...c->...r", slices.astype(jnp.float32),
                    _mark_varying(v, inner_axis),
                    precision=dot_precision("fp32"))
    return _psum_inner(jnp.sum(tv * tv, axis=-1), inner_axis)


def build_chunk_fn(slices: jax.Array, cfg, inner_axis=None):
    """(chunk_fn, k) for the chunk-resumable serving path (DESIGN.md
    §7.7): the k-sweep gate-chunk body `step_chunk` advances SolveState
    with, dispatched on MSCConfig exactly like `top_eigenpairs` —
    cfg.use_kernels selects the fused Pallas chunk, else the einsum
    probe.  Requires cfg.matrix_free (a chunk-persistent gram operand is
    a follow-up; the serving engines only build matrix-free pipelines).
    """
    if not cfg.matrix_free:
        raise ValueError("chunk-resumable solves require matrix_free=True "
                         "(the explicit gram has no persistent-operand "
                         "form yet)")
    k = max(1, min(cfg.power_check_every, cfg.power_iters))
    if cfg.use_kernels:
        from repro.kernels import ops as kops

        block_r = cfg.block_r if cfg.block_r else 256
        return kops.build_chunk_fn(slices, k, precision=cfg.precision,
                                   inner_axis=inner_axis,
                                   block_r=block_r), k
    return make_chunk_probe(
        matvec_matrix_free(slices, cfg.precision, inner_axis,
                           overlap=cfg.inner_overlap), k), k


@partial(jax.jit, static_argnames=("n_iters", "tol", "check_every",
                                   "precision", "vary_axes", "axis_name",
                                   "inner_axis"))
def power_iteration_matrix_free(slices: jax.Array, n_iters: int = 60,
                                tol: float = 0.0, check_every: int = 6,
                                precision: str = "fp32",
                                vary_axes=None, axis_name=None,
                                inner_axis=None, c_valid=None):
    """Top eigenpair of T_iᵀT_i for a batch of slices, without forming C_i.

    slices: (b, r, c), or (B, b, r, c) for B independent requests — with
    inner_axis set, r is this device's row-block of each slice and both
    matvec halves psum their partials over it.
    Returns (lambdas (..., b), vectors (..., b, c), iters with the
    request shape — () unbatched, (B,) batched).
    λ_i = ‖T_i v_i‖² is the fp32 Rayleigh quotient of C_i at the final v_i
    regardless of the precision policy.
    """
    c = slices.shape[-1]
    matvec = matvec_matrix_free(slices, precision, inner_axis)
    v = _mark_varying(_init_vectors(slices.shape[:-2], c, jnp.float32,
                                   c_valid), vary_axes)
    v, iters = _run_adaptive(matvec, v, n_iters, tol, check_every,
                             axis_name, vary_axes)
    return rayleigh_fp32(slices, v, inner_axis), v, iters


@partial(jax.jit, static_argnames=("n_iters", "tol", "check_every",
                                   "precision", "use_kernel", "vary_axes",
                                   "axis_name", "inner_axis"))
def power_iteration_gram(slices: jax.Array, n_iters: int = 60,
                         tol: float = 0.0, check_every: int = 6,
                         precision: str = "fp32", use_kernel: bool = False,
                         vary_axes=None, axis_name=None, inner_axis=None,
                         c_valid=None):
    """Paper-faithful path: form C_i = T_iᵀT_i explicitly, then iterate.

    slices: (b, r, c) or request-batched (B, b, r, c).  Returns
    (lambdas (..., b), vectors (..., b, c), iters with the request shape).
    The gram is always accumulated and stored in fp32; under bf16_fp32
    the formation and iteration *operands* are bf16.  With inner_axis
    set, the r·c² formation MACs split q ways (partial gram over local
    rows, one psum); the c×c result is replicated over the inner axis
    and the iteration proceeds without further collectives.
    """
    dt = compute_dtype(precision)
    if use_kernel:
        from repro.kernels import ops as kops

        gram = kops.batched_gram(slices.astype(dt), out_dtype=jnp.float32,
                                 precision=dot_precision(precision))
    else:
        gram = jnp.einsum("...rc,...rd->...cd", slices.astype(dt),
                          slices.astype(dt),
                          precision=dot_precision(precision),
                          preferred_element_type=jnp.float32)
    gram = _psum_inner(gram, inner_axis)
    return power_iteration_on_gram(gram, n_iters=n_iters, tol=tol,
                                   check_every=check_every,
                                   precision=precision, vary_axes=vary_axes,
                                   axis_name=axis_name, c_valid=c_valid)


@partial(jax.jit, static_argnames=("n_iters", "tol", "check_every",
                                   "precision", "vary_axes", "axis_name"))
def power_iteration_on_gram(gram: jax.Array, n_iters: int = 60,
                            tol: float = 0.0, check_every: int = 6,
                            precision: str = "fp32", vary_axes=None,
                            axis_name=None, c_valid=None):
    """Power iteration given covariance matrices (..., b, c, c)."""
    c = gram.shape[-1]
    dt = compute_dtype(precision)
    g = gram.astype(dt)

    def matvec(v):
        return jnp.einsum("...cd,...d->...c", g, v.astype(dt),
                          precision=dot_precision(precision),
                          preferred_element_type=jnp.float32)

    v = _mark_varying(_init_vectors(gram.shape[:-2], c, jnp.float32,
                                   c_valid), vary_axes)
    v, iters = _run_adaptive(matvec, v, n_iters, tol, check_every,
                             axis_name, vary_axes)
    lam = jnp.einsum("...c,...cd,...d->...", v, gram.astype(jnp.float32), v,
                     precision=dot_precision("fp32"))
    return lam, v, iters


def top_eigenpairs(slices: jax.Array, cfg, vary_axes=None, axis_name=None,
                   inner_axis=None, c_valid=None):
    """Dispatch on MSCConfig: matrix_free/use_kernels select the path;
    power_tol/power_check_every/precision configure the solver.

    inner_axis: mesh axis the slice rows are sharded over (contractions
    psum over it); c_valid: column-validity bound under c-padding (a
    static int, or a per-request array on the batched serving path).
    slices may carry a leading request dim (B, b, r, c).
    Returns (lambdas (..., b), vectors (..., b, c), iters) — iters is
    the realized sweep count per request (== cfg.power_iters when the
    gate never fires), shaped () unbatched / (B,) batched.
    """
    kw = dict(n_iters=cfg.power_iters, tol=cfg.power_tol,
              check_every=cfg.power_check_every, precision=cfg.precision,
              vary_axes=vary_axes, axis_name=axis_name,
              inner_axis=inner_axis, c_valid=c_valid)
    if cfg.matrix_free:
        if cfg.use_kernels:
            from repro.kernels import ops as kops

            return kops.power_iterate_matrix_free(slices, **kw)
        return power_iteration_matrix_free(slices, **kw)
    return power_iteration_gram(slices, use_kernel=cfg.use_kernels, **kw)


def rayleigh_residual(slices: jax.Array, lam: jax.Array, v: jax.Array):
    """‖C v − λ v‖ / max(λ, 1) per slice — convergence diagnostic for tests."""
    tv = jnp.einsum("brc,bc->br", slices, v)
    cv = jnp.einsum("brc,br->bc", slices, tv)
    resid = jnp.linalg.norm(cv - lam[:, None] * v, axis=-1)
    return resid / jnp.maximum(lam, 1.0)
