"""ModeSchedule — the shared substrate of every parallel MSC schedule.

Before this layer, `core/parallel.py` held three near-duplicate builders
(flat/gspmd, flat/collective, grouped) that each re-implemented the same
shard_map plumbing: slice padding + validity masks, PartitionSpec
construction, the per-device Alg. 2 body (eigensolve → λ pmax →
normalize → similarity epilogue), lockstep convergence gating, and the
epilogue dispatch.  `ModeSchedule` owns all of that once; the schedules
in `core/parallel.py` are now thin *layout declarations* over it.

Mesh model — 2-D ("slice", "inner") sharding:

  slice_axes — shard the slice index m (the paper's only parallel dim;
      the "group communicator" of Alg. 2 / Fig. 3).  λ-max reduction,
      the lockstep convergence gate, and the similarity epilogue
      (all_gather or ppermute ring) all run over these axes.
  inner_axes — NEW: shard the *within-slice* row (contraction) dim r.
      Each device holds a (b, r/q, c) sub-block, so per-device tensor
      memory is O(m·r·c/(p·q)) and a single huge slice can exceed one
      device's HBM — the memory wall both 1-D schedules hit at paper
      scale.  The T·v / Tᵀ(T v) / gram contractions compute partial
      sums over local rows and `lax.psum` over "inner" (the
      consensus-style distributed eigensolve contraction); v, λ, and
      the epilogue stay replicated across "inner" because c is never
      sharded (the per-slice eigenvector must stay whole).
  group_axes — axes the data varies over without participating in any
      collective (the grouped schedule's "mode"=3 axis: one unfolding
      per group, exactly paper Fig. 3).

Padding contract: the slice dim pads to a multiple of the slice shards
and r to a multiple of the inner shards — zero rows contribute exactly
nothing to TᵀT, ‖T v‖², or the epilogue, so only the slice-index mask
is ever consulted.  When a relayout forces padding of a *column* dim c
(the flat-collective path pads all tensor dims to p·q multiples), the
eigensolver's deterministic start vector is masked and renormalized over
the first `c_valid` entries, which makes the padded-c iterates
bit-identical to the unpadded ones (zero columns stay exactly zero
through every matvec and norm).

Replication discipline (checked `jax.shard_map` vma semantics): loop
carries are typed as varying over group+slice axes only; operands
entering an inner-sharded contraction are `pcast`-lifted to varying
over the inner axes and the partial results `psum`-lowered back, so
d/λ leave the shard_map replicated over "inner" and the out_specs never
mention it.

Request batching (DESIGN.md §7.6): the batched entry points
(`build_batched_mode_fn` / `run_mode_batched` / `finalize_mode_batched`)
run B independent requests through the same per-device body — the
leading request dim rides replicated through every PartitionSpec, the
convergence gate issues per-request verdicts under a batch-max lockstep
exit, and the serving engine's bucket padding reuses the validity-mask
contract with *traced* per-request slice counts and column bounds.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .extraction import extract_cluster
from .power_iter import compute_dtype, dot_precision, top_eigenpairs
from .types import ModeResult, MSCConfig

AxisName = Union[str, Tuple[str, ...]]
Axes = Tuple[str, ...]

EPILOGUES = ("allgather", "ring")


def norm_axes(ax: Optional[AxisName]) -> Axes:
    """None | "a" | ("a", "b") → canonical tuple form."""
    if ax is None:
        return ()
    return (ax,) if isinstance(ax, str) else tuple(ax)


def axis_arg(axes: Axes) -> Optional[AxisName]:
    """Canonical tuple → the form jax collectives take (str when single)."""
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes


def _spec_entry(axes: Axes):
    """Canonical tuple → a PartitionSpec entry."""
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes


def pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


# ------------------------------------------------------------------ epilogue

def _chunk_rowsum(v_local: jax.Array, chunk: jax.Array,
                  acc: Optional[jax.Array], cfg: MSCConfig) -> jax.Array:
    """acc + Σ_j |v_local · chunkᵀ|_{:,j} — one epilogue block contribution.

    Both epilogues route through the same accumulating kernel
    (`kernels/ring.py:abs_rowsum`): the allgather epilogue is the
    degenerate single-chunk case (acc=None, chunk=the gathered V).
    A leading request dim (B, rows, c) batches B independent requests —
    the similarity tile is block-diagonal in requests, so the product
    stays per-request (DESIGN.md §7.6).
    """
    if cfg.use_kernels:
        from repro.kernels import ops as kops

        return kops.abs_rowsum(v_local, chunk, acc,
                               block_i=cfg.block_i or 128,
                               block_j=cfg.block_j or 128,
                               precision=dot_precision(cfg.precision))
    prod = jnp.abs(jnp.einsum("...ic,...jc->...ij", v_local, chunk,
                              precision=dot_precision(cfg.precision),
                              preferred_element_type=jnp.float32))
    d = jnp.sum(prod, axis=-1)
    return d if acc is None else acc + d


def _ring_rowsum(v_local: jax.Array, cfg: MSCConfig, axis_name: AxisName,
                 shards: int) -> jax.Array:
    """Ring similarity epilogue (DESIGN.md §7.4).

    p-1 lax.ppermute steps circulate the (b, c) chunks of V around the
    group axis; each device folds the chunk it currently holds into its
    running row-sums.  Inside the loop body the forward ppermute and the
    chunk matmul both read the carried chunk and are otherwise
    independent, so XLA's async collective-permute can hide step k+1's
    transfer under step k's compute.  The full m×c V is never resident:
    peak epilogue buffer is one chunk (plus the recv landing buffer).
    """
    d = _chunk_rowsum(v_local, v_local, None, cfg)
    if shards == 1:
        return d
    perm = [(i, (i + 1) % shards) for i in range(shards)]

    def body(_, carry):
        chunk, d = carry
        nxt = jax.lax.ppermute(chunk, axis_name, perm)
        return nxt, _chunk_rowsum(v_local, chunk, d, cfg)

    chunk = jax.lax.ppermute(v_local, axis_name, perm)
    chunk, d = jax.lax.fori_loop(0, shards - 2, body, (chunk, d))
    # last received chunk needs no forwarding — it completes the ring
    return _chunk_rowsum(v_local, chunk, d, cfg)


def epilogue_rowsum(v_local: jax.Array, *, cfg: MSCConfig,
                    axis_name: AxisName, shards: int) -> jax.Array:
    """d_local = row-block sums of |V Vᵀ| from this device's rows of V.

    v_local: (rows, c), or (B, rows, c) for B batched requests — the
    collectives then move one B-times-larger message over the same
    schedule, and every contraction stays per-request.

    The paper's MPI_Allgatherv(M) + full |V Vᵀ| row-sum, under the
    MSCConfig.epilogue policy: "allgather" replicates V (blocking
    all_gather, O(m·c) peak buffer), "ring" streams chunks neighbor-to-
    neighbor (O(m·c/p) peak buffer, transfer hidden under compute).
    Operands are cast to the precision policy's compute dtype *before*
    the collective, so bf16_fp32 also halves the epilogue link traffic.
    On 2-D meshes the collectives run over the slice axes only; "inner"
    devices hold replicated V rows and recompute identical sums.
    """
    if cfg.epilogue not in EPILOGUES:
        raise ValueError(
            f"unknown epilogue {cfg.epilogue!r}; expected {EPILOGUES}")
    dt = compute_dtype(cfg.precision)
    vl = v_local.astype(dt)
    if cfg.epilogue == "ring":
        return _ring_rowsum(vl, cfg, axis_name, shards)
    # MPI_Allgatherv(M) over the group → full V on every group member
    # (the gather axis is the slice-row dim: 0 unbatched, 1 under a
    # leading request dim)
    v_full = jax.lax.all_gather(vl, axis_name, axis=vl.ndim - 2, tiled=True)
    # row-block of C = |V Vᵀ| and its row sums; padded columns are zero
    # rows of V and contribute nothing.
    return _chunk_rowsum(vl, v_full, None, cfg)


# -------------------------------------------------------------- ModeSchedule

@dataclasses.dataclass(frozen=True)
class ModeSchedule:
    """One mode-layout declaration: which mesh axes shard what.

    Owns every piece of shard_map plumbing the schedules share — see the
    module docstring.  The flat schedule instantiates one ModeSchedule
    and runs the three modes through it sequentially; the grouped
    schedule adds `group_axes=("mode",)` and runs the stacked unfoldings
    in one shot.
    """

    mesh: Mesh
    cfg: MSCConfig
    slice_axes: Axes
    inner_axes: Axes = ()
    group_axes: Axes = ()

    def __post_init__(self):
        all_axes = self.group_axes + self.slice_axes + self.inner_axes
        missing = [a for a in all_axes if a not in self.mesh.shape]
        if missing:
            raise ValueError(f"axes {missing} not in mesh {self.mesh.shape}")
        if len(set(all_axes)) != len(all_axes):
            raise ValueError(f"overlapping axis roles: {all_axes}")
        if not self.slice_axes:
            raise ValueError("ModeSchedule needs at least one slice axis")

    # ---- static mesh facts -------------------------------------------
    @property
    def slice_shards(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.slice_axes)

    @property
    def inner_shards(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.inner_axes) \
            if self.inner_axes else 1

    @property
    def slice_axis(self) -> AxisName:
        """Collective axis-name form of the slice axes."""
        return axis_arg(self.slice_axes)

    @property
    def inner_axis(self) -> Optional[AxisName]:
        return axis_arg(self.inner_axes)

    @property
    def vary_axes(self) -> Axes:
        """Axes the eigensolver loop carries vary over (NOT "inner": the
        carries are psum-replicated across it, see module docstring)."""
        return self.group_axes + self.slice_axes

    # ---- PartitionSpecs ----------------------------------------------
    @property
    def block_spec(self) -> P:
        """(b, r, c) slice-major blocks: (slice, inner, replicated)."""
        return P(_spec_entry(self.slice_axes),
                 _spec_entry(self.inner_axes), None)

    @property
    def vector_spec(self) -> P:
        """(b,) per-slice vectors (valid mask, d, λ): slice-sharded,
        replicated over inner."""
        return P(_spec_entry(self.slice_axes))

    @property
    def stacked_block_spec(self) -> P:
        """(mode, b, r, c) stacked unfoldings (grouped schedule)."""
        return P(_spec_entry(self.group_axes),
                 _spec_entry(self.slice_axes),
                 _spec_entry(self.inner_axes), None)

    @property
    def stacked_vector_spec(self) -> P:
        return P(_spec_entry(self.group_axes), _spec_entry(self.slice_axes))

    @property
    def batched_block_spec(self) -> P:
        """(B, b, r, c) request-batched blocks: the leading request dim
        is replicated-free (every device holds its shard of every
        request), the rest shard exactly like block_spec."""
        return P(None, _spec_entry(self.slice_axes),
                 _spec_entry(self.inner_axes), None)

    @property
    def batched_vector_spec(self) -> P:
        """(B, b) per-request per-slice vectors."""
        return P(None, _spec_entry(self.slice_axes))

    # ---- padding / masking -------------------------------------------
    def pad_amounts(self, m: int, r: int) -> Tuple[int, int]:
        """(m_pad, r_pad): slice dim to even slice shards, row dim to
        even inner shards (zero rows drop out of every contraction)."""
        return pad_to(m, self.slice_shards), pad_to(r, self.inner_shards)

    def pad_slices(self, slices: jax.Array):
        """(m, r, c) → (padded (m', r', c), valid (m',), m)."""
        m, r, _ = slices.shape
        m_pad, r_pad = self.pad_amounts(m, r)
        if (m_pad, r_pad) != (m, r):
            slices = jnp.pad(slices, ((0, m_pad - m), (0, r_pad - r), (0, 0)))
        valid = jnp.arange(m_pad) < m
        return slices, valid, m

    # ---- the shared per-device body (paper Alg. 2, minus extraction) --
    def mode_local(self, block: jax.Array, valid_local: jax.Array,
                   c_valid=None):
        """Per-device mode computation.

        block: (b, r_local, c) — this device's sub-block of one mode's
          unfolding (slice-sharded rows of slices; inner-sharded rows
          *within* each slice when inner_axes is set) — or (B, b,
          r_local, c) for B batched requests (DESIGN.md §7.6): all
          reductions below stay per-request, so one body serves both.
        valid_local: bool (b,) / (B, b) — False on padding slices.
        c_valid: column-validity bound when the relayout padded c
          (None ⇔ all columns valid; a static int, or a (B, 1) array of
          per-request bounds on the serving path).

        The adaptive eigensolver's convergence gate pmax-reduces its
        residual maxima over the slice axes, so every group member runs
        the same number of sweeps (lockstep exit — padding slices are
        all-zero and contribute zero residual, hence never delay the
        gate).  Batched requests each gate independently — a converged
        request's iterate freezes and its counter stops while the loop
        exits on the batch max.  Inner-sharded contractions psum their
        partials over the inner axes inside each sweep.

        Returns (d_local (..., b), lam_local (..., b), iters (1,) /
        (B, 1)) — this device's shard of d and λ plus the realized
        power-iteration sweep count per request (identical on every
        group member by the lockstep gate; the trailing singleton lets
        it pass through sharded out_specs and be max-reduced outside).
        """
        lam, vec, iters = top_eigenpairs(
            block, self.cfg, vary_axes=self.vary_axes,
            axis_name=self.slice_axis, inner_axis=self.inner_axis,
            c_valid=c_valid)
        d_local, lam = self._similarity_tail(lam, vec, valid_local)
        return d_local, lam, iters[..., None]

    def _similarity_tail(self, lam, vec, valid_local):
        """λ-max normalize + similarity epilogue — the Alg. 2 tail after
        the eigensolve, shared by mode_local and the chunk-resumable
        body (same code ⇒ same numerics on both serving paths).
        Returns (d_local, lam) with padding slices zeroed in both."""
        lam = jnp.where(valid_local, lam, 0.0)
        # MPI_Allreduce(λ, MAX) over the group — fp32 regardless of precision
        lam_max = jax.lax.pmax(jnp.max(lam, axis=-1), self.slice_axis)
        scale = lam / jnp.maximum(lam_max, 1e-30)[..., None]
        v_local = jnp.where(valid_local[..., None], scale[..., None] * vec,
                            0.0)
        d_local = epilogue_rowsum(v_local, cfg=self.cfg,
                                  axis_name=self.slice_axis,
                                  shards=self.slice_shards)
        return jnp.where(valid_local, d_local, 0.0), lam

    # ---- shard_map entry points --------------------------------------
    def build_mode_fn(self, c_valid: Optional[int] = None):
        """shard_map'd (slices (m', r', c), valid (m',)) → (d, λ, iters).

        iters comes back as one counter per slice-shard (global shape
        (slice_shards,)); callers max-reduce it into ModeResult.
        """
        return jax.shard_map(
            partial(self.mode_local, c_valid=c_valid),
            mesh=self.mesh,
            in_specs=(self.block_spec, self.vector_spec),
            out_specs=(self.vector_spec, self.vector_spec,
                       self.vector_spec),
        )

    def run_mode(self, slices: jax.Array):
        """Pad one mode's slice-major tensor and run it (flat schedule)."""
        from jax.sharding import NamedSharding

        padded, valid, m = self.pad_slices(slices)
        # pin the padded block layout so the initial distribution is one
        # well-defined reshard instead of GSPMD's replicate-then-slice
        # fallback (§Perf msc it 2b — without this the tensor argument
        # lands replicated on every device whenever padding intervenes)
        padded = jax.lax.with_sharding_constraint(
            padded, NamedSharding(self.mesh, self.block_spec))
        d, lam, iters = self.build_mode_fn()(padded, valid)
        return d, lam, iters, valid, m

    def finalize_mode(self, d, lam, iters, valid, m: int) -> ModeResult:
        """Replicated cluster extraction + trimming (the tiny epilogue the
        paper Gathers to a root; running it under jit on every device
        removes the root bottleneck entirely)."""
        mask, n_it = extract_cluster(d, self.cfg.epsilon, valid,
                                     self.cfg.max_extraction_iters)
        return ModeResult(mask=mask[:m], d=d[:m], lambdas=lam[:m],
                          n_iters=n_it, power_iters_run=jnp.max(iters))

    # ---- request-batched entry points (DESIGN.md §7.6) ----------------
    def build_batched_mode_fn(self):
        """shard_map'd (slices (B, m', r', c), valid (B, m'), c_req (B,))
        → (d (B, m'), λ (B, m'), iters (B, slice_shards)).

        One compiled body serves B independent requests: the request dim
        rides replicated through every PartitionSpec, the per-request
        column bounds (c_req) mask each request's eigensolver init, and
        iters comes back per request per slice-shard (max-reduced into
        ModeResult by finalize_mode_batched)."""
        def body(block, valid_local, c_req):
            return self.mode_local(block, valid_local,
                                   c_valid=c_req[:, None])

        return jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(self.batched_block_spec, self.batched_vector_spec,
                      P(None)),
            out_specs=(self.batched_vector_spec, self.batched_vector_spec,
                       self.batched_vector_spec),
        )

    def run_mode_batched(self, slices: jax.Array, m_req: jax.Array,
                         c_req: jax.Array):
        """Run one mode for a bucket of B requests.

        slices: (B, M, R, C) — bucket-padded slice-major unfoldings,
          request i's true data in the leading (m_req[i], r, c_req[i])
          corner, zeros beyond (the serving engine's padding contract).
        m_req / c_req: (B,) int32 true slice / column counts; rows need
          no bound (zero rows drop out of every contraction), columns
          mask the deterministic eigensolver init so the bucket-padded
          iterates stay bit-identical to the unpadded ones.

        Returns (d, lam, iters, valid) still at the padded size; the
        engine trims per request on the host.
        """
        from jax.sharding import NamedSharding

        _, m, r, _ = slices.shape
        m_pad, r_pad = self.pad_amounts(m, r)
        if (m_pad, r_pad) != (m, r):
            slices = jnp.pad(slices, ((0, 0), (0, m_pad - m),
                                      (0, r_pad - r), (0, 0)))
        valid = jnp.arange(m_pad)[None, :] < m_req[:, None]
        slices = jax.lax.with_sharding_constraint(
            slices, NamedSharding(self.mesh, self.batched_block_spec))
        d, lam, iters = self.build_batched_mode_fn()(slices, valid, c_req)
        return d, lam, iters, valid

    def finalize_mode_batched(self, d, lam, iters, valid) -> ModeResult:
        """Per-request replicated extraction (vmapped over the request
        dim) + the per-request sweep report: iters arrives (B,
        slice_shards) and reduces over devices only — NOT over requests,
        so ModeResult.power_iters_run keeps each request's own realized
        sweep count.  Results stay bucket-padded; the engine trims."""
        mask, n_it = jax.vmap(
            lambda dd, vv: extract_cluster(dd, self.cfg.epsilon, vv,
                                           self.cfg.max_extraction_iters)
        )(d, valid)
        return ModeResult(mask=mask, d=d, lambdas=lam, n_iters=n_it,
                          power_iters_run=jnp.max(iters, axis=-1))

    # ---- chunk-resumable entry points (DESIGN.md §7.7) ----------------
    #
    # The continuous serving engine persists one SolveState per mode per
    # slot table on device between dispatches.  Global layout (B = slot
    # count, m' = padded slice dim, S = slice_shards):
    #
    #   v (B, m', c)  lam/resid (B, m')  iters/done (B, S)
    #
    # iters/done are per-request verdicts, identical across the S shard
    # columns (the gate pmax-reduces over the slice axes); carrying them
    # at (B, S) through sharded specs keeps the whole carry pytree
    # uniform — every leaf enters and leaves shard_map varying over the
    # slice axes only, replicated over "inner".

    @property
    def batched_carry_specs(self) -> "SolveState":
        """SolveState-of-PartitionSpecs for the persistent per-mode carry."""
        from .power_iter import SolveState

        vs = self.batched_vector_spec
        return SolveState(v=P(None, _spec_entry(self.slice_axes), None),
                          lam=vs, resid=vs, iters=vs, done=vs)

    def init_mode_carry(self, B: int, m_pad: int, c: int, c_req, done,
                        warm_v=None, use_warm=None, resume_lam=None,
                        resume_resid=None, resume_iters=None,
                        resume_done=None, use_resume=None):
        """Fresh global carry for one mode of a B-slot table.

        c_req: (B,) per-request column bounds masking the deterministic
        eigensolver init (the serving bucket-padding contract); done:
        (B,) bool — True seeds the slot inert (its iterate never
        advances), the state of a slot that has no live request yet.
        Plain jnp, runs inside the refill executable (outside shard_map:
        the init is replicated by construction).

        warm_v/use_warm (both traced, DESIGN.md §7.10): the warm-start
        admission path.  Slot b starts from the cached iterates
        `warm_v[b]` (a (B, m_pad, c) staging array the engine fills from
        the result cache's tier-2 near-hit) where `use_warm[b]`, else
        from the deterministic init — so near-duplicate requests resume
        a nearly-converged solve and the adaptive gate fires within a
        chunk or two.  Because both ride the SAME refill executable as
        cold admissions (cold dispatches pass zeros + all-False), warm
        starts add zero recompiles.

        resume_* / use_resume (all traced, DESIGN.md §7.12): the
        preempt-to-host re-admission path.  Where `use_resume[b]`, slot
        b restores its FULL exported SolveState row — `warm_v[b]` taken
        verbatim (no re-normalization: the exported iterate must come
        back bit-identical, unlike a donor warm start), λ/residual from
        `resume_lam`/`resume_resid` ((B, m_pad) staging), and the
        per-request sweep counter and verdict from `resume_iters`/
        `resume_done` ((B,) per mode) — so a preempted slot continues
        exactly where its last chunk left it, and the realized
        `power_iters_run` at eviction equals the uninterrupted run's.
        use_warm and use_resume are mutually exclusive per slot (engine
        contract).  All three admission flavors share the ONE lowered
        refill signature; cold dispatches pass device-resident zeros.
        """
        from .power_iter import SolveState, _init_vectors, merge_warm_start

        S = self.slice_shards
        v = _init_vectors((B, m_pad), c, jnp.float32,
                          c_valid=jnp.asarray(c_req)[:, None])
        if warm_v is not None:
            v = merge_warm_start(v, warm_v, use_warm)
        lam = jnp.zeros((B, m_pad), jnp.float32)
        resid = jnp.zeros((B, m_pad), jnp.float32)
        iters = jnp.zeros((B, S), jnp.int32)
        done_eff = jnp.asarray(done)
        if use_resume is not None:
            ur = jnp.asarray(use_resume)
            v = jnp.where(ur[:, None, None],
                          jnp.asarray(warm_v, jnp.float32), v)
            lam = jnp.where(ur[:, None], jnp.asarray(resume_lam), lam)
            resid = jnp.where(ur[:, None], jnp.asarray(resume_resid),
                              resid)
            iters = jnp.where(
                ur[:, None],
                jnp.broadcast_to(
                    jnp.asarray(resume_iters, jnp.int32)[:, None], (B, S)),
                iters)
            done_eff = jnp.where(ur, jnp.asarray(resume_done), done_eff)
        return SolveState(
            v=v, lam=lam, resid=resid, iters=iters,
            done=jnp.broadcast_to(done_eff[:, None], (B, S)))

    def export_carry(self, carry, m: int):
        """Canonical mesh-independent host form of one mode's persistent
        carry (checkpointing, DESIGN.md §7.8): fully-addressable numpy
        arrays with the slice dim trimmed to the true bucket size m and
        the per-request verdict columns collapsed to one value.

        Trimming is lossless: slice rows beyond m are zero padding whose
        v/λ/resid stay exactly zero through every chunk (zero slices
        give zero residual and never gate), so `import_carry` re-pads
        with zeros for ANY target mesh without perturbing live slots.
        iters/done ride at (B, S) with identical values in all S shard
        columns (the gate pmax-reduces over the slice axes); column 0 is
        the canonical copy."""
        import numpy as np

        g = lambda x: np.asarray(jax.device_get(x))  # noqa: E731
        from .power_iter import SolveState

        return SolveState(v=g(carry.v)[:, :m], lam=g(carry.lam)[:, :m],
                          resid=g(carry.resid)[:, :m],
                          iters=g(carry.iters)[:, 0],
                          done=g(carry.done)[:, 0])

    def import_carry(self, host, m_pad: int):
        """Device-resident carry for THIS schedule's mesh from a
        canonical host export: re-pad the slice dim to this mesh's
        padded size, re-broadcast the per-request verdicts to this
        mesh's shard count, and device_put under `batched_carry_specs`
        — the reshard-on-restore step that makes a solve checkpointed
        on one `msc_mesh_shape` factorization resumable on another."""
        import numpy as np
        from jax.sharding import NamedSharding

        from .power_iter import SolveState

        B, m = host.lam.shape
        S = self.slice_shards

        def padm(a):
            if m_pad == m:
                return a
            out = np.zeros((B, m_pad) + a.shape[2:], a.dtype)
            out[:, :m] = a
            return out

        specs = self.batched_carry_specs
        sh = lambda s: NamedSharding(self.mesh, s)  # noqa: E731
        bcast = lambda a: np.ascontiguousarray(  # noqa: E731
            np.broadcast_to(np.asarray(a)[:, None], (B, S)))
        return SolveState(
            v=jax.device_put(padm(host.v), sh(specs.v)),
            lam=jax.device_put(padm(host.lam), sh(specs.lam)),
            resid=jax.device_put(padm(host.resid), sh(specs.resid)),
            iters=jax.device_put(bcast(host.iters), sh(specs.iters)),
            done=jax.device_put(bcast(host.done), sh(specs.done)))

    def chunk_local(self, block, carry, steps: int = 1):
        """Per-device chunk-step body for one mode: `steps` gate chunks
        over the local carry view — the resumable analogue of
        `mode_local`'s eigensolve.

        Every slot advances `steps × power_check_every` sweeps; a
        finished slot's state passes through frozen (`step_chunk`'s
        per-request masking), which is what lets the similarity tail be
        deferred to eviction time (`finalize_local`): the iterate a
        finished slot is finalized from is bit-identical no matter how
        many further chunks its slot table ran.  Padding slices are
        all-zero, so no validity mask is needed here — they contribute
        zero residual and never hold the gate open.
        """
        from .power_iter import SolveState, build_chunk_fn, step_chunk

        cfg = self.cfg
        st = SolveState(carry.v, carry.lam, carry.resid,
                        carry.iters[..., 0], carry.done[..., 0])
        chunk_fn, k = build_chunk_fn(block, cfg, inner_axis=self.inner_axis)

        def one(_, s):
            return step_chunk(chunk_fn, s, k=k, n_iters=cfg.power_iters,
                              tol=cfg.power_tol, axis_name=self.slice_axis)

        st = jax.lax.fori_loop(0, steps, one, st) if steps > 1 \
            else one(0, st)
        return SolveState(st.v, st.lam, st.resid,
                          st.iters[..., None], st.done[..., None])

    def finalize_local(self, block, valid_local, v):
        """Per-device similarity tail from a carry's (frozen) iterates:
        final fp32 Rayleigh quotient, λ-max normalization, epilogue —
        the same `_similarity_tail` the one-shot paths use.  The
        continuous engine runs this inside the refill executable
        (finalize-on-evict), NOT per chunk: at paper scale the epilogue
        is link-bound, so recomputing it every gate chunk would hand
        back much of the occupancy win (see
        roofline.continuous_serving_model)."""
        from .power_iter import rayleigh_fp32

        lam = rayleigh_fp32(block, v, self.inner_axis)
        return self._similarity_tail(lam, v, valid_local)

    @staticmethod
    def repack_local(perm, take_new, block, carry, new_block, new_carry):
        """Per-device slot-table compaction/refill for one mode:
        block'[s] = new_block[s] if take_new[s] else block[perm[s]], and
        likewise for every carry leaf — an arbitrary slot permutation
        (the scheduler's compaction policy) fused with refill selection.
        The slot dim is replicated in every spec, so the gather is
        device-local: repacking never moves tensor bytes over links.

        Each output slot is selected on its own and the slots stacked:
        XLA then writes every row straight into the output, where a
        whole-table `old[perm]` gather materializes a copy of the table
        first (at a 400³ bucket with 4 slots, 7.3 GB of temporaries on
        a TPU v5e, more than the table itself)."""
        def sel(old, new):
            return jnp.stack([
                jnp.where(take_new[s], new[s],
                          jax.lax.dynamic_index_in_dim(old, perm[s],
                                                       keepdims=False))
                for s in range(old.shape[0])])

        return sel(block, new_block), jax.tree.map(sel, carry, new_carry)

    def build_batched_chunk_fn(self, steps: int = 1):
        """shard_map'd single-mode chunk step (stage-level tests; the
        engine fuses all three modes into one region — MSCChunkPlan)."""
        specs = self.batched_carry_specs
        return jax.shard_map(
            partial(self.chunk_local, steps=steps), mesh=self.mesh,
            in_specs=(self.batched_block_spec, specs), out_specs=specs,
        )

    def build_batched_finalize_fn(self):
        """shard_map'd single-mode finalize (stage-level tests)."""
        return jax.shard_map(
            self.finalize_local, mesh=self.mesh,
            in_specs=(self.batched_block_spec, self.batched_vector_spec,
                      self.batched_carry_specs.v),
            out_specs=(self.batched_vector_spec, self.batched_vector_spec),
        )


def build_mode_runner(sched: ModeSchedule, c_valid: Optional[int] = None):
    """jitted (padded slices (m', r', c), valid (m',)) → (d, λ, iters):
    one mode's eigensolve + epilogue stage in isolation, with the inputs
    explicitly *committed* to the schedule's shardings.

    Unlike the full pipelines — whose tensor argument GSPMD may leave
    replicated when padding/transposes sit between it and the shard_map
    — the compiled module here receives the block already distributed,
    exactly as it would arrive at production scale (where the whole
    point of the inner axis is that no device can hold full slices).
    benchmarks/inner_shard.py compiles this to measure the per-device
    eigensolve working set; tests use it for stage-level parity.
    """
    from jax.sharding import NamedSharding

    in_sh = (NamedSharding(sched.mesh, sched.block_spec),
             NamedSharding(sched.mesh, sched.vector_spec))
    fn = sched.build_mode_fn(c_valid=c_valid)
    return jax.jit(lambda block, valid: fn(block, valid),
                   in_shardings=in_sh)


def build_epilogue_rowsum(mesh: Mesh, cfg: MSCConfig,
                          axis_name: Optional[AxisName] = None):
    """jitted V (m, c) → d (m,): the similarity epilogue in isolation.

    Compiles just the MPI_Allgatherv-analogue epilogue selected by
    cfg.epilogue over a row-sharded V (padding rows to even shards, like
    the full schedules).  benchmarks/ring_epilogue.py compiles this to
    measure allgather-vs-ring collective traffic without the surrounding
    eigensolve HLO; tests use it for epilogue-only parity.
    """
    axes = norm_axes(axis_name) if axis_name is not None \
        else tuple(mesh.axis_names)
    shards = math.prod(mesh.shape[a] for a in axes)
    in_spec = P(_spec_entry(axes))
    local = jax.shard_map(
        partial(epilogue_rowsum, cfg=cfg, axis_name=axis_arg(axes),
                shards=shards),
        mesh=mesh, in_specs=(in_spec,), out_specs=in_spec,
    )

    @jax.jit
    def run(v_rows: jax.Array) -> jax.Array:
        m, _ = v_rows.shape
        m_pad = pad_to(m, shards)
        if m_pad != m:
            v_rows = jnp.pad(v_rows, ((0, m_pad - m), (0, 0)))
        return local(v_rows)[:m]

    return run
