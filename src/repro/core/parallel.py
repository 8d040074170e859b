"""Parallel MSC via shard_map (paper Alg. 2, adapted to SPMD/TPU).

All schedules are thin *layout declarations* over the shared
`core/schedule.py:ModeSchedule` substrate, which owns the padding and
validity masks, the PartitionSpecs, the per-device Alg. 2 body
(eigensolve → λ pmax → normalize → similarity epilogue), the lockstep
convergence gating, and the epilogue dispatch.  What remains here is
only what genuinely differs per schedule: which mesh axes play which
role, and how the tensor moves between the three mode layouts.

* **flat** (beyond-paper): the three modes are processed one after
  another, each using *all* slice-axis devices.  Per mode this gives 3×
  the parallelism of the paper's grouped layout and holds one layout of
  the tensor at a time.  Because all three modes live in one jit, XLA's
  scheduler is free to interleave mode-2's eigensolves with mode-1's
  collectives — recovering the paper's cross-mode overlap without
  dedicating processes to it.

* **grouped** (paper-faithful): mesh axes ("mode"=3, "slice"=p/3), the
  MPI 3-group layout of Fig. 3.  The stacked unfoldings are sharded
  (mode, slice) so each group holds its own unfolding, distributed along
  its slicing axis; collectives run over the "slice" axis only — the
  exact analogue of the paper's group communicators.  Cube tensors only
  (the MPI version has the same restriction in its balanced setting).

* **2-D ("slice", "inner") meshes** (DESIGN.md §7.5): every schedule
  additionally accepts an "inner" mesh axis that shards the
  *within-slice* row dim r, dropping per-device tensor memory to
  O(m·r·c/(p·q)) so a single slice can exceed one device's HBM.  The
  eigensolve contractions psum over "inner"; the λ reduction, gate, and
  epilogue stay on the slice axes (see core/schedule.py).

Collective mapping (paper → here):
  MPI_Allgatherv(M)      → epilogue="allgather": lax.all_gather(V_local,
                           slice_axis, tiled), or
                           epilogue="ring": p-1 lax.ppermute steps
                           streaming (m/p)×c chunks of V around the
                           slice axis while each device accumulates
                           d += Σ|V_l · chunkᵀ| against the chunk it
                           holds (DESIGN.md §7.4) — same link bytes,
                           O(m·c/p) peak buffer instead of O(m·c), and
                           the chunk matmul overlaps the next transfer.
  MPI_Allreduce(λ, MAX)  → lax.pmax(λ_local_max, slice_axis)
  MPI_Gatherv(d → root)  → d returned sharded; the (tiny) extraction runs
                           replicated under jit instead of on one root —
                           removes the root bottleneck and the final
                           Gatherv(J) entirely.
  (new, no MPI analogue) → lax.psum(partial Tᵀ(T v), "inner") — the
                           distributed eigensolve contraction.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_msc_mesh  # noqa: F401  (public re-export)

from .msc import MODE_PERMS, mode_slices
from .schedule import (EPILOGUES, ModeSchedule, axis_arg,  # noqa: F401
                       build_epilogue_rowsum, epilogue_rowsum, norm_axes,
                       pad_to)
from .types import MSCConfig, MSCResult


RELAYOUTS = ("gspmd", "collective", "collective_stream")


def _single_axis(ax):
    """The one axis name of `ax`, or None when it spans several axes
    (the stream relayout's ppermute needs a single named ring)."""
    if isinstance(ax, str):
        return ax
    if isinstance(ax, (tuple, list)) and len(ax) == 1:
        return ax[0]
    return None


def _stream_all_to_all(x, axis_name, split_axis: int, concat_axis: int,
                       shards: int):
    """Ring-streamed tiled all_to_all (DESIGN.md §7.11): p−1
    lax.ppermute chunk steps, bit-identical to
    `lax.all_to_all(..., tiled=True)` over the same axis.

    A blocking all_to_all is one collective: downstream compute waits
    for the whole payload.  Decomposed into per-peer ppermutes — step k
    moves my split-part (i+k) mod p to device (i+k) mod p, each chunk
    L/p of the local bytes — the chunks are independent collectives the
    scheduler can interleave with unrelated compute, exactly the PR 2
    ring-epilogue pattern: the previous mode's eigensolve sweeps hide
    the next mode's relayout (`roofline.relayout_model`).  Pure data
    movement (dynamic_slice in, dynamic_update_slice out, no
    arithmetic), so results are bit-identical to the blocking a2a.
    """
    p = shards
    part = x.shape[split_axis] // p
    csize = x.shape[concat_axis]
    idx = jax.lax.axis_index(axis_name)

    def take(j):
        start = [0] * x.ndim
        start[split_axis] = j * part
        sizes = list(x.shape)
        sizes[split_axis] = part
        return jax.lax.dynamic_slice(x, start, sizes)

    out_shape = list(x.shape)
    out_shape[split_axis] = part
    out_shape[concat_axis] = csize * p

    def place(out, chunk, j):
        start = [0] * len(out_shape)
        start[concat_axis] = j * csize
        return jax.lax.dynamic_update_slice(out, chunk, start)

    # my own part needs no transfer; peers arrive one ppermute each
    out = place(jnp.zeros(out_shape, x.dtype), take(idx), idx)
    for k in range(1, p):
        perm = [(s, (s + k) % p) for s in range(p)]
        chunk = jax.lax.ppermute(take((idx + k) % p), axis_name, perm)
        out = place(out, chunk, (idx - k) % p)
    return out


def _a2a(x, ax, split_axis: int, concat_axis: int, shards: int,
         stream: bool):
    """One inter-mode relayout collective: blocking tiled all_to_all, or
    the ring-streamed decomposition when `stream` (single-name axes of
    ≥ 2 shards only — composed axes and p=1 keep the blocking form,
    which is what the roofline chooser assumes too)."""
    name = _single_axis(ax)
    if stream and name is not None and shards > 1:
        return _stream_all_to_all(x, name, split_axis, concat_axis, shards)
    return jax.lax.all_to_all(x, ax, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def _resolve_auto(mesh: Mesh, cfg: MSCConfig, shape, relayout: str,
                  axis_name, inner_axis, B: int = 1):
    """Resolve relayout="auto" / cfg.epilogue="auto" for one tensor
    shape from the roofline models (DESIGN.md §7.11) — flags become
    overrides simply by not saying "auto"."""
    from repro.roofline import choose_epilogue, choose_relayout

    sched = _flat_schedule(mesh, cfg, axis_name, inner_axis)
    p, q = sched.slice_shards, sched.inner_shards
    if relayout == "auto":
        relayout = choose_relayout(shape, p, q, B=B,
                                   sweeps=max(cfg.power_check_every, 1))
    if cfg.epilogue == "auto":
        m1, m2, m3 = shape
        # mode 1 dominates the epilogue bytes on cubes; all modes share
        # one policy (the schedules take a single cfg.epilogue)
        cfg = cfg.with_(epilogue=choose_epilogue(m1, m3, p))
    return cfg, relayout


def _flat_schedule(mesh: Mesh, cfg: MSCConfig, axis_name,
                   inner_axis) -> ModeSchedule:
    """Resolve the flat schedule's axis roles.

    axis_name=None derives the roles from the mesh via the MSC logical
    axes (sharding/specs.py): "inner" shards rows when present, every
    other axis composes the slice axis — so 1-D and production
    (data, model) meshes behave exactly as before 2-D sharding.
    """
    if axis_name is not None:
        slice_axes, inner_axes = norm_axes(axis_name), norm_axes(inner_axis)
    else:
        from repro.sharding.specs import msc_axes

        slice_axes, inner_axes = msc_axes(
            mesh, inner_axis=inner_axis if inner_axis is not None else "inner")
    return ModeSchedule(mesh, cfg, slice_axes, inner_axes)


def build_msc_parallel_flat(
    mesh: Mesh,
    cfg: MSCConfig,
    axis_name=None,
    relayout: str = "gspmd",
    inner_axis: Optional[str] = None,
):
    """jitted tensor → MSCResult, flat schedule (all devices per mode).

    relayout: how the tensor moves between the three mode layouts.
      "gspmd"      — global transpose outside shard_map; the SPMD
                     partitioner picks the collectives.  Measured on
                     m=1000/256 devices: ~6-8 GiB/device of involuntary
                     full-rematerialization fusions (§Perf msc it 2).
      "collective" — explicit `lax.all_to_all`s inside shard_map (the
                     SPMD analogue of the paper's per-group
                     redistribution, Fig. 3): exactly
                     tensor_bytes/device of link traffic, no
                     materialized intermediates.  On 2-D meshes one
                     extra all_to_all over "inner" first frees the
                     row-sharded dim (see _build_flat_collective).
      "collective_stream" — the collective schedule with each
                     all_to_all decomposed into p−1 ppermute chunk
                     steps (`_stream_all_to_all`): bit-identical
                     relayout, but the chunks interleave with the
                     previous mode's eigensolve sweeps (DESIGN.md
                     §7.11) instead of blocking on one collective.
      "auto"       — pick per tensor shape from
                     `roofline.choose_relayout`; cfg.epilogue="auto"
                     resolves alongside via `choose_epilogue` (works
                     with any relayout setting).
    """
    if relayout == "auto" or cfg.epilogue == "auto":
        built = {}

        def run_auto(tensor: jax.Array) -> MSCResult:
            key = tuple(tensor.shape)
            if key not in built:
                rcfg, rlay = _resolve_auto(mesh, cfg, key, relayout,
                                           axis_name, inner_axis)
                built[key] = build_msc_parallel_flat(
                    mesh, rcfg, axis_name, rlay, inner_axis)
            return built[key](tensor)

        return run_auto
    sched = _flat_schedule(mesh, cfg, axis_name, inner_axis)
    if relayout in ("collective", "collective_stream"):
        return _build_flat_collective(sched,
                                      stream=relayout == "collective_stream")
    if relayout != "gspmd":
        raise ValueError(f"unknown relayout {relayout!r}; "
                         f"expected one of {RELAYOUTS + ('auto',)}")

    @jax.jit
    def run(tensor: jax.Array) -> MSCResult:
        modes = []
        for j in range(3):
            d, lam, iters, valid, m = sched.run_mode(mode_slices(tensor, j))
            modes.append(sched.finalize_mode(d, lam, iters, valid, m))
        return MSCResult(modes=tuple(modes))

    return run


def _build_flat_collective(sched: ModeSchedule, stream: bool = False):
    """Flat schedule with explicit all_to_all relayout (§Perf msc it 2).

    The tensor is distributed once — mode-1 slices over the slice axes,
    mode-1 rows (= m2) over the inner axes — and each dim is padded up
    front to the multiple its all_to_all splits demand (m1: p·q, m2:
    lcm(p, q), m3: p) so each relayout is a clean tiled all_to_all.
    Zero row-padding drops out of every covariance (TᵀT
    sums over rows); zero *column*-padding is neutralized by masking
    the eigensolver's start vector to the true column count (`c_valid`,
    bit-identical iterates — see core/power_iter._init_vectors), so the
    per-mode valid masks still only gate the slice index.

    Relayout on a (p, q) mesh (q=1 degenerates to the 1-D paths):
      step A (shared):  all_to_all over "inner" (split m1, concat m2)
                        frees the row-sharded dim: every device now
                        holds full m2/m3 ranges of a (m1/(p·q))-row
                        block — m1 re-shards jointly over both axes,
                        which is harmless because m1 is a pure
                        contraction dim for modes 2 and 3.
      mode 2:           all_to_all over slice (split m2, concat m1).
      mode 3:           all_to_all over slice (split m3, concat m1).
    Each a2a moves exactly tensor_bytes/device of link traffic.
    """
    mesh, cfg = sched.mesh, sched.cfg
    slice_ax, inner_ax = sched.slice_axis, sched.inner_axis
    p, q = sched.slice_shards, sched.inner_shards
    # per-dim pad multiples: m1 is split by p then re-split by q (step
    # A); m2 is inner-sharded (q) and later slice-split (p); m3 is only
    # ever slice-split — keeping each minimal avoids inflating the c
    # width (m3 is the column dim of modes 1/2, m2 of mode 3)
    m1_mult = p * q
    m2_mult = p * q // math.gcd(p, q)
    m3_mult = p
    in_spec = sched.vector_spec

    def whole(t_block, valid0, valid1, valid2, *, c_valids):
        # t_block: (m1P/p, m2P/q, m3P) — my block of the mode-1 layout.
        outs = [sched.mode_local(t_block, valid0, c_valid=c_valids[0])]

        blk = t_block
        if sched.inner_axes:  # step A: free the inner-sharded dim
            blk = _a2a(blk, inner_ax, 0, 1, q, stream)
        # mode 2: m2 takes the slice axes; (m1P/(pq), m2P, m3P) →
        # (m1P/q, m2P/p, m3P) → slice-major (m2P/p, m1P/q, m3P)
        b2 = _a2a(blk, slice_ax, 1, 0, p, stream)
        outs.append(sched.mode_local(jnp.transpose(b2, (1, 0, 2)), valid1,
                                     c_valid=c_valids[1]))
        # mode 3: m3 takes the slice axes → slice-major (m3P/p, m1P/q, m2P)
        b3 = _a2a(blk, slice_ax, 2, 0, p, stream)
        outs.append(sched.mode_local(jnp.transpose(b3, (2, 0, 1)), valid2,
                                     c_valid=c_valids[2]))
        return tuple(outs)

    @jax.jit
    def run(tensor: jax.Array) -> MSCResult:
        m1, m2, m3 = tensor.shape
        m1p, m2p, m3p = (pad_to(m, mult) for m, mult in
                         ((m1, m1_mult), (m2, m2_mult), (m3, m3_mult)))
        t = jnp.pad(tensor, ((0, m1p - m1), (0, m2p - m2), (0, m3p - m3)))
        # pin the padded tensor's layout to (slice, inner) sharding so the
        # initial redistribution is one well-defined reshard instead of
        # GSPMD's replicate-then-slice fallback (§Perf msc it 2b)
        t = jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh, sched.block_spec))
        local = jax.shard_map(
            # c of modes 1/2 is m3, of mode 3 is m2 (static per shape)
            lambda *a: whole(*a, c_valids=(m3, m3, m2)),
            mesh=mesh,
            in_specs=(sched.block_spec, in_spec, in_spec, in_spec),
            out_specs=tuple((in_spec, in_spec, in_spec) for _ in range(3)),
        )
        valids = tuple(jnp.arange(mp) < m
                       for mp, m in ((m1p, m1), (m2p, m2), (m3p, m3)))
        results = local(t, *valids)
        modes = []
        for (d, lam, iters), valid, m in zip(results, valids, (m1, m2, m3)):
            modes.append(sched.finalize_mode(d, lam, iters, valid, m))
        return MSCResult(modes=tuple(modes))

    return run


def build_msc_parallel_grouped(
    mesh: Mesh,
    cfg: MSCConfig,
    mode_axis: str = "mode",
    slice_axis: str = "slice",
    inner_axis: Optional[str] = None,
):
    """jitted tensor → MSCResult, paper-faithful 3-group schedule.

    Requires mesh.shape[mode_axis] == 3 and a cube tensor.  The stacked
    unfoldings (3, m, r, c) are sharded (mode, slice[, inner]): each
    group of p/3 devices holds exactly its own unfolding,
    block-distributed along the slicing axis (and, on 3-D meshes, its
    rows along the inner axis) — the data layout of paper Fig. 3.
    """
    if mesh.shape[mode_axis] != 3:
        raise ValueError(
            f"grouped schedule needs {mode_axis}=3, got mesh {mesh.shape}")
    if inner_axis is None and "inner" in mesh.shape:
        inner_axis = "inner"
    sched = ModeSchedule(mesh, cfg, slice_axes=(slice_axis,),
                         inner_axes=norm_axes(inner_axis),
                         group_axes=(mode_axis,))

    def local_fn(stack_block, valid_block):
        # stack_block: (1, b, r, c); collectives over slice/inner only →
        # group-local, the analogue of the MPI group communicator (the
        # ring epilogue circulates chunks within each mode group).
        d, lam, iters = sched.mode_local(stack_block[0], valid_block[0])
        return d[None], lam[None], iters[None]

    local = jax.shard_map(local_fn, mesh=mesh,
                      in_specs=(sched.stacked_block_spec,
                                sched.stacked_vector_spec),
                      out_specs=(sched.stacked_vector_spec,) * 3)

    @jax.jit
    def run(tensor: jax.Array) -> MSCResult:
        m1, m2, m3 = tensor.shape
        if not (m1 == m2 == m3):
            raise ValueError("grouped schedule requires a cube tensor")
        stack = jnp.stack([mode_slices(tensor, j) for j in range(3)])
        m = m1
        m_pad, r_pad = sched.pad_amounts(m, m)
        if (m_pad, r_pad) != (m, m):
            stack = jnp.pad(stack, ((0, 0), (0, m_pad - m),
                                    (0, r_pad - m), (0, 0)))
        # as in the flat paths: pin the stacked layout (§Perf msc it 2b)
        stack = jax.lax.with_sharding_constraint(
            stack, NamedSharding(mesh, sched.stacked_block_spec))
        valid = jnp.arange(m_pad) < m
        valid3 = jnp.broadcast_to(valid, (3, m_pad))
        d3, lam3, it3 = local(stack, valid3)
        modes = []
        for j in range(3):
            modes.append(sched.finalize_mode(d3[j], lam3[j], it3[j],
                                             valid, m))
        return MSCResult(modes=tuple(modes))

    return run


# column dim of modes 1/2 is m3, of mode 3 is m2 (see MODE_PERMS)
C_OF = (2, 2, 1)


def build_msc_batched(
    mesh: Mesh,
    cfg: MSCConfig,
    axis_name=None,
    inner_axis: Optional[str] = None,
    relayout: str = "gspmd",
):
    """jitted (tensors (B, M1, M2, M3), dims (B, 3)) → batched MSCResult.

    The request-batched flat schedule (DESIGN.md §7.6): B independent
    MSC decompositions — bucket-padded to one shape by the serving
    engine, true sizes in `dims` — run through ONE set of compiled
    shard_map bodies.  Per mode, the leading request dim rides
    replicated through ModeSchedule's batched specs, the eigensolver
    gates each request independently (per-request `power_iters_run`,
    batch-max lockstep exit), the epilogue collectives move one
    B-times-larger message over the same schedule, and extraction vmaps
    over requests.  Every field of the returned ModeResults carries a
    leading B dim at the bucket-padded size; callers slice
    `[i, :dims[i, j]]` per request (MSCServeEngine does this on host).

    Because `dims` is a traced argument, one executable serves *any*
    request sizes inside its bucket — the zero-retrace contract of the
    serving engine's executable cache.

    relayout: "gspmd" (per-mode global transpose, partitioner-chosen
    collectives), "collective" (explicit all_to_all relayout — the
    §Perf msc it 2 schedule with every split/concat axis shifted under
    the leading request dim, so batches move exactly
    B·tensor_bytes/device of link traffic with no materialized
    intermediates), "collective_stream" (the same schedule with each
    a2a ring-streamed as p−1 ppermute chunks, DESIGN.md §7.11), or
    "auto" (per-shape roofline choice — also resolves
    cfg.epilogue="auto").
    """
    if relayout == "auto" or cfg.epilogue == "auto":
        built = {}

        def run_auto(batch: jax.Array, dims: jax.Array) -> MSCResult:
            key = tuple(batch.shape)
            if key not in built:
                rcfg, rlay = _resolve_auto(mesh, cfg, key[1:], relayout,
                                           axis_name, inner_axis,
                                           B=key[0])
                built[key] = build_msc_batched(mesh, rcfg, axis_name,
                                               inner_axis, rlay)
            return built[key](batch, dims)

        return run_auto
    sched = _flat_schedule(mesh, cfg, axis_name, inner_axis)
    if relayout in ("collective", "collective_stream"):
        return _build_batched_collective(
            sched, stream=relayout == "collective_stream")
    if relayout != "gspmd":
        raise ValueError(f"unknown relayout {relayout!r}; "
                         f"expected one of {RELAYOUTS + ('auto',)}")

    @jax.jit
    def run(batch: jax.Array, dims: jax.Array) -> MSCResult:
        modes = []
        for j in range(3):
            perm = (0,) + tuple(a + 1 for a in MODE_PERMS[j])
            d, lam, iters, valid = sched.run_mode_batched(
                jnp.transpose(batch, perm), dims[:, j], dims[:, C_OF[j]])
            modes.append(sched.finalize_mode_batched(d, lam, iters, valid))
        return MSCResult(modes=tuple(modes))

    return run


def _build_batched_collective(sched: ModeSchedule, stream: bool = False):
    """Request-batched flat schedule with explicit all_to_all relayout.

    Identical collective schedule to `_build_flat_collective` — one
    shared inner-axis all_to_all frees the row-sharded dim, then one
    slice-axis all_to_all per remaining mode — with every split/concat
    axis shifted one right under the leading request dim (which is
    replicated in every spec, so the a2a messages are simply B times
    larger over the same links).  Per-request column bounds replace the
    static c_valids: `dims` is traced, so one executable serves any
    request sizes inside its bucket, exactly like the gspmd path.
    """
    mesh, cfg = sched.mesh, sched.cfg
    slice_ax, inner_ax = sched.slice_axis, sched.inner_axis
    p, q = sched.slice_shards, sched.inner_shards
    # per-dim pad multiples — same derivation as _build_flat_collective
    m1_mult = p * q
    m2_mult = p * q // math.gcd(p, q)
    m3_mult = p
    vspec = sched.batched_vector_spec

    def whole(t_block, valid0, valid1, valid2, c0, c1, c2):
        # t_block: (B, m1P/p, m2P/q, m3P) — my block of the mode-1 layout.
        outs = [sched.mode_local(t_block, valid0, c_valid=c0[:, None])]

        blk = t_block
        if sched.inner_axes:  # step A: free the inner-sharded dim
            blk = _a2a(blk, inner_ax, 1, 2, q, stream)
        # mode 2: m2 takes the slice axes; (B, m1P/(pq), m2P, m3P) →
        # (B, m1P/q, m2P/p, m3P) → slice-major (B, m2P/p, m1P/q, m3P)
        b2 = _a2a(blk, slice_ax, 2, 1, p, stream)
        outs.append(sched.mode_local(jnp.transpose(b2, (0, 2, 1, 3)),
                                     valid1, c_valid=c1[:, None]))
        # mode 3: m3 takes the slice axes → (B, m3P/p, m1P/q, m2P)
        b3 = _a2a(blk, slice_ax, 3, 1, p, stream)
        outs.append(sched.mode_local(jnp.transpose(b3, (0, 3, 1, 2)),
                                     valid2, c_valid=c2[:, None]))
        return tuple(outs)

    @jax.jit
    def run(batch: jax.Array, dims: jax.Array) -> MSCResult:
        _, m1, m2, m3 = batch.shape
        m1p, m2p, m3p = (pad_to(m, mult) for m, mult in
                         ((m1, m1_mult), (m2, m2_mult), (m3, m3_mult)))
        t = jnp.pad(batch, ((0, 0), (0, m1p - m1), (0, m2p - m2),
                            (0, m3p - m3)))
        t = jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh, sched.batched_block_spec))
        local = jax.shard_map(
            whole, mesh=mesh,
            in_specs=(sched.batched_block_spec, vspec, vspec, vspec,
                      P(None), P(None), P(None)),
            out_specs=tuple((vspec, vspec, vspec) for _ in range(3)),
        )
        valids = tuple(jnp.arange(mp)[None, :] < dims[:, j][:, None]
                       for j, mp in enumerate((m1p, m2p, m3p)))
        c_reqs = tuple(dims[:, C_OF[j]] for j in range(3))
        results = local(t, *valids, *c_reqs)
        modes = []
        for (d, lam, iters), valid in zip(results, valids):
            modes.append(sched.finalize_mode_batched(d, lam, iters, valid))
        return MSCResult(modes=tuple(modes))

    return run


class MSCChunkPlan:
    """Builders for the continuous engine's two per-bucket executables
    (DESIGN.md §7.7).

    The static batched pipeline (`build_msc_batched`) runs a request
    batch to completion inside one executable — its adaptive while_loop
    exits on the batch max, so one slow-converging request holds all B
    slots.  The chunk plan cuts that loop at the gate-chunk boundary
    and lifts it to the host:

      * `build_step()` — ONE gate chunk (`chunks_per_step ×
        power_check_every` sweeps) for all three modes of all B slots,
        over persistent device-resident state, returning the per-slot
        `finished` verdicts.  Modes advance *concurrently* (each chunk
        touches all three), so a slot is resident for max(mode sweeps),
        not the sum — and the chunk itself is pure eigensolve advance.
      * `build_refill()` — the evict/finalize/repack step between
        chunks: the similarity epilogue + extraction for every slot
        from the pre-repack (frozen) state — a finished slot's results,
        read by the engine at eviction — fused with an arbitrary slot
        permutation (the scheduler's compaction policy) and refill of
        freed slots from newly arrived requests.  Deferring the
        epilogue to eviction time keeps the per-chunk cost free of the
        link-bound |V Vᵀ| pass (frozen iterates make the deferred
        finalize bit-identical), while keeping the executable count per
        bucket at exactly two.

    State per mode: the padded slice-major block (read-only between
    refills) and a `SolveState` carry — see
    ModeSchedule.batched_carry_specs for the global layout.  Holding
    all three unfoldings triples resident tensor memory vs the static
    path's one-layout-at-a-time; that is the price of cross-mode
    concurrency (noted in DESIGN.md §7.7).

    Every computation is per-slot (the gate, λ-max, epilogue, and
    extraction all keep the leading request dim), which is what makes
    results invariant under slot placement, eviction order, and arrival
    interleaving — the correctness contract of
    tests/test_msc_continuous.py.
    """

    def __init__(self, mesh: Mesh, cfg: MSCConfig, axis_name=None,
                 inner_axis: Optional[str] = None,
                 chunks_per_step: int = 1,
                 replicate_outputs: bool = False):
        if not cfg.matrix_free:
            raise ValueError("the continuous engine requires "
                             "matrix_free=True (see power_iter."
                             "build_chunk_fn)")
        self.sched = _flat_schedule(mesh, cfg, axis_name, inner_axis)
        self.chunks_per_step = int(chunks_per_step)
        # multi-process meshes (launch/distributed.py): the engine reads
        # `finished` and the evicted slots' results on the host, which
        # np.asarray can only do on fully-addressable arrays — constrain
        # those outputs replicated so every process holds the whole
        # value (one extra all-gather of tiny per-slot vectors per
        # dispatch; single-process meshes skip it)
        self.replicate_outputs = bool(replicate_outputs)

    def _replicated(self, tree):
        if not self.replicate_outputs:
            return tree
        rep = NamedSharding(self.sched.mesh, P())
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, rep), tree)

    # ---- shapes / structs --------------------------------------------
    def mode_shapes(self, bucket, B: int):
        """Padded (B, m', r', c) block shape per mode."""
        shapes = []
        for j in range(3):
            m, r, c = (bucket[i] for i in MODE_PERMS[j])
            m_pad, r_pad = self.sched.pad_amounts(m, r)
            shapes.append((B, m_pad, r_pad, c))
        return tuple(shapes)

    def _block_sharding(self) -> NamedSharding:
        return NamedSharding(self.sched.mesh, self.sched.batched_block_spec)

    def _carry_shardings(self):
        from .power_iter import SolveState

        s = self.sched.batched_carry_specs
        mesh = self.sched.mesh
        return SolveState(*(NamedSharding(mesh, spec) for spec in
                            (s.v, s.lam, s.resid, s.iters, s.done)))

    def _carry_struct(self, B: int, m_pad: int, c: int):
        from .power_iter import SolveState

        S = self.sched.slice_shards
        sh = self._carry_shardings()
        sds = jax.ShapeDtypeStruct
        return SolveState(
            v=sds((B, m_pad, c), jnp.float32, sharding=sh.v),
            lam=sds((B, m_pad), jnp.float32, sharding=sh.lam),
            resid=sds((B, m_pad), jnp.float32, sharding=sh.resid),
            iters=sds((B, S), jnp.int32, sharding=sh.iters),
            done=sds((B, S), jnp.bool_, sharding=sh.done))

    def state_structs(self, bucket, B: int, dtype):
        """(blocks, carries) ShapeDtypeStructs with shardings — the AOT
        lowering signature of the persistent slot-table state."""
        bsh = self._block_sharding()
        blocks, carries = [], []
        for shape in self.mode_shapes(bucket, B):
            blocks.append(jax.ShapeDtypeStruct(shape, dtype, sharding=bsh))
            carries.append(self._carry_struct(B, shape[1], shape[3]))
        return tuple(blocks), tuple(carries)

    def stage_shape(self, bucket, B: int):
        """(B, M1, M2, M3) shape of the refill's staging: each admitted
        tensor once, zero-padded to the bucket, sharded like mode 0's
        block (`_block_sharding`; mode 0's permutation is the identity).
        The refill unfolds it into the three mode blocks on the device.
        Bucket dims are multiples of the shard counts (the engine's
        `_bucket_quantum`), so the unfoldings are exactly
        `mode_shapes`."""
        return (B,) + tuple(bucket)

    def zero_stage(self, bucket, B: int, dtype):
        """Device-resident all-zero staging, sharded like the refill
        executable expects — reused for eviction-only refills so they
        transfer no staging bytes host→device."""
        import numpy as np

        return jax.device_put(np.zeros(self.stage_shape(bucket, B), dtype),
                              self._block_sharding())

    def warm_shapes(self, bucket, B: int):
        """(B, m', c) warm-start staging shape per mode — one row of
        cached eigenvector iterates per slot, laid out exactly like the
        carry's `v` leaf (DESIGN.md §7.10)."""
        return tuple((B, m_pad, c)
                     for (B, m_pad, _, c) in self.mode_shapes(bucket, B))

    def zero_warm(self, bucket, B: int):
        """Device-resident all-zero warm-start staging (carry-v
        sharding) — passed on every refill with no warm admissions, so
        the cold path transfers no warm bytes host→device and the
        executable signature never changes (zero-recompile contract)."""
        import numpy as np

        vsh = self._carry_shardings().v
        return tuple(jax.device_put(np.zeros(sh, np.float32), vsh)
                     for sh in self.warm_shapes(bucket, B))

    def resume_shapes(self, bucket, B: int):
        """(B, m') λ/residual resume staging shape per mode — the
        preempt-to-host re-admission inputs (DESIGN.md §7.12), laid out
        exactly like the carry's lam/resid leaves.  The resumed
        iterate itself rides the warm_v staging (warm_shapes)."""
        return tuple((B, m_pad)
                     for (B, m_pad, _, _) in self.mode_shapes(bucket, B))

    def zero_resume(self, bucket, B: int):
        """Device-resident all-zero resume staging (carry lam/resid
        sharding) plus host-side zero (B, 3) iters/done selectors —
        passed on every refill with no resumed admissions, so the cold
        path transfers no resume bytes and the ONE lowered refill
        signature covers preempt/resume re-admissions too (the
        zero-recompile contract of DESIGN.md §7.12)."""
        import numpy as np

        lsh = self._carry_shardings().lam
        lam = tuple(jax.device_put(np.zeros(sh, np.float32), lsh)
                    for sh in self.resume_shapes(bucket, B))
        resid = tuple(jax.device_put(np.zeros(sh, np.float32), lsh)
                      for sh in self.resume_shapes(bucket, B))
        return (lam, resid, np.zeros((B, 3), np.int32),
                np.zeros((B, 3), np.bool_))

    def export_slot(self, bucket, carries, slot: int):
        """Canonical host form of ONE slot's three mode-carry rows — the
        preempt-to-host export (DESIGN.md §7.12).  Reuses the §7.8
        checkpoint trim (ModeSchedule.export_carry semantics): each
        mode's slice dim is cut to the true bucket size — lossless,
        because a preempted slot has run ≥ 1 chunk, after which its
        padding-slice iterates are exactly zero — and the per-request
        verdict columns collapse to the canonical copy.  Returns one
        host SolveState per mode with leaves v (m, c), lam (m,),
        resid (m,), iters (scalar), done (scalar)."""
        import numpy as np

        from .power_iter import SolveState

        out = []
        for j, carry in enumerate(carries):
            m = bucket[MODE_PERMS[j][0]]
            g = lambda x: np.asarray(jax.device_get(x))  # noqa: E731
            out.append(SolveState(
                v=g(carry.v)[slot, :m], lam=g(carry.lam)[slot, :m],
                resid=g(carry.resid)[slot, :m],
                iters=int(g(carry.iters)[slot, 0]),
                done=bool(g(carry.done)[slot, 0])))
        return out

    def init_state(self, bucket, B: int, dtype):
        """Fresh device-resident slot table: zero blocks, every slot
        inert (done=True ⇒ frozen until the first refill)."""
        import numpy as np

        blocks_s, carries_s = self.state_structs(bucket, B, dtype)
        blocks = tuple(jax.device_put(np.zeros(b.shape, b.dtype), b.sharding)
                       for b in blocks_s)
        carries = []
        for c in carries_s:
            leaves, treedef = jax.tree_util.tree_flatten(c)
            filled = [jax.device_put(
                np.ones(l.shape, bool) if l.dtype == jnp.bool_
                else np.zeros(l.shape, l.dtype), l.sharding)
                for l in leaves]
            carries.append(jax.tree_util.tree_unflatten(treedef, filled))
        return blocks, tuple(carries)

    # ---- checkpoint export / rebuild-from-carry (DESIGN.md §7.8) ------
    def export_carries(self, bucket, carries):
        """Canonical host form of a bucket's three mode carries — the
        mesh-independent payload the engine checkpoints.  Each mode
        trims its padded slice dim back to the true bucket size (see
        ModeSchedule.export_carry), so the export restores onto any
        `msc_mesh_shape` factorization."""
        out = []
        for j, carry in enumerate(carries):
            m = bucket[MODE_PERMS[j][0]]
            out.append(self.sched.export_carry(carry, m))
        return out

    def import_carries(self, bucket, host_carries):
        """Device-resident carries for the CURRENT mesh from a canonical
        host export (reshard-on-restore): re-pad each mode's slice dim
        to this mesh's padded size and device_put under this mesh's
        carry shardings."""
        out = []
        for j, host in enumerate(host_carries):
            m, r, _ = (bucket[i] for i in MODE_PERMS[j])
            m_pad, _ = self.sched.pad_amounts(m, r)
            out.append(self.sched.import_carry(host, m_pad))
        return tuple(out)

    def rebuild_blocks(self, bucket, B: int, dtype, arrs):
        """Device blocks for the current mesh from per-slot host tensors
        — the restore path's analogue of admission staging.  `arrs` is a
        length-B list, None for slots without a live request (their rows
        stay zero, exactly the state the running engine's scatter left
        them in).  The same three MODE_PERMS transposes of the same
        zero-padded tensors that the refill applies on the device to the
        staging (transposes are exact) make the rebuilt blocks
        byte-identical to the checkpointed engine's device state — the
        root of the bit-identical-resume contract."""
        import numpy as np

        bsh = self._block_sharding()
        blocks = []
        for j, shape in enumerate(self.mode_shapes(bucket, B)):
            host = np.zeros(shape, dtype)
            for s, arr in enumerate(arrs):
                if arr is None:
                    continue
                t = np.transpose(arr, MODE_PERMS[j])
                host[s, :t.shape[0], :t.shape[1], :t.shape[2]] = t
            blocks.append(jax.device_put(host, bsh))
        return tuple(blocks)

    # ---- the two executables -----------------------------------------
    def build_step(self):
        """(blocks, carries) → (carries', finished).

        One scheduler tick: every slot's three modes advance one gate
        chunk (finished modes pass through frozen).  `finished` (B,) is
        True once all three of a slot's modes are converged or capped —
        the engine evicts exactly these slots at the next refill.
        """
        sched = self.sched
        cap = sched.cfg.power_iters
        specs = sched.batched_carry_specs
        bspec = sched.batched_block_spec
        steps = self.chunks_per_step

        # all three modes advance inside ONE shard_map region: a chunk
        # step is many small collectives (per-chunk gate pmaxes), so
        # region entry/exit barriers would otherwise triple the fixed
        # per-dispatch cost that continuous batching pays per chunk
        def local(b0, c0, b1, c1, b2, c2):
            out = []
            for j, (b, c) in enumerate(((b0, c0), (b1, c1), (b2, c2))):
                # the scope names this mode's operations in a profile
                with jax.named_scope(f"mode{j}/matvecs"):
                    out.append(sched.chunk_local(b, c, steps=steps))
            return tuple(out)

        fused = jax.shard_map(
            local, mesh=sched.mesh,
            in_specs=(bspec, specs) * 3,
            out_specs=(specs,) * 3,
        )

        def step(blocks, carries):
            out_carries = fused(blocks[0], carries[0], blocks[1],
                                carries[1], blocks[2], carries[2])
            finished = None
            for carry in out_carries:
                fin_j = carry.done[:, 0] | (carry.iters[:, 0] >= cap)
                finished = fin_j if finished is None else finished & fin_j
            return tuple(out_carries), self._replicated(finished)

        return step

    def build_refill(self):
        """(blocks, carries, dims, staged, new_dims, take_new,
        new_done, perm, warm_v, use_warm, resume_lam, resume_resid,
        resume_iters, resume_done, use_resume) → (blocks', carries',
        results).

        The evict/finalize/repack step.  `results` is the bucket-padded
        batched MSCResult finalized from the PRE-repack state (`dims`
        holds the pre-repack per-slot true sizes): similarity epilogue +
        extraction from every slot's current — for finished slots,
        frozen — iterates.  The engine reads exactly the evicted slots'
        rows; freezing makes those rows independent of when the finalize
        runs.

        Then the repack: slot s takes a fresh request where take_new[s],
        else old slot perm[s]'s state verbatim.  new_done[s]=True seeds
        slot s inert (a freed slot with no arrival to admit).
        `staged` is the (B, M1, M2, M3) staging (`stage_shape`): each
        admitted tensor once, zero-padded to the bucket.  The executable
        unfolds it into the three mode-major blocks with the MODE_PERMS
        transposes `build_msc_batched` uses, so the host ships each
        tensor once and transposes nothing; on a multi-device mesh the
        unfoldings of modes 1 and 2 move the staged cubes by an
        all-to-all.  The gather/select runs under shard_map
        (device-local — repacking moves no link bytes), fused with the
        finalize in one region.

        `warm_v` (per-mode (B, m', c) staging, `warm_shapes`) and
        `use_warm` ((B,) bool) are the tier-2 warm-start inputs
        (DESIGN.md §7.10): slot s's fresh carry starts from the cached
        iterates warm_v[j][s] where use_warm[s], else the deterministic
        init.  Cold dispatches pass the device-resident `zero_warm`
        zeros + all-False, so ONE executable serves both paths — warm
        admissions recompile nothing.

        `resume_lam`/`resume_resid` (per-mode (B, m') staging,
        `resume_shapes`), `resume_iters`/`resume_done` ((B, 3) per-mode
        selectors), and `use_resume` ((B,) bool) are the preempt-to-host
        re-admission inputs (DESIGN.md §7.12): slot s restores its full
        exported SolveState — the iterate rides `warm_v` verbatim
        (init_mode_carry skips the warm re-normalization under
        use_resume, keeping the resumed iterate bit-identical) — so the
        solve continues exactly where the preempted chunk left it.
        Cold/warm dispatches pass `zero_resume` + all-False; the resume
        inputs are part of the ONE lowered signature from the start, so
        the preempt path reuses the existing repack executable with
        zero recompiles.
        """
        sched = self.sched
        specs = sched.batched_carry_specs
        bspec = sched.batched_block_spec
        vspec = sched.batched_vector_spec

        # finalize + repack for all three modes in ONE shard_map region
        # (same barrier-amortization argument as build_step)
        def local(perm, take_new, *groups):
            outs = []
            for j, (block, carry, valid, nblock, ncarry) in enumerate(
                    zip(*([iter(groups)] * 5))):
                with jax.named_scope(f"mode{j}/finalize"):
                    d, lam = sched.finalize_local(block, valid, carry.v)
                with jax.named_scope(f"mode{j}/repack"):
                    blk, car = sched.repack_local(perm, take_new, block,
                                                  carry, nblock, ncarry)
                outs.extend((d, lam, blk, car))
            return tuple(outs)

        fused = jax.shard_map(
            local, mesh=sched.mesh,
            in_specs=(P(None), P(None)) + (bspec, specs, vspec, bspec,
                                           specs) * 3,
            out_specs=(vspec, vspec, bspec, specs) * 3,
        )

        bsh = self._block_sharding()

        def refill(blocks, carries, dims, staged, new_dims, take_new,
                   new_done, perm, warm_v, use_warm, resume_lam,
                   resume_resid, resume_iters, resume_done, use_resume):
            args = []
            valids = []
            for j in range(3):
                with jax.named_scope(f"mode{j}/repack"):
                    nblock = jax.lax.with_sharding_constraint(
                        jnp.transpose(staged, (0,) + tuple(
                            a + 1 for a in MODE_PERMS[j])), bsh)
                    B, m_pad, _, c = nblock.shape
                    ncarry = sched.init_mode_carry(
                        B, m_pad, c, new_dims[:, C_OF[j]], new_done,
                        warm_v=warm_v[j], use_warm=use_warm,
                        resume_lam=resume_lam[j],
                        resume_resid=resume_resid[j],
                        resume_iters=resume_iters[:, j],
                        resume_done=resume_done[:, j], use_resume=use_resume)
                valid = jnp.arange(m_pad)[None, :] < dims[:, j][:, None]
                valids.append(valid)
                args.extend((blocks[j], carries[j], valid, nblock, ncarry))
            outs = fused(perm, take_new, *args)
            modes, out_blocks, out_carries = [], [], []
            for j in range(3):
                d, lam, blk, car = outs[4 * j:4 * j + 4]
                with jax.named_scope(f"mode{j}/finalize"):
                    modes.append(sched.finalize_mode_batched(
                        d, lam, carries[j].iters, valids[j]))
                out_blocks.append(blk)
                out_carries.append(car)
            return (tuple(out_blocks), tuple(out_carries),
                    self._replicated(MSCResult(modes=tuple(modes))))

        return refill


def build_msc_parallel(mesh: Mesh, cfg: MSCConfig, schedule: str = "flat",
                       **kw):
    if schedule == "flat":
        return build_msc_parallel_flat(mesh, cfg, **kw)
    if schedule == "grouped":
        return build_msc_parallel_grouped(mesh, cfg, **kw)
    raise ValueError(f"unknown schedule {schedule!r}")
