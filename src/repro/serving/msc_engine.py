"""Batched multi-tensor MSC serving (DESIGN.md §7.6).

The paper parallelizes ONE decomposition, but the workloads built on
MSC — DBSCAN-MSC hyperparameter sweeps, MCAM affinity construction —
issue many independent requests.  Dispatching them one jit-trace at a
time pays Python dispatch, collective rendezvous, and (on a cold shape)
trace + compile per request.  `MSCServeEngine` amortizes all of it:

  * **shape buckets** — request dims round up to `bucket_quantum`
    multiples, so a stream of nearby shapes shares a handful of padded
    shapes.  Padding rides ModeSchedule's existing validity-mask
    contract: per-request slice masks (`dims` is a *traced* argument of
    the batched executable) plus per-request column bounds masking the
    eigensolver init, so bucket-padded results stay bit-identical to
    unpadded ones.
  * **compiled-executable cache** — one AOT `.lower().compile()` per
    (bucket shape, microbatch size, dtype, mesh, cfg); a warm bucket
    performs ZERO retraces/recompiles by construction (the executable is
    invoked directly, never re-traced; tests/test_msc_serving.py pins
    this with jax.monitoring compile-event counters).
  * **microbatch assembly** — requests in a bucket are packed into
    fixed-size microbatches of `max_batch` (short batches filled with
    (1,1,1) zero requests, which converge at the first gate probe and
    never delay the batch-max lockstep exit), so the steady state is one
    dispatch per `max_batch` requests with no shape diversity at all.

Results come back as host-side (numpy) per-request MSCResults — trimmed
to true sizes, per-request `power_iters_run` intact — keeping the hot
path free of per-request jax dispatches (slicing device arrays would
re-trace tiny gather programs per shape).

`MSCContinuousEngine` (DESIGN.md §7.7) replaces the static microbatch
with a continuous-batching decode loop: per-bucket slot tables of
persistent device-resident eigensolver state advance in gate chunks,
converged requests are evicted (and finalized) mid-flight, and freed
slots refill from an admission queue — so a slow-converging request no
longer parks B-1 slots at the batch-max lockstep exit.

Fault tolerance (DESIGN.md §7.8): the continuous engine is crash-safe
and mesh-elastic.  Every `ckpt_every_chunks` gate chunks it snapshots
each bucket's slot table — the canonical (mesh-independent) host form
of the three `SolveState` carries, the slot→request map, admitted
tensors, the admission queue, and `ServeStats` — through
`checkpoint/store.py` (atomic tmp+replace writes, per-leaf SHA).
`MSCContinuousEngine.restore(directory)` rebuilds the engine on the
CURRENT mesh (possibly a different `msc_mesh_shape` factorization) and
resumes mid-solve; masks and realized sweep counts are bit-identical
to the uninterrupted run.  Dispatch failures at run time (a JAX runtime
error or a planted `InjectedFault`) retry with exponential backoff,
degrade to the sequential oracle after `max_retries`, and shed new
submissions (`LoadShedError`) while a bucket is recovering; any other
exception in a dispatch is a bug and propagates.
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
import warnings
from collections import defaultdict, deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from repro.checkpoint.store import (gc_checkpoints, load_leaves,
                                    restorable_steps, save_checkpoint)
from repro.core.parallel import MSCChunkPlan, build_msc_batched
from repro.core.power_iter import SolveState
from repro.core.schedule import pad_to
from repro.core.types import ModeResult, MSCConfig, MSCResult
from repro.serving.faults import InjectedFault, LoadShedError

# filler requests must have ≥1 valid slice/column per mode: an all-zero
# (1,1,1) request has zero residual (gate fires at the first probe) and
# a nonempty masked init (no 0/0), so it never delays the lockstep exit.
_FILLER_DIMS = (1, 1, 1)

# What the recovery boundary retries: failures of a dispatch at run time
# (a device or runtime error, or a planted fault).  Anything else — a
# TypeError, a ValueError, a lowering error — is a bug in the dispatch
# and propagates instead of being served by the oracle.
_DISPATCH_FAILURES = (InjectedFault, jax.errors.JaxRuntimeError)


@dataclasses.dataclass(frozen=True)
class ServeStats:
    """Counters for the serving hot path (cumulative per engine).

    The first five are shared by both engines; the rest are the
    continuous engine's decode-loop counters (all cumulative, so
    `delta` stays a plain field-wise subtraction):

      exec_cache_hits — compiled-EXECUTABLE cache hits (warm-bucket
        dispatches that skipped lower+compile).  Distinct from the
        result cache below.

      chunk_steps / refills — dispatches of the two per-bucket
        executables (`dispatches` counts both).
      evictions — slots freed by a finished request (== requests served
        through the continuous path).
      slot_chunks / busy_slot_chunks — slot·chunk capacity dispatched
        vs the share holding a live request; their ratio is the slot
        occupancy the continuous scheduler exists to maximize.
      queue_wait_chunks — total chunks requests spent queued before
        admission (divide by `requests` for the mean wait).
      staged_bytes — bytes of the host (numpy) arrays handed to refill
        dispatches, which copy them to the device before they run: the
        bucket-shaped staging when a refill admits, the warm and resume
        staging when a slot uses them, and the small per-slot arrays
        (divide by `refills` for the bytes per refill).

    Fault-tolerance counters (DESIGN.md §7.8):

      checkpoints_written / restores — engine-state snapshots taken and
        engines rebuilt from one.
      retries — dispatch retries scheduled after a failure (each comes
        with exponential backoff; `max_retries` of them in a row
        triggers the sequential-oracle fallback).
      shed_requests — submits rejected (LoadShedError) while a bucket
        was recovering.
      fallback_requests — requests served by the degrade-to-sequential
        oracle after retries were exhausted.

    Multi-host fault-tolerance counters (DESIGN.md §7.9, bumped by the
    launch/distributed.py control plane via `note_ft_event`):

      heartbeats_missed — control-channel ack waits that timed out or
        hit EOF (a SIGKILLed worker closes its socket instantly).
      host_losses — distinct worker-loss events the master detected.
      reinits — engines rebuilt on a reduced host set after a loss.
      shard_files_written — per-process checkpoint shard files written
        across all processes (the master sums worker acks).

    Result-cache counters (DESIGN.md §7.10, continuous engine with a
    `result_cache` attached):

      cache_hits / cache_misses — tier-1 exact hits served instantly
        from the content-addressed result cache vs requests that went
        to the device path.
      warm_starts — admissions whose eigensolver carry was seeded from
        a cached near-duplicate's iterates (tier 2).
      warm_sweeps_saved — Σ over warm-started requests of
        max(0, donor sweeps − realized sweeps), per mode: the power
        iteration the warm start skipped.

    Autotuner counters (DESIGN.md §7.11, continuous engine with
    autotuning enabled):

      autotune_searches — per-bucket block searches that actually
        measured candidates (autotune-cache misses).  A warm engine —
        or one that reloaded a persisted autotune cache — performs 0.
      autotune_cache_hits — bucket resolutions served from the
        autotune cache (in-memory or reloaded), compiling only the
        winner.

    SLO-scheduler counters (DESIGN.md §7.12):

      preemptions / resumes — slots swapped to host mid-solve to make
        room for a higher-priority waiter, and parked requests
        re-admitted through the refill executable's resume inputs.
      deadline_misses — requests that finalized after their
        `deadline_chunks` budget had elapsed.
      slo_sheds — submits rejected (LoadShedError) because the
        queue-wait model predicted the request would blow `slo_chunks`
        (shed BEFORE solving; a subset of `shed_requests`).
      idle_bucket_ticks — chunk dispatches of a bucket that left free
        slots idle while its own queue was non-empty (refill batching;
        0 by construction when refill_min_free == 1).
    """

    requests: int = 0
    dispatches: int = 0
    compiles: int = 0
    exec_cache_hits: int = 0
    filler_slots: int = 0
    chunk_steps: int = 0
    refills: int = 0
    evictions: int = 0
    slot_chunks: int = 0
    busy_slot_chunks: int = 0
    queue_wait_chunks: int = 0
    staged_bytes: int = 0
    checkpoints_written: int = 0
    restores: int = 0
    retries: int = 0
    shed_requests: int = 0
    fallback_requests: int = 0
    heartbeats_missed: int = 0
    host_losses: int = 0
    reinits: int = 0
    shard_files_written: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    warm_starts: int = 0
    warm_sweeps_saved: int = 0
    autotune_searches: int = 0
    autotune_cache_hits: int = 0
    preemptions: int = 0
    resumes: int = 0
    deadline_misses: int = 0
    slo_sheds: int = 0
    idle_bucket_ticks: int = 0

    @property
    def occupancy(self) -> float:
        """Live-slot share of dispatched slot·chunk capacity."""
        return (self.busy_slot_chunks / self.slot_chunks
                if self.slot_chunks else 0.0)

    def delta(self, other: "ServeStats") -> "ServeStats":
        return ServeStats(*(a - b for a, b in
                            zip(dataclasses.astuple(self),
                                dataclasses.astuple(other))))


def _bucket_quantum(mesh: Mesh, inner_axis: Optional[str],
                    bucket_quantum: int) -> int:
    """Dims round up to shard multiples too, so bucket padding and
    schedule padding coincide (no second pad inside the jit).  Each dim
    is a slice dim (multiple of p) in one mode and a row dim (multiple
    of q) in another, so lcm(p, q) suffices — NOT p·q."""
    q = mesh.shape.get(inner_axis or "inner", 1)
    p = int(np.prod([s for a, s in mesh.shape.items()
                     if a != (inner_axis or "inner")]))
    return pad_to(int(bucket_quantum), math.lcm(p, q))


def _bucket_of(shape: Sequence[int], quantum: int) -> Tuple[int, int, int]:
    if len(shape) != 3 or any(s < 1 for s in shape):
        raise ValueError(f"MSC serves third-order tensors, got {shape}")
    return tuple(pad_to(int(s), quantum) for s in shape)


class MSCServeEngine:
    """Batched MSC serving over one mesh + config.

    Parameters:
      mesh: the MSC device mesh (flat schedule; 1-D ("slice",) or 2-D
        ("slice", "inner") — see launch/mesh.py:make_msc_mesh).
      cfg: MSCConfig shared by every request (part of the cache key —
        run one engine per config).
      max_batch: microbatch size B; every dispatch carries exactly B
        request slots (filled with inert (1,1,1) requests when the
        stream leaves a remainder), so each bucket compiles exactly one
        executable.
      bucket_quantum: dims round up to multiples of this (and of the
        mesh shard counts, so in-bucket padding already satisfies the
        schedule's even-shard contract).
      dtype: request tensor dtype at the engine boundary (the precision
        *policy* stays cfg.precision).
      relayout: passed to build_msc_batched — "gspmd" (default),
        "collective" / "collective_stream" (explicit batched all_to_all
        relayout, blocking or ring-streamed), or "auto" (per-bucket
        pick from roofline.choose_relayout; cfg.epilogue="auto"
        resolves alongside — DESIGN.md §7.11).

    `run(tensors)` is the whole API: a list of third-order tensors in,
    a list of per-request MSCResults (host-side numpy, true sizes) out,
    in order.
    """

    def __init__(self, mesh: Mesh, cfg: MSCConfig, *, max_batch: int = 8,
                 bucket_quantum: int = 8, dtype=jnp.float32,
                 axis_name=None, inner_axis: Optional[str] = None,
                 relayout: str = "gspmd"):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.mesh = mesh
        self.cfg = cfg
        self.max_batch = int(max_batch)
        self.dtype = jnp.dtype(dtype)
        self._axis_name = axis_name
        self._inner_axis = inner_axis
        self._relayout = relayout
        # "auto" anywhere: defer building runners — each bucket gets a
        # concrete (relayout, epilogue) from the roofline choosers at
        # its first (and only) lower+compile in _executable
        self._auto = relayout == "auto" or cfg.epilogue == "auto"
        self._runner = None if self._auto else build_msc_batched(
            mesh, cfg, axis_name=axis_name, inner_axis=inner_axis,
            relayout=relayout)
        self._runners: Dict[Tuple[int, int, int], object] = {}
        self._quantum = _bucket_quantum(mesh, inner_axis, bucket_quantum)
        self._cache: Dict[Tuple, jax.stages.Compiled] = {}
        self._stats = ServeStats()

    # ---- bucketing ---------------------------------------------------
    def bucket_of(self, shape: Sequence[int]) -> Tuple[int, int, int]:
        """Bucket = each dim rounded up to the engine quantum."""
        return _bucket_of(shape, self._quantum)

    # ---- executable cache --------------------------------------------
    def _executable(self, bucket: Tuple[int, int, int]):
        """AOT-compiled batched pipeline for one bucket (cache hit on a
        warm bucket — no trace, no compile)."""
        key = (bucket, self.max_batch, str(self.dtype),
               tuple(self.mesh.shape.items()), self.cfg)
        compiled = self._cache.get(key)
        if compiled is None:
            runner = self._runner
            if self._auto:
                runner = self._runners.get(bucket)
                if runner is None:
                    from repro.core.parallel import _resolve_auto
                    rcfg, rlay = _resolve_auto(
                        self.mesh, self.cfg, bucket, self._relayout,
                        self._axis_name, self._inner_axis,
                        B=self.max_batch)
                    runner = build_msc_batched(
                        self.mesh, rcfg, axis_name=self._axis_name,
                        inner_axis=self._inner_axis, relayout=rlay)
                    self._runners[bucket] = runner
            lowered = runner.lower(
                jax.ShapeDtypeStruct((self.max_batch,) + bucket, self.dtype),
                jax.ShapeDtypeStruct((self.max_batch, 3), jnp.int32))
            compiled = lowered.compile()
            self._cache[key] = compiled
            self._stats = dataclasses.replace(
                self._stats, compiles=self._stats.compiles + 1)
        else:
            self._stats = dataclasses.replace(
                self._stats,
                exec_cache_hits=self._stats.exec_cache_hits + 1)
        return compiled

    @property
    def stats(self) -> ServeStats:
        return self._stats

    # ---- the hot path ------------------------------------------------
    def run(self, tensors: Sequence) -> List[MSCResult]:
        """Serve a batch of independent MSC requests.

        Groups requests by bucket, packs each group into max_batch-sized
        microbatches (padding the remainder with inert filler), and
        dispatches one cached executable per microbatch.  Returns one
        trimmed host-side MSCResult per input tensor, in input order.
        """
        results: List[Optional[MSCResult]] = [None] * len(tensors)
        groups: Dict[Tuple[int, int, int], List[int]] = defaultdict(list)
        for i, t in enumerate(tensors):
            groups[self.bucket_of(np.shape(t))].append(i)

        for bucket, idxs in groups.items():
            for start in range(0, len(idxs), self.max_batch):
                chunk = idxs[start:start + self.max_batch]
                self._dispatch(bucket, chunk, tensors, results)
        return results  # type: ignore[return-value]

    def _dispatch(self, bucket, chunk, tensors, results):
        b = self.max_batch
        batch = np.zeros((b,) + bucket, self.dtype)
        dims = np.tile(np.int32(_FILLER_DIMS), (b, 1))
        for s, i in enumerate(chunk):
            t = np.asarray(tensors[i], self.dtype)
            batch[s, :t.shape[0], :t.shape[1], :t.shape[2]] = t
            dims[s] = t.shape
        compiled = self._executable(bucket)
        out = compiled(batch, dims)
        self._stats = dataclasses.replace(
            self._stats,
            requests=self._stats.requests + len(chunk),
            dispatches=self._stats.dispatches + 1,
            filler_slots=self._stats.filler_slots + b - len(chunk))
        host = jax.tree.map(np.asarray, out)
        for s, i in enumerate(chunk):
            results[i] = _trim_request(host, s, tuple(int(x)
                                                      for x in dims[s]))


def _trim_request(host: MSCResult, s: int, shape) -> MSCResult:
    """Slice request s's true-size results out of the bucket-padded
    batched pytree (all host-side numpy — no jax dispatch)."""
    modes = []
    for j, res in enumerate(host.modes):
        m = shape[j]
        modes.append(ModeResult(
            mask=res.mask[s, :m], d=res.d[s, :m], lambdas=res.lambdas[s, :m],
            n_iters=res.n_iters[s], power_iters_run=res.power_iters_run[s]))
    return MSCResult(modes=tuple(modes))


# ------------------------------------------------------------------ §7.7

class _SlotTable:
    """Per-bucket slot-table runtime of the continuous engine: the
    device-resident state (blocks + carries), the host-side slot→request
    map and per-slot dims, the per-class admission queues, the parked
    (preempted-to-host) requests, and the bucket's chunk clock.  Pure
    bookkeeping — all policy lives in the engine."""

    def __init__(self, bucket, blocks, carries, slots: int, dtype,
                 mode_shapes):
        self.bucket = bucket
        self.blocks = blocks
        self.carries = carries
        self.slot_req: List[Optional[int]] = [None] * slots
        self.dims = np.tile(np.int32(_FILLER_DIMS), (slots, 1))
        # per-priority-class FIFO queues (DESIGN.md §7.12); entries are
        # (rid, submit_tick, deadline_tick) with deadline_tick < 0 for
        # "no deadline".  Class 0 is the most urgent.
        self.queues: Dict[int, Deque[Tuple[int, int, int]]] = {}
        self.chunk = 0
        self.fin = np.zeros(slots, bool)  # last chunk's finished flags
        # per-slot scheduler state: priority class, absolute deadline
        # tick (engine clock; -1 = none), and chunks dispatched while
        # resident (the preemption policy's progress proxy)
        self.prio = np.zeros(slots, np.int32)
        self.deadline = np.full(slots, -1, np.int64)
        self.progress = np.zeros(slots, np.int64)
        # preempted-to-host requests: rid → dict(arr, carries (host
        # SolveState per mode), priority, deadline, warm_meta, progress)
        self.parked: Dict[int, Dict] = {}
        # cross-bucket device-time credit (weighted round-robin)
        self.credit = 0.0
        # host copies of the live slots' tensors: the checkpoint payload
        # blocks are rebuilt from (device blocks are a pure function of
        # admitted tensors) and the fallback oracle's input
        self.arrs: List[Optional[np.ndarray]] = [None] * slots
        # recovery state (engine policy writes these)
        self.retries = 0
        self.retry_at = 0.0
        # reusable staging: each admitted tensor once, zero-padded to the
        # bucket (B, M1, M2, M3), unfolded by the refill on the device;
        # dirty[s] marks slots that hold a previous admission's bytes and
        # must be re-zeroed before a tensor smaller than the bucket lands
        self.stage = np.zeros((slots,) + tuple(bucket), dtype)
        self.dirty = np.zeros(slots, bool)
        # warm-start staging (DESIGN.md §7.10): cached eigenvector
        # iterates land here in carry-v layout ((B, m_pad, c) per mode,
        # always f32 like SolveState.v) for the refill executable's
        # warm_v inputs; warm_meta[s] keeps the donor's realized sweep
        # counts until eviction settles `warm_sweeps_saved`
        self.warm_stage = tuple(np.zeros((sh[0], sh[1], sh[3]), np.float32)
                                for sh in mode_shapes)
        self.warm_dirty = np.zeros(slots, bool)
        self.warm_meta: List[Optional[Tuple[int, int, int]]] = [None] * slots
        # resume staging (DESIGN.md §7.12): a parked slot's exported
        # λ/residual rows land here for the refill executable's resume
        # inputs (v rides warm_stage verbatim — init_mode_carry takes it
        # un-normalized under use_resume); iters/done are per-mode
        # scalars, one (slots, 3) row each
        self.resume_lam = tuple(np.zeros((sh[0], sh[1]), np.float32)
                                for sh in mode_shapes)
        self.resume_resid = tuple(np.zeros((sh[0], sh[1]), np.float32)
                                  for sh in mode_shapes)
        self.resume_iters = np.zeros((slots, 3), np.int32)
        self.resume_done = np.zeros((slots, 3), bool)
        self.resume_dirty = np.zeros(slots, bool)

    # ---- per-class queue bookkeeping (DESIGN.md §7.12) ---------------
    def queue_for(self, priority: int) -> Deque[Tuple[int, int, int]]:
        q = self.queues.get(int(priority))
        if q is None:
            q = self.queues[int(priority)] = deque()
        return q

    def queue_len(self) -> int:
        return sum(len(q) for q in self.queues.values())

    def queued(self) -> List[Tuple[int, int, int, int]]:
        """(priority, rid, submit_tick, deadline) in per-class pop
        order, classes ascending — the deterministic drain order."""
        out = []
        for pr in sorted(self.queues):
            out.extend((pr,) + e for e in self.queues[pr])
        return out

    def pop_best(self, tick: int, aging_chunks: int):
        """Pop the head with the lowest EFFECTIVE priority
        `class − wait/aging_chunks` (weighted aging: a queued request
        gains one class of urgency per aging_chunks ticks waited, so
        low-priority work cannot starve).  FIFO within a class; the
        more urgent class wins exact ties.  Returns
        (priority, rid, submit_tick, deadline) or None."""
        best = None
        for pr in sorted(self.queues):
            q = self.queues[pr]
            if not q:
                continue
            eff = pr - (tick - q[0][1]) / max(1, aging_chunks)
            if best is None or eff < best[0]:
                best = (eff, pr)
        if best is None:
            return None
        pr = best[1]
        rid, sub, dl = self.queues[pr].popleft()
        return pr, rid, sub, dl

    def import_slot(self, s: int, carries):
        """Write one parked request's exported per-mode SolveState back
        into the warm/resume staging rows: v into the warm staging
        (selected verbatim under use_resume — no re-normalization, the
        bit-exactness contract), λ/resid/iters/done into the resume
        staging.  Padded rows stay zero, which round-trips exactly
        because a preempted slot has run ≥1 chunk and its padded
        iterate rows are already exactly zero (same argument as §7.8
        checkpoints)."""
        if self.warm_dirty[s]:
            for st in self.warm_stage:
                st[s] = 0
        if self.resume_dirty[s]:
            for st in self.resume_lam:
                st[s] = 0
            for st in self.resume_resid:
                st[s] = 0
        for j, host in enumerate(carries):
            v = np.asarray(host.v, np.float32)
            self.warm_stage[j][s, :v.shape[0], :v.shape[1]] = v
            self.resume_lam[j][s, :v.shape[0]] = np.asarray(
                host.lam, np.float32)
            self.resume_resid[j][s, :v.shape[0]] = np.asarray(
                host.resid, np.float32)
            self.resume_iters[s, j] = int(host.iters)
            self.resume_done[s, j] = bool(host.done)
        self.warm_dirty[s] = True
        self.resume_dirty[s] = True

    def admit_write(self, s: int, arr: np.ndarray):
        """Copy one admitted tensor into slot s of the staging, as it is
        (one contiguous copy; the refill executable unfolds it on the
        device).  A tensor that fills the bucket overwrites every byte
        of the slot, so only a smaller one re-zeroes a dirty slot."""
        if self.dirty[s] and arr.shape != self.stage.shape[1:]:
            with TraceAnnotation("msc.admit.zero"):
                self.stage[s] = 0
        with TraceAnnotation("msc.admit.copy"):
            self.stage[s, :arr.shape[0], :arr.shape[1], :arr.shape[2]] = arr
        self.dirty[s] = True

    def write_warm(self, s: int, vectors):
        """Write one near-hit donor's true-size (m_j, c_j) iterates into
        slot s of the warm staging buffers (zero-padded to carry
        layout — padded rows contribute nothing after the merge)."""
        if self.warm_dirty[s]:
            for st in self.warm_stage:
                st[s] = 0
        for j, v in enumerate(vectors):
            v = np.asarray(v, np.float32)
            self.warm_stage[j][s, :v.shape[0], :v.shape[1]] = v
        self.warm_dirty[s] = True

    @property
    def live(self) -> int:
        return sum(r is not None for r in self.slot_req)

    @property
    def free(self) -> List[int]:
        return [s for s, r in enumerate(self.slot_req) if r is None]

    def has_work(self) -> bool:
        return self.queue_len() > 0 or self.live > 0


class MSCContinuousEngine:
    """Continuous-batching MSC serving (DESIGN.md §7.7) — the MSC
    analogue of an LLM engine's decode loop.

    Where `MSCServeEngine` runs a static microbatch to completion in one
    dispatch (batch-max lockstep: one slow-converging request holds all
    B slots, and new arrivals wait for the next assembly), this engine
    executes in *gate chunks*: each `step()` advances every slot's three
    mode eigensolves by `power_check_every` sweeps through one resumable
    chunk-step executable over persistent device state; slots whose
    request finished are evicted at the next tick's refill executable,
    which finalizes their results (similarity epilogue + extraction from
    the frozen iterates — deferring the link-bound epilogue off the
    per-chunk path), compacts live state, and admits queued requests
    into the freed slots.  Two AOT executables per bucket, both cached —
    a warm bucket performs zero retraces/recompiles across an arbitrary
    arrival/eviction interleaving.

    Scheduler policy knobs:
      refill_min_free — batch refills: only repack once this many slots
        are free (a repack dispatch touches the whole slot table, so
        admitting one request at a time wastes dispatches under load).
      max_queue_chunks — starvation bound, enforced PER CLASS PER
        BUCKET on the engine's tick clock: once any class's oldest
        queued request has waited this many scheduler ticks, refill at
        the next free slot regardless of refill_min_free.  (The engine
        clock advances every step() even for buckets the cross-bucket
        rotation skipped, so a hot bucket cannot starve a cold one.)
      placement — where admitted requests land: "compact" moves live
        slots to the front (slot order = admission order, the LLM
        engine's compaction), "stable" leaves live slots in place and
        fills holes.  Per-request results are invariant to the choice —
        every computation keeps the leading slot dim — which
        tests/test_msc_continuous.py pins by permuting it.
      chunks_per_step — gate chunks fused per dispatch (coarser
        eviction granularity, fewer dispatches; sweep counts and
        results are unchanged because probes stay at check_every
        boundaries).

    SLO-scheduler knobs (DESIGN.md §7.12):
      preempt — allow preempt-to-host: when a strictly more urgent
        request queues and no slot is free, export the lower-priority
        slot with the MOST predicted remaining sweeps (conditional
        tail of the measured sweep histogram) to host, admit the
        waiter, and re-admit the parked request later through the same
        refill executable's resume inputs.  Masks and realized sweep
        counts are bit-identical to the uninterrupted run; the resume
        inputs are part of the ONE lowered refill signature, so the
        zero-recompile contract holds.  Forced off on multi-process
        meshes (replicate_outputs) — the sharded carries are not fully
        addressable on any single host (gang-scheduling across hosts
        is the §7.9 follow-on).
      preempt_min_remaining_chunks — only preempt a victim predicted
        to hold its slot for MORE than this many further chunks
        (preempting a nearly-done solve wastes its residency).
      aging_chunks — weighted-aging rate of the per-class queues: a
        queued request gains one priority class of urgency per this
        many ticks waited, so low priority ages into service.
      slo_chunks — admission control: shed a submit (LoadShedError)
        when `roofline.expected_queue_wait` predicts its queue wait
        would exceed this many chunks — BEFORE solving.  None disables.
      bucket_policy — "weighted" (default) rotates ONE bucket onto the
        device per tick by accumulated queue-depth credit (cross-bucket
        device-time sharing: no bucket idles the device while another
        queues); "all" steps every bucket each tick (the pre-§7.12
        behavior; also what a single-bucket stream degenerates to).

    Fault-tolerance knobs (DESIGN.md §7.8):
      checkpoint_dir — enable periodic checkpointing of the whole
        engine state (None disables it); `restore(checkpoint_dir)`
        rebuilds and resumes, on the same mesh or a different
        `msc_mesh_shape` factorization (elastic restore).
      ckpt_every_chunks — gate chunks between snapshots (across all
        buckets); `checkpoint()` can also be called explicitly.
      keep_checkpoints — keep-last-k GC of the checkpoint directory.
      max_retries — consecutive dispatch retries before a bucket
        degrades to the sequential oracle (`fallback_requests`).
      retry_backoff_s / retry_backoff_max_s — exponential backoff
        between retries (base doubling per attempt, capped).
      fault_injector — a serving/faults.py FaultInjector consulted at
        every dispatch site (tests/benches only).

    Result-cache knobs (DESIGN.md §7.10):
      result_cache — a serving/result_cache.py MSCResultCache placed in
        front of the engine.  submit() first probes it with the
        content-addressed key (canonical tensor SHA-256 ⊕ config
        fingerprint ⊕ code-version salt); an exact hit is answered from
        the cache at the next step() without touching the device.
        Every request served through the device path (or the fallback
        oracle) is inserted at eviction — with its frozen eigenvector
        iterates and spectral sketch on single-process meshes, so it
        can donate tier-2 warm starts.
      warm_start — also probe tier 2 at submit: a near-duplicate
        (sketch within the cache's tolerance, same shape) seeds the
        admitted slot's eigensolver carry from the cached V through the
        refill executable's warm-start inputs.  The warm inputs are
        part of the refill's lowered signature from the start, so
        enabling this performs ZERO new retraces/recompiles; masks stay
        bit-identical to a cold solve (the gate just fires earlier).

    Autotune / auto-config knobs (DESIGN.md §7.11):
      autotune — enable the roofline-driven auto-config layer: per
        bucket, kernel block shapes come from a measured search at the
        AOT compile site (core/autotune.py; a degenerate one-candidate
        "search" on the einsum path), and `inner_overlap` switches on
        when `roofline.eigensolve_model` predicts the double-buffered
        inner psum wins (q > 1 meshes).  Explicit cfg.block_* values
        are overrides: the search is skipped for knobs the caller
        pinned.  All of it is numerics-neutral — masks stay
        bit-identical — and winners ride the per-bucket executable
        cache, so warm serving still performs 0 searches/recompiles.
      autotune_cache — a core/autotune.py AutotuneCache holding
        persisted winners (implies autotune); without one, autotune=True
        creates an engine-private cache persisted under
        `<checkpoint_dir>/autotune` when checkpointing is on.
      cfg.epilogue="auto" — per-bucket epilogue from
        `roofline.choose_epilogue` instead of a flag.
      chunks_per_step="auto" — per-bucket gate-chunk fusion from
        `roofline.choose_chunk_steps`, fed by the measured sweep
        histogram of previously served requests (cold buckets assume
        4 gate chunks).
      donate_buffers — donate the slot-table carries to the chunk-step
        and refill executables, and the blocks to the refill
        (`donate_argnums`): XLA aliases the outputs onto the inputs,
        halving the slot table's HBM high-water mark per dispatch.  Safe
        because the engine always replaces `tb.carries` / `tb.blocks`
        with the dispatch output and never re-reads the input.  Forced
        off when a fault_injector is attached — an injected post-dispatch failure consumes the
        donated carry, and the retry contract re-dispatches the same
        buffers (real failures still recover: the sequential-oracle
        fallback rebuilds state from the stashed host tensors).

    `run(tensors)` serves a closed batch; `submit()` + `step()` expose
    the decode loop for streaming arrivals (launch/msc_serve.py).
    """

    def __init__(self, mesh: Mesh, cfg: MSCConfig, *, slots: int = 8,
                 bucket_quantum: int = 8, dtype=jnp.float32,
                 axis_name=None, inner_axis: Optional[str] = None,
                 chunks_per_step=1, refill_min_free: int = 1,
                 max_queue_chunks: int = 8, placement: str = "compact",
                 checkpoint_dir: Optional[str] = None,
                 ckpt_every_chunks: int = 8, keep_checkpoints: int = 3,
                 max_retries: int = 3, retry_backoff_s: float = 0.05,
                 retry_backoff_max_s: float = 2.0, fault_injector=None,
                 replicate_outputs: bool = False, result_cache=None,
                 warm_start: bool = False, autotune: bool = False,
                 autotune_cache=None, donate_buffers: bool = True,
                 preempt: bool = True,
                 preempt_min_remaining_chunks: int = 2,
                 aging_chunks: int = 16,
                 slo_chunks: Optional[int] = None,
                 bucket_policy: str = "weighted"):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if placement not in ("compact", "stable"):
            raise ValueError(f"unknown placement {placement!r}; "
                             f"expected 'compact' or 'stable'")
        if bucket_policy not in ("weighted", "all"):
            raise ValueError(f"unknown bucket_policy {bucket_policy!r}; "
                             f"expected 'weighted' or 'all'")
        if cfg.power_tol <= 0.0:
            raise ValueError("continuous batching needs the adaptive gate "
                             "(cfg.power_tol > 0); without it every slot "
                             "runs to the cap and eviction never helps")
        self.mesh = mesh
        self.cfg = cfg
        self.slots = int(slots)
        self.dtype = jnp.dtype(dtype)
        # clamp to the table size: a threshold no drain can reach would
        # deadlock admission (the starvation clock only advances while
        # chunks run)
        self.refill_min_free = min(max(1, int(refill_min_free)),
                                   self.slots)
        self.max_queue_chunks = int(max_queue_chunks)
        self.placement = placement
        # ---- SLO scheduler (DESIGN.md §7.12) ----
        # preempt-to-host needs host-addressable carries; multi-process
        # meshes (replicate_outputs) park it (§7.9 gang-scheduling is
        # the follow-on)
        self.preempt = bool(preempt) and not replicate_outputs
        self.preempt_min_remaining_chunks = int(preempt_min_remaining_chunks)
        self.aging_chunks = max(1, int(aging_chunks))
        self.slo_chunks = None if slo_chunks is None else int(slo_chunks)
        self.bucket_policy = bucket_policy
        self._tick = 0                      # engine scheduler clock
        # the default plan needs a concrete config — "auto" knobs
        # resolve per bucket in _plan_for; the base stands in wherever
        # no bucket is in scope (fallback oracle, checkpoint plumbing)
        self._base_cfg = (cfg.with_(epilogue="allgather")
                          if cfg.epilogue == "auto" else cfg)
        self._chunks_param = chunks_per_step
        base_chunks = (1 if chunks_per_step == "auto"
                       else int(chunks_per_step))
        self._axis_name = axis_name
        self._inner_axis = inner_axis
        # replicate_outputs=True on multi-process (jax.distributed)
        # meshes: host-read outputs must be fully addressable everywhere
        # (see MSCChunkPlan); the per-process executables stay identical
        # across hosts, which is what keeps the lockstep control plane
        # (launch/distributed.py) deterministic.
        self._plan = MSCChunkPlan(mesh, self._base_cfg,
                                  axis_name=axis_name,
                                  inner_axis=inner_axis,
                                  chunks_per_step=base_chunks,
                                  replicate_outputs=replicate_outputs)
        self._quantum = _bucket_quantum(mesh, inner_axis, bucket_quantum)
        self._quantum_base = int(bucket_quantum)  # mesh-independent (ckpt)
        self._cache: Dict[Tuple, Tuple] = {}
        self._tables: Dict[Tuple[int, int, int], _SlotTable] = {}
        self._pending: Dict[int, Tuple[np.ndarray, Tuple[int, int, int]]] = {}
        self._next_rid = 0
        self._stats = ServeStats()
        # ---- fault tolerance (DESIGN.md §7.8) ----
        self.checkpoint_dir = checkpoint_dir
        self.ckpt_every_chunks = int(ckpt_every_chunks)
        self.keep_checkpoints = int(keep_checkpoints)
        self.max_retries = int(max_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.retry_backoff_max_s = float(retry_backoff_max_s)
        self._faults = fault_injector
        self._recovering: set = set()   # buckets mid-retry (sheds load)
        self._total_chunks = 0          # monotonic ckpt step id
        self._chunks_since_ckpt = 0
        # ---- result cache (DESIGN.md §7.10) ----
        self.result_cache = result_cache
        self.warm_start = bool(warm_start)
        self._salt: Optional[str] = None      # cache_salt(), lazy
        self._ready: Dict[int, MSCResult] = {}       # tier-1 hits
        self._req_key: Dict[int, str] = {}           # rid → cache key
        self._req_sketch: Dict[int, np.ndarray] = {}
        self._warm_pending: Dict[int, object] = {}   # rid → NearHit
        # ---- autotune / auto-config (DESIGN.md §7.11) ----
        self.autotune_cache = autotune_cache
        if autotune and autotune_cache is None:
            from repro.core.autotune import AutotuneCache
            self.autotune_cache = AutotuneCache(
                persist_dir=os.path.join(checkpoint_dir, "autotune")
                if checkpoint_dir else None)
        self._autotune = self.autotune_cache is not None
        self.donate_buffers = (bool(donate_buffers)
                               and fault_injector is None)
        self._bucket_plans: Dict[Tuple[int, int, int], MSCChunkPlan] = {}
        # winner (plan, step-executable) a live search just compiled,
        # consumed by _executables so the winning config compiles once
        self._tuned_step: Dict[Tuple[int, int, int], Tuple] = {}
        # realized max-mode sweep counts of served requests — the
        # measured histogram feeding choose_chunk_steps
        self._sweep_hist: Deque[int] = deque(maxlen=256)

    # ---- bucketing / cache -------------------------------------------
    def bucket_of(self, shape: Sequence[int]) -> Tuple[int, int, int]:
        """Bucket = each dim rounded up to the engine quantum."""
        return _bucket_of(shape, self._quantum)

    @property
    def stats(self) -> ServeStats:
        return self._stats

    def _bump(self, **deltas):
        self._stats = dataclasses.replace(
            self._stats, **{k: getattr(self._stats, k) + v
                            for k, v in deltas.items()})

    def note_ft_event(self, **deltas):
        """Bump fault-tolerance counters owned by an outer control plane
        (the multi-host driver in launch/distributed.py: heartbeat
        misses, host losses, reinits, shard files written)."""
        self._bump(**deltas)

    # ---- per-bucket auto-config + block autotune (DESIGN.md §7.11) ----
    def _resolve_bucket(self, bucket) -> Tuple[MSCConfig, int]:
        """Resolved (cfg, chunks_per_step) for one bucket: the roofline
        choosers fill every knob the caller left on "auto"; explicit
        flags pass through untouched (flags are overrides)."""
        cfg = self._base_cfg
        p = self._plan.sched.slice_shards
        q = self._plan.sched.inner_shards
        check = max(cfg.power_check_every, 1)
        if self.cfg.epilogue == "auto":
            from repro.roofline import choose_epilogue
            # mode 1 dominates epilogue bytes on near-cube buckets; the
            # schedules take one policy (same framing as parallel.py)
            cfg = cfg.with_(epilogue=choose_epilogue(bucket[0], bucket[2],
                                                     p))
        if self._autotune and q > 1 and not cfg.inner_overlap:
            from repro.roofline import eigensolve_model
            m, r, c = bucket
            plain = eigensolve_model(m, r, c, p, q, sweeps=check)
            both = eigensolve_model(m, r, c, p, q, sweeps=check,
                                    overlap=True)
            if both["latency_s"] < plain["latency_s"]:
                cfg = cfg.with_(inner_overlap=True)
        chunks = self._plan.chunks_per_step
        if self._chunks_param == "auto":
            from repro.roofline import choose_chunk_steps
            hist = list(self._sweep_hist) or [4 * check]
            chunks = choose_chunk_steps(hist, self.slots,
                                        check_every=check, shape=bucket,
                                        p=p, q=q, epilogue=cfg.epilogue)
        return cfg, chunks

    def _make_plan(self, cfg: MSCConfig, chunks: int) -> MSCChunkPlan:
        if cfg == self._base_cfg and chunks == self._plan.chunks_per_step:
            return self._plan
        return MSCChunkPlan(self.mesh, cfg, axis_name=self._axis_name,
                            inner_axis=self._inner_axis,
                            chunks_per_step=chunks,
                            replicate_outputs=self._plan.replicate_outputs)

    def _tune_blocks(self, bucket, cfg: MSCConfig,
                     chunks: int) -> MSCConfig:
        """Resolve kernel block shapes — and validate the roofline
        models' config proposals — for one bucket through the autotune
        cache.  A live search compiles and times each candidate's
        chunk-step AND refill executables on scratch state, exactly at
        the AOT site (the similarity epilogue runs in the refill, so
        block_i/block_j and the epilogue pick are only observable
        there), and stashes the winner's executables so they never
        compile twice.  When `_resolve_bucket` proposed a non-default
        epilogue/inner_overlap, both variants enter the measured
        candidate set with the hand-set default first: the model
        proposes, the measurement disposes, and the default wins
        near-ties — auto-config does no harm on hardware the comm model
        doesn't describe.  Knobs the caller pinned in cfg are not
        searched."""
        from repro.core import autotune as at

        base = self._base_cfg
        variants = [cfg]
        if (cfg.epilogue != base.epilogue
                or cfg.inner_overlap != base.inner_overlap):
            variants = [cfg.with_(epilogue=base.epilogue,
                                  inner_overlap=base.inner_overlap), cfg]
        pinned = (cfg.block_r is not None and cfg.block_i is not None
                  and cfg.block_j is not None)
        if pinned and len(variants) == 1:
            return cfg   # fully pinned, no proposal to validate
        ac = self.autotune_cache
        key = at.autotune_key(bucket + (self.slots,),
                              tuple(self.mesh.shape.items()),
                              str(self.dtype), cfg, salt=ac.salt)
        bcands = [c for c in at.block_candidates(bucket, cfg.use_kernels)
                  if all(getattr(cfg, k) in (None, v)
                         for k, v in c.items())] \
            or [{k: getattr(cfg, k) if getattr(cfg, k) is not None else v
                 for k, v in at.DEFAULT_BLOCKS.items()}]
        cands = [dict(b, epilogue=v.epilogue,
                      inner_overlap=v.inner_overlap)
                 for v in variants for b in bcands]
        searches0 = ac.searches
        B = self.slots
        fill = np.tile(np.int32(_FILLER_DIMS), (B, 1))
        no = np.zeros(B, bool)

        def measure(cand):
            ccfg = cfg.with_(**cand)
            plan = self._make_plan(ccfg, chunks)
            step = self._compile_step(plan, bucket)
            refill = self._compile_refill(plan, bucket)
            secs = []
            # rep 0 is a warmup: a fresh executable's first dispatch
            # pays one-time host costs that would swamp the comparison
            for rep in range(4):
                blocks, carries = plan.init_state(bucket, B, self.dtype)
                stage = plan.zero_stage(bucket, B, self.dtype)
                warm = plan.zero_warm(bucket, B)
                zres = plan.zero_resume(bucket, B)
                t0 = time.perf_counter()
                carries, _ = step(blocks, carries)
                blocks, carries, _ = refill(
                    blocks, carries, fill, stage, fill, no,
                    np.ones(B, bool), np.arange(B, dtype=np.int32),
                    warm, no, zres[0], zres[1], zres[2], zres[3], no)
                jax.block_until_ready(carries)
                if rep:
                    secs.append(time.perf_counter() - t0)
            secs.sort()
            return secs[len(secs) // 2], (plan, step, refill)

        margin = (at.VALIDATE_MARGIN if len(variants) > 1
                  else at.DEFAULT_MARGIN)
        knobs, payload = ac.resolve(key, cands, measure, margin=margin)
        if ac.searches > searches0:
            self._bump(autotune_searches=1, compiles=2 * len(cands))
            ac.persist()
        else:
            self._bump(autotune_cache_hits=1)
        tuned = cfg.with_(**knobs)
        if payload is not None:
            self._tuned_step[bucket] = payload
        return tuned

    def _plan_for(self, bucket) -> MSCChunkPlan:
        """The bucket's resolved chunk plan (cached): base plan when
        nothing resolves differently, else one built from the bucket's
        auto-configured config."""
        plan = self._bucket_plans.get(bucket)
        if plan is None:
            cfg, chunks = self._resolve_bucket(bucket)
            if self._autotune:
                cfg = self._tune_blocks(bucket, cfg, chunks)
                stash = self._tuned_step.get(bucket)
                if stash is not None:
                    plan = stash[0]
            if plan is None:
                plan = self._make_plan(cfg, chunks)
            self._bucket_plans[bucket] = plan
        return plan

    def _compile_step(self, plan: MSCChunkPlan, bucket):
        blocks_s, carries_s = plan.state_structs(bucket, self.slots,
                                                 self.dtype)
        donate = (1,) if self.donate_buffers else ()
        return jax.jit(plan.build_step(),
                       donate_argnums=donate).lower(
            blocks_s, carries_s).compile()

    def _compile_refill(self, plan: MSCChunkPlan, bucket):
        B = self.slots
        i32 = jnp.int32
        blocks_s, carries_s = plan.state_structs(bucket, B, self.dtype)
        dims_s = jax.ShapeDtypeStruct((B, 3), i32)
        bsh = plan._block_sharding()
        stage_s = jax.ShapeDtypeStruct(plan.stage_shape(bucket, B),
                                       self.dtype, sharding=bsh)
        # warm-start inputs are part of the ONE lowered refill signature
        # (cold refills pass device-resident zeros + all-False), so the
        # zero-recompile contract covers warm admissions too
        vsh = plan._carry_shardings().v
        warm_s = tuple(jax.ShapeDtypeStruct(sh, jnp.float32, sharding=vsh)
                       for sh in plan.warm_shapes(bucket, B))
        # resume (preempt-to-host) inputs are likewise part of the ONE
        # lowered signature: cold/warm refills pass device-resident
        # zeros + all-False use_resume, so preemption adds no recompile
        lsh = plan._carry_shardings().lam
        res_s = tuple(jax.ShapeDtypeStruct(sh, jnp.float32, sharding=lsh)
                      for sh in plan.resume_shapes(bucket, B))
        # the refill replaces the blocks too: donating them lets the
        # repacked table reuse their HBM instead of doubling it
        donate = (0, 1) if self.donate_buffers else ()
        return jax.jit(plan.build_refill(),
                       donate_argnums=donate).lower(
            blocks_s, carries_s, dims_s, stage_s, dims_s,
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((B,), i32), warm_s,
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            res_s, res_s,
            jax.ShapeDtypeStruct((B, 3), i32),
            jax.ShapeDtypeStruct((B, 3), jnp.bool_),
            jax.ShapeDtypeStruct((B,), jnp.bool_)).compile()

    def _executables(self, bucket):
        """(chunk-step, refill) AOT executables for one bucket — the
        only two programs a bucket ever runs (zero-retrace contract).
        With autotuning on, the bucket's plan carries the resolved
        blocks/epilogue/overlap/fusion; a just-searched bucket reuses
        the winner's already-compiled chunk step."""
        plan = self._plan_for(bucket)
        key = (bucket, self.slots, str(self.dtype),
               tuple(self.mesh.shape.items()), plan.sched.cfg,
               plan.chunks_per_step, self.donate_buffers)
        entry = self._cache.get(key)
        if entry is not None:
            self._bump(exec_cache_hits=1)
            return entry
        stash = self._tuned_step.pop(bucket, None)
        if stash is not None and stash[0] is plan:
            # the search compiled (and counted) the winner's pair
            step, refill = stash[1], stash[2]
            new_compiles = 0
        else:
            step = self._compile_step(plan, bucket)
            refill = self._compile_refill(plan, bucket)
            new_compiles = 2
        entry = (step, refill)
        self._cache[key] = entry
        self._bump(compiles=new_compiles)
        return entry

    def _table(self, bucket) -> _SlotTable:
        tb = self._tables.get(bucket)
        if tb is None:
            plan = self._plan_for(bucket)
            blocks, carries = plan.init_state(bucket, self.slots,
                                              self.dtype)
            tb = _SlotTable(bucket, blocks, carries, self.slots, self.dtype,
                            plan.mode_shapes(bucket, self.slots))
            tb.zero_stage = plan.zero_stage(bucket, self.slots,
                                            self.dtype)
            tb.zero_warm = plan.zero_warm(bucket, self.slots)
            tb.zero_resume = plan.zero_resume(bucket, self.slots)
            self._tables[bucket] = tb
        return tb

    # ---- the decode loop ---------------------------------------------
    def submit(self, tensor, *, priority: int = 0,
               deadline_chunks: Optional[int] = None) -> int:
        """Queue one request; returns its id (the key `step()` results
        come back under).

        priority — non-negative class, 0 most urgent; requests drain
          per class under weighted aging (DESIGN.md §7.12).
        deadline_chunks — optional SLO budget in scheduler ticks; a
          request finalizing later counts a `deadline_misses` (advisory
          — the result is still delivered).

        Raises LoadShedError while any bucket is recovering from a
        dispatch failure, or when `slo_chunks` is set and the queue-wait
        model predicts this request would wait longer than the bound —
        shedding BEFORE solving keeps a sick or saturated engine from
        growing an unbounded queue (clients resubmit later)."""
        if priority < 0:
            raise ValueError(f"priority must be >= 0, got {priority}")
        if deadline_chunks is not None and deadline_chunks < 1:
            raise ValueError(f"deadline_chunks must be >= 1, "
                             f"got {deadline_chunks}")
        with TraceAnnotation("msc.submit") as span:
            arr = np.asarray(tensor, self.dtype)
            cache = self.result_cache
            key = None
            if cache is not None:
                # tier-1 probe BEFORE the load-shed gate: an exact hit never
                # touches the (possibly sick) device path, so there is
                # nothing to shed
                if self._salt is None:
                    from repro.core.fingerprint import cache_salt
                    self._salt = cache_salt()
                from repro.core.fingerprint import result_cache_key
                key = result_cache_key(arr, self.cfg, salt=self._salt)
                res = cache.get(key)
                if res is not None:
                    rid = self._next_rid
                    self._next_rid += 1
                    span.set_metadata(rid=rid)
                    self._ready[rid] = res
                    self._bump(requests=1, cache_hits=1)
                    return rid
            if self._recovering:
                self._bump(shed_requests=1)
                raise LoadShedError(
                    f"engine is recovering from a dispatch failure on "
                    f"bucket(s) {sorted(self._recovering)}; resubmit after "
                    f"recovery")
            bucket = self.bucket_of(arr.shape)
            tb = self._table(bucket)
            if self.slo_chunks is not None:
                pred = self._predicted_wait(tb, int(priority))
                if pred > self.slo_chunks:
                    self._bump(shed_requests=1, slo_sheds=1)
                    raise LoadShedError(
                        f"predicted queue wait {pred:.1f} chunks exceeds the "
                        f"SLO bound {self.slo_chunks} for bucket {bucket} "
                        f"(priority {priority}); resubmit later")
            rid = self._next_rid
            self._next_rid += 1
            span.set_metadata(rid=rid)
            self._pending[rid] = (arr, bucket)
            deadline = (-1 if deadline_chunks is None
                        else self._tick + int(deadline_chunks))
            tb.queue_for(priority).append((rid, self._tick, deadline))
            self._bump(requests=1)
            if cache is not None:
                self._bump(cache_misses=1)
                self._req_key[rid] = key
                if self.warm_start:
                    from repro.core.fingerprint import spectral_sketch
                    sketch = spectral_sketch(arr, r=cache.sketch_r)
                    self._req_sketch[rid] = sketch
                    hit = cache.lookup_near(sketch, arr.shape)
                    if hit is not None:
                        self._warm_pending[rid] = hit
            return rid

    def has_work(self) -> bool:
        return bool(self._ready) or any(tb.has_work()
                                        for tb in self._tables.values())

    def step(self) -> Dict[int, MSCResult]:
        """One scheduler tick: admit (policy permitting), advance one
        gate chunk, evict finished slots.  Under bucket_policy
        "weighted" exactly ONE bucket runs per tick — the one with the
        most accumulated queue-depth credit — so device time is shared
        across buckets in proportion to their load (cross-bucket slot
        sharing, DESIGN.md §7.12); "all" steps every bucket.  Returns
        the requests that finished this tick — the ONLY copy (the
        engine retains nothing, so a long-running decode loop doesn't
        accumulate served results)."""
        finished: Dict[int, MSCResult] = {}
        self._tick += 1
        if self._ready:   # tier-1 cache hits, answered without a dispatch
            finished.update(self._ready)
            self._ready.clear()
        now = time.monotonic()
        ready = [tb for tb in self._tables.values() if tb.has_work()]
        runnable = [tb for tb in ready
                    if not tb.retry_at or now >= tb.retry_at]
        if (self.bucket_policy == "weighted" and len(ready) > 1
                and runnable):
            # accumulate credit on EVERY bucket with work (so a skipped
            # bucket's claim grows), then run the runnable max; ties
            # break on bucket id for determinism
            for tb in ready:
                tb.credit += tb.live + tb.queue_len()
            chosen = max(runnable, key=lambda t: (t.credit, t.bucket))
            chosen.credit = 0.0
            finished.update(self._step_table(chosen))
        else:
            for tb in ready:
                finished.update(self._step_table(tb))
        if (self.checkpoint_dir is not None and self.ckpt_every_chunks > 0
                and self._chunks_since_ckpt >= self.ckpt_every_chunks):
            self.checkpoint()
        return finished

    def run(self, tensors: Sequence, *,
            priorities: Optional[Sequence[int]] = None,
            deadline_chunks: Optional[Sequence[Optional[int]]] = None
            ) -> List[MSCResult]:
        """Serve a closed set of requests to completion, in order.
        Optional per-request `priorities` / `deadline_chunks` ride
        through to submit().

        Drives step() until its own submissions finish; don't interleave
        with an external submit()/step() loop — results step() hands out
        while run() drains would be collected (and dropped) here."""
        rids = [self.submit(
            t,
            priority=0 if priorities is None else int(priorities[i]),
            deadline_chunks=None if deadline_chunks is None
            else deadline_chunks[i])
            for i, t in enumerate(tensors)]
        got: Dict[int, MSCResult] = {}
        while self.has_work() and not all(r in got for r in rids):
            got.update(self.step())
        return [got[r] for r in rids]

    # ---- per-bucket tick ---------------------------------------------
    def _should_admit(self, tb: _SlotTable, n_free: int) -> bool:
        if n_free == 0 or tb.queue_len() == 0:
            return False
        if n_free >= self.refill_min_free:
            return True
        # starvation bound, per CLASS per BUCKET on the engine's tick
        # clock: the clock advances even on ticks the cross-bucket
        # rotation gave to another bucket, so neither a hot bucket nor
        # a hot class can starve the rest past max_queue_chunks
        return any(self._tick - q[0][1] >= self.max_queue_chunks
                   for q in tb.queues.values() if q)

    def _mean_chunks(self, tb: _SlotTable) -> float:
        """Measured mean request residency in gate chunks (the sweep
        histogram over this engine's served requests; cold default 4
        chunk-steps)."""
        k = max(1, self.cfg.power_check_every)
        per = k * self._plan_for(tb.bucket).chunks_per_step
        hist = list(self._sweep_hist)
        if not hist:
            return 4.0
        return max(1.0, float(np.mean(hist)) / per)

    def _predicted_wait(self, tb: _SlotTable, priority: int) -> float:
        """Predicted queue wait (chunks) for a new request of `priority`
        joining this bucket — the admission-control input
        (roofline.expected_queue_wait)."""
        from repro.roofline import expected_queue_wait

        ahead = sum(len(q) for pr, q in tb.queues.items()
                    if pr <= priority)
        return expected_queue_wait(ahead, len(tb.free), self.slots,
                                   self._mean_chunks(tb))

    def _plan_preempt(self, tb: _SlotTable, n_free: int) -> List[int]:
        """Pick at most ONE slot to preempt-to-host this tick
        (DESIGN.md §7.12): only when no slot frees up anyway, a
        STRICTLY more urgent request waits, and some lower-priority
        victim is predicted to hold its slot for more than
        `preempt_min_remaining_chunks` further chunks.  Among victims,
        evict the one with the MOST predicted remaining sweeps (the
        conditional tail of the measured sweep histogram over its
        current progress) — the §7.11 histogram reused as policy."""
        if not self.preempt or n_free > 0:
            return []
        waiting = [pr for pr, q in tb.queues.items() if q]
        if not waiting:
            return []
        from repro.core.power_iter import predict_remaining_sweeps

        urgent = min(waiting)
        k = max(1, self.cfg.power_check_every)
        per = k * self._plan_for(tb.bucket).chunks_per_step
        cap = self.cfg.power_iters
        best = None
        for s, rid in enumerate(tb.slot_req):
            if rid is None or tb.fin[s] or tb.prio[s] <= urgent:
                continue
            cur = int(tb.progress[s]) * per
            rem = predict_remaining_sweeps(self._sweep_hist, cur, cap=cap,
                                           check_every=k) / per
            if rem > self.preempt_min_remaining_chunks:
                if best is None or rem > best[0]:
                    best = (rem, s)
        return [] if best is None else [best[1]]

    def _permutation(self, tb: _SlotTable) -> np.ndarray:
        """Slot permutation for the repack (new[s] = old[perm[s]])."""
        if self.placement == "compact":
            order = ([s for s, r in enumerate(tb.slot_req) if r is not None]
                     + tb.free)
            return np.asarray(order, np.int32)
        return np.arange(self.slots, dtype=np.int32)

    def _refill(self, tb: _SlotTable, refill_exec, evict: List[int],
                preempt: List[int]) -> Dict[int, MSCResult]:
        """Evict/finalize/repack dispatch: finalize results for `evict`
        slots, export `preempt` slots to host (parked, re-queued at the
        front of their class), free both, then permute + admit — one
        dispatch of the ONE lowered refill executable covers all of it
        (resume inputs included in its signature from the start, so the
        zero-recompile contract holds across any preempt/resume
        interleaving)."""
        old_dims = tb.dims.copy()
        old_deadline = tb.deadline.copy()
        old_warm_meta = list(tb.warm_meta)
        evict_rids = [(s, tb.slot_req[s]) for s in evict]
        cache = self.result_cache
        # host-read the frozen iterates of the evicted slots BEFORE the
        # dispatch replaces tb.carries: they become tier-2 warm-start
        # donors.  Skipped on multi-process meshes (replicate_outputs) —
        # the sharded carries are not fully addressable on any one host.
        # Preempted slots are deliberately NOT captured: their iterates
        # are mid-solve, so a sketch insert would seed later warm starts
        # from an unconverged state (stale-capture hazard).
        capture = None
        if (cache is not None and evict_rids
                and not self._plan.replicate_outputs):
            capture = [np.asarray(tb.carries[j].v) for j in range(3)]
        plan = self._plan_for(tb.bucket)
        for s in preempt:
            rid = tb.slot_req[s]
            tb.parked[rid] = {
                "arr": tb.arrs[s],
                "carries": plan.export_slot(tb.bucket, tb.carries, s),
                "priority": int(tb.prio[s]),
                "deadline": int(tb.deadline[s]),
                "warm_meta": tb.warm_meta[s],
                "progress": int(tb.progress[s]),
            }
            # re-queue at the FRONT of its class (it is the class's
            # oldest work); the wait clock restarts at the preemption
            # tick, so parked time counts as queue wait
            tb.queue_for(tb.prio[s]).appendleft(
                (rid, self._tick, int(tb.deadline[s])))
        for s in evict + preempt:
            tb.slot_req[s] = None
            tb.arrs[s] = None
            tb.warm_meta[s] = None
            tb.prio[s] = 0
            tb.deadline[s] = -1
            tb.progress[s] = 0
        perm = self._permutation(tb)
        tb.slot_req = [tb.slot_req[p] for p in perm]
        tb.arrs = [tb.arrs[p] for p in perm]
        tb.dims = tb.dims[perm]
        tb.fin = tb.fin[perm]
        tb.warm_meta = [tb.warm_meta[p] for p in perm]
        tb.prio = tb.prio[perm]
        tb.deadline = tb.deadline[perm]
        tb.progress = tb.progress[perm]
        new_dims = np.tile(np.int32(_FILLER_DIMS), (self.slots, 1))
        take_new = np.zeros(self.slots, bool)
        new_done = np.ones(self.slots, bool)
        use_warm = np.zeros(self.slots, bool)
        use_resume = np.zeros(self.slots, bool)
        waited = 0
        n_resumes = 0
        for s in tb.free:
            entry = tb.pop_best(self._tick, self.aging_chunks)
            if entry is None:
                break
            pr, rid, submitted, deadline = entry
            with TraceAnnotation("msc.admit", rid=rid, slot=s):
                parked = tb.parked.pop(rid, None)
                if parked is not None:
                    arr = parked["arr"]
                    tb.admit_write(s, arr)
                    tb.import_slot(s, parked["carries"])
                    use_resume[s] = True
                    tb.warm_meta[s] = parked["warm_meta"]
                    tb.progress[s] = parked["progress"]
                    n_resumes += 1
                else:
                    arr, _ = self._pending.pop(rid)
                    tb.admit_write(s, arr)
                    tb.progress[s] = 0
                    hit = self._warm_pending.pop(rid, None)
                    if hit is not None:
                        tb.write_warm(s, hit.vectors)
                        use_warm[s] = True
                        tb.warm_meta[s] = hit.donor_iters
                        self._bump(warm_starts=1)
                    else:
                        tb.warm_meta[s] = None
            new_dims[s] = arr.shape
            take_new[s] = True
            new_done[s] = False
            tb.slot_req[s] = rid
            tb.arrs[s] = arr
            tb.dims[s] = arr.shape
            tb.fin[s] = False
            tb.prio[s] = pr
            tb.deadline[s] = deadline
            waited += self._tick - submitted
        # eviction-only repack: reuse the device-resident zero staging
        # so no staging bytes cross the host boundary
        stage = tb.stage if take_new.any() else tb.zero_stage
        wstage = (tb.warm_stage if use_warm.any() or use_resume.any()
                  else tb.zero_warm)
        rstage = ((tb.resume_lam, tb.resume_resid, tb.resume_iters,
                   tb.resume_done) if use_resume.any()
                  else tb.zero_resume)
        args = (tb.blocks, tb.carries, old_dims, stage, new_dims, take_new,
                new_done, perm, wstage, use_warm, *rstage, use_resume)
        # the host arrays among them are what the call copies to the device
        staged = sum(x.nbytes for x in jax.tree.leaves(args)
                     if isinstance(x, np.ndarray))
        with TraceAnnotation("msc.refill.call", tick=self._tick):
            tb.blocks, tb.carries, results = self._invoke(
                "refill", refill_exec, *args)
        self._bump(refills=1, dispatches=1, queue_wait_chunks=waited,
                   staged_bytes=staged, evictions=len(evict_rids),
                   preemptions=len(preempt), resumes=n_resumes)
        out: Dict[int, MSCResult] = {}
        if evict_rids:
            from repro.core.parallel import C_OF

            with TraceAnnotation("msc.refill.read", tick=self._tick):
                host = jax.tree.map(np.asarray, results)
                for s, rid in evict_rids:
                    res = _trim_request(
                        host, s, tuple(int(x) for x in old_dims[s]))
                    out[rid] = res
                    if old_deadline[s] >= 0 and self._tick > old_deadline[s]:
                        self._bump(deadline_misses=1)
                    pir = [res.modes[j].power_iters_run for j in range(3)]
                    if all(x is not None for x in pir):
                        # measured sweep histogram feeding choose_chunk_steps
                        self._sweep_hist.append(max(int(x) for x in pir))
                    wm = old_warm_meta[s]
                    if wm is not None:
                        self._bump(warm_sweeps_saved=sum(
                            max(0, int(di) - int(res.modes[j].power_iters_run))
                            for j, di in enumerate(wm)))
                    key = self._req_key.pop(rid, None)
                    sketch = self._req_sketch.pop(rid, None)
                    if cache is not None and key is not None:
                        vecs = None
                        if capture is not None:
                            d = old_dims[s]
                            vecs = tuple(capture[j][s, :d[j], :d[C_OF[j]]]
                                         for j in range(3))
                        cache.put(key, res, shape=old_dims[s], vectors=vecs,
                                  sketch=sketch)
        return out

    def _step_table(self, tb: _SlotTable) -> Dict[int, MSCResult]:
        if tb.retry_at and time.monotonic() < tb.retry_at:
            return {}  # backing off before this bucket's next retry
        step_exec, refill_exec = self._executables(tb.bucket)
        # evict slots the last chunk finished + admit queued arrivals —
        # one repack dispatch covers both (and finalizes the evicted
        # slots' results from their frozen iterates)
        evict = [s for s in range(self.slots)
                 if tb.fin[s] and tb.slot_req[s] is not None]
        preempt = self._plan_preempt(tb, len(tb.free) + len(evict))
        out: Dict[int, MSCResult] = {}
        if (evict or preempt
                or self._should_admit(tb, len(tb.free) + len(evict))):
            # _refill mutates host bookkeeping before its dispatch;
            # snapshot it so a failed dispatch rolls back to a state the
            # retry re-plans identically from (device state is only
            # REPLACED by dispatch outputs, never mutated in place)
            snap = (list(tb.slot_req), list(tb.arrs), tb.dims.copy(),
                    tb.fin.copy(),
                    {pr: deque(q) for pr, q in tb.queues.items()},
                    dict(self._pending), list(tb.warm_meta),
                    dict(self._warm_pending), dict(self._req_key),
                    dict(self._req_sketch), dict(tb.parked),
                    tb.prio.copy(), tb.deadline.copy(),
                    tb.progress.copy())
            try:
                with TraceAnnotation("msc.refill", tick=self._tick):
                    out = self._refill(tb, refill_exec, evict, preempt)
            except _DISPATCH_FAILURES as e:
                (tb.slot_req, tb.arrs, tb.dims, tb.fin, tb.queues,
                 self._pending, tb.warm_meta, self._warm_pending,
                 self._req_key, self._req_sketch, tb.parked,
                 tb.prio, tb.deadline, tb.progress) = snap
                return self._dispatch_failed(tb, e, out)
        if tb.live > 0:
            live = tb.live
            # refill batching can leave free slots idle while this
            # bucket's own queue is non-empty — the diagnostic the
            # cross-bucket bench gates at 0 for refill_min_free == 1
            if tb.queue_len() > 0 and len(tb.free) > 0:
                self._bump(idle_bucket_ticks=1)
            advanced = [s for s, r in enumerate(tb.slot_req)
                        if r is not None and not tb.fin[s]]
            try:
                with TraceAnnotation("msc.chunk.call", tick=self._tick):
                    carries, finished = self._invoke("chunk", step_exec,
                                                     tb.blocks, tb.carries)
            except _DISPATCH_FAILURES as e:
                # nothing to roll back: the chunk dispatch is functional
                # (results from a successful refill still get delivered)
                return self._dispatch_failed(tb, e, out)
            tb.carries = carries
            # the host waits here for the chunk step to finish
            with TraceAnnotation("msc.chunk.read", tick=self._tick):
                tb.fin = np.asarray(finished)
            tb.chunk += 1
            tb.progress[advanced] += 1
            self._total_chunks += 1
            self._chunks_since_ckpt += 1
            self._bump(chunk_steps=1, dispatches=1,
                       slot_chunks=self.slots, busy_slot_chunks=live)
        tb.retries = 0
        tb.retry_at = 0.0
        self._recovering.discard(tb.bucket)
        return out

    # ---- recovery policy (DESIGN.md §7.8) -----------------------------
    def _invoke(self, kind: str, fn, *args):
        """Run one dispatch through the fault-injection hooks."""
        if self._faults is not None:
            self._faults.before(kind)
        result = fn(*args)
        if self._faults is not None:
            self._faults.after(kind)
        return result

    def _dispatch_failed(self, tb: _SlotTable, exc: Exception,
                         out: Dict[int, MSCResult]) -> Dict[int, MSCResult]:
        """Bounded retry with exponential backoff; sequential-oracle
        fallback once retries are exhausted.  `out` carries results a
        dispatch earlier in the same tick already produced."""
        tb.retries += 1
        if tb.retries > self.max_retries:
            warnings.warn(
                f"bucket {tb.bucket}: dispatch failed {tb.retries} "
                f"consecutive times ({exc!r}); serving its requests "
                f"through the sequential oracle")
            out.update(self._fallback_table(tb))
            return out
        self._recovering.add(tb.bucket)
        self._bump(retries=1)
        backoff = min(self.retry_backoff_s * (2 ** (tb.retries - 1)),
                      self.retry_backoff_max_s)
        tb.retry_at = time.monotonic() + backoff
        return out

    def _fallback_table(self, tb: _SlotTable) -> Dict[int, MSCResult]:
        """Degrade-to-sequential: solve every live and queued request of
        a sick bucket host-side via the one-tensor oracle (msc_sequential
        — the reference the continuous path is bit-identical to), then
        reset the table to a fresh inert state.  Slow, but no request is
        lost and the bucket comes back healthy."""
        from repro.core.msc import msc_sequential

        jobs: List[Tuple[int, np.ndarray]] = []
        for s, rid in enumerate(tb.slot_req):
            if rid is not None:
                jobs.append((rid, tb.arrs[s]))
        for pr in sorted(tb.queues):
            q = tb.queues[pr]
            while q:
                rid, _, _ = q.popleft()
                parked = tb.parked.pop(rid, None)
                arr = (parked["arr"] if parked is not None
                       else self._pending.pop(rid)[0])
                jobs.append((rid, arr))
        tb.parked.clear()
        out: Dict[int, MSCResult] = {}
        for rid, arr in jobs:
            # _base_cfg: the oracle needs a concrete epilogue, and the
            # knob is collective-only anyway (ignored sequentially)
            res = msc_sequential(jnp.asarray(arr), self._base_cfg)
            host = jax.tree.map(np.asarray, res)
            out[rid] = host
            # the oracle path still feeds tier 1 (exact repeats of a
            # fallback-served tensor hit the cache); no iterates to
            # donate, so no tier-2 sketch entry
            key = self._req_key.pop(rid, None)
            self._req_sketch.pop(rid, None)
            self._warm_pending.pop(rid, None)
            if self.result_cache is not None and key is not None:
                self.result_cache.put(key, host, shape=arr.shape)
        tb.blocks, tb.carries = self._plan.init_state(tb.bucket, self.slots,
                                                      self.dtype)
        tb.slot_req = [None] * self.slots
        tb.arrs = [None] * self.slots
        tb.dims = np.tile(np.int32(_FILLER_DIMS), (self.slots, 1))
        tb.fin = np.zeros(self.slots, bool)
        tb.dirty = np.ones(self.slots, bool)
        tb.warm_dirty = np.ones(self.slots, bool)
        tb.warm_meta = [None] * self.slots
        tb.resume_dirty = np.ones(self.slots, bool)
        tb.prio = np.zeros(self.slots, np.int32)
        tb.deadline = np.full(self.slots, -1, np.int64)
        tb.progress = np.zeros(self.slots, np.int64)
        tb.retries = 0
        tb.retry_at = 0.0
        self._recovering.discard(tb.bucket)
        self._bump(fallback_requests=len(out))
        return out

    # ---- checkpoint / restore (DESIGN.md §7.8) ------------------------
    def checkpoint(self) -> Optional[str]:
        """Snapshot the whole engine (every bucket's slot table, queue,
        stats) to `checkpoint_dir` keyed by the global chunk clock.
        Atomic: a crash mid-write never clobbers the previous step."""
        if self.checkpoint_dir is None:
            return None
        if self._faults is not None:
            self._faults.before("checkpoint")
        leaves, meta = self._export()
        path = save_checkpoint(self.checkpoint_dir, self._total_chunks,
                               leaves, extra=meta)
        gc_checkpoints(self.checkpoint_dir, self.keep_checkpoints)
        self._chunks_since_ckpt = 0
        self._bump(checkpoints_written=1)
        return path

    def _export(self) -> Tuple[List[np.ndarray], Dict]:
        """Flat leaf list + JSON metadata of the full engine state.

        Leaves are CANONICAL host arrays — carries trimmed to true bucket
        dims and collapsed to one replica column (schedule.export_carry),
        device blocks omitted entirely (they are a pure function of the
        stashed admitted tensors, so restore rebuilds them byte-identical
        on whatever mesh it runs under).  That is what makes the
        checkpoint mesh-independent.

        Result-cache bookkeeping (_req_key/_req_sketch/_warm_pending) is
        deliberately NOT checkpointed: a restored engine re-solves its
        in-flight requests correctly either way, it just skips their
        cache insertion / warm accounting — the cache persists itself
        separately (MSCResultCache.persist)."""
        leaves: List[np.ndarray] = []
        buckets_meta = []
        for bucket in sorted(self._tables):
            tb = self._tables[bucket]
            for host in self._plan.export_carries(bucket, tb.carries):
                leaves.extend([host.v, host.lam, host.resid,
                               host.iters, host.done])
            leaves.extend(self._export_sched_leaves(tb))
            buckets_meta.append(self._bucket_meta(tb))
        return leaves, self._export_meta(buckets_meta)

    def _export_sched_leaves(self, tb: _SlotTable) -> List[np.ndarray]:
        """The host-side bookkeeping leaves of one bucket, in the §7.12
        checkpoint order: dims, fin, slot rids, per-slot scheduler state
        (priority/deadline/progress), the flattened per-class queue as
        (N, 4) rows (priority, rid, submit_tick, deadline), live
        tensors, queued tensors (parked requests' from their parked
        copy), then each parked request's exported carries (v, λ, resid
        per mode — iters/done ride the metadata)."""
        queued = tb.queued()
        leaves = [tb.dims.astype(np.int32),
                  np.asarray(tb.fin, np.bool_),
                  np.asarray([-1 if r is None else r
                              for r in tb.slot_req], np.int64),
                  tb.prio.astype(np.int64),
                  tb.deadline.astype(np.int64),
                  tb.progress.astype(np.int64),
                  np.asarray(queued, np.int64).reshape(-1, 4)]
        leaves += [tb.arrs[s] for s, r in enumerate(tb.slot_req)
                   if r is not None]
        leaves += [tb.parked[rid]["arr"] if rid in tb.parked
                   else self._pending[rid][0] for _, rid, _, _ in queued]
        for _, rid, _, _ in queued:
            if rid in tb.parked:
                for host in tb.parked[rid]["carries"]:
                    leaves += [np.asarray(host.v), np.asarray(host.lam),
                               np.asarray(host.resid)]
        return leaves

    def _bucket_meta(self, tb: _SlotTable) -> Dict:
        live = [s for s, r in enumerate(tb.slot_req) if r is not None]
        parked_meta = []
        for _, rid, _, _ in tb.queued():
            p = tb.parked.get(rid)
            if p is not None:
                parked_meta.append({
                    "rid": int(rid), "progress": int(p["progress"]),
                    "iters": [int(h.iters) for h in p["carries"]],
                    "done": [bool(h.done) for h in p["carries"]],
                    "warm_meta": (None if p["warm_meta"] is None
                                  else [int(x) for x in p["warm_meta"]]),
                })
        return {"bucket": list(tb.bucket), "chunk": tb.chunk,
                "live_slots": live, "parked": parked_meta}

    def _export_meta(self, buckets_meta, **over) -> Dict:
        meta = {
            "format": 1,
            "mesh": [[a, int(s)] for a, s in self.mesh.shape.items()],
            "slots": self.slots,
            "dtype": str(self.dtype),
            "cfg": dataclasses.asdict(self.cfg),
            "policy": {
                "bucket_quantum": self._quantum_base,
                "chunks_per_step": self._chunks_param,
                "autotune": self._autotune,
                "donate_buffers": self.donate_buffers,
                "refill_min_free": self.refill_min_free,
                "max_queue_chunks": self.max_queue_chunks,
                "placement": self.placement,
                "ckpt_every_chunks": self.ckpt_every_chunks,
                "keep_checkpoints": self.keep_checkpoints,
                "max_retries": self.max_retries,
                "retry_backoff_s": self.retry_backoff_s,
                "retry_backoff_max_s": self.retry_backoff_max_s,
                "preempt": self.preempt,
                "preempt_min_remaining_chunks":
                    self.preempt_min_remaining_chunks,
                "aging_chunks": self.aging_chunks,
                "slo_chunks": self.slo_chunks,
                "bucket_policy": self.bucket_policy,
            },
            "tick": self._tick,
            "next_rid": self._next_rid,
            "total_chunks": self._total_chunks,
            "stats": dataclasses.asdict(self._stats),
            "buckets": buckets_meta,
        }
        meta.update(over)
        return meta

    def _export_split(self):
        """(device_indexed, host_indexed, meta): the multi-host
        checkpoint payload (DESIGN.md §7.9).

        Same flat leaf order as `_export`, but the 15 per-bucket carry
        leaves stay as their PADDED device-layout jax.Arrays — on a
        process-spanning mesh no process can materialize their global
        values, so each process writes its own addressable shards
        (store.write_process_shards) and the master commits the rest
        (`host_indexed`, fully host-side bookkeeping) whole.  The meta
        carries `carry_layout="device"` so `_import` knows to
        canonicalize (trim padding, collapse verdict columns) at
        restore, after which the checkpoint is exactly as
        mesh-independent as the format-1 export."""
        device: List[Tuple[int, jax.Array]] = []
        host: List[Tuple[int, np.ndarray]] = []
        i = 0
        buckets_meta = []
        for bucket in sorted(self._tables):
            tb = self._tables[bucket]
            for carry in tb.carries:
                for leaf in (carry.v, carry.lam, carry.resid,
                             carry.iters, carry.done):
                    device.append((i, leaf))
                    i += 1
            for leaf in self._export_sched_leaves(tb):
                host.append((i, leaf))
                i += 1
            buckets_meta.append(self._bucket_meta(tb))
        return device, host, self._export_meta(buckets_meta,
                                               carry_layout="device")

    @classmethod
    def restore(cls, directory: str, *, mesh: Optional[Mesh] = None,
                mesh_shape: Optional[Tuple[int, int]] = None,
                step: Optional[int] = None, verify: bool = True,
                fault_injector=None, checkpoint_dir: Optional[str] = None,
                **policy_overrides) -> "MSCContinuousEngine":
        """Rebuild an engine from the newest restorable checkpoint and
        resume mid-solve.

        Elastic: pass `mesh` (or `mesh_shape` for make_msc_mesh over the
        visible devices) to restore onto a DIFFERENT device count /
        factorization than the checkpoint was taken on — carries reshard
        via device_put under the new schedule's shardings, blocks are
        rebuilt from the stashed tensors, and only the restored buckets'
        executables recompile.  Steps whose leaves fail SHA verification
        are skipped with a warning (degrade-to-previous).  Keyword
        overrides replace checkpointed policy knobs (slots and cfg are
        structural and always come from the checkpoint)."""
        steps = ([int(step)] if step is not None
                 else restorable_steps(directory, verify_sha=False))
        leaves = meta = used = None
        for s in steps:
            try:
                leaves, meta = load_leaves(directory, s, verify=verify)
                used = s
                break
            except (IOError, OSError, ValueError) as e:
                warnings.warn(f"checkpoint step {s} failed restore ({e}); "
                              f"trying the previous step")
        if used is None:
            raise FileNotFoundError(
                f"no restorable engine checkpoint under {directory!r}")
        cfg = MSCConfig(**meta["cfg"])
        if mesh is None:
            from repro.launch.mesh import make_msc_mesh
            mesh = make_msc_mesh("flat", shape=mesh_shape)
        policy = dict(meta["policy"])
        policy.update(policy_overrides)
        eng = cls(mesh, cfg, slots=int(meta["slots"]),
                  dtype=jnp.dtype(meta["dtype"]),
                  checkpoint_dir=checkpoint_dir or directory,
                  fault_injector=fault_injector, **policy)
        eng._import(leaves, meta)
        return eng

    def _import(self, leaves: List[np.ndarray], meta: Dict):
        """Rebuild every slot table from an _export leaf list, under the
        CURRENT mesh (import_carry re-pads + device_puts each carry leaf
        with this engine's shardings; rebuild_blocks unfolds the
        stashed tensors into the bytes the admission path's refill
        wrote)."""
        from repro.core.msc import MODE_PERMS

        # multi-host (format 2) checkpoints store the carries in PADDED
        # device layout (reassembled from per-process shards); trim each
        # mode's slice dim to the true bucket size and collapse the
        # replicated per-request verdict columns to the canonical copy —
        # after which the import path is identical to format 1 (and just
        # as mesh-elastic)
        device_layout = meta.get("carry_layout") == "device"
        it = iter(leaves)
        for bmeta in meta["buckets"]:
            bucket = tuple(int(x) for x in bmeta["bucket"])
            host_carries = []
            for j in range(3):
                v, lam, resid, iters, done = (next(it) for _ in range(5))
                if device_layout:
                    m = bucket[MODE_PERMS[j][0]]
                    v, lam, resid = v[:, :m], lam[:, :m], resid[:, :m]
                    iters, done = iters[:, 0], done[:, 0]
                host_carries.append(SolveState(v=v, lam=lam, resid=resid,
                                               iters=iters, done=done))
            dims = np.asarray(next(it), np.int32)
            fin = np.asarray(next(it), bool)
            slot_rids = np.asarray(next(it), np.int64)
            # scheduler-era (§7.12) checkpoints carry per-slot
            # priority/deadline/progress, an (N, 4) per-class queue,
            # and parked (preempted) requests; pre-§7.12 ones have the
            # (N, 2) FIFO — import as class 0, no deadline
            sched = "tick" in meta
            if sched:
                prio = np.asarray(next(it), np.int64).astype(np.int32)
                deadline = np.asarray(next(it), np.int64)
                progress = np.asarray(next(it), np.int64)
                queue = np.asarray(next(it), np.int64).reshape(-1, 4)
            else:
                q2 = np.asarray(next(it), np.int64).reshape(-1, 2)
                queue = np.concatenate(
                    [np.zeros((len(q2), 1), np.int64), q2,
                     np.full((len(q2), 1), -1, np.int64)], axis=1)
            arrs: List[Optional[np.ndarray]] = [None] * self.slots
            for s in bmeta["live_slots"]:
                arrs[s] = np.asarray(next(it), self.dtype)
            carries = self._plan.import_carries(bucket, host_carries)
            blocks = self._plan.rebuild_blocks(bucket, self.slots,
                                               self.dtype, arrs)
            tb = _SlotTable(bucket, blocks, carries, self.slots,
                            self.dtype,
                            self._plan.mode_shapes(bucket, self.slots))
            tb.zero_stage = self._plan.zero_stage(bucket, self.slots,
                                                  self.dtype)
            tb.zero_warm = self._plan.zero_warm(bucket, self.slots)
            tb.zero_resume = self._plan.zero_resume(bucket, self.slots)
            tb.slot_req = [None if r < 0 else int(r) for r in slot_rids]
            tb.arrs = arrs
            tb.dims = dims
            tb.fin = fin
            tb.chunk = int(bmeta["chunk"])
            if sched:
                tb.prio = prio
                tb.deadline = deadline
                tb.progress = progress
            parked_meta = {int(pm["rid"]): pm
                           for pm in bmeta.get("parked", [])}
            parked_arrs: Dict[int, np.ndarray] = {}
            for pr, rid, submitted, dl in queue:
                tb.queue_for(int(pr)).append(
                    (int(rid), int(submitted), int(dl)))
                a = np.asarray(next(it), self.dtype)
                if int(rid) in parked_meta:
                    parked_arrs[int(rid)] = a
                else:
                    self._pending[int(rid)] = (a, bucket)
            for pr, rid, _, dl in queue:
                pm = parked_meta.get(int(rid))
                if pm is None:
                    continue
                carr = []
                for j in range(3):
                    v, lam, resid = (np.asarray(next(it))
                                     for _ in range(3))
                    carr.append(SolveState(
                        v=v, lam=lam, resid=resid,
                        iters=int(pm["iters"][j]),
                        done=bool(pm["done"][j])))
                tb.parked[int(rid)] = {
                    "arr": parked_arrs[int(rid)], "carries": carr,
                    "priority": int(pr), "deadline": int(dl),
                    "warm_meta": (None if pm["warm_meta"] is None
                                  else tuple(pm["warm_meta"])),
                    "progress": int(pm["progress"]),
                }
            self._tables[bucket] = tb
        self._next_rid = int(meta["next_rid"])
        # a checkpoint of an older engine may carry counters this one
        # dropped
        known = {f.name for f in dataclasses.fields(ServeStats)}
        self._stats = ServeStats(**{k: v for k, v in meta["stats"].items()
                                    if k in known})
        self._total_chunks = int(meta["total_chunks"])
        self._tick = int(meta.get("tick", 0))
        self._chunks_since_ckpt = 0
        self._bump(restores=1)
