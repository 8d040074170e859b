"""Batched MSC serving example: a 3-bucket request stream end to end.

The DBSCAN-MSC / MCAM regime (PAPERS.md): many independent MSC requests
of assorted sizes.  `MSCServeEngine` rounds each request's dims up to a
shape bucket, packs each bucket into fixed-size microbatches, and runs
every microbatch through ONE cached executable — so after the first
request of each bucket, serving performs zero retraces and zero
recompiles (DESIGN.md §7.6).

The second half streams the same buckets through the continuous-
batching `MSCContinuousEngine` (DESIGN.md §7.7) under Poisson arrivals
with mixed convergence difficulty — a few near-noise slow convergers
salted into fast high-γ requests — and prints the decode loop's
occupancy, eviction, and queue-wait counters from the new ServeStats
fields.

The final section fronts the continuous engine with the two-tier
content-addressed result cache (DESIGN.md §7.10) and replays a
repeat-heavy mix: exact repeats are answered without touching the
device (even from a different memory layout — the key is
content-addressed), and near-duplicates warm-start from the cached
eigenvector iterates, converging at their first gate probe.

  PYTHONPATH=src python examples/msc_serve.py
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/msc_serve.py --mesh-shape 4,2
"""
import argparse
import time

import jax

from repro.core import (MSCConfig, PlantedSpec, make_planted_tensor,
                        make_msc_mesh, planted_masks, recovery_rate)
from repro.launch.msc_serve import simulate_continuous
from repro.serving import MSCContinuousEngine, MSCServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--mesh-shape", default=None)
    ap.add_argument("--arrival-rate", type=float, default=1.5,
                    help="mean Poisson arrivals per scheduler tick in "
                         "the continuous-stream half")
    args = ap.parse_args()

    # a stream spanning three buckets (quantum 8 → 16³ / 24³ / 40³),
    # with non-cube stragglers landing in the cube buckets via padding
    specs = [
        PlantedSpec.paper(14, 70.0),
        PlantedSpec.paper(21, 70.0),
        PlantedSpec(shape=(21, 24, 18), cluster_sizes=(2, 3, 2), gamma=60.0),
        PlantedSpec.paper(33, 70.0),
        PlantedSpec.paper(16, 70.0),
        PlantedSpec.paper(24, 40.0),
        PlantedSpec(shape=(38, 33, 39), cluster_sizes=(4, 3, 4), gamma=70.0),
        PlantedSpec.paper(21, 90.0),
    ]
    tensors = [make_planted_tensor(jax.random.PRNGKey(i), s)
               for i, s in enumerate(specs)]

    mesh = make_msc_mesh("flat",
                         shape=(tuple(int(s) for s in
                                      args.mesh_shape.split(","))
                                if args.mesh_shape else None))
    cfg = MSCConfig(epsilon=3e-4)
    engine = MSCServeEngine(mesh, cfg, max_batch=args.max_batch)

    buckets = {}
    for t in tensors:
        buckets.setdefault(engine.bucket_of(t.shape), []).append(t.shape)
    print(f"mesh {dict(mesh.shape)}; {len(tensors)} requests → "
          f"{len(buckets)} buckets:")
    for b, shapes in sorted(buckets.items()):
        print(f"  {b}: {shapes}")

    t0 = time.time()
    results = engine.run(tensors)          # cold: one compile per bucket
    print(f"\ncold pass {time.time() - t0:.2f}s "
          f"({engine.stats.compiles} executables compiled)")
    t0 = time.time()
    results = engine.run(tensors)          # warm: zero compiles
    warm = time.time() - t0
    s = engine.stats
    print(f"warm pass {warm:.2f}s — {s.exec_cache_hits} exec cache hits, "
          f"{s.compiles} total compiles (none new), "
          f"{s.filler_slots} filler slots\n")

    for spec, res in zip(specs, results):
        rec = float(recovery_rate(planted_masks(spec),
                                  [res[j].mask for j in range(3)]))
        print(f"  {str(spec.shape):14s} rec={rec:.3f} "
              f"sweeps={[int(res[j].power_iters_run) for j in range(3)]}")

    # ---- continuous decode loop under Poisson arrivals ----------------
    # mixed difficulty: every 4th request is near-noise (γ=2, ~10-20x
    # the sweeps), the rest are well-separated — the skewed mix where
    # static microbatching parks 7 slots on the slowest request
    stream_specs = [PlantedSpec.paper((14, 21, 16, 24)[i % 4],
                                      2.0 if i % 4 == 0 else 120.0)
                    for i in range(12)]
    stream = [make_planted_tensor(jax.random.PRNGKey(100 + i), s)
              for i, s in enumerate(stream_specs)]
    ceng = MSCContinuousEngine(mesh, cfg.with_(power_tol=1e-2),
                               slots=args.max_batch)
    probes = {}
    for t in stream:
        probes.setdefault(ceng.bucket_of(t.shape), t)
    ceng.run(list(probes.values()))  # warm each bucket's two executables
    base = ceng.stats
    print(f"\ncontinuous stream: {len(stream)} requests, Poisson "
          f"{args.arrival_rate}/tick, slots={args.max_batch}")
    results, ticks, stream_s, _ = simulate_continuous(
        ceng, stream, arrival_rate=args.arrival_rate, seed=7)
    s = ceng.stats.delta(base)  # the stream only, not the warmup
    print(f"drained in {ticks} ticks / {stream_s:.2f}s "
          f"({len(results) / stream_s:.1f} req/s)")
    print(f"occupancy {s.occupancy:.2f} "
          f"({s.busy_slot_chunks}/{s.slot_chunks} slot-chunks), "
          f"{s.evictions} evictions over {s.refills} refills, "
          f"mean queue wait "
          f"{s.queue_wait_chunks / max(s.requests, 1):.2f} chunks")
    for i, spec in enumerate(stream_specs):
        sw = [int(results[i][j].power_iters_run) for j in range(3)]
        kind = "slow" if i % 4 == 0 else "fast"
        print(f"  req {i:2d} {str(spec.shape):14s} {kind} sweeps={sw}")

    # ---- mixed priorities + preempt-to-host (DESIGN.md §7.12) ---------
    # interactive (class 0) requests racing batch (class 1) near-noise
    # work: the SLO scheduler preempts a long-running batch slot to
    # host when an interactive request would otherwise queue, then
    # resumes it later through the same refill executable — masks and
    # sweep counts stay bit-identical to an uninterrupted run
    sched_specs = [PlantedSpec.paper(16, 2.0 if i % 3 == 0 else 150.0)
                   for i in range(9)]
    sched_stream = [make_planted_tensor(jax.random.PRNGKey(300 + i), s)
                    for i, s in enumerate(sched_specs)]
    seng = MSCContinuousEngine(mesh, cfg.with_(power_tol=1e-2),
                               slots=max(2, args.max_batch // 2),
                               preempt_min_remaining_chunks=1)
    seng.run(sched_stream[:3])   # warm executables + sweep histogram
    base = seng.stats
    print(f"\nmixed-priority stream: {len(sched_stream)} requests "
          f"(every 3rd near-noise → class 1, rest class 0)")
    got = {}
    rids = [seng.submit(t, priority=1 if i % 3 == 0 else 0,
                        deadline_chunks=64)
            for i, t in enumerate(sched_stream)]
    while seng.has_work():
        got.update(seng.step())
    s = seng.stats.delta(base)
    print(f"scheduler: {s.preemptions} preemptions, {s.resumes} resumes, "
          f"{s.deadline_misses} deadline misses; mean queue wait "
          f"{s.queue_wait_chunks / max(s.requests, 1):.2f} chunks")
    for i, rid in enumerate(rids):
        sw = [int(got[rid][j].power_iters_run) for j in range(3)]
        cls = 1 if i % 3 == 0 else 0
        print(f"  req {i:2d} class {cls} sweeps={sw}")

    # ---- result cache: repeats + near-duplicates (DESIGN.md §7.10) ----
    # the millions-of-users regime: a Zipf-ish stream where most arrivals
    # are exact repeats (tier-1: answered from the cache, zero device
    # work) or small perturbations of something already served (tier-2:
    # the admission seeds its eigensolver from the cached iterates and
    # converges at the first gate probe)
    import numpy as np

    from repro.serving import MSCResultCache

    rng = np.random.RandomState(42)
    # slow convergers (γ=2, near-noise): the requests worth caching
    pool = [np.asarray(make_planted_tensor(jax.random.PRNGKey(200 + i),
                                           PlantedSpec.paper(16, 2.0)),
                       np.float32) for i in range(3)]
    mix = []
    for i in range(9):
        base = pool[i % len(pool)]
        if i % 3 == 2:     # near-duplicate: ~0.3% relative perturbation
            noise = rng.standard_normal(base.shape).astype(np.float32)
            mix.append(("near", base + 0.003 * base.std() * noise))
        else:              # exact repeat (different memory layout, even)
            mix.append(("exact", np.asfortranarray(base)))

    cache = MSCResultCache(max_bytes=64 << 20)
    keng = MSCContinuousEngine(mesh, cfg.with_(power_tol=1e-2),
                               slots=args.max_batch, result_cache=cache,
                               warm_start=True)
    cold_results = keng.run(pool)  # cold: solves + seeds the cache
    base_stats = keng.stats
    t0 = time.time()
    mix_results = keng.run([t for _, t in mix])
    mix_s = time.time() - t0
    s = keng.stats.delta(base_stats)
    print(f"\nresult-cache mix: {len(mix)} requests in {mix_s:.2f}s — "
          f"{s.cache_hits} exact hits, {s.warm_starts} warm starts, "
          f"{s.cache_misses} misses ({s.dispatches} device dispatches)")
    print(f"  cache: {len(cache)} entries, {cache.nbytes >> 10} KiB, "
          f"{s.warm_sweeps_saved} sweeps saved by warm starts")
    for i, res in enumerate(cold_results):
        sw = [int(res[j].power_iters_run) for j in range(3)]
        print(f"  cold  sweeps={sw}")
    for (kind, _), res in zip(mix, mix_results):
        sw = [int(res[j].power_iters_run) for j in range(3)]
        print(f"  {kind:5s} sweeps={sw}")


if __name__ == "__main__":
    main()
