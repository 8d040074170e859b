"""Batched MSC serving driver (CLI) — the DESIGN.md §7.6/§7.7 workloads.

Generates a stream of independent planted-tensor MSC requests with
mixed shapes, serves it through `MSCServeEngine` (shape buckets,
compiled-executable cache, fixed-size microbatches), and reports the
bucket/cache behavior plus batched-vs-looped throughput — i.e. the
DBSCAN-MSC / MCAM many-request regime end to end.

With `--continuous` the same stream is ALSO driven through the
continuous-batching `MSCContinuousEngine` as a streaming arrival
simulation: requests arrive at Poisson times (in gate-chunk ticks,
`--arrival-rate` per tick), every `--slow-every`-th request is a
near-noise slow converger (the skewed mix static lockstep handles
worst), and the decode loop's occupancy / queue-wait / eviction
counters are reported next to the static engine's time on the same
request set.

Examples:
  PYTHONPATH=src python -m repro.launch.msc_serve
  PYTHONPATH=src python -m repro.launch.msc_serve \\
      --sizes 16,21,24,33 --requests 12 --max-batch 4 --epilogue ring
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
      python -m repro.launch.msc_serve --mesh-shape 4,2 \\
      --continuous --arrival-rate 2 --slow-every 6
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
      python -m repro.launch.msc_serve --continuous --autotune \\
      --epilogue auto --chunks-per-step auto   # §7.11 auto-config
  PYTHONPATH=src python -m repro.launch.msc_serve --continuous \\
      --priority-mix 0:0.5,1:1.5 --slo-chunks 32 \\
      --slow-every 8                           # §7.12 SLO scheduler
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.core import (MSCConfig, PlantedSpec, make_planted_tensor,
                        make_msc_mesh, planted_masks, recovery_rate)
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import MSCContinuousEngine, MSCServeEngine


def build_request_stream(sizes, n_requests: int, seed: int,
                         slow_every: int = 0, gamma_slow: float = 2.0):
    """n_requests planted cubes cycling through `sizes` (mixed buckets);
    with slow_every > 0, every slow_every-th request is a near-noise
    slow converger (the §7.7 skewed-convergence mix)."""
    specs, tensors = [], []
    for i in range(n_requests):
        m = sizes[i % len(sizes)]
        gamma = gamma_slow if slow_every and i % slow_every == 0 \
            else float(max(m, 40))
        specs.append(PlantedSpec.paper(m, gamma=gamma))
        tensors.append(make_planted_tensor(jax.random.PRNGKey(seed + i),
                                           specs[-1]))
    return specs, tensors


def simulate_continuous(engine: MSCContinuousEngine, tensors, *,
                        arrival_rate: float, seed: int,
                        priority_rates=None, deadline_chunks=None):
    """Drive the decode loop under Poisson arrivals.

    Inter-arrival gaps are Exponential(1/arrival_rate) in units of
    scheduler ticks; each tick submits everything that has arrived,
    then advances the scheduler one tick.  With `priority_rates`
    ({class: arrivals/tick}, DESIGN.md §7.12) each request draws its
    class with probability proportional to the class rates and the
    total arrival rate is their sum (overriding `arrival_rate`);
    `deadline_chunks` rides through to submit().  Submits the engine
    sheds (LoadShedError — SLO admission control) are dropped and
    counted.  Returns (results dict, ticks, wall seconds, shed count).
    """
    import numpy as np

    from repro.serving.faults import LoadShedError

    rng = np.random.RandomState(seed)
    if priority_rates:
        classes = sorted(priority_rates)
        rates = np.asarray([priority_rates[c] for c in classes], float)
        arrival_rate = float(rates.sum())
        prio = [classes[i] for i in
                rng.choice(len(classes), size=len(tensors),
                           p=rates / rates.sum())]
    else:
        prio = [0] * len(tensors)
    arrivals = np.cumsum(rng.exponential(1.0 / max(arrival_rate, 1e-9),
                                         len(tensors)))
    results, rid_of = {}, {}
    tick, nxt, shed = 0, 0, 0
    t0 = time.time()
    while nxt < len(tensors) or engine.has_work():
        while nxt < len(tensors) and arrivals[nxt] <= tick:
            try:
                rid_of[engine.submit(tensors[nxt], priority=prio[nxt],
                                     deadline_chunks=deadline_chunks)] = nxt
            except LoadShedError:
                shed += 1
            nxt += 1
        if engine.has_work():
            for rid, res in engine.step().items():
                results[rid_of[rid]] = res
        tick += 1
    return results, tick, time.time() - t0, shed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="16,21,33",
                    help="comma-separated cube sizes the stream cycles "
                         "through (three values = a 3-bucket stream)")
    ap.add_argument("--requests", type=int, default=9)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="microbatch size B (one executable per bucket)")
    ap.add_argument("--bucket-quantum", type=int, default=8,
                    help="request dims round up to multiples of this")
    ap.add_argument("--mesh-shape", default=None,
                    help="flat-mesh factorization, e.g. '4,2' (DESIGN.md "
                         "§7.5)")
    ap.add_argument("--epilogue", default="allgather",
                    choices=("allgather", "ring", "auto"),
                    help="'auto' resolves per bucket from the roofline "
                         "comm model (DESIGN.md §7.11)")
    ap.add_argument("--precision", default="fp32",
                    choices=("fp32", "bf16_fp32"))
    ap.add_argument("--power-tol", type=float, default=1e-2)
    ap.add_argument("--no-loop-compare", action="store_true",
                    help="skip the B=1 looped-baseline timing")
    ap.add_argument("--continuous", action="store_true",
                    help="also stream the requests through the "
                         "continuous-batching engine (DESIGN.md §7.7)")
    ap.add_argument("--slots", type=int, default=None,
                    help="continuous slot-table size (default: max-batch)")
    ap.add_argument("--chunks-per-step", default="1",
                    help="gate chunks fused per dispatch, or 'auto' "
                         "(roofline pick from the measured sweep "
                         "histogram, DESIGN.md §7.11)")
    ap.add_argument("--autotune", action="store_true",
                    help="continuous mode: search kernel block shapes "
                         "and validate roofline config proposals per "
                         "bucket at warmup; winners persist under "
                         "<--checkpoint-dir>/autotune")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable slot-table buffer donation on the "
                         "hot executables")
    ap.add_argument("--arrival-rate", type=float, default=2.0,
                    help="mean Poisson arrivals per scheduler tick "
                         "(continuous mode)")
    ap.add_argument("--priority-mix", default=None,
                    help="per-class Poisson arrival rates, e.g. "
                         "'0:0.5,1:1.5' (class 0 most urgent); overrides "
                         "--arrival-rate with the sum (DESIGN.md §7.12)")
    ap.add_argument("--slo-chunks", type=int, default=None,
                    help="shed submits whose predicted queue wait "
                         "exceeds this many chunks (admission control)")
    ap.add_argument("--deadline-chunks", type=int, default=None,
                    help="per-request deadline budget in scheduler "
                         "ticks (advisory; misses are counted)")
    ap.add_argument("--no-preempt", action="store_true",
                    help="disable preempt-to-host (FIFO-within-class "
                         "residency)")
    ap.add_argument("--bucket-policy", default="weighted",
                    choices=("weighted", "all"),
                    help="cross-bucket device-time sharing: 'weighted' "
                         "rotates one bucket per tick by queue-depth "
                         "credit, 'all' steps every bucket")
    ap.add_argument("--slow-every", type=int, default=0,
                    help="every Nth request is a near-noise slow "
                         "converger (0 = homogeneous stream)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="continuous mode: checkpoint engine state here "
                         "every --ckpt-every gate chunks (DESIGN.md §7.8)")
    ap.add_argument("--ckpt-every", type=int, default=8,
                    help="gate chunks between checkpoints")
    ap.add_argument("--restore", default=None, metavar="DIR",
                    help="continuous mode: restore the engine from the "
                         "newest checkpoint under DIR onto the live "
                         "device set (elastic), drain its in-flight "
                         "requests, then serve the stream (implies "
                         "--continuous)")
    ap.add_argument("--cache-dir", default=None, metavar="DIR",
                    help="continuous mode: attach a content-addressed "
                         "result cache persisted under DIR (DESIGN.md "
                         "§7.10); exact repeats are answered without "
                         "touching the device")
    ap.add_argument("--cache-max-bytes", type=int, default=256 << 20,
                    help="result-cache LRU payload budget")
    ap.add_argument("--warm-start", action="store_true",
                    help="continuous mode: attach the result cache "
                         "(in-memory unless --cache-dir) and seed "
                         "near-duplicate admissions from cached "
                         "eigenvector iterates (tier 2)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.restore:
        args.continuous = True

    sizes = [int(s) for s in args.sizes.split(",")]
    shape = (tuple(int(s) for s in args.mesh_shape.split(","))
             if args.mesh_shape else None)
    mesh = make_msc_mesh("flat", shape=shape)
    cfg = MSCConfig(epsilon=3e-4, power_tol=args.power_tol,
                    precision=args.precision, epilogue=args.epilogue)
    print(f"MSC serve: {args.requests} requests over sizes {sizes}, "
          f"mesh {dict(mesh.shape)}, B={args.max_batch}, "
          f"epilogue={args.epilogue} precision={args.precision}")

    specs, tensors = build_request_stream(sizes, args.requests, args.seed,
                                          slow_every=args.slow_every)
    engine = MSCServeEngine(mesh, cfg, max_batch=args.max_batch,
                            bucket_quantum=args.bucket_quantum)
    buckets = sorted({engine.bucket_of(t.shape) for t in tensors})
    print(f"buckets: {buckets}")

    t0 = time.time()
    results = engine.run(tensors)   # cold: compiles one exec per bucket
    cold_s = time.time() - t0
    t0 = time.time()
    engine.run(tensors)             # warm: pure cache hits
    warm_s = time.time() - t0

    for i, (spec, res) in enumerate(zip(specs, results)):
        rec = float(recovery_rate(planted_masks(spec),
                                  [res[j].mask for j in range(3)]))
        print(f"  req {i}: shape={spec.shape} rec={rec:.3f} "
              f"sizes={[int(res[j].mask.sum()) for j in range(3)]} "
              f"sweeps={[int(res[j].power_iters_run) for j in range(3)]}")

    s = engine.stats
    print(f"stats: {s.dispatches} dispatches, {s.compiles} compiles, "
          f"{s.exec_cache_hits} exec cache hits, "
          f"{s.filler_slots} filler slots")
    print(f"cold {cold_s:.2f}s (incl. {s.compiles} compiles), "
          f"warm {warm_s:.2f}s "
          f"({args.requests / warm_s:.1f} req/s)")

    if not args.no_loop_compare:
        loop = MSCServeEngine(mesh, cfg, max_batch=1,
                              bucket_quantum=args.bucket_quantum)
        loop.run(tensors)  # warm its caches
        t0 = time.time()
        loop.run(tensors)
        loop_s = time.time() - t0
        print(f"looped (B=1) warm {loop_s:.2f}s → batched speedup "
              f"{loop_s / warm_s:.2f}x")

    if args.continuous:
        print(f"\ncontinuous decode loop: Poisson arrivals "
              f"{args.arrival_rate}/tick, slow-every={args.slow_every}")
        rcache = None
        if args.cache_dir or args.warm_start:
            from repro.serving import MSCResultCache

            rcache = MSCResultCache(max_bytes=args.cache_max_bytes,
                                    persist_dir=args.cache_dir)
            if len(rcache):
                print(f"result cache: reloaded {len(rcache)} entr"
                      f"{'y' if len(rcache) == 1 else 'ies'} "
                      f"({rcache.nbytes >> 10} KiB) from {args.cache_dir}")
        if args.restore:
            from repro.launch.elastic import restore_msc_engine

            ceng = restore_msc_engine(
                args.restore,
                checkpoint_dir=args.checkpoint_dir or args.restore,
                ckpt_every_chunks=args.ckpt_every,
                result_cache=rcache, warm_start=args.warm_start)
            drained = {}
            while ceng.has_work():
                drained.update(ceng.step())
            print(f"restored from {args.restore} onto mesh "
                  f"{dict(ceng.mesh.shape)}; drained {len(drained)} "
                  f"in-flight request(s)")
        else:
            chunks = (args.chunks_per_step if args.chunks_per_step == "auto"
                      else int(args.chunks_per_step))
            ceng = MSCContinuousEngine(
                mesh, cfg, slots=args.slots or args.max_batch,
                bucket_quantum=args.bucket_quantum,
                chunks_per_step=chunks,
                checkpoint_dir=args.checkpoint_dir,
                ckpt_every_chunks=args.ckpt_every,
                result_cache=rcache, warm_start=args.warm_start,
                autotune=args.autotune,
                donate_buffers=not args.no_donate,
                preempt=not args.no_preempt,
                slo_chunks=args.slo_chunks,
                bucket_policy=args.bucket_policy)
        probes = {}  # warm every bucket's executables off the clock
        for t in tensors:
            probes.setdefault(ceng.bucket_of(t.shape), t)
        ceng.run(list(probes.values()))
        base = ceng.stats
        mix = None
        if args.priority_mix:
            mix = {int(k): float(v) for k, v in
                   (kv.split(":") for kv in args.priority_mix.split(","))}
            print(f"  priority mix: {mix} arrivals/tick per class")
        results, ticks, stream_s, shed = simulate_continuous(
            ceng, tensors, arrival_rate=args.arrival_rate, seed=args.seed,
            priority_rates=mix, deadline_chunks=args.deadline_chunks)
        cs = ceng.stats.delta(base)  # the stream only, not the warmup
        print(f"streamed {len(results)} results over {ticks} ticks in "
              f"{stream_s:.2f}s ({len(results) / stream_s:.1f} req/s)")
        print(f"  occupancy {cs.occupancy:.2f} "
              f"({cs.busy_slot_chunks}/{cs.slot_chunks} slot-chunks), "
              f"{cs.evictions} evictions, {cs.refills} refills, "
              f"mean queue wait "
              f"{cs.queue_wait_chunks / max(cs.requests, 1):.2f} chunks")
        ss = ceng.stats  # scheduler counters (cumulative)
        print(f"  scheduler: {ss.preemptions} preemptions, "
              f"{ss.resumes} resumes, {ss.deadline_misses} deadline "
              f"misses, {ss.slo_sheds} SLO-shed ({shed} dropped), "
              f"{ss.idle_bucket_ticks} idle-bucket ticks")
        fs = ceng.stats  # cumulative — restores predate the base snapshot
        print(f"  fault tolerance: {fs.checkpoints_written} checkpoints, "
              f"{fs.restores} restores, {fs.retries} retries, "
              f"{fs.shed_requests} shed, "
              f"{fs.fallback_requests} fallback-served, "
              f"{fs.heartbeats_missed} heartbeats missed, "
              f"{fs.host_losses} host losses, {fs.reinits} reinits, "
              f"{fs.shard_files_written} shard files, "
              f"{fs.cache_hits} cache hits / {fs.cache_misses} misses, "
              f"{fs.warm_starts} warm starts "
              f"({fs.warm_sweeps_saved} sweeps saved)")
        if args.autotune:
            print(f"  autotune: {fs.autotune_searches} searches, "
                  f"{fs.autotune_cache_hits} cache hits")
        if rcache is not None and args.cache_dir:
            rcache.persist()
            print(f"  result cache persisted: {len(rcache)} entries, "
                  f"{rcache.nbytes >> 10} KiB → {args.cache_dir}")
        for i in (0, len(tensors) - 1):
            sw = [int(results[i][j].power_iters_run) for j in range(3)]
            print(f"  req {i}: sweeps={sw}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
