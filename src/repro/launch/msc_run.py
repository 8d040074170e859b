"""MSC driver (CLI) — the paper's end-to-end workload.

Generates the paper's planted rank-1 tensor (§IV), runs MSC (sequential
reference or the shard_map-parallel version, flat or grouped schedule),
and reports cluster quality (recovery rate / similarity index, Eq. 6)
plus wall time — i.e. paper Fig. 4 for one (γ, ε) point.

Examples:
  PYTHONPATH=src python -m repro.launch.msc_run --m 60 --gamma 60
  PYTHONPATH=src python -m repro.launch.msc_run --m 60 --gamma 60 \
      --schedule sequential --epsilon 1e-5     # the "ε too large" regime
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import (MSCConfig, PlantedSpec, make_planted_tensor,
                        msc_sequential, msc_similarity_matrices,
                        planted_masks, recovery_rate, similarity_index)
from repro.core.parallel import build_msc_parallel, make_msc_mesh
from repro.launch.compile_cache import enable_compile_cache


def _run_batched(mesh, cfg, spec, args) -> int:
    """--batch B: serve B independent planted requests in one dispatch
    (MSCServeEngine, DESIGN.md §7.6) and report per-request quality plus
    batched-vs-looped warm throughput."""
    import numpy as np

    from repro.serving import MSCServeEngine

    tensors = [make_planted_tensor(jax.random.PRNGKey(args.seed + i), spec)
               for i in range(args.batch)]
    true_masks = planted_masks(spec)
    engine = MSCServeEngine(mesh, cfg, max_batch=args.batch)
    t0 = time.time()
    results = engine.run(tensors)
    cold = time.time() - t0
    t0 = time.time()
    engine.run(tensors)
    warm = time.time() - t0
    recs = [float(recovery_rate(true_masks, [r[j].mask for j in range(3)]))
            for r in results]
    sweeps = [[int(r[j].power_iters_run) for j in range(3)] for r in results]
    for i, (rec, sw) in enumerate(zip(recs, sweeps)):
        print(f"  req {i}: rec={rec:.3f} sweeps={sw}")
    loop = MSCServeEngine(mesh, cfg, max_batch=1)
    loop.run(tensors)
    t0 = time.time()
    loop.run(tensors)
    loop_warm = time.time() - t0
    print(f"mean rec={np.mean(recs):.3f} B={args.batch} "
          f"cold={cold:.2f}s warm={warm:.2f}s "
          f"looped-warm={loop_warm:.2f}s speedup={loop_warm / warm:.2f}x "
          f"({engine.stats.compiles} executables compiled)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=60, help="cube tensor size")
    ap.add_argument("--gamma", type=float, default=None,
                    help="signal weight (default: m, as in paper Fig. 6)")
    ap.add_argument("--epsilon", type=float, default=None,
                    help="similarity threshold (default: Thm II.1-valid)")
    ap.add_argument("--schedule", default="flat",
                    choices=("sequential", "flat", "grouped"))
    ap.add_argument("--mesh-shape", default=None,
                    help="explicit mesh factorization, e.g. '4,2' = "
                         "(slice=4, inner=2): the inner axis shards the "
                         "within-slice rows so per-device memory is "
                         "O(m*r*c/(p*q)) (DESIGN.md §7.5); grouped "
                         "takes 'slice,inner' per mode group")
    ap.add_argument("--relayout", default="gspmd",
                    choices=("gspmd", "collective"),
                    help="flat-schedule mode relayout (§Perf msc it 2)")
    ap.add_argument("--epilogue", default="allgather",
                    choices=("allgather", "ring"),
                    help="similarity epilogue: blocking all_gather of V "
                         "vs ppermute-streamed ring (DESIGN.md §7.4)")
    ap.add_argument("--power-iters", type=int, default=60,
                    help="power-iteration sweep cap")
    ap.add_argument("--power-tol", type=float, default=1e-2,
                    help="adaptive convergence tolerance (DESIGN.md §7.3); "
                         "0 = fixed trip count")
    ap.add_argument("--precision", default="fp32",
                    choices=("fp32", "bf16_fp32"),
                    help="eigensolve operand precision policy")
    ap.add_argument("--gram", action="store_true",
                    help="paper-faithful explicit covariance (default: "
                         "matrix-free, beyond-paper)")
    ap.add_argument("--kernels", action="store_true",
                    help="route hot spots through the Pallas kernels")
    ap.add_argument("--batch", type=int, default=0,
                    help="serve this many independent planted requests "
                         "through MSCServeEngine in one batched dispatch "
                         "instead of one tensor (DESIGN.md §7.6); "
                         "parallel schedules only")
    ap.add_argument("--repeats", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    m = args.m
    gamma = args.gamma if args.gamma is not None else float(m)
    l = max(1, m // 10)
    # Theorem II.1: sqrt(eps) <= 1/(m-l)
    eps = args.epsilon if args.epsilon is not None else 0.5 / (m - l) ** 2
    spec = PlantedSpec.paper(m, gamma)
    cfg = MSCConfig(epsilon=eps, power_iters=args.power_iters,
                    power_tol=args.power_tol, precision=args.precision,
                    matrix_free=not args.gram, epilogue=args.epilogue,
                    max_extraction_iters=m, use_kernels=args.kernels)

    print(f"MSC m={m}^3 gamma={gamma} eps={eps:.2e} l={l} "
          f"schedule={args.schedule} matrix_free={not args.gram} "
          f"power_tol={args.power_tol} precision={args.precision} "
          f"epilogue={args.epilogue} devices={len(jax.devices())}")

    if args.schedule == "sequential":
        if args.batch:
            raise SystemExit("--batch needs a parallel schedule (the "
                             "serving engine compiles the flat schedule)")
        run = lambda t: msc_sequential(t, cfg)  # noqa: E731
    else:
        shape = (tuple(int(s) for s in args.mesh_shape.split(","))
                 if args.mesh_shape else None)
        mesh = make_msc_mesh(args.schedule, shape=shape)
        print(f"mesh: {dict(mesh.shape)}")
        if args.batch:
            return _run_batched(mesh, cfg, spec, args)
        kw = ({"relayout": args.relayout} if args.schedule == "flat" else {})
        run = build_msc_parallel(mesh, cfg, schedule=args.schedule, **kw)

    recs, sims, times = [], [], []
    for r in range(args.repeats):
        key = jax.random.PRNGKey(args.seed + r)
        tensor = make_planted_tensor(key, spec)
        true_masks = planted_masks(spec)
        t0 = time.time()
        result = jax.block_until_ready(run(tensor))
        times.append(time.time() - t0)
        pred = [mr.mask for mr in result.modes]
        rec = float(recovery_rate(true_masks, pred))
        c_mats = msc_similarity_matrices(tensor, cfg)
        sim = float(similarity_index(c_mats, pred))
        recs.append(rec)
        sims.append(sim)
        sweeps = [mr.power_iters_run for mr in result.modes]
        sweeps_s = ("" if any(s is None for s in sweeps)
                    else f" sweeps={[int(s) for s in sweeps]}")
        print(f"  run {r}: rec={rec:.3f} sim={sim:.3f} "
              f"sizes={[int(mr.size) for mr in result.modes]} "
              f"t={times[-1]:.2f}s{sweeps_s}")

    import numpy as np

    print(f"mean rec={np.mean(recs):.3f} sim={np.mean(sims):.3f} "
          f"t={np.mean(times):.2f}s (first run includes compile)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
