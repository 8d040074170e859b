#!/usr/bin/env python3
"""Smoke test of the continuous MSC engine on a TPU.

Drives `MSCContinuousEngine` through its user entry points (`submit` /
`step`, the serving CLI's arrival loop) on a stream of the paper's
planted cubes and checks what comes out.  Phases on one chip:

  engine     8 cubes, m = 200 and 400 alternating (two buckets), every
             4th near-noise (γ = 2), the rest at γ = 3m (a high gap).
             Both buckets are warmed, then the
             stream runs twice.  Every request must return with masks
             equal to `msc_sequential` run on the chip, planted recovery
             1.0 on the high-gap requests, no compile after the warm-up,
             and no retry, fallback or shed.
  kernels    the m = 400 requests again with `use_kernels=True`: masks
             equal the einsum path's, and the chunk-step executable
             holds a compiled Pallas kernel (`tpu_custom_call`).
  precision  one m = 200 request's d and λ under precision="fp32"
             against a float64 numpy run of the same sweeps: the error
             must stay within fp32 rounding.

With --four-chips it runs only the paper's parallel scheme across four
chips and its comparison: an m = 400 stream on the flat mesh as (4,) and
as (2, 2), each with the allgather and the ring epilogue, masks against
`msc_sequential`, and the slot table's bytes spread over the four chips.

One process; it starts none.  Where JAX finds no TPU it exits non-zero
and prints no result.  The last line of stdout is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
The wall times it prints are smoke timings, not benchmark numbers.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the four-chip host
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SIZES = (200, 400)          # the low end of the paper's m = 200…1400
N_REQUESTS = 8
SLOW_EVERY = 4              # every 4th request near-noise (γ = 2)
SLOTS = 4
SEED = 0
ARRIVAL_RATE = 2.0          # Poisson arrivals per scheduler tick
# fp32 rounding over at most 60 sweeps of a 400-wide contraction stays
# near 1e-5 relative; a bf16 pass (8 mantissa bits) lands near 1e-3
PRECISION_TOL = 1e-4


def log(*args):
    print(*args, flush=True)


def smoke_config():
    from repro.core import MSCConfig

    return MSCConfig(epsilon=3e-4, power_tol=1e-2)


def request_stream(sizes, n=N_REQUESTS, seed=SEED):
    """(specs, host tensors): planted cubes cycling through `sizes`,
    every SLOW_EVERY-th near-noise (γ = 2) and the rest at γ = 3m.

    A planted slice's singular value is γ/√l against a noise edge near
    2√m, so with l = m/10 the margin is γ/(0.63 m): γ = m (the serving
    CLI's stream) leaves 1.6x and recovers 0.68 of the m = 400 cluster,
    γ = 3m leaves 4.7x and recovers all of it in one gate chunk."""
    import jax

    from repro.core import PlantedSpec, make_planted_tensor

    specs, tensors = [], []
    for i in range(n):
        m = sizes[i % len(sizes)]
        specs.append(PlantedSpec.paper(
            m, 2.0 if i % SLOW_EVERY == 0 else 3.0 * m))
        tensors.append(np.asarray(make_planted_tensor(
            jax.random.PRNGKey(seed + i), specs[-1])))
    return specs, tensors


def _masks(result):
    return tuple(np.asarray(result[j].mask) for j in range(3))


def oracle_masks(tensors, cfg):
    """Masks of `msc_sequential` on the default device, per request."""
    import jax.numpy as jnp

    from repro.core import msc_sequential

    return [_masks(msc_sequential(jnp.asarray(t), cfg)) for t in tensors]


class CompileClock:
    """Backend compile seconds, summed from jax.monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def _counter_failures(stats, where):
    return [f"{where}: {name} = {getattr(stats, name)}"
            for name in ("retries", "fallback_requests", "shed_requests")
            if getattr(stats, name)]


def _mask_failures(got, want, where):
    return [f"{where}: request {i} masks differ from the oracle"
            for i, (g, w) in enumerate(zip(got, want))
            if not all(np.array_equal(a, b) for a, b in zip(g, w))]


def _stream(engine, tensors):
    """One pass of the serving CLI's arrival loop; results in order."""
    from repro.launch.msc_serve import simulate_continuous

    results, ticks, wall, shed = simulate_continuous(
        engine, tensors, arrival_rate=ARRIVAL_RATE, seed=SEED)
    return [results.get(i) for i in range(len(tensors))], ticks, wall, shed


def engine_phase(mesh, tensors, specs, oracle, *, slots=SLOTS,
                 clock=None):
    """Warm every bucket, stream the requests twice; returns (per-request
    masks of the first pass, failures)."""
    from repro.core import planted_masks, recovery_rate
    from repro.serving import MSCContinuousEngine

    engine = MSCContinuousEngine(mesh, smoke_config(), slots=slots)
    probes = {}
    for t in tensors:
        probes.setdefault(engine.bucket_of(t.shape), t)
    for bucket, t in sorted(probes.items()):
        c0 = clock.seconds if clock else 0.0
        t0 = time.perf_counter()
        engine.run([t])
        compile_s = (f"{clock.seconds - c0:.3f} s" if clock
                     else "not measured")
        log(f"  bucket {bucket}: compile {compile_s}, warm-up "
            f"{time.perf_counter() - t0:.3f} s (smoke timing)")
    failures = []
    masks = None
    for n_pass in (1, 2):
        before = engine.stats
        results, ticks, wall, shed = _stream(engine, tensors)
        delta = engine.stats.delta(before)
        log(f"  pass {n_pass}: {sum(r is not None for r in results)}/"
            f"{len(tensors)} returned over {ticks} ticks, "
            f"{delta.compiles} compiles, {wall:.3f} s (smoke timing)")
        if any(r is None for r in results) or shed:
            failures.append(f"engine pass {n_pass}: "
                            f"{sum(r is None for r in results)} requests "
                            f"did not return, {shed} shed")
            continue
        if delta.compiles:
            failures.append(f"engine pass {n_pass}: {delta.compiles} "
                            f"compiles after the warm-up")
        got = [_masks(r) for r in results]
        failures += _mask_failures(got, oracle, f"engine pass {n_pass}")
        if n_pass == 1:
            masks = got
            for i, (spec, res) in enumerate(zip(specs, results)):
                rec = float(recovery_rate(planted_masks(spec), got[i]))
                sweeps = [int(res[j].power_iters_run) for j in range(3)]
                log(f"  req {i}: m={spec.shape[0]} gamma={spec.gamma:g} "
                    f"sweeps={sweeps} recovery={rec:.3f}")
                if spec.gamma > 2.0 and rec != 1.0:
                    failures.append(f"engine: request {i} (high gap) "
                                    f"recovered {rec:.3f}")
    failures += _counter_failures(engine.stats, "engine")
    return masks, failures


def kernel_phase(mesh, tensors, einsum_masks, *, slots=SLOTS):
    """The same requests through the Pallas kernel path; returns
    (whether the chunk-step executable holds a compiled kernel,
    failures)."""
    from repro.serving import MSCContinuousEngine

    engine = MSCContinuousEngine(mesh, smoke_config().with_(use_kernels=True),
                                 slots=slots)
    t0 = time.perf_counter()
    results, ticks, wall, shed = _stream(engine, tensors)
    log(f"  {len(tensors)} requests over {ticks} ticks, "
        f"{engine.stats.compiles} compiles, "
        f"{time.perf_counter() - t0:.3f} s with compiles (smoke timing)")
    if any(r is None for r in results) or shed:
        return False, [f"kernels: {sum(r is None for r in results)} "
                       f"requests did not return, {shed} shed"]
    failures = _mask_failures([_masks(r) for r in results], einsum_masks,
                              "kernels (vs the einsum path)")
    failures += _counter_failures(engine.stats, "kernels")
    step_exec, _ = engine._executables(engine.bucket_of(tensors[0].shape))
    return "tpu_custom_call" in step_exec.as_text(), failures


def float64_mode(tensor, mode, sweeps):
    """d and λ of one mode from `sweeps` matrix-free power sweeps in
    float64 numpy: the solver's arithmetic (core/power_iter.py) without
    its rounding."""
    from repro.core.msc import MODE_PERMS

    t = np.transpose(np.asarray(tensor, np.float64), MODE_PERMS[mode])
    c = t.shape[2]
    v = 1.0 + 0.01 * np.sin(1.37 * np.arange(c) + 0.3)
    v = np.broadcast_to(v / np.linalg.norm(v), (t.shape[0], c))
    for _ in range(sweeps):
        w = np.matmul((t @ v[:, :, None])[:, :, 0][:, None, :], t)[:, 0]
        v = w / (np.linalg.norm(w, axis=1, keepdims=True) + 1e-30)
    tv = (t @ v[:, :, None])[:, :, 0]
    lam = np.sum(tv * tv, axis=1)
    rows = (lam / lam.max())[:, None] * v
    return np.abs(rows @ rows.T).sum(axis=1), lam


def precision_phase(tensor):
    """Largest relative error of d and λ under precision="fp32" against
    float64, over the three modes of one request."""
    import jax.numpy as jnp

    from repro.core import msc_sequential

    res = msc_sequential(jnp.asarray(tensor), smoke_config())
    err = {"d": 0.0, "lambda": 0.0}
    for j in range(3):
        d64, lam64 = float64_mode(tensor, j, int(res[j].power_iters_run))
        for name, got, want in (("d", res[j].d, d64),
                                ("lambda", res[j].lambdas, lam64)):
            rel = np.max(np.abs(np.asarray(got, np.float64) - want))
            err[name] = max(err[name], float(rel / np.max(np.abs(want))))
    return err


def four_chip_phase(devices, tensors, oracle, *, slots=SLOTS):
    """The stream on the flat mesh as (p,) and (p/2, 2) over `devices`,
    each with both epilogues; returns failures."""
    from repro.core import make_msc_mesh
    from repro.serving import MSCContinuousEngine

    n = len(devices)
    failures = []
    for shape in ((n,), (n // 2, 2)):
        mesh = make_msc_mesh("flat", devices=devices, shape=shape)
        for epilogue in ("allgather", "ring"):
            where = f"mesh {shape} {epilogue}"
            cfg = smoke_config().with_(epilogue=epilogue)
            engine = MSCContinuousEngine(mesh, cfg, slots=slots)
            rids = [engine.submit(t) for t in tensors]
            out = engine.step()         # the first refill admits a table
            bucket = engine.bucket_of(tensors[0].shape)
            failures += _spread_failures(
                devices, 3 * slots * int(np.prod(bucket)) * 4, where)
            while engine.has_work():
                out.update(engine.step())
            missing = [i for i, r in enumerate(rids) if r not in out]
            if missing:
                failures.append(f"{where}: requests {missing} did not "
                                f"return")
                continue
            got = [_masks(out[r]) for r in rids]
            bad = _mask_failures(got, oracle, where)
            log(f"  {where}: {len(got)} requests, "
                f"{'masks == oracle' if not bad else 'MASKS DIFFER'}, "
                f"{engine.stats.compiles} compiles")
            failures += bad + _counter_failures(engine.stats, where)
            del engine
            gc.collect()    # the next engine's table is measured alone
    return failures


def _spread_failures(devices, blocks, where):
    """Each device must hold at least its share of the slot table's
    `blocks` bytes (three fp32 unfoldings per slot), and none all of
    them: a table placed on devices[0] alone fails.  Backends without
    memory stats check nothing."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return []
    used = [s["bytes_in_use"] for s in stats]
    log(f"  {where}: blocks {blocks} B in all; bytes_in_use per device "
        f"{used}")
    if min(used) < blocks / len(devices) or max(used) >= blocks:
        return [f"{where}: slot table not spread over the devices "
                f"(bytes_in_use {used}, blocks {blocks})"]
    return []


def _tpu():
    """JAX's first device where it is a TPU, else None."""
    import jax

    dev = jax.devices()[0]
    return dev if dev.platform == "tpu" else None


def _phase(name, fn, failures):
    """Run one phase; an exception fails it but the next phases run."""
    log(f"[{name}]")
    t0 = time.perf_counter()
    try:
        out = fn()
    except Exception:   # noqa: BLE001 — phase boundary: report, go on
        traceback.print_exc()
        failures.append(f"{name}: raised (traceback on stderr)")
        return None
    finally:
        gc.collect()    # free the phase's device state before the next
    log(f"[{name}] {time.perf_counter() - t0:.1f} s (smoke timing)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip parallel path")
    args = ap.parse_args(argv)

    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke.py: no repro package under {src}; run it from "
              f"a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    dev = _tpu()
    if dev is None:
        print(f"chip_smoke.py: JAX found no TPU (device 0 is "
              f"{jax.devices()[0].platform!r}); this test runs on the "
              f"chip only", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device_kind: {dev.device_kind}, {len(jax.devices())} device(s), "
        f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    failures = []

    if args.four_chips:
        devices = jax.devices()[:4]
        if len(devices) != 4:
            print(f"chip_smoke.py: --four-chips needs 4 chips, found "
                  f"{len(devices)}", file=sys.stderr)
            return 2
        specs, tensors = request_stream((max(SIZES),))
        oracle = _phase("oracle", lambda: oracle_masks(tensors,
                                                       smoke_config()),
                        failures)
        if oracle is not None:
            failures += _phase(
                "four-chips",
                lambda: four_chip_phase(devices, tensors, oracle),
                failures) or []
    else:
        from repro.core import make_msc_mesh

        mesh = make_msc_mesh("flat", devices=jax.devices()[:1])
        specs, tensors = request_stream(SIZES)
        oracle = _phase("oracle", lambda: oracle_masks(tensors,
                                                       smoke_config()),
                        failures)
        einsum = None
        if oracle is not None:
            einsum, bad = _phase(
                "engine", lambda: engine_phase(mesh, tensors, specs, oracle,
                                               clock=clock),
                failures) or (None, [])
            failures += bad
        log(f"peak_bytes_in_use: "
            f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
        if einsum is not None:
            big = [i for i, s in enumerate(specs)
                   if s.shape[0] == max(SIZES)]
            custom, bad = _phase(
                "kernels", lambda: kernel_phase(
                    mesh, [tensors[i] for i in big],
                    [einsum[i] for i in big]),
                failures) or (True, [])
            failures += bad
            log(f"  chunk-step executable holds tpu_custom_call: {custom}")
            if not custom:
                failures.append("kernels: no tpu_custom_call in the "
                                "chunk-step executable")
        i = next(i for i, s in enumerate(specs)
                 if s.shape[0] == min(SIZES) and s.gamma > 2.0)
        err = _phase("precision", lambda: precision_phase(tensors[i]),
                     failures)
        if err is not None:
            log(f"  request {i}: fp32 vs float64, max relative error "
                f"d {err['d']:.3e}, lambda {err['lambda']:.3e} "
                f"(tolerance {PRECISION_TOL:g})")
            if max(err.values()) > PRECISION_TOL:
                failures.append(f"precision: fp32 off float64 by "
                                f"{max(err.values()):.3e}")
        log(f"peak_bytes_in_use: "
            f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
    log(f"backend compile seconds in all: {clock.seconds:.3f}")
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
