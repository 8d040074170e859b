"""One run of one cell: set-up, the measured window, the drain, the
comparison with the reference, and the result line.

The timed path is the user's: `MSCContinuousEngine.submit` and `step`,
driven by one thread.  A closed loop keeps `callers_per_slot · slots`
requests outstanding and sends a caller's next tensor as soon as its
masks come back.  The window opens at the start of a tick and closes at
the end of the first tick that ends past `--seconds`.  After it the loop
sends nothing more and steps until every request has returned, or a
minute has passed.  Then the engine is freed,
and every returned answer is compared with `reference.solve` of its
tensor.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import cells
import reference
import traffic as traffic_mod

DRAIN_S = 60.0


@dataclasses.dataclass
class Request:
    rid: int
    pool: int
    done: Optional[float] = None
    done_tick: Optional[int] = None
    result: Optional[list] = None   # per mode: mask, d, lambdas, sweeps


@dataclasses.dataclass
class Run:
    """What a run leaves for the metric readers (`metrics/<name>.py`)."""

    cell: cells.Cell
    setup_s: float
    window: tuple            # host clock (start, end) of the window's ticks
    requests: List[Request]
    ticks: list              # (tick, start, end) of every engine step
    counters: Dict[str, float]   # ServeStats over the window
    peaks: dict
    trace: Optional[object] = None   # xplane.Reduced of the traced window
    traced_ticks: tuple = (0, -1)    # first and last tick under the trace


class CompileClock:
    """Backend compiles and their seconds, from jax.monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout, so only a checkout's first run of a cell compiles."""
    import jax

    path = os.path.join(cells.CHECKOUT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def make_engine(config: dict, devices):
    """The system under test, as the configuration file states it."""
    from repro.core import MSCConfig, make_msc_mesh
    from repro.serving import MSCContinuousEngine

    cfg = MSCConfig(**config["msc"])
    mesh = make_msc_mesh("flat", devices=list(devices)[:int(config["chips"])],
                         shape=tuple(config["mesh"]))
    inner = "inner" if "inner" in mesh.axis_names else None
    return MSCContinuousEngine(mesh, cfg, slots=int(config["slots"]),
                               inner_axis=inner)


def _annotate(on: bool):
    if not on:
        return lambda name, **kw: contextlib.nullcontext()
    import jax.profiler

    return jax.profiler.TraceAnnotation


class Loop:
    """Drives the engine with one mix; records every request and tick."""

    def __init__(self, engine, pool, cell: cells.Cell, seed: int,
                 clock: Callable[[], float] = time.perf_counter):
        self.engine = engine
        self.pool = pool
        self.traffic = cell.traffic
        self.order = traffic_mod.pool_order(cell.traffic, seed)
        self.clock = clock
        self.requests: List[Request] = []
        self.by_rid: Dict[int, Request] = {}
        self.ticks: list = []
        self.tick = 0
        self.accepting = True
        self.span = _annotate(False)
        if self.traffic["loop"] != "closed":
            raise ValueError(f"unknown loop {self.traffic['loop']!r}")
        self.callers = (int(self.traffic["callers_per_slot"])
                        * int(cell.config["slots"]))

    def _submit(self):
        n = len(self.requests)
        idx = int(self.order[n % len(self.order)])
        with self.span("engine.submit"):
            rid = self.engine.submit(self.pool[idx])
        req = Request(rid=rid, pool=idx)
        self.requests.append(req)
        self.by_rid[rid] = req

    def _step(self):
        t0 = self.clock()
        with self.span("engine.step", tick=self.tick):
            out = self.engine.step()
        t1 = self.clock()
        self.ticks.append((self.tick, t0, t1))
        for rid, res in out.items():
            req = self.by_rid[rid]
            req.done, req.done_tick = t1, self.tick
            req.result = [{"mask": np.asarray(mr.mask),
                           "d": np.asarray(mr.d),
                           "lambdas": np.asarray(mr.lambdas),
                           "sweeps": int(mr.power_iters_run)}
                          for mr in res.modes]
            if self.accepting:
                self._submit()
        self.tick += 1

    def run_until(self, until: float):
        while len(self.requests) < self.callers:
            self._submit()
        while self.clock() < until:
            self._step()

    def drain(self, deadline: float):
        self.accepting = False
        while self.engine.has_work() and self.clock() < deadline:
            self._step()


def _counters(stats) -> Dict[str, float]:
    return {f.name: getattr(stats, f.name)
            for f in dataclasses.fields(stats)}


def compare(requests: List[Request], refs: Dict[int, list],
            limits: Dict[str, float]) -> tuple:
    """(numbers, failed): each compared number over every request, and
    how many requests never returned or returned a wrong answer."""
    mask_diff = sweeps_diff = 0
    d_gap = lambda_gap = 0.0
    failed = 0
    for req in requests:
        if req.result is None:
            failed += 1
            continue
        bad = False
        for got, want in zip(req.result, refs[req.pool]):
            flips = int(np.sum(got["mask"] != want["mask"]))
            mask_diff += flips
            sweeps_diff += int(got["sweeps"] != want["sweeps"])
            gaps = {}
            for key in ("d", "lambdas"):
                scale = float(np.max(np.abs(want[key]))) or 1.0
                gaps[key] = float(np.max(np.abs(
                    np.asarray(got[key], np.float64) - want[key]))) / scale
            d_gap = max(d_gap, gaps["d"])
            lambda_gap = max(lambda_gap, gaps["lambdas"])
            bad |= (flips > 0 or got["sweeps"] != want["sweeps"]
                    or gaps["d"] > limits["d_gap"]
                    or gaps["lambdas"] > limits["lambda_gap"])
        failed += bad
    numbers = {"missing": sum(r.result is None for r in requests),
               "mask_diff": mask_diff, "sweeps_diff": sweeps_diff,
               "d_gap": d_gap, "lambda_gap": lambda_gap}
    return numbers, failed


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool, *,
        devices, peaks: dict, t_start: float, log: Callable[[str], None],
        trace_dir: Optional[str] = None, break_engine=None) -> dict:
    """One run; returns the result line's object.  `break_engine` wraps
    the engine before the window (the fault tests break the timed path
    with it)."""
    config = cell.config
    compiles = CompileClock()
    t0 = time.perf_counter()
    pool = traffic_mod.make_pool(config, cell.traffic, seed)
    log(f"pool: {pool.shape[0]} tensors of {pool.shape[1:]} made in "
        f"{time.perf_counter() - t0:.3f} s")
    engine = make_engine(config, devices)
    t0 = time.perf_counter()
    engine.run([pool[0]])      # compiles, or loads, the cell's one bucket
    log(f"warm-up: {engine.stats.compiles} engine compiles, "
        f"{compiles.count} backend compiles ({compiles.seconds:.3f} s) in "
        f"{time.perf_counter() - t0:.3f} s")
    if break_engine is not None:
        engine = break_engine(engine)
    loop = Loop(engine, pool, cell, seed)
    warm = float(cell.traffic["warmup_s"])
    loop.run_until(time.perf_counter() + warm)

    tmp = None
    if trace:
        import jax.profiler

        tmp = trace_dir or tempfile.mkdtemp(prefix="msc-bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
        loop.span = _annotate(True)
    stats0, n_compiles0 = engine.stats, compiles.count
    first_tick = loop.tick
    w0 = time.perf_counter()
    setup_s = w0 - t_start
    with loop.span("bench.window"):
        loop.run_until(w0 + seconds)
    w1 = time.perf_counter()
    last_tick = loop.tick - 1
    window_compiles = compiles.count - n_compiles0
    counters = _counters(engine.stats.delta(stats0))
    if trace:
        jax.profiler.stop_trace()
        loop.span = _annotate(False)
    loop.drain(w1 + DRAIN_S)
    dev = devices[0]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:cell.chips])
    reqs = loop.requests
    log(f"window: {w1 - w0:.6f} s over ticks {first_tick}..{last_tick}, "
        f"{sum(1 for r in reqs if w0 <= (r.done or 0) <= w1)} "
        f"tensors returned in it, {len(reqs)} sent in the run; "
        f"{window_compiles} backend compiles and "
        f"{int(counters['compiles'])} engine compiles inside it")
    ticks = loop.ticks
    del loop, engine
    gc.collect()

    t0 = time.perf_counter()
    refs = {i: reference.solve(pool[i], config["msc"])
            for i in sorted({r.pool for r in reqs if r.result is not None})}
    numbers, failed = compare(reqs, refs, cell.checks["limits"])
    log(f"reference: {len(refs)} distinct tensors for {len(reqs)} requests "
        f"in {time.perf_counter() - t0:.3f} s")

    reduced = None
    if trace:
        import xplane

        reduced = xplane.reduce_dir(tmp)
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
    record = Run(cell=cell, setup_s=setup_s,
                 window=(w0, w1), requests=reqs, ticks=ticks,
                 counters=counters, peaks=peaks, trace=reduced,
                 traced_ticks=(first_tick, last_tick))
    metrics = {}
    for spec in (cell.per_layer if trace else cell.end_to_end):
        value = cells.reader(spec["name"]).read(record)
        if value is not None:
            metrics[spec["name"]] = {"value": float(value),
                                     "unit": spec["unit"]}
    limits = cell.checks["limits"]
    checks = {name: {"value": numbers[name], "limit": limits[name]}
              for name in numbers}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(reqs) and all(numbers[n] <= limits[n]
                                         for n in numbers),
           "attempted": len(reqs), "failed": failed, "metrics": metrics,
           "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown()
    out["checks"] = checks
    return out
