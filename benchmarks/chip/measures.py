"""Arithmetic the metric readers share: the work served in the window,
what the algorithm needs, and the host's share of the traced window.

The eigensolve needs, per realized power sweep of one mode, one read of
that mode's unpadded unfolding: m1·m2·m3 elements at the configuration's
operand width, and 4·m1·m2·m3 operations (Tᵀ(T v) for every slice).  A
request returned at tick t ran its chunks at ticks t − n … t − 1, with
n = max over modes of sweeps / power_check_every, and its mode j's sweeps
in the first sweeps_j / power_check_every of them: so the sweeps that fell
inside the traced ticks are counted exactly, for every request that
returned (the loop drains the engine after the window).
"""
from __future__ import annotations

import math
from typing import Optional

OPERAND_BYTES = {"fp32": 4, "bf16_fp32": 2}


def _check_every(config: dict) -> int:
    msc = config["msc"]
    return max(1, min(int(msc["power_check_every"]), int(msc["power_iters"])))


def _chunks(req, k: int) -> tuple:
    """(first tick, chunks per mode) of a returned request."""
    chunks = [math.ceil(m["sweeps"] / k) for m in req.result]
    return req.done_tick - max(chunks), chunks


def served_tensors(run) -> float:
    """Tensors served in the window: each returned request counts the
    share of its service, from the start of the tick that admitted it to
    the end of the tick that ran its last chunk, that falls in the
    window."""
    k = _check_every(run.cell.config)
    span = {tick: (t0, t1) for tick, t0, t1 in run.ticks}
    w0, w1 = run.window
    total = 0.0
    for req in run.requests:
        if req.result is None:
            continue
        first, chunks = _chunks(req, k)
        first = max(first, min(span))
        start, end = span[first][0], span[req.done_tick - 1][1]
        total += max(0.0, min(end, w1) - max(start, w0)) / (end - start)
    return total


def traced_sweeps(run) -> int:
    """Power sweeps, over all modes and requests, run in the traced ticks."""
    k = _check_every(run.cell.config)
    lo, hi = run.traced_ticks
    total = 0
    for req in run.requests:
        if req.result is None:
            continue
        first, chunks = _chunks(req, k)
        for n in chunks:
            inside = min(first + n - 1, hi) - max(first, lo) + 1
            total += k * max(0, inside)
    return total


def eigensolve_work(run, sweeps: int) -> tuple:
    """(bytes, operations) the algorithm needs for `sweeps` sweeps."""
    config = run.cell.config
    elems = int(config["m"]) ** 3
    return (sweeps * elems * OPERAND_BYTES[config["msc"]["precision"]],
            sweeps * 4 * elems)


def eigensolve_roofline(run) -> Optional[float]:
    """The chunk step's share of the chip's roofline, in %: the least
    time the needed bytes and operations take at the published peaks,
    over the chunk step's device time in the traced window."""
    if run.trace is None:
        return None
    n, seconds = run.trace.program("jit_step")
    sweeps = traced_sweeps(run)
    if not n or not sweeps:
        return None
    nbytes, ops = eigensolve_work(run, sweeps)
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                ops / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds


def program_ms(run, name: str) -> Optional[float]:
    """Mean device milliseconds per execution of one compiled program."""
    if run.trace is None:
        return None
    n, seconds = run.trace.program(name)
    return 1e3 * seconds / n if n else None


def host_ms_per_tensor(run) -> Optional[float]:
    """Host milliseconds inside `submit` and `step` with the device idle,
    per tensor returned in the traced ticks."""
    if run.trace is None:
        return None
    lo, hi = run.traced_ticks
    done = sum(1 for r in run.requests
               if r.done_tick is not None and lo <= r.done_tick <= hi)
    return 1e3 * run.trace.host_self_s() / done if done else None

