"""Shared benchmark utilities.

Timing on this container is single-core CPU; every benchmark therefore
reports (a) measured walltime at CPU-feasible sizes and, where the paper's
figure is about *scaling*, (b) the roofline-projected TPU-v5e numbers
derived from compiled HLO (same methodology as EXPERIMENTS.md §Roofline).
Multi-device runs use subprocesses with XLA_FLAGS device-count overrides
so the parent process keeps the 1 real device (assignment requirement).
"""
from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time
from typing import Callable, Dict, List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
OUT_DIR = os.path.join(REPO, "experiments", "bench")


def time_fn(fn: Callable, *args, repeats: int = 3, warmup: int = 1) -> Dict:
    """Median walltime of fn(*args) with block_until_ready."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return {"median_s": statistics.median(ts), "min_s": min(ts),
            "repeats": repeats}


def run_subprocess_json(code: str, n_devices: int, timeout: int = 1200) -> Dict:
    """Run `code` in a subprocess with n fake CPU devices; parse last-line
    JSON.  The child is pinned to the CPU: it simulates its devices on
    the host and must never reach for a chip the parent may hold."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def save_rows(name: str, rows: List[Dict]):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, name + ".json"), "w") as f:
        json.dump(rows, f, indent=2)


def print_rows(name: str, rows: List[Dict]):
    if not rows:
        print(f"[{name}] no rows")
        return
    keys = list(rows[0].keys())
    print(f"\n[{name}]")
    print(",".join(keys))
    for r in rows:
        print(",".join(f"{r.get(k, '')}" if not isinstance(r.get(k), float)
                       else f"{r[k]:.6g}" for k in keys))
