"""The on-chip benchmark of the continuous MSC engine: one cell, one run.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs the cell of BENCHMARK.json named by --workload on the chips of this
machine, in this one process.  It makes the cell's tensors from --seed,
warms up, measures for --seconds, compares every returned answer with the
plain reference (reference.py), and prints as its last line of standard
output one JSON object: `correct`, `attempted`, `failed`, `metrics` (the
cell's end-to-end metrics, or with --trace 1 its per-layer ones read from
a profiler trace of the window), `device`, with --trace 1 `breakdown`,
and last `checks`: each compared number beside its limit.  The same
numbers are the last lines of standard error.

It exits non-zero and prints no result where JAX finds no TPU, fewer
chips than the cell asks for, a chip the peaks table lacks, or no program
beside the benchmark to run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import cells  # noqa: E402


def _fail(msg: str) -> int:
    print(f"run.py: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: deleted)")
    args = ap.parse_args(argv)

    src = os.path.join(cells.CHECKOUT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return _fail(f"no program to measure: {src}/repro is missing")
    try:
        cell = cells.load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        return _fail(f"cannot load cell {args.workload!r}: {e}")
    sys.path.insert(0, src)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"JAX found no TPU (device 0 is {dev.platform!r}); "
                     f"the benchmark measures the chip only")
    if len(devices) < cell.chips:
        return _fail(f"cell {cell.name} needs {cell.chips} chips, JAX "
                     f"found {len(devices)}")
    peaks = cells.peaks_of(dev.device_kind)
    if peaks is None:
        return _fail(f"no peaks for device_kind {dev.device_kind!r} in "
                     f"peaks.json")
    tag = f"[{dev.platform} {dev.device_kind} x{len(devices)}]"

    def log(msg: str):
        print(f"{tag} {msg}", flush=True)

    import harness

    harness.use_compile_cache()
    log(f"cell {cell.name}: seed {args.seed}, {args.seconds:g} s, "
        f"trace {args.trace}")
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      devices=devices, peaks=peaks, t_start=T_START, log=log,
                      trace_dir=args.trace_dir)
    for name, c in out["checks"].items():
        print(f"{tag} check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"{tag} correct = {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
