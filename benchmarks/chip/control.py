"""The control of a cell's comparison: the reference put in the program's
place at the next precision below the configuration's, which the
comparison has to fail.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3

For each seed it makes the cell's pool of tensors as a run does, solves
every tensor with the reference at the configuration's precision (fp32
contractions at HIGHEST) and at HIGH (three bf16 passes, the step a later
change would be tempted to take), and compares the two as a run compares
the engine's answers: one line per seed with each number beside the
cell's limit, and a last line of JSON with all of them.  A run on the
chip, like run.py.  The benchmark's own runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import cells
import harness
import reference
import traffic


def readings(cell: cells.Cell, seed: int) -> dict:
    """The numbers a run compares, with the three-pass reference in the
    program's place, over every tensor of the seed's pool."""
    pool = traffic.make_pool(cell.config, cell.traffic, seed)
    reqs, refs = [], {}
    for i in range(pool.shape[0]):
        refs[i] = reference.solve(pool[i], cell.config["msc"])
        got = reference.solve(pool[i], cell.config["msc"], passes=3)
        reqs.append(harness.Request(rid=i, pool=i, result=got))
    numbers, failed = harness.compare(reqs, refs, cell.checks["limits"])
    return dict(numbers, failed=failed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    sys.path.insert(0, os.path.join(cells.CHECKOUT, "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control.py: JAX found no TPU (device 0 is "
              f"{dev.platform!r})", file=sys.stderr)
        return 2
    tag = f"[{dev.platform} {dev.device_kind} x{len(jax.devices())}]"
    harness.use_compile_cache()
    out = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out[seed] = readings(cell, seed)
        limits = cell.checks["limits"]
        shown = ", ".join(f"{k} {v!r} (limit {limits[k]!r})"
                          for k, v in out[seed].items() if k in limits)
        print(f"{tag} {cell.name} seed {seed}: control {shown}; "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"workload": cell.name, "control": "3 bf16 passes",
                      "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
