"""The on-chip benchmark's harness (benchmarks/chip/) on the CPU, at tiny
sizes, in a few seconds.

    JAX_PLATFORMS=cpu python -m pytest -q tests/chip_bench

What runs on the chip is checked here without it: the loader and a cell
added as new files, the reduction of a trace recorded on a TPU v5 lite,
the metric readers on fixed spans and counters, the eigensolve's byte
count, the refusals of run.py, and a whole run (set-up, window, drain,
comparison) sound and with the timed path broken underneath.
"""
from __future__ import annotations

import copy
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(CHECKOUT, "benchmarks", "chip")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))

import cells  # noqa: E402
import harness  # noqa: E402
import measures  # noqa: E402
import xplane  # noqa: E402

TRACE = os.path.join(BENCH, "testdata", "window.xplane.pb")
WORKLOADS = [w["name"] for w in cells.load_benchmark()["workloads"]]


def _tiny(name: str, **traffic) -> cells.Cell:
    """The cell at m = 16 with 2 slots and a pool of 4."""
    c = cells.load_cell(name)
    config = copy.deepcopy(c.config)
    config.update(m=16, l=4, slots=2)
    mix = dict(c.traffic, pool=4, warmup_s=0.1, **traffic)
    return cells.Cell(name=c.name, chips=1, config=config, traffic=mix,
                      checks=c.checks, end_to_end=c.end_to_end,
                      per_layer=c.per_layer)


# ---- the loader ---------------------------------------------------------

@pytest.mark.parametrize("name", WORKLOADS)
def test_every_cell_loads_with_its_metrics(name):
    cell = cells.load_cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    assert {m["moves"] for m in cell.per_layer} <= e2e
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cells.reader(m["name"]).read)
    assert set(cell.checks["limits"]) == {"missing", "mask_diff",
                                          "sweeps_diff", "d_gap",
                                          "lambda_gap"}


def test_every_metric_has_a_reader_and_every_reader_a_metric():
    bench = cells.load_benchmark()
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
             if f.endswith(".py")}
    assert names == files


def test_a_cell_added_as_new_files_loads_without_an_edit(tmp_path):
    """A new configuration, mix, cell and per-layer metric: new files and
    new entries in BENCHMARK.json, and no existing file touched."""
    root = tmp_path / "checkout"
    bench_dir = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_dir)
    bench = cells.load_benchmark()
    conf = dict(json.loads((bench_dir / "configs" /
                            "paper-cube-m400.json").read_text()),
                name="paper-cube-m300", m=300, l=30)
    (bench_dir / "configs" / "paper-cube-m300.json").write_text(
        json.dumps(conf))
    (bench_dir / "traffic" / "midgap-batch.json").write_text(json.dumps(
        {"why": "a test mix", "loop": "closed", "callers_per_slot": 1,
         "gamma": {"value": 50.0}, "pool": 2, "warmup_s": 1}))
    (bench_dir / "checks" / "m300-midgap-batch.json").write_text(
        (bench_dir / "checks" / "m400-lowgap-batch.json").read_text())
    (bench_dir / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return len(run.requests)\n")
    bench["configs"].append({"name": "paper-cube-m300",
                             "source": "https://arxiv.org/abs/2309.17383",
                             "file": "benchmarks/chip/configs/"
                                     "paper-cube-m300.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "m300-midgap-batch",
                               "config": "paper-cube-m300",
                               "traffic": "midgap-batch", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({"name": "requests_seen", "unit": "requests",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "scheduler",
                               "moves": "tensors_per_s",
                               "workloads": ["m300-midgap-batch"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell("m300-midgap-batch", root=str(root),
                           bench_dir=str(bench_dir))
    assert cell.config["m"] == 300 and cell.traffic["gamma"]["value"] == 50
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "tensors_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["requests_seen"]
    mod = cells.reader("requests_seen", bench_dir=str(bench_dir))
    assert mod.read(harness.Run(cell=cell, setup_s=0,
                                window=(0, 1), requests=[1, 2], ticks=[],
                                counters={}, peaks={})) == 2


def test_peaks_are_keyed_by_device_kind_with_their_source():
    assert cells.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert cells.peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert cells.peaks_of("TPU v9 imaginary") is None
    with open(os.path.join(BENCH, "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["source"]


# ---- the trace ----------------------------------------------------------

@pytest.fixture(scope="module")
def trace():
    return xplane.reduce_file(TRACE)


def test_trace_reduction_of_a_recorded_window(trace):
    """testdata/window.xplane.pb: two ticks of m400-lowgap-batch traced on
    one TPU v5 lite (the recipe is in testdata/README)."""
    assert 0 < trace.busy_s < trace.window_s
    steps, step_s = trace.program("jit_step")
    refills, refill_s = trace.program("jit_refill")
    assert steps >= 1 and refills >= 1
    assert step_s > 0 and refill_s > 0
    # the two programs are all the device ran: their executions span the
    # busy time, with the short stalls inside them
    inside = sum(s for label, s in trace.gaps if label.startswith("inside"))
    assert step_s + refill_s == pytest.approx(trace.busy_s + inside,
                                              rel=0.01)
    # the ops account for the busy time, and sit inside a program
    assert sum(trace.ops.values()) >= trace.busy_s * (1 - 1e-6)
    assert all(n.split("/")[0] in trace.modules for n in trace.ops)
    # every idle stretch is in the window and the gaps fill what busy leaves
    idle = sum(s for _, s in trace.gaps)
    assert idle == pytest.approx(trace.window_s - trace.busy_s, rel=1e-6)
    assert len(trace.spans["engine.step"]) >= 2


def test_trace_breakdown_is_bounded_and_labelled(trace):
    b = trace.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"] == sorted(b["device_ops"], key=lambda x: -x[1])
    assert any(name.startswith("engine.step") for name, _ in b["idle_gaps"])


def test_union_and_overlap():
    merged = xplane.union([(3, 4), (0, 1), (0.5, 2), (5, 6)])
    assert merged == [(0, 2), (3, 4), (5, 6)]
    assert xplane.overlap(merged, 1, 5.5) == pytest.approx(2.5)
    assert xplane.overlap(merged, 6, 7) == 0


# ---- the readers --------------------------------------------------------

def _req(rid, done_tick, sweeps):
    r = harness.Request(rid=rid, pool=0, done_tick=done_tick)
    r.result = [{"sweeps": s} for s in sweeps]
    return r


def _fake_run(requests, trace=None, traced=(0, -1), counters=None,
              m=400, precision="fp32"):
    cell = _tiny("m400-lowgap-batch")
    config = copy.deepcopy(cell.config)
    config["m"] = m
    config["msc"]["precision"] = precision
    cell = cells.Cell(name=cell.name, chips=1, config=config,
                      traffic=cell.traffic, checks=cell.checks,
                      end_to_end=cell.end_to_end, per_layer=cell.per_layer)
    # tick i runs from 100 + i to 101 + i; the window holds ticks 2..9
    return harness.Run(cell=cell, setup_s=3.5,
                       window=(102.0, 110.0), requests=requests,
                       ticks=[(i, 100.0 + i, 101.0 + i) for i in range(40)],
                       counters=counters or {},
                       peaks=cells.peaks_of("TPU v5 lite"), trace=trace,
                       traced_ticks=traced)


def _fake_trace():
    return xplane.Reduced(
        window=(0.0, 2.0), busy=[(0.0, 0.5), (1.0, 1.3)], busy_s=0.8,
        modules={"jit_step": (4, 0.6), "jit_refill": (2, 0.2)},
        ops={"jit_step/fusion": 0.6, "jit_refill/copy": 0.2},
        spans={"engine.step": [(0.0, 0.9), (0.9, 1.5)],
               "engine.submit": [(1.6, 1.7)]},
        gaps=[("engine.step, before jit_step", 0.5),
              ("outside the engine, before the window's end", 0.7)])


def test_sweeps_in_the_traced_ticks_are_counted_exactly():
    # k = 6; a request returned at tick 10 after 10 chunks ran them at
    # ticks 0..9; one with sweeps (6, 12, 6) returned at tick 5 ran
    # ticks 3..4, its mode 1 at both and modes 0 and 2 at tick 3 only
    a = _req(0, 10, (60, 60, 60))
    b = _req(1, 5, (6, 12, 6))
    run = _fake_run([a, b], traced=(4, 7))
    assert measures.traced_sweeps(run) == 3 * 4 * 6 + 6
    run = _fake_run([a, b], traced=(0, 20))
    assert measures.traced_sweeps(run) == 180 + 24


def test_eigensolve_bytes_match_a_hand_count():
    run = _fake_run([], m=400)
    # one sweep of one mode reads the 400³ fp32 unfolding once: 256 MB,
    # and does 4·400³ operations (Tᵀ(T v) over 400 slices of 400 × 400)
    assert measures.eigensolve_work(run, 1) == (256_000_000, 256_000_000)
    half = _fake_run([], m=200, precision="bf16_fp32")
    assert measures.eigensolve_work(half, 10) == (10 * 8_000_000 * 2,
                                                  10 * 32_000_000)


def test_readers_compute_known_values():
    reqs = [_req(0, 3, (6, 6, 6)), _req(1, 3, (6, 6, 6)),
            _req(2, 30, (6, 6, 6)), _req(3, 2, (6, 6, 6)),
            _req(4, 12, (60, 60, 60))]
    run = _fake_run(reqs, trace=_fake_trace(), traced=(2, 3),
                    counters={"busy_slot_chunks": 6, "slot_chunks": 8})

    def read(name):
        return cells.reader(name).read(run)

    assert read("setup_s") == 3.5
    # served in the window [102, 110]: requests 0 and 1 whole (their one
    # chunk at tick 2), 2 and 3 not at all (ticks 29 and 1), and 8 of
    # request 4's 10 seconds (ticks 2..11): 2.8 tensors in 8 s
    assert read("tensors_per_s") == pytest.approx(2.8 / 8)
    assert read("device_idle_share.batch") == pytest.approx(0.6)
    assert read("chunk_step_ms.batch") == pytest.approx(150.0)
    assert read("refill_ms.batch") == pytest.approx(100.0)
    assert read("slot_occupancy.batch") == pytest.approx(0.75)
    # host spans 0.9 + 0.6 + 0.1 s, of which the device ran 0.5 + 0.3 s;
    # three tensors returned at ticks 2..3
    assert read("engine_host_ms_per_tensor.batch") == pytest.approx(
        1e3 * 0.8 / 3)
    # ticks 2..3 hold the one chunk of requests 0 and 1 (request 3's ran
    # at tick 1) and two of request 4's ten: (2 + 2) × 3 modes × 6
    # sweeps of 256e6 B, in 0.6 s
    want = 100 * (72 * 256e6 / 819e9) / 0.6
    assert read("eigensolve_roofline") == pytest.approx(want)


def test_readers_return_nothing_without_a_trace():
    run = _fake_run([_req(0, 3, (6, 6, 6))],
                    counters={"busy_slot_chunks": 0, "slot_chunks": 0})
    for name in ("device_idle_share.batch", "chunk_step_ms.batch",
                 "refill_ms.batch", "eigensolve_roofline",
                 "engine_host_ms_per_tensor.batch", "slot_occupancy.batch"):
        assert cells.reader(name).read(run) is None


# ---- run.py's refusals ----------------------------------------------------

def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "m400-lowgap-batch", "--seed", "3", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    proc = _run_py(CHECKOUT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    for path in cells.load_benchmark()["paths"]:
        shutil.copytree(os.path.join(CHECKOUT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


# ---- whole runs on the CPU, sound and broken ----------------------------

_ENGINES = {}


@pytest.fixture
def whole_run(monkeypatch):
    """One whole run of a cell, 0.2 s long.  The runs of this file share
    one engine per shape, which a drained run leaves empty, so it
    compiles once."""
    import jax
    import time

    make = harness.make_engine

    def shared(config, devices):
        key = json.dumps([config["m"], config["slots"], config["msc"]])
        if key not in _ENGINES:
            _ENGINES[key] = make(config, devices)
        return _ENGINES[key]

    monkeypatch.setattr(harness, "make_engine", shared)

    def run(cell, break_engine=None):
        return harness.run(cell, 2 ** 31 + 11, 0.2, False,
                           devices=jax.devices(),
                           peaks=cells.peaks_of("TPU v5 lite"),
                           t_start=time.perf_counter(), log=lambda _: None,
                           break_engine=break_engine)
    return run


class _Broken:
    """The engine with one answer altered, or lost, where it is made."""

    def __init__(self, engine, fault):
        self.engine, self.fault, self.done = engine, fault, False

    def __getattr__(self, name):
        return getattr(self.engine, name)

    def step(self):
        out = self.engine.step()
        if out and not self.done:
            self.done = True
            rid = next(iter(out))
            if self.fault == "lost":
                del out[rid]
                return out
            mode = out[rid].modes[0]
            if self.fault == "mask":
                mode.mask = np.logical_not(mode.mask)
            elif self.fault == "d":
                mode.d = np.asarray(mode.d) * np.float32(1.001)
            elif self.fault == "sweeps":
                mode.power_iters_run = int(mode.power_iters_run) + 6
        return out


@pytest.mark.parametrize("name", WORKLOADS)
def test_a_sound_run_is_correct(name, whole_run):
    out = whole_run(_tiny(name))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "tensors_per_s"}


@pytest.mark.parametrize("fault,number",
                         [("mask", "mask_diff"), ("d", "d_gap"),
                          ("sweeps", "sweeps_diff"), ("lost", "missing")])
def test_a_broken_answer_makes_the_run_incorrect(fault, number, whole_run):
    out = whole_run(_tiny("m400-lowgap-batch"),
                    break_engine=lambda e: _Broken(e, fault))
    assert not out["correct"]
    assert out["failed"] >= 1
    c = out["checks"][number]
    assert c["value"] > c["limit"]


# ---- the control ----------------------------------------------------------

@pytest.mark.parametrize("name", WORKLOADS)
def test_the_control_fails_the_cells_limits(name):
    """control.py at m = 32: the three-pass reference in the program's
    place fails the cell's limits.  Masks and sweeps come out unchanged;
    d and λ lie off the fp32 reference by more than the limits allow,
    which is why d_gap and lambda_gap carry the control's failure on the
    chip too."""
    import control

    cell = _tiny(name)
    config = copy.deepcopy(cell.config)
    config.update(m=32, l=3)
    cell = dataclasses.replace(cell, config=config,
                               traffic=dict(cell.traffic, pool=2))
    got = control.readings(cell, 2 ** 31 + 5)
    limits = cell.checks["limits"]
    assert got["missing"] == 0
    assert any(got[k] > limits[k] for k in limits), (got, limits)
