"""chip_smoke.py's phases on the CPU, at small sizes.

The script refuses to run anywhere but on a TPU, so these tests call its
phase functions directly: m ∈ {16, 24} (two buckets, every 4th request
near-noise), 2 slots, Pallas kernels interpreted.  The platform is the
test process's own (JAX_PLATFORMS=cpu); the four-chip phase runs on
four simulated devices in a subprocess.
"""
import importlib.util
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
SIZES = (16, 24)
SLOTS = 2


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load()


@pytest.fixture(scope="module")
def stream():
    specs, tensors = cs.request_stream(SIZES)
    return specs, tensors, cs.oracle_masks(tensors, cs.smoke_config())


@pytest.fixture(scope="module")
def mesh():
    import jax

    from repro.core import make_msc_mesh

    return make_msc_mesh("flat", devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def engine_run(stream, mesh):
    specs, tensors, oracle = stream
    return cs.engine_phase(mesh, tensors, specs, oracle, slots=SLOTS)


def test_stream_has_both_buckets_and_near_noise(stream):
    specs, tensors, _ = stream
    assert len(tensors) == cs.N_REQUESTS
    assert {s.shape[0] for s in specs} == set(SIZES)
    assert [s.gamma == 2.0 for s in specs] == \
        [i % cs.SLOW_EVERY == 0 for i in range(len(specs))]


def test_engine_phase_matches_oracle(engine_run, stream):
    masks, failures = engine_run
    assert failures == []
    _, _, oracle = stream
    for got, want in zip(masks, oracle):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)


def test_kernel_phase_matches_einsum(engine_run, stream, mesh):
    masks, _ = engine_run
    specs, tensors, _ = stream
    big = [i for i, s in enumerate(specs) if s.shape[0] == max(SIZES)]
    custom, failures = cs.kernel_phase(
        mesh, [tensors[i] for i in big], [masks[i] for i in big],
        slots=SLOTS)
    assert failures == []
    assert custom is False    # interpreted: no compiled TPU kernel


def test_precision_phase_within_fp32_on_cpu(stream):
    specs, tensors, _ = stream
    i = next(i for i, s in enumerate(specs)
             if s.shape[0] == min(SIZES) and s.gamma > 2.0)
    err = cs.precision_phase(tensors[i])
    assert max(err.values()) <= cs.PRECISION_TOL, err


def test_float64_reference_follows_the_solver():
    """float64_mode replays the solver's sweeps: at a fixed sweep count
    it matches the fp32 matrix-free solver to fp32 rounding."""
    import jax.numpy as jnp

    from repro.core import MSCConfig, msc_sequential

    rng = np.random.RandomState(0)
    t = rng.standard_normal((12, 10, 14)).astype(np.float32)
    res = msc_sequential(jnp.asarray(t), MSCConfig(power_tol=0.0,
                                                   power_iters=9))
    for j in range(3):
        d, lam = cs.float64_mode(t, j, 9)
        np.testing.assert_allclose(np.asarray(res[j].d), d, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(res[j].lambdas), lam,
                                   rtol=1e-4)


FOUR = r"""
import importlib.util, jax
spec = importlib.util.spec_from_file_location("chip_smoke", {script!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
_, tensors = cs.request_stream((24,), n=4)
oracle = cs.oracle_masks(tensors, cs.smoke_config())
failures = cs.four_chip_phase(jax.devices()[:4], tensors, oracle, slots=2)
assert failures == [], failures
print("OK")
"""


def test_four_chip_phase_on_simulated_devices(subproc):
    assert "OK" in subproc(FOUR.format(script=SCRIPT), 4, timeout=900)


@pytest.mark.parametrize("alone", [False, True],
                         ids=["checkout", "script-alone"])
def test_script_refuses_to_run_off_the_chip(tmp_path, alone):
    """With no TPU, or without the rest of the repository, the script
    exits non-zero and prints no result line."""
    script = SCRIPT
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=300, env=env,
                          cwd=os.path.dirname(script))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
