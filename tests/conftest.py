"""Shared pytest fixtures.

IMPORTANT: no XLA_FLAGS / device-count overrides here — smoke tests and
benches must see the 1 real CPU device.  Multi-device tests spawn
subprocesses with their own XLA_FLAGS and JAX_PLATFORMS=cpu: their
devices are simulated on the host, and on a machine with a TPU the
parent process may hold the chip.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_with_devices(code: str, n_devices: int, timeout: int = 600):
    """Run a python snippet in a subprocess with n fake CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}"
        )
    return proc.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_with_devices
