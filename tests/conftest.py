"""Shared test fixtures.

NOTE: XLA_FLAGS device-count forcing is deliberately NOT set here — smoke
tests and benchmarks must see the single real CPU device. Tests that need a
multi-device mesh run a script in a child process (``run_cpu_script``) that
forces 8 fake devices with an early os.environ write.
"""
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute subprocess compile tests (deselect with "
        "-m 'not slow')")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def key():
    return jax.random.key(0)


@pytest.fixture()
def run_cpu_script():
    """Run a Python script in a child process, with the repo's ``src`` on
    its path and JAX held to the CPU backend: a child can never share an
    accelerator that its parent's process holds.  Returns the
    CompletedProcess (text stdout/stderr); ``env`` adds variables."""
    def run(script: str, timeout: float, env=None):
        env = {"PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu",
               **{k: os.environ[k] for k in ("PATH", "HOME", "TMPDIR")
                  if k in os.environ}, **(env or {})}
        return subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, env=env,
                              cwd=REPO, timeout=timeout)
    return run
