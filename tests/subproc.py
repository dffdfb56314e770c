"""Child processes for tests that need several devices.

XLA fixes the device count at the first jax import, so a test that needs N
devices runs its code in a child with N virtual CPU devices.  Children are
pinned to the CPU: on a machine with a chip, a child that reached for it
would fight its parent over it.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(args: list, devices: int,
              timeout: int = 540) -> subprocess.CompletedProcess:
    """``python <args>`` from the repo root on ``devices`` CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(ROOT, "src"))
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=ROOT)


def _stdout(p: subprocess.CompletedProcess) -> str:
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def run_py(code: str, devices: int = 4, timeout: int = 540) -> str:
    """Run a code snippet; returns its stdout, asserting exit 0."""
    return _stdout(run_child(["-c", textwrap.dedent(code)], devices, timeout))


def run_cli(args: list, devices: int = 2, timeout: int = 540) -> str:
    """Run ``python -m <args>``; returns its stdout, asserting exit 0."""
    return _stdout(run_child(["-m", *args], devices, timeout))
