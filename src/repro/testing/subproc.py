"""Run a repro.testing check module in a subprocess with N fake devices.

The child gets exactly N devices regardless of what the parent inherited
(``tests/conftest.py`` sets 8 idempotently for the main pytest process),
so every multi-device correctness check runs as
``python -m repro.testing.<module>`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` pinned in its env.
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

SRC = str(pathlib.Path(__file__).resolve().parents[2])


def pinned_env(devices: int = 8) -> dict[str, str]:
    """A child-process environment with the fake-device count, ``src`` on
    ``PYTHONPATH``, and the CPU platform pinned — the one way any repro
    subprocess (check modules, chaos cluster workers) gets its devices,
    regardless of what this process inherited.  These children are CPU
    emulation: on a machine with an accelerator the chip belongs to one
    process, so a child must never reach for it."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def run_check(module: str, *args: str, devices: int = 8, timeout: int = 900) -> str:
    env = pinned_env(devices)
    proc = subprocess.run(
        [sys.executable, "-m", module, *args],
        env=env, capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"multi-device check {module} {args} failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    return proc.stdout
