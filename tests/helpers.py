"""Test helpers: run code in a subprocess with N host devices.

jax fixes the device count at first backend init, so multi-device tests
(shard_map, all_to_all) run in fresh subprocesses with
--xla_force_host_platform_device_count set. Single-device tests run
in-process and see 1 device, as required. The child always runs on the
CPU backend (JAX_PLATFORMS=cpu): it emulates devices, and it must never
reach for a chip, whatever the caller's environment.

The driver script is piped over stdin and compiled with ``optimize=0``,
NOT passed to ``python -c``: under CI's PYTHONOPTIMIZE=1 job a ``-c``
script's ``assert`` statements (the byte-identity / valsort acceptance
gates) would be stripped and the end-to-end checks silently vacuous.
Compiling the driver at optimize=0 keeps its asserts alive while every
*imported* product module still compiles under -O — which is exactly
the split that job exists to test.
"""
from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Keeps the driver's asserts even when the interpreter runs with -O.
_WRAPPER = ("import sys; _src = sys.stdin.read(); "
            "exec(compile(_src, '<run_with_devices>', 'exec', optimize=0))")


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", _WRAPPER],
        input=code,
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout[-3000:]}\n"
            f"--- stderr ---\n{proc.stderr[-3000:]}"
        )
    return proc.stdout
