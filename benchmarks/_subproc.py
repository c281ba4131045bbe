"""Run a benchmark body in a subprocess with N host devices.

Multi-device benches cannot set XLA_FLAGS in-process (the orchestrator
must keep the default single device), so they follow the same subprocess
pattern as tests/helpers.py `run_devices_script`.  The children measure deterministic
counts on host devices and always run on the CPU: a parent that has
touched JAX holds the chip, and a child that opened it too would fail or
hang.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(body: str, ndev: int = 8, timeout: int = 1200) -> str:
    script = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
        import numpy as np
        import jax
        import jax.numpy as jnp
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}/src:{REPO}"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(f"subprocess failed:\n{out.stdout}\n{out.stderr}")
    return out.stdout
