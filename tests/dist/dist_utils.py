"""Shared helper: run a test snippet under N forced host devices.

The parent pytest process locked its device count at first jax import,
so multi-device assertions run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (same pattern as
``benchmarks/common.run_multi_device``). The snippet should raise (or
``assert``) on failure; stdout is returned for extra checks.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_ndev(body: str, n_devices: int = 8, timeout: int = 600) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={n_devices}")
        import sys
        sys.path.insert(0, {os.path.join(REPO, 'src')!r})
        import jax, jax.numpy as jnp
        import numpy as np
        assert len(jax.devices()) == {n_devices}, jax.devices()
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout)
    if r.returncode != 0:
        raise AssertionError(
            f"{n_devices}-device subprocess failed:\n{r.stderr[-4000:]}")
    return r.stdout
