"""Shared benchmark utilities: timing + subprocess multi-device runs."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def time_fn(fn, *args, iters: int = 10, warmup: int = 3) -> float:
    """Median wall time per call in microseconds."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def run_multi_device(body: str, n_devices: int = 8, timeout: int = 900) -> str:
    """Run a snippet in a subprocess with N forced host devices.

    The child is pinned to the CPU backend: its devices are forced host
    devices, and a child that reached for an accelerator would contend
    with the parent, which holds it once it has touched JAX.
    The snippet should print CSV lines `name,us_per_call,derived`.
    """
    script = textwrap.dedent(f"""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
        import sys
        sys.path.insert(0, {os.path.join(REPO, 'src')!r})
        sys.path.insert(0, {REPO!r})
        import jax, jax.numpy as jnp
        import numpy as np
        from benchmarks.common import time_fn
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"multi-device bench failed:\n{r.stderr[-3000:]}")
    return r.stdout
