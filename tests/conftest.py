"""Shared test helpers. NOTE: no XLA_FLAGS here — smoke tests and benches
must see 1 device; multi-device tests spawn subprocesses (test_dist.py,
the engine-mesh parity test) via `run_forced_devices`."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# CI runs the multi-device lane as a matrix over device counts (2, 8);
# tests must derive mesh shapes from len(jax.devices()), not hardcode 8
FORCED_DEVICES = int(os.environ.get("REPRO_TEST_DEVICE_COUNT", "8"))


def run_forced_devices(code: str, devices: int = 0,
                       timeout: int = 900) -> str:
    """Run ``code`` in a subprocess with a forced multi-device host
    platform (default: the CI matrix's $REPRO_TEST_DEVICE_COUNT, else 8).
    The main pytest process keeps its single-device view (required by
    the smoke tests), so anything needing >1 device goes through here."""
    devices = devices or FORCED_DEVICES
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    return out.stdout


def tree_maxdiff(t1, t2) -> float:
    d = jax.tree.map(
        lambda a, b: float(jnp.abs(jnp.asarray(a, jnp.float32)
                                   - jnp.asarray(b, jnp.float32)).max()),
        t1, t2)
    return jax.tree_util.tree_reduce(max, d, 0.0)


def tree_abssum(t) -> float:
    d = jax.tree.map(lambda a: float(jnp.abs(jnp.asarray(a, jnp.float32)
                                             ).sum()), t)
    return jax.tree_util.tree_reduce(lambda a, b: a + b, d, 0.0)


@pytest.fixture
def paged_plan(monkeypatch):
    """Run the fused routing kernel on its paged memory plan at every size:
    the residency budget drops to 0 bytes. The kernel's jit wrapper keys
    its trace cache on shapes, not on the budget, so traces are cleared on
    entry and on exit."""
    from repro.kernels import common
    jax.clear_caches()
    monkeypatch.setattr(common, "FUSED_RESIDENT_BYTES", 0)
    yield
    jax.clear_caches()
