"""Multi-device harness for the distributed suite.

Two patterns, mirroring how the paper's cross-backend CI runs the same tests
on every vendor's hardware:

* **env-guard**: when this conftest is imported before jax (i.e. running
  ``pytest tests/distributed`` standalone, or the dedicated CI job), it
  forces ``--xla_force_host_platform_device_count=8`` so the whole suite
  runs in-process against 8 virtual CPU devices.  When jax is already
  imported (the full tier-1 run, where other suites came first and the
  device count is locked at 1), the guard is inert and device-hungry tests
  skip cleanly via :func:`require_devices` — single-shard cases still run.
* **spawn**: ``run_with_devices`` executes a script in a subprocess with the
  flag set, for the acceptance-critical cases that must run even inside a
  single-device parent (same pattern as tests/distributed/test_multidevice).
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")

# env-guard: only effective if jax has not initialized its backend yet; never
# override a device count the environment already chose
if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def _require_devices(n: int):
    """Skip (cleanly, with the remedy in the message) unless ``n`` devices."""
    import jax

    have = len(jax.devices())
    if have < n:
        pytest.skip(
            f"needs {n} devices, have {have} — run this suite standalone or "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )


@pytest.fixture
def require_devices():
    """Callable fixture: ``require_devices(n)`` skips unless n devices."""
    return _require_devices


def _run_with_devices(body: str, n: int = 8) -> str:
    """Run a python script in a subprocess with ``n`` forced host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        env=env,
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.fixture(scope="session")
def run_with_devices():
    """Callable fixture: run a script in a subprocess with forced devices."""
    return _run_with_devices
