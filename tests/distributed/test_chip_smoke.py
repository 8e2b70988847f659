"""CPU rehearsal of ``chip_smoke.py``: its phases at a small grid.

The one-chip phase runs in interpret mode (``pallas_interpret``) against the
XLA executor; the four-chip phase runs on four virtual CPU devices.  The
script itself must refuse a host without a TPU.
"""

import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phase_interpret(chip_smoke, capsys):
    from repro.core import make_executor

    ex = make_executor("pallas_interpret")
    assert chip_smoke.one_chip_phase(8, ex, make_executor("xla"))
    out = capsys.readouterr().out
    assert "FAIL" not in out
    for op in ("spmv_dot_ell", "axpy_norm", "block_jacobi_apply"):
        assert f"[PASS] {op} served by pallas" in out


def test_four_chip_phase_virtual_devices(run_with_devices):
    out = run_with_devices(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from repro.core import make_executor
        assert mod.four_chip_phase(8, make_executor("pallas_interpret"))
        print("FOUR CHIP PHASE OK")
    """, n=4)
    assert "FOUR CHIP PHASE OK" in out
    assert "[PASS] result spans 4 devices" in out
    assert "[PASS] spmv_ell served by pallas" in out


def test_refuses_cpu_only_host():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, SCRIPT, "--n-side", "4"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
