"""Whole runs on the CPU at a small size: a sound run is correct, a run with
the timed path broken underneath is not, the control fails the limits, and
with no TPU a run exits non-zero and prints no result."""

import contextlib
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from chipbench import check
from chipbench.conftest import REPO, make_tiny_root

ONE_CHIP = ("poisson3d-128-bj.rhs", "poisson3d-128-bj.transient")
FOUR_CHIPS = "poisson3d-128-jacobi-x4.rhs"
FAULTS = ("state_unchanged", "half_left_out", "answer_altered")


@contextlib.contextmanager
def planted(fault: str):
    """Break the program's timed path underneath the harness:

    - ``state_unchanged``: the solver's loop returns the state it was given;
    - ``half_left_out``: the SpMV leaves out the second half of the rows,
      and its fused reduction doubles the first half's (the mean over the
      rest);
    - ``exchange_left_out``: the halo all-gather between chips delivers zeros;
    - ``answer_altered``: one entry of the solution is changed where the
      solver produces it.
    """
    import jax
    import jax.numpy as jnp

    from repro.distributed import solvers as dist_solvers
    from repro.solvers import krylov
    from repro.sparse import ops

    saved = []

    def patch(obj, name, value):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if fault == "state_unchanged":
        patch(jax.lax, "while_loop", lambda cond, body, init: init)
    elif fault == "half_left_out":
        apply, spmv_dot = ops.apply, ops.spmv_dot

        def half(y):
            return y.at[y.shape[0] // 2:].set(0)

        def half_spmv_dot(A, x, w=None, *, executor=None):
            y = half(spmv_dot(A, x, w, executor=executor)[0])
            k = y.shape[0] // 2
            return y, 2 * jnp.vdot((x if w is None else w)[:k], y[:k])

        patch(ops, "apply", lambda A, x, *, executor=None: half(apply(A, x, executor=executor)))
        patch(ops, "spmv_dot", half_spmv_dot)
    elif fault == "exchange_left_out":
        all_gather = jax.lax.all_gather
        patch(jax.lax, "all_gather",
              lambda x, axis_name, **kw: jnp.zeros_like(all_gather(x, axis_name, **kw)))
    elif fault == "answer_altered":
        cg = krylov.cg

        def altered_cg(*args, **kwargs):
            res = cg(*args, **kwargs)
            return dataclasses.replace(res, x=res.x.at[0].add(1.0))

        patch(krylov, "cg", altered_cg)
    else:
        raise ValueError(fault)
    dist_solvers._JIT_CACHE.clear()  # compiled sound closures must not serve
    try:
        yield
    finally:
        for obj, name, value in reversed(saved):
            setattr(obj, name, value)
        dist_solvers._JIT_CACHE.clear()


def quiet(*args):
    pass


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_a_sound_run_is_correct(tiny_bench, cell):
    from chipbench.run import run_cell

    res = run_cell(tiny_bench, cell, 2 ** 33 + 5, 0.2, False, log=quiet)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "check"]
    assert set(res["metrics"]) == {"solution_s", "setup_s"}  # no peak count on the CPU
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["check"]) == set(check.NUMBERS)
    if cell.endswith("transient"):
        assert res["attempted"] % 3 == 0  # whole rounds of the dt cycle


@pytest.mark.parametrize("cell", ONE_CHIP)
@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(tiny_bench, cell, fault):
    from chipbench.run import run_cell

    with planted(fault):
        res = run_cell(tiny_bench, cell, 11, 0.2, False, log=quiet)
    assert not res["correct"] and res["failed"] >= 1
    assert any(v["value"] > v["limit"] for v in res["check"].values())


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_the_control_fails_the_limits(tiny_bench, cell):
    """The plain reference in bfloat16, in the program's place, on the same
    requests: some number of every seed is over its limit, while the
    program's readings are under every limit."""
    from chipbench.readings import collect

    limits = tiny_bench.limits(cell)
    out = collect(tiny_bench, cell, [3, 2 ** 35], [4, 5, 2 ** 36], log=quiet)
    for reading in out["program"].values():
        assert all(reading[k] <= limits[k] for k in check.NUMBERS)
    for reading in out["control"].values():
        assert any(reading[k] > limits[k] for k in check.NUMBERS)


FOUR_CHIP_CHILD = """
import json, sys
sys.path[:0] = [{repo!r}, {src!r}]
from chipbench.bench import Bench
from chipbench.readings import collect
from chipbench.run import run_cell
from chipbench.test_chipbench_run import planted, quiet
bench = Bench({root!r})
out = {{"sound": run_cell(bench, {cell!r}, 2 ** 34 + 1, 0.2, False, log=quiet)["correct"]}}
for fault in {faults!r}:
    with planted(fault):
        out[fault] = run_cell(bench, {cell!r}, 17, 0.2, False, log=quiet)["correct"]
limits = bench.limits({cell!r})
r = collect(bench, {cell!r}, [], [6, 7, 2 ** 37], log=quiet)
out["control_fails"] = all(any(v[k] > limits[k] for k in limits) for v in r["control"].values())
print(json.dumps(out))
"""


def test_four_chip_faults_and_control(tmp_path):
    """On four CPU devices: a sound run is correct; each fault the cell can
    have (the three above and the halo exchange left out) makes it not
    correct; the control fails the limits."""
    root = make_tiny_root(str(tmp_path))
    faults = FAULTS + ("exchange_left_out",)
    code = FOUR_CHIP_CHILD.format(repo=REPO, src=os.path.join(REPO, "src"), root=root,
                                  cell=FOUR_CHIPS, faults=faults)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, **{f: False for f in faults}, "control_fails": True}


def test_no_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cwd, root in ((REPO, REPO), (str(tmp_path), make_tiny_root(str(tmp_path)))):
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "chipbench", "run.py"),
             "--workload", "poisson3d-128-bj.rhs", "--seed", str(2 ** 40),
             "--seconds", "1", "--trace", "0"],
            cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode != 0
        assert '"correct"' not in proc.stdout
        assert "needs 1 TPU chip" in proc.stderr
