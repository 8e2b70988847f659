"""The multigrid cell: the program's hierarchy and cycle against the plain
float64 reference (``preconds/sa_amg.py``), whole CPU runs of the cell at a
small size, and its two per-layer metrics."""

import types

import numpy as np
import pytest

from chipbench import check
from chipbench.bench import Bench, part
from chipbench.conftest import REPO
from chipbench.scopes import ScopedEvent, ScopedTrace
from chipbench.test_chipbench_run import FAULTS, planted, quiet
from chipbench.tracing import Event

CELL = "poisson3d-128-amg.rhs"
CONFIG = "poisson3d-128-amg"
#: the cycle's relative 2-norm error against float64: float32 accumulation
#: through the levels' Galerkin products drifts about 1e-5 at 128^3
CYCLE_TOL = 1e-4


@pytest.fixture(scope="module")
def opts():
    config = Bench(REPO).config(CONFIG)
    return {k: v for k, v in config["precond"].items() if k != "kind"}


def _program_levels(M, samples):
    gauges = {(s["name"], s["labels"].get("level")): int(s["value"]) for s in samples}
    depth = len(M.levels)
    return ([(gauges["amg_level_rows", str(k)], gauges["amg_level_nnz", str(k)],
              gauges["amg_transfer_nnz", str(k)]) for k in range(depth)]
            + [(gauges["amg_level_rows", str(depth)], gauges["amg_level_nnz", str(depth)],
                None)])


@pytest.mark.parametrize("n_side", [16, 32])
def test_the_hierarchy_and_cycle_agree_with_the_float64_reference(n_side, opts):
    """Level by level the same rows, stored entries and transfer entries;
    the program's float32 cycle within :data:`CYCLE_TOL` of the reference's
    float64 one, and the reference's own cycle in bfloat16 outside it."""
    import jax
    import jax.numpy as jnp

    from repro import sparse
    from repro.core import make_executor
    from repro.observability import metrics

    from chipbench.systems import poisson7

    ref = part("preconds", "sa_amg")
    system = poisson7.host_csr(n_side)
    A = sparse.ell_from_csr_host(system.indptr, system.indices, system.values,
                                 (system.n, system.n))
    metrics.reset()
    M = ref.generate(A, opts, make_executor("xla"))
    t = ref.reference_operand(system, system.values, opts)
    assert _program_levels(M, metrics.samples()) == ref.levels_of(t)

    r = np.random.default_rng(n_side).standard_normal(system.n)
    cycle = jax.jit(lambda t, v: ref.reference_apply(t, v, opts))
    with jax.enable_x64(True):
        want = np.asarray(cycle(jnp.asarray(t), jnp.asarray(r)))
    got = np.asarray(jax.jit(lambda M, v: M.apply(v))(M, jnp.asarray(r, jnp.float32)))
    low = np.asarray(cycle(jnp.asarray(t, jnp.bfloat16), jnp.asarray(r, jnp.bfloat16)),
                     np.float64)

    def err(y):
        return np.linalg.norm(y - want) / np.linalg.norm(want)

    assert err(got) <= CYCLE_TOL
    assert err(low) > CYCLE_TOL


def test_the_cells_seeds_draw_images_of_one_right_hand_side():
    """The cell's system is ``poisson7``: a seed picks one of its 32
    symmetry images of one fixed draw, so every ``b`` has the same norm and
    seeds differ in more than the sign."""
    from chipbench import workload

    cell = Bench(REPO).cell(CELL)
    system = cell.parts.system.build({"n_side": 8})
    assert system.images == 32 and system.n == 512
    bs = [workload.make_pool(system, cell.mix, seed, "float32")[0].b
          for seed in range(16)]
    norms = [np.linalg.norm(b.astype(np.float64)) for b in bs]
    assert max(norms) == pytest.approx(min(norms), rel=1e-6)
    distinct = {np.abs(b).tobytes() for b in bs}
    assert len(distinct) > 2


def test_the_reference_refuses_what_it_does_not_implement(opts):
    ref = part("preconds", "sa_amg")
    from chipbench.systems import poisson7

    system = poisson7.host_csr(4)
    for key, value in (("cycle", "w"), ("smoother", "block_jacobi"),
                       ("coarse_solver", "cg")):
        with pytest.raises(ValueError):
            ref.reference_operand(system, system.values, dict(opts, **{key: value}))
    with pytest.raises(ValueError, match="level counters"):
        ref.operand_bytes(system.n, opts, 4)


def test_a_sound_run_is_correct(tiny_bench):
    from chipbench.run import run_cell

    res = run_cell(tiny_bench, CELL, 2 ** 33 + 9, 0.2, False, log=quiet)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"solution_s", "setup_s"}  # no peak count on the CPU


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(tiny_bench, fault):
    from chipbench.run import run_cell

    with planted(fault):
        res = run_cell(tiny_bench, CELL, 13, 0.2, False, log=quiet)
    assert not res["correct"] and res["failed"] >= 1


def test_the_control_fails_the_limits(tiny_bench):
    from chipbench.readings import collect

    limits = tiny_bench.limits(CELL)
    out = collect(tiny_bench, CELL, [3, 2 ** 35], [4, 5, 2 ** 36], log=quiet)
    for reading in out["program"].values():
        assert all(reading[k] <= limits[k] for k in check.NUMBERS)
    for reading in out["control"].values():
        assert any(reading[k] > limits[k] for k in check.NUMBERS)


def test_the_cell_reports_the_metrics_its_path_defines():
    spec = Bench(REPO)
    per_layer = {e["name"] for e, _ in spec.metrics(CELL, "per_layer")}
    assert per_layer == {"iterations", "device_idle", "mg_cycle_roofline", "mg_coarse_share"}


# -- the metrics ------------------------------------------------------------------
LOOP = "jit(_solve_one_chip)/while/body/Multigrid.apply/"


def _samples(levels):
    out = []
    for k, (rows, nnz, transfer) in enumerate(levels):
        out += [{"name": "amg_level_rows", "labels": {"level": str(k)}, "value": rows},
                {"name": "amg_level_nnz", "labels": {"level": str(k)}, "value": nnz}]
        if transfer is not None:
            out.append({"name": "amg_transfer_nnz", "labels": {"level": str(k)},
                        "value": transfer})
    return out


def _metric(name):
    return dict((e["name"], m) for e, m in Bench(REPO).metrics(CELL, "per_layer"))[name]


def test_cycle_min_bytes_counts_each_application_once():
    roof = _metric("mg_cycle_roofline")
    levels = [(1000, 6400, 5000), (100, 2000, 600), (10, 100, None)]
    sizes = roof.hierarchy_sizes(_samples(levels))
    assert sizes == levels
    # A twice, P and R once, the inverse diagonal once a sweep; the dense inverse
    want = (2 * 6400 + 5000 + 2 * 1000) + (2 * 2000 + 600 + 2 * 100) + 10 * 10
    assert roof.cycle_min_bytes(sizes, 4) == 4 * want
    # a program that counts no transfers, or not the rows of a level it built
    assert roof.hierarchy_sizes(_samples([(1000, 6400, None), (10, 100, None)])) == []
    assert roof.hierarchy_sizes(_samples(levels)[1:]) == []
    assert roof.hierarchy_sizes(_samples(levels[:2])) == []


def _ctx(iterations=(3, 5)):
    return types.SimpleNamespace(
        summary=types.SimpleNamespace(busy_s={0: 4.0}),
        requests=[{"iterations": k, "clock": {}} for k in iterations],
        config={"precond": {"kind": "sa_amg"}, "dtype": "float32"},
        lib=types.SimpleNamespace(distributed=False),
        peak=lambda key: 819e9,
    )


def _trace():
    """Device 0 over a 10 s window: the fine level's sweeps over [1, 3],
    the transfers and the coarser levels over [3, 4], the CG's own ops over
    [4, 5]."""
    ops = [
        ScopedEvent("fusion.1 fusion f32[8]", 1.0, 3.0,
                    scope=LOOP + "Multigrid.level0/spmv_ell/gather"),
        ScopedEvent("fusion.2 fusion f32[8]", 3.0, 3.25,
                    scope=LOOP + "Multigrid.restrict0/spmv_ell/gather"),
        ScopedEvent("fusion.3 fusion f32[8]", 3.25, 3.5,
                    scope=LOOP + "Multigrid.level1/spmv_ell/gather"),
        ScopedEvent("dot.4 dot f32[8]", 3.5, 3.75, scope=LOOP + "Multigrid.coarse/dot"),
        ScopedEvent("fusion.5 fusion f32[8]", 3.75, 4.0,
                    scope=LOOP + "Multigrid.prolong0/spmv_ell/gather"),
        ScopedEvent("fusion.6 fusion f32[8]", 4.0, 5.0,
                    scope="jit(_solve_one_chip)/while/body/spmv_dot_ell/gather"),
    ]
    return ScopedTrace({0: ops}, [Event("window", 0.0, 10.0)], [])


def test_the_multigrid_metrics_read_their_scopes(monkeypatch):
    from chipbench import scopes
    from repro.observability import metrics

    monkeypatch.setattr(scopes, "load", lambda ctx: _trace())
    levels = [(1000, 6400, 5000), (100, 2000, 600), (10, 100, None)]
    monkeypatch.setattr(metrics, "samples", lambda: _samples(levels))
    calls = (3 + 1) + (5 + 1)
    roof = _metric("mg_cycle_roofline")
    least = roof.cycle_min_bytes(levels, 4)
    assert roof.read(_ctx()) == pytest.approx(100.0 * least / 819e9 / (3.0 / calls))
    assert _metric("mg_coarse_share").read(_ctx()) == pytest.approx(100.0 * 1.0 / 3.0)
    # a program without the transfer counter (an older one) reads nothing
    monkeypatch.setattr(metrics, "samples", lambda: _samples([(1000, 6400, None)]))
    assert roof.read(_ctx()) is None


def test_the_multigrid_metrics_read_nothing_without_their_scopes(monkeypatch, capsys):
    from chipbench import scopes

    t = _trace()
    t.devices = {0: [ScopedEvent(e.name, e.start, e.end, e.collective, "jit(f)/while")
                     for e in t.devices[0]]}
    monkeypatch.setattr(scopes, "load", lambda ctx: t)
    assert _metric("mg_coarse_share").read(_ctx()) is None
    assert "chipbench: no " in capsys.readouterr().err
    untraced = _ctx()
    untraced.summary = None
    assert _metric("mg_cycle_roofline").read(untraced) is None
    assert _metric("mg_coarse_share").read(untraced) is None
