"""``BENCHMARK.json`` keeps the shape the benchmark is held to, and the
harness finds a configuration, a mix, a metric and a cell's limits by name,
so a later one is a new file and a new entry."""

import json
import os
import re
import shutil
import sys

import pytest

from chipbench.bench import Bench

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert spec["paths"] == ["chipbench"]
    assert spec["command"] == ["python3", "chipbench/run.py"]
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_entries_keep_their_shapes(spec):
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("chipbench/") and os.path.isfile(os.path.join(REPO, c["file"]))
        assert c["source"].startswith("https://") and 1 <= len(c["why"]) <= 200
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(1, len(spec["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in spec[k]]
    assert len(names) == len(set(names))


def test_end_to_end_metrics(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")


def test_per_layer_metrics_move_a_metric_their_cells_report(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    layers = {}
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m.get("workloads", cells)) <= cells
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]].get("workloads", cells)
        layers.setdefault(m["layer"], []).append(m["name"])
    assert {"solver", "preconditioner", "kernels", "collectives", "device"} <= set(layers)


def test_every_name_has_its_file(spec):
    bench = Bench(REPO)
    for w in spec["workloads"]:
        config = bench.config(w["config"])
        assert config["chips"] == w["chips"]
        bench.mix(w["traffic"])
        bench.limits(w["name"])
        assert hasattr(bench.system(config), "build")
        for kind in ("end_to_end", "per_layer"):
            for entry, module in bench.metrics(w["name"], kind):
                assert callable(module.read), entry["name"]


CG_UNFUSED = """
from chipbench.solvers.cg import reference  # noqa: F401
UNFUSED = True

def solve(A, b, M, stop, executor, precond_opts=None):
    from repro.solvers import krylov
    res = krylov.cg(A, b, M=M, precond_opts=precond_opts or None, stop=stop,
                    executor=executor, strict=False, fused=False)
    return res.x, res.iterations
"""


@pytest.mark.parametrize("key, value", [
    ("solver", "gmres"),
    ("format", "sellp"),
    ("precond", {"kind": "amg"}),
    ("dtype", "float16"),
    ("format", "dist_ell"),  # a distributed format on one chip
    ("solver", "../check"),
])
def test_a_part_the_harness_lacks_is_refused(tmp_path, key, value):
    """A configuration that names a solver, format, preconditioner or dtype
    with no module (or pairs a format with the wrong number of chips) is
    refused before anything runs, not run as something else."""
    bench = Bench(REPO)
    config = dict(bench.config("poisson3d-128-bj"), **{key: value})
    with pytest.raises(ValueError):
        bench.parts(config)


def test_every_part_module_has_its_api(spec):
    from chipbench import bench as bench_module

    for kind, api in bench_module.PART_API.items():
        for name in os.listdir(os.path.join(HERE, kind)):
            if name.endswith(".py"):
                module = bench_module.part(kind, name[:-3])
                assert all(hasattr(module, a) for a in api), (kind, name)


def test_a_new_config_mix_metric_and_cell_are_found_by_name(tmp_path):
    """Everything a later cell needs is a new file plus its entries, a new
    solver among them: the harness's own files stay as they are."""
    root = str(tmp_path)
    shutil.copytree(HERE, os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    d = os.path.join(root, "chipbench")
    def files():
        return {os.path.join(dirpath, n): open(os.path.join(dirpath, n), "rb").read()
                for dirpath, _, names in os.walk(d) for n in names
                if "__pycache__" not in dirpath}

    before = files()

    with open(os.path.join(d, "configs", "poisson3d-64-jacobi.json"), "w") as f:
        json.dump({"system": {"generator": "poisson7", "n_side": 8}, "format": "ell",
                   "solver": "cg_unfused", "dtype": "float32",
                   "precond": {"kind": "jacobi"}, "chips": 1,
                   "stop": {"reduction_factor": 1e-6, "max_iters": 100}}, f)
    with open(os.path.join(d, "solvers", "cg_unfused.py"), "w") as f:
        f.write(CG_UNFUSED)
    with open(os.path.join(d, "traffic", "bursty.json"), "w") as f:
        json.dump({"operator": "fixed", "diag_shift_dt": [5], "pool": 1,
                   "requests_per_round": 2}, f)
    with open(os.path.join(d, "metrics", "answers.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx.requests)\n")
    shutil.copy(os.path.join(d, "limits", "poisson3d-128-bj.rhs.json"),
                os.path.join(d, "limits", "poisson3d-64-jacobi.bursty.json"))
    spec["configs"].append({"name": "poisson3d-64-jacobi", "source": "https://example.org",
                            "file": "chipbench/configs/poisson3d-64-jacobi.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "poisson3d-64-jacobi.bursty",
                              "config": "poisson3d-64-jacobi", "traffic": "bursty",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "answers", "unit": "n", "better": "higher",
                              "source": "program_counter", "layer": "solver",
                              "moves": "solution_s",
                              "workloads": ["poisson3d-64-jacobi.bursty"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    bench = Bench(root)
    assert bench.config("poisson3d-64-jacobi")["precond"] == {"kind": "jacobi"}
    assert bench.cell("poisson3d-64-jacobi.bursty").parts.solver.UNFUSED
    assert bench.mix("bursty")["diag_shift_dt"] == [5]
    assert set(bench.limits("poisson3d-64-jacobi.bursty")) == {"resid", "resid_inf"}
    per_layer = dict((e["name"], m) for e, m in
                     bench.metrics("poisson3d-64-jacobi.bursty", "per_layer"))
    assert "answers" in per_layer and "spmv_roofline" not in per_layer
    assert "answers" not in dict(
        (e["name"], m) for e, m in bench.metrics("poisson3d-128-bj.rhs", "per_layer"))
    after = files()
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 5  # the five new files

    if os.path.join(REPO, "src") not in sys.path:
        sys.path.insert(0, os.path.join(REPO, "src"))
    from chipbench.run import run_cell

    res = run_cell(bench, "poisson3d-64-jacobi.bursty", 3, 0.1, False, log=lambda *a: None)
    assert res["correct"] and res["attempted"] % 2 == 0


def test_a_mix_that_lacks_a_key_is_refused(tmp_path):
    from chipbench import workload

    with pytest.raises(KeyError):
        workload.validate_mix({"operator": "fixed", "pool": 1}, "broken")
    with pytest.raises(ValueError):
        workload.validate_mix({"operator": "fixed", "diag_shift_dt": [1, 2], "pool": 1,
                               "requests_per_round": 1}, "two shifts")
