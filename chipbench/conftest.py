"""Fixtures of the benchmark's CPU tests: a copy of the benchmark whose
configurations run at a size the CPU holds."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
#: grid side of the copies (8 divides it, as it divides 128)
TINY_SIDE = 16


def make_tiny_root(dest: str, n_side: int = TINY_SIDE) -> str:
    """Copy ``BENCHMARK.json`` and the benchmark's directory to ``dest`` and
    shrink every configuration's grid to ``n_side`` (with the rows, entries
    and slab rows it states)."""
    shutil.copytree(HERE, os.path.join(dest, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    configs = os.path.join(dest, "chipbench", "configs")
    for name in os.listdir(configs):
        path = os.path.join(configs, name)
        with open(path) as f:
            config = json.load(f)
        config["system"].update(n_side=n_side, rows=n_side ** 3,
                                nnz=7 * n_side ** 3 - 6 * n_side ** 2)
        if "local_rows" in config:
            config["local_rows"] = n_side ** 3 // config["chips"]
        with open(path, "w") as f:
            json.dump(config, f)
    return dest


@pytest.fixture
def tiny_bench(tmp_path):
    """A :class:`Bench` over a shrunken copy; the program is importable."""
    if os.path.join(REPO, "src") not in sys.path:
        sys.path.insert(0, os.path.join(REPO, "src"))
    from chipbench.bench import Bench

    return Bench(make_tiny_root(str(tmp_path)))
