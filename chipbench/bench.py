"""Finds what ``BENCHMARK.json`` names, each in a file of its own.

Under the benchmark's directory (the first of ``paths``):

- a configuration is the ``file`` its entry names.  Its keys name the parts
  it is built from, each a module of its own: ``system.generator`` names
  ``systems/<generator>.py`` (the plain reference of the operator),
  ``format`` names ``formats/<format>.py`` (the program's conversion),
  ``solver`` names ``solvers/<solver>.py`` (the program's entry point and the
  plain reference of the method), ``precond.kind`` names
  ``preconds/<kind>.py`` (the program's generation, the plain reference of
  the apply, and its byte count); ``dtype`` is one that ``control.LOWER``
  knows.  A name with no module is refused before anything runs;
- a traffic mix is ``traffic/<name>.json`` (read by ``workload.py``);
- a metric, end to end or per layer, is ``metrics/<name>.py`` with a
  function ``read(ctx)`` that returns the value in the metric's unit, or
  ``None`` where it finds nothing to read;
- a cell's limits for ``correct`` are ``limits/<cell>.json``.

A later cell, mix, metric, configuration, solver, format or preconditioner
is a new file and a new entry: nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

from chipbench import check, control, workload

HERE = os.path.dirname(os.path.abspath(__file__))
#: what a module of each kind must define
PART_API = {
    "systems": ("build", "device_operator"),
    "formats": ("DISTRIBUTED", "convert"),
    "solvers": ("solve", "reference"),
    "preconds": ("generate", "operand_bytes", "reference_operand", "reference_apply"),
}
PART_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_\-]{0,63}$")


@dataclasses.dataclass(frozen=True)
class Parts:
    """The modules a configuration is built from."""

    system: object
    format: object
    solver: object
    precond: object


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    mix: dict
    parts: Parts
    limits: dict


class Bench:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        self.dir = os.path.join(self.root, self.spec["paths"][0])

    def _entry(self, key: str, name: str) -> dict:
        for entry in self.spec[key]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"BENCHMARK.json has no {key} entry {name!r}")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        with open(os.path.join(self.root, self._entry("configs", name)["file"])) as f:
            return json.load(f)

    def mix(self, name: str) -> dict:
        with open(os.path.join(self.dir, "traffic", f"{name}.json")) as f:
            return workload.validate_mix(json.load(f), name)

    def limits(self, cell: str) -> dict:
        return check.load_limits(os.path.join(self.dir, "limits", f"{cell}.json"))

    def part(self, kind: str, name: str):
        """The module ``<kind>/<name>.py``; a name with none is refused."""
        return part(kind, name, self.dir)

    def system(self, config: dict):
        """The plain reference module of the configuration's operator."""
        return self.part("systems", config["system"]["generator"])

    def parts(self, config: dict) -> Parts:
        """The configuration's parts, each found by the name it gives; a
        name, dtype or pairing the harness does not implement is refused."""
        dtype = config["dtype"]
        if dtype not in control.LOWER:
            raise ValueError(f"no dtype {dtype!r} in the harness "
                             f"(known: {sorted(control.LOWER)})")
        parts = Parts(
            system=self.system(config),
            format=self.part("formats", config["format"]),
            solver=self.part("solvers", config["solver"]),
            precond=self.part("preconds", config["precond"]["kind"]),
        )
        chips = int(config["chips"])
        if bool(parts.format.DISTRIBUTED) != (chips > 1):
            raise ValueError(f"format {config['format']!r} on {chips} chip(s): a "
                             "distributed format takes several chips, any other one")
        return parts

    def cell(self, name: str) -> Cell:
        entry = self.workload(name)
        config = self.config(entry["config"])
        if int(config["chips"]) != int(entry["chips"]):
            raise ValueError(f"cell {name} asks for {entry['chips']} chip(s), its "
                             f"configuration states {config['chips']}")
        return Cell(name=name, chips=int(entry["chips"]), config=config,
                    mix=self.mix(entry["traffic"]), parts=self.parts(config),
                    limits=self.limits(name))

    def metrics(self, cell: str, kind: str) -> list:
        """``[(entry, module)]`` of the ``end_to_end`` or ``per_layer``
        metrics this cell reports."""
        return [
            (m, _load(os.path.join(self.dir, "metrics", f"{m['name']}.py")))
            for m in self.spec[kind]
            if cell in m.get("workloads", [cell])
        ]


def part(kind: str, name: str, base: str = HERE):
    """The module ``<base>/<kind>/<name>.py``, which defines what
    :data:`PART_API` asks of its kind; anything else is refused."""
    if kind not in PART_API:
        raise ValueError(f"no kind of part {kind!r}")
    path = os.path.join(base, kind, f"{name}.py")
    if not isinstance(name, str) or not PART_NAME.match(name) or not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(os.path.join(base, kind))
                       if f.endswith(".py") and not f.startswith("_"))
        raise ValueError(f"no {kind} {name!r} in the harness (known: {known})")
    module = _load(path)
    missing = [a for a in PART_API[kind] if not hasattr(module, a)]
    if missing:
        raise ValueError(f"{kind}/{name}.py lacks {missing}")
    return module


def _load(path: str):
    kind = os.path.basename(os.path.dirname(path))
    stem = os.path.splitext(os.path.basename(path))[0]
    name = f"chipbench_{kind}_{stem}".replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module  # dataclasses look their module up here
    spec.loader.exec_module(module)
    return module
