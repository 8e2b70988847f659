#!/usr/bin/env python3
"""Readings that set a cell's limits for ``correct``.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 [--requests N] [--out file.json]

In one process: the library is set up once for the cell (``run.set_up``),
then for each seed the requests a run would send (``--requests``, by
default one round of the mix), with ``x*`` drawn from the seed itself, go
through the timed path, ``Library.serve``, and each answer is checked
(``run.check_answers``); then the control (``control.py``: the plain
reference one precision down) answers the same requests of each control
seed.  Prints,
per number compared, the largest reading of the program (the lower reading)
and the smallest of the control (the upper reading).  The benchmark's own
runs never run this.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run  # noqa: E402


def worst(per_answer: list) -> dict:
    from chipbench import check

    return {k: max(r[k] for r in per_answer) for k in check.NUMBERS}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated program seeds")
    ap.add_argument("--control-seeds", default="", help="comma-separated")
    ap.add_argument("--requests", type=int, default=0,
                    help="requests per seed (default: one round of the mix)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    bench = run.prepare(args.workload)
    if bench is None:
        return 2
    summary = collect(bench, args.workload,
                      [int(s) for s in args.seeds.split(",") if s],
                      [int(s) for s in args.control_seeds.split(",") if s],
                      args.requests)
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    return 0


def collect(bench, name: str, seeds: list, control_seeds: list,
            n_requests: int = 0, log=print) -> dict:
    """The program's readings on ``seeds`` and the control's on
    ``control_seeds``, with the lower and upper reading of each number.
    Each seed's requests draw ``x*`` from the seed itself."""
    from chipbench import control, workload

    cell = bench.cell(name)
    config, mix = cell.config, cell.mix
    n_requests = n_requests or int(mix["requests_per_round"])
    system = cell.parts.system.build(config["system"])

    def pool(seed):
        return workload.make_pool(system, mix, seed, config["dtype"], xstar="seed")

    first = pool(seeds[0] if seeds else 0)
    lib = run.set_up(cell, system, first, log)
    program = {}
    for seed in seeds:
        t = time.perf_counter()
        requests = pool(seed)
        answers, iterations = [], []
        for k in run.order(n_requests, len(requests)):
            x, its = lib.serve(requests[k], {})
            answers.append((k, x))
            iterations.append(its)
        program[seed] = dict(worst(run.check_answers(system, requests, answers)),
                             iterations=iterations)
        log(f"program seed {seed}: {program[seed]} ({time.perf_counter() - t:.1f} s)")
    del lib

    controls = {}
    for seed in control_seeds:
        t = time.perf_counter()
        requests = pool(seed)
        answers = control.answers(cell, system, requests,
                                  run.order(n_requests, len(requests)))
        controls[seed] = worst(run.check_answers(system, requests, answers))
        log(f"control seed {seed} ({control.LOWER[config['dtype']]}): "
            f"{controls[seed]} ({time.perf_counter() - t:.1f} s)")

    numbers = list(cell.limits)
    return {
        "workload": name,
        "requests_per_seed": n_requests,
        "xstar": "seed",
        "program": program,
        "control": controls,
        "lower": {k: max(v[k] for v in program.values()) for k in numbers}
        if program else {},
        "upper": {k: min(v[k] for v in controls.values()) for k in numbers}
        if controls else {},
    }


if __name__ == "__main__":
    raise SystemExit(main())
