#!/usr/bin/env python3
"""Chip benchmark of the library: one cell per run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a configuration
under a traffic mix.  A run makes the system and the request pool from the
seed, sets up the library and warms up every shape the cell uses (all of it
``setup_s``), then starts requests back to back, one client, until
``--seconds`` have passed and the round in flight has finished: that is the
window.  After the window every answer is checked against float64 on the
host (``check.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
window under the profiler and reports its per-layer metrics.  The last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``check``, each compared number beside its limit); the last lines of
standard error repeat the check.  With no TPU, or fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()  # setup_s counts from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the fixed compile-cache directory inside the checkout (ignored by git,
#: like the traces beside it)
CACHE_DIR = os.path.join(ROOT, ".chipbench", "jax_cache")


class Context:
    """What a metric's ``read(ctx)`` may look at."""

    def __init__(self, *, cell, config, mix, lib, device_kind, requests,
                 setup_s, window_s, memory_peak_bytes, summary, trace_dir):
        self.cell = cell
        self.config = config
        self.mix = mix
        self.lib = lib
        self.device_kind = device_kind
        self.requests = requests  # [{"iterations": int, "clock": {span: s}}]
        self.setup_s = setup_s
        self.window_s = window_s
        self.memory_peak_bytes = memory_peak_bytes
        self.summary = summary  # tracing.Summary of the window, or None
        self._trace_dir = trace_dir

    def peak(self, key: str) -> float:
        from chipbench import roofline

        return roofline.peak(self.device_kind, key)

    def probe(self, name: str, fn, *args, calls: int = 20) -> float:
        """Device seconds per call of ``fn(*args)``, jitted alone and run
        ``calls`` times under the profiler (compiled before it starts)."""
        import jax

        from chipbench import tracing

        f = jax.jit(fn)
        jax.block_until_ready(f(*args))
        log_dir = os.path.join(self._trace_dir, f"probe-{name}")
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(log_dir, profiler_options=_profile_options())
        try:
            for _ in range(calls):
                jax.block_until_ready(f(*args))
        finally:
            jax.profiler.stop_trace()
        trace = tracing.read_xspace(tracing.newest_xspace(log_dir), ())
        return tracing.device_seconds(trace) / calls


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # the harness's spans; no runtime internals
    return opts


def _memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _window(lib, pool, round_size: int, seconds: float):
    """Requests back to back until ``seconds`` have passed and a round is
    whole: ``(answers, requests, window_s, compiles)``, where an answer is
    ``(pool index, x)`` and ``compiles`` counts the programs the window
    asked the compiler or its cache for (none, when set-up warmed up all)."""
    import jax

    from chipbench import tracing

    compiles = []

    def on_event(event, **kwargs):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            compiles.append(event)

    jax.monitoring.register_event_listener(on_event)
    answers, requests = [], []
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            while True:
                k = len(answers) % len(pool)
                clock = {}
                x, iterations = lib.serve(pool[k], clock)
                answers.append((k, x))
                requests.append({"iterations": iterations, "clock": clock})
                if (time.perf_counter() - t0 >= seconds
                        and len(answers) % round_size == 0):
                    break
        window_s = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_listener(on_event)
    return answers, requests, window_s, len(compiles)


def order(n_requests: int, pool_size: int) -> list:
    """The pool index of each of a run's first ``n_requests`` requests."""
    return [j % pool_size for j in range(n_requests)]


def set_up(cell, system, pool, log=print):
    """The library for ``cell``, its fixed operator converted and generated
    and every program it runs compiled (or loaded from the cache) by a
    warm-up request with ``b = 0``, which runs no iteration."""
    import numpy as np

    from chipbench import library, workload

    lib = library.Library(cell, system)
    t = time.perf_counter()
    lib.setup(pool[0].values)
    log(f"set-up of the fixed operator: {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    lib.serve(workload.Request(pool[0].values, np.zeros(system.n, lib.dtype)), {})
    log(f"warm-up (compile or cache load): {time.perf_counter() - t:.3f} s")
    return lib


def check_answers(system, pool, answers) -> list:
    """The compared numbers of each answer ``(pool index, x)``."""
    from chipbench import check

    return [check.readings(system.indptr, system.indices, pool[k].values, pool[k].b, x)
            for k, x in answers]


def run_cell(bench, name: str, seed: int, seconds: float, trace: bool,
             *, t_start: float = None, log=print) -> dict:
    """One run of cell ``name``; returns the result object.  Looks for no
    chip: :func:`main` does that before it calls here."""
    import jax

    from chipbench import check, library, tracing, workload

    t_start = time.perf_counter() if t_start is None else t_start
    log(f"process start to the harness: {time.perf_counter() - t_start:.3f} s")
    cell = bench.cell(name)
    config, mix = cell.config, cell.mix
    metrics = bench.metrics(name, "per_layer" if trace else "end_to_end")
    devices = jax.devices()[:cell.chips]

    t = time.perf_counter()
    system = cell.parts.system.build(config["system"])
    pool = workload.make_pool(system, mix, seed, config["dtype"])
    log(f"system: {config['system']['generator']} n={system.n} nnz={system.nnz}; "
        f"pool of {len(pool)} requests from seed {seed}: "
        f"{time.perf_counter() - t:.3f} s")
    lib = set_up(cell, system, pool, log)
    log("served by: " + " ".join(f"{op}={space}"
                                 for op, space in lib.served_spaces().items()))
    setup_s = time.perf_counter() - t_start

    trace_dir = os.path.join(bench.root, ".chipbench", "trace", name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(os.path.join(trace_dir, "window"),
                                 profiler_options=_profile_options())
    answers, requests, window_s, n_compiles = _window(
        lib, pool, int(mix["requests_per_round"]), seconds)
    summary = None
    if trace:
        jax.profiler.stop_trace()
        summary = tracing.summarize(tracing.read_xspace(
            tracing.newest_xspace(os.path.join(trace_dir, "window")), library.SPANS))
    memory_peak = _memory_peak(devices)
    its = [r["iterations"] for r in requests]
    log(f"window: {len(answers)} requests in {window_s:.3f} s, iterations "
        f"{min(its)}..{max(its)}, compiles in window {n_compiles}")

    ctx = Context(cell=bench.workload(name), config=config, mix=mix, lib=lib,
                  device_kind=devices[0].device_kind, requests=requests,
                  setup_s=setup_s, window_s=window_s,
                  memory_peak_bytes=memory_peak, summary=summary,
                  trace_dir=trace_dir)
    values = {}
    for entry, module in metrics:
        value = module.read(ctx)
        if value is not None:
            values[entry["name"]] = {"value": value, "unit": entry["unit"]}
    del ctx, lib
    gc.collect()

    correct, failed, worst = check.judge(check_answers(system, pool, answers), cell.limits)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    result = {"correct": correct, "attempted": len(answers), "failed": failed,
              "metrics": values, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = tracing.breakdown(summary)
    result["check"] = worst
    return result


def prepare(name: str):
    """Make the program importable, find the chips cell ``name`` needs, and
    fix the compile cache; ``None`` (after saying why) where the chips are
    not there or their peaks are not known."""
    # libtpu logs to a fixed path under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    import jax

    from chipbench import roofline
    from chipbench.bench import Bench

    bench = Bench(ROOT)
    chips = bench.cell(name).chips  # refuses a part the harness lacks
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"chipbench: cell {name} needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return None
    try:
        roofline.peak(devices[0].device_kind, "hbm_bytes_per_s")
    except KeyError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = prepare(args.workload)
    if bench is None:
        return 2
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
    for k, v in result["check"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
