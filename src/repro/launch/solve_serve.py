"""Persistent solve-service driver: continuous batching + setup cache.

Stands up :class:`repro.serve.SolveService`, replays a synthetic Poisson
request stream over a sparsity-pattern gallery (``repro.serve.traffic``),
and reports serving metrics: solves/sec, p50/p99 end-to-end latency (from
the sub-unit-bucketed ``serve_latency_s`` histogram), and setup-cache hit
rates per tier.

A warmup pass (one request per gallery pattern) absorbs jit compilation and
populates the pattern tier, as a long-running service would be; the measured
stream then runs against a warm cache.  The run ends with a greppable
``SERVE-GATE: PASS|FAIL`` line — the CI smoke gate — asserting that every
request converged, the cache actually hit, and p99 latency stayed under the
bound.

Usage:
    python -m repro.launch.solve_serve --smoke
    python -m repro.launch.solve_serve --requests 256 --rate-hz 200 \
        --gallery 4 --repeat-ratio 0.6 --slots 8 --p99-bound 0.5
"""

from __future__ import annotations

import argparse
import time

from repro.core import make_executor, use_executor
from repro.launch.cache import use_compile_cache
from repro.observability import metrics, trace
from repro.serve import ServeConfig, SolveService, TrafficConfig
from repro.serve.traffic import generate_traffic, pattern_gallery
from repro.serve.request import SolveRequest
from repro.solvers.common import Stop

__all__ = ["run_serve", "main"]


def _warmup(svc: SolveService, traffic_cfg: TrafficConfig) -> None:
    """One solve per gallery pattern: compiles closures, fills the cache."""
    import numpy as np

    rng = np.random.default_rng(traffic_cfg.seed + 97)
    ids = []
    for indptr, indices, make_values in pattern_gallery(traffic_cfg):
        req = SolveRequest(
            indptr=indptr, indices=indices, values=make_values()[2],
            b=rng.normal(size=traffic_cfg.n).astype(np.float32),
            shape=(traffic_cfg.n, traffic_cfg.n),
        )
        ids.append(svc.submit(req))
    svc.gather(ids, timeout=300.0)


def run_serve(
    config: ServeConfig,
    traffic_cfg: TrafficConfig,
    *,
    executor=None,
    pace: bool = True,
):
    """Warm up, replay the stream, and return ``(responses, wall_s)``."""
    traffic = generate_traffic(traffic_cfg)
    with SolveService(config, executor=executor) as svc:
        _warmup(svc, traffic_cfg)
        metrics.reset()  # measure the steady state, not compilation
        t0 = time.perf_counter()
        ids = []
        for gap, req in traffic:
            if pace and gap > 0:
                time.sleep(gap)
            ids.append(svc.submit(req))
        responses = svc.gather(ids, timeout=600.0)
        wall = time.perf_counter() - t0
    return responses, wall


def report(responses, wall: float, p99_bound: float) -> bool:
    num = len(responses)
    converged = sum(r.converged for r in responses)
    p_hits = sum(r.pattern_hit for r in responses)
    f_hits = sum(r.factors_hit for r in responses)
    iters = sum(r.iterations for r in responses)
    h = metrics.histogram("serve_latency_s")
    p50, p99 = h.quantile(0.5), h.quantile(0.99)
    rate = num / max(wall, 1e-9)

    print(f"solve_serve: {num} requests in {wall:.3f} s "
          f"({rate:.1f} solves/sec, {iters} total iterations)")
    print(f"  converged {converged}/{num}")
    print(f"  cache hits: pattern {p_hits}/{num}  factors {f_hits}/{num}")
    cache = {k: int(v) for k, v in sorted(metrics_cache_stats().items())}
    print(f"  cache counters: {cache}")
    print(f"  latency p50 = {_fmt_s(p50)}  p99 = {_fmt_s(p99)}  "
          f"(bound {p99_bound:g} s)")

    ok = (
        converged == num
        and p_hits > 0
        and p99 is not None
        and p99 < p99_bound
    )
    print(f"SERVE-GATE: {'PASS' if ok else 'FAIL'}")
    return ok


def metrics_cache_stats():
    out = {}
    for name in ("serve_cache_hits", "serve_cache_misses",
                 "serve_cache_evictions"):
        for tier in ("pattern", "values"):
            out[f"{name}_{tier}"] = metrics.counter(name, tier=tier).value
    return out


def _fmt_s(v) -> str:
    return "n/a" if v is None else f"{v * 1e3:.3g} ms"


def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small end-to-end run for CI (48 requests)")
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--rate-hz", type=float, default=200.0,
                    help="Poisson arrival rate of the synthetic stream")
    ap.add_argument("--gallery", type=int, default=4,
                    help="distinct sparsity patterns in the traffic")
    ap.add_argument("--repeat-ratio", type=float, default=0.6,
                    help="fraction of requests reusing a previous matrix")
    ap.add_argument("--n", type=int, default=24, help="rows per system")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=8,
                    help="batch slots per pattern lane")
    ap.add_argument("--chunk-sweeps", type=int, default=8,
                    help="masked sweeps per jitted advance chunk")
    ap.add_argument("--solver", default="cg", choices=("cg", "bicgstab"))
    ap.add_argument("--format", default="csr", choices=("csr", "ell"),
                    dest="fmt")
    ap.add_argument("--precond", default="block_jacobi",
                    choices=("block_jacobi", "none"))
    ap.add_argument("--block-size", type=int, default=4)
    ap.add_argument("--max-iters", type=int, default=500)
    ap.add_argument("--tol", type=float, default=1e-5)
    ap.add_argument("--p99-bound", type=float, default=2.0,
                    help="gate: p99 end-to-end latency must stay under this")
    ap.add_argument("--no-pace", action="store_true",
                    help="submit the whole stream at once (throughput mode)")
    ap.add_argument("--executor", default="xla")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="write the metrics registry snapshot here")
    trace.add_cli_flag(ap)
    args = ap.parse_args(argv)
    trace.enable_from_args(args)

    requests = 48 if args.smoke else args.requests
    gallery = min(args.gallery, 3) if args.smoke else args.gallery

    config = ServeConfig(
        slots=args.slots,
        chunk_sweeps=args.chunk_sweeps,
        solver=args.solver,
        fmt=args.fmt,
        precond=args.precond,
        block_size=args.block_size,
        stop=Stop(max_iters=args.max_iters, reduction_factor=args.tol),
    )
    traffic_cfg = TrafficConfig(
        num_requests=requests,
        rate_hz=args.rate_hz,
        gallery_size=gallery,
        repeat_ratio=args.repeat_ratio,
        n=args.n,
        seed=args.seed,
    )
    print(f"solve_serve: {requests} requests @ {args.rate_hz:g} Hz, "
          f"gallery={gallery} repeat={args.repeat_ratio:g}, "
          f"{args.solver}/{args.fmt}/{args.precond} slots={args.slots}, "
          f"seed={args.seed}, executor={args.executor}")

    ex = make_executor(args.executor)
    with use_executor(ex):
        responses, wall = run_serve(
            config, traffic_cfg, executor=ex, pace=not args.no_pace
        )
    ok = report(responses, wall, args.p99_bound)
    if args.metrics_jsonl:
        print(f"  metrics -> {metrics.export_jsonl(args.metrics_jsonl)}")
    if args.trace and trace.export():
        print(f"  trace -> {args.trace}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
