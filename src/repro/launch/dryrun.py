import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init): the dry-run — and only the dry-run — sees 512 host
placeholder devices so ``jax.make_mesh`` can build the production meshes.

Per cell this driver:

  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs abstract params / optimizer state / batch / cache
     (ShapeDtypeStruct only — nothing is allocated),
  3. ``jax.jit(step, in_shardings=..., out_shardings=...).lower(...)``,
  4. ``.compile()`` — sharding mismatches, non-divisible layouts, or OOM
     surface here and are bugs in the framework,
  5. records ``compiled.memory_analysis()``, ``compiled.cost_analysis()`` and
     the collective-byte census parsed from the optimized HLO
     into ``experiments/dryrun/<cell>.json`` for the §Roofline analysis.

Usage:
    python -m repro.launch.dryrun --arch granite-8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--zero zero1|fsdp]
"""

import argparse
import dataclasses
import functools
import json
import re
import time
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, cells, get_config, ARCH_IDS
from repro.core.params import TPU_V5E
from repro.distributed import sharding as shd
from repro.launch import costmodel
from repro.launch import steps as steps_lib
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.observability import trace
from repro.optim import adamw, warmup_cosine_schedule

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")

# per-chip hardware constants (TPU v5e) for the roofline terms
PEAK_FLOPS = TPU_V5E.peak_flops_bf16
HBM_BW = TPU_V5E.hbm_bandwidth
ICI_BW = TPU_V5E.ici_bandwidth

_COLLECTIVE_RE = re.compile(
    r"=\s*(?P<out>\([^)]*\)|\S+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"(?P<dtype>[a-z][a-z0-9]+)\[(?P<dims>[\d,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}
# bytes-on-the-wire multiplier per output byte (ring algorithms)
_WIRE_FACTOR = {
    "all-reduce": 2.0,  # reduce-scatter + all-gather
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        b = _DTYPE_BYTES.get(m.group("dtype"))
        if b is None:
            continue
        dims = m.group("dims")
        n = 1
        for d in dims.split(",") if dims else []:
            if d:
                n *= int(d)
        total += n * b
    return total


def collective_census(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum output bytes of every collective op in the optimized HLO."""
    census: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        nbytes = _shape_bytes(m.group("out"))
        entry = census.setdefault(op, {"count": 0, "bytes": 0.0})
        entry["count"] += 1
        entry["bytes"] += nbytes
    return census


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (train, dense) / 6*N_active*D (MoE); 2*N*D decode."""
    shapes, _ = steps_lib.model_shapes_and_axes(cfg)
    n_total = sum(
        s.size for s in jax.tree_util.tree_leaves(shapes)
        if jnp.issubdtype(s.dtype, jnp.floating)
    )
    n_active = n_total
    if cfg.family == "moe":
        # subtract inactive routed-expert params (padded experts included)
        from repro.nn.moe import padded_experts

        per_expert = 3 * cfg.d_model * cfg.d_expert * cfg.n_layers
        n_active = n_total - (padded_experts(cfg) - cfg.top_k) * per_expert
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    factor = 6 if shape.kind == "train" else 2
    return factor * n_active * tokens, n_total, n_active


def build_cell(arch: str, shape_name: str, *, multi_pod: bool, zero: str = "zero1",
               attn: str = "chunked", sp: bool = True, capacity: float = None,
               remat: str = "block", moe_dispatch: str = "gather"):
    """Returns (jitted_fn, example_args, mesh, cfg, shape).

    ``attn="dense"`` is the paper-faithful straightforward baseline (records
    the S^2 score materialization); ``"chunked"`` is the production portable
    path (flash algorithm in XLA) and the dry-run default — the Pallas flash
    kernel is the TPU-native backend validated in interpret mode.
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    cfg = dataclasses.replace(cfg, attn_impl=attn)
    if shape.kind == "train":
        # activation checkpointing on by default for the big train cells
        cfg = dataclasses.replace(cfg, remat=remat)
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    if sp and shape.kind in ("train", "prefill") and shape.seq_len % 16 == 0:
        # sequence-parallel residual sharding (production default; the
        # non-SP baseline is recorded for the §Perf hillclimb cells)
        cfg = dataclasses.replace(cfg, sp_spec=(batch_axes, "model"))
    if cfg.family == "moe":
        # expert-parallel shard_map dispatch over the model axis
        cfg = dataclasses.replace(
            cfg, moe_spec=(batch_axes, "model"), moe_dispatch=moe_dispatch
        )
        if capacity is not None:
            cfg = dataclasses.replace(cfg, moe_capacity_factor=capacity)
    mesh = make_production_mesh(multi_pod=multi_pod)
    opt = adamw(warmup_cosine_schedule(3e-4, 2000, 100_000))

    shapes, axes, p_sh, opt_shapes, opt_sh = steps_lib.train_shardings(
        mesh, cfg, opt, zero=zero
    )

    if shape.kind == "train":
        batch = steps_lib.batch_struct(cfg, shape.global_batch, shape.seq_len)
        b_sh = shd.batch_shardings(mesh, batch)
        raw = steps_lib.make_train_step(cfg, opt)
        fn = jax.jit(
            raw,
            in_shardings=(p_sh, opt_sh, b_sh),
            out_shardings=(p_sh, opt_sh, None),
        )
        args = (shapes, opt_shapes, batch)
    elif shape.kind == "prefill":
        cache = steps_lib.cache_struct(cfg, shape.global_batch, shape.seq_len)
        c_sh = shd.cache_shardings(mesh, cache, lm.cache_axes(cfg))
        batch = steps_lib.batch_struct(cfg, shape.global_batch, shape.seq_len)
        batch.pop("labels")
        b_sh = shd.batch_shardings(mesh, batch)
        raw = steps_lib.make_prefill_step(cfg)
        fn = jax.jit(
            raw,
            in_shardings=(p_sh, b_sh, c_sh),
            out_shardings=(None, c_sh),
        )
        args = (shapes, batch, cache)
    elif shape.kind == "decode":
        cache = steps_lib.cache_struct(cfg, shape.global_batch, shape.seq_len)
        c_sh = shd.cache_shardings(mesh, cache, lm.cache_axes(cfg))
        batch = steps_lib.batch_struct(cfg, shape.global_batch, 1)
        batch.pop("labels")
        b_sh = shd.batch_shardings(mesh, batch)
        length = jax.ShapeDtypeStruct((), jnp.int32)
        raw = steps_lib.make_decode_step(cfg)
        fn = jax.jit(
            raw,
            in_shardings=(p_sh, b_sh, None, c_sh),
            out_shardings=(None, c_sh),
        )
        args = (shapes, batch, length, cache)
    else:
        raise ValueError(shape.kind)
    return fn, raw, args, mesh, cfg, shape


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, zero: str = "zero1",
             attn: str = "chunked", sp: bool = True, capacity: float = None,
             remat: str = "block", moe_dispatch: str = "gather",
             flash_cost: bool = False, tag: str = "",
             save: bool = True, verbose: bool = True) -> Dict:
    t0 = time.perf_counter()
    fn, raw_fn, args, mesh, cfg, shape = build_cell(
        arch, shape_name, multi_pod=multi_pod, zero=zero, attn=attn, sp=sp,
        capacity=capacity, remat=remat, moe_dispatch=moe_dispatch,
    )
    n_chips = mesh.size
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
        logical = costmodel.function_cost(raw_fn, *args)
        logical_flash = None
        if flash_cost and shape.kind in ("prefill", "decode"):
            # kernel-contract costing: trace under the Pallas executor so the
            # hot ops appear as pallas_call units (HBM traffic = BlockSpec io)
            from repro.core import PallasInterpretExecutor, use_executor

            with use_executor(PallasInterpretExecutor()):
                logical_flash = costmodel.function_cost(raw_fn, *args)

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    census = collective_census(hlo)

    # raw HLO cost analysis (recorded for reference) undercounts while-loop
    # bodies (counted once regardless of trip count — see costmodel.py), so
    # the roofline compute/memory terms come from the jaxpr walker instead.
    hlo_flops = float(cost.get("flops", 0.0))
    hlo_bytes = float(cost.get("bytes accessed", 0.0))
    coll_bytes = sum(
        e["bytes"] * _WIRE_FACTOR[op] for op, e in census.items()
    )

    mflops, n_total, n_active = model_flops(cfg, shape)
    compute_t = logical["flops"] / n_chips / PEAK_FLOPS
    # memory term uses the fusion-aware estimate; the unfused upper bound is
    # recorded alongside (see costmodel.py for both definitions)
    memory_t = logical["fused_bytes"] / n_chips / HBM_BW
    memory_t_unfused = logical["bytes"] / n_chips / HBM_BW
    collective_t = coll_bytes / ICI_BW

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": f"{'2x16x16' if multi_pod else '16x16'}",
        "chips": n_chips,
        "zero": zero,
        "attn": attn,
        "sp": sp,
        "tag": tag,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "per_device": {
            "logical_flops": logical["flops"] / n_chips,
            "logical_bytes_unfused": logical["bytes"] / n_chips,
            "logical_bytes_fused_est": logical["fused_bytes"] / n_chips,
            "hlo_flops_raw": hlo_flops,  # while bodies counted once — see costmodel
            "hlo_bytes_raw": hlo_bytes,
            "collective_bytes_wire": coll_bytes,
        },
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": float(mem.peak_memory_in_bytes),
        },
        "collectives": census,
        "roofline": {
            "compute_s": compute_t,
            "memory_s": memory_t,
            "memory_s_unfused": memory_t_unfused,
            "collective_s": collective_t,
            "bottleneck": max(
                ("compute", compute_t),
                ("memory", memory_t),
                ("collective", collective_t),
                key=lambda kv: kv[1],
            )[0],
        },
        "model_flops": {
            "total_params": n_total,
            "active_params": n_active,
            "model_flops_global": mflops,
            "model_flops_per_chip": mflops / n_chips,
            "useful_fraction": mflops / logical["flops"] if logical["flops"] else None,
        },
    }
    if logical_flash is not None:
        result["roofline_flash"] = {
            "compute_s": logical_flash["flops"] / n_chips / PEAK_FLOPS,
            "memory_s": logical_flash["fused_bytes"] / n_chips / HBM_BW,
        }
    if verbose:
        r = result["roofline"]
        print(
            f"[{arch} x {shape_name} x {result['mesh']}] compile {t_compile:.0f}s | "
            f"compute {r['compute_s']*1e3:.2f}ms memory {r['memory_s']*1e3:.2f}ms "
            f"collective {r['collective_s']*1e3:.2f}ms -> {r['bottleneck']}-bound | "
            f"useful {result['model_flops']['useful_fraction']}"
        )
        print(f"  memory_analysis: {result['memory_analysis']}")
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        suffix = "_mp" if multi_pod else ""
        zsuffix = "" if zero == "zero1" else f"_{zero}"
        asuffix = "" if attn == "chunked" else f"_{attn}"
        tsuffix = f"_{tag}" if tag else ""
        path = os.path.join(
            OUT_DIR, f"{arch}__{shape_name}{suffix}{zsuffix}{asuffix}{tsuffix}.json"
        )
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
    return result


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--zero", default="zero1", choices=("none", "zero1", "fsdp"))
    ap.add_argument("--attn", default="chunked", choices=("dense", "chunked"))
    ap.add_argument("--no-sp", action="store_true",
                    help="disable sequence-parallel residual sharding")
    ap.add_argument("--capacity", type=float, default=None,
                    help="MoE expert-parallel capacity factor")
    ap.add_argument("--remat", default="block", choices=("none", "block", "dots"))
    ap.add_argument("--flash-cost", action="store_true",
                    help="also cost the Pallas kernel-contract path (prefill/decode)")
    ap.add_argument("--moe-dispatch", default="gather", choices=("gather", "a2a"))
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    trace.add_cli_flag(ap)
    args = ap.parse_args()
    trace.enable_from_args(args)

    if args.all:
        failures = []
        for arch in ARCH_IDS:
            for shape_name in cells(arch):
                try:
                    run_cell(arch, shape_name, multi_pod=args.multi_pod,
                             zero=args.zero, attn=args.attn)
                except Exception as e:  # noqa: BLE001 — report and continue
                    failures.append((arch, shape_name, repr(e)))
                    print(f"[{arch} x {shape_name}] FAILED: {e}")
        if failures:
            raise SystemExit(f"{len(failures)} cells failed: {failures}")
        print("ALL CELLS PASSED")
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required (or --all)")
        run_cell(args.arch.replace("-", "_"), args.shape,
                 multi_pod=args.multi_pod, zero=args.zero, attn=args.attn,
                 sp=not args.no_sp, capacity=args.capacity, remat=args.remat,
                 moe_dispatch=args.moe_dispatch,
                 flash_cost=args.flash_cost, tag=args.tag)
    if args.trace and trace.export():
        print(f"trace -> {args.trace}")


if __name__ == "__main__":
    main()
