"""Training driver: data -> sharded train_step -> checkpoints, fault-tolerant.

Runs anywhere: on this CPU container with ``--smoke`` (reduced config, visible
loss decrease against the synthetic chain's entropy floor), on a real pod with
the full config.  Wiring demonstrated here:

* deterministic resumable data (repro.data),
* pjit train step with logical-axis shardings (repro.distributed.sharding),
* async atomic checkpoints + exact resume (step, data state) (repro.checkpoint),
* preemption checkpoint-and-exit, straggler monitor, restart supervisor
  (repro.runtime).

Usage:
    python -m repro.launch.train --arch smollm-135m --smoke --steps 60
    python -m repro.launch.train --arch smollm-135m --smoke --steps 60 \
        --resume --ckpt-dir /tmp/ckpt   # restart path
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.observability import trace
from repro.configs import get_config, get_smoke_config
from repro.data import DataConfig, DataIterator, entropy_floor
from repro.distributed import sharding as shd
from repro.launch import steps as steps_lib
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.optim import adamw, warmup_cosine_schedule
from repro.runtime import PreemptionHandler, StragglerMonitor


def build_state(cfg, opt, mesh, ckpt: Optional[CheckpointManager], data_cfg):
    """Init or restore (params, opt_state, data_iter, start_step)."""
    shapes, axes = steps_lib.model_shapes_and_axes(cfg)
    p_sh = shd.param_shardings(mesh, shapes, axes)

    data_iter = DataIterator(data_cfg)
    if ckpt is not None and ckpt.latest_step() is not None:
        opt_shapes = steps_lib.opt_state_shapes(opt, shapes)
        target = {"params": shapes, "opt": opt_shapes}
        shardings = {"params": p_sh, "opt": jax.tree_util.tree_map(
            lambda _: shd.replicated(mesh), opt_shapes)}
        tree, meta = ckpt.restore(target=target, shardings=shardings)
        data_iter.restore(meta["data"])
        print(f"[train] restored step {meta['step']} from {ckpt.directory}")
        return tree["params"], tree["opt"], data_iter, int(meta["step"])

    params, _ = lm.init_model(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params, p_sh)
    opt_state = opt.init(params)
    return params, opt_state, data_iter, 0


def train(
    cfg,
    *,
    steps: int,
    global_batch: int,
    seq_len: int,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 20,
    resume: bool = False,
    data_shards: int = 1,
    mesh=None,
    log_every: int = 10,
    preemption: Optional[PreemptionHandler] = None,
    stop_at_step: Optional[int] = None,  # simulate an interruption (tests)
):
    mesh = mesh or make_host_mesh(1, 1)
    opt = adamw(warmup_cosine_schedule(3e-3, max(steps // 10, 1), steps),
                weight_decay=0.01)
    data_cfg = DataConfig(
        vocab=cfg.vocab,
        seq_len=seq_len,
        global_batch=global_batch,
        num_shards=data_shards,
        seed=17,
        stub_embed_dim=cfg.d_model if cfg.frontend == "stub_embeddings" else 0,
    )
    ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if not resume and ckpt is not None and ckpt.latest_step() is not None:
        raise SystemExit(
            f"{ckpt_dir} already has checkpoints; pass --resume to continue"
        )

    params, opt_state, data_iter, start = build_state(cfg, opt, mesh, ckpt, data_cfg)
    step_fn = jax.jit(steps_lib.make_train_step(cfg, opt), donate_argnums=(0, 1))
    monitor = StragglerMonitor(window=50, factor=4.0)

    losses = []
    t_start = time.perf_counter()
    with mesh:
        for step in range(start, steps):
            batch = {k: jnp.asarray(v) for k, v in next(data_iter).items()}
            monitor.start_step()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            jax.block_until_ready(metrics["loss"])
            if monitor.end_step():
                print(f"[train] step {step}: straggler alarm "
                      f"(median {monitor.median*1e3:.0f}ms)")
            losses.append(float(metrics["loss"]))
            if step % log_every == 0 or step == steps - 1:
                print(
                    f"[train] step {step:5d} loss {float(metrics['loss']):.4f} "
                    f"lr {float(metrics['lr']):.2e} gnorm "
                    f"{float(metrics['grad_norm']):.2f}"
                )
            want_ckpt = ckpt is not None and (
                (step + 1) % ckpt_every == 0 or step == steps - 1
            )
            if preemption is not None and preemption.preempted:
                if ckpt is not None:
                    ckpt.save(step + 1, {"params": params, "opt": opt_state},
                              metadata={"step": step + 1, "data": data_iter.state()},
                              block=True)
                    print(f"[train] preempted — checkpointed step {step+1}, exiting")
                return params, losses
            if want_ckpt:
                ckpt.save(step + 1, {"params": params, "opt": opt_state},
                          metadata={"step": step + 1, "data": data_iter.state()})
            if stop_at_step is not None and step + 1 >= stop_at_step:
                if ckpt is not None:
                    ckpt.wait()
                print(f"[train] stopped at step {step + 1} (requested)")
                return params, losses
    if ckpt is not None:
        ckpt.wait()
    dt = time.perf_counter() - t_start
    tok_s = (steps - start) * global_batch * seq_len / max(dt, 1e-9)
    print(f"[train] done: {steps - start} steps in {dt:.1f}s ({tok_s:.0f} tok/s); "
          f"final loss {losses[-1]:.4f} (entropy floor {entropy_floor(data_cfg):.4f})")
    return params, losses


def train_deq(*, steps: int, batch: int, lr: float = 3e-2,
              log_every: int = 5) -> bool:
    """Train the deep-equilibrium regression model end to end.

    Every forward is a batched GMRES solve; every backward an adjoint solve
    through the ``Transpose`` combinator.  Returns True when the loss
    strictly decreased from first to last logged value (the DEQ-GATE
    criterion).
    """
    from repro.models import deq as deq_lib

    cfg = deq_lib.DeqConfig()
    params = deq_lib.init_deq(jax.random.PRNGKey(0), cfg)
    opt = adamw(lambda _: jnp.asarray(lr, jnp.float32),
                weight_decay=0.0, clip_norm=None)
    opt_state = opt.init(params)
    batch_data = deq_lib.synthetic_batch(0, batch, cfg)

    @jax.jit
    def step_fn(params, opt_state, batch_data):
        loss, grads = jax.value_and_grad(deq_lib.deq_loss)(params, batch_data, cfg)
        params, opt_state, _ = opt.update(params, grads, opt_state)
        return params, opt_state, loss

    losses = []
    for step in range(steps):
        params, opt_state, loss = step_fn(params, opt_state, batch_data)
        losses.append(float(loss))
        if step % log_every == 0 or step == steps - 1:
            print(f"[deq] step {step:4d} loss {losses[-1]:.6f}")
    decreased = losses[-1] < losses[0]
    print(f"DEQ-GATE: {'PASS' if decreased else 'FAIL'} "
          f"(loss {losses[0]:.6f} -> {losses[-1]:.6f})")
    return decreased


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--model", default="lm", choices=["lm", "deq"])
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    trace.add_cli_flag(ap)
    args = ap.parse_args()
    trace.enable_from_args(args)

    if args.model == "deq":
        steps = min(args.steps, 30) if args.smoke else args.steps
        ok = train_deq(steps=steps, batch=args.global_batch)
        raise SystemExit(0 if ok else 1)

    if args.arch is None:
        ap.error("--arch is required for --model lm")
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    handler = PreemptionHandler().install()
    train(
        cfg,
        steps=args.steps,
        global_batch=args.global_batch,
        seq_len=args.seq_len,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        resume=args.resume,
        preemption=handler,
    )
    if args.trace and trace.export():
        print(f"trace -> {args.trace}")


if __name__ == "__main__":
    main()
