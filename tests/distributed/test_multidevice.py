"""Multi-device semantics under 8 virtual CPU devices (subprocess-isolated —
the device-count flag must never leak into other tests' jax runtime)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")


def run_with_devices(body: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    script = textwrap.dedent(body)
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True, text=True,
        timeout=560,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_pjit_train_step_executes():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.launch import steps as steps_lib
        from repro.launch.mesh import make_host_mesh
        from repro.distributed import sharding as shd
        from repro.models import lm
        from repro.optim import adamw, constant_schedule

        mesh = make_host_mesh(2, 4)
        cfg = get_smoke_config("granite_8b")
        opt = adamw(constant_schedule(1e-3))
        params, axes = lm.init_model(jax.random.PRNGKey(0), cfg)
        shapes, _, p_sh, _, opt_sh = steps_lib.train_shardings(mesh, cfg, opt)
        params = jax.device_put(params, p_sh)
        state = jax.device_put(opt.init(params), opt_sh)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
        }
        b_sh = shd.batch_shardings(mesh, batch)
        batch = jax.device_put(batch, b_sh)
        fn = jax.jit(steps_lib.make_train_step(cfg, opt),
                     in_shardings=(p_sh, opt_sh, b_sh),
                     out_shardings=(p_sh, opt_sh, None))
        with jax.set_mesh(mesh):
            p2, s2, m = fn(params, state, batch)
        loss = float(m["loss"])
        assert np.isfinite(loss), loss
        print("LOSS", loss)
    """)


def test_ring_collective_matmuls():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collective_matmul import (
            ring_reduce_scatter_matmul, ring_all_gather_matmul)
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(1, 8)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
        want = np.asarray(x @ w)
        with jax.set_mesh(mesh):
            got = jax.jit(jax.shard_map(
                lambda xs, ws: ring_reduce_scatter_matmul(xs, ws, "model"),
                in_specs=(P(None, "model"), P("model", None)),
                out_specs=P(None, "model")))(x, w)
            assert np.abs(np.asarray(got) - want).max() < 1e-3
            got2 = jax.jit(jax.shard_map(
                lambda xs, ws: ring_all_gather_matmul(xs, ws, "model"),
                in_specs=(P("model", None), P(None, "model")),
                out_specs=P(None, "model")))(x, w)
            assert np.abs(np.asarray(got2) - want).max() < 1e-3
        print("RING OK")
    """)


def test_moe_expert_parallel_matches_dense():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import ModelConfig
        from repro.nn import moe as moe_lib
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(2, 4)
        cfg = ModelConfig(name='t', family='moe', n_layers=1, d_model=32,
                          vocab=64, n_experts=8, top_k=2, d_expert=64,
                          shared_expert_ff=48, moe_spec=(("data",), "model"),
                          moe_capacity_factor=8.0)
        p, _ = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(50)
        x = jnp.asarray(rng.normal(size=(4, 16, 32)).astype(np.float32))
        with jax.set_mesh(mesh):
            y_ep, m = jax.jit(lambda x: moe_lib.moe_forward(p, x, cfg, impl="ep"))(x)
        y_dense, _ = moe_lib.moe_forward(p, x, cfg, impl="dense")
        assert np.abs(np.asarray(y_ep) - np.asarray(y_dense)).max() < 1e-4
        assert float(m["moe_drop_frac"]) == 0.0

        def loss_ep(p, x):
            y, _ = moe_lib.moe_forward(p, x, cfg, impl="ep")
            return jnp.sum(y**2)
        def loss_dense(p, x):
            y, _ = moe_lib.moe_forward(p, x, cfg, impl="dense")
            return jnp.sum(y**2)
        with jax.set_mesh(mesh):
            g_ep = jax.jit(jax.grad(loss_ep))(p, x)
        g_dense = jax.grad(loss_dense)(p, x)
        for key in ("gate", "up", "down", "router"):
            e = np.abs(np.asarray(g_ep[key]) - np.asarray(g_dense[key])).max()
            rel = e / max(np.abs(np.asarray(g_dense[key])).max(), 1e-9)
            assert rel < 1e-3, (key, rel)
        print("MOE EP OK")
    """)


def test_compressed_psum_shard_map():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.optim import compressed_psum, init_error_state
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(8, 1)
        rng = np.random.default_rng(1)
        g = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
        err = jnp.zeros((8, 64), jnp.float32)

        def body(g_l, e_l):
            out, new_e = compressed_psum({"w": g_l[0]}, {"w": e_l[0]}, "data")
            return out["w"][None], new_e["w"][None]

        with jax.set_mesh(mesh):
            out, new_err = jax.jit(jax.shard_map(
                body, in_specs=(P("data", None), P("data", None)),
                out_specs=(P("data", None), P("data", None))))(g, err)
        want = np.asarray(g).mean(axis=0)
        got = np.asarray(out)[0]
        # int8 quantization error bounded by the shared scale
        scale = np.abs(np.asarray(g)).max() / 127.0
        assert np.abs(got - want).max() < scale * 1.5
        # every shard got the same reduced value
        assert np.abs(np.asarray(out) - got[None]).max() < 1e-7
        print("COMPRESSED PSUM OK")
    """)


def test_elastic_checkpoint_reshard():
    run_with_devices("""
        import tempfile
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.launch.mesh import make_host_mesh

        rng = np.random.default_rng(2)
        w = rng.normal(size=(16, 32)).astype(np.float32)
        mesh_a = make_host_mesh(2, 4)
        mesh_b = make_host_mesh(8, 1)
        sh_a = NamedSharding(mesh_a, P("data", "model"))
        sh_b = NamedSharding(mesh_b, P("data", None))
        tree = {"w": jax.device_put(jnp.asarray(w), sh_a)}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, tree, block=True)
            proto = {"w": jnp.zeros((16, 32), jnp.float32)}
            got, _ = mgr.restore(target=proto, shardings={"w": sh_b})
            assert got["w"].sharding == sh_b
            np.testing.assert_array_equal(np.asarray(got["w"]), w)
        print("ELASTIC RESHARD OK")
    """)


def test_sequence_parallel_constraint_executes():
    run_with_devices("""
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_host_mesh
        from repro.models import lm

        mesh = make_host_mesh(2, 4)
        cfg = dataclasses.replace(get_smoke_config("granite_8b"),
                                  sp_spec=(("data",), "model"),
                                  attn_impl="chunked")
        params, _ = lm.init_model(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
        with jax.set_mesh(mesh):
            logits_sp, _ = jax.jit(lambda t: lm.forward(params, cfg, tokens=t))(tokens)
        cfg0 = dataclasses.replace(cfg, sp_spec=(), attn_impl="dense")
        logits, _ = lm.forward(params, cfg0, tokens=tokens)
        err = np.abs(np.asarray(logits_sp) - np.asarray(logits)).max()
        assert err < 2e-3, err
        print("SP OK", err)
    """)


def test_compressed_dp_training_converges():
    """End-to-end DP training with int8-EF gradient compression: the
    compressed run must track the uncompressed loss trajectory."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.data import DataConfig, global_step_batch
        from repro.launch import steps as steps_lib
        from repro.launch.mesh import make_host_mesh
        from repro.models import lm
        from repro.optim import adamw, constant_schedule

        mesh = make_host_mesh(8, 1)
        cfg = get_smoke_config("smollm_135m")
        opt = adamw(constant_schedule(3e-3), weight_decay=0.0)
        params, _ = lm.init_model(jax.random.PRNGKey(0), cfg)
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8, seed=3)

        # uncompressed reference
        step_ref = jax.jit(steps_lib.make_train_step(cfg, opt))
        p_ref, s_ref = params, opt.init(params)
        ref_losses = []
        for i in range(12):
            batch = {k: jnp.asarray(v) for k, v in global_step_batch(dcfg, i).items()}
            p_ref, s_ref, m = step_ref(p_ref, s_ref, batch)
            ref_losses.append(float(m["loss"]))

        # compressed DP
        step_c, init_err = steps_lib.make_compressed_dp_train_step(cfg, opt)
        p_c, s_c = params, opt.init(params)
        err = init_err(params, 8)
        c_losses = []
        with jax.set_mesh(mesh):
            fn = jax.jit(step_c)
            for i in range(12):
                batch = {k: jnp.asarray(v) for k, v in global_step_batch(dcfg, i).items()}
                p_c, s_c, err, m = fn(p_c, s_c, err, batch)
                c_losses.append(float(m["loss"]))

        ref, com = np.array(ref_losses), np.array(c_losses)
        assert com[-1] < com[0] - 0.1, com          # learning
        assert np.abs(ref - com).max() < 0.05, (ref, com)  # tracks reference
        print("COMPRESSED DP OK", ref[-1], com[-1])
    """)


def test_dryrun_cell_end_to_end():
    """One real dry-run cell (lower+compile+roofline) under 64 placeholder
    devices with a shrunken production-mesh shape — covers the launch path."""
    run_with_devices("""
        import json
        import repro.launch.dryrun as dr
        import repro.launch.mesh as mesh_lib
        import jax

        # shrink the production mesh to the available 64 devices
        orig = mesh_lib.make_production_mesh
        def small(*, multi_pod=False):
            shape = (2, 4, 8) if multi_pod else (8, 8)
            axes = ("pod", "data", "model") if multi_pod else ("data", "model")
            return jax.make_mesh(
                shape, axes,
                axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
        mesh_lib.make_production_mesh = small
        dr.make_production_mesh = small

        res = dr.run_cell("granite_8b", "decode_32k", multi_pod=False,
                          save=False, verbose=False)
        assert res["roofline"]["bottleneck"] in ("compute", "memory", "collective")
        assert res["per_device"]["logical_flops"] > 0
        assert res["memory_analysis"]["peak_bytes"] is not None
        res_mp = dr.run_cell("granite_8b", "decode_32k", multi_pod=True,
                             save=False, verbose=False)
        assert res_mp["chips"] == 64
        print("DRYRUN CELL OK", res["roofline"]["bottleneck"])
    """, n=64)
