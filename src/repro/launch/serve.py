"""Serving driver: batched prefill + decode with KV/state caches.

Greedy decoding over batched synthetic prompts; demonstrates the serving
contract every architecture implements (prefill fills the cache at offset 0,
decode_step appends one token), including the attention-free (RWKV) and
hybrid (Zamba2) recurrent-state paths.

Usage:
    python -m repro.launch.serve --arch rwkv6-3b --smoke --prompt-len 32 \
        --gen-len 32 --batch 4
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.observability import trace
from repro.launch import steps as steps_lib
from repro.launch.cache import use_compile_cache
from repro.models import lm


def serve(cfg, *, batch: int, prompt_len: int, gen_len: int, seed: int = 0,
          greedy: bool = True, temperature: float = 1.0):
    rng = np.random.default_rng(seed)
    params, _ = lm.init_model(jax.random.PRNGKey(0), cfg)
    s_max = prompt_len + gen_len

    feed = {}
    if cfg.frontend == "stub_embeddings":
        feed["embeds"] = jnp.asarray(
            rng.normal(size=(batch, prompt_len, cfg.d_model)).astype(np.float32)
        )
    else:
        feed["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab, size=(batch, prompt_len)), jnp.int32
        )

    prefill_fn = jax.jit(steps_lib.make_prefill_step(cfg))
    decode_fn = jax.jit(steps_lib.make_decode_step(cfg))

    cache = lm.init_cache(cfg, batch, s_max)
    t0 = time.perf_counter()
    logits, cache = prefill_fn(params, feed, cache)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    key = jax.random.PRNGKey(seed)

    def sample(logits, key):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, axis=-1).astype(
            jnp.int32
        )

    tokens = sample(logits, key)  # (B,)
    generated = [tokens]
    t0 = time.perf_counter()
    for t in range(prompt_len, prompt_len + gen_len - 1):
        key, sub = jax.random.split(key)
        step_feed = {}
        if cfg.frontend == "stub_embeddings":
            # stub frontend: embed the sampled token through the LM embedding
            step_feed["embeds"] = lm.embed(
                params["embedding"], tokens[:, None]
            ).astype(jnp.dtype(cfg.dtype))
        else:
            step_feed["tokens"] = tokens[:, None]
        logits, cache = decode_fn(params, step_feed, jnp.int32(t), cache)
        tokens = sample(logits, sub)
        generated.append(tokens)
    jax.block_until_ready(tokens)
    t_decode = time.perf_counter() - t0

    out = jnp.stack(generated, axis=1)  # (B, gen_len)
    tok_s = batch * (gen_len - 1) / max(t_decode, 1e-9)
    print(
        f"[serve] {cfg.name}: prefill {batch}x{prompt_len} in {t_prefill*1e3:.0f}ms; "
        f"decode {gen_len-1} steps at {tok_s:.1f} tok/s"
    )
    return out


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="rng seed for prompts and sampling")
    trace.add_cli_flag(ap)
    args = ap.parse_args()
    trace.enable_from_args(args)
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    serve(
        cfg,
        batch=args.batch,
        prompt_len=args.prompt_len,
        gen_len=args.gen_len,
        seed=args.seed,
        greedy=args.temperature == 0.0,
        temperature=max(args.temperature, 1e-3),
    )
    if args.trace and trace.export():
        print(f"trace -> {args.trace}")


if __name__ == "__main__":
    main()
