"""Batched serving driver: KV/SSM-cache decode under the production mesh.

`make_serve_step` jits one decode step with the cache partition specs from
`partitioning.py` (batch over data; KV-heads or cache length over model —
flash-decoding-style partial-softmax combine is inserted by GSPMD when the
length is the sharded dim). `serve_loop` runs greedy decoding for a batch
of requests on the host's devices.
"""
from __future__ import annotations

import argparse
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.config import ModelConfig, ShapeConfig
from repro.launch import partitioning as parts
from repro.launch.hostenv import (enable_compile_cache, host_timer,
                                  maybe_preload_tcmalloc)
from repro.models import registry as models

Pytree = Any


def make_serve_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                    cache_like: Pytree, plan=None, donate: bool = True):
    from repro.config import ShardingPlan
    plan = plan or ShardingPlan(grad_sharding="none")
    p_specs = parts.param_pspecs(cfg, mesh, plan)
    c_specs = parts.cache_pspecs(cfg, shape, mesh, cache_like)
    t_spec = parts.decode_token_pspec(shape, mesh)

    def serve_step(params, tokens, cache):
        return models.decode_step(params, cfg, tokens, cache)

    return jax.jit(
        serve_step,
        in_shardings=(parts.to_named(mesh, p_specs),
                      jax.sharding.NamedSharding(mesh, t_spec),
                      parts.to_named(mesh, c_specs)),
        out_shardings=(None, parts.to_named(mesh, c_specs)),
        donate_argnums=(2,) if donate else (),
    )


def serve_loop(cfg: ModelConfig, *, batch: int = 4, prompt_len: int = 8,
               max_new_tokens: int = 16, max_len: int = 64, seed: int = 0,
               mesh: Mesh | None = None, greedy: bool = True) -> dict:
    """Greedy decode: prefill via repeated decode steps (single-host demo),
    then generate. Returns tokens + tokens/sec."""
    if mesh is None:
        dev = np.array(jax.devices()[:1]).reshape(1, 1)
        mesh = Mesh(dev, ("data", "model"))
    shape = ShapeConfig("serve", seq_len=max_len, global_batch=batch,
                        kind="decode")
    params = models.init_params(jax.random.PRNGKey(seed), cfg)
    cache = models.init_cache(cfg, batch, max_len)
    if cfg.is_encdec:
        from repro.models import encdec
        fd = cfg.frontend_dim or cfg.d_model
        frames = jax.random.normal(jax.random.PRNGKey(seed + 1),
                                   (batch, cfg.encoder_seq, fd))
        cache = encdec.init_cache(cfg, batch, max_len, params=params,
                                  frames=frames)
    step_fn = make_serve_step(cfg, shape, mesh, cache)

    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab, (batch, prompt_len)).astype(np.int32)
    generated = []
    tok = jnp.asarray(prompt[:, :1])
    t0 = host_timer()
    logits = None
    for t in range(prompt_len + max_new_tokens - 1):
        logits, cache = step_fn(params, tok, cache)
        if t + 1 < prompt_len:
            tok = jnp.asarray(prompt[:, t + 1:t + 2])
        else:
            nxt = jnp.argmax(logits[:, -1], axis=-1) if greedy else \
                jax.random.categorical(jax.random.PRNGKey(t), logits[:, -1])
            tok = nxt[:, None].astype(jnp.int32)
            generated.append(np.asarray(tok))
    dt = host_timer() - t0
    gen = np.concatenate(generated, axis=1) if generated else np.zeros((batch, 0))
    total_tokens = batch * (prompt_len + max_new_tokens - 1)
    return {"generated": gen, "tokens_per_s": total_tokens / dt,
            "wall_s": dt}


def main(argv=None):
    ap = argparse.ArgumentParser(description="batched serving driver")
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new_tokens", type=int, default=16)
    args = ap.parse_args(argv)

    from repro.configs import get_arch
    spec = get_arch(args.arch)
    cfg = spec.smoke if args.smoke else spec.model
    out = serve_loop(cfg, batch=args.batch, max_new_tokens=args.new_tokens)
    print(f"[serve] {args.arch}: {out['tokens_per_s']:.1f} tok/s, "
          f"generated shape {out['generated'].shape}")


if __name__ == "__main__":
    maybe_preload_tcmalloc()
    enable_compile_cache()
    main()
