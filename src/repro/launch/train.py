"""Distributed trainer: DP/TP(/EP/SP) + the paper's gradient sharding.

Two execution paths for the same aggregation semantics:

  * ``gspmd`` (production): jit with partition specs. The ShardingPlan picks
    the aggregation strategy exactly as the paper's topologies map to TPU
    (DESIGN.md §3): ``none`` = replicated optimizer, full-gradient
    all-reduce (λ-FL/LIFL analogue); ``zero1`` = optimizer state sharded
    over the replica axes → XLA lowers reduce-scatter + sharded update +
    all-gather (GradsSharding); ``zero3`` = parameters FSDP-sharded too.

  * ``shardmap`` (paper-faithful demonstration): explicit
    flatten → reduce-scatter(mean) → per-device |θ|/M shard optimizer step
    (optionally QSGD-compressed on the wire) → all-gather → unflatten, via
    ``core.device_agg``. Bit-comparable to the serverless implementation.

The training loop adds the production substrate: checkpoint/restart
(atomic, manifested), deterministic data restart, metric logging.

A third path runs the paper's own setting end to end:
:func:`federated_train_loop` drives multi-round federated training through
a :class:`repro.api.FederatedSession`, which carries per-client timing
across rounds internally so that — under ``schedule="pipelined"`` — round
r+1 client local compute and uploads overlap round r read-back, and the
whole session's modeled wall-clock reflects the overlap win over the
barrier schedule.
"""
from __future__ import annotations

import argparse
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, ShapeConfig, ShardingPlan
from repro.core import device_agg
from repro.core.sharding import flatten, unflatten
from repro.launch import partitioning as parts
from repro.launch.hostenv import (enable_compile_cache, host_timer,
                                  maybe_preload_tcmalloc)
from repro.models import registry as models
from repro.optim import Optimizer, adamw, apply_updates

Pytree = Any


# ---------------------------------------------------------------------------
# GSPMD path
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, optimizer: Optimizer):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            models.loss_fn, has_aux=True)(params, cfg, batch)
        updates, new_state = optimizer.update(grads, opt_state, params)
        new_params = apply_updates(params, updates)
        metrics = dict(metrics, grad_norm=_gnorm(grads))
        return new_params, new_state, metrics

    return train_step


def _gnorm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def jit_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                   plan: ShardingPlan, optimizer: Optimizer,
                   opt_state_like: Pytree, donate: bool = True):
    """jit the train step with the plan's partition specs."""
    p_specs = parts.param_pspecs(cfg, mesh, plan)
    o_specs = parts.opt_state_pspecs(cfg, mesh, plan, opt_state_like, p_specs)
    b_specs = batch = parts.batch_pspecs(cfg, shape, mesh)
    step = make_train_step(cfg, optimizer)
    return jax.jit(
        step,
        in_shardings=(parts.to_named(mesh, p_specs),
                      parts.to_named(mesh, o_specs),
                      parts.to_named(mesh, b_specs)),
        out_shardings=(parts.to_named(mesh, p_specs),
                       parts.to_named(mesh, o_specs), None),
        donate_argnums=(0, 1) if donate else (),
    )


# ---------------------------------------------------------------------------
# shard_map path — explicit GradsSharding over devices
# ---------------------------------------------------------------------------

def make_shardmap_train_step(cfg: ModelConfig, mesh: Mesh, lr: float,
                             momentum: float = 0.9,
                             compress: str = "none"):
    """Paper-faithful device port: every replica computes local grads (its
    micro-batch = a "client"), the flat gradient is reduce-scattered so
    device j holds averaged shard j (M = replica count), the SGD update runs
    on the shard (O(|θ|/M) optimizer state), and updated shards are
    all-gathered (Step 4 reconstruct).

    Returns (step_fn, init_velocity_fn). Params/velocity replicated in/out;
    state sharding is internal to the step (per-device flat shards).
    """
    rep = parts.replica_axes(mesh)
    m = 1
    for a in rep:
        m *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]

    def local_grads(params, batch):
        (loss, _), grads = jax.value_and_grad(
            models.loss_fn, has_aux=True)(params, cfg, batch)
        return loss, grads

    def step(params, velocity_shard, batch):
        # per-device local gradients (client update)
        loss, grads = local_grads(params, batch)
        flat, spec = flatten(grads)
        flat, pad = device_agg.pad_to_multiple(flat, m)

        # Step 3: reduce-scatter mean (each device = one shard aggregator)
        shard_avg = flat
        for ax in rep:
            size = jax.lax.psum(1, ax)
            shard_avg = jax.lax.psum_scatter(shard_avg, ax,
                                             scatter_dimension=0, tiled=True)
        shard_avg = shard_avg / m
        loss = jax.lax.pmean(loss, rep)

        if compress == "qsgd8":
            # compress the *averaged* shard (paper §VI: per-shard compression)
            from repro.kernels import ops as kops
            codes, scales, l = kops.qsgd_compress(shard_avg)
            shard_avg = kops.qsgd_decompress(codes, scales, l)

        # sharded SGD-momentum update on this device's |θ|/M slice
        new_v = momentum * velocity_shard + shard_avg
        flat_params, pspec = flatten(params)
        flat_params, _ = device_agg.pad_to_multiple(flat_params, m)
        my_shard = jax.lax.dynamic_slice_in_dim(
            flat_params, _shard_index(rep) * shard_avg.shape[0],
            shard_avg.shape[0])
        new_shard = my_shard - lr * new_v

        # Step 4: reconstruct (all-gather updated shards)
        out = new_shard
        for ax in reversed(rep):
            out = jax.lax.all_gather(out, ax, axis=0, tiled=True)
        if pad:
            out = out[:-pad]
        new_params = unflatten(out, pspec)
        return new_params, new_v, loss

    def _shard_index(axes):
        idx = jax.lax.axis_index(axes[0])
        for ax in axes[1:]:
            idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
        return idx

    b_axes = rep if len(rep) > 1 else rep[0]
    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(rep if len(rep) > 1 else rep[0]),
                  {"tokens": P(b_axes, None), "labels": P(b_axes, None)}),
        out_specs=(P(), P(rep if len(rep) > 1 else rep[0]), P()),
        check_vma=False)

    def init_velocity(params):
        flat, _ = flatten(params)
        n = flat.shape[0]
        n_pad = n + ((-n) % m)
        sharding = NamedSharding(mesh, P(rep if len(rep) > 1 else rep[0]))
        return jax.device_put(jnp.zeros((n_pad,), jnp.float32), sharding)

    return jax.jit(smapped, donate_argnums=(1,)), init_velocity


# ---------------------------------------------------------------------------
# Serverless federated training (multi-round, schedule-aware)
# ---------------------------------------------------------------------------

class FederatedPipeline:
    """Deprecated: absorbed into :class:`repro.api.FederatedSession`,
    which threads ``client_done_s -> client_ready_s`` internally. Kept as
    a shim for external callers that drive ``aggregate_round`` by hand.

    Under the pipelined schedule a client may finish reading round r's
    averaged shards while stragglers are still downloading; feeding each
    round's ``client_done_s`` into the next round's ``client_ready_s`` lets
    that client's round r+1 upload start immediately — uploads overlap
    read-back, and the session wall-clock is the true makespan rather than
    a sum of round walls."""

    def __init__(self, schedule: str | None = None, upload=None):
        self.schedule = schedule
        self.upload = upload
        self.client_ready: tuple | None = None
        self.session_start_s: float | None = None
        self.session_end_s: float = 0.0
        self.round_walls: list[float] = []

    def round_kwargs(self) -> dict:
        """kwargs for the next ``aggregate_round`` call."""
        return {"schedule": self.schedule, "upload": self.upload,
                "client_ready_s": self.client_ready}

    def observe(self, result) -> None:
        """Fold one round's result into the session timeline."""
        if self.session_start_s is None:
            self.session_start_s = result.round_start_s
        done = result.client_done_s
        self.client_ready = done if len(done) else None
        self.session_end_s = max(self.session_end_s, result.round_end_s)
        self.round_walls.append(result.wall_clock_s)

    @property
    def session_wall_s(self) -> float:
        """Makespan of the whole session (first upload to last read-back)."""
        if self.session_start_s is None:
            return 0.0
        return self.session_end_s - self.session_start_s


def federated_train_loop(client_grad_fn, *, rounds: int,
                         topology: str = "gradssharding", n_shards: int = 4,
                         partition: str = "uniform", tensor_sizes=None,
                         engine=None, schedule: str | None = None,
                         upload=None, store=None, runtime=None,
                         on_round=None) -> dict:
    """Multi-round serverless aggregation driver (the paper's setting).

    ``client_grad_fn(rnd)`` returns the round's client gradients (flat f32
    vectors — typically local-SGD deltas). Rounds run through a
    :class:`repro.api.FederatedSession`, which threads per-client timing
    internally so pipelined sessions overlap rounds. ``on_round(rnd,
    result)`` is called after each round (apply the update, log,
    checkpoint). Returns the results plus session timing:
    ``session_wall_s`` (makespan) and ``sum_round_walls_s`` (what a fully
    barriered session would report).
    """
    from repro.api import FederatedSession, SessionConfig

    session = FederatedSession(
        SessionConfig(topology=topology, n_shards=n_shards,
                      partition=partition, tensor_sizes=tensor_sizes,
                      engine=engine, schedule=schedule, upload=upload),
        store=store, runtime=runtime)
    results = []
    for rnd, res in enumerate(session.run(client_grad_fn, rounds)):
        results.append(res)
        if on_round is not None:
            on_round(rnd, res)
    return {
        "results": results,
        "session_wall_s": session.session_wall_s,
        "sum_round_walls_s": session.sum_round_walls_s,
        "lambda_cost": session.runtime.total_cost(),
        "store": session.store,
        "runtime": session.runtime,
    }


# ---------------------------------------------------------------------------
# Training loop with checkpoint/restart
# ---------------------------------------------------------------------------

def train_loop(cfg: ModelConfig, *, steps: int, batch_size: int, seq_len: int,
               lr: float = 3e-4, mesh: Mesh | None = None,
               plan: ShardingPlan = ShardingPlan(),
               ckpt_dir: str | None = None, ckpt_every: int = 50,
               seed: int = 0, log_every: int = 10,
               data=None) -> dict:
    """End-to-end driver: synthetic LM data, AdamW, checkpoint/restart."""
    from repro.checkpoint import CheckpointManager
    from repro.data import SyntheticLM

    if mesh is None:
        dev = np.array(jax.devices()[:1]).reshape(1, 1)
        mesh = Mesh(dev, ("data", "model"))
    data = data or SyntheticLM(vocab=cfg.vocab, seq_len=seq_len, seed=seed)
    shape = ShapeConfig("train", seq_len=seq_len, global_batch=batch_size,
                        kind="train")

    optimizer = adamw(lr, grad_clip_norm=1.0)
    params = models.init_params(jax.random.PRNGKey(seed), cfg)
    opt_state = optimizer.init(params)
    start_step = 0

    manager = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if manager is not None:
        restored = manager.restore_latest((params, opt_state))
        if restored is not None:
            start_step, (params, opt_state), _ = restored
            print(f"[train] resumed from step {start_step}")

    step_fn = jit_train_step(cfg, shape, mesh, plan, optimizer, opt_state)

    b_shardings = parts.to_named(
        mesh, parts.batch_pspecs(cfg, shape, mesh))
    losses = []
    t0 = host_timer()
    for step in range(start_step, steps):
        batch = data.batch(client=0, step=step, batch_size=batch_size)
        batch = jax.tree.map(
            lambda x, s: jax.device_put(x, s), batch,
            {k: b_shardings[k] for k in batch})
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if log_every and step % log_every == 0:
            print(f"[train] step {step:5d} loss {losses[-1]:.4f} "
                  f"({host_timer() - t0:.1f}s)")
        if manager is not None and (step + 1) % ckpt_every == 0:
            manager.save(step + 1, (params, opt_state))
    if manager is not None:
        manager.save(steps, (params, opt_state))
    return {"losses": losses, "params": params, "final_loss":
            float(np.mean(losses[-5:])) if losses else float("nan")}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description="distributed trainer")
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad_sharding", default="zero1",
                    choices=["none", "zero1", "zero3"])
    ap.add_argument("--ckpt_dir", default=None)
    args = ap.parse_args(argv)

    from repro.configs import get_arch
    spec = get_arch(args.arch)
    cfg = spec.smoke if args.smoke else spec.model
    plan = ShardingPlan(grad_sharding=args.grad_sharding)
    out = train_loop(cfg, steps=args.steps, batch_size=args.batch,
                     seq_len=args.seq, lr=args.lr, plan=plan,
                     ckpt_dir=args.ckpt_dir)
    print(f"[train] done: final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    maybe_preload_tcmalloc()
    enable_compile_cache()
    main()
