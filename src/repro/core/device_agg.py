"""Device-parallel aggregation: the paper's topologies as TPU collectives.

The serverless architectures map onto mesh collectives (DESIGN.md §3):

  * full-gradient (λ-FL/LIFL leaf semantics)  -> ``all_reduce_mean``:
    every replica ends with the full averaged gradient, O(|θ|) memory each.
  * GradsSharding                             -> ``reduce_scatter_mean``:
    replica j ends with averaged shard j only, O(|θ|/M) memory each —
    bit-identical semantics to sharding + per-shard averaging.
  * shard reconstruct (Step 4)                -> ``all_gather_shards``.
  * λ-FL's two-level tree                     -> ``hierarchical_all_reduce``:
    reduce inside the pod (fast ICI ≈ leaf aggregators), then across pods
    (slow DCI ≈ root) — same math, fewer cross-pod bytes.

All functions run inside ``shard_map`` with per-device views; M = product of
the replica axis sizes. Used by the ZeRO trainer (`launch/train.py`) and
verified against the host numpy mean on 8 fake CPU devices and on a
four-chip v5e host (``chip_smoke.py --chips 4``).
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

Pytree = Any


# ---------------------------------------------------------------------------
# In-shard_map collective primitives (operate on per-device views)
# ---------------------------------------------------------------------------

def pmean(tree: Pytree, axes) -> Pytree:
    return jax.tree.map(lambda g: lax.pmean(g, axes), tree)


def all_gather_flat(shard: jax.Array, axis: str) -> jax.Array:
    return lax.all_gather(shard, axis, axis=0, tiled=True)


def hierarchical_mean(tree: Pytree, inner_axis: str,
                      outer_axis: str) -> Pytree:
    """Two-stage mean: inner (ICI/pod-local ≈ λ-FL leaves) then outer
    (DCI/cross-pod ≈ root). Algebraically the joint mean for equal group
    sizes."""
    t = jax.tree.map(lambda g: lax.pmean(g, inner_axis), tree)
    return jax.tree.map(lambda g: lax.pmean(g, outer_axis), t)


# ---------------------------------------------------------------------------
# Padding helpers
# ---------------------------------------------------------------------------

def pad_to_multiple(flat: jax.Array, m: int) -> tuple[jax.Array, int]:
    pad = (-flat.shape[0]) % m
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


# ---------------------------------------------------------------------------
# jit-level wrappers over a mesh (gradient pytrees)
# ---------------------------------------------------------------------------

def _replica_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def all_reduce_mean(mesh: Mesh, grads: Pytree,
                    hierarchical: bool = False) -> Pytree:
    """Full-gradient aggregation over the replica axes (λ-FL analogue).

    Every leaf of ``grads`` stacks one contribution per replica on its
    leading axis (length = replica count, sharded over the replica axes);
    every device ends with the full mean, replicated."""
    axes = _replica_axes(mesh)

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes), out_specs=P(),
             check_vma=False)
    def agg(g):
        g = jax.tree.map(lambda x: x[0], g)
        if hierarchical and len(axes) > 1:
            return hierarchical_mean(g, axes[-1], axes[0])
        return pmean(g, axes)

    return agg(grads)


def reduce_scatter_mean_flat(mesh: Mesh, stack: jax.Array) -> jax.Array:
    """GradsSharding: per-replica flat gradients -> per-device mean shard.

    ``stack`` is (M, L), one contribution per replica (row r on replica
    r, sharded over the replica axes), L a multiple of M (callers pad via
    ``pad_to_multiple``). Output is (L,) sharded over the same axes:
    device d owns averaged shard d."""
    axes = _replica_axes(mesh)

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes), out_specs=P(axes),
             check_vma=False)
    def agg(g):
        out = g[0]
        m = 1
        for ax in axes:
            out = lax.psum_scatter(out, ax, scatter_dimension=0, tiled=True)
            m *= lax.psum(1, ax)
        return out / m

    return agg(stack)


# ---------------------------------------------------------------------------
# Host CPU meshes (the host_mesh engine's substrate)
# ---------------------------------------------------------------------------

def host_cpu_devices() -> list:
    """Every visible host CPU device — more than one when the process was
    started with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    return [d for d in jax.devices() if d.platform == "cpu"]


def make_fold_mesh(n_devices: int | None = None) -> Mesh:
    """A 1-D ``fold``-axis mesh over host CPU devices.

    ``n_devices=None`` takes every visible CPU device; an explicit count
    larger than what XLA exposes is an error with the fix spelled out
    (the device count is fixed at process start, before jax imports).
    """
    devices = host_cpu_devices()
    if not devices:
        raise RuntimeError(
            "no host CPU devices visible — the host_mesh engine needs the "
            "CPU platform")
    if n_devices is not None:
        if n_devices < 1:
            raise ValueError(f"host_mesh must be >= 1, got {n_devices}")
        if n_devices > len(devices):
            raise ValueError(
                f"host_mesh={n_devices} exceeds the {len(devices)} visible "
                f"CPU device(s); start the process with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n_devices} "
                f"(before jax is imported)")
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), ("fold",))


def mesh_fold_sum(mesh: Mesh, stack) -> "jax.Array":
    """Element-sharded sequential left-fold sum of ``stack`` (N, L) -> (L,).

    Each mesh device owns a contiguous slice of the element axis and adds
    the N rows of its slice **in row order** — the exact f32 add chain of
    the streaming reference (and of ``agg_engine._node_chunk``), so the
    returned sum is bit-identical to the single-threaded numpy fold; the
    caller performs the final divide host-side to keep the one-divide op
    sequence.  L is padded to a device multiple and trimmed after.
    """
    stack = np.ascontiguousarray(np.asarray(stack, np.float32))
    n, l = stack.shape
    m = mesh.devices.size
    padded, _pad = pad_to_multiple_cols(stack, m)

    @partial(jax.shard_map, mesh=mesh, in_specs=P(None, "fold"),
             out_specs=P("fold"), check_vma=False)
    def fold(block):
        out = block[0]
        for i in range(1, n):
            out = out + block[i]
        return out

    return np.asarray(jax.jit(fold)(padded))[:l]


def pad_to_multiple_cols(arr, m: int):
    """Pad the last axis of (N, L) to a multiple of ``m``."""
    pad = (-arr.shape[-1]) % m
    if pad:
        arr = jnp.pad(arr, ((0, 0), (0, pad)))
    return arr, pad


def all_gather_shards(mesh: Mesh, shards: jax.Array) -> jax.Array:
    """Step 4: reconstruct the full flat vector from per-device shards."""
    axes = _replica_axes(mesh)

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes), out_specs=P(),
             check_vma=False)
    def gather(s):
        out = s
        for ax in reversed(axes):
            out = all_gather_flat(out, ax)
        return out

    return gather(shards)
