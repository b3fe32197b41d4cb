"""Compile-only guards: the aggregation path's kernels for a described v5e.

Each test lowers and compiles a main-path kernel — or the four-chip mesh
collective — with the TPU compiler for a ``v5e:2x2`` topology that is
described, not attached, at the paper's headline size: VGG-16's 512.3 MB
gradient, N=20 clients, M=8 shards. Nothing runs; a tiling, VMEM or HBM
refusal that interpret mode cannot see fails here. The topology is
described inside a module fixture (never at import) and the tests skip
where it cannot be; the persistent compile cache is off around them,
since a compile for a described chip cannot be read back without one.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_workloads import PAPER_WORKLOADS
from repro.kernels import fedavg_stream as fa
from repro.kernels import ops
from repro.kernels import quantize as q
from repro.kernels import topk_sparsify as tk

N, M, TILE = 20, 8, 32 * 128
VGG16_ELEMS = int(PAPER_WORKLOADS["vgg16"].grad_mb * 1024 * 1024 / 4)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


SHARD_ROWS = _ceil_div(_ceil_div(VGG16_ELEMS, M), TILE) * 32  # one shard
#: ``memory_stats()["bytes_limit"]`` of one v5e chip, as the chip reports
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, kernel: bool = True):
    compiled = jax.jit(fn).lower(*shapes).compile()
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _fits(compiled, limit=V5E_BYTES_LIMIT) -> int:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= limit, (used, limit)
    return used


@pytest.mark.parametrize("weighted", [False, True])
def test_fedavg_stream_compiles_at_vgg16_shard(one_chip, weighted):
    x = jax.ShapeDtypeStruct((N, SHARD_ROWS, 128), jnp.float32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=one_chip)
    if weighted:
        c = _compile(lambda x, w: fa.fedavg_stream(x, w, interpret=False),
                     x, w)
    else:
        c = _compile(lambda x: fa.fedavg_stream(x, interpret=False), x)
    assert _fits(c) >= 4 * N * SHARD_ROWS * 128


def test_quantize_dequantize_compile_at_vgg16_shard(one_chip):
    x = jax.ShapeDtypeStruct((SHARD_ROWS, 128), jnp.float32,
                             sharding=one_chip)
    codes = jax.ShapeDtypeStruct((SHARD_ROWS, 128), jnp.int8,
                                 sharding=one_chip)
    scales = jax.ShapeDtypeStruct((SHARD_ROWS // 32, 1), jnp.float32,
                                  sharding=one_chip)
    _fits(_compile(lambda x: q.quantize(x, interpret=False), x))
    _fits(_compile(lambda c, s: q.dequantize(c, s, interpret=False),
                   codes, scales))


def test_topk_sparsify_compiles_at_vgg16_shard(one_chip):
    x = jax.ShapeDtypeStruct((SHARD_ROWS, 128), jnp.float32,
                             sharding=one_chip)
    _fits(_compile(lambda x: tk.topk_sparsify(x, 128, interpret=False), x))


def test_largest_fold_window_fits_one_v5e(one_chip):
    """The biggest launch the batched engine forms for a VGG-16 N=20
    round on an idle chip compiles, fills at most one staging buffer, and
    two such launches in flight fit half of the chip's HBM."""
    windows = ops.fold_windows(VGG16_ELEMS, N, V5E_BYTES_LIMIT // 2)
    assert len(windows) > 1           # one launch would not fit
    cols = max(_ceil_div(b - a, TILE) * TILE for a, b in windows)
    assert 4 * N * cols <= ops.STAGING_BYTES
    x = jax.ShapeDtypeStruct((N, cols // 128, 128), jnp.float32,
                             sharding=one_chip)
    c = _compile(lambda x: ops._fold_sum(x, None, 32, False), x)
    assert 2 * _fits(c) <= V5E_BYTES_LIMIT // 2
    assert _fits(c) >= 4 * (N + 1) * cols


def test_mesh_reduce_scatter_compiles_on_four_chips(topo):
    """GradsSharding as a mesh collective: a (4, L) stack, one VGG-16-size
    contribution per chip, reduce-scattered then all-gathered."""
    from repro.core import device_agg
    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(2, 2),
                             ("pod", "data"))
    padded = VGG16_ELEMS + (-VGG16_ELEMS) % 4
    stack = jax.ShapeDtypeStruct(
        (4, padded), jnp.float32,
        sharding=NamedSharding(mesh, P(("pod", "data"))))

    def round_trip(s):
        shards = device_agg.reduce_scatter_mean_flat(mesh, s)
        return device_agg.all_gather_shards(mesh, shards)

    # the v5e compiler lowers this reduce-scatter as an all-reduce plus a
    # per-device slice; the mean shards stay sharded over the four chips
    scatter = _compile(lambda s: device_agg.reduce_scatter_mean_flat(mesh, s),
                       stack, kernel=False)
    assert "all-reduce" in scatter.as_text()
    assert scatter.output_shardings.spec == P(("pod", "data"))
    _fits(scatter)
    _fits(_compile(round_trip, stack, kernel=False))
