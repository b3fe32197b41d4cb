"""Gradient partitioning invariants (paper Step 1 / Step 4)."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # bare env: deterministic fallback
    from _hypothesis_fallback import given, settings
    from _hypothesis_fallback import strategies as st

import jax
import jax.numpy as jnp

from repro.core.sharding import (
    flatten,
    make_plan,
    plan_balanced,
    plan_layer_contiguous,
    plan_uniform,
    reconstruct,
    shard,
    unflatten,
)


# ---------------------------------------------------------------------------
# Plans
# ---------------------------------------------------------------------------

@given(total=st.integers(1, 10_000), m=st.integers(1, 64))
@settings(max_examples=200, deadline=None)
def test_uniform_plan_covers_everything(total, m):
    plan = plan_uniform(total, m)
    sizes = plan.shard_sizes()
    assert sum(sizes) == total
    assert len(sizes) == m
    # contiguous, ordered, disjoint
    stops = [segs[0][1] for segs in plan.segments]
    starts = [segs[0][0] for segs in plan.segments]
    assert starts[0] == 0 and stops[-1] == total
    assert all(a == b for a, b in zip(stops[:-1], starts[1:]))
    # balanced within 1 element — the O(|θ|/M) bound
    assert max(sizes) - min(sizes) <= 1


@given(st.lists(st.integers(1, 5_000), min_size=1, max_size=40),
       st.integers(1, 16))
@settings(max_examples=100, deadline=None)
def test_balanced_plan_partitions_tensors(sizes, m):
    plan = plan_balanced(sizes, m)
    assert sum(plan.shard_sizes()) == sum(sizes)
    # every tensor range appears exactly once
    seen = sorted(r for segs in plan.segments for r in segs)
    offsets = np.cumsum([0] + sizes)
    expect = sorted((int(offsets[i]), int(offsets[i + 1]))
                    for i in range(len(sizes)))
    assert seen == expect


def test_balanced_beats_layer_contiguous_on_heterogeneous():
    # one dominant tensor (an MoE expert block / embedding) + many small ones
    sizes = [100_000] + [500] * 40
    m = 4
    bal = plan_balanced(sizes, m)
    cont = plan_layer_contiguous(sizes, m)
    assert bal.imbalance() <= cont.imbalance()


@given(total=st.integers(8, 5_000), m=st.integers(1, 16),
       seed=st.integers(0, 99))
@settings(max_examples=50, deadline=None)
def test_shard_reconstruct_roundtrip_uniform(total, m, seed):
    rng = np.random.default_rng(seed)
    flat = rng.standard_normal(total).astype(np.float32)
    plan = plan_uniform(total, m)
    back = reconstruct(shard(flat, plan), plan)
    np.testing.assert_array_equal(back, flat)


@given(st.lists(st.integers(1, 300), min_size=2, max_size=12),
       st.integers(1, 6))
@settings(max_examples=50, deadline=None)
def test_shard_reconstruct_roundtrip_balanced(sizes, m):
    rng = np.random.default_rng(0)
    flat = rng.standard_normal(sum(sizes)).astype(np.float32)
    plan = plan_balanced(sizes, m)
    back = reconstruct(shard(flat, plan), plan)
    np.testing.assert_array_equal(back, flat)


@pytest.mark.parametrize("plan", [
    plan_uniform(4_096, 4),
    plan_uniform(4_096, 1),
    plan_layer_contiguous([1_000, 96, 3_000], 3),
    plan_balanced([1_000, 96, 3_000], 2),
], ids=["uniform", "one-shard", "layer_contiguous", "balanced"])
def test_reconstruct_of_caller_shards_is_a_new_array(plan):
    """Shards of an array the caller made are copied into a new array,
    whatever their layout."""
    flat = np.random.default_rng(1).standard_normal(plan.total) \
        .astype(np.float32)
    back = reconstruct(shard(flat, plan), plan)
    assert back is not flat and not np.shares_memory(back, flat)
    np.testing.assert_array_equal(back, flat)


def test_reconstruct_hands_out_a_fold_buffer_once():
    """The fold's means at their plan segments come back as the fold's own
    buffer, the first time only: shards of it later, or views of any other
    buffer, are copied."""
    from repro.kernels import ops
    plan = plan_uniform(3_000, 3)
    stacks = [np.random.default_rng(j).standard_normal((2, 1_000))
              .astype(np.float32) for j in range(3)]
    means = ops.fedavg_multi(stacks, workers=1)
    whole = reconstruct(means, plan)
    assert all(m.base is whole for m in means)
    again = reconstruct(means, plan)
    assert again is not whole and not np.shares_memory(again, whole)
    np.testing.assert_array_equal(again, whole)
    swapped = ops.fedavg_multi(stacks[::-1], workers=1)[::-1]
    back = reconstruct(swapped, plan)
    assert not any(np.shares_memory(back, m) for m in swapped)
    np.testing.assert_array_equal(back, whole)


# ---------------------------------------------------------------------------
# Flatten / unflatten
# ---------------------------------------------------------------------------

def test_flatten_roundtrip_pytree():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16),
                  "d": jnp.float32(3.0)}}
    flat, spec = flatten(tree)
    assert flat.shape == (6 + 4 + 1,)
    back = unflatten(flat, spec)
    for l1, l2 in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert l1.dtype == l2.dtype
        np.testing.assert_allclose(np.asarray(l1, np.float32),
                                   np.asarray(l2, np.float32))


def test_make_plan_validation():
    with pytest.raises(ValueError):
        make_plan("balanced", 100, 4, None)
    with pytest.raises(ValueError):
        make_plan("nope", 100, 4, [100])
    assert make_plan("uniform", 100, 4).n_shards == 4
