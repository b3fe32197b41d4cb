"""The round's fold spread over several chips (``SessionConfig.chips``).

``ops.fedavg_multi`` cuts a round's columns into one tile-aligned range
per device and streams each range through its own staging pipeline. Here
the devices are four host CPU devices in a subprocess
(``--xla_force_host_platform_device_count=4``, so the main test process
keeps its one-device view) and the kernel runs in interpret mode
(``REPRO_AGG_PALLAS=1``). Every column's add chain is the same f32 left
fold on whichever chip, so four chips are bit-identical to one.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.kernels import ops

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TILE = 32 * ops.LANES
#: GPT-2 Large's f32 gradient elements (2953 MB, the repository's rule)
GPT2_LARGE_ELEMS = 774_111_232


def run_four_devices(body: str) -> str:
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["REPRO_AGG_PALLAS"] = "1"
        import numpy as np
        import jax
        from repro.kernels import ops
        assert len(jax.local_devices()) == 4
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_AGG_ENGINE", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.mark.parametrize("total, chips", [
    (1, 4), (3 * TILE, 4), (33_110, 4), (10_000, 3), (5 * TILE + 7, 2),
    (100, 1), (GPT2_LARGE_ELEMS, 4)])
def test_chip_ranges_are_tile_aligned_balanced_and_cover_once(total, chips):
    ranges = ops.chip_ranges(total, chips)
    assert len(ranges) == chips
    assert ranges[0][0] == 0 and ranges[-1][1] == total
    assert all(a == b_prev for (_, b_prev), (a, _) in zip(ranges, ranges[1:]))
    # every cut on a tile, bar the empty ranges at the end (fewer tiles
    # than chips)
    assert all((a % TILE == 0 and a < b) or a == b == total for a, b in ranges)
    tiles = [-(-(b - a) // TILE) for a, b in ranges]
    assert max(tiles) - min(tiles) <= 1
    assert tiles == sorted(tiles, reverse=True)  # the first chips hold extras
    assert ranges[0][1] > 0


def test_gpt2_large_chip_ranges_are_its_four_shards():
    """At M=4 with uniform shards, each chip's range is one aggregator's
    shard."""
    from repro.core.sharding import plan_uniform

    shards = [seg for (seg,) in plan_uniform(GPT2_LARGE_ELEMS, 4).segments]
    assert ops.chip_ranges(GPT2_LARGE_ELEMS, 4) == shards


@pytest.mark.parametrize("m", [4, 6])
@pytest.mark.parametrize("weighted", [False, True], ids=["mean", "weighted"])
def test_fedavg_multi_on_four_chips_is_bit_identical(m, weighted):
    """Four devices, several windows each, against the one-device call and
    the numpy left fold (power-of-two weights: every product is exact)."""
    run_four_devices(f"""
        n, tile, weighted = 5, 32 * 128, {weighted}
        ops.STAGING_BYTES = 4 * n * tile * 2
        rng = np.random.default_rng({m})
        lengths = [int(x) for x in rng.integers(1, 4 * tile, {m})]
        stacks = [rng.standard_normal((n, l)).astype(np.float32)
                  for l in lengths]
        w = np.array([1, 2, 0.5, 4, 0.25], np.float32) if weighted else None
        ranges = ops.chip_ranges(sum(lengths), 4)
        per_chip = [len(ops.fold_windows(b - a, n, None)) for a, b in ranges]
        assert min(per_chip) >= 2, per_chip
        four = ops.fedavg_multi(stacks, w, workers=1,
                                devices=jax.local_devices())
        one = ops.fedavg_multi(stacks, w, workers=1)
        for stack, got, want in zip(stacks, four, one):
            acc = stack[0] * w[0] if weighted else stack[0].copy()
            for i, row in enumerate(stack[1:], 1):
                acc += row * w[i] if weighted else row
            ref = acc / (w.sum() if weighted else np.float32(n))
            assert got.dtype == np.float32 and got.shape == ref.shape
            assert np.array_equal(got, want) and np.array_equal(got, ref)
        print("OK", per_chip)
    """)


def test_fedavg_multi_on_four_chips_divides_into_one_buffer():
    """Each chip's windows divide into their own column range of the one
    buffer: the means are its consecutive views in stack order, equal to
    the numpy evaluator bit for bit, sharing no memory with the inputs or
    the eight staging buffers."""
    out = run_four_devices("""
        from repro.core.agg_engine import LazyAverage, _evaluate_nodes
        n, tile = 3, 32 * 128
        ops.STAGING_BYTES = 4 * n * tile
        rng = np.random.default_rng(16)
        lengths = [3 * tile + 5, 7, 4 * tile, 2 * tile + 1]
        stacks = [rng.standard_normal((n, l)).astype(np.float32)
                  for l in lengths]
        means = ops.fedavg_multi(stacks, workers=1,
                                 devices=jax.local_devices())
        buf = means[0].base
        assert buf.dtype == np.float32 and buf.shape == (sum(lengths),)
        addr = buf.__array_interface__["data"][0]
        for off, l, mean in zip(np.cumsum([0] + lengths), lengths, means):
            assert mean.base is buf and mean.shape == (l,)
            assert mean.__array_interface__["data"][0] == addr + 4 * off
        nodes = [LazyAverage(list(stack), None) for stack in stacks]
        _evaluate_nodes(nodes)
        assert all(np.array_equal(m, nd.out) for m, nd in zip(means, nodes))
        assert len(ops._staging) == 8
        for other in [*stacks, *ops._staging.values()]:
            assert not np.shares_memory(buf, other)
        print("ONE_BUFFER_OK")
    """)
    assert "ONE_BUFFER_OK" in out


def test_session_on_four_chips_matches_the_streaming_engine():
    out = run_four_devices("""
        from repro.api import FederatedSession, SessionConfig
        ops.STAGING_BYTES = 4 * 6 * 32 * 128
        rng = np.random.default_rng(3)
        grads = [rng.standard_normal(70_001).astype(np.float32)
                 for _ in range(6)]
        for workers in (1, 2):
            four = FederatedSession(SessionConfig(
                n_shards=4, chips=4, workers=workers)).round(grads)
            ref = FederatedSession(SessionConfig(
                n_shards=4, engine="streaming")).round(grads)
            assert np.array_equal(four.avg_flat, ref.avg_flat)
            assert (four.kernel_folds, four.engine) == (4, "batched")
            assert (four.puts, four.gets) == (ref.puts, ref.gets)
        print("SESSION_OK")
    """)
    assert "SESSION_OK" in out


def test_chips_the_session_cannot_use_are_refused():
    out = run_four_devices("""
        from repro.api import FederatedSession, SessionConfig
        refused = []
        for kw in [dict(chips=5), dict(chips=0),
                   dict(chips=2, engine="streaming"),
                   dict(chips=4, engine="incremental"),
                   dict(chips=2, engine="host_mesh", host_mesh=2)]:
            try:
                FederatedSession(SessionConfig(**kw))
            except ValueError as e:
                refused.append(str(e))
        assert len(refused) == 5, refused
        assert "exceeds the 4 local devices" in refused[0]
        assert all("needs engine='batched'" in r for r in refused[2:])
        FederatedSession(SessionConfig(chips=4))
        FederatedSession(SessionConfig(chips=2, engine="batched"))
        print("REFUSED_OK")
    """)
    assert "REFUSED_OK" in out


def test_chips_above_the_local_devices_are_refused_here():
    from repro.api import FederatedSession, SessionConfig

    import jax

    with pytest.raises(ValueError, match="local devices"):
        FederatedSession(SessionConfig(chips=len(jax.local_devices()) + 1))
