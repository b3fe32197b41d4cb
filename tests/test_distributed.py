"""Multi-device (8 fake CPU devices) checks, run in subprocesses so the main
test process keeps its single-device view.

Verifies DESIGN.md §3's central mapping: reduce-scatter gradient sharding
(GradsSharding on TPU) over per-replica contributions agrees with
full-gradient all-reduce (λ-FL analogue) and with the host numpy mean.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(body: str, n_devices: int = 8) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_devices}"
        import numpy as np
        import jax, jax.numpy as jnp
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_device_count_isolated():
    out = run_subprocess("print(len(jax.devices()))")
    assert out.strip().endswith("8")


def test_reduce_scatter_equals_allreduce_equals_numpy():
    run_subprocess("""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.core import device_agg

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        rows = NamedSharding(mesh, P(("pod", "data")))
        rng = np.random.default_rng(0)
        # one distinct contribution per replica (pod*data = 4), stacked on
        # the leading axis and sharded so replica r holds row r
        tree = {"a": rng.standard_normal((4, 4, 6)).astype(np.float32),
                "b": rng.standard_normal((4, 17)).astype(np.float32)}
        ref = {k: v.mean(axis=0) for k, v in tree.items()}
        dev = {k: jax.device_put(v, rows) for k, v in tree.items()}

        for hier in (False, True):
            ar = device_agg.all_reduce_mean(mesh, dev, hierarchical=hier)
            for k in tree:
                np.testing.assert_allclose(np.asarray(ar[k]), ref[k],
                                           rtol=1e-6, atol=1e-7)

        # reduce-scatter + all-gather reconstructs the mean
        flat = tree["b"]
        pad = (-flat.shape[1]) % 4
        flat_p = np.pad(flat, ((0, 0), (0, pad)))
        shards = device_agg.reduce_scatter_mean_flat(
            mesh, jax.device_put(flat_p, rows))
        # device (pod, data) owns shard pod*2 + data, on every model index
        owned = {(d.id, s.index[0].start) for d in mesh.devices.flat
                 for s in shards.addressable_shards if s.device == d}
        assert len(owned) == 8 and \
            len({start for _d, start in owned}) == 4, owned
        full = np.asarray(device_agg.all_gather_shards(mesh, shards))
        np.testing.assert_allclose(full[:flat.shape[1]], ref["b"],
                                   rtol=1e-6, atol=1e-7)
        print("DEVICE_AGG_OK")
    """)


def test_shardmap_trainer_matches_single_device_fedavg():
    """The shard_map GradsSharding trainer (devices = clients, reduce-scatter
    = shard aggregators) must match a single-device step on the concatenated
    batch — the same invariance the paper proves for the serverless path."""
    run_subprocess("""
        import dataclasses
        from repro.configs import get_arch
        from repro.launch.mesh import make_mesh
        from repro.launch.train import make_shardmap_train_step
        from repro.models import registry as models
        from repro.core.sharding import flatten

        cfg = dataclasses.replace(get_arch("tinyllama-1.1b").smoke,
                                  n_layers=2, remat=False,
                                  compute_dtype=jnp.float32)
        mesh = make_mesh((4, 2), ("data", "model"))
        params = models.init_params(jax.random.PRNGKey(0), cfg)

        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg.vocab, (8, 17))
        batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                 "labels": jnp.asarray(toks[:, 1:], jnp.int32)}

        step, init_v = make_shardmap_train_step(cfg, mesh, lr=0.1,
                                                momentum=0.0)
        v = init_v(params)
        new_params, _, loss = step(params, v, batch)

        # single-device reference: same loss fn over the whole batch
        (ref_loss, _), grads = jax.value_and_grad(
            models.loss_fn, has_aux=True)(params, cfg, batch)
        ref_params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)

        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        f1, _ = flatten(new_params)
        f2, _ = flatten(ref_params)
        np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                                   rtol=2e-4, atol=2e-5)
        print("SHARDMAP_TRAINER_OK")
    """)


@pytest.mark.parametrize("gs", ["zero1", "zero3"])
def test_gspmd_plans_agree(gs):
    """Sharding plans produce the same training numerics as the replicated
    baseline (they only change layout + collective schedule)."""
    run_subprocess(f"""
        import dataclasses
        from repro.config import ShapeConfig, ShardingPlan
        from repro.configs import get_arch
        from repro.launch.mesh import make_mesh
        from repro.launch.train import jit_train_step
        from repro.models import registry as models
        from repro.optim import adamw
        from repro.core.sharding import flatten

        cfg = dataclasses.replace(get_arch("tinyllama-1.1b").smoke,
                                  n_layers=2, remat=False,
                                  compute_dtype=jnp.float32)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = ShapeConfig("t", seq_len=16, global_batch=8, kind="train")
        opt = adamw(1e-3)
        params = models.init_params(jax.random.PRNGKey(0), cfg)
        state = opt.init(params)
        rng = np.random.default_rng(1)
        toks = rng.integers(0, cfg.vocab, (8, 17))
        batch = {{"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                  "labels": jnp.asarray(toks[:, 1:], jnp.int32)}}

        outs = {{}}
        for gs in ("none", "{gs}"):
            plan = ShardingPlan(grad_sharding=gs)
            step = jit_train_step(cfg, shape, mesh, plan, opt, state,
                                  donate=False)
            p2, s2, m = step(params, state, batch)
            outs[gs] = (flatten(p2)[0], float(m["loss"]))
        assert abs(outs["{gs}"][1] - outs["none"][1]) < 1e-5
        # plans reassociate fp reductions (collective schedules differ):
        # tolerance covers the observed ~4e-4 worst relative deviation
        np.testing.assert_allclose(np.asarray(outs["{gs}"][0]),
                                   np.asarray(outs["none"][0]),
                                   rtol=5e-4, atol=1e-4)
        print("GSPMD_PLANS_OK")
    """)


def test_qsgd_compressed_training_still_learns():
    """Compressed-gradient shard_map training (paper §VI composition):
    loss decreases despite int8 gradient quantization."""
    run_subprocess("""
        import dataclasses
        from repro.configs import get_arch
        from repro.launch.mesh import make_mesh
        from repro.launch.train import make_shardmap_train_step
        from repro.models import registry as models

        cfg = dataclasses.replace(get_arch("tinyllama-1.1b").smoke,
                                  n_layers=2, remat=False)
        mesh = make_mesh((4, 2), ("data", "model"))
        params = models.init_params(jax.random.PRNGKey(0), cfg)
        step, init_v = make_shardmap_train_step(cfg, mesh, lr=0.05,
                                                momentum=0.9,
                                                compress="qsgd8")
        v = init_v(params)
        rng = np.random.default_rng(0)
        losses = []
        for i in range(10):
            toks = rng.integers(0, 64, (8, 17))
            batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                     "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
            params, v, loss = step(params, v, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        print("QSGD_TRAIN_OK", losses[0], losses[-1])
    """)


@pytest.mark.slow
def test_dryrun_tiny_cell_scan2_matches_unroll():
    """scan2's per-layer scaling must agree with a genuine full unroll on a
    small config (validates the dry-run accounting method)."""
    run_subprocess("""
        import dataclasses, json
        from repro.config import ShapeConfig, ShardingPlan
        from repro.configs import get_arch, REGISTRY
        from repro.launch.mesh import make_mesh
        from repro.launch import dryrun as dr
        from repro.config import ArchSpec

        # register a small-but-multi-layer variant as its own arch
        base = get_arch("tinyllama-1.1b")
        small = dataclasses.replace(base.model, n_layers=4, d_model=128,
                                    n_heads=4, n_kv_heads=2, head_dim=32,
                                    d_ff=256, vocab=512, attn_chunk=64)
        REGISTRY["tiny-test"] = ArchSpec("tiny-test", small, base.smoke)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = ShapeConfig("t", seq_len=256, global_batch=8, kind="train")
        plan = ShardingPlan()
        r2 = dr.analyze_cell("tiny-test", shape, mesh, "tiny", plan,
                             mode="scan2", verbose=False)
        ru = dr.analyze_cell("tiny-test", shape, mesh, "tiny", plan,
                             mode="unroll", verbose=False)
        f_rel = abs(r2["flops_per_device"] - ru["flops_per_device"]) / \
            ru["flops_per_device"]
        assert f_rel < 0.05, (r2["flops_per_device"], ru["flops_per_device"])
        c2 = r2["collectives"]["total_bytes"]
        cu = ru["collectives"]["total_bytes"]
        assert cu == 0 or abs(c2 - cu) / max(cu, 1) < 0.15, (c2, cu)
        print("SCAN2_VS_UNROLL_OK", f_rel)
    """)


def test_moe_local_dispatch_matches_global():
    """shard_map per-device MoE dispatch (the §Perf B1 optimization) must
    match the global-dispatch path in forward and gradients."""
    run_subprocess("""
        import dataclasses
        from repro.configs import get_arch
        from repro.models import registry as R, meshctx
        from repro.launch.mesh import make_mesh

        smoke = get_arch("phi3.5-moe-42b-a6.6b").smoke
        cfg = dataclasses.replace(
            smoke, compute_dtype=jnp.float32, remat=False,
            moe=dataclasses.replace(smoke.moe, capacity_factor=8.0))
        params = R.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg.vocab, (8, 17))
        batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                 "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
        l_global = R.forward(params, cfg, batch)
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg_l = dataclasses.replace(cfg, moe_dispatch="local")
        with meshctx.use_mesh(mesh):
            l_local = jax.jit(lambda p, b: R.forward(p, cfg_l, b))(params,
                                                                   batch)
            def loss_l(p):
                return R.loss_fn(p, cfg_l, batch)[0]
            g = jax.grad(loss_l)(params)
        np.testing.assert_allclose(np.asarray(l_global),
                                   np.asarray(l_local),
                                   rtol=2e-4, atol=2e-4)
        gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
        assert np.isfinite(gn) and gn > 0
        print("MOE_LOCAL_OK")
    """)


# ---------------------------------------------------------------------------
# host_mesh engine: shard_map folds over fake CPU devices (PR 9)
# ---------------------------------------------------------------------------

def test_host_mesh_fold_sum_bit_identical_to_numpy_chain():
    """The on-device sequential left-fold must replay the exact f32 add
    chain of the streaming reference — bit-identical, not allclose —
    on 8-, 4- and 2-device meshes (element sharding never reorders the
    per-element op sequence)."""
    run_subprocess("""
        from repro.core import device_agg

        rng = np.random.default_rng(2)
        stack = rng.standard_normal((7, 5_003)).astype(np.float32)
        ref = stack[0].copy()
        for i in range(1, 7):
            ref = ref + stack[i]
        for nd in (8, 4, 2, None):
            mesh = device_agg.make_fold_mesh(nd)
            total = device_agg.mesh_fold_sum(mesh, stack)
            assert np.array_equal(total, ref), nd
        # host-side divide completes the engine's op sequence
        avg = np.empty(5_003, np.float32)
        np.divide(ref, np.float32(7.0), out=avg)
        assert np.array_equal(avg, (ref / np.float32(7.0)))
        print("MESH_FOLD_OK")
    """)


def test_host_mesh_engine_end_to_end_bit_identical():
    """run_round(engine='host_mesh') == streaming, bit for bit, on both
    an unweighted tree (lambda_fl) and the sharded topology; weighted
    folds fall back to the numpy evaluator inside the same backend."""
    run_subprocess("""
        from repro.core.topology import run_round
        from repro.serverless.runtime import LambdaRuntime
        from repro.store import ObjectStore

        rng = np.random.default_rng(3)
        grads = [rng.standard_normal(4_099).astype(np.float32)
                 for _ in range(9)]
        for topology, opts in [("lambda_fl", {}),
                               ("gradssharding", {"n_shards": 4})]:
            ref = run_round(topology, grads, rnd=0, store=ObjectStore(),
                            runtime=LambdaRuntime(), engine="streaming",
                            **opts)
            got = run_round(topology, grads, rnd=0, store=ObjectStore(),
                            runtime=LambdaRuntime(), engine="host_mesh",
                            host_mesh=4, **opts)
            assert np.array_equal(got.avg_flat, ref.avg_flat), topology
            assert (got.puts, got.gets) == (ref.puts, ref.gets)
            assert got.wall_clock_s == ref.wall_clock_s
        print("HOST_MESH_ROUND_OK")
    """)


def test_host_mesh_session_and_errors():
    """SessionConfig(engine='host_mesh', host_mesh=N) drives the engine
    through the facade; an oversized device request names the XLA_FLAGS
    fix; the knob is rejected on other engines."""
    run_subprocess("""
        from repro.api import FederatedSession, SessionConfig

        rng = np.random.default_rng(4)
        grads = [rng.standard_normal(2_048).astype(np.float32)
                 for _ in range(6)]
        ref = FederatedSession(SessionConfig(
            topology="lifl", engine="streaming")).round(grads)
        got = FederatedSession(SessionConfig(
            topology="lifl", engine="host_mesh", host_mesh=8)).round(grads)
        assert np.array_equal(got.avg_flat, ref.avg_flat)

        try:
            FederatedSession(SessionConfig(
                engine="host_mesh", host_mesh=64)).round(grads)
            raise SystemExit("oversized mesh should have raised")
        except ValueError as e:
            assert "xla_force_host_platform_device_count" in str(e)
        print("HOST_MESH_SESSION_OK")
    """)
