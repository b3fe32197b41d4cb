"""Chip smoke test: the paper's headline GradsSharding round on one TPU.

One process, run from the repository root:

    python chip_smoke.py             # one chip: the session round + codecs
    python chip_smoke.py --chips 4   # four chips: the mesh collectives only

With one chip it runs VGG-16's 512.3 MB gradient (f32 elements counted as
``benchmarks/agg_engine_bench.py`` counts them) from N=20 seeded clients
through ``FederatedSession`` with ``topology="gradssharding"``, M=8 shards,
the default ``batched`` engine and the barrier schedule: two identity
rounds (the first compiles), then one ``qsgd8`` and one ``topk`` round. Each
round's ``avg_flat`` must be bit-identical to an ``engine="streaming"``
session on the same gradients, and every shard must have been folded by
the compiled Pallas kernel. With ``--chips 4`` it reduce-scatters and
all-gathers four distinct VGG-16-size contributions, one per chip
(GradsSharding as a mesh collective), all-reduces them (the lambda-FL
comparison), and checks both against the host numpy mean.

It exits non-zero, without a result line, unless JAX's first device is a
TPU. Its last stdout line is one JSON object naming the device. Round
seconds and device memory printed on earlier lines are smoke timings, not
benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor
from importlib import metadata

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MB = 1024 * 1024
N_CLIENTS = 20
N_SHARDS = 8
SEED = 0


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def vgg16_elems() -> int:
    from repro.configs.paper_workloads import PAPER_WORKLOADS

    return int(PAPER_WORKLOADS["vgg16"].grad_mb * MB / 4)


def make_grads(n: int, elems: int, seed: int) -> list:
    """N seeded f32 normal gradients, one PCG64 stream per client."""

    def one(i: int) -> np.ndarray:
        rng = np.random.default_rng([seed, i])
        return rng.standard_normal(elems, dtype=np.float32)

    with ThreadPoolExecutor(max(1, min(n, os.cpu_count() or 1))) as ex:
        return list(ex.map(one, range(n)))


def host_available_bytes() -> int | None:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        return None
    return None


def ulp_report(got: np.ndarray, ref: np.ndarray) -> str:
    diff = got.view(np.int32).astype(np.int64) - ref.view(np.int32)
    bad = np.count_nonzero(diff)
    return f"{bad} of {got.size} elements differ, max {np.abs(diff).max()} ulp"


# ---------------------------------------------------------------------------
# one chip: FederatedSession rounds
# ---------------------------------------------------------------------------


def session_round(grads, timer, **overrides):
    from repro.api import FederatedSession, SessionConfig

    cfg = SessionConfig(
        topology="gradssharding",
        n_shards=N_SHARDS,
        schedule="barrier",
        keep_records=False,
        **overrides,
    )
    session = FederatedSession(cfg)
    t0 = timer()
    result = session.round(grads)
    return result, timer() - t0


def check_round(result, elems: int, label: str) -> None:
    avg = result.avg_flat
    require(avg.shape == (elems,), f"{label}: avg_flat shape {avg.shape}")
    require(avg.dtype == np.float32, f"{label}: avg_flat dtype {avg.dtype}")
    require(bool(np.isfinite(avg).all()), f"{label}: non-finite avg_flat")


def check_kernel_folded(result, label: str) -> None:
    require(result.engine == "batched", f"{label}: engine {result.engine}")
    require(
        result.kernel_folds == N_SHARDS,
        f"{label}: {result.kernel_folds} of {N_SHARDS} shards folded by the "
        f"kernel",
    )


def check_bit_identical(got, ref, label: str) -> None:
    same = np.array_equal(got.avg_flat, ref.avg_flat)
    print(
        f"{label}: batched vs streaming avg_flat "
        + ("bit-identical" if same else ulp_report(got.avg_flat, ref.avg_flat))
    )
    require(same, f"{label}: batched avg_flat differs from streaming")


def run_identity(grads, elems: int, dev, timer) -> None:
    avgs = []
    for r, note in enumerate(("first, compiles", "second")):
        res, secs = session_round(grads, timer)
        label = f"identity round {r}"
        check_round(res, elems, label)
        check_kernel_folded(res, label)
        print(
            f"smoke timing: {label} ({note}): {secs:.3f} s wall, "
            f"{res.kernel_folds}/{N_SHARDS} shards folded by the kernel"
        )
        avgs.append(res.avg_flat)
    require(np.array_equal(avgs[0], avgs[1]), "identity rounds disagree")
    mem = dev.memory_stats() or {}
    print(
        f"smoke device memory: peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
        f"bytes_limit={mem.get('bytes_limit')}"
    )
    ref, secs = session_round(grads, timer, engine="streaming")
    print(f"smoke timing: identity streaming reference round: {secs:.3f} s wall")
    check_bit_identical(res, ref, "identity")
    require(
        np.array_equal(ref.avg_flat, grads_mean(grads)),
        "streaming avg_flat differs from the host f32 left-fold mean",
    )


def grads_mean(grads) -> np.ndarray:
    acc = grads[0].copy()
    for g in grads[1:]:
        acc += g
    return acc / np.float32(len(grads))


def device_divide_report(grads) -> None:
    """How an on-device f32 divide by N compares with the host's —
    the divide the kernel's old finalize pass made on the chip."""
    import jax.numpy as jnp

    x = grads[0][: len(grads[0]) // N_SHARDS]
    n = np.float32(len(grads))
    dev = np.asarray(jnp.asarray(x) / n)
    print(f"device vs host f32 divide by N={len(grads)}: {ulp_report(dev, x / n)}")


def run_codec(grads, elems: int, codec: str, timer) -> None:
    res, secs = session_round(grads, timer, codec=codec)
    label = f"{codec} round"
    check_round(res, elems, label)
    check_kernel_folded(res, label)
    require(bool(np.isfinite(res.codec_error)), f"{label}: codec_error not finite")
    print(
        f"smoke timing: {label}: {secs:.3f} s wall, codec_error="
        f"{res.codec_error!r}, {res.kernel_folds}/{N_SHARDS} shards folded"
    )
    ref, secs = session_round(
        grads, timer, codec=codec, engine="streaming", track_codec_error=False
    )
    print(f"smoke timing: {label} streaming reference: {secs:.3f} s wall")
    check_bit_identical(res, ref, codec)


def mirror_report(grad: np.ndarray) -> None:
    """Chip kernels vs their numpy mirrors on one client's M shards."""
    from repro.core import wire_codec as wc
    from repro.core.sharding import plan_uniform, shard

    k = wc.get_codec("topk").k_per_block
    codes = scales = mask = vals = elems = tiles = 0
    for flat in shard(grad, plan_uniform(grad.size, N_SHARDS)):
        ck, sk = wc.qsgd8_kernel(flat)
        cn, sn = wc.qsgd8_numpy(flat)
        codes += int(np.count_nonzero(ck != cn))
        scales += int(np.count_nonzero(sk.view(np.int32) != sn.view(np.int32)))
        dk, dn = wc.topk_kernel(flat, k), wc.topk_numpy(flat, k)
        mask += int(np.count_nonzero((dk != 0) != (dn != 0)))
        vals += int(np.count_nonzero(dk.view(np.int32) != dn.view(np.int32)))
        elems += flat.size
        tiles += sk.size
    print(
        f"kernel vs numpy mirror, client 0 ({elems} elements, {tiles} tiles): "
        f"qsgd8 codes differ {codes}, scales differ {scales}; "
        f"topk mask entries differ {mask}, dense values differ {vals}"
    )


def run_one_chip(devs, n: int, elems: int, timer) -> None:
    grad_bytes = 4 * elems
    avail = host_available_bytes()
    if avail is not None and avail < 2 * n * grad_bytes:
        cut = max(2, avail // (2 * grad_bytes))
        print(
            f"cut N {n} -> {cut}: host has {avail} B available, "
            f"N={n} held twice needs {2 * n * grad_bytes} B"
        )
        n = cut
    t0 = timer()
    grads = make_grads(n, elems, SEED)
    print(
        f"clients N={n}, M={N_SHARDS}, {elems} f32 elements "
        f"({grad_bytes} B) per client, generated in {timer() - t0:.3f} s"
    )
    device_divide_report(grads)
    run_identity(grads, elems, devs[0], timer)
    for codec in ("qsgd8", "topk"):
        run_codec(grads, elems, codec, timer)
    mirror_report(grads[0])


# ---------------------------------------------------------------------------
# four chips: GradsSharding and lambda-FL as mesh collectives
# ---------------------------------------------------------------------------


def run_mesh(devs, elems: int, timer) -> None:
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import device_agg
    from repro.launch.mesh import make_mesh

    m = len(devs)
    require(m == 4, f"--chips 4 needs four devices, found {m}")
    mesh = make_mesh((2, 2), ("pod", "data"))
    padded = elems + (-elems) % m
    stack = np.zeros((m, padded), np.float32)
    for i, g in enumerate(make_grads(m, elems, SEED + 1)):
        stack[i, :elems] = g
    ref = grads_mean(list(stack[:, :elems]))
    # any order of an m-term f32 sum is within (m-1)·u·Σ|x| of the exact
    # sum (u = 2^-24), as is the host's left fold; /m is exact
    bound = 2 * (m - 1) * 2.0**-24 * np.abs(stack[:, :elems]).sum(axis=0) / m

    rows = jax.device_put(stack, NamedSharding(mesh, P(("pod", "data"))))
    t0 = timer()
    shards = device_agg.reduce_scatter_mean_flat(mesh, rows)
    shards.block_until_ready()
    rs_s = timer() - t0
    owners = {s.device for s in shards.addressable_shards}
    require(len(owners) == m, f"mean shards on {len(owners)} devices, not {m}")
    print(
        f"reduce-scatter: {m} shards of {padded // m} elements on devices "
        f"{sorted(d.id for d in owners)}"
    )
    t0 = timer()
    full = device_agg.all_gather_shards(mesh, shards)
    full.block_until_ready()
    ag_s = timer() - t0
    t0 = timer()
    allred = device_agg.all_reduce_mean(mesh, rows)
    allred.block_until_ready()
    ar_s = timer() - t0
    print(
        f"smoke timing (first calls, compile included): reduce-scatter "
        f"{rs_s:.3f} s, all-gather {ag_s:.3f} s, all-reduce {ar_s:.3f} s"
    )
    for label, arr in (("gradssharding", full), ("lambda_fl", allred)):
        got = np.asarray(arr)[:elems]
        err = np.abs(got - ref)
        print(
            f"{label} vs host numpy mean: {np.count_nonzero(err)} of {elems} "
            f"elements differ, max |diff| {err.max()!r}, worst share of the "
            f"f32 bound {(err / np.maximum(bound, 1e-45)).max()!r}"
        )
        require(bool((err <= bound).all()), f"{label}: mean outside f32 bound")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="4 runs only the mesh collectives across four chips",
    )
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform}", file=sys.stderr)
        return 2
    from repro.kernels import ops
    from repro.launch.hostenv import enable_compile_cache, host_timer

    cache = enable_compile_cache()
    print(
        f"jax {jax.__version__}, jaxlib {metadata.version('jaxlib')}, "
        f"libtpu {metadata.version('libtpu')}"
    )
    print(f"device_kind {dev.device_kind!r}, device count {len(devs)}")
    print(f"compile cache: {cache}")
    try:
        if args.chips == 4:
            run_mesh(devs, vgg16_elems(), host_timer)
        else:
            require(
                ops.kernel_mode() == "compiled",
                f"kernel mode {ops.kernel_mode()!r} on a TPU, not compiled",
            )
            run_one_chip(devs, N_CLIENTS, vgg16_elems(), host_timer)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
