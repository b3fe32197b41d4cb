"""One run of one benchmark cell on the chip.

A cell is ``<config>.<traffic>``: the deployment in
``chipbench/configs/<config>.json`` under the round mix in
``chipbench/traffic/<traffic>.json``, both named by the cell's entry in
``BENCHMARK.json``. A run:

1. Set-up (``setup_s``, from process start): checks the device against
   ``chipbench/peaks.json``, points JAX's compile cache at a fixed
   directory, generates two contribution sets A and B from the seed, builds
   one ``FederatedSession`` from the two files and runs one warm-up round
   on set A.
2. Window: whole rounds back to back on B, A, B, ... until ``seconds``
   have passed, then the round in progress finishes. ``round_s`` is the
   window's length over its rounds.
3. Check: every round's ``avg_flat`` against the plain reference
   (``chipbench/reference.py``), then the result line.

With ``trace`` on, the window runs under the profiler and the harness's
spans wrap the engine's ``end_round`` and the kernel entry
``fedavg_multi``; the per-layer metrics are the readers
``chipbench/metrics/<name>.py`` that ``BENCHMARK.json`` lists for the
cell. The program runs at its own defaults: ``REPRO_*`` variables are
removed before it is imported, and the session is built from the files
alone.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from chipbench import reference
from chipbench import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
#: where the harness wraps the program in a traced run: (module, attribute
#: path, span name); a target the program no longer has is skipped, and
#: the metrics that read its span then report nothing
SPAN_TARGETS = (
    ("repro.core.agg_engine", "BatchedBackend.end_round", "end_round"),
    ("repro.kernels.ops", "fedavg_multi", "fedavg_multi"),
)
#: JAX's monitoring events that mean a program was traced or compiled
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
#: the limit of the one number compared: an exact comparison
ULP_LIMIT = 0


class BenchError(Exception):
    """The run cannot measure: no accelerator, an unknown chip or cell."""


def clock() -> float:
    return time.perf_counter()


def load_json(*parts: str):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    per_layer: list


def load_cell(name: str) -> Cell:
    """A cell of ``BENCHMARK.json`` with its configuration, traffic and
    per-layer metrics."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r} (have {sorted(cells)})")
    w = cells[name]
    return Cell(name, int(w["chips"]),
                load_json("configs", w["config"] + ".json"),
                load_json("traffic", w["traffic"] + ".json"),
                [m for m in bench["per_layer"] if name in m.get("workloads", [name])])


def strip_program_env() -> None:
    """Leave the program at its defaults: drop every ``REPRO_*`` knob."""
    for key in [k for k in os.environ if k.startswith("REPRO_")]:
        del os.environ[key]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path: the one that
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``.
    Every program is kept, however quick its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_device(chips: int):
    """(the devices the cell uses, their peaks) — or BenchError where JAX
    finds no TPU, fewer than ``chips`` of them, or a chip that
    ``peaks.json`` does not list."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    peaks = load_json("peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device_kind {kind!r} is not in chipbench/peaks.json")
    return devs[:chips], peaks[kind]


def make_set(n: int, elems: int, seed: int, stream: int) -> list:
    """N seeded f32 normal contributions, one PCG64 stream per client
    (``chip_smoke.make_grads``, with a stream per contribution set)."""
    key = int(seed) & (2**64 - 1)

    def one(i: int) -> np.ndarray:
        rng = np.random.default_rng([key, stream, i])
        return rng.standard_normal(elems, dtype=np.float32)

    with ThreadPoolExecutor(max(1, min(n, os.cpu_count() or 1))) as ex:
        return list(ex.map(one, range(n)))


def build_session(cell: Cell):
    """One ``FederatedSession`` from the configuration's and the traffic's
    ``session`` entries and nothing else."""
    from repro.api import FederatedSession, SessionConfig

    return FederatedSession(SessionConfig(**cell.config["session"],
                                          **cell.traffic["session"]))


class Spans:
    """Host-clock spans around calls into the program; in a traced run each
    is also a ``TraceAnnotation`` on the profiler's clock."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + name)
        else:
            ctx = contextlib.nullcontext()
        with ctx:
            t0 = clock()
            try:
                yield
            finally:
                self.records.append((name, t0, clock()))

    @contextlib.contextmanager
    def wrapping(self, targets=SPAN_TARGETS):
        """Wrap each target that exists in a span of its name, for the
        duration of the block."""
        patched = []
        try:
            for module, path, name in targets:
                *owner_path, attr = path.split(".")
                owner = importlib.import_module(module)
                for part in owner_path:
                    owner = getattr(owner, part, None)
                orig = getattr(owner, attr, None) if owner is not None else None
                if orig is None:
                    continue

                def wrapper(*a, _orig=orig, _name=name, **k):
                    with self.span(_name):
                        return _orig(*a, **k)

                setattr(owner, attr, wrapper)
                patched.append((owner, attr, orig))
            yield
        finally:
            for owner, attr, orig in reversed(patched):
                setattr(owner, attr, orig)

    def per_round(self) -> list[dict]:
        """Each round's wall and the summed spans of each name inside it;
        a name that never ran reads None."""
        names = {n for n, _, _ in self.records if n != tr.ROUND}
        rounds = []
        for _, a, b in (r for r in self.records if r[0] == tr.ROUND):
            row = {"wall_s": b - a}
            for name in names:
                inside = [t1 - t0 for n, t0, t1 in self.records
                          if n == name and a <= t0 and t1 <= b]
                row[name] = sum(inside) if inside else None
            rounds.append(row)
        return rounds


@dataclass
class Run:
    """What the per-layer metric readers see of one traced run."""

    config: dict
    peak: dict
    rounds: list
    trace: tr.Reduced | None
    memory_peak_bytes: int | None

    def span_s(self, name: str) -> list | None:
        """Per round, the seconds the host spent in span ``name``; None
        where the span never ran."""
        vals = [r.get(name) for r in self.rounds]
        if not vals or any(v is None for v in vals):
            return None
        return vals


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_window(session, sets: list, seconds: float, spans: Spans) -> list:
    """Whole rounds on B, A, B, ... until ``seconds`` have passed; returns
    (set index, result) per round."""
    out = []
    t0 = clock()
    while True:
        s = (len(out) + 1) % 2
        with spans.span(tr.ROUND):
            res = session.round(sets[s])
        out.append((s, res))
        if clock() - t0 >= seconds:
            return out


def check(cell: Cell, sets: list, answers: list) -> list[dict]:
    """The gap of each round's answer from the reference of its set."""
    m = cell.config["session"]["n_shards"]
    codec = cell.traffic["session"]["codec"]
    gaps: list = [None] * len(answers)
    for s in (0, 1):
        idx = [i for i, (si, _) in enumerate(answers) if si == s]
        if not idx:
            continue
        ref = reference.round_reference(sets[s], m, codec, cell.traffic["codec_params"])
        for i in idx:
            gaps[i] = reference.ulp_gap(answers[i][1], ref)
        del ref
    return gaps


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, devices=None, peak=None, session_factory=build_session,
             trace_dir: str | None = None, log=print) -> dict:
    """One run of ``cell``; returns the result line's object. ``devices``
    and ``peak`` come from :func:`check_device`."""
    import jax

    cfg = cell.config
    n, elems = int(cfg["n_clients"]), int(cfg["grad_elems"])
    t = clock()
    sets = [make_set(n, elems, seed, 0), make_set(n, elems, seed, 1)]
    log(f"contributions: 2 sets of N={n} x {elems} f32 in {clock() - t:.3f} s")
    session = session_factory(cell)
    t = clock()
    warm = session.round(sets[0])
    log(f"warm-up round: {clock() - t:.3f} s, kernel_folds={getattr(warm, 'kernel_folds', None)}")
    del warm
    compiles: list = []

    def on_event(event, _secs, **_kw):
        if event in COMPILE_EVENTS:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    spans = Spans(annotate=trace)
    log_dir = None
    if trace:
        log_dir = trace_dir or tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
    setup_s = clock() - t_process
    try:
        if trace:
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        with spans.wrapping() if trace else contextlib.nullcontext():
            answers = run_window(session, sets, seconds, spans)
    finally:
        if trace:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(on_event)
    rounds = spans.per_round()
    marks = [(a, b) for nm, a, b in spans.records if nm == tr.ROUND]
    window_s = marks[-1][1] - marks[0][0]
    mem = (devices[0].memory_stats() or {}) if devices else {}
    memory_peak = mem.get("peak_bytes_in_use")
    folds = [getattr(res, "kernel_folds", None) for _, res in answers]
    log(f"window: {len(answers)} rounds in {window_s!r} s, kernel_folds per round {sorted(set(folds))}, "
        f"compiles in window {len(compiles)}")
    log(f"round walls (s): {[round(r['wall_s'], 4) for r in rounds]}")
    answers = [(s, res.avg_flat) for s, res in answers]
    del session
    gc.collect()

    reduced = None
    if trace:
        reduced = tr.reduce(*tr.load(tr.find_xplane(log_dir)))
        if trace_dir is None:
            shutil.rmtree(log_dir, ignore_errors=True)
        log(f"trace: window {reduced.window_s!r} s, busy {reduced.busy_s!r} s, "
            f"idle by host span {reduced.gap_totals()}")

    t = clock()
    gaps = check(cell, sets, answers)
    log(f"reference check: {clock() - t:.3f} s")
    worst = max(g["max_ulp"] for g in gaps)
    off = [(i, g) for i, g in enumerate(gaps) if g["max_ulp"] > ULP_LIMIT]
    log(f"reference check of {len(gaps)} rounds: worst max ulp gap {worst}, "
        f"{len(off)} rounds off the reference")
    for i, g in off[:10]:
        log(f"round {i} (set {'AB'[answers[i][0]]}): max ulp gap {g['max_ulp']}, "
            f"{g['differing']} of {elems} elements differ, max |diff| {g['max_abs']!r}")
    failed = len(off)

    if trace:
        run = Run(cfg, peak or {}, rounds, reduced, memory_peak)
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {"round_s": {"value": window_s / len(answers), "unit": "s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    dev = devices[0] if devices else jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices) if devices else 1, "memory_peak_bytes": memory_peak}
    result = {"correct": failed == 0, "attempted": len(answers), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {"max_ulp_gap": {"value": worst, "limit": ULP_LIMIT}}
    return result


def main(argv=None, t_process: float | None = None) -> int:
    import argparse

    t_process = clock() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace here instead of a temporary directory")
    args = ap.parse_args(argv)
    strip_program_env()
    try:
        cell = load_cell(args.workload)
        import repro.api  # noqa: F401  the program under test must be importable
        enable_compile_cache()
        devices, peak = check_device(cell.chips)
    except (BenchError, ImportError, OSError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_process=t_process,
                      devices=devices, peak=peak, trace_dir=args.trace_dir)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
