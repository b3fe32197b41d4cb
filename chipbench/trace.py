"""Reduction of a JAX profiler trace to device busy time, kernel times and idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device events
are read from each TPU plane's op line (:data:`OP_LINE`, or every line of a
plane that has none); host events are the harness's own spans, the
``TraceAnnotation`` events whose names start with :data:`SPAN_PREFIX`. Both
carry the profiler's one clock, so an idle gap on the device can be put
beside what the host was doing at the time.

The window is the first round span's start to the last one's end. Busy time
is the union of a device's op intervals inside the window, averaged over
the devices that ran anything; an idle gap is a stretch of the window in
which no op ran on the first such device.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
ROUND = "round"
#: the harness spans an idle gap is named by, innermost first; a gap
#: inside a round but outside both is the round driver's
GAP_OWNERS = ("fedavg_multi", "end_round")
DRIVER = "driver"
TOP = 10


@dataclass
class Event:
    name: str
    start: float  # ns on the profiler's clock
    end: float


@dataclass
class Reduced:
    """What the metric readers take from a trace."""

    window_s: float
    busy_s: float
    rounds: int
    #: TPU planes on which some op ran
    devices: int
    #: device seconds inside the window by op name, averaged over devices
    op_s: dict = field(default_factory=dict)
    #: idle gaps on the first device, longest first: (owner span, seconds)
    gaps: list = field(default_factory=list)

    def kernel_s(self, *needles: str) -> float | None:
        """Device seconds of the ops whose names contain any of ``needles``;
        None where no such op ran."""
        hits = [s for name, s in self.op_s.items() if any(n in name for n in needles)]
        return sum(hits) if hits else None

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}

    def gap_totals(self) -> dict:
        totals: dict = {}
        for owner, s in self.gaps:
            totals[owner] = totals.get(owner, 0.0) + s
        return totals


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(text: str) -> str:
    """An op's HLO instruction name: a TPU trace names each op by its whole
    HLO line, ``%_fold_sum.1 = f32[...] custom-call(...), ...``."""
    return text.split(" = ", 1)[0]


def load(path: str) -> tuple[dict, list]:
    """(device events by plane name, harness span events) of one trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OP_LINE] or lines
            devices[plane.name] = [Event(op_name(e.name), e.start_ns, e.end_ns)
                                   for ln in ops for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(Event(e.name[len(SPAN_PREFIX):], e.start_ns, e.end_ns)
                             for e in ln.events if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _owner(t: float, spans: list[Event]) -> str:
    for name in GAP_OWNERS:
        if any(s.start <= t <= s.end for s in spans if s.name == name):
            return name
    return DRIVER


def reduce(devices: dict, spans: list[Event]) -> Reduced:
    rounds = [s for s in spans if s.name == ROUND]
    if not rounds:
        raise ValueError("the trace holds no round span")
    w0, w1 = min(s.start for s in rounds), max(s.end for s in rounds)
    busy, op_s, first_union = [], {}, None
    active = [name for name in sorted(devices) if devices[name]]
    for name in active:
        clipped = [(max(e.start, w0), min(e.end, w1), e.name) for e in devices[name]]
        clipped = [c for c in clipped if c[1] > c[0]]
        for a, b, op in clipped:
            op_s[op] = op_s.get(op, 0.0) + (b - a) / 1e9 / len(active)
        union = _union([(a, b) for a, b, _ in clipped])
        busy.append(sum(b - a for a, b in union) / 1e9)
        if first_union is None:
            first_union = union
    gaps = []
    if first_union is not None:
        edges = [w0] + [t for iv in first_union for t in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_owner(0.5 * (a + b), spans), (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(w1 - w0) / 1e9,
                   busy_s=sum(busy) / len(busy) if busy else 0.0,
                   rounds=len(rounds), devices=len(active), op_s=op_s, gaps=gaps)
