"""The program's own spans in a profiler trace, beside the harness's reduction.

The program marks its work with ``repro.<name>`` spans (``repro.tracing``)
that carry integer stats, such as the bytes of a host-to-device copy.
:func:`load` reads them from a ``.xplane.pb``; :func:`reduce` adds them to
what :func:`chipbench.trace.reduce` makes of the same trace:

- per round (grouped by the harness's ``round`` spans), the seconds spent
  in the program spans of one name, and the sum of one of their stats;
- the idle gaps cut at program-span boundaries, each piece owned by the
  innermost program span that covers it (adjacent pieces of one gap with
  the same owner are joined). A piece that no program span covers keeps
  :mod:`chipbench.trace`'s rule.

With no program span in the trace the result is exactly
:func:`chipbench.trace.reduce`'s. Run on a trace kept with
``chipbench/run.py --trace 1 --trace-dir <dir>``::

    python3 chipbench/program_trace.py <dir>

prints the per-round split of the round as one JSON object.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
from dataclasses import dataclass, field

if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from chipbench import trace as tr  # noqa: E402

PROGRAM_PREFIX = "repro."
#: the program spans that split a fold window, in the order they run
FOLD_PARTS = ("fold.fill", "fold.h2d", "fold.kernel", "fold.d2h", "fold.divide")


@dataclass
class Event(tr.Event):
    stats: dict = field(default_factory=dict)


@dataclass
class Reduced(tr.Reduced):
    """:class:`chipbench.trace.Reduced` with the program's spans."""

    #: the harness's round spans, in order
    round_spans: list = field(default_factory=list)
    #: the program's spans, full names (``repro.fold.h2d``)
    program: list = field(default_factory=list)

    def _per_round(self, name: str, value) -> list | None:
        full = PROGRAM_PREFIX + name
        hits = [e for e in self.program if e.name == full]
        if not hits:
            return None
        return [sum(value(e) for e in hits if r.start <= e.start <= r.end)
                for r in self.round_spans]

    def span_s(self, name: str) -> list | None:
        """Per round, the seconds in program spans ``name`` (``fold.h2d``);
        None where no such span ran."""
        return self._per_round(name, lambda e: (e.end - e.start) / 1e9)

    def stat_sum(self, name: str, stat: str) -> list | None:
        """Per round, the sum of stat ``stat`` over program spans ``name``."""
        return self._per_round(name, lambda e: e.stats.get(stat, 0))


def load(path: str) -> list[Event]:
    """The program's spans of one trace, from every host plane."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out.extend(Event(e.name, e.start_ns, e.end_ns, dict(e.stats))
                           for e in ln.events if e.name.startswith(PROGRAM_PREFIX))
    return out


def _first_union(devices: dict, w0: float, w1: float) -> list:
    for name in sorted(devices):
        if devices[name]:
            clipped = [(max(e.start, w0), min(e.end, w1)) for e in devices[name]]
            return tr._union([c for c in clipped if c[1] > c[0]])
    return []


def _cut(a: float, b: float, program: list, spans: list) -> list:
    """Gap ``[a, b)`` cut at program-span boundaries, as (owner, ns) pieces."""
    edges = sorted({a, b} | {t for e in program for t in (e.start, e.end) if a < t < b})
    pieces: list = []
    for x, y in zip(edges, edges[1:]):
        mid = 0.5 * (x + y)
        covering = [e for e in program if e.start <= mid <= e.end]
        owner = (min(covering, key=lambda e: e.end - e.start).name if covering
                 else tr._owner(mid, spans))
        if pieces and pieces[-1][0] == owner:
            pieces[-1][1] += y - x
        else:
            pieces.append([owner, y - x])
    return pieces


def reduce(devices: dict, spans: list, program: list) -> Reduced:
    base = tr.reduce(devices, spans)
    rounds = sorted((s for s in spans if s.name == tr.ROUND), key=lambda s: s.start)
    red = Reduced(**{f.name: getattr(base, f.name) for f in dataclasses.fields(base)},
                  round_spans=rounds, program=program)
    if not program:
        return red
    w0, w1 = rounds[0].start, max(s.end for s in rounds)
    union = _first_union(devices, w0, w1)
    if not union:
        return red
    edges = [w0] + [t for iv in union for t in iv] + [w1]
    gaps = [(owner, ns / 1e9)
            for a, b in zip(edges[::2], edges[1::2]) if b > a
            for owner, ns in _cut(a, b, program, spans)]
    red.gaps = sorted(gaps, key=lambda g: -g[1])
    return red


def split(red: Reduced, spans: list) -> dict:
    """Per round, the mean seconds of each program span name, the harness's
    ``fedavg_multi`` spans (of ``spans``) beside the sum of the fold
    window's parts, the host-to-device rate, and the share of idle time
    that program spans own."""
    n = len(red.round_spans)
    names = sorted({e.name[len(PROGRAM_PREFIX):] for e in red.program})
    per_round = {name: sum(red.span_s(name)) / n for name in names}
    folds = sum((s.end - s.start) / 1e9 for s in spans if s.name == "fedavg_multi")
    h2d_bytes, h2d_s = red.stat_sum("fold.h2d", "bytes"), red.span_s("fold.h2d")
    idle = sum(s for _, s in red.gaps)
    owned = sum(s for o, s in red.gaps if o.startswith(PROGRAM_PREFIX))
    return {
        "rounds": n,
        "window_s": red.window_s,
        "busy_s": red.busy_s,
        "span_s_per_round": per_round,
        "fold_parts_s_per_round": sum(per_round.get(p, 0.0) for p in FOLD_PARTS),
        "harness_fedavg_multi_s_per_round": folds / n,
        "h2d_gbps": sum(h2d_bytes) / sum(h2d_s) / 1e9 if h2d_s and sum(h2d_s) else None,
        "idle_s": idle,
        "idle_owned_by_program": owned / idle if idle else None,
        "idle_by_owner": red.gap_totals(),
        "breakdown": red.breakdown(),
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    path = tr.find_xplane(args[0])
    devices, spans = tr.load(path)
    print(json.dumps(split(reduce(devices, spans, load(path)), spans)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
