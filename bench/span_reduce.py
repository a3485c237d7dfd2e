#!/usr/bin/env python3
"""Put a trace's device idle time down to the host spans open in it.

    python3 bench/span_reduce.py <trace directory or .xplane.pb> [--chips N]

Host spans come in two families on the profiler's own clock: the
benchmark's ``bench.*`` and the program's ``fl.*`` (``repro.core.spans``
names them all).  The window, the device busy time and the idle
intervals of the first device are those of ``trace_reduce``: the window
is the extent of the ``bench.*`` spans (of the ``fl.*`` spans in a trace
that has none).  Then:

- ``spans``: for each ``fl.*`` name, the count of its spans in the
  window and their seconds inside it;
- ``idle_by_span``: the first device's idle time, each instant put down
  to the innermost (shortest) span of either family open at that
  instant, or ``none``; the values sum to the window less the busy time;
- ``idle_within``: for each name of either family, the idle seconds
  inside any of its spans, at any depth;
- ``gaps``: the longest idle gaps, each named by the innermost span open
  at its middle.

``METRICS`` reads four per-layer numbers from that (``None`` where the
span they read is absent).  The benchmark's readers do not reach them
yet: ``run.py`` hands its readers ``trace_reduce``'s reduction only.
"""
from __future__ import annotations

import argparse
import heapq
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace_reduce  # noqa: E402

FL = "fl."
BENCH = trace_reduce.SPAN_PREFIX
NONE = "none"


@dataclass
class SpanReduced:
    window_s: float = 0.0
    busy_s: float = 0.0
    spans: dict = field(default_factory=dict)         # fl.* -> [count, s]
    idle_by_span: dict = field(default_factory=dict)  # innermost -> s
    idle_within: dict = field(default_factory=dict)   # name -> s
    gaps: list = field(default_factory=list)          # [(span, s)]

    def count(self, name: str) -> int:
        return self.spans.get(name, (0, 0.0))[0]

    def seconds(self, name: str) -> float:
        return self.spans.get(name, (0, 0.0))[1]

    def mean_ms(self, name: str):
        n = self.count(name)
        return self.seconds(name) / n * 1e3 if n else None


def loop_idle_ms_per_cohort(r: SpanReduced):
    """Device idle inside the engine loop's step spans (``fl.cohort``,
    ``fl.round``) other than their eval boundaries, per step."""
    steps = r.count("fl.cohort") + r.count("fl.round")
    if not steps:
        return None
    idle = (r.idle_within.get("fl.cohort", 0.0)
            + r.idle_within.get("fl.round", 0.0)
            - r.idle_within.get("fl.eval", 0.0))
    return idle / steps * 1e3


def stage_ms_per_cohort(r: SpanReduced):
    """Mean duration of ``fl.stage``."""
    return r.mean_ms("fl.stage")


def eval_ms_per_eval(r: SpanReduced):
    """Mean duration of ``fl.eval``."""
    return r.mean_ms("fl.eval")


def rebuild_ms_per_experiment(r: SpanReduced):
    """Testbed, runner and loop set-up per ``Session.run``: the rebuild
    a sigma sweep pays inside the window."""
    runs = r.count("fl.session.run")
    if not runs:
        return None
    s = sum(r.seconds(n) for n in ("fl.session.testbed", "fl.session.runner",
                                   "fl.startup"))
    return s / runs * 1e3


METRICS = {f.__name__: f for f in (
    loop_idle_ms_per_cohort, stage_ms_per_cohort, eval_ms_per_eval,
    rebuild_ms_per_experiment)}


def _innermost(spans, lo, hi) -> list:
    """``[(start, end, name)]`` pieces covering ``[lo, hi]``, each named
    by the shortest span open over all of it, or ``none``."""
    points = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                                if lo < t < hi})
    by_start = sorted(spans)
    heap, out, j = [], [], 0
    for a, b in zip(points, points[1:]):
        while j < len(by_start) and by_start[j][0] <= a:
            s, e, name = by_start[j]
            heapq.heappush(heap, (e - s, e, j, name))
            j += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        out.append((a, b, heap[0][3] if heap else NONE))
    return out


def _overlap(pieces, idle) -> list:
    """Idle seconds of each piece: both lists sorted and disjoint."""
    out, i = [], 0
    for a, b, name in pieces:
        while i < len(idle) and idle[i][1] <= a:
            i += 1
        k, tot = i, 0
        while k < len(idle) and idle[k][0] < b:
            tot += min(b, idle[k][1]) - max(a, idle[k][0])
            k += 1
        out.append((name, tot * 1e-9))
    return out


def reduce_file(path: str, chips: int | None = None) -> SpanReduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, dev_ops = [], {}
    for plane in pd.planes:
        if trace_reduce._is_device(plane.name):
            if chips is not None and int(plane.name.rsplit(":", 1)[1]) >= chips:
                continue
            ivs = dev_ops.setdefault(plane.name, [])
            for ln in plane.lines:
                if ln.name == trace_reduce.OPS_LINE:
                    ivs.extend((ev.start_ns, ev.end_ns) for ev in ln.events)
        else:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith((FL, BENCH)):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
    red = SpanReduced()
    framing = [sp for sp in spans if sp[2].startswith(BENCH)] or spans
    if not framing or not dev_ops:
        return red
    lo = min(s for s, _, _ in framing)
    hi = max(e for _, e, _ in framing)
    red.window_s = (hi - lo) * 1e-9
    busy = {n: trace_reduce._clip(trace_reduce._union(ivs), lo, hi)
            for n, ivs in dev_ops.items()}
    red.busy_s = sum(sum(e - s for s, e in u) for u in busy.values()) \
        * 1e-9 / len(busy)
    first = busy[sorted(busy)[0]]
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    inside = [(max(s, lo), min(e, hi), n) for s, e, n in spans
              if e > lo and s < hi]
    for s, e, n in inside:
        if n.startswith(FL):
            c = red.spans.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += (e - s) * 1e-9
    for name, s in _overlap(_innermost(inside, lo, hi), idle):
        red.idle_by_span[name] = red.idle_by_span.get(name, 0.0) + s
    for name in {n for _, _, n in inside}:
        union = trace_reduce._union((s, e) for s, e, n in inside if n == name)
        red.idle_within[name] = sum(
            s for _, s in _overlap([(a, b, name) for a, b in union], idle))
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:trace_reduce.TOP]:
        mid = (s + e) / 2
        open_ = [sp for sp in inside if sp[0] <= mid <= sp[1]]
        name = min(open_, key=lambda sp: sp[1] - sp[0])[2] if open_ else NONE
        red.gaps.append((name, (e - s) * 1e-9))
    return red


def summary(red: SpanReduced) -> dict:
    return {"window_s": red.window_s, "busy_s": red.busy_s,
            "idle_s": red.window_s - red.busy_s,
            "metrics": {k: f(red) for k, f in METRICS.items()},
            "idle_by_span": dict(sorted(red.idle_by_span.items(),
                                        key=lambda kv: -kv[1])),
            "idle_within": red.idle_within, "spans": red.spans,
            "gaps": red.gaps}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a .xplane.pb, or a directory holding one")
    ap.add_argument("--chips", type=int, default=None)
    args = ap.parse_args(argv)
    path = (args.trace if args.trace.endswith(".xplane.pb")
            else trace_reduce.find_trace(args.trace))
    print(json.dumps(summary(reduce_file(path, args.chips)), indent=1))


if __name__ == "__main__":
    main()
