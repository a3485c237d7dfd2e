"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer readers use: device busy and idle time, device time per
program, per operation, of the ``dp_clip`` kernel and of collectives,
and the longest idle gaps, each named by the benchmark's host span that
was open in it.

Device planes are ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds one
event per operation that ran, named by its HLO instruction
(``%name = type kind(operands), ...``); the reduction keys it by
``name kind type`` with the layouts dropped.  A Pallas kernel is a
``custom-call`` named after the jitted function that launched it
(``dp_clip_mean_noise_cohort.N``).  Their ``XLA Modules`` line holds one
event per program launch, named after the jitted function
(``jit_cohort_step(<hash>)``).  Host spans are the benchmark's
``TraceAnnotation``s, named ``bench.*``, on the host plane.  The window
is the extent of those spans.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
# the fused DP kernel's passes: custom-calls launched by the jitted
# wrapper in kernels/dp_clip/ops.py
KERNEL = "dp_clip"
CUSTOM_CALL = "custom-call"
_HLO = re.compile(r"%?(\S+) = (.*?) ([a-z][\w-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "all_gather", "all_reduce",
               "reduce_scatter", "all_to_all", "collective_permute")
TOP = 10


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


@dataclass
class Reduced:
    window_s: float = 0.0
    busy_s: float = 0.0                  # averaged over devices
    devices: int = 0
    ops: dict = field(default_factory=dict)        # name -> s, all devices
    programs: dict = field(default_factory=dict)   # name -> s, all devices
    gaps: list = field(default_factory=list)       # [(span, s)] longest

    def program_seconds(self, needle: str) -> float:
        """Device seconds of programs whose name holds ``needle``,
        averaged over the devices."""
        tot = sum(s for n, s in self.programs.items() if needle in n)
        return tot / max(1, self.devices)

    def kernel_seconds(self) -> float:
        """Device seconds of the ``dp_clip`` kernel's passes, averaged
        over the devices."""
        tot = sum(s for n, s in self.ops.items()
                  if KERNEL in n and CUSTOM_CALL in n)
        return tot / max(1, self.devices)

    def collective_seconds(self) -> float:
        tot = sum(s for n, s in self.ops.items()
                  if any(c in n for c in COLLECTIVES))
        return tot / max(1, self.devices)

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s / max(1, self.devices)] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def op_name(text: str) -> str:
    """``name kind type`` of an HLO instruction's text, layouts dropped:
    ``multiply_reduce_fusion.38 fusion (f32[128], f32[128,5,40,64])``."""
    m = _HLO.match(text)
    if not m:
        return text[:160]
    typ = _LAYOUT.sub("", _LAYOUT.sub("", m.group(2)))
    return f"{m.group(1)} {m.group(3)} {typ}"[:160]


def _is_device(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit()


def reduce_file(path: str, chips: int | None = None) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans = []                                     # (start, end, name)
    dev_ops = {}                                   # plane -> [(s, e)]
    red = Reduced()
    for plane in pd.planes:
        if _is_device(plane.name):
            if chips is not None and int(plane.name.rsplit(":", 1)[1]) >= chips:
                continue
            ivs = dev_ops.setdefault(plane.name, [])
            lines = {ln.name: ln for ln in plane.lines}
            for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                ivs.append((ev.start_ns, ev.end_ns))
                name = op_name(ev.name)
                red.ops[name] = red.ops.get(name, 0.0) + ev.duration_ns * 1e-9
            for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines else ()):
                red.programs[ev.name] = (red.programs.get(ev.name, 0.0)
                                         + ev.duration_ns * 1e-9)
        else:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
    red.devices = len(dev_ops)
    if not spans or not dev_ops:
        return red
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    red.window_s = (hi - lo) * 1e-9
    busy = {}
    for name, ivs in dev_ops.items():
        busy[name] = _clip(_union(ivs), lo, hi)
    red.busy_s = sum(sum(e - s for s, e in u) for u in busy.values()) \
        * 1e-9 / len(busy)
    # idle gaps of the first device, named by the innermost open span
    first = busy[sorted(busy)[0]]
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    for s, e in gaps[:TOP]:
        mid = (s + e) / 2
        open_ = [sp for sp in spans if sp[0] <= mid <= sp[1]]
        name = min(open_, key=lambda sp: sp[1] - sp[0])[2] if open_ else "none"
        red.gaps.append((name, (e - s) * 1e-9))
    return red


def find_trace(directory) -> str:
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


def reduce_dir(directory, chips: int | None = None) -> Reduced:
    return reduce_file(find_trace(directory), chips)
