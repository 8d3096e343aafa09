"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists of ``[name, start_ns, duration_ns]``: the device operations of each
chip the cell used (the ``XLA Ops`` line of plane ``/device:TPU:<id>``) and
the harness's own host spans (``jax.profiler.TraceAnnotation``), among them
``window``, which bounds the measured window. ``reduce`` works on that form
alone, so a test can hand it a synthetic trace or a recorded one.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
COLLECTIVE = "collective-permute"
SPAN_NAMES = ("send", "submit", "step", "fetch")  # the harness's request spans
TOP = 10
Interval = Tuple[float, float]


def load(tdir: str, device_ids: Sequence[int]) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {tdir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    host_names = {"window", *SPAN_NAMES}
    want = {f"/device:TPU:{i}": str(i) for i in device_ids}
    devices: Dict[str, List[list]] = {}
    host: List[list] = []
    for plane in data.planes:
        if plane.name in want:
            ops = [[e.name, e.start_ns, e.duration_ns]
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[want[plane.name]] = ops
        elif plane.name.startswith("/host:"):
            host.extend([e.name, e.start_ns, e.duration_ns]
                        for line in plane.lines for e in line.events
                        if e.name in host_names)
    missing = sorted(set(want.values()) - set(devices))
    if missing:
        raise RuntimeError(f"trace has no plane for devices {missing}; planes: "
                           f"{[p.name for p in data.planes]}")
    return {"devices": devices, "host": host}


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def label(name: str) -> str:
    """A short name for a device op. On the TPU an op's trace name is its
    HLO text, ``%fusion.23 = f32[112,64]{...} fusion(...)``: keep the
    instruction's name and, where it has one, its array shape."""
    m = re.match(r"%(\S+) = ([a-z0-9]+\[[0-9,]*\])?", name)
    if not m:
        return name[:80]
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def self_times(ops: Sequence[list], lo: float, hi: float) -> Dict[str, float]:
    """Nanoseconds inside ``[lo, hi]`` that each op label spent outside the
    ops nested in it (a ``while`` or ``conditional`` holds its body's ops),
    summed by label."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [end, label, self ns]

    def close(top):
        out[top[1]] = out.get(top[1], 0.0) + top[2]

    for n, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        # an op inside the one on top of the stack is nested in it; one
        # that starts after it ends, or outlasts it, is not
        while stack and (stack[-1][0] <= s or s + d > stack[-1][0]):
            close(stack.pop())
        own = _length(_clip([(s, s + d)], lo, hi))
        if stack:
            stack[-1][2] -= own
        stack.append([s + d, label(n), own])
    while stack:
        close(stack.pop())
    return out


def reduce(trace: dict) -> dict:
    """Busy, idle and collective time over the ``window`` span.

    Busy time is the union of a device's op intervals inside the window;
    collective time the union of its ``collective-permute`` ops. The
    busiest device is the one with the most busy time. Its top ops are
    ranked by self time. Idle gaps are those of the busiest device, each
    named for the host span that overlaps it most (``none`` where no span
    does).
    """
    windows = [(s, s + d) for n, s, d in trace["host"] if n == "window"]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} window spans, not one")
    w0, w1 = windows[0]
    window_ns = w1 - w0
    per_dev = {}
    for dev, ops in trace["devices"].items():
        busy = merge(_clip(((s, s + d) for _, s, d in ops), w0, w1))
        coll = merge(_clip(((s, s + d) for n, s, d in ops if COLLECTIVE in n), w0, w1))
        per_dev[dev] = {"busy": busy, "busy_ns": _length(busy),
                        "collective_ns": _length(coll), "ops": ops}
    if not per_dev:
        raise ValueError("trace holds no device")
    top = max(per_dev, key=lambda k: per_dev[k]["busy_ns"])
    hot = per_dev[top]

    # the request spans follow one another, so a gap meets at most the
    # span before it, the spans inside it and the span after it
    spans = sorted((s, s + d, n) for n, s, d in trace["host"] if n in SPAN_NAMES)
    starts = [s for s, _, _ in spans]
    gaps = []
    prev = w0
    for s, e in hot["busy"] + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named: List[Tuple[str, float]] = []
    totals: Dict[str, float] = {}
    for g0, g1 in gaps:
        best, over = "none", 0.0
        i = bisect.bisect_left(starts, g1) - 1
        while i >= 0 and (spans[i][1] > g0 or spans[i][0] >= g0):
            s, e, n = spans[i]
            o = min(e, g1) - max(s, g0)
            if o > over:
                best, over = n, o
            i -= 1
        named.append((best, (g1 - g0) / 1e9))
        totals[best] = totals.get(best, 0.0) + (g1 - g0) / 1e9
    named.sort(key=lambda x: -x[1])
    idle = sorted(([f"total:{k}", v] for k, v in totals.items()), key=lambda x: -x[1])
    idle = (idle + [[f"longest:{n}", t] for n, t in named])[:TOP]
    own = self_times(hot["ops"], w0, w1)
    ops = sorted(((k, v) for k, v in own.items() if v > 0), key=lambda kv: -kv[1])[:TOP]
    n_dev = len(per_dev)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(p["busy_ns"] for p in per_dev.values()) / n_dev / 1e9,
        "busiest_device": top,
        "busiest_busy_s": hot["busy_ns"] / 1e9,
        "busiest_collective_s": hot["collective_ns"] / 1e9,
        "idle_share": sum(1 - p["busy_ns"] / window_ns for p in per_dev.values()) / n_dev,
        "top_ops": [[k, v / 1e9] for k, v in ops],
        "idle_gaps": idle,
    }
