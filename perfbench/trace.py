"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the device's busy intervals, its operations with their durations,
and the idle gaps labelled by what the host was doing.

Everything is clipped to the traced window, which the harness marks with
a ``jax.profiler.TraceAnnotation`` named ``WINDOW`` on the host; gaps are
labelled by the innermost span then open on that same host thread.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Set, Tuple

WINDOW = "perfbench.traced"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: An op event's name is its whole HLO instruction; a Pallas kernel is a
#: custom call to this target.
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                               # averaged over devices
    devices: int
    ops: Dict[str, float]                       # op name -> device seconds
    op_counts: Dict[str, int]
    gaps: List[Tuple[str, float]]               # (host label, seconds)
    pallas: Set[str]                            # op names of Pallas kernels


def newest_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...), ...`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def gaps_between(busy, lo, hi):
    """Idle intervals of [lo, hi] outside the disjoint sorted ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def labels(mids, host):
    """For each time in ``mids``, the innermost (shortest) host event open
    then: a sweep over events sorted by start, with a heap of the open
    ones keyed by duration, expired ones dropped as they reach the top."""
    import heapq
    events = sorted(host, key=lambda h: h[1])
    order = sorted(range(len(mids)), key=lambda i: mids[i])
    out = ["(no host span)"] * len(mids)
    heap, k = [], 0
    for i in order:
        mid = mids[i]
        while k < len(events) and events[k][1] <= mid:
            name, s, e = events[k]
            heapq.heappush(heap, (e - s, e, name))
            k += 1
        while heap and heap[0][1] <= mid:
            heapq.heappop(heap)
        if heap:
            out[i] = heap[0][2]
    return out


def reduce(planes) -> Reduced:
    """``planes``: the ``.planes`` of a ``jax.profiler.ProfileData``."""
    host, devices = None, []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = list(_events(line))
                # the harness's own thread: the one that marked the window
                if host is None and any(n == WINDOW for n, _, _ in evs):
                    host = evs
    if host is None:
        raise ValueError(f"no host span {WINDOW!r} in the trace")
    lo, hi = next((s, e) for name, s, e in host if name == WINDOW)
    host = [h for h in host if h[0] != WINDOW]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    busy_total = 0.0
    ops = collections.Counter()
    counts = collections.Counter()
    pallas = set()
    first_busy = None
    for plane in devices:
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for hlo, s, e in _events(line):
                if e <= lo or s >= hi:
                    continue
                s, e = max(s, lo), min(e, hi)
                ivs.append((s, e))
                name = op_name(hlo)
                ops[name] += (e - s) / 1e9
                counts[name] += 1
                if PALLAS_TARGET in hlo:
                    pallas.add(name)
        busy = merge(ivs)
        busy_total += sum(e - s for s, e in busy) / 1e9
        if first_busy is None:
            first_busy = busy
    by_label = collections.Counter()
    gaps = gaps_between(first_busy, lo, hi)
    for (s, e), name in zip(gaps, labels([(s + e) / 2 for s, e in gaps],
                                         host)):
        by_label[name] += (e - s) / 1e9
    return Reduced(window_s=(hi - lo) / 1e9,
                   busy_s=busy_total / len(devices), devices=len(devices),
                   ops=dict(ops), op_counts=dict(counts),
                   gaps=by_label.most_common(), pallas=pallas)


def load(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path).planes)


def describe(path: str, top: int = 8) -> str:
    """Planes, lines, their time spans and the commonest event names: for
    reading a trace by hand before trusting the reduction."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(_events(line))
            if evs:
                names = collections.Counter(n for n, _, _ in evs)
                lo = min(s for _, s, _ in evs)
                hi = max(e for _, _, e in evs)
                out.append(f"{plane.name} | {line.name} | {len(evs)} events "
                           f"in [{lo:.0f}, {hi:.0f}] ns | "
                           f"{names.most_common(top)}")
    return "\n".join(out)
