"""From a JAX profiler trace (`.xplane.pb`) to the numbers the benchmark
reports: device busy seconds (the union of the intervals in which an
operation ran on the device), the traced window, the operations that
took most device time, and the longest idle gaps, each attributed to
the host span open when the gap began.

A TPU's plane is `/device:TPU:<i>`; its line `XLA Ops` holds one event
per executed operation (the lines `XLA Modules` and `Steps` hold the
enclosing programs and are not added to it, or time would count twice).
Host threads are lines of the plane `/host:CPU`; the benchmark's own
annotations there start with `bench:`, and the program's host work shows
under the names the profiler prints. All times are picoseconds on one
clock inside the file; `reduce_events` works on plain tuples so it can
be checked on a recorded reduction without the profiler.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
BENCH_PREFIX = "bench:"
TOP = 10
NAME_CHARS = 200      # an XLA op's name is its whole HLO line: keep its head
MIN_GAP_S = 1e-3
# host spans shorter than this never own a gap worth reporting, and a
# pass produces hundreds of thousands of them
MIN_HOST_SPAN_S = 1e-3
SHORT_GAPS = "gaps-under-1ms"
Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of `window` that `busy` (merged) does not cover."""
    out, at = [], window[0]
    for s, e in busy:
        if e <= window[0] or s >= window[1]:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < window[1]:
        out.append((at, window[1]))
    return out


def attribute(gap: Interval, host: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost host span open when the gap began (the latest
    start among those that cover that instant); `host-idle` if none."""
    best, best_start = "host-idle", -1.0
    for name, s, e in host:
        if s <= gap[0] < e and s > best_start:
            best, best_start = name, s
    return best


def reduce_events(device_ops: Dict[int, List[Tuple[str, float, float]]],
                  host: Sequence[Tuple[str, float, float]],
                  window: Optional[Interval] = None,
                  n_devices: Optional[int] = None) -> dict:
    """`device_ops`: device index -> [(op name, start s, end s)];
    `host`: [(span name, start s, end s)]. The window is the benchmark's
    own annotation if there is one, else first to last device event.
    Busy seconds are averaged over the devices used."""
    n_dev = n_devices or max(len(device_ops), 1)
    if window is None:
        marks = [(s, e) for name, s, e in host
                 if name.startswith(BENCH_PREFIX)]
        if marks:
            window = (min(s for s, _ in marks), max(e for _, e in marks))
        else:
            all_ev = [(s, e) for ops in device_ops.values()
                      for _, s, e in ops]
            if not all_ev:
                return {"busy_s": 0.0, "window_s": 0.0, "idle_share": None,
                        "device_ops": [], "idle_gaps": [], "n_ops": 0}
            window = (min(s for s, _ in all_ev), max(e for _, e in all_ev))
    w0, w1 = window
    busy_total, by_op, by_gap, n_ops = 0.0, {}, {}, 0
    spans = [h for h in host if not h[0].startswith(BENCH_PREFIX)]
    for ops in device_ops.values():
        clipped = [(max(s, w0), min(e, w1)) for _, s, e in ops
                   if e > w0 and s < w1]
        merged = union(clipped)
        busy_total += sum(e - s for s, e in merged)
        for name, s, e in ops:
            if e > w0 and s < w1:
                by_op[name] = by_op.get(name, 0.0) \
                    + (min(e, w1) - max(s, w0)) / n_dev
                n_ops += 1
        for g in gaps(merged, window):
            # a pass leaves hundreds of thousands of gaps between
            # back-to-back operations: only the long ones are looked up
            who = (attribute(g, spans) if g[1] - g[0] >= MIN_GAP_S
                   else SHORT_GAPS)
            by_gap[who] = by_gap.get(who, 0.0) + (g[1] - g[0]) / n_dev
    busy = busy_total / n_dev
    span = w1 - w0

    def top(d):
        return [[k[:NAME_CHARS], v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": busy, "window_s": span,
            "idle_share": 1.0 - busy / span if span > 0 else None,
            "device_ops": top(by_op), "idle_gaps": top(by_gap),
            "n_ops": n_ops}


def read_xplane(path: str):
    """(device_ops, host spans) in seconds from an `.xplane.pb`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops: Dict[int, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = device_ops.setdefault(idx, [])
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append((ev.name, s, s + ev.duration_ns * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    s = ev.start_ns * 1e-9
                    host.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return device_ops, host


def describe_xplane(path: str, limit: int = 6) -> List[str]:
    """Planes, lines, event counts and a few first events: what to look
    at by hand before trusting the reduction on a new backend."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:limit]:
                out.append(f"    {ev.name[:80]!r} start_ns={ev.start_ns:.0f} "
                           f"dur_ns={ev.duration_ns:.0f}")
    return out


def reduce_xplane(path: str, n_devices: int) -> dict:
    device_ops, host = read_xplane(path)
    host = [h for h in host if h[2] - h[1] >= MIN_HOST_SPAN_S
            or h[0].startswith(BENCH_PREFIX)]
    return reduce_events(device_ops, host, n_devices=n_devices)
