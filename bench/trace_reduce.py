"""Reduce a JAX profiler trace (`.xplane.pb`) to the benchmark's device
numbers.

The traced window runs from the start of the first host span named
`bench.dispatch` to the start of the span `bench.trace_end`, which the
harness opens just before it stops the profiler, or to the end of the last
`bench.fetch` where no job was in flight by then. The harness opens those spans, through
`jax.profiler.TraceAnnotation`, around each job's dispatch, its
`block_until_ready` and the fetch of its answers. Within the window:

- busy: the union of the intervals in which an operation ran on a device,
  from the "XLA Ops" line of each device plane, averaged over the devices;
- device time by operation, summed over the window, counting only
  innermost operations (a `while` whose body's operations are traced
  inside it is not counted again), under the operation's HLO name;
- the idle gaps between busy intervals, each labelled with the host span
  (`bench.*`) that overlaps it most, or "(no span)".
"""
from __future__ import annotations

import collections
import glob
import os
import re

WINDOW_OPEN, WINDOW_END, LAST_FETCH = ("bench.dispatch", "bench.trace_end",
                                       "bench.fetch")
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler.start_trace` dir."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """A short label out of a TPU event's HLO text: the operation's name and
    its (first) result shape, `%fusion.12 = f32[85]{0:T(128)} fusion(...)`
    -> `fusion.12 f32[85]`. A name with no HLO text is kept as it is."""
    name, eq, rest = event_name.partition(" = ")
    if not eq:
        return event_name
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    return f"{name.lstrip('%')} {shape.group(0) if shape else ''}".strip()


def _leaves(ops):
    """The events that hold no other event of their line inside them."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[0] >= o[1]]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def reduce_trace(path: str) -> dict | None:
    """{window_s, busy_s, device_ops, idle_gaps, devices (plane names)},
    or None when the
    trace holds no window or no device operation inside it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, devices, names = [], [], []
    for plane in data.planes:
        if _is_device(plane.name):
            ops = [(e.start_ns, e.end_ns, e.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:         # a device plane without operations is no chip
                devices.append(ops)
                names.append(plane.name)
        elif plane.name.startswith("/host:"):
            spans.extend((e.start_ns, e.end_ns, e.name)
                         for line in plane.lines for e in line.events
                         if e.name.startswith(SPAN_PREFIX))
    opens = [s for s, _, n in spans if n == WINDOW_OPEN]
    fetched = [e for _, e, n in spans if n == LAST_FETCH]
    ends = [s for s, _, n in spans if n == WINDOW_END]
    if not opens or not (fetched or ends) or not any(devices):
        return None
    lo = min(opens)
    if ends and (not fetched or max(opens) > max(fetched)):
        hi = min(ends)      # the profiler stopped inside a job
    else:
        hi = max(fetched)
    if hi <= lo:
        return None

    busy_total, by_op, gaps = 0.0, collections.Counter(), []
    for ops in devices:
        for s, e, name in _leaves(ops):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                by_op[op_name(name)] += (e - s) * 1e-9
        busy = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    if busy_total <= 0:
        return None

    def label(gap):
        s, e = gap
        best, name = 0, "(no span)"
        for ss, se, n in spans:
            overlap = min(e, se) - max(s, ss)
            if overlap > best:
                best, name = overlap, n
        return name

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / len(devices),
        "devices": names,
        "device_ops": [[n, s] for n, s in by_op.most_common(TOP)],
        "idle_gaps": [[label(g), (g[1] - g[0]) * 1e-9] for g in gaps[:TOP]],
    }
