"""The profiler trace of a run's window, and its reduction to numbers.

The traced run records JAX's profiler trace around the measured window.
Device operations come from the device planes' ``XLA Ops`` line; the
benchmark's own host spans (`bench.harness.span`, names ``bench.*``)
come from the host plane. From them:

* ``busy_s``: the union of the device-op intervals inside the window,
  averaged over the devices traced;
* ``window_s``: the length of the traced window (the ``bench.window``
  span);
* ``device_ops``: the ten ops with the most self time (an op's time
  less that of the ops nested inside it, as a while loop's body is in
  the loop), named by the HLO instruction and, for a custom call, its
  target;
* ``idle_gaps``: the ten longest gaps between device ops inside the
  window, each named by the innermost ``bench.*`` span the host was in
  at the gap's middle.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, NamedTuple, Optional

WINDOW_SPAN = "bench.window"
OP_LINE = "XLA Ops"


class Event(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


class Events(NamedTuple):
    devices: Dict[str, List[Event]]   # device plane -> its ops
    host: List[Event]                 # the bench.* spans


def read_xplane(path: str) -> Events:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [Event(e.name, e.start_ns, e.end_ns)
                   for line in plane.lines if line.name == OP_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host.extend(Event(e.name, e.start_ns, e.end_ns)
                        for line in plane.lines for e in line.events
                        if e.name.startswith("bench."))
    return Events(devices, host)


def short_name(name: str) -> str:
    """``%custom-call.218 LuDecompositionBlock`` from a full HLO line."""
    head = name.split(" = ")[0]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{head} {target.group(1)}" if target else head


def _self_times(ops, lo, hi) -> dict:
    """Each op's time inside ``[lo, hi]`` less that of the ops nested in
    it, summed by short name."""
    out, stack = {}, []   # [end, name, nested time, own time]

    def close(item):
        name = item[1]
        out[name] = out.get(name, 0.0) + item[3] - item[2]

    for e in sorted(ops, key=lambda e: (e.start_ns, -e.end_ns)):
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t <= s:
            continue
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += t - s
        stack.append([t, short_name(e.name), 0.0, t - s])
    while stack:
        close(stack.pop())
    return out


def _union(intervals, lo, hi) -> list:
    """Merged ``[start, end]`` intervals clipped to ``[lo, hi]``."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(events: Events, top: int = 10) -> Optional[dict]:
    """The numbers of a traced window; None where the trace holds no
    window span or no device op."""
    windows = [e for e in events.host if e.name == WINDOW_SPAN]
    if not windows or not events.devices:
        return None
    lo, hi = windows[0].start_ns, windows[0].end_ns
    busy, gaps, op_time = [], [], {}
    for ops in events.devices.values():
        merged = _union([(e.start_ns, e.end_ns) for e in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        for name, t in _self_times(ops, lo, hi).items():
            op_time[name] = op_time.get(name, 0.0) + t
    spans = [e for e in events.host if e.name != WINDOW_SPAN]

    def doing(mid):
        inside = [e for e in spans if e.start_ns <= mid <= e.end_ns]
        if not inside:
            return "bench.window"
        return min(inside, key=lambda e: e.end_ns - e.start_ns).name

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[name, t / 1e9] for name, t in sorted(
            op_time.items(), key=lambda kv: kv[1], reverse=True)[:top]],
        "idle_gaps": [[doing((s + e) / 2), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }


class Tracer:
    """Records the profiler trace while the context is open (a no-op
    when ``enabled`` is false); `summary` holds the reduction after."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.summary: Optional[dict] = None
        self._dir = None

    def __enter__(self):
        if self.enabled:
            import jax

            self._dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self._dir)
        return self

    def __exit__(self, *exc):
        if not self.enabled:
            return False
        import jax

        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                              recursive=True)
            if paths:
                self.summary = reduce(read_xplane(paths[0]))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False
