"""Device time by smoother stage and host time by serving span, from a
profiler trace of the program.

The program names its own work (DESIGN.md §6, "Tracing the live
service"): every device op carries the ``smoother.*`` named scope of the
stage that traced it in its HLO ``op_name``, and every launch is a
``serve.launch`` host span around ``serve.pad``, ``serve.dispatch``,
``serve.wait`` and ``serve.unpack``. This adds to `bench.tracing.reduce`'s
numbers, which it computes the same way:

* ``scopes``: device self time (an op's time less that of the ops nested
  in it) by the innermost ``smoother.*`` scope of each op, and
  ``(unscoped)`` for ops outside every stage, summed over the devices;
* ``host_spans``: ``[self time, count]`` by ``serve.*`` span name, self
  time being the span's time less that of the spans nested in it on the
  same thread;
* ``idle_gaps``: named by the innermost ``bench.*`` or ``serve.*`` span
  the host was in, so a gap reads ``serve.pad`` or ``serve.unpack``;
* ``idle_by_span``: ``[seconds, gaps]`` of all the window's idle gaps by
  that name, which splits the device's idle time between the host's
  steps.

An op's ``op_name`` is the ``tf_op`` stat of its event metadata in the
trace, which `jax.profiler.ProfileData` does not expose; `op_names` reads
it from the trace file's protobuf. It is what the executable was compiled
with: an executable loaded from JAX's persistent compilation cache keeps
the names of the code that first compiled it, and the cache key leaves
``op_name`` out.

A trace without a ``bench.window`` span (a trace of the live service) is
read over its device ops' extent.

    python3 bench/stages.py <trace directory or .xplane.pb file>
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import tracing  # noqa: E402

SCOPE = re.compile(r"smoother\.[a-z]+")
UNSCOPED = "(unscoped)"
HOST_PREFIXES = ("bench.", "serve.")
OP_NAME_STAT = "tf_op"


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    thread: int


class Trace(NamedTuple):
    events: tracing.Events            # device ops; bench.* and serve.* spans
    scopes: Dict[str, List[str]]      # device plane -> each op's scope
    spans: List[Span]                 # the host spans with their thread


def innermost_scope(op_name: str) -> str:
    """The last ``smoother.*`` scope in an op's name stack."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else UNSCOPED


# -- the trace file's protobuf (XSpace), as far as op names need it --------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of the message in ``buf[lo:hi]``; a
    length-delimited value is its ``(start, end)``."""
    i = lo
    while i < hi:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value, i = None, i + width
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield tag >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, spans) -> Iterator[Tuple[int, tuple]]:
    for lo, hi in spans:
        key, value = 0, (lo, lo)
        for f, v in _fields(buf, lo, hi):
            if f == 1:
                key = v
            elif f == 2:
                value = v
        yield key, value


def op_names(path: str) -> Dict[str, List[str]]:
    """Device plane -> the ``op_name`` of each event of its ``XLA Ops``
    line, in the line's order ("" where the trace has none)."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):          # XSpace.planes
        if f != 1:
            continue
        name, lines, metas, stats = "", [], [], []
        for g, v in _fields(buf, *plane):                 # XPlane
            if g == 2:
                name = _text(buf, v)
            elif g == 3:
                lines.append(v)
            elif g == 4:
                metas.append(v)
            elif g == 5:
                stats.append(v)
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for key, value in _map_entries(buf, stats):      # XStatMetadata
            for g, v in _fields(buf, *value):
                if g == 2:
                    stat_names[key] = _text(buf, v)
        op_stat = [k for k, v in stat_names.items() if v == OP_NAME_STAT]
        meta_op = {}
        for key, value in _map_entries(buf, metas):      # XEventMetadata
            for g, stat in _fields(buf, *value):
                if g != 5:
                    continue
                sid, text = None, None
                for h, v in _fields(buf, *stat):          # XStat
                    if h == 1:
                        sid = v
                    elif h == 5:
                        text = _text(buf, v)
                    elif h == 7:
                        text = stat_names.get(v)
                if sid in op_stat and text is not None:
                    meta_op[key] = text
        for line in lines:
            line_name, events = "", []
            for g, v in _fields(buf, *line):              # XLine
                if g == 2:
                    line_name = _text(buf, v)
                elif g == 4:
                    events.append(v)
            if line_name != tracing.OP_LINE:
                continue
            ids = []
            for lo, hi in events:                         # XEvent
                mid = 0
                for g, v in _fields(buf, lo, hi):
                    if g == 1:
                        mid = v
                        break
                ids.append(mid)
            out[name] = [meta_op.get(i, "") for i in ids]
    return out


# -- reading and reducing --------------------------------------------------

def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    names = op_names(path)
    data = ProfileData.from_file(path)
    devices, scopes, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [tracing.Event(e.name, e.start_ns, e.end_ns)
                   for line in plane.lines if line.name == tracing.OP_LINE
                   for e in line.events]
            if not ops:
                continue
            stack = names.get(plane.name, [])
            if len(stack) != len(ops):     # cannot pair them: read none
                stack = [""] * len(ops)
            devices[plane.name] = ops
            scopes[plane.name] = [innermost_scope(n) for n in stack]
        elif plane.name.startswith("/host:"):
            spans.extend(Span(e.name, e.start_ns, e.end_ns, thread)
                         for thread, line in enumerate(plane.lines)
                         for e in line.events
                         if e.name.startswith(HOST_PREFIXES))
    host = [tracing.Event(s.name, s.start_ns, s.end_ns) for s in spans]
    return Trace(tracing.Events(devices, host), scopes, spans)


def with_window(trace: Trace) -> Trace:
    """The trace itself where it has a ``bench.window`` span, else with
    one over its device ops' extent."""
    if any(e.name == tracing.WINDOW_SPAN for e in trace.events.host):
        return trace
    ops = [e for v in trace.events.devices.values() for e in v]
    if not ops:
        return trace
    window = tracing.Event(tracing.WINDOW_SPAN,
                           min(e.start_ns for e in ops),
                           max(e.end_ns for e in ops))
    return trace._replace(events=trace.events._replace(
        host=trace.events.host + [window]))


def reduce(trace: Trace, top: int = 10) -> Optional[dict]:
    """`tracing.reduce`'s numbers plus ``idle_by_span``, ``scopes`` and
    ``host_spans``; None where it reads nothing."""
    out = tracing.reduce(trace.events, top=None)
    if out is None:
        return None
    idle: Dict[str, list] = {}
    for name, t in out["idle_gaps"]:
        entry = idle.setdefault(name, [0.0, 0])
        entry[0] += t
        entry[1] += 1
    out["device_ops"] = out["device_ops"][:top]
    out["idle_gaps"] = out["idle_gaps"][:top]
    out["idle_by_span"] = dict(sorted(idle.items(), key=lambda kv: -kv[1][0]))
    window = next(e for e in trace.events.host
                  if e.name == tracing.WINDOW_SPAN)
    lo, hi = window.start_ns, window.end_ns
    scopes: Dict[str, float] = {}
    for plane, ops in trace.events.devices.items():
        # Self times keyed by the op's scope instead of its name.
        named = [tracing.Event(scope, e.start_ns, e.end_ns)
                 for e, scope in zip(ops, trace.scopes[plane])]
        for scope, t in tracing._self_times(named, lo, hi).items():
            scopes[scope] = scopes.get(scope, 0.0) + t
    spans: Dict[str, list] = {}
    for thread in {s.thread for s in trace.spans}:
        mine = [tracing.Event(s.name, s.start_ns, s.end_ns)
                for s in trace.spans
                if s.thread == thread and s.name.startswith("serve.")]
        for s in mine:
            if s.start_ns < hi and s.end_ns > lo:
                spans.setdefault(s.name, [0.0, 0])[1] += 1
        for name, t in tracing._self_times(mine, lo, hi).items():
            spans[name][0] += t / 1e9
    out["scopes"] = {k: v / 1e9 for k, v in sorted(
        scopes.items(), key=lambda kv: kv[1], reverse=True)}
    out["host_spans"] = dict(sorted(spans.items()))
    return out


def find_xplane(path: str) -> str:
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise SystemExit(f"no .xplane.pb under {path}")
        return max(found, key=os.path.getmtime)
    return path


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    summary = reduce(with_window(read_xplane(find_xplane(args[0]))))
    if summary is None:
        print("no device op in the trace", file=sys.stderr)
        return 1
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
