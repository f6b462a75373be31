"""The trace reduction on a small trace recorded on a TPU v5e (a fleet
job of 8 tracks, served twice; device ops of the ``XLA Ops`` line and
the benchmark's host spans), kept as a fixture."""
import json
import os

import pytest

from bench import tracing

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_small.json")


def _events(window=None):
    with open(FIXTURE) as fh:
        raw = json.load(fh)
    devices = {k: [tracing.Event(*e) for e in v]
               for k, v in raw["devices"].items()}
    host = [tracing.Event(*e) for e in raw["host"]
            if e[0] != tracing.WINDOW_SPAN]
    lo, hi = window or next((e[1], e[2]) for e in raw["host"]
                            if e[0] == tracing.WINDOW_SPAN)
    host.append(tracing.Event(tracing.WINDOW_SPAN, lo, hi))
    return tracing.Events(devices, host), lo, hi


def _busy_by_sweep(ops, lo, hi):
    """Covered time by counting open intervals along sorted edges."""
    edges = sorted([(max(e.start_ns, lo), 1) for e in ops
                    if e.end_ns > lo and e.start_ns < hi]
                   + [(min(e.end_ns, hi), -1) for e in ops
                      if e.end_ns > lo and e.start_ns < hi])
    busy, depth, last = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def _windows():
    ev, lo, hi = _events()
    ops = next(iter(ev.devices.values()))
    mid = (min(e.start_ns for e in ops) + max(e.end_ns for e in ops)) / 2
    return [None, (lo, mid), (mid, hi)]


@pytest.mark.parametrize("window", _windows(), ids=["whole", "first_half",
                                                    "second_half"])
def test_busy_and_idle_match_a_sweep(window):
    events, lo, hi = _events(window)
    got = tracing.reduce(events)
    ops = next(iter(events.devices.values()))
    assert got["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert got["busy_s"] == pytest.approx(
        _busy_by_sweep(ops, lo, hi) / 1e9, rel=1e-9, abs=1e-12)
    assert 0 < got["busy_s"] <= got["window_s"]
    gaps = [g[1] for g in got["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10
    # The longest gaps cannot add up to more than the idle time.
    assert sum(gaps) <= got["window_s"] - got["busy_s"] + 1e-9


def test_breakdown_names_ops_and_host_spans():
    events, lo, hi = _events()
    got = tracing.reduce(events)
    ops = got["device_ops"]
    assert 0 < len(ops) <= 10
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    total = sum(min(e.end_ns, hi) - max(e.start_ns, lo)
                for e in next(iter(events.devices.values())))
    assert sum(t for _, t in ops) <= total / 1e9 + 1e-12
    names = {e.name for e in events.host}
    assert all(label in names for label, _ in got["idle_gaps"])


def test_no_window_or_no_device_reads_nothing():
    events, _, _ = _events()
    assert tracing.reduce(tracing.Events({}, events.host)) is None
    assert tracing.reduce(tracing.Events(
        events.devices, [e for e in events.host
                         if e.name != tracing.WINDOW_SPAN])) is None


@pytest.mark.parametrize("ops,want", [
    ([("%while.1 = loop", 0, 10), ("%fusion.2 = f", 2, 5)],
     {"%while.1": 7, "%fusion.2": 3}),
    ([("%while.1 = loop", 0, 10), ("%fusion.2 = f", 2, 5),
      ("%custom-call.3 = c, custom_call_target=\"Cholesky\"", 5, 9)],
     {"%while.1": 3, "%fusion.2": 3, "%custom-call.3 Cholesky": 4}),
    ([("%fusion.2 = f", 0, 4), ("%fusion.2 = f", 6, 8)], {"%fusion.2": 6}),
])
def test_device_ops_are_self_times(ops, want):
    events = tracing.Events(
        {"/device:TPU:0": [tracing.Event(*o) for o in ops]},
        [tracing.Event(tracing.WINDOW_SPAN, 0, 10)])
    got = dict(tracing.reduce(events)["device_ops"])
    assert got == pytest.approx({k: v / 1e9 for k, v in want.items()})
