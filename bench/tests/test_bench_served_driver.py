"""The real-clock served driver, with a fake executor and a fake clock: a
stall raises the latency of every request behind it."""
import types

import numpy as np
import pytest

from bench.drivers import open_loop

pytest.importorskip("repro.launch.autobatch")


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def sleep(self, dt):
        self.now += dt


class FakeServer:
    """Answers every flush after ``compute`` seconds; the first flush
    that starts at or after ``stall_at`` takes ``stall`` seconds more."""

    def __init__(self, clock, compute=0.05, stall_at=0.5, stall=0.0):
        self.clock, self.compute = clock, compute
        self.stall_at, self.stall = stall_at, stall
        self.stall_span = None
        self.cfg = types.SimpleNamespace(deadline_s=2.0)
        self.model = types.SimpleNamespace(nx=1)
        self.model_id = "fake"
        self.icfg = types.SimpleNamespace(method="ekf")

    def run_flush(self, fl):
        dt = self.compute
        if self.stall and self.stall_span is None and \
                self.clock.now >= self.stall_at:
            dt += self.stall
            self.stall_span = (self.clock.now, self.clock.now + dt)
        self.clock.now += dt
        outcomes = {r.req_id: "ok" for r in fl.requests}
        store = {r.req_id: (np.zeros((r.n + 1, 1)), 0.0)
                 for r in fl.requests}
        return dt, outcomes, store, len(fl.requests)

    def retry_request(self, req):  # no lane fails here
        raise AssertionError("no retry expected")


def _serve(stall):
    from repro.launch.autobatch import ComputeEstimator, FlushPolicy

    clock = FakeClock()
    server = FakeServer(clock, stall=stall)
    policy = FlushPolicy(kind="deadline", max_batch=4, max_wait=0.1,
                         slack=1.25)
    due = np.arange(40) * 0.05
    ys = [np.zeros((10, 1)) for _ in due]
    out = open_loop.serve(server, ys, due, policy, ComputeEstimator(0.4),
                          clock=clock, sleep=clock.sleep)
    return server, due, out


@pytest.mark.parametrize("stall", [0.5, 1.0, 2.0])
def test_a_stall_delays_every_request_behind_it(stall):
    _, _, calm = _serve(0.0)
    server, due, out = _serve(stall)
    lat = out["latency_s"]
    assert np.all(np.isfinite(lat)) and np.all(lat > 0)
    start, end = server.stall_span
    behind = (due >= start) & (due < end)
    assert behind.any()
    # A request due while the executor stalls cannot finish before the
    # stall ends: its latency counts the wait from its due time.
    assert np.all(lat[behind] >= end - due[behind])
    assert np.percentile(lat, 95) > np.percentile(calm["latency_s"], 95)
    assert np.max(lat) >= stall


@pytest.mark.parametrize("stall", [0.0, 1.0])
def test_every_request_is_answered_once_with_its_wait(stall):
    _, due, out = _serve(stall)
    served = sorted(i for launch in out["launches"] for i in launch)
    assert served == list(range(len(due)))
    assert np.all(out["queue_wait_s"] >= 0)
    assert np.all(out["queue_wait_s"] <= out["latency_s"])
    assert np.all(out["lateness_s"] >= 0)
