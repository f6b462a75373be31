"""Instrumentation of the smoother service: named scopes on every stage,
profiler spans around each launch, and the launches' work counters.

Scopes change HLO metadata only, so a server traced without them must
give bit-identical answers; the counters are exact and hand-counted.
"""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Gaussian, LaneStatus, SmootherSpec
from repro.core.scopes import STAGES
from repro.data import CoordinatedTurnConfig, make_coordinated_turn_model, \
    simulate_trajectory
from repro.launch.autobatch import pad_width
from repro.launch.serve import (LAUNCH_COUNTERS, SmootherServeConfig,
                                SmootherServer, launch_counts)

LENGTHS = [16, 9, 16]   # one width-4 launch at n_pad 16: 3 real lanes


@pytest.fixture(scope="module")
def model():
    return make_coordinated_turn_model(CoordinatedTurnConfig())


@pytest.fixture(scope="module")
def requests(model):
    return [np.asarray(simulate_trajectory(
        model, L, jax.random.PRNGKey(30 + i))[1])
        for i, L in enumerate(LENGTHS)]


def _server(model, **axes):
    """A width-4 server; adaptive damping from lambda 1 (the benchmark's
    spec) unless ``axes`` say otherwise."""
    axes = {"damping": "adaptive", "lm_lambda": 1.0, **axes}
    cfg = SmootherServeConfig(max_batch=4, n_iter=10, tol=1e-6)
    return SmootherServer(model, cfg, spec=SmootherSpec(
        n_iter=10, tol=1e-6, **axes))


def test_scopes_leave_answers_bit_identical(model, requests, monkeypatch):
    scoped = _server(model).smooth_batch(requests, 16, 4)
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_server = _server(model)
    bare = bare_server.smooth_batch(requests, 16, 4)
    hlo = bare_server._run.lower(*bare_server._pad_bucket(
        requests, 16, 4)).as_text(debug_info=True)
    assert "smoother." not in hlo          # the scopes really were off
    for got, want in zip(scoped[0], bare[0]):
        np.testing.assert_array_equal(got, want)
    assert scoped[2] == bare[2] and scoped[3] == bare[3]
    for got, want in zip(scoped[1], bare[1]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    jax.clear_caches()


def test_served_run_names_every_stage(model, requests):
    server = _server(model)
    hlo = server._run.lower(*server._pad_bucket(requests, 16, 4)) \
        .compile().as_text()
    op_names = [n for n in re.findall(r'op_name="([^"]+)"', hlo)
                if n.startswith("jit(")]
    found = {m for n in op_names for m in re.findall(r"smoother\.[a-z]+", n)}
    assert found == set(STAGES)


@pytest.mark.parametrize("n_pad,b_pad,lengths,iterations,want", [
    # Every lane runs the whole budget: nothing idle.
    (512, 128, [512] * 100, [10] * 128, (65536, 51200, 1000, 1000)),
    # Lanes stop at 5, 6 and 10 passes; the padded lane copies lane 0.
    (16, 4, [16, 9, 16], [5, 6, 10, 5], (64, 41, 30, 21)),
    # One real lane of a width-1 launch, time-padded 9 -> 16.
    (16, 1, [9], [3], (16, 9, 3, 3)),
], ids=["full_budget", "ragged_passes", "one_lane"])
def test_launch_counts_by_hand(n_pad, b_pad, lengths, iterations, want):
    got = launch_counts(n_pad, b_pad, lengths, np.asarray(iterations))
    assert tuple(got[k] for k in LAUNCH_COUNTERS) == want


def test_counters_follow_lane_status(model, requests):
    """Lanes that stop at different passes: the server's counters are the
    hand count of the launch's own `LaneStatus.iterations`."""
    server = _server(model, damping="fixed", lm_lambda=0.0)
    _, info, _, _ = server.smooth_batch(requests, 16, 4)
    iters = np.asarray(info.iterations)
    assert len(set(iters[:3].tolist())) > 1, iters  # passes differ
    assert server.counters == {
        "step_lanes_launched": 4 * 16, "step_lanes_real": 41,
        "lane_passes_run": 3 * int(iters.max()),
        "lane_passes_active": int(iters[:3].sum())}


def _stub_run(nx):
    """A launch that returns shapes only: every lane ran 10 passes."""
    def run(ys, rs):
        b, n = ys.shape[:2]
        traj = Gaussian(mean=jnp.zeros((b, n + 1, nx)),
                        cov=jnp.zeros((b, n + 1, nx, nx)))
        info = LaneStatus(iterations=jnp.full((b,), 10, jnp.int32),
                          final_delta=jnp.zeros((b,)),
                          code=jnp.zeros((b,), jnp.int32),
                          final_cost=jnp.zeros((b,)))
        return traj, info, jnp.zeros((b, n))
    return run


def test_pow2_fleet_padded_share(model):
    """The benchmark's fleet-pow2 job: 100 tracks each of 128, 256 and 512
    steps in width-128 launches pads 28 of every 128 lanes: 21.875 %."""
    cfg = SmootherServeConfig(max_batch=128, n_iter=10)
    server = SmootherServer(model, cfg)
    server._run = _stub_run(model.nx)
    requests = [np.zeros((n, model.ny)) for n in (128, 256, 512)
                for _ in range(100)]
    stats = server.serve_requests(requests, emit=lambda *_: None)
    assert pad_width(100, 128) == 128 and stats["launches"] == 3
    assert stats["step_lanes_launched"] == 128 * (128 + 256 + 512)
    assert stats["step_lanes_real"] == 100 * (128 + 256 + 512)
    share = 100 * (1 - stats["step_lanes_real"]
                   / stats["step_lanes_launched"])
    assert share == 21.875
    assert stats["lane_passes_run"] == stats["lane_passes_active"] == 3000
    assert stats["mean_iterations"] == 10.0


def test_stream_stats_count_only_the_stream(model, requests):
    server = _server(model)
    oneshot = server.serve_requests(requests, emit=lambda *_: None)
    stream = server.serve_stream(requests, np.zeros(len(requests)),
                                 emit=lambda *_: None)
    for k in LAUNCH_COUNTERS:   # warmup launches are not the stream's
        assert stream[k] == oneshot[k] > 0
    assert stream["mean_iterations"] == oneshot["mean_iterations"]


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]


def test_launch_spans_nest(model, requests, tmp_path):
    server = _server(model)
    server.warmup([16], [4])          # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        server.smooth_batch(requests, 16, 4)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    launches = [e for e in events if e[0] == "serve.launch"]
    assert len(launches) == 1
    _, lo, hi, args = launches[0]
    assert (args["n_pad"], args["b_pad"], args["lanes"], args["lane"]) \
        == (16, 4, 3, "primary")
    children = sorted((e for e in events if e[0] != "serve.launch"),
                      key=lambda e: e[1])
    assert [e[0] for e in children] == ["serve.pad", "serve.dispatch",
                                        "serve.wait", "serve.unpack"]
    assert all(lo <= s <= t <= hi for _, s, t, _ in children)
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))


def test_warmup_autotune_span(model, tmp_path):
    server = _server(model)
    jax.profiler.start_trace(str(tmp_path))
    try:
        server.warmup([16], [4])
    finally:
        jax.profiler.stop_trace()
    names = [e[0] for e in _host_events(tmp_path)]
    assert names.count("serve.autotune") == 1
    assert names.count("serve.launch") == 1   # the compile launch
