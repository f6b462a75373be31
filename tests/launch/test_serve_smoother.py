"""Smoother serving workload: bucketing, padding, and correctness.

Time-axis padding uses uninformative (R-inflated) measurements, so a
padded request's posteriors on the real steps must match the unpadded
single-trajectory smoother.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LANE_DIVERGED, IteratedConfig, iterated_smoother
from repro.launch.autobatch import FlushPolicy
from repro.data import CoordinatedTurnConfig, make_coordinated_turn_model, \
    simulate_trajectory
from repro.launch.serve import (LAUNCH_COUNTERS, SmootherServeConfig,
                                SmootherServer, launch_counts,
                                serve_smoother)


@pytest.fixture(scope="module")
def served():
    model = make_coordinated_turn_model(CoordinatedTurnConfig())
    cfg = SmootherServeConfig(requests=5, n=12, max_batch=4, n_iter=3,
                              tol=0.0, lm_lambda=0.0, f64=True)
    server = SmootherServer(model, cfg)
    lengths = [12, 7, 12, 5, 7]
    requests = [np.asarray(simulate_trajectory(
        model, L, jax.random.PRNGKey(10 + i))[1])
        for i, L in enumerate(lengths)]
    stats = server.serve_requests(requests, emit=lambda *_: None)
    return model, cfg, lengths, requests, stats


def test_bucketing_and_shapes(served):
    model, cfg, lengths, requests, stats = served
    # Lengths {12} -> bucket 16, {7, 5} -> bucket 8: two launches.
    assert stats["launches"] == 2
    for L, mean in zip(lengths, stats["results"]):
        assert mean.shape == (L + 1, model.nx)
        assert np.all(np.isfinite(mean))


def test_padded_results_match_unpadded(served):
    """Real-step posteriors must be unchanged by time padding."""
    model, cfg, lengths, requests, stats = served
    icfg = IteratedConfig(method=cfg.method, n_iter=cfg.n_iter,
                          tol=cfg.tol, lm_lambda=cfg.lm_lambda)
    for L, ys, mean in zip(lengths, requests, stats["results"]):
        want = iterated_smoother(model, jnp.asarray(ys), icfg)
        np.testing.assert_allclose(mean, np.asarray(want.mean),
                                   rtol=1e-5, atol=1e-6)


def test_serve_smoother_end_to_end():
    stats = serve_smoother(
        SmootherServeConfig(requests=3, n=8, max_batch=2, n_iter=2,
                            tol=0.0, lm_lambda=0.0, vary_lengths=True),
        emit=lambda *_: None)
    assert stats["requests"] == 3
    assert stats["mean_rmse"] < 1.0
    assert len(stats["results"]) == 3


def test_stream_policies_match_oneshot_results():
    """The autobatch queue changes *when* buckets launch, never *what*
    they compute: streaming results (static and deadline policies) must
    match the one-shot bucketing path per request."""
    model = make_coordinated_turn_model(CoordinatedTurnConfig())
    cfg = SmootherServeConfig(requests=3, n=8, max_batch=2, n_iter=2,
                              tol=0.0, lm_lambda=0.0, vary_lengths=False,
                              policy="static", deadline_s=0.5,
                              max_wait_s=0.05)
    server = SmootherServer(model, cfg)
    requests = [np.asarray(simulate_trajectory(
        model, 8, jax.random.PRNGKey(20 + i))[1]) for i in range(3)]

    quiet = lambda *_: None  # noqa: E731
    arrivals = np.zeros(3)   # degenerate stream: everything at t=0
    st_static = server.serve_stream(requests, arrivals, emit=quiet)
    st_dead = server.serve_stream(
        requests, np.asarray([0.0, 0.0, 0.1]), emit=quiet,
        policy=FlushPolicy(kind="deadline", max_batch=cfg.max_batch,
                           max_wait=cfg.max_wait_s))
    oneshot = server.serve_requests(requests, emit=quiet)

    for a, b, c in zip(oneshot["results"], st_static["results"],
                       st_dead["results"]):
        np.testing.assert_allclose(b, a, rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(c, a, rtol=1e-12, atol=1e-12)
    for stats in (st_static, st_dead):
        assert stats["requests"] == 3
        assert stats["launches"] >= 2          # max_batch=2 forces a split
        assert stats["latency_p95_s"] > 0.0
        assert 0.0 <= stats["deadline_hit_rate"] <= 1.0
        assert stats["compiles"] <= 4          # pow2 widths: bounded cache


def test_stream_serve_smoother_end_to_end():
    stats = serve_smoother(
        SmootherServeConfig(requests=4, n=8, max_batch=2, n_iter=2,
                            tol=0.0, lm_lambda=0.0, vary_lengths=False,
                            arrival="bursty", policy="deadline",
                            rate=100.0, burst_size=2, deadline_s=1.0,
                            max_wait_s=0.05),
        emit=lambda *_: None)
    assert stats["requests"] == 4
    assert stats["mean_rmse"] < 1.0
    assert all(m is not None for m in stats["results"])
    assert stats["flush_reasons"]    # at least one flush actually fired


def test_oneshot_reports_verdicts(served):
    """The one-shot path reports a verdict per request, as the stream
    does: every healthy lane is "ok"."""
    *_, stats = served
    assert stats["verdicts"] == {"ok": 5}


def test_f64_serving_refused_on_tpu(monkeypatch):
    """float64 serving cannot compile on a TPU; the server says so up
    front instead of failing inside the first launch."""
    from repro.launch import serve

    monkeypatch.setattr(serve.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="float64 serving does not run"):
        serve.serve_dtype(SmootherServeConfig(f64=True))
    assert serve.serve_dtype(SmootherServeConfig(f64=False)) == jnp.float32


def _width4_server(model):
    return SmootherServer(model, SmootherServeConfig(
        max_batch=4, n_iter=3, tol=0.0, lm_lambda=1.0, f64=False))


def test_warm_launch_with_new_lengths_compiles_nothing():
    """Answers are cut from one host copy of the launch: track lengths
    the warm launch never had, in a bucket it compiled, compile nothing
    (slicing the device array per request compiled programs for each
    new (lane, length))."""
    from jax._src.dispatch import BACKEND_COMPILE_EVENT

    model = make_coordinated_turn_model(CoordinatedTurnConfig())
    server = _width4_server(model)
    server.warmup([16], [4])
    batches = [[np.zeros((n, model.ny)) for n in lengths]
               for lengths in ([5, 9, 13], [6, 11, 14])]
    compiles = []

    def listen(event, duration, **kwargs):
        if event == BACKEND_COMPILE_EVENT:
            compiles.append(kwargs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for batch in batches:
            means, *_ = server.smooth_batch(batch, 16, 4)
            assert [m.shape[0] for m in means] == [len(y) + 1
                                                   for y in batch]
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []


def _diverge_lane_1(run):
    """``run`` with lane 1 reported `LANE_DIVERGED` and lane 2's mean
    not finite at its last (padded) step."""
    def stub(ys, rs):
        traj, info, ll_steps = run(ys, rs)
        traj = traj._replace(mean=traj.mean.at[2, -1].set(jnp.nan))
        info = info._replace(code=info.code.at[1].set(LANE_DIVERGED))
        return traj, info, ll_steps
    return stub


@pytest.mark.parametrize("diverged", [False, True],
                         ids=["padded", "diverged_lane"])
def test_answers_are_the_launch_outputs_sliced_on_the_host(diverged):
    """`smooth_batch` returns the jitted launch's own outputs, each real
    lane's cut to its length on the host, bit for bit: a bucket padded
    in time (9 and 13 of 16 steps) and in batch (3 real lanes of 4)."""
    model = make_coordinated_turn_model(CoordinatedTurnConfig())
    server = _width4_server(model)
    if diverged:
        server._run = _diverge_lane_1(server._run)
    lengths = [16, 9, 13]
    requests = [np.asarray(simulate_trajectory(
        model, n, jax.random.PRNGKey(40 + i))[1])
        for i, n in enumerate(lengths)]
    traj, info, ll_steps = server._run(*server._pad_bucket(requests, 16, 4))
    mean, ll_steps, code, iterations = (
        np.asarray(a) for a in (traj.mean, ll_steps, info.code,
                                info.iterations))

    means, got_info, lls, health = server.smooth_batch(requests, 16, 4)

    assert len(means) == len(lls) == len(health) == 3
    for i, n in enumerate(lengths):
        np.testing.assert_array_equal(means[i], mean[i, :n + 1])
        assert lls[i] == float(np.sum(ll_steps[i, :n]))
    assert health == [bool(code[i] != LANE_DIVERGED)
                      and bool(np.isfinite(mean[i, :n + 1]).all())
                      for i, n in enumerate(lengths)]
    assert health == ([True, False, True] if diverged else [True] * 3)
    assert isinstance(got_info.code, np.ndarray)
    assert isinstance(got_info.iterations, np.ndarray)
    np.testing.assert_array_equal(got_info.code, code)
    np.testing.assert_array_equal(got_info.iterations, iterations)
    want = launch_counts(16, 4, lengths, iterations)
    assert server.counters == {k: want[k] for k in LAUNCH_COUNTERS}
