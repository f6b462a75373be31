"""A run with the timed path broken underneath comes out not correct.

Each cell's harness is driven on the CPU at a tiny size (the look for a
chip skipped), with its own limits file, once sound and once per fault
that a one-chip smoother cell can have:

* ``state_unchanged``: the smoother hands back its initial trajectory;
* ``half_batch``: only half of each launch's lanes are computed, the
  other half answered from them;
* ``answer_altered``: one answer of each launch altered where it is
  produced (lane 0, whose position is moved by 0.5, past the
  0.2 limit on the widest gap).

A cell on one chip has no exchange between chips to leave out.
"""
import time

import jax.numpy as jnp
import pytest

from bench import harness

TINY = {
    "fleet-300": dict(tracks=12, length_min=5, length_max=24, max_batch=4),
    "fleet-pow2-300": dict(tracks=12, lengths=[8, 16], max_batch=4),
    "served-poisson": dict(rate=16.0, length_min=9, length_max=24,
                           max_batch=4, check_sample=6),
}
#: (cell, traffic): the fleet cell, and under its configuration and limits
#: the ragged fleet mix (time padded) and the served open-loop mix, which
#: no cell runs yet.
CASES = [("ct-ieks.fleet-pow2", None), ("ct-ieks.fleet-pow2", "fleet-300"),
         ("ct-ieks.fleet-pow2", "served-poisson")]


def _break(run_fn, fault, m0):
    def broken(ys, rs):
        if fault == "half_batch":
            h = (ys.shape[0] + 1) // 2
            ys = ys.at[h:].set(ys[:ys.shape[0] - h])
            rs = rs.at[h:].set(rs[:rs.shape[0] - h])
        traj, info, ll = run_fn(ys, rs)
        if fault == "state_unchanged":
            traj = traj._replace(mean=jnp.broadcast_to(m0, traj.mean.shape))
        elif fault == "answer_altered":
            traj = traj._replace(mean=traj.mean.at[0, :, :2].add(0.5))
        return traj, info, ll

    return broken


@pytest.fixture(scope="module", params=CASES,
                ids=[c[1] or c[0] for c in CASES])
def prepared(request):
    """One tiny run, set up once (its executables compiled)."""
    import numpy as np

    from bench import traffic as traffic_lib
    from bench.reference.problem import load_problem

    cell, traffic = request.param
    workload = dict(harness.find(harness.load_benchmark()["workloads"],
                                 cell, "workload"))
    workload["traffic"] = traffic or workload["traffic"]
    tr = traffic_lib.load(workload["traffic"])
    tr.update(TINY[workload["traffic"]])
    run = harness.make_run(workload, 2 ** 31 + 99, 1.0, False,
                           traffic_override=tr)
    run.problem = load_problem(run.config["problem"],
                               np.dtype(run.config["dtype"]))
    state = run.driver.setup(run)
    return run, state, state["server"]._run


def _correct(run, state):
    from bench import check

    run.outcome = run.driver.window(run, state)
    return check.check(run, state, run.driver.sample(run, state))


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch",
                                   "answer_altered"])
def test_broken_timed_path_is_not_correct(prepared, fault):
    run, state, run_fn = prepared
    server = state["server"]
    server._run = run_fn if fault is None else _break(
        run_fn, fault, server.model.m0)
    try:
        t0 = time.perf_counter()
        correct, checks = _correct(run, state)
        assert time.perf_counter() - t0 < 120
    finally:
        server._run = run_fn
    assert correct == (fault is None), checks
