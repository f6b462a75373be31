"""Offline fleet jobs, closed loop.

A job is the run's whole set of tracks, served by one
`SmootherServer.serve_requests` call: bucketed by time, padded, and
launched ``max_batch`` lanes at a time. The window re-serves the job until
``seconds`` have passed and counts whole jobs: every track of every job
that the program answered as sound (verdict ``ok``), over the time from
the first job's start to the last one's end.

Traffic parameters: ``tracks``, the lengths (``lengths``, or
``length_min`` and ``length_max``; see `bench.traffic.lengths`) and
``max_batch``. Every answer of the window's last job is checked.
"""
from __future__ import annotations

import time

import numpy as np

from bench import program, traffic as traffic_lib
from bench.harness import Outcome, span


def setup(run) -> dict:
    """Tracks and server; the program's combine choice made for every
    launch shape; then one warm job, which compiles (or loads from the
    compile cache) everything the window's jobs run."""
    from repro.core import build_smoother

    state = {"server": program.build_server(run.config, run.traffic)}
    reseed(run, state)
    server = state["server"]
    smoother = build_smoother(server.spec)
    with span("bench.warmup"):
        for n_pad, widths in sorted(program.launch_shapes(
                server, state["lens"]).items()):
            for b_pad in sorted(widths):
                smoother.autotune(b_pad, n_pad, server.model.nx,
                                  server.model.m0.dtype)
        server.serve_requests(state["ys"], emit=lambda *_: None)
    return state


def reseed(run, state: dict) -> None:
    """The run's tracks, made from its seed."""
    lens = traffic_lib.lengths(run.traffic, run.traffic["tracks"], run.seed)
    with span("bench.generate"):
        state["ys"] = traffic_lib.tracks(run.problem, lens, run.seed)
    state["lens"] = lens


def window(run, state: dict) -> Outcome:
    server, ys = state["server"], state["ys"]
    jobs, iters, verdicts = 0, [], {}
    t0 = time.perf_counter()
    while True:
        with span("bench.serve_requests"):
            stats = server.serve_requests(ys, emit=lambda *_: None)
        jobs += 1
        iters.append(stats["mean_iterations"])
        for k, v in stats["verdicts"].items():
            verdicts[k] = verdicts.get(k, 0) + v
        if time.perf_counter() - t0 >= run.seconds:
            break
    elapsed = time.perf_counter() - t0
    ok = verdicts.get("ok", 0)
    return Outcome(
        window_s=elapsed, attempted=jobs * len(ys),
        failed=jobs * len(ys) - ok, completed=ok,
        results_failed=len(ys) - stats["verdicts"].get("ok", 0),
        results=stats["results"], mean_iterations=float(np.mean(iters)),
        step_passes=float(np.sum(state["lens"])) * float(np.mean(iters))
        * jobs,
        extra={"jobs": jobs, "verdicts": verdicts})


def sample(run, state: dict) -> list:
    """Indices of the requests checked: every track of the last job."""
    return list(range(len(state["ys"])))
