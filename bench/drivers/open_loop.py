"""A served stream, open loop, on the real clock.

Requests are due at the traffic's send times whether or not earlier ones
have finished. One thread does everything, in the order a single-threaded
server would: it submits every request that is due to the program's
`AutobatchQueue`, takes the flushes that `pop_ready(now)` says are due,
runs each through `SmootherServer.run_flush`, re-submits a request whose
lane failed through `retry_request`, and otherwise sleeps until the next
send time or the queue's next timer. Each request is timed from its due
time to its result on the host, so a stall counts against every request
it delays.

Traffic parameters: ``rate`` (requests per second), ``burst``,
``length_min``, ``length_max``, ``max_batch``, the flush policy
(``policy``, ``deadline_s``, ``max_wait_s``, ``slack``) and
``check_sample`` (requests compared with the reference).
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import program, traffic as traffic_lib
from bench.harness import Outcome, span


def make_policy(traffic: dict):
    from repro.launch.autobatch import FlushPolicy

    return FlushPolicy(kind=traffic["policy"],
                       max_batch=traffic["max_batch"],
                       max_wait=traffic["max_wait_s"],
                       slack=traffic["slack"])


def setup(run) -> dict:
    from repro.launch.autobatch import ComputeEstimator

    policy = make_policy(run.traffic)
    state = {"server": program.build_server(run.config, run.traffic),
             "policy": policy,
             "estimator": ComputeEstimator(policy.ema_alpha,
                                           policy.default_compute)}
    reseed(run, state)
    server = state["server"]
    n_pads = sorted({server.queue_signature(int(n))[2]
                     for n in state["lens"]})
    widths = sorted({policy.pad_width(k)
                     for k in range(1, policy.max_batch + 1)})
    with span("bench.warmup"):
        server.warmup(n_pads, widths, state["estimator"]
                      if policy.kind == "deadline" else None)
    return state


def reseed(run, state: dict) -> None:
    """The run's send times, lengths and tracks, made from its seed."""
    due = traffic_lib.arrivals(run.traffic, run.seconds, run.seed)
    lens = traffic_lib.lengths(run.traffic, len(due), run.seed)
    with span("bench.generate"):
        state["ys"] = traffic_lib.tracks(run.problem, lens, run.seed)
    state.update(lens=lens, due=due)


def serve(server, ys, due, policy, estimator, clock=time.perf_counter,
          sleep=time.sleep) -> dict:
    """Serve requests ``ys`` due at ``due`` (seconds from the start) on
    the real clock (``clock``/``sleep`` are the test's to replace).
    Returns per-request latency, queue wait and verdict, each flush's
    seconds, and the generator's lateness."""
    from repro.launch.autobatch import (VERDICT_FAILED, AutobatchQueue,
                                        QueuedRequest)

    deadline_s = server.cfg.deadline_s
    reqs = [QueuedRequest(req_id=i, n=len(y), nx=server.model.nx,
                          arrival=float(t), deadline=float(t) + deadline_s,
                          payload=y, model_id=server.model_id,
                          method=server.icfg.method)
            for i, (y, t) in enumerate(zip(ys, due))]
    queue = AutobatchQueue(policy, estimator)
    count = len(reqs)
    latency = np.full(count, np.nan)
    wait = np.full(count, np.nan)
    verdict = [None] * count
    results = [None] * count
    lateness, flush_s, launches = [], [], []
    sent = 0
    t0 = clock()
    while sent < count or len(queue):
        now = clock() - t0
        with span("bench.submit"):
            while sent < count and reqs[sent].arrival <= now:
                queue.submit(reqs[sent], now)
                lateness.append(now - reqs[sent].arrival)
                sent += 1
        with span("bench.pop_ready"):
            flushes = queue.pop_ready(now)
            if not flushes and sent == count and \
                    math.isinf(queue.next_due()):
                flushes = queue.pop_ready(now, drain=True)
        for fl in flushes:
            start = clock() - t0
            with span("bench.run_flush"):
                dt, outcomes, store, _ = server.run_flush(fl)
            end = clock() - t0
            flush_s.append(end - start)
            queue.estimator.observe(fl.signature, fl.b_pad, dt)
            launches.append([r.req_id for r in fl.requests])
            for r in fl.requests:
                v = outcomes[r.req_id]
                if v == VERDICT_FAILED and r.attempt == 0:
                    queue.submit(server.retry_request(r), end)
                    continue
                latency[r.req_id] = end - r.arrival
                wait[r.req_id] = start - r.arrival
                verdict[r.req_id] = v
                results[r.req_id] = store[r.req_id][0]
        if not flushes:
            nxt = min(reqs[sent].arrival if sent < count else math.inf,
                      queue.next_due())
            pause = nxt - (clock() - t0)
            if pause > 0:
                with span("bench.idle_wait"):
                    sleep(pause)
    return {"latency_s": latency, "queue_wait_s": wait, "verdict": verdict,
            "results": results, "flush_s": np.asarray(flush_s),
            "lateness_s": np.asarray(lateness), "launches": launches,
            "elapsed_s": clock() - t0}


def window(run, state: dict) -> Outcome:
    out = serve(state["server"], state["ys"], state["due"], state["policy"],
                state["estimator"])
    state["served"] = out
    ok = sum(v in ("ok", "retried") for v in out["verdict"])
    late = out["lateness_s"]
    print(f"[open_loop] {len(out['verdict'])} requests due in "
          f"{run.seconds} s, {len(out['launches'])} launches; generator "
          f"lateness p95 {float(np.percentile(late, 95)) * 1e3!r} ms, max "
          f"{float(late.max()) * 1e3!r} ms", flush=True)
    return Outcome(
        window_s=out["elapsed_s"], attempted=len(out["verdict"]),
        failed=len(out["verdict"]) - ok, completed=ok,
        results_failed=len(out["verdict"]) - ok,
        results=out["results"], latency_s=out["latency_s"],
        queue_wait_s=out["queue_wait_s"], flush_s=out["flush_s"],
        extra={"launches": len(out["launches"])})


def sample(run, state: dict) -> list:
    """Requests compared with the reference, drawn from the seed among
    those finished: the first and last lane of launches drawn in turn,
    and the longest request, until ``check_sample`` are chosen; at
    least one of each time bucket; and every finished request whose
    verdict is not ``ok``."""
    server, lens = state["server"], state["lens"]
    launches = state["served"]["launches"]
    results = state["served"]["results"]
    want = run.traffic["check_sample"]
    rng = traffic_lib.rng_for(run.seed, "check")
    done = [i for i, r in enumerate(results) if r is not None]
    chosen = {max(done, key=lambda i: lens[i])} if done else set()
    for k in rng.permutation(len(launches)):
        if len(chosen) >= want:
            break
        ids = [i for i in launches[k] if results[i] is not None]
        if ids:
            chosen.update((ids[0], ids[-1]))
    buckets = {server.queue_signature(int(lens[i]))[2] for i in chosen}
    for i in rng.permutation(done):
        n_pad = server.queue_signature(int(lens[i]))[2]
        if n_pad not in buckets:
            chosen.add(int(i))
            buckets.add(n_pad)
    verdict = state["served"]["verdict"]
    chosen.update(i for i in done if verdict[i] not in ("ok", "retried"))
    return sorted(int(i) for i in chosen)
