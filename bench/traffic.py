"""The one traffic generator: lengths, arrival times and tracks from a seed.

A traffic mix is a data file, ``bench/traffic/<name>.json``; this module
reads its parameters, and the driver named by its ``kind`` serves it.

Every seed gets the same *set* of lengths and of gaps between arrivals,
in another order: lengths are the ``(i + 1/2) / N`` quantiles of their
distribution and gaps the quantiles of the exponential distribution,
each shuffled by the seed. So the work a run offers does not change with
the seed; what the seed changes is which track is long, when each one
arrives, and every measurement (the noise of each simulated track).
"""
from __future__ import annotations

import functools
import json
import math
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as fh:
        return json.load(fh)


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per use (``stream``) of one seed; any
    non-negative whole number is a seed."""
    return np.random.default_rng([int(seed), *stream.encode()])


def _quantiles(count: int) -> np.ndarray:
    return (np.arange(count) + 0.5) / count


def lengths(traffic: dict, count: int, seed: int) -> np.ndarray:
    """``count`` track lengths, shuffled by the seed: the listed
    ``lengths`` in equal shares, or else log-uniform on ``[length_min,
    length_max]`` (whole steps)."""
    if "lengths" in traffic:
        out = np.resize(np.asarray(traffic["lengths"], np.int64), count)
        return rng_for(seed, "lengths").permutation(out)
    lo, hi = traffic["length_min"], traffic["length_max"]
    u = _quantiles(count)
    out = np.floor(np.exp(math.log(lo) + u * (math.log(hi + 1)
                                              - math.log(lo))))
    out = np.clip(out, lo, hi).astype(np.int64)
    return rng_for(seed, "lengths").permutation(out)


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Send times in ``[0, seconds)`` at ``rate`` requests per second:
    bursts of ``burst`` requests (1: a Poisson stream) whose starts are
    exponential gaps of mean ``burst / rate``, drawn as quantiles and
    shuffled by the seed."""
    rate, burst = float(traffic["rate"]), int(traffic.get("burst", 1))
    n_bursts = max(1, int(round(seconds * rate / burst)))
    gaps = -np.log1p(-_quantiles(n_bursts)) * burst / rate
    gaps = rng_for(seed, "arrivals").permutation(gaps)
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    starts *= seconds / (starts[-1] + gaps[-1])   # the last gap ends the window
    return np.repeat(starts, burst)


def tracks(problem, lens: np.ndarray, seed: int):
    """Simulate one track per length on the default device, in one
    jitted call at the longest length, and cut each to its own. Returns
    the measurement sequences (host arrays ``[n_i, ny]``)."""
    import jax

    from bench.reference.problem import simulate

    sim = functools.partial(simulate, problem, int(lens.max()))

    @jax.jit
    def run(keys):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(sim)(keys)

    key = jax.random.PRNGKey(int(rng_for(seed, "tracks").integers(2 ** 31)))
    _, ys = run(jax.random.split(key, len(lens)))
    ys = np.asarray(ys)
    return [ys[i, :n] for i, n in enumerate(lens)]
