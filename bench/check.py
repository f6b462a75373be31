"""Whether the timed path's answers are correct.

Once the window has closed, the requests a driver checks (in a fleet
cell, every track of its last job) are smoothed again by the plain
reference (`bench.reference.smoother`) in float64 on the host's CPU, each
on its *unpadded* measurements, and the program's answer for each is
compared with it. The numbers compared, each against its limit in
``bench/limits/<cell>.json``:

* ``missing``: requests due in the window with no answer, or with one
  that is not finite. Limit 0.
* ``failed_over_reference``: answers that the program gave up on (a
  verdict other than ``ok``) beyond the checked tracks on which the
  reference gives up too: the spec gives up on a track whose damping
  cap is exhausted, and so do both sides. Limit 0.
* ``compared_fewest``: the fewest tracks compared in any length class
  (the power of two at or above a track's length, which is also the
  program's time bucket), so that no class drops out of the check.
  At least 1.
* ``pos_gap_max``: the widest gap, over the compared tracks and their
  real steps ``0..n``, between the program's and the reference's
  smoothed position ``(p_x, p_y)``.
* ``pos_gap_median``: the median over the compared tracks of each
  track's widest position gap.

The compared tracks are the checked ones on which the reference's
Gauss-Newton iteration converged within the spec's passes. Where it has
not, the iterate after the last pass depends on every rounding on the
way: there the reference in float32 at precision ``highest`` parts from
itself in float64 about as far as the control does (PERF.md has the
readings), so no limit tells a sound run from the control. Their widest
gap is printed, and not judged.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path

import numpy as np

LIMITS_DIR = Path(__file__).resolve().parent / "limits"
POS = slice(0, 2)


def load_limits(cell: str) -> dict:
    with open(LIMITS_DIR / f"{cell}.json") as fh:
        return json.load(fh)


def length_class(n: int) -> int:
    """The power of two at or above ``n``."""
    return 1 << (int(n) - 1).bit_length()


@functools.lru_cache(maxsize=None)
def _reference(problem_json: str, spec_json: str, dtype: str, matmul: str):
    """The jitted reference for one configuration, precision and dtype
    (built once per process)."""
    import jax
    import jax.numpy as jnp

    from bench.reference.problem import load_problem
    from bench.reference.smoother import smooth_tracks

    problem = load_problem(json.loads(problem_json), jnp.dtype(dtype))
    spec = json.loads(spec_json)
    return problem, jax.jit(lambda ys, ns: smooth_tracks(
        problem, ys, ns, spec, matmul=matmul))


def reference_answers(problem_cfg: dict, spec: dict, ys: list,
                      dtype="float64", matmul="highest", device=None):
    """The reference's smoothed means (one ``[n_i + 1, nx]`` array per
    track of ``ys``), whether it converged on each, and whether it gave
    up on each, run per length class on ``device`` (the host CPU by
    default)."""
    import jax

    device = device or jax.devices("cpu")[0]
    classes: dict = {}
    for i, y in enumerate(ys):
        classes.setdefault(length_class(len(y)), []).append(i)
    means, converged = [None] * len(ys), np.zeros(len(ys), bool)
    gave_up = np.zeros(len(ys), bool)
    with jax.enable_x64(dtype == "float64"), jax.default_device(device):
        problem, run = _reference(json.dumps(problem_cfg, sort_keys=True),
                                  json.dumps(spec, sort_keys=True), dtype,
                                  matmul)
        for T, idx in sorted(classes.items()):
            pad = np.zeros((len(idx), T, problem.ny), dtype)
            for k, i in enumerate(idx):
                pad[k, :len(ys[i])] = ys[i]
            ns = np.asarray([len(ys[i]) for i in idx], np.int32)
            res = run(jax.device_put(pad, device),
                      jax.device_put(ns, device))
            mean = np.asarray(res.mean)
            div = np.asarray(res.diverged)
            ok = np.asarray(res.converged) & ~div
            for k, i in enumerate(idx):
                means[i] = mean[k, :len(ys[i]) + 1]
                converged[i], gave_up[i] = ok[k], div[k]
    return means, converged, gave_up


def gaps(answers: list, ref_means: list, lens) -> np.ndarray:
    """Each track's widest position gap over its real steps."""
    return np.asarray([
        float(np.max(np.abs(np.asarray(a, np.float64)[:n + 1, POS]
                            - np.asarray(r, np.float64)[:n + 1, POS])))
        for a, r, n in zip(answers, ref_means, lens)])


def numbers(answers: list, ref_means: list, converged: np.ndarray, lens,
            missing: int, failed_over_reference: int) -> dict:
    g = gaps(answers, ref_means, lens)[converged]
    per_class: dict = {}
    for n, c in zip(lens, converged):
        per_class[length_class(n)] = (per_class.get(length_class(n), 0)
                                      + int(c))
    return {
        "missing": int(missing),
        "failed_over_reference": int(failed_over_reference),
        "compared_fewest": min(per_class.values()) if per_class else 0,
        "pos_gap_max": float(np.max(g)) if len(g) else float("nan"),
        "pos_gap_median": float(np.median(g)) if len(g) else float("nan"),
    }


def report(answers: list, ref_means: list, converged: np.ndarray,
           lens) -> str:
    """Per length class: tracks checked and compared, and the first and
    last compared position in request order (in a fleet job, the lanes
    of the class's first launch); then the widest gap on the tracks not
    compared."""
    g = gaps(answers, ref_means, lens)
    parts = []
    for T in sorted({length_class(n) for n in lens}):
        conv = [c for c, n in zip(converged, lens) if length_class(n) == T]
        hit = [p for p, c in enumerate(conv) if c]
        ends = f", positions {hit[0]} to {hit[-1]}" if hit else ""
        parts.append(f"class {T}: {len(hit)} of {len(conv)} compared{ends}")
    rest = g[~converged]
    tail = (f"; widest gap where the reference did not converge "
            f"{float(np.max(rest))!r} over {len(rest)} tracks (not judged)"
            if len(rest) else "")
    return "; ".join(parts) + tail


def judge(values: dict, limits: dict) -> tuple:
    """``(correct, checks)``: every number against its limit (``max``:
    at most; ``min``: at least). A number that is not finite fails."""
    checks, ok = {}, True
    for name, value in values.items():
        lim = limits[name]
        if "max" in lim:
            good = bool(np.isfinite(value) and value <= lim["max"])
            checks[name] = {"value": value, "max": lim["max"]}
        else:
            good = bool(np.isfinite(value) and value >= lim["min"])
            checks[name] = {"value": value, "min": lim["min"]}
        ok &= good
    return ok, checks


def check(run, state: dict, idx: list) -> tuple:
    """Compare the answers of the window's requests ``idx`` (the
    driver's sample); ``(correct, checks)``."""
    results = run.outcome.results
    missing = sum(r is None or not np.all(np.isfinite(r)) for r in results)
    ys = [state["ys"][i] for i in idx]
    lens = [len(y) for y in ys]
    answers = [results[i] if results[i] is not None
               else np.full((n + 1, run.problem.nx), np.nan)
               for i, n in zip(idx, lens)]
    ref, conv, gave_up = reference_answers(run.config["problem"],
                                           run.config["spec"], ys)
    failed_over = max(0, run.outcome.results_failed - int(gave_up.sum()))
    values = numbers(answers, ref, conv, lens, missing, failed_over)
    print(f"[check] {len(idx)} tracks checked against the reference: "
          f"{report(answers, ref, conv, lens)}; the program gave up on "
          f"{run.outcome.results_failed}, the reference on "
          f"{int(gave_up.sum())}", flush=True)
    return judge(values, load_limits(run.cell))
