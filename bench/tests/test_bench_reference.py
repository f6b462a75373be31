"""The plain float64 reference against the program at tiny sizes: tracks
served through one padded time bucket under adaptive damping agree with
the reference run on the unpadded tracks."""
import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, program, traffic
from bench.reference.problem import load_problem
from bench.reference.smoother import make_mm

LENS = np.asarray([9, 13, 16, 11])   # one time bucket of 16 steps


def _config(name):
    with open(os.path.join(harness.BENCH_DIR, "configs", f"{name}.json")) \
            as fh:
        cfg = json.load(fh)
    cfg = copy.deepcopy(cfg)
    cfg["dtype"] = "float64"
    return cfg


@pytest.mark.parametrize("name", ["ct-ieks", "ct-ipls"])
def test_reference_matches_program_through_a_padded_bucket(name):
    cfg = _config(name)
    with jax.enable_x64(True):
        problem = load_problem(cfg["problem"], jnp.float64)
        ys = traffic.tracks(problem, LENS, seed=2 ** 31 + 7)
        server = program.build_server(cfg, {"max_batch": 4})
        stats = server.serve_requests(ys, emit=lambda *_: None)
        ref, conv, _ = check.reference_answers(cfg["problem"], cfg["spec"],
                                               ys)
    assert stats["launches"] == 1 and conv.sum() >= 2
    gaps = check.gaps(stats["results"], ref, LENS)[conv]
    # float64 on both sides; on the tracks the reference converges on,
    # what is left is the padded steps' damping pseudo-measurements and
    # the order of the parallel combines. (Where it has not converged
    # after ten passes, the padded and the unpadded iterates part by up
    # to 1e-3.)
    assert np.max(gaps) < 1e-6, gaps


@pytest.mark.parametrize("shape", [(5, 5, 5), (7, 5, 1), (2, 5, 5)])
def test_high_products_are_three_bfloat16_passes(shape):
    m, k, n = shape
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    high = np.asarray(make_mm("high")(a, b), np.float64)
    highest = np.asarray(make_mm("highest")(a, b), np.float64)
    scale = np.max(np.abs(exact))
    err_high = np.max(np.abs(high - exact)) / scale
    err_highest = np.max(np.abs(highest - exact)) / scale
    assert err_highest < 1e-6
    # The dropped lo*lo term and bfloat16 rounding of the lo parts leave
    # an error far above float32's, and far below a single bf16 pass.
    assert 1e-7 < err_high < 1e-4
