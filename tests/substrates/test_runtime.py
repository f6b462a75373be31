"""Fault-tolerance runtime tests: watchdog, preemption handler, retries,
elastic resharding + compressed cross-pod psum (subprocess)."""
import os
import signal

import pytest

from repro.runtime import (PreemptionHandler, StepWatchdog, with_retries)
from tests._subproc import check_snippet


def test_watchdog_flags_straggler():
    wd = StepWatchdog(threshold=2.0, warmup_steps=1)
    for i in range(6):
        assert wd.observe(i, 1.0) is None
    rep = wd.observe(6, 3.5)
    assert rep is not None and rep.ratio > 2.0
    # Outlier must not pollute the EMA: the next normal step is fine.
    assert wd.observe(7, 1.0) is None
    assert len(wd.reports) == 1


def test_watchdog_adapts_to_slow_drift():
    wd = StepWatchdog(threshold=2.0, warmup_steps=1, ema_decay=0.5)
    for i, d in enumerate([1.0, 1.2, 1.4, 1.7, 2.0, 2.4]):
        assert wd.observe(i, d) is None  # gradual drift is not a straggler


def test_preemption_handler():
    h = PreemptionHandler(signals=(signal.SIGUSR1,)).install()
    try:
        assert not h.preemption_requested
        os.kill(os.getpid(), signal.SIGUSR1)
        assert h.preemption_requested
    finally:
        h.uninstall()


def test_with_retries_recovers_then_raises():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert with_retries(flaky, max_retries=2)() == "ok"

    def always_fails():
        raise RuntimeError("persistent")

    with pytest.raises(RuntimeError):
        with_retries(always_fails, max_retries=1)()


COMPRESSED_PSUM_SNIPPET = r"""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.optim import compressed_psum, init_compression

mesh = make_mesh((8,), ("pod",))
g = jnp.arange(8 * 32, dtype=jnp.float32).reshape(8, 32) / 17.0
state = init_compression({"g": g[0]})

@partial(jax.shard_map, mesh=mesh, in_specs=(P("pod", None),),
         out_specs=P("pod", None))
def reduce_grads(gs):
    out, _ = compressed_psum({"g": gs[0]}, state, "pod")
    return out["g"][None]

got = jax.jit(reduce_grads)(g)
want = jnp.sum(g, axis=0)
# int8 quantization: agreement within ~1% of max magnitude.
np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                           atol=0.02 * float(jnp.max(jnp.abs(want))))
print("PSUM_OK")
"""


@pytest.mark.subproc
def test_compressed_psum_across_devices():
    out = check_snippet(COMPRESSED_PSUM_SNIPPET, n_devices=8)
    assert "PSUM_OK" in out
