"""Sequence-parallel mLSTM (shard_map + cross-device state scan) must
match the single-device chunkwise form, and gradients must flow
(subprocess, 8 devices)."""
import pytest

from tests._subproc import check_snippet

SNIPPET = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduced_config
from repro.launch.mesh import make_mesh
from repro.models.xlstm import init_mlstm, mlstm_layer

cfg = reduced_config(get_config("xlstm-350m"))
params, _ = init_mlstm(cfg, jax.random.PRNGKey(0), jnp.float32)
B, T = 2, 128   # T = tp(4) * CT(32) ok
x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model),
                      jnp.float32)

ref, _ = mlstm_layer(params, x, cfg)          # no mesh: chunked form

mesh = make_mesh((2, 4), ("data", "model"))
with mesh:
    got, _ = jax.jit(lambda p, xx: mlstm_layer(p, xx, cfg)[0])(params, x), None

np.testing.assert_allclose(np.asarray(got[0] if isinstance(got, tuple)
                                      else got),
                           np.asarray(ref), rtol=2e-4, atol=2e-4)

def loss(p):
    with mesh:
        y, _ = mlstm_layer(p, x, cfg)
    return jnp.sum(y ** 2)

g = jax.grad(loss)(params)
gn = jnp.sqrt(sum(jnp.sum(v ** 2) for v in jax.tree_util.tree_leaves(g)))
assert jnp.isfinite(gn) and float(gn) > 0, gn
print("MLSTM_SP_OK", float(gn))
"""


@pytest.mark.subproc
def test_sequence_parallel_mlstm_matches_chunked():
    out = check_snippet(SNIPPET, n_devices=8, timeout=560)
    assert "MLSTM_SP_OK" in out
