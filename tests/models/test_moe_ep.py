"""Expert-parallel (shard_map) MoE must equal the global-dispatch path
bit-for-bit-ish under drop-free capacity (subprocess, 8-device mesh)."""
import pytest

from tests._subproc import check_snippet

SNIPPET = r"""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduced_config
from repro.launch.mesh import make_mesh
from repro.models.moe import init_moe, _moe_layer_global, moe_layer

cfg = dataclasses.replace(
    reduced_config(get_config("deepseek-moe-16b")),
    capacity_factor=2.0)   # E/k: drop-free -> paths must agree exactly
params, _ = init_moe(cfg, jax.random.PRNGKey(0), jnp.float32)
B, T = 4, 16
x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model),
                      jnp.float32)

ref, aux_ref = _moe_layer_global(params, x, cfg)

mesh = make_mesh((2, 4), ("data", "model"))
with mesh:
    got, aux_got = jax.jit(lambda p, xx: moe_layer(p, xx, cfg))(params, x)

np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
np.testing.assert_allclose(float(aux_got), float(aux_ref), rtol=1e-4)

# Gradients must flow through the EP path (a2a + scatter combine).
def loss(p):
    with mesh:
        out, aux = moe_layer(p, x, cfg)
    return jnp.sum(out ** 2) + aux

g = jax.grad(loss)(params)
gn = jnp.sqrt(sum(jnp.sum(v ** 2) for v in jax.tree_util.tree_leaves(g)))
assert jnp.isfinite(gn) and float(gn) > 0
print("MOE_EP_OK", float(gn))
"""


@pytest.mark.subproc
def test_ep_matches_global_dispatch():
    out = check_snippet(SNIPPET, n_devices=8, timeout=560)
    assert "MOE_EP_OK" in out
