"""Cross-device sharded scan == single-device scan (8-device subprocess).

Verifies the cluster-level form of the paper's method: per-device Blelloch
scan + ppermute exchange must reproduce `jax.lax.associative_scan` exactly,
for both the filtering (prefix) and smoothing (suffix) combines, and for
the diagonal linear recurrence used by the SSM layers.
"""
import pytest

from tests._subproc import check_snippet

SNIPPET = r"""
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_enable_x64", True)
from functools import partial
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import (filtering_combine, filtering_identity,
                        smoothing_combine, smoothing_identity,
                        sharded_associative_scan, associative_scan,
                        linear_recurrence_scan)
from repro.core.types import FilteringElement, SmoothingElement

assert len(jax.devices()) == 8, jax.devices()
mesh = jax.make_mesh((8,), ("sp",))
n, nx = 64, 3
rng = np.random.default_rng(0)
psd = lambda: (lambda a: a @ np.swapaxes(a, -1, -2) / nx + 0.05 * np.eye(nx))(
    rng.standard_normal((n, nx, nx)))
fe = FilteringElement(
    A=jnp.asarray(rng.standard_normal((n, nx, nx)) / np.sqrt(nx)),
    b=jnp.asarray(rng.standard_normal((n, nx))),
    C=jnp.asarray(psd()),
    eta=jnp.asarray(rng.standard_normal((n, nx))),
    J=jnp.asarray(psd()))
se = SmoothingElement(
    E=jnp.asarray(rng.standard_normal((n, nx, nx)) / np.sqrt(nx)),
    g=jnp.asarray(rng.standard_normal((n, nx))),
    L=jnp.asarray(psd()))

spec_f = FilteringElement(A=P("sp"), b=P("sp"), C=P("sp"), eta=P("sp"), J=P("sp"))
spec_s = SmoothingElement(E=P("sp"), g=P("sp"), L=P("sp"))

@partial(jax.shard_map, mesh=mesh, in_specs=(spec_f,), out_specs=spec_f)
def sharded_prefix(e):
    return sharded_associative_scan(filtering_combine, e, axis_name="sp",
                                    identity=filtering_identity(nx, jnp.float64))

@partial(jax.shard_map, mesh=mesh, in_specs=(spec_s,), out_specs=spec_s)
def sharded_suffix(e):
    return sharded_associative_scan(smoothing_combine, e, axis_name="sp",
                                    identity=smoothing_identity(nx, jnp.float64),
                                    reverse=True)

ref_f = associative_scan(filtering_combine, fe)
got_f = jax.jit(sharded_prefix)(fe)
for r, g in zip(jax.tree_util.tree_leaves(ref_f), jax.tree_util.tree_leaves(got_f)):
    np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-8, atol=1e-9)

ref_s = associative_scan(smoothing_combine, se, reverse=True)
got_s = jax.jit(sharded_suffix)(se)
for r, g in zip(jax.tree_util.tree_leaves(ref_s), jax.tree_util.tree_leaves(got_s)):
    np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-8, atol=1e-9)

# Diagonal linear recurrence (SSM layer engine) across devices.
d = 16
a = jnp.asarray(rng.uniform(0.5, 1.0, (n, d)))
b = jnp.asarray(rng.standard_normal((n, d)))
ref_h = linear_recurrence_scan(a, b)

@partial(jax.shard_map, mesh=mesh, in_specs=(P("sp"), P("sp")), out_specs=P("sp"))
def sharded_rec(a, b):
    return linear_recurrence_scan(a, b, axis_name="sp")

got_h = jax.jit(sharded_rec)(a, b)
np.testing.assert_allclose(np.asarray(got_h), np.asarray(ref_h),
                           rtol=1e-10, atol=1e-10)

# Uneven work per device is impossible here (shard_map needs equal shards),
# but n=64 over 8 devices exercises multi-element shards; also check n=8
# (one element per device: pure cross-device path).
fe1 = jax.tree_util.tree_map(lambda x: x[:8], fe)
ref1 = associative_scan(filtering_combine, fe1)
got1 = jax.jit(sharded_prefix)(fe1)
for r, g in zip(jax.tree_util.tree_leaves(ref1), jax.tree_util.tree_leaves(got1)):
    np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-8, atol=1e-9)
print("SHARDED_SCAN_OK")
"""


@pytest.mark.subproc
def test_sharded_scan_matches_single_device():
    out = check_snippet(SNIPPET, n_devices=8)
    assert "SHARDED_SCAN_OK" in out
