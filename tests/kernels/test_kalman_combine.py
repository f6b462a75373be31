"""kalman_combine kernel vs pure-jnp oracle: shape/dtype sweeps in
interpret mode, plus use inside the full parallel smoother scan."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import FilteringElement, SmoothingElement
from repro.kernels.kalman_combine import ops, ref
from repro.kernels.kalman_combine.kalman_combine import (
    block_rows, filtering_combine_batched, smoothing_combine_batched,
    _gauss_jordan_inverse)


def _rand_filtering(rng, B, nx, dtype):
    psd = lambda: jnp.asarray(
        (lambda a: a @ np.swapaxes(a, -1, -2) / nx + 0.1 * np.eye(nx))(
            rng.standard_normal((B, nx, nx))), dtype)
    return FilteringElement(
        A=jnp.asarray(rng.standard_normal((B, nx, nx)) / np.sqrt(nx), dtype),
        b=jnp.asarray(rng.standard_normal((B, nx)), dtype),
        C=psd(), eta=jnp.asarray(rng.standard_normal((B, nx)), dtype),
        J=psd())


def _rand_smoothing(rng, B, nx, dtype):
    psd = jnp.asarray(
        (lambda a: a @ np.swapaxes(a, -1, -2) / nx + 0.1 * np.eye(nx))(
            rng.standard_normal((B, nx, nx))), dtype)
    return SmoothingElement(
        E=jnp.asarray(rng.standard_normal((B, nx, nx)) / np.sqrt(nx), dtype),
        g=jnp.asarray(rng.standard_normal((B, nx)), dtype),
        L=psd)


TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-5),
       jnp.float64: dict(rtol=1e-9, atol=1e-10)}


@pytest.mark.parametrize("B", [1, 7, 64, 513])
@pytest.mark.parametrize("nx", [1, 2, 5, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_filtering_combine_matches_oracle(B, nx, dtype):
    rng = np.random.default_rng(B * 100 + nx)
    ei = _rand_filtering(rng, B, nx, dtype)
    ej = _rand_filtering(rng, B, nx, dtype)
    got = filtering_combine_batched(ei, ej, tile=64, interpret=True)
    want = ref.filtering_combine_batched_ref(ei, ej)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   **TOL[dtype])
        assert g.dtype == w.dtype


@pytest.mark.parametrize("B", [1, 7, 64, 513])
@pytest.mark.parametrize("nx", [1, 3, 5, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_smoothing_combine_matches_oracle(B, nx, dtype):
    rng = np.random.default_rng(B * 100 + nx + 1)
    ei = _rand_smoothing(rng, B, nx, dtype)
    ej = _rand_smoothing(rng, B, nx, dtype)
    got = smoothing_combine_batched(ei, ej, tile=64, interpret=True)
    want = ref.smoothing_combine_batched_ref(ei, ej)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   **TOL[dtype])


@pytest.mark.parametrize("ragged", [False, True])
@pytest.mark.parametrize("nx", [1, 2, 4, 5, 8])
@pytest.mark.parametrize("kind", ["filtering", "smoothing"])
def test_combine_at_dispatch_tile_matches_oracle(kind, nx, ragged):
    """The kernels at the tile the dispatch picks for nx (`block_rows`,
    the one that compiles for the chip), over two grid steps, and over a
    batch that is not a multiple of the tile (padded last block)."""
    make, fn, oracle = {
        "filtering": (_rand_filtering, filtering_combine_batched,
                      ref.filtering_combine_batched_ref),
        "smoothing": (_rand_smoothing, smoothing_combine_batched,
                      ref.smoothing_combine_batched_ref),
    }[kind]
    B = 2 * block_rows(nx) + (3 if ragged else 0)
    rng = np.random.default_rng(B * 10 + nx)
    ei = make(rng, B, nx, jnp.float32)
    ej = make(rng, B, nx, jnp.float32)
    got = fn(ei, ej, interpret=True)
    want = oracle(ei, ej)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   **TOL[jnp.float32])


def test_block_rows_shrinks_with_nx():
    """Powers of two, at least one sublane tile (8), and never wider for
    a larger nx."""
    rows = [block_rows(nx) for nx in range(1, 17)]
    assert all(r >= 8 and r & (r - 1) == 0 for r in rows)
    assert rows == sorted(rows, reverse=True)


def test_compiled_kernel_refuses_float64():
    """Mosaic has no float64 lowering: the compiled path says so instead
    of failing inside the compiler."""
    e = _rand_smoothing(np.random.default_rng(0), 8, 2, jnp.float64)
    with pytest.raises(ValueError, match="float32 only"):
        smoothing_combine_batched(e, e, interpret=False)


@pytest.mark.parametrize("n", [1, 2, 4, 6, 10])
def test_gauss_jordan_inverse(n):
    rng = np.random.default_rng(n)
    a = rng.standard_normal((16, n, n))
    W = np.eye(n) + a @ np.swapaxes(a, -1, -2) / n  # I + PSD: safe, no pivot
    inv = _gauss_jordan_inverse(jnp.asarray(W))
    np.testing.assert_allclose(np.asarray(inv @ W),
                               np.broadcast_to(np.eye(n), W.shape),
                               rtol=1e-8, atol=1e-8)


def test_kernel_inside_full_scan():
    """combine_impl='pallas' through the whole parallel smoother must match
    the jnp scan end-to-end (this is the integration the framework uses)."""
    from repro.core import parallel_filter_smoother
    from tests.core.test_parallel_vs_sequential import random_linear_ssm
    lin, ys, m0, P0 = random_linear_ssm(jax.random.PRNGKey(5), 96, 5, 2)
    f_j, s_j = parallel_filter_smoother(lin, ys, m0, P0, combine_impl="jnp")
    f_p, s_p = parallel_filter_smoother(lin, ys, m0, P0,
                                        combine_impl="pallas")
    np.testing.assert_allclose(np.asarray(f_p.mean), np.asarray(f_j.mean),
                               rtol=1e-8, atol=1e-9)
    np.testing.assert_allclose(np.asarray(s_p.mean), np.asarray(s_j.mean),
                               rtol=1e-8, atol=1e-9)
    np.testing.assert_allclose(np.asarray(s_p.cov), np.asarray(s_j.cov),
                               rtol=1e-8, atol=1e-9)


def test_dispatch_helper():
    from repro.core.parallel import filtering_combine, smoothing_combine
    f_op = ops.batched_combine_for(filtering_combine, total_elems=64)
    s_op = ops.batched_combine_for(smoothing_combine, total_elems=64)
    assert f_op.func is ops.filtering_combine_op
    assert s_op.func is ops.smoothing_combine_op
    f = ops.batched_combine_for(lambda a, b: a)
    assert callable(f)


def test_select_impl_is_static():
    """The policy is a pure function of the call site's total element
    count and resolved backend — a Python int/str, never a traced value
    or per-level batch size."""
    # With a kernel backend: kernel above the threshold, ref below.
    assert ops.select_impl(None, backend="interpret") == "kernel"
    assert ops.select_impl(ops._MIN_KERNEL_BATCH,
                           backend="interpret") == "kernel"
    assert ops.select_impl(ops._MIN_KERNEL_BATCH - 1,
                           backend="interpret") == "ref"
    # No backend argument: the host platform's lowering decides. Where
    # none exists (CPU CI) the default is the fused twin at EVERY size —
    # never an interpret-mode kernel (the off-TPU dispatch bugfix).
    expect = "fused" if ops.kernel_backend() is None else "kernel"
    assert ops.select_impl(None) == expect
    assert ops.select_impl(10_000) == expect


def test_off_accelerator_pallas_falls_back_to_fused():
    """Forcing combine_impl="pallas" where only interpret mode exists
    must (a) warn once, (b) produce bit-identical outputs to the fused
    twin — the scan runs the *same* fused code, not a slow kernel."""
    import warnings

    from repro.core import associative_scan, filtering_combine

    if ops.kernel_backend() is not None:
        pytest.skip("host has a compiled kernel lowering")
    rng = np.random.default_rng(3)
    elems = _rand_filtering(rng, 32, 3, jnp.float64)
    ops._warned.discard("pallas-no-lowering")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out_p = associative_scan(filtering_combine, elems,
                                 combine_impl="pallas")
        out_p2 = associative_scan(filtering_combine, elems,
                                  combine_impl="pallas")
    msgs = [str(x.message) for x in w
            if "no compiled lowering" in str(x.message)]
    assert len(msgs) == 1, f"expected exactly one warning, got {msgs}"
    out_f = associative_scan(filtering_combine, elems,
                             combine_impl="fused")
    for a, b, c in zip(out_p, out_f, out_p2):
        assert bool(jnp.all(a == b)) and bool(jnp.all(a == c))


def test_wrong_platform_backend_degrades_with_warning():
    """backend="tpu"/"gpu" on a mismatched host raises, naming the
    missing device (a fused run would pass a CPU run off as that
    device's); "interpret" is honored; unknown names raise."""
    have = ops.kernel_backend()
    wrong = "tpu" if have != "tpu" else "gpu"
    with pytest.raises(RuntimeError, match=f"no {wrong} device"):
        ops.resolve_backend(wrong)
    assert ops.resolve_backend("interpret") == "interpret"
    if have is not None:
        assert ops.resolve_backend(have) == have
    with pytest.raises(ValueError):
        ops.resolve_backend("cuda")


@pytest.mark.parametrize("n,expect", [(32, "kernel"), (4, "ref")])
def test_dispatch_is_trace_stable_across_scan_levels(monkeypatch, n,
                                                     expect):
    """One scan = one implementation: with total elems >= threshold every
    Blelloch level runs the kernel, even levels whose pair count is below
    the threshold (and symmetrically for small scans). A per-level policy
    would flip paths mid-scan and retrace the kernel at each level."""
    from repro.core import associative_scan, filtering_combine

    counts = {"kernel": 0, "ref": 0}
    orig_k = ops._k.filtering_combine_batched
    orig_r = ops._ref.filtering_combine_batched_ref

    def count_k(ei, ej, **kw):
        counts["kernel"] += 1
        return orig_k(ei, ej, **kw)

    def count_r(ei, ej):
        if ei.b.shape[0] > 0:  # empty levels legitimately take the ref
            counts["ref"] += 1
        return orig_r(ei, ej)

    monkeypatch.setattr(ops._k, "filtering_combine_batched", count_k)
    monkeypatch.setattr(ops._ref, "filtering_combine_batched_ref", count_r)

    rng = np.random.default_rng(0)
    elems = _rand_filtering(rng, n, 3, jnp.float64)
    # "pallas:interpret" forces the kernel lowering so the dispatch-path
    # counters below see kernel-vs-ref choices even on CPU CI (plain
    # "pallas" correctly degrades to the fused twin off-accelerator).
    out = associative_scan(filtering_combine, elems,
                           combine_impl="pallas:interpret")
    jax.block_until_ready(out.b)
    other = "ref" if expect == "kernel" else "kernel"
    assert counts[expect] > 0
    assert counts[other] == 0, (
        f"dispatch flipped to {other} mid-scan: {counts}")


def test_autotune_verdict_is_keyed_by_dtype(monkeypatch):
    """A kernel verdict measured in float32 does not route a float64
    trace of the same shape to the kernel (float32-only lowering)."""
    from repro.kernels.kalman_combine import autotune as at

    monkeypatch.setattr(at, "_cache", {})
    at._cache[at.cache_key("spec", 4, 8, 2, jnp.float32)] = {
        "choice": at.CHOICE_KERNEL}
    assert at.decide("spec", 4, 8, 2, jnp.float32) == at.CHOICE_KERNEL
    assert at.decide("spec", 4, 8, 2, jnp.float64) == at.CHOICE_FUSED
    assert at.decide("spec", 4, 8, 2) == at.CHOICE_FUSED


def test_autotune_records_a_kernel_the_compiler_refuses(monkeypatch):
    """At a shape where the kernel does not compile (on a TPU: nx = 40,
    whose blocked inverse Mosaic cannot lower), set-up goes on: the entry
    says "fused", keeps the refusal, and a warning names it."""
    from repro.kernels.kalman_combine import autotune as at

    def refused(ei, ej):
        raise NotImplementedError("Unimplemented primitive in Pallas TPU "
                                  "lowering: dynamic_slice")

    monkeypatch.setattr(at, "_cache", {})
    monkeypatch.setattr(at._ops, "kernel_backend", lambda: "tpu")
    monkeypatch.setattr(at._ops, "batched_combine_for",
                        lambda *a, **k: refused)
    with pytest.warns(RuntimeWarning, match="does not compile at B=4, T=8, "
                      "nx=3.*dynamic_slice"):
        entry = at.autotune("spec", 4, 8, 3, jnp.float32)
    assert entry["choice"] == at.CHOICE_FUSED
    assert entry["kernel_us"] is None and entry["fused_us"] > 0
    assert entry["kernel_error"].startswith("NotImplementedError")
    assert at.decide("spec", 4, 8, 3, jnp.float32) == at.CHOICE_FUSED
