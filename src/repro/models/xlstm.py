"""xLSTM blocks: chunkwise-parallel mLSTM (matrix memory — runs on the
paper's scan primitive at chunk granularity) and sequential sLSTM (scalar
memory with recurrent gate mixing — *not* scan-parallelizable, per the
xLSTM paper; see DESIGN.md §4).

Implementation notes (documented deviations):
  * mLSTM gates are sigmoid-bounded (log-sigmoid forget in log space,
    sigmoid input) instead of the paper's exp input gate + stabilizer —
    this makes the chunked form stabilizer-free with identical structure
    (matrix memory C, normalizer n, per-head scalar gates).
  * sLSTM keeps exponential gating with the m_t stabilizer and
    block-diagonal recurrent weights, executed with `lax.scan`.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import normal_init, rms_norm, silu


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

class MLSTMCache(NamedTuple):
    C: jnp.ndarray  # [B, H, dh, dh] matrix memory
    n: jnp.ndarray  # [B, H, dh] normalizer
    conv: jnp.ndarray  # [B, K-1, din]


def init_mlstm(cfg: ModelConfig, key, dtype):
    d = cfg.d_model
    din = int(cfg.mlstm_proj_factor * d)
    H = cfg.num_heads
    K = cfg.ssm_conv
    ks = jax.random.split(key, 7)
    params = {
        "in_proj": normal_init(ks[0], (d, 2 * din), dtype),
        "conv_w": normal_init(ks[1], (K, din), dtype, scale=0.5),
        "wq": normal_init(ks[2], (din, din), dtype),
        "wk": normal_init(ks[3], (din, din), dtype),
        "wv": normal_init(ks[4], (din, din), dtype),
        "w_gates": normal_init(ks[5], (d, 2 * H), dtype),
        "norm_w": jnp.ones((din,), dtype),
        "out_proj": normal_init(ks[6], (din, d), dtype),
    }
    specs = {
        "in_proj": P(None, "model"),
        "conv_w": P(None, "model"),
        "wq": P(None, "model"), "wk": P(None, "model"),
        "wv": P(None, "model"),
        "w_gates": P(None, None),
        "norm_w": P("model"),
        "out_proj": P("model", None),
    }
    return params, specs


def _heads(x, H):
    B, T, D = x.shape
    return x.reshape(B, T, H, D // H).transpose(0, 2, 1, 3)  # [B,H,T,dh]


def _mlstm_chunked(q, k, v, lf, li, CT: int, state=None):
    """Chunkwise-parallel mLSTM attention.

    q/k/v [B, H, T, dh] (q pre-scaled); lf/li [B, H, T] log-forget and
    log-input gates (both <= 0). Returns (h [B,H,T,dh], (C, n) final).
    """
    B, H, T, dh = q.shape
    pad = (-T) % CT
    if pad:
        z4 = ((0, 0), (0, 0), (0, pad), (0, 0))
        z3 = ((0, 0), (0, 0), (0, pad))
        q, k, v = (jnp.pad(a, z4) for a in (q, k, v))
        # Padded steps: forget=1 (lf=0) keeps state; input=0 kills writes.
        lf = jnp.pad(lf, z3)
        li = jnp.pad(li, z3, constant_values=-1e30)
    nc = (T + pad) // CT
    qc = q.reshape(B, H, nc, CT, dh)
    kc = k.reshape(B, H, nc, CT, dh)
    vc = v.reshape(B, H, nc, CT, dh)
    lfc = lf.reshape(B, H, nc, CT).astype(jnp.float32)
    lic = li.reshape(B, H, nc, CT).astype(jnp.float32)

    Lf = jnp.cumsum(lfc, axis=-1)                       # [B,H,nc,CT]
    # Intra-chunk decay matrix D[t,s] = exp(Lf_t - Lf_s + li_s), s <= t.
    Ddec = Lf[..., :, None] - Lf[..., None, :] + lic[..., None, :]
    tri = jnp.tril(jnp.ones((CT, CT), bool))
    Ddec = jnp.where(tri, Ddec, -1e30)
    Dm = jnp.exp(Ddec)                                  # [B,H,nc,CT,CT]

    # Per-chunk writes to the running state (value at chunk end):
    wts = jnp.exp(Lf[..., -1:] - Lf + lic)              # [B,H,nc,CT]
    S = jnp.einsum("bhnt,bhntk,bhntv->bhnkv", wts, kc, vc)
    zn = jnp.einsum("bhnt,bhntk->bhnk", wts, kc)
    Ftot = jnp.exp(Lf[..., -1])                         # [B,H,nc]

    if state is None:
        C0 = jnp.zeros((B, H, dh, dh), jnp.float32)
        n0 = jnp.zeros((B, H, dh), jnp.float32)
    else:
        C0, n0 = state

    def body(carry, inp):
        C, n = carry
        f, Sc, zc = inp
        return ((f[..., None, None] * C + Sc, f[..., None] * n + zc),
                (C, n))  # emit the *pre*-chunk state

    (Cf, nf), (Cs, ns) = jax.lax.scan(
        body, (C0, n0),
        (jnp.moveaxis(Ftot, -1, 0), jnp.moveaxis(S, 2, 0),
         jnp.moveaxis(zn, 2, 0)))
    Cs = jnp.moveaxis(Cs, 0, 2)                         # [B,H,nc,dh,dh]
    ns = jnp.moveaxis(ns, 0, 2)                         # [B,H,nc,dh]

    scores = jnp.einsum("bhntd,bhnsd->bhnts", qc.astype(jnp.float32),
                        kc.astype(jnp.float32))
    intra = jnp.einsum("bhnts,bhnts,bhnsv->bhntv", Dm, scores,
                       vc.astype(jnp.float32))
    inter = jnp.exp(Lf)[..., None] * jnp.einsum(
        "bhnkv,bhntk->bhntv", Cs, qc.astype(jnp.float32))
    denom_intra = jnp.einsum("bhnts,bhnts->bhnt", Dm, scores)
    denom_inter = jnp.exp(Lf) * jnp.einsum("bhnk,bhntk->bhnt", ns,
                                           qc.astype(jnp.float32))
    denom = jnp.maximum(jnp.abs(denom_intra + denom_inter), 1.0)
    h = (intra + inter) / denom[..., None]
    h = h.reshape(B, H, nc * CT, dh)[:, :, :T]
    return h, (Cf, nf)


def _mlstm_chunk_aggregate(k, v, lf, li, CT: int):
    """Per-rank aggregate state contribution (zero-init): returns
    (Ftot [B,H], C_end [B,H,dh,dh], n_end [B,H,dh]) — the element of the
    cross-device state scan. Cheap: no [CT, CT] intra terms."""
    B, H, T, dh = k.shape
    pad = (-T) % CT
    if pad:
        z4 = ((0, 0), (0, 0), (0, pad), (0, 0))
        z3 = ((0, 0), (0, 0), (0, pad))
        k, v = jnp.pad(k, z4), jnp.pad(v, z4)
        lf = jnp.pad(lf, z3)
        li = jnp.pad(li, z3, constant_values=-1e30)
    nc = (T + pad) // CT
    kc = k.reshape(B, H, nc, CT, dh)
    vc = v.reshape(B, H, nc, CT, dh)
    lfc = lf.reshape(B, H, nc, CT).astype(jnp.float32)
    lic = li.reshape(B, H, nc, CT).astype(jnp.float32)
    Lf = jnp.cumsum(lfc, axis=-1)
    wts = jnp.exp(Lf[..., -1:] - Lf + lic)
    S = jnp.einsum("bhnt,bhntk,bhntv->bhnkv", wts, kc, vc)
    zn = jnp.einsum("bhnt,bhntk->bhnk", wts, kc)
    Lc = Lf[..., -1]                                    # [B,H,nc]
    total = jnp.sum(Lc, axis=-1)
    suffix = jnp.exp(total[..., None] - jnp.cumsum(Lc, axis=-1))
    C_end = jnp.einsum("bhn,bhnkv->bhkv", suffix, S)
    n_end = jnp.einsum("bhn,bhnk->bhk", suffix, zn)
    return jnp.exp(total), C_end, n_end


def _mlstm_state_combine(ei, ej):
    """Cross-rank composition of mLSTM state contributions — the paper's
    smoothing combine (Eq. 19) with per-head scalar E and matrix 'mean':
    (F, C, n)_i (x) (F, C, n)_j = (F_i F_j, F_j C_i + C_j, F_j n_i + n_j).
    """
    Fi, Ci, ni = ei
    Fj, Cj, nj = ej
    return (Fi * Fj, Fj[..., None, None] * Ci + Cj,
            Fj[..., None] * ni + nj)


def _mlstm_sp(q, k, v, lf, li, CT: int, mesh):
    """Sequence-parallel mLSTM: each 'model' rank runs the chunkwise form
    on its T/tp slice; the running (C, n) state crosses ranks via the
    cross-device exclusive scan from `repro.core.scan` — the cluster-level
    instance of the paper's associative-scan primitive (DESIGN.md §2;
    EXPERIMENTS.md §Perf, xlstm iteration 2)."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from repro.core.scan import device_exclusive_scan

    batch_ax = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    B, H, T, dh = q.shape

    def local_fn(q_l, k_l, v_l, lf_l, li_l):
        Ftot, C_end, n_end = _mlstm_chunk_aggregate(k_l, v_l, lf_l, li_l,
                                                    CT)
        ident = (jnp.ones_like(Ftot), jnp.zeros_like(C_end),
                 jnp.zeros_like(n_end))
        _, C_in, n_in = device_exclusive_scan(
            _mlstm_state_combine, (Ftot, C_end, n_end),
            axis_name="model", identity=ident)
        h, _ = _mlstm_chunked(q_l, k_l, v_l, lf_l, li_l, CT,
                              state=(C_in, n_in))
        return h

    spec4 = P(batch_ax, None, "model", None)
    spec3 = P(batch_ax, None, "model")
    return jax.shard_map(local_fn, mesh=mesh,
                     in_specs=(spec4, spec4, spec4, spec3, spec3),
                     out_specs=spec4, check_vma=False)(q, k, v, lf, li)


def mlstm_layer(params, x, cfg: ModelConfig, *,
                cache: Optional[MLSTMCache] = None
                ) -> Tuple[jnp.ndarray, Optional[MLSTMCache]]:
    """x [B, T, d] -> (y [B, T, d], cache)."""
    from repro.models.layers import _active_mesh
    from repro.models.ssm import _causal_conv  # shared depthwise conv
    B, T, d = x.shape
    H = cfg.num_heads
    din = int(cfg.mlstm_proj_factor * d)
    dh = din // H
    xz = x @ params["in_proj"]
    u, og = xz[..., :din], xz[..., din:]

    hist = cache.conv if cache is not None else None
    uc = silu(_causal_conv(u, params["conv_w"], history=hist))
    q = _heads(uc @ params["wq"], H) / (dh ** 0.5)
    k = _heads(uc @ params["wk"], H)
    v = _heads(u @ params["wv"], H)
    gates = (x @ params["w_gates"]).astype(jnp.float32)
    lf = jax.nn.log_sigmoid(gates[..., :H]).transpose(0, 2, 1)  # [B,H,T]
    li = jax.nn.log_sigmoid(gates[..., H:]).transpose(0, 2, 1)

    if cache is not None:
        # Single-step decode.
        f = jnp.exp(lf[..., 0])                         # [B,H]
        i = jnp.exp(li[..., 0])
        kv = jnp.einsum("bhk,bhv->bhkv", k[:, :, 0].astype(jnp.float32),
                        v[:, :, 0].astype(jnp.float32))
        C = f[..., None, None] * cache.C + i[..., None, None] * kv
        n = f[..., None] * cache.n + i[..., None] * k[:, :, 0]
        num = jnp.einsum("bhkv,bhk->bhv", C, q[:, :, 0].astype(jnp.float32))
        den = jnp.maximum(jnp.abs(jnp.einsum(
            "bhk,bhk->bh", n, q[:, :, 0].astype(jnp.float32))), 1.0)
        h = (num / den[..., None])[:, :, None, :]       # [B,H,1,dh]
        new_conv = jnp.concatenate([cache.conv, u], axis=1)[:, 1:]
        new_cache = MLSTMCache(C=C, n=n, conv=new_conv)
    else:
        mesh = _active_mesh()
        CT = min(cfg.scan_chunk, T)
        use_sp = (mesh is not None and "model" in mesh.axis_names
                  and mesh.shape["model"] > 1
                  and T % (mesh.shape["model"] * CT) == 0)
        if use_sp:
            h = _mlstm_sp(q, k, v, lf, li, CT, mesh)
        else:
            h, _ = _mlstm_chunked(q, k, v, lf, li, CT=CT)
        new_cache = None

    h = h.transpose(0, 2, 1, 3).reshape(B, -1, din).astype(x.dtype)
    h = rms_norm(h, params["norm_w"], cfg.rmsnorm_eps)
    y = (h * jax.nn.sigmoid(og.astype(jnp.float32)).astype(x.dtype)) \
        @ params["out_proj"]
    return y, new_cache


def init_mlstm_cache(cfg: ModelConfig, B: int, dtype) -> MLSTMCache:
    din = int(cfg.mlstm_proj_factor * cfg.d_model)
    dh = din // cfg.num_heads
    return MLSTMCache(
        C=jnp.zeros((B, cfg.num_heads, dh, dh), jnp.float32),
        n=jnp.zeros((B, cfg.num_heads, dh), jnp.float32),
        conv=jnp.zeros((B, cfg.ssm_conv - 1, din), dtype))


def mlstm_cache_spec(cfg: ModelConfig, batch_spec=("data",)):
    return MLSTMCache(C=P(batch_spec, None, "model", None),
                      n=P(batch_spec, None, "model"),
                      conv=P(batch_spec, None, "model"))


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

class SLSTMCache(NamedTuple):
    c: jnp.ndarray  # [B, d]
    n: jnp.ndarray  # [B, d]
    h: jnp.ndarray  # [B, d]
    m: jnp.ndarray  # [B, d] stabilizer


def init_slstm(cfg: ModelConfig, key, dtype):
    d = cfg.d_model
    H = cfg.num_heads
    dh = d // H
    ff = int(d * 4 / 3 / 64) * 64 or 64
    ks = jax.random.split(key, 5)
    params = {
        "w_in": normal_init(ks[0], (d, 4 * d), dtype),
        # Block-diagonal recurrent mixing: [H, dh, 4*dh].
        "r": normal_init(ks[1], (H, dh, 4 * dh), dtype),
        "b": jnp.zeros((4 * d,), dtype),
        "up": normal_init(ks[2], (d, 2 * ff), dtype),
        "down": normal_init(ks[3], (ff, d), dtype),
        "norm_w": jnp.ones((d,), dtype),
    }
    specs = {
        "w_in": P(None, "model"),
        "r": P(None, None, "model"),
        "b": P("model"),
        "up": P(None, "model"),
        "down": P("model", None),
        "norm_w": P(None),
    }
    return params, specs


def _slstm_step(params, carry, pre_x, H):
    """One sLSTM step. pre_x [B, 4d] is the input part; recurrent part is
    added here. Gate layout: [i | f | z | o]."""
    c, n, h, m = carry
    B, d = h.shape
    dh = d // H
    hr = h.reshape(B, H, dh)
    rec = jnp.einsum("bhk,hkj->bhj", hr,
                     params["r"].astype(jnp.float32))  # [B,H,4dh]
    rec = rec.reshape(B, H, 4, dh).transpose(0, 2, 1, 3).reshape(B, 4 * d)
    pre = pre_x + rec + params["b"].astype(jnp.float32)
    ig, fg, zg, og = jnp.split(pre, 4, axis=-1)
    # Stabilized exponential gating (xLSTM Eq. sLSTM).
    log_f = jax.nn.log_sigmoid(fg)
    m_new = jnp.maximum(log_f + m, ig)
    i = jnp.exp(ig - m_new)
    f = jnp.exp(log_f + m - m_new)
    z = jnp.tanh(zg)
    o = jax.nn.sigmoid(og)
    c_new = f * c + i * z
    n_new = jnp.maximum(f * n + i, 1e-6)
    h_new = o * (c_new / n_new)
    return (c_new, n_new, h_new, m_new), h_new


def slstm_layer(params, x, cfg: ModelConfig, *,
                cache: Optional[SLSTMCache] = None
                ) -> Tuple[jnp.ndarray, Optional[SLSTMCache]]:
    from repro.models.layers import maybe_shard
    B, T, d = x.shape
    H = cfg.num_heads
    pre = (x @ params["w_in"]).astype(jnp.float32)       # [B, T, 4d]
    # The sequential scan consumes one timestep per iteration: a T-sharded
    # (sequence-parallel) layout would force a per-step reshard — XLA sinks
    # a full-array transpose+copy INTO the 32k-step loop (observed: 64 MB
    # per step). Replicate once, scan locally (EXPERIMENTS.md §Perf,
    # xlstm iteration 1).
    pre = maybe_shard(pre, "batch", None, None)
    if cache is None:
        carry0 = tuple(jnp.zeros((B, d), jnp.float32) for _ in range(3)) \
            + (jnp.full((B, d), -1e30, jnp.float32),)
        carry, hs = jax.lax.scan(
            lambda ca, p: _slstm_step(params, ca, p, H),
            carry0, jnp.moveaxis(pre, 1, 0))
        h = jnp.moveaxis(hs, 0, 1).astype(x.dtype)       # [B, T, d]
        new_cache = None
    else:
        carry = (cache.c, cache.n, cache.h, cache.m)
        carry, h1 = _slstm_step(params, carry, pre[:, 0], H)
        h = h1[:, None, :].astype(x.dtype)
        new_cache = SLSTMCache(*carry)
    h = rms_norm(h, params["norm_w"], cfg.rmsnorm_eps)
    up = h @ params["up"]
    ff = up.shape[-1] // 2
    y = (jax.nn.gelu(up[..., :ff]) * up[..., ff:]) @ params["down"]
    return y, new_cache


def init_slstm_cache(cfg: ModelConfig, B: int, dtype) -> SLSTMCache:
    d = cfg.d_model
    z = jnp.zeros((B, d), jnp.float32)
    return SLSTMCache(c=z, n=z, h=z, m=jnp.full((B, d), -1e30, jnp.float32))


def slstm_cache_spec(cfg: ModelConfig, batch_spec=("data",)):
    return SLSTMCache(c=P(batch_spec, "model"), n=P(batch_spec, "model"),
                      h=P(batch_spec, "model"), m=P(batch_spec, "model"))
