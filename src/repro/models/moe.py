"""Mixture-of-Experts layer: top-k routing with sort-based capacity
dispatch (no [T, E, C] one-hot tensors — the dispatch is a static-shape
scatter/gather, which is what keeps 1M-token batches lowerable), shared
experts (DeepSeek-MoE), and an auxiliary load-balancing loss.

Expert parallelism (DESIGN.md §6): expert-stacked weights ``[E, ...]``
shard E over 'model' when divisible (deepseek: 64/16); otherwise experts
are replicated across 'model' and the per-expert FFN dim is sharded
(grok: 8 experts, d_ff 32768/16) with weights additionally sharded over
'data' (FSDP-style) for memory.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import normal_init, silu


def init_moe(cfg: ModelConfig, key, dtype):
    d = cfg.d_model
    dff = cfg.d_ff_per_expert
    E = cfg.num_experts
    ks = jax.random.split(key, 5)
    shard_experts = E % cfg.tp_size == 0
    if shard_experts:
        e_spec, f_spec, d2 = "model", None, None
    else:
        e_spec, f_spec, d2 = None, "model", "data"
    params = {
        "router": normal_init(ks[0], (d, E), dtype),
        "w_gate": normal_init(ks[1], (E, d, dff), dtype),
        "w_up": normal_init(ks[2], (E, d, dff), dtype),
        "w_down": normal_init(ks[3], (E, dff, d), dtype),
    }
    specs = {
        "router": P(None, None),
        "w_gate": P(e_spec, d2, f_spec),
        "w_up": P(e_spec, d2, f_spec),
        "w_down": P(e_spec, f_spec, d2),
    }
    if cfg.num_shared_experts:
        dsh = dff * cfg.num_shared_experts
        kss = jax.random.split(ks[4], 3)
        params["shared"] = {
            "w_gate": normal_init(kss[0], (d, dsh), dtype),
            "w_up": normal_init(kss[1], (d, dsh), dtype),
            "w_down": normal_init(kss[2], (dsh, d), dtype),
        }
        specs["shared"] = {
            "w_gate": P(None, "model"),
            "w_up": P(None, "model"),
            "w_down": P("model", None),
        }
    return params, specs


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    per = n_tokens * cfg.num_experts_per_tok / cfg.num_experts
    cap = int(per * cfg.capacity_factor) + 1
    return max(4, ((cap + 3) // 4) * 4)


def moe_layer(params, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [B, T, d] -> (out [B, T, d], aux load-balance loss scalar).

    Dispatch selection: under an active mesh with experts divisible by the
    model axis (and T shardable), the shard_map expert-parallel path runs —
    local per-shard routing + all_to_all to expert owners + local combine.
    The global (pure-GSPMD) path below is the fallback for CPU tests,
    decode (T == 1) and expert-replicated archs (grok); its token-sorted
    gathers are *global*, which GSPMD can only replicate — the EP path
    exists precisely because that costs TBs/chip at 1M-token batches
    (EXPERIMENTS.md §Perf, deepseek hillclimb)."""
    from repro.models.layers import _active_mesh
    mesh = _active_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        tp = mesh.shape["model"]
        if (tp > 1 and cfg.num_experts % tp == 0
                and x.shape[1] % tp == 0):
            return _moe_layer_ep(params, x, cfg, mesh)
    return _moe_layer_global(params, x, cfg)


def _moe_layer_global(params, x: jnp.ndarray, cfg: ModelConfig
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, T, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    xt = x.reshape(B * T, d)
    n = B * T

    logits = (xt @ params["router"]).astype(jnp.float32)   # [n, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)         # [n, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # Aux loss (Switch-style): mean prob mass vs. token fraction per expert.
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)

    # ---- sort-based capacity dispatch ----
    C = _capacity(n, cfg)
    flat_e = expert_ids.reshape(-1)                         # [n*k]
    flat_tok = jnp.repeat(jnp.arange(n), k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e)                             # stable
    e_sorted = flat_e[order]
    tok_sorted = flat_tok[order]
    gate_sorted = flat_gate[order]
    counts = jnp.bincount(flat_e, length=E)                 # [E]
    starts = jnp.cumsum(counts) - counts                    # exclusive
    pos_in_e = jnp.arange(n * k) - starts[e_sorted]         # rank in expert
    keep = pos_in_e < C                                     # capacity drop
    slot = jnp.where(keep, e_sorted * C + pos_in_e, E * C)  # overflow slot

    buf = jnp.zeros((E * C + 1, d), x.dtype)
    buf = buf.at[slot].set(xt[tok_sorted].astype(x.dtype))
    buf = buf[:-1].reshape(E, C, d)

    # ---- expert FFN (batched over E) ----
    h = silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h, params["w_down"])     # [E, C, d]

    # ---- combine ----
    y_flat = y.reshape(E * C, d)
    gathered = y_flat[jnp.where(keep, slot, 0)]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    out = jnp.zeros((n, d), jnp.float32)
    out = out.at[tok_sorted].add(
        gathered.astype(jnp.float32) * gate_sorted[:, None])
    out = out.astype(x.dtype)

    if cfg.num_shared_experts:
        sh = params["shared"]
        out = out + (silu(xt @ sh["w_gate"]) * (xt @ sh["w_up"])) \
            @ sh["w_down"]
    return out.reshape(B, T, d), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Expert-parallel (shard_map) dispatch — EXPERIMENTS.md §Perf
# ---------------------------------------------------------------------------

def _route_local(xt, router, cfg: ModelConfig):
    """Local routing + sort-based bucketing for a per-shard token slice.
    Returns (buf [E, C, d], combine metadata, aux parts)."""
    n, d = xt.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    logits = (xt @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E,
                                 dtype=jnp.float32), axis=0)
    C = _capacity(n, cfg)
    flat_e = expert_ids.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(n), k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e)
    e_sorted = flat_e[order]
    tok_sorted = flat_tok[order]
    gate_sorted = flat_gate[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(n * k) - starts[e_sorted]
    keep = pos_in_e < C
    slot = jnp.where(keep, e_sorted * C + pos_in_e, E * C)
    buf = jnp.zeros((E * C + 1, d), xt.dtype)
    buf = buf.at[slot].set(xt[tok_sorted].astype(xt.dtype))
    buf = buf[:-1].reshape(E, C, d)
    return buf, (keep, slot, tok_sorted, gate_sorted, C), (me, ce)


def _moe_layer_ep(params, x: jnp.ndarray, cfg: ModelConfig, mesh
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert parallelism via shard_map: tokens stay shard-local through
    routing/sort; only capacity-bucket payloads cross the wire (one
    all_to_all each way over 'model'), and expert FLOPs shard over
    data x model. Replaces the global path's replicated token-sorted
    gathers (TBs/chip) with ~n_loc*k*d bucket traffic."""
    from jax.sharding import PartitionSpec as P

    E, k = cfg.num_experts, cfg.num_experts_per_tok
    tp = mesh.shape["model"]
    E_l = E // tp
    batch_ax = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    mean_axes = tuple(a for a in mesh.axis_names)

    def local_fn(xl, router, wg, wu, wd, shared):
        B_l, T_l, d = xl.shape
        xt = xl.reshape(B_l * T_l, d)
        buf, meta, (me, ce) = _route_local(xt, router, cfg)
        keep, slot, tok_sorted, gate_sorted, C = meta
        aux = E * jnp.sum(jax.lax.pmean(me, mean_axes)
                          * jax.lax.pmean(ce, mean_axes))

        # To expert owners: [E, C, d] -> [tp, E_l, C, d] -a2a-> same shape
        # where leading index p now holds *rank p's* tokens for my E_l
        # experts.
        send = buf.reshape(tp, E_l, C, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        toks = recv.reshape(tp, E_l, C, d).transpose(1, 0, 2, 3) \
            .reshape(E_l, tp * C, d)
        h = silu(jnp.einsum("ecd,edf->ecf", toks, wg)) * \
            jnp.einsum("ecd,edf->ecf", toks, wu)
        y = jnp.einsum("ecf,efd->ecd", h, wd)          # [E_l, tp*C, d]
        back = y.reshape(E_l, tp, C, d).transpose(1, 0, 2, 3)
        mine = jax.lax.all_to_all(back, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        y_flat = jnp.concatenate(
            [mine.reshape(E * C, d), jnp.zeros((1, d), mine.dtype)],
            axis=0)

        # Gather-based combine: invert the sort permutation so each token
        # reads its k expert outputs directly — no f32 scatter-add buffer
        # (EXPERIMENTS.md §Perf, deepseek iteration 4).
        n = B_l * T_l
        k = cfg.num_experts_per_tok
        inv = jnp.argsort(tok_sorted * (n * k) + jnp.arange(n * k))
        slot_pertok = jnp.where(keep, slot, E * C)[inv].reshape(n, k)
        gate_pertok = gate_sorted[inv].reshape(n, k)
        picked = y_flat[slot_pertok]                   # [n, k, d]
        out = jnp.einsum("nk,nkd->nd", gate_pertok.astype(jnp.float32),
                         picked.astype(jnp.float32))
        out = out.astype(xl.dtype)

        if shared is not None:
            # Shared experts with the explicit sequence-parallel pattern:
            # all-gather the T/tp token slice over 'model', run the
            # TP-sharded FFN, reduce-scatter the dsh-partial outputs back
            # to the local slice. Replaces the full-T f32 all-reduce GSPMD
            # emits when this runs outside the shard (EXPERIMENTS.md
            # §Perf, deepseek iteration 3).
            sg, su, sd = shared
            xg = jax.lax.all_gather(xt, "model", axis=0, tiled=True)
            hsh = silu(xg @ sg) * (xg @ su)
            part = hsh @ sd                      # partial over dsh shards
            out = out + jax.lax.psum_scatter(part, "model",
                                             scatter_dimension=0,
                                             tiled=True)
        return out.reshape(B_l, T_l, d), aux

    shared_in = None
    shared_specs = None
    if cfg.num_shared_experts:
        sh = params["shared"]
        shared_in = (sh["w_gate"], sh["w_up"], sh["w_down"])
        shared_specs = (P(None, "model"), P(None, "model"),
                        P("model", None))

    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(batch_ax, "model", None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None), shared_specs),
        out_specs=(P(batch_ax, "model", None), P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"], shared_in)
    return out, aux.astype(jnp.float32)
