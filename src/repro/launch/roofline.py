"""Roofline analysis (task spec deliverable (g)).

Three terms per (arch x shape x mesh), derived from the compiled dry-run
via `repro.launch.hlo_analysis` (exact per-chip FLOPs / HBM traffic /
collective bytes, with while-loop trip counts applied — see that module
for why raw ``cost_analysis()`` under-counts scanned models):

  compute_term    = FLOPs_per_chip / peak FLOP/s       [bf16 MXU peak]
  memory_term     = HBM_bytes_per_chip / HBM bytes/s   [HBM bandwidth]
  collective_term = collective bytes_per_chip / ICI    [per link]

with the peaks of the chip the step targets, from `CHIP_PEAKS`.

MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) for train; 2*N*D for
single forward (prefill); 2*N*B for one decode step. The ratio
MODEL_FLOPS / HLO_FLOPs shows how much compiled compute is "useful"
(catches remat/redundancy waste; remat'd train is expected ~0.7x, causal
block-skipping and padded-head waste show up here too).
"""
from __future__ import annotations

from typing import NamedTuple

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.hlo_analysis import analyze_hlo  # noqa: F401 (re-export)


class ChipPeaks(NamedTuple):
    flops: float     # bf16 FLOP/s per chip
    hbm_bw: float    # HBM bytes/s per chip
    ici_bw: float    # interconnect bytes/s per link


#: Published per-chip peaks, keyed by `jax.Device.device_kind`.
#: "TPU v5 lite" is the TPU v5e; source: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
#: chip-to-chip interconnect = 200 GB/s over 4 links).
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; a chip missing from `CHIP_PEAKS` is
    an error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                       ) from None


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def roofline_report(cfg: ModelConfig, shape: ShapeConfig, cell: dict,
                    device_kind: str) -> dict:
    """``cell`` carries per-chip 'flops', 'hbm_bytes', 'collective_bytes'
    from `analyze_hlo` plus 'chips'; ``device_kind`` names the chip the
    step targets (a key of `CHIP_PEAKS`)."""
    peaks = chip_peaks(device_kind)
    chips = cell["chips"]
    compute_term = cell["flops"] / peaks.flops
    memory_term = cell["hbm_bytes"] / peaks.hbm_bw
    collective_term = cell["collective_bytes"]["total"] / peaks.ici_bw
    terms = {"compute_s": compute_term, "memory_s": memory_term,
             "collective_s": collective_term}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    step_time = max(terms.values())
    # Roofline fraction: useful-FLOPs rate vs peak, if the step ran at the
    # dominant-term bound (the CPU-container stand-in for measured MFU).
    frac = (mf / chips / peaks.flops) / step_time if step_time > 0 else 0.0
    total_hlo_flops = cell["flops"] * chips
    return {
        **{k: float(f"{v:.6g}") for k, v in terms.items()},
        "dominant": dominant,
        "model_flops": float(f"{mf:.6g}"),
        "useful_flops_ratio": float(f"{(mf / total_hlo_flops):.4g}")
        if total_hlo_flops else 0.0,
        "roofline_fraction": float(f"{frac:.4g}"),
    }


def format_table(results: list) -> str:
    """EXPERIMENTS.md-ready markdown table."""
    hdr = ("| arch | shape | mesh | compute (s) | memory (s) | "
           "collective (s) | dominant | useful FLOPs | roofline frac |")
    sep = "|" + "---|" * 9
    rows = [hdr, sep]
    for r in results:
        if r["status"] == "skipped":
            rows.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                        f"— | — | — | skipped | — | — |")
            continue
        if r["status"] != "ok":
            rows.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                        f"FAILED | | | | | |")
            continue
        rl = r["roofline"]
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {rl['compute_s']:.3e} | {rl['memory_s']:.3e} "
            f"| {rl['collective_s']:.3e} | {rl['dominant'].split('_')[0]} "
            f"| {rl['useful_flops_ratio']:.3f} "
            f"| {rl['roofline_fraction']:.3f} |")
    return "\n".join(rows)
