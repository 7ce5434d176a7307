"""Roofline assembly: three terms per (arch x shape x mesh) cell.

Terms (TPU v5e per chip: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI):

  compute    = dot_FLOPs_per_device / peak_FLOPs
  memory     = HBM_traffic_per_device / HBM_bw
  collective = collective_bytes_per_device / link_bw

``dot_FLOPs`` and ``collective_bytes`` come from the trip-count-corrected
HLO walk of the *compiled* partitioned module (launch/hlo_cost.py — XLA's
flat cost_analysis counts while bodies once, recorded raw alongside).
HBM traffic uses an explicit analytic model (weights / optimizer / KV-cache
/ activation streams; formulas below) because post-fusion byte attribution
is not recoverable from the HLO text.

Also reported per cell: MODEL_FLOPS = 6*N_active*D (train) or 2*N_active*D
(inference), the useful-compute ratio MODEL_FLOPS / (HLO dot FLOPs * chips),
the dominant term, and a one-line "what would move it" note.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from repro.configs import SHAPES, get_config, list_archs
from repro.configs.base import ModelConfig, ShapeSpec

PEAK_FLOPS = 197e12      # bf16 per chip
HBM_BW = 819e9           # B/s per chip
LINK_BW = 50e9           # B/s per ICI link

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


# ---------------------------------------------------------------------------
# analytic model inputs
# ---------------------------------------------------------------------------
def expert_params_per_layer(cfg: ModelConfig) -> int:
    if cfg.moe is None:
        return 0
    return 3 * cfg.d_model * cfg.moe.d_expert


def active_params(cfg: ModelConfig, total: int) -> int:
    """Parameters touched per token (MoE: top_k + shared experts only)."""
    if cfg.moe is None:
        return total
    per = expert_params_per_layer(cfg)
    inactive = (cfg.moe.n_experts - cfg.moe.top_k) * per * cfg.n_layers
    return total - inactive


def model_flops(cfg: ModelConfig, shape: ShapeSpec, n_active: int) -> float:
    """6*N*D for training, 2*N*D for inference (D = tokens this step)."""
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token


def kv_cache_bytes(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """Global KV/state cache bytes at full context."""
    b, s = shape.global_batch, shape.seq_len
    per_layer = 0.0
    for kind in cfg.block_pattern:
        if kind == "attn":
            per_layer += 2 * cfg.n_kv_heads * cfg.head_dim * s * 2.0
        elif kind == "local":
            w = min(cfg.window or s, s)
            per_layer += 2 * cfg.n_kv_heads * cfg.head_dim * w * 2.0
        elif kind == "mla":
            per_layer += (cfg.mla.kv_lora + cfg.mla.rope_dim) * s * 2.0
        elif kind == "rglru":
            per_layer += cfg.lru_dim * 4.0 + (cfg.conv_width - 1) * cfg.lru_dim * 4.0
        elif kind in ("mlstm", "slstm"):
            per_layer += cfg.n_heads * (cfg.head_dim ** 2 + 2 * cfg.head_dim) * 4.0
    n_per_pattern = cfg.n_layers / max(len(cfg.block_pattern), 1)
    return b * per_layer * n_per_pattern


def analytic_hbm_bytes(cfg: ModelConfig, shape: ShapeSpec, n_total: int,
                       n_active: int, n_dev: int,
                       weight_bytes_per_param: float = 2.0) -> float:
    """Per-device HBM traffic per step (documented napkin model).

    train:  weights read fwd+bwd+remat-recompute (3x) + grad write (4B)
            + AdamW m/v read+write (16B) + param write (2B)
            + activation stream ~12 x tokens x d_model x layers x 2B
    prefill: active weights read once + activation stream ~6x + cache write
    decode:  active weights read once (every step!) + full cache read
    """
    toks_dev = shape.global_batch * shape.seq_len / n_dev
    d, nl = cfg.d_model, cfg.n_layers
    if shape.kind == "train":
        p_dev = n_total / n_dev
        w = p_dev * (3 * weight_bytes_per_param + 4 + 16 + 2)
        acts = 12.0 * toks_dev * d * nl * 2.0
        return w + acts
    if shape.kind == "prefill":
        p_dev = n_active / n_dev  # inactive experts untouched per token-block
        acts = 6.0 * toks_dev * d * nl * 2.0
        cache = kv_cache_bytes(cfg, shape) / n_dev
        return p_dev * weight_bytes_per_param + acts + cache
    # decode
    p_dev = n_active / n_dev
    cache = kv_cache_bytes(cfg, shape) / n_dev
    return p_dev * weight_bytes_per_param + cache


# ---------------------------------------------------------------------------
# assembly
# ---------------------------------------------------------------------------
def _advice(dom: str, cfg: ModelConfig, shape: ShapeSpec) -> str:
    if dom == "collective":
        if cfg.moe is not None:
            return ("replicated-dispatch EP psums full activations every MoE "
                    "layer; switch combine to reduce-scatter + seq-sharding")
        return "shard more weights FSDP to turn all-reduces into reduce-scatters"
    if dom == "memory":
        if shape.kind == "decode":
            return ("weights re-read every token: int8/CSD frozen-weight "
                    "serving (paper technique) halves the stream")
        return "raise arithmetic intensity: bigger per-device batch or less remat"
    return "compute-bound: good; next win is overlap of FSDP gathers with matmuls"


def cell_report(rec: dict) -> dict | None:
    if rec.get("status") != "ok":
        return None
    cfg = get_config(rec["arch"])
    shape = SHAPES[rec["shape"]]
    n_dev = rec["n_devices"]
    n_total = rec["param_count"]
    n_act = active_params(cfg, n_total)

    flops_dev = rec["hlo_walk"]["dot_flops"] + rec["hlo_walk"]["conv_flops"]
    coll_dev = rec["hlo_walk"]["total_collective_bytes"]
    hbm_dev = analytic_hbm_bytes(cfg, shape, n_total, n_act, n_dev)

    t_c = flops_dev / PEAK_FLOPS
    t_m = hbm_dev / HBM_BW
    t_n = coll_dev / LINK_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_n}
    dom = max(terms, key=terms.get)
    mf = model_flops(cfg, shape, n_act)
    hlo_global = flops_dev * n_dev
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
        "dominant": dom,
        "model_flops": mf,
        "useful_ratio": mf / hlo_global if hlo_global else float("nan"),
        "step_s_bound": max(terms.values()),
        "roofline_frac": (terms["compute"] / max(terms.values())
                          if max(terms.values()) > 0 else 0.0),
        "peak_mem_gb": rec["memory_per_device"]["peak_bytes"] / 2**30,
        "advice": _advice(dom, cfg, shape),
    }


def load_all(mesh_dir: str = "pod16x16", variants: bool = False) -> list:
    out = []
    for p in sorted((RESULTS / mesh_dir).glob("*.json")):
        rec = json.loads(p.read_text())
        if bool(rec.get("variant")) != variants:
            continue
        out.append(rec)
    return out


def to_markdown(reports: list) -> str:
    hdr = ("| arch | shape | compute s | memory s | collective s | dominant "
           "| 6ND/HLO | roofline frac | mem GB/dev | next lever |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    rows = []
    for r in reports:
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.2e} "
            f"| {r['memory_s']:.2e} | {r['collective_s']:.2e} "
            f"| **{r['dominant']}** | {r['useful_ratio']:.2f} "
            f"| {r['roofline_frac']:.2f} | {r['peak_mem_gb']:.1f} "
            f"| {r['advice']} |")
    return hdr + "\n".join(rows)


def main():
    recs = load_all()
    reports = [r for r in (cell_report(x) for x in recs) if r]
    print(to_markdown(reports))
    out = RESULTS.parent / "roofline.md"
    out.write_text(to_markdown(reports) + "\n")
    print(f"\nwrote {out}")


if __name__ == "__main__":
    main()
