"""§Roofline: three-term roofline per (arch × shape) from the dry-run.

Terms (seconds per step, TPU v5e constants):

    compute    = FLOPs_global     / (chips × 197e12 FLOP/s)
    memory     = HBM_bytes/device / 819e9 B/s          (per-device traffic)
    collective = coll_bytes/device / 50e9 B/s          (per-link ICI)

FLOPs and HBM bytes come from an analytic model of the *implementation as
lowered* (masked-full chunked attention, capacity-factor MoE, 1×-remat
training), because XLA's ``cost_analysis`` counts a ``while`` body once
regardless of trip count — the raw HLO numbers are recorded for reference
and the scan undercount is called out per cell.  Collective bytes use the
dry-run's trip-count-aware HLO parse.

MODEL_FLOPS uses the assignment's definition: 6·N·D (dense) / 6·N_active·D
(MoE) for training, 2·N·D for inference kinds; the ratio against the
analytic HLO-level FLOPs exposes remat/padding/capacity waste.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from repro.configs import arch_names, get_config
from repro.configs.base import ModelConfig, ShapeCfg

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # B/s per chip
LINK_BW = 50e9               # B/s per ICI link

__all__ = ["analytic_cell", "roofline_table", "run"]


def _sublayer_counts(cfg: ModelConfig) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for st in cfg.stages:
        for layer in st.period:
            for sub in layer:
                counts[sub] = counts.get(sub, 0) + st.n_periods
    return counts


def analytic_cell(cfg: ModelConfig, shape: ShapeCfg, chips: int = 256) -> Dict[str, Any]:
    """Global FLOPs + per-device HBM bytes for one cell, as implemented."""
    B, S = shape.global_batch, shape.seq_len
    D, Hd = cfg.d_model, cfg.head_dim
    H, KVH = cfg.n_heads, cfg.n_kv_heads
    F, V = cfg.d_ff, cfg.vocab_size
    counts = _sublayer_counts(cfg)
    kind = shape.kind
    decode = kind == "decode"
    T = B * (1 if decode else S)            # tokens processed this step
    Sctx = S                                 # cache/context length

    fl = 0.0
    # --- attention ---
    n_attn = counts.get("attn", 0) + counts.get("attn_local", 0)
    if n_attn:
        proj = 2 * T * D * Hd * (2 * H + 2 * KVH)
        if decode:
            sc = 4 * B * H * Hd * Sctx      # scores + pv over the cache
            sc_local = 4 * B * H * Hd * min(cfg.window or Sctx, Sctx)
            fl += counts.get("attn", 0) * (proj + sc) + counts.get("attn_local", 0) * (proj + sc_local)
        else:
            # chunked masked-full: all S×S pairs computed then masked
            sc = 4 * B * H * Hd * S * S
            fl += n_attn * (proj + sc)
    # --- dense mlp ---
    if counts.get("mlp"):
        fl += counts["mlp"] * 6 * T * D * F
    # --- moe ---
    if counts.get("moe"):
        slots = T * cfg.top_k * cfg.capacity_factor
        fl += counts["moe"] * (6 * slots * D * cfg.moe_d_ff + 2 * T * D * cfg.n_experts)
    # --- mamba ---
    if counts.get("mamba"):
        di = cfg.mamba_expand * D
        ds = cfg.mamba_d_state
        dr = max(1, D // 16)
        per = (
            2 * T * D * 2 * di + 2 * T * cfg.mamba_d_conv * di
            + 2 * T * di * (dr + 2 * ds) + 2 * T * dr * di
            + 8 * T * di * ds + 2 * T * di * D
        )
        fl += counts["mamba"] * per
    # --- xlstm ---
    if counts.get("mlstm"):
        chunk = min(128, max(S, 1))
        per = (
            2 * T * D * D * 3                 # qkv
            + 4 * T * H * Hd * (Hd + (1 if decode else chunk))
            + 2 * T * D * D                   # out proj
        )
        fl += counts["mlstm"] * per
    if counts.get("slstm"):
        fl += counts["slstm"] * (2 * T * D * 4 * D * 2 + 2 * T * D * D)
    # --- head / loss ---
    fl += 2 * T * D * V
    if kind == "train":
        fl *= 4.0                             # fwd + bwd(2×) + remat re-fwd

    # ----- HBM bytes per device -----
    pbytes = cfg.param_count() * 2            # bf16 params
    mom = 4 if cfg.opt_state_dtype == "fp32" else 2
    obytes = cfg.param_count() * 2 * mom
    L = cfg.n_layers
    act_elem_bytes = 2
    if kind == "train":
        weights_traffic = 4 * pbytes + 2 * obytes + 4 * cfg.param_count()  # +grads f32-ish
        act_traffic = 8 * T * D * L * act_elem_bytes
        hbm = (weights_traffic + act_traffic) / chips
    elif kind == "prefill":
        kv_write = 2 * T * KVH * Hd * n_attn * 2
        hbm = (pbytes + 4 * T * D * L * act_elem_bytes + kv_write) / chips
    else:  # decode
        kv_full = 2 * Sctx * B * KVH * Hd * counts.get("attn", 0) * 2
        kv_local = 2 * min(cfg.window or Sctx, Sctx) * B * KVH * Hd * counts.get("attn_local", 0) * 2
        state_bytes = 0
        if counts.get("mamba"):
            di = cfg.mamba_expand * D
            state_bytes += counts["mamba"] * B * di * cfg.mamba_d_state * 4 * 2
        if counts.get("mlstm"):
            state_bytes += counts["mlstm"] * B * H * Hd * Hd * 4 * 2
        hbm = (pbytes + kv_full + kv_local + state_bytes) / chips

    # MODEL_FLOPS per the assignment definition
    n_active = cfg.active_param_count()
    model_flops = (6 if kind == "train" else 2) * n_active * T
    return {
        "flops_global": fl,
        "hbm_bytes_per_device": hbm,
        "model_flops": model_flops,
        "tokens": T,
    }


def roofline_table(
    dryrun_json: str, *, chips: int = 256, mesh: str = "16x16"
) -> List[Dict[str, Any]]:
    with open(dryrun_json) as f:
        records = json.load(f)
    out = []
    for rec in records:
        if rec.get("mesh") != mesh:
            continue
        cfg = get_config(rec["arch"])
        shape = cfg.shape(rec["shape"])
        row: Dict[str, Any] = {
            "arch": rec["arch"],
            "shape": rec["shape"],
            "status": rec["status"],
        }
        if rec["status"] != "ok":
            row["reason"] = rec.get("reason", rec.get("error", ""))[:120]
            out.append(row)
            continue
        a = analytic_cell(cfg, shape, chips)
        coll_dev = rec["collectives"]["total_bytes"]
        t_compute = a["flops_global"] / (chips * PEAK_FLOPS)
        t_memory = a["hbm_bytes_per_device"] / HBM_BW
        t_coll = coll_dev / LINK_BW
        dominant = max(
            ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
            key=lambda kv: kv[1],
        )[0]
        bound = max(t_compute, t_memory, t_coll)
        row.update(
            compute_s=t_compute,
            memory_s=t_memory,
            collective_s=t_coll,
            dominant=dominant,
            model_flops=a["model_flops"],
            hlo_flops_analytic=a["flops_global"],
            useful_ratio=a["model_flops"] / max(a["flops_global"], 1),
            roofline_fraction=(a["model_flops"] / (chips * PEAK_FLOPS)) / max(bound, 1e-12),
            hlo_flops_raw_per_dev=rec.get("flops", -1),
            coll_bytes_per_dev=coll_dev,
            mem_temp_gb=rec["memory"]["temp_bytes"] / 1e9,
            mem_args_gb=rec["memory"]["argument_bytes"] / 1e9,
        )
        out.append(row)
    return out


def run(dryrun_json: Optional[str] = None):
    from .common import Row

    path = dryrun_json or os.environ.get("REPRO_DRYRUN_JSON", "results/dryrun_single.json")
    rows: List[Row] = []
    if not os.path.exists(path):
        rows.append(Row("roofline/missing", 0.0, f"no dry-run results at {path}"))
        return rows
    for cell in roofline_table(path):
        if cell["status"] != "ok":
            rows.append(Row(f"roofline/{cell['arch']}/{cell['shape']}", 0.0, cell["status"]))
            continue
        rows.append(
            Row(
                f"roofline/{cell['arch']}/{cell['shape']}",
                cell["compute_s"] * 1e6,
                f"mem_us={cell['memory_s']*1e6:.1f};coll_us={cell['collective_s']*1e6:.1f};"
                f"dominant={cell['dominant']};useful_ratio={cell['useful_ratio']:.2f};"
                f"roofline_frac={cell['roofline_fraction']:.3f}",
            )
        )
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r.csv())
