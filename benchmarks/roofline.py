"""§Roofline: three-term roofline per (arch x shape x mesh) from the dry-run
artifacts (results/dryrun/*.json — loop-aware HLO flops/bytes/collectives).

  compute    = HLO_FLOPs_per_device / peak_FLOP/s          (197 TF bf16)
  memory     = HLO_bytes_per_device / HBM_bw               (819 GB/s)
  collective = effective ICI bytes per device / link bw    (~50 GB/s)

Effective collective bytes apply the standard ring factors on the result
size r over a group of g participants:
  all-gather (g-1)/g * r, all-reduce 2(g-1)/g * r, reduce-scatter (g-1)/g * r,
  all-to-all (g-1)/g * r, collective-permute r.
Group size is approximated by the axis the op shards over — we report with
g = 16 (model axis; the dominant group in this sharding).

MODEL_FLOPS = 6*N*D (dense params N, tokens D) for train (3x forward) and
2*N*D for prefill/decode forward-only; MoE uses active params — i.e. the
SPARSE (grouped) expert accounting: per token only its top_k experts' rows
count. The serving engines' dense full-batch decode discipline used to
spend U (distinct experts) x B (batch) row evaluations per layer instead;
``decode_expert_flops`` makes that dense-vs-grouped delta explicit from a
layer's [B, k] selection matrix, and ``expert_flops_per_row`` is the
per-(token, expert) unit the engines' PerfCounters row totals convert with
(benchmarks/bench_latency --grouped).
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, Optional

import numpy as np

from repro.configs.base import ARCH_IDS, INPUT_SHAPES, get_config
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16
from repro.models.moe_layer import n_experts_padded

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                          "dryrun")

_FACTORS = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
            "all-to-all": 1.0, "collective-permute": 1.0}


def param_counts(cfg) -> Dict[str, float]:
    """(total, active) parameter counts from the config."""
    d = cfg.d_model
    hd = cfg.hd if cfg.n_heads else 0
    emb = cfg.vocab * d
    per_attn = (cfg.n_heads + 2 * cfg.n_kv_heads + cfg.n_heads) * hd * d
    total = active = emb
    if cfg.family in ("dense", "moe", "vlm"):
        n_moe = cfg.n_layers - cfg.n_dense_layers
        for _ in range(cfg.n_dense_layers):
            total += per_attn + 3 * d * cfg.dense_d_ff
            active += per_attn + 3 * d * cfg.dense_d_ff
        if cfg.is_moe:
            shared = 3 * d * cfg.n_shared_experts * cfg.d_expert
            total += n_moe * (per_attn + shared
                              + cfg.n_experts * 3 * d * cfg.d_expert
                              + d * cfg.n_experts)
            active += n_moe * (per_attn + shared
                               + cfg.top_k * 3 * d * cfg.d_expert
                               + d * cfg.n_experts)
        else:
            body = cfg.n_layers * (per_attn + 3 * d * cfg.d_ff)
            if cfg.family == "vlm":
                body += (cfg.n_layers // cfg.cross_attn_every) * \
                    (per_attn + 3 * d * cfg.d_ff)  # cross blocks
            total += body
            active += body
    elif cfg.family == "encdec":
        blk = per_attn + 3 * d * cfg.d_ff
        total += cfg.enc_layers * blk + cfg.n_layers * (blk + per_attn)
        active = total
    elif cfg.family in ("ssm", "hybrid"):
        d_in = cfg.ssm_expand * d
        h = d_in // cfg.ssm_head_dim
        gn = cfg.ssm_groups * cfg.ssm_state
        per_ssm = (2 * d * d_in + 2 * d * gn + d * h
                   + (d_in + 2 * gn) * cfg.ssm_conv + d_in * d)
        total += cfg.n_layers * per_ssm
        if cfg.family == "hybrid":
            total += per_attn + 3 * d * cfg.d_ff  # one shared block
        active = total
    return {"total": total, "active": active}


def expert_flops_per_row(cfg) -> float:
    """FLOPs of ONE (token, expert) FFN row evaluation: three
    d_model x d_expert GEMM rows (gate, up, down) at 2 FLOPs/MAC."""
    return 6.0 * cfg.d_model * cfg.d_expert


def decode_expert_flops(cfg, selections) -> Dict[str, float]:
    """Per-layer decode expert FLOPs under the two execution disciplines.

    ``selections``: [B, k] expert picks of one layer's batched decode step.
    The dense full-batch path evaluates every DISTINCT expert over all B
    rows (U * B row evaluations); the segment-gathered path evaluates only
    each expert's selecting rows (sum of per-expert selecting-row counts,
    <= B * k). The roofline's active-param accounting above corresponds to
    the grouped figure — the dense one is the redundancy sparse execution
    removes."""
    sel = np.asarray(selections)
    B = sel.shape[0]
    uniq = np.unique(sel)
    dense_rows = int(uniq.size) * B
    grouped_rows = int(sum(int(np.any(sel == e, axis=1).sum())
                           for e in uniq))
    per = expert_flops_per_row(cfg)
    return {"dense_rows": dense_rows, "grouped_rows": grouped_rows,
            "dense_flops": dense_rows * per,
            "grouped_flops": grouped_rows * per}


def model_flops(cfg, shape) -> float:
    pc = param_counts(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * pc["active"] * tokens


def load_record(arch: str, shape: str, mesh: str) -> Optional[dict]:
    tag = f"{arch.replace('.', '_')}__{shape}__{mesh}.json"
    path = os.path.join(DRYRUN_DIR, tag)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def roofline_terms(rec: dict, group: int = 16) -> Optional[dict]:
    hc = rec.get("hlo_cost")
    if not hc or "flops" not in hc:
        return None
    coll = rec.get("collectives") or {}
    coll_eff = 0.0
    for op, v in coll.items():
        f = _FACTORS.get(op, 1.0) * (group - 1) / group
        coll_eff += v["bytes"] * f
    t_comp = hc["flops"] / PEAK_FLOPS_BF16
    t_mem = hc["bytes"] / HBM_BW
    t_coll = coll_eff / ICI_BW
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll,
             "collective_bytes_eff": coll_eff}
    terms["bottleneck"] = max(
        (("compute", t_comp), ("memory", t_mem), ("collective", t_coll)),
        key=lambda kv: kv[1])[0]
    return terms


def full_table(mesh: str = "single"):
    rows = []
    for arch in ARCH_IDS:
        if arch == "mixtral_8x7b":
            continue
        cfg = get_config(arch)
        for sname, shape in INPUT_SHAPES.items():
            if sname == "long_500k" and not cfg.supports_long_decode:
                continue
            rec = load_record(cfg.name, sname, mesh)
            if rec is None or not rec.get("ok"):
                rows.append({"arch": cfg.name, "shape": sname, "mesh": mesh,
                             "ok": False})
                continue
            t = roofline_terms(rec)
            mf = model_flops(cfg, shape) / rec["chips"]
            row = {"arch": cfg.name, "shape": sname, "mesh": mesh, "ok": True,
                   "model_flops_dev": mf, **(t or {})}
            if t:
                row["useful_ratio"] = mf / max(rec["hlo_cost"]["flops"], 1)
                dom = max(t["compute_s"], t["memory_s"], t["collective_s"])
                row["dominant_s"] = dom
                row["roofline_frac"] = (mf / PEAK_FLOPS_BF16) / max(dom, 1e-12)
            rows.append(row)
    return rows


def run(quick=False):
    rows = []
    for r in full_table("single"):
        if not r.get("ok"):
            rows.append((f"roofline/{r['arch']}/{r['shape']}", 0.0, "MISSING"))
            continue
        rows.append((
            f"roofline/{r['arch']}/{r['shape']}",
            r["dominant_s"] * 1e6,
            f"comp={r['compute_s']:.4f}s,mem={r['memory_s']:.4f}s,"
            f"coll={r['collective_s']:.4f}s,bound={r['bottleneck']},"
            f"useful={r['useful_ratio']:.2f},"
            f"roofline_frac={r['roofline_frac']:.3f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
