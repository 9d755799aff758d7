"""Roofline analysis (deliverable g): combine the dry-run's compiled-HLO
measurements with an analytic TPU-execution model into the three roofline
terms per (arch x shape x mesh).

Methodology (documented in EXPERIMENTS.md §Roofline):
- compute term    = max(HLO_flops, analytic_flops) / peak. The HLO count is
  trip-weighted (repro.launch.hlo_cost) and captures replication waste; the
  analytic floor covers decode cells where XLA:CPU strength-reduces GEMV
  dots out of existence.
- memory term     = analytic HBM traffic / bw. The compiled-HLO traffic is
  reported as reference but reflects XLA:CPU's fusion (far less aggressive
  than TPU) and would overstate TPU HBM traffic by ~an order of magnitude.
  The analytic model assumes flash/SSD kernels keep score matrices in VMEM.
- collective term = HLO collective bytes (trip-weighted, per device), with
  ring factors: all-reduce 2x, others 1x.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro.configs import SHAPES, get_config
from repro.launch.mesh import PRODUCTION_KIND, hw_peaks
from repro.models.registry import build_model

ART = os.path.join(os.path.dirname(__file__), "artifacts", "dryrun")


# ----------------------------------------------------------- analytic flops
def _attn_layers(cfg, seq: int, kind: str):
    """[(n_layers, context_len, q_len_factor)] attention context terms."""
    out = []
    s = seq
    if cfg.family in ("dense", "moe"):
        if cfg.local_global_period:
            n_global = cfg.num_layers // cfg.local_global_period
            n_local = cfg.num_layers - n_global
            out.append((n_global, s / 2 if kind != "decode" else s, 1.0))
            w = min(cfg.sliding_window, s)
            out.append((n_local, w / 2 if kind != "decode" else w, 1.0))
        else:
            out.append((cfg.num_layers, s / 2 if kind != "decode" else s, 1.0))
    elif cfg.family == "vlm":
        n_cross = cfg.num_layers // cfg.cross_attn_period
        out.append((cfg.num_layers - n_cross,
                    s / 2 if kind != "decode" else s, 1.0))
        out.append((n_cross, cfg.vision_seq, 1.0))
    elif cfg.family == "audio":
        out.append((cfg.num_layers, s / 2 if kind != "decode" else s, 1.0))
        out.append((cfg.num_layers, cfg.encoder_seq, 1.0))   # cross
        if kind != "decode":  # encoder runs on train/prefill
            out.append((cfg.encoder_layers, cfg.encoder_seq, 1.0))
    elif cfg.family == "hybrid":
        n_attn = cfg.num_layers // cfg.attn_period
        out.append((n_attn, s / 2 if kind != "decode" else s, 1.0))
    return out


def analytic_flops(arch: str, shape_name: str) -> float:
    """Useful total FLOPs for one step of this cell (whole mesh)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    kind = shape.kind
    tokens = shape.global_batch * (shape.seq_len if kind != "decode" else 1)
    mult = {"train": 6, "prefill": 2, "decode": 2}[kind]
    total = mult * model.active_param_count() * tokens

    # attention context terms: 4*T*H*hd flops/token/layer (QK^T + PV)
    attn_mult = {"train": 4, "prefill": 1, "decode": 1}[kind]
    hd = cfg.resolved_head_dim
    for n_layers, ctx, _ in _attn_layers(cfg, shape.seq_len, kind):
        total += (attn_mult * n_layers * 4 * ctx * cfg.num_heads * hd) * tokens

    # SSD terms: ~2*chunk*(n+p) flops/token/head/layer intra-chunk
    if cfg.family in ("ssm", "hybrid"):
        n_mamba = cfg.num_layers
        if cfg.family == "hybrid":
            n_mamba -= cfg.num_layers // cfg.attn_period
        if kind == "decode":
            per_tok = 4 * cfg.ssm_state * cfg.ssm_head_dim   # state update+read
        else:
            per_tok = 2 * cfg.ssm_chunk * (cfg.ssm_state + cfg.ssm_head_dim)
        total += (attn_mult * n_mamba * per_tok * cfg.ssm_nheads) * tokens
    return total


# --------------------------------------------------------- analytic memory
def _shard_counts(rules: str, n_chips: int):
    """(param shards, moment shards, data shards) under the rule set."""
    model_axis = 16
    data_axes = n_chips // model_axis
    if rules in ("fsdp_tp", "long"):
        return n_chips, n_chips, data_axes
    return model_axis, n_chips, data_axes     # tp: params TP-only; ZeRO moments


def analytic_memory_bytes(arch: str, shape_name: str, rules: str,
                          n_chips: int, grad_accum: int = 1) -> float:
    """Per-device HBM traffic for one step, assuming TPU-fused kernels
    (flash attention / fused SSD: score matrices never round-trip HBM)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    n = model.param_count()
    kind = shape.kind
    pshard, zshard, dshard = _shard_counts(rules, n_chips)
    p_dev = 2.0 * n / pshard

    if kind == "train":
        # params: fwd read + remat read + bwd read + write; fsdp re-gathers
        # per microbatch
        traffic = p_dev * (3 * grad_accum + 1)
        traffic += (8.0 * n / zshard) * 2 * 2          # mu+nu read+write f32
        traffic += (4.0 * n / zshard) * 2 * grad_accum  # grad accum rw f32
    elif kind == "prefill":
        traffic = p_dev
    else:
        traffic = p_dev                                 # one full param read
    # activations: residual stream IO per layer (read+write a handful of
    # times: norms, proj in/out, residual adds) — c ~= 10 for train (incl.
    # remat re-reads), 4 otherwise
    tokens_local = shape.global_batch * (shape.seq_len if kind != "decode"
                                         else 1) / dshard
    c = 10 if kind == "train" else 4
    layers = cfg.num_layers + cfg.encoder_layers
    traffic += layers * tokens_local * cfg.d_model * 2.0 * c
    # KV cache traffic
    if kind != "train" and cfg.num_heads:
        kvb = (2 * cfg.num_layers * shape.global_batch * shape.seq_len
               * cfg.num_kv_heads * cfg.resolved_head_dim * 2.0)
        kvb /= n_chips  # cache sharded over (batch x kv-or-seq)
        traffic += kvb  # prefill: write; decode: read
    if cfg.family in ("ssm", "hybrid") and kind == "decode":
        state = (cfg.num_layers * shape.global_batch * cfg.ssm_nheads
                 * cfg.ssm_head_dim * cfg.ssm_state * 4.0) / max(dshard, 1)
        traffic += 2 * state
    return traffic


# ------------------------------------------------------------------ report
def load_cells(mesh_dir: str) -> List[dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(ART, mesh_dir, "*.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def note_for(dominant: str, useful: float) -> str:
    if dominant == "collective":
        return ("sequence-parallel TP (reduce-scatter/all-gather in bf16) "
                "would cut the per-layer activation all-reduces")
    if dominant == "memory":
        return ("raise arithmetic intensity: bigger microbatch per device / "
                "fuse optimizer update; params+moments traffic dominates")
    if useful < 0.3:
        return ("compute-bound but replicated: pad heads to a mesh multiple "
                "so attention shards over the model axis")
    return "near roofline: compute-bound with useful work dominating"


def analyze_cell(cell: dict) -> Optional[dict]:
    if cell.get("status") != "OK":
        return None
    arch, shape_name = cell["arch"], cell["shape"]
    n_chips = cell["n_chips"]
    rl = cell["roofline"]
    a_flops = analytic_flops(arch, shape_name)
    a_flops_dev = a_flops / n_chips
    hlo_flops_dev = rl["hlo_flops_per_device"]
    flops_dev = max(hlo_flops_dev, a_flops_dev)
    mem_dev = analytic_memory_bytes(arch, shape_name,
                                    cell.get("rules", "tp"), n_chips,
                                    cell.get("grad_accum", 1))
    coll = cell["collectives"]["bytes_by_kind"]
    coll_dev = (2.0 * coll.get("all-reduce", 0.0)
                + coll.get("all-gather", 0.0)
                + coll.get("reduce-scatter", 0.0)
                + coll.get("all-to-all", 0.0)
                + coll.get("collective-permute", 0.0))
    hw = hw_peaks(PRODUCTION_KIND)
    compute_s = flops_dev / hw["peak_flops_bf16"]
    memory_s = mem_dev / hw["hbm_bw"]
    collective_s = coll_dev / hw["ici_bw"]
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    model_flops = rl["model_flops_total"]
    useful = model_flops / max(flops_dev * n_chips, 1e-9)
    # roofline fraction: useful work at peak over the modelled step time
    frac = (model_flops / n_chips / hw["peak_flops_bf16"]) / max(bound, 1e-12)
    return {
        "arch": arch, "shape": shape_name, "mesh": cell["mesh"],
        "rules": cell.get("rules"), "grad_accum": cell.get("grad_accum", 1),
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s, "dominant": dominant,
        "model_flops": model_flops,
        "hlo_flops_total": hlo_flops_dev * n_chips,
        "useful_ratio": useful, "roofline_fraction": frac,
        "hlo_traffic_ref_bytes": rl["hlo_bytes_per_device"],
        "note": note_for(dominant, useful),
    }


def markdown_table(rows: List[dict]) -> str:
    hdr = ("| arch | shape | rules | compute s | memory s | collective s | "
           "dominant | useful % | roofline % |\n"
           "|---|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['rules']} "
            f"| {r['compute_s']:.3f} | {r['memory_s']:.3f} "
            f"| {r['collective_s']:.3f} | {r['dominant']} "
            f"| {100 * r['useful_ratio']:.1f} "
            f"| {100 * r['roofline_fraction']:.1f} |")
    return hdr + "\n".join(lines)


def run() -> list[tuple[str, float, str]]:
    rows_out = []
    table_rows = []
    for cell in load_cells("pod_16x16"):
        r = analyze_cell(cell)
        if r is None:
            rows_out.append((f"roofline/{cell['arch']}/{cell['shape']}",
                             0.0, cell.get("reason", cell.get("status"))))
            continue
        table_rows.append(r)
        rows_out.append(
            (f"roofline/{r['arch']}/{r['shape']}/fraction",
             r["roofline_fraction"],
             f"dom={r['dominant']} useful={100*r['useful_ratio']:.0f}%"))
    os.makedirs(os.path.join(ART, ".."), exist_ok=True)
    with open(os.path.join(ART, "..", "roofline_pod.json"), "w") as f:
        json.dump(table_rows, f, indent=1)
    with open(os.path.join(ART, "..", "roofline_pod.md"), "w") as f:
        f.write(markdown_table(table_rows))
    return rows_out


if __name__ == "__main__":
    for r in run():
        print(r)
