"""Cells of the benchmark cut to a size the CPU test run can hold.

Widths, depth, vocabulary and lengths are cut alike in the configuration
file's published keys (what the reference reads) and in the program's
overrides (what the served system builds), so the two still describe one
model.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def qwen2_cfg() -> dict:
    cfg = load("bench", "configs", "qwen2-0.5b.json")
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
    cfg["program"] = dict(cfg["program"], overrides=dict(
        d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, num_layers=2,
        d_ff=128, vocab_size=512))
    return cfg


def mamba2_cfg() -> dict:
    cfg = load("bench", "configs", "mamba2-2.7b.json")
    cfg.update(d_model=64, n_layer=2, vocab_size=500)
    cfg["mixer"] = dict(cfg["mixer"], d_state=16, headdim=16, chunk_size=8)
    cfg["program"] = dict(cfg["program"], overrides=dict(
        d_model=64, num_layers=2, vocab_size=512, ssm_state=16,
        ssm_head_dim=16, ssm_chunk=8, norm_eps=1e-5))
    return cfg


def traffic(name: str = "grpo-verify-solo") -> dict:
    tr = load("bench", "traffic", f"{name}.json")
    tr.update(prompts_per_step=2, group_size=4, prompt_len=16,
              max_new_tokens=16, seq_len=32)
    tr["verifier"] = dict(tr["verifier"], median_s=0.01, cap_s=0.05)
    return tr
