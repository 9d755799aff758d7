"""Model FLOPs of a served GRPO step, from the configuration's sizes.

A position processed in a forward pass costs 2 FLOPs per weight of every
matrix product it passes (the tied head included, the embedding lookup
not), plus its sequence mixing: causal attention (QK^T and PV over the
positions up to it) for a decoder, the state update and read-out of the
selective state space for Mamba-2. A trained position costs three forward
passes (forward, and a backward pass of twice the forward). Recomputation
under remat is not counted: this is what the model needs, not what the
program ran.
"""
from __future__ import annotations


def matmul_weights(family: str, a: dict) -> int:
    if family == "qwen2":
        per_layer = (a["d"] * a["H"] * a["hd"] * 2 + a["d"] * a["K"] * a["hd"] * 2
                     + 3 * a["d"] * a["ff"])
    elif family == "mamba2":
        proj = 2 * a["di"] + 2 * a["g"] * a["n"] + a["h"]
        per_layer = a["d"] * proj + a["di"] * a["d"]
    else:
        raise ValueError(f"no FLOP count for family {family!r}")
    return a["L"] * per_layer + a["V"] * a["d"]


def mixing_flops(family: str, a: dict, position: int) -> int:
    """Sequence-mixing FLOPs of the token at 0-based ``position``."""
    if family == "qwen2":
        return 4 * a["L"] * (position + 1) * a["H"] * a["hd"]
    return 5 * a["L"] * a["h"] * a["p"] * a["n"]


def forward_flops(family: str, a: dict, rows: int, seq: int) -> float:
    """One forward pass over positions 0..seq-1 of ``rows`` rows."""
    per_row = sum(2 * matmul_weights(family, a) + mixing_flops(family, a, t)
                  for t in range(seq))
    return float(rows * per_row)


def step_flops(family: str, a: dict, rows: int, seq: int) -> dict:
    """FLOPs of one GRPO step's GENERATE (prefill and decode process every
    position of the packed sequence once) and UPDATE_ACTOR (trains on it)."""
    fwd = forward_flops(family, a, rows, seq)
    return {"GENERATE": fwd, "UPDATE_ACTOR": 3 * fwd}
