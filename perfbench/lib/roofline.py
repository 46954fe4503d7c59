"""Peaks, roofline shares and model FLOP utilisation.

The peak table (``peaks.json``) is keyed by JAX's ``device_kind``; a device
that is not in it is an error, never a default. Operation and byte counts
are computed from shapes here, not read from the compiler, so that every
PR counts the same work in the same way.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, Sequence

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def least_seconds(self, peak: Dict[str, float]) -> float:
        return max(self.flops / peak["bf16_flops"],
                   self.bytes / peak["hbm_bytes_per_s"])

    def bound(self, peak: Dict[str, float]) -> str:
        compute = self.flops / peak["bf16_flops"]
        memory = self.bytes / peak["hbm_bytes_per_s"]
        return "compute" if compute >= memory else "memory"


def roofline_pct(cost: Cost, seconds: float, peak: Dict[str, float]) -> float:
    """Least time the chip could take for ``cost`` over the measured time,
    in percent."""
    if seconds <= 0:
        raise ValueError(f"kernel time must be positive, got {seconds}")
    return 100.0 * cost.least_seconds(peak) / seconds


def mfu_pct(flops_per_unit: float, units_per_s: float,
            peak: Dict[str, float], chips: int = 1) -> float:
    """Model FLOPs per unit of work times units per second over the chips'
    bf16 peak, in percent."""
    return 100.0 * flops_per_unit * units_per_s / (chips * peak["bf16_flops"])


# ------------------------------------------------------------ vision ----

def dog_cost(tile: Sequence[int], sigma1: Sequence[float],
             sigma2: Sequence[float], radius: int) -> Cost:
    """Difference of Gaussians on a float32 tile: each separable pass is a
    (2 * radius + 1)-tap multiply-add per voxel, then one subtraction. The
    bytes are the least any schedule moves: the float32 tile read once and
    the response written once."""
    n = 1
    for s in tile:
        n *= int(s)
    taps = 2 * radius + 1
    passes = sum(1 for s in list(sigma1) + list(sigma2) if s > 0)
    return Cost(flops=float(passes * 2 * taps * n + n), bytes=float(8 * n))


# ------------------------------------------------------------- llama ----

def llama_matmul_params(cfg: Dict) -> int:
    """Parameters that enter a matrix product per token: attention and MLP
    projections of every layer, and the output head (the tied embedding
    counts once, as the head; its lookup is no product)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    per_layer = d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def llama_attention_flops(cfg: Dict, context: float) -> float:
    """Forward FLOPs of the score and value products for one query token
    that attends to ``context`` positions, over all layers."""
    hd, h = cfg["head_dim"], cfg["num_attention_heads"]
    return cfg["num_hidden_layers"] * 2 * 2 * h * hd * context


def llama_train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward and backward FLOPs per trained token: 6 per matrix-product
    parameter, plus three times the causal attention products at the mean
    causal context (S + 1) / 2. Recomputation is not counted."""
    return (6.0 * llama_matmul_params(cfg)
            + 3.0 * llama_attention_flops(cfg, (seq_len + 1) / 2.0))

