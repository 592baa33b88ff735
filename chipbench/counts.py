"""Work that a cell's computation needs, from the configuration's shapes.

Operations and bytes are what the mathematics asks for, whatever the
program does to compute it: rematerialised work, padding and the GEMM
backend's own rounding passes are not counted, so a share of a peak can only rise when the program wastes less.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class Dims:
    def __init__(self, conf: Dict):
        self.d = int(conf["hidden_size"])
        self.ff = int(conf["intermediate_size"])
        self.h = int(conf["num_attention_heads"])
        self.kv = int(conf["num_key_value_heads"])
        self.n_layers = int(conf["num_hidden_layers"])
        self.vocab = int(conf["vocab_size"])
        self.hd = int(conf.get("head_dim") or self.d // self.h)


def gemm_params_per_layer(conf: Dict) -> int:
    """Weights of one decoder layer's matmuls: q, k, v, o and the SwiGLU
    gate, up and down projections (biases and norm scales excluded)."""
    m = Dims(conf)
    return (m.d * m.h * m.hd + 2 * m.d * m.kv * m.hd + m.h * m.hd * m.d
            + 3 * m.d * m.ff)


def gemm_params(conf: Dict) -> int:
    """Every matmul weight of the model: all layers plus the vocabulary
    head (the tied embedding counts once, as the head's weight)."""
    m = Dims(conf)
    return m.n_layers * gemm_params_per_layer(conf) + m.d * m.vocab


def attn_flops_per_token(conf: Dict, context: float) -> float:
    """Forward flops of the two attention products (scores and values) of
    one token that attends to ``context`` positions, over all layers."""
    m = Dims(conf)
    return m.n_layers * 2 * 2 * context * m.h * m.hd


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Forward and backward flops per trained token, causal attention over
    a sequence of ``seq`` (mean context seq/2); recomputation excluded."""
    return 6.0 * gemm_params(conf) + 3.0 * attn_flops_per_token(conf,
                                                                 seq / 2)


def peaks(device_kind: str) -> Dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "notes":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add the chip with its source")
    return table[device_kind]


def compute_peak(device_kind: str, policy: Dict) -> float:
    """The cell's compute peak by the rule in peaks.json's notes."""
    p = peaks(device_kind)
    if policy["mode"] in ("mirage", "mirage_fast"):
        return p["int8_ops_per_s"]
    return p["bf16_flops_per_s"]
