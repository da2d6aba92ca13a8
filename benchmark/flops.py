"""Operations the algorithm needs, from the configuration's sizes alone.

``train_step_flops`` is a copy of the program's ``bench._analytic_flops``
(MXU terms only: dense projections, attention, adjacency products, the
fused output head; backward = 2x forward for parameter matmuls, nothing
recomputed is counted). The decode counts follow the same terms for one
request's prefill and for one beam row advancing one position.
"""

from __future__ import annotations

from typing import Dict


def _geom(cfg: Dict):
    d = cfg["embedding_dim"]
    sou, sub = cfg["sou_len"], cfg["sub_token_len"]
    g = sou + sub + cfg["ast_change_len"]
    v_out = cfg["vocab_size"] + sou + sub
    return d, sou, sou + sub, g, cfg["tar_len"], v_out, cfg["num_layers"]


def _encoder_fwd(cfg: Dict) -> float:
    d, sou, _s, g, _t, _v, L = _geom(cfg)
    adj = g * g * d * 2 if cfg.get("adjacency_impl", "dense") == "dense" else 0
    return L * (4 * sou * d * d * 2 + 2 * g * d * d * 2), L * adj


def train_step_flops(cfg: Dict, batch_size: int) -> float:
    """One forward + backward + optimizer step over ``batch_size`` commits."""
    d, _sou, s, _g, t, v, L = _geom(cfg)
    enc, adj = _encoder_fwd(cfg)
    dec = L * ((6 * t + 2 * s) * d * d * 2
               + 2 * (t * t + t * s) * d * 2
               + 2 * t * d * 4 * d * 2)
    head = (t * d * v * 2 + s * d * d * 2 + t * d * d * 2 + t * s * d * 2)
    # A.x backward is dx = A^T.dout only (the adjacency has no gradient):
    # 2x forward, not the 3x of parameter matmuls
    return 3.0 * batch_size * (enc + dec + head) + 2.0 * batch_size * adj


def prefill_flops(cfg: Dict) -> float:
    """One request's prefill: encoder forward, per-layer cross-attention K/V
    of the encoder states, the copy head's source projection."""
    d, _sou, s, _g, _t, _v, L = _geom(cfg)
    enc, adj = _encoder_fwd(cfg)
    return enc + adj + L * 2 * s * d * d * 2 + s * d * d * 2


def decode_position_flops(cfg: Dict, attended: float) -> float:
    """One request advancing one position: ``beam_size`` rows through the
    decoder stack (self-attention over ``attended`` cached positions,
    cross-attention over the source, FFN) and the fused gen+copy head."""
    d, _sou, s, _g, _t, _v, L = _geom(cfg)
    v = cfg["vocab_size"]
    row = L * (4 * d * d * 2 + 2 * attended * d * 2      # self-attention
               + 2 * d * d * 2 + 2 * s * d * 2           # cross-attention
               + 2 * d * 4 * d * 2)                      # FFN
    row += d * v * 2 + d * d * 2 + s * d * 2 + d * 2 * 2  # heads
    return cfg["beam_size"] * row
