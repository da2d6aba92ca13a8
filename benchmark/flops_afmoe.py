"""Operations and bytes Trinity-Mini's forward pass needs, from the
configuration file's sizes: what the mfu and roofline metrics of its cell
divide by.

Counted is what the algorithm needs at the LEAST: real prompt tokens (bucket
padding is not counted); of prefill attention the causal half on a full
layer and, on a window layer, ``min(i + 1, sliding_window)`` keys for the
query at position i; an expert's products only for the assignments it got;
each weight read once a position; of the cache the keys inside window or
context of the slots occupied. A program that scores P x P under a mask, or
reads a whole arena, therefore reads LOW, never over 100 %.
A multiply-add is 2 operations."""

from __future__ import annotations

from typing import Dict

BYTES = 2   # bfloat16 weights and cache
SLIDING = "sliding_attention"


def attn_proj_params(cfg: Dict) -> int:
    """One layer's W_q, W_g, W_o and W_k, W_v."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return d * hd * (3 * cfg["num_attention_heads"]
                     + 2 * cfg["num_key_value_heads"])


def kv_dim(cfg: Dict) -> int:
    """Values one token caches a layer: [k | v] of every key/value head."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def expert_params(cfg: Dict) -> int:
    """One routed (or shared) expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def experts_held(cfg: Dict) -> int:
    return int(cfg.get("experts_held", cfg["num_experts"]))


def layer_counts(cfg: Dict):
    """(dense layers, expert layers, window layers, full layers)."""
    dense = cfg["num_dense_layers"]
    window = sum(t == SLIDING for t in cfg["layer_types"])
    return (dense, cfg["num_hidden_layers"] - dense, window,
            cfg["num_hidden_layers"] - window)


def fixed_params(cfg: Dict) -> int:
    """Parameters every token's pass multiplies by, the head left out: the
    attention projections, the dense layers, routers and shared experts."""
    d = cfg["hidden_size"]
    n_dense, n_moe, _w, _f = layer_counts(cfg)
    return (cfg["num_hidden_layers"] * attn_proj_params(cfg)
            + n_dense * 3 * d * cfg["intermediate_size"]
            + n_moe * (d * cfg["num_experts"]
                       + cfg["num_shared_experts"] * expert_params(cfg)))


def routed_flops(cfg: Dict, assignments_held: float) -> float:
    """The grouped products of ``assignments_held`` (token, held expert)
    pairs."""
    return 2.0 * expert_params(cfg) * assignments_held


def expected_held_assignments(cfg: Dict, tokens: float) -> float:
    """(token, held expert) pairs ``tokens`` tokens make over all expert
    layers when the router spreads evenly: top-k x held / num_experts."""
    _d, n_moe, _w, _f = layer_counts(cfg)
    return (tokens * n_moe * cfg["num_experts_per_tok"]
            * experts_held(cfg) / cfg["num_experts"])


def window_keys(cfg: Dict, context: float) -> float:
    """Keys a window layer's query with ``context`` positions before and
    at it can see."""
    return min(context, cfg["sliding_window"])


def attended_pairs(cfg: Dict, length: int) -> float:
    """(query, key) pairs of one prompt over ALL layers: the causal half on
    a full layer, ``min(i + 1, window)`` keys a query on a window layer."""
    _d, _m, n_win, n_full = layer_counts(cfg)
    W = cfg["sliding_window"]
    full = length * (length + 1) / 2.0
    inside = min(length, W)
    win = inside * (inside + 1) / 2.0 + max(length - W, 0) * W
    return n_full * full + n_win * win


def pair_flops(cfg: Dict) -> float:
    """Operations one (query, key) pair costs over all query heads: the
    score and the value product."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def prefill_flops(cfg: Dict, length: int, assignments_held: float) -> float:
    """One prompt of ``length`` real tokens through every layer held (no
    head: prefill predicts nothing)."""
    return (2.0 * fixed_params(cfg) * length
            + pair_flops(cfg) * attended_pairs(cfg, length)
            + routed_flops(cfg, assignments_held))


def decode_row_flops(cfg: Dict, context: float, assignments_held: float
                     ) -> float:
    """One position of one beam with ``context`` cached tokens before and
    at it, head included."""
    _d, _m, n_win, n_full = layer_counts(cfg)
    keys = n_full * context + n_win * window_keys(cfg, context)
    return (2.0 * fixed_params(cfg) + pair_flops(cfg) * keys
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
            + routed_flops(cfg, assignments_held))


def request_flops(cfg: Dict, prompt_len: int, positions: int, beam: int
                  ) -> float:
    """A whole request, the routed experts' part left out (the window's own
    count of held assignments gives that: :func:`routed_flops`)."""
    total = prefill_flops(cfg, prompt_len, 0.0)
    for t in range(positions):
        total += beam * decode_row_flops(cfg, prompt_len + t + 1, 0.0)
    return total


def step_weight_bytes(cfg: Dict, rows: float) -> float:
    """Weights one decode position of ``rows`` beam rows must read at
    least: everything outside the routed experts once, and each held
    expert that got an assignment (expected over an even router: an expert
    is missed with probability (1 - 1/num_experts)^(rows x top-k))."""
    _d, n_moe, _w, _f = layer_counts(cfg)
    E = cfg["num_experts"]
    hit = 1.0 - (1.0 - 1.0 / E) ** (rows * cfg["num_experts_per_tok"])
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return BYTES * (fixed_params(cfg) + head
                    + n_moe * experts_held(cfg) * hit * expert_params(cfg))


def step_kv_bytes(cfg: Dict, prompt_len: float, gen_len: float, beam: int
                  ) -> float:
    """Cache one occupied slot's position must read at least, with
    ``gen_len`` generated positions (this one in): on a full layer the
    prompt's once (the beams share it) and each beam's generated ones; on a
    window layer only what lies inside the window."""
    _d, _m, n_win, n_full = layer_counts(cfg)
    W = cfg["sliding_window"]
    gen_in = min(gen_len, W)
    prompt_in = min(prompt_len, max(W - gen_len, 0))
    return BYTES * kv_dim(cfg) * (
        n_full * (prompt_len + beam * gen_len)
        + n_win * (prompt_in + beam * gen_in))
