"""Operations and bytes A.X-K1's forward pass needs, from the configuration
file's sizes: what the mfu and roofline metrics of its cell divide by.

Counted is what the algorithm needs at the least: real prompt tokens (bucket
padding is not counted), the causal half of prefill attention, an expert's
products only for the assignments it got (an expert that is not held, or got
none, costs nothing), each weight read once a position.
A multiply-add is 2 operations."""

from __future__ import annotations

from typing import Dict

BYTES = 2   # bfloat16 weights and latents


def _attn_proj_params(cfg: Dict) -> int:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    return (d * rq + rq * H * (dn + dr) + d * (r + dr)
            + r * H * (dn + dv) + H * dv * d)


def latent_dim(cfg: Dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def expert_params(cfg: Dict) -> int:
    """One routed (or shared) expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_counts(cfg: Dict):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def fixed_params(cfg: Dict) -> int:
    """Parameters every token's pass multiplies by, the head left out: the
    attention projections, the dense layers, routers and shared experts."""
    d = cfg["hidden_size"]
    n_dense, n_moe = layer_counts(cfg)
    return (cfg["num_hidden_layers"] * _attn_proj_params(cfg)
            + n_dense * 3 * d * cfg["intermediate_size"]
            + n_moe * (d * cfg["published"]["n_routed_experts"]
                       + cfg["n_shared_experts"] * expert_params(cfg)))


def routed_flops(cfg: Dict, assignments_held: float) -> float:
    """The grouped products of ``assignments_held`` (token, held expert)
    pairs; an assignment to an expert that is not held costs nothing."""
    return 2.0 * expert_params(cfg) * assignments_held


def expected_held_assignments(cfg: Dict, tokens: float) -> float:
    """(token, held expert) pairs ``tokens`` tokens make over all expert
    layers when the router spreads evenly: top-k x held / published."""
    _d, n_moe = layer_counts(cfg)
    return (tokens * n_moe * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / cfg["published"]["n_routed_experts"])


def prefill_flops(cfg: Dict, length: int, assignments_held: float) -> float:
    """One prompt of ``length`` real tokens through every layer held
    (materialised causal attention, no head: prefill predicts nothing)."""
    H = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    pairs = length * (length + 1) / 2.0
    attn = cfg["num_hidden_layers"] * 2.0 * H * (qk + cfg["v_head_dim"]) \
        * pairs
    return 2.0 * fixed_params(cfg) * length + attn \
        + routed_flops(cfg, assignments_held)


def decode_row_flops(cfg: Dict, context: float, assignments_held: float
                     ) -> float:
    """One position of one beam with ``context`` cached tokens before and
    at it (absorbed attention: scores and values over the latents), head
    included."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r = cfg["kv_lora_rank"]
    absorb = 2.0 * H * r * cfg["qk_nope_head_dim"]     # q_nope W_uk^T
    attn = 2.0 * H * (latent_dim(cfg) + r) * context
    # W_ukv is used in its two halves (absorb, then W_uv); its own product
    # with c_kv is not made, so it leaves the projections' count
    proj = _attn_proj_params(cfg) - r * H * (cfg["qk_nope_head_dim"]
                                             + cfg["v_head_dim"])
    uv = 2.0 * H * r * cfg["v_head_dim"]
    per_layer = 2.0 * proj + absorb + uv + attn
    return (cfg["num_hidden_layers"] * per_layer
            + 2.0 * (fixed_params(cfg)
                     - cfg["num_hidden_layers"] * _attn_proj_params(cfg))
            + 2.0 * d * cfg["vocab_size"]
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
    is missed with probability (1 - 1/published)^(rows x top-k))."""
    _d, n_moe = layer_counts(cfg)
    E = cfg["published"]["n_routed_experts"]
    hit = 1.0 - (1.0 - 1.0 / E) ** (rows * cfg["num_experts_per_tok"])
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return BYTES * (fixed_params(cfg) + head
                    + n_moe * cfg["n_routed_experts"] * hit
                    * expert_params(cfg))


def step_latent_bytes(cfg: Dict, prompt_len: float, gen_len: float,
                      beam: int) -> float:
    """Latents one occupied slot's position must read at least: its
    prompt's once (the beams share them) and each beam's generated ones."""
    return BYTES * cfg["num_hidden_layers"] * latent_dim(cfg) \
        * (prompt_len + beam * gen_len)
