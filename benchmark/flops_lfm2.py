"""Operations and bytes LFM2-8B-A1B's forward pass needs, from the
configuration file's sizes: what the mfu and roofline metrics of its cell
divide by.

Counted is what the algorithm needs at the LEAST: real prompt tokens (bucket
padding is not counted); **matrix products only** — the short convolution's
three taps and its two gates are elementwise, not the MXU's, and stay out of
the numerator as norms and softmaxes do; of prefill attention the causal
half; an expert's products for the top-k assignments a token makes (every
expert is held, so the count is exact without the device's); each weight
read once a position, an expert only where some row is expected to choose it;
of the caches the occupied beams' convolution tails READ AND WRITTEN and the
keys and values inside the context of the slots occupied; of a prefill
nothing of the last layer that no cache keeps. A program that
convolves padded tokens, or reads a whole arena, therefore reads LOW, never
over 100 %. A multiply-add is 2 operations."""

from __future__ import annotations

from typing import Dict

from .weights_lfm2 import CONV, head_dim, param_shapes  # noqa: F401

BYTES = 2   # bfloat16 weights, keys and values, convolution tails


def layer_counts(cfg: Dict):
    """(conv layers, attention layers, dense layers, expert layers)."""
    conv = sum(t == CONV for t in cfg["layer_types"])
    dense = cfg["num_dense_layers"]
    L = cfg["num_hidden_layers"]
    return conv, L - conv, dense, L - dense


def conv_params(cfg: Dict) -> int:
    """One short convolution's W_in (d, 3d), W_out (d, d) and taps."""
    d = cfg["hidden_size"]
    return 4 * d * d + cfg["conv_L_cache"] * d


def attn_proj_params(cfg: Dict) -> int:
    """One attention layer's W_q, W_o and W_k, W_v."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    return d * hd * 2 * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def dense_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def param_count(cfg: Dict) -> int:
    """Every parameter, from the sizes alone (nothing is allocated); the
    embedding once: it is the head too."""
    n_conv, n_attn, n_dense, n_moe = layer_counts(cfg)
    d, E = cfg["hidden_size"], cfg["num_experts"]
    return (n_conv * conv_params(cfg)
            + n_attn * (attn_proj_params(cfg) + 2 * head_dim(cfg))
            + n_dense * dense_params(cfg)
            + n_moe * (E * expert_params(cfg) + d * E + E)
            + cfg["num_hidden_layers"] * 2 * d
            + cfg["vocab_size"] * d + d)


def fixed_params(cfg: Dict) -> int:
    """Matrix parameters every token's pass multiplies by, the routed
    experts and the head left out: the mixers' products, the dense layers
    and the routers."""
    n_conv, n_attn, n_dense, n_moe = layer_counts(cfg)
    d = cfg["hidden_size"]
    return (n_conv * 4 * d * d + n_attn * attn_proj_params(cfg)
            + n_dense * dense_params(cfg) + n_moe * d * cfg["num_experts"])


def routed_flops(cfg: Dict) -> float:
    """One token's routed experts over every expert layer: top-k of them a
    layer, no shared expert."""
    _c, _a, _d, n_moe = layer_counts(cfg)
    return 2.0 * n_moe * cfg["num_experts_per_tok"] * expert_params(cfg)


def pair_flops(cfg: Dict) -> float:
    """Operations one (query, key) pair costs over all query heads: the
    score and the value product."""
    return 4.0 * cfg["num_attention_heads"] * head_dim(cfg)


def unread_in_prefill(cfg: Dict) -> float:
    """Operations a token of the LAST layer costs that nothing a prefill
    hands over reads (so the compiled prefill does not compute them): its
    feed-forward, and of its mixer all but what the arena keeps — of a
    short convolution the C third of W_in and W_out, of an attention layer
    W_q, W_o and the pairs (counted by :func:`prefill_flops`'s caller)."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    last = L - 1
    ffn = (dense_params(cfg) if last < cfg["num_dense_layers"] else
           d * cfg["num_experts"]
           + cfg["num_experts_per_tok"] * expert_params(cfg))
    # a conv layer's C third of W_in and W_out; an attention layer's W_q
    # and W_o: d x d each either way
    return 2.0 * (ffn + 2 * d * d)


def prefill_flops(cfg: Dict, length: int) -> float:
    """One prompt of ``length`` real tokens through every layer, less what
    of the last one nothing reads (no head: prefill predicts nothing)."""
    _c, n_attn, _d, _m = layer_counts(cfg)
    if cfg["layer_types"][-1] != CONV:
        n_attn -= 1
    return ((2.0 * fixed_params(cfg) + routed_flops(cfg)
             - unread_in_prefill(cfg)) * length
            + pair_flops(cfg) * n_attn * length * (length + 1) / 2.0)


def decode_row_flops(cfg: Dict, context: float) -> float:
    """One position of one beam with ``context`` cached tokens before and
    at it, head included."""
    _c, n_attn, _d, _m = layer_counts(cfg)
    return (2.0 * fixed_params(cfg) + routed_flops(cfg)
            + pair_flops(cfg) * n_attn * context
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def request_flops(cfg: Dict, prompt_len: int, positions: int, beam: int
                  ) -> float:
    total = prefill_flops(cfg, prompt_len)
    for t in range(positions):
        total += beam * decode_row_flops(cfg, prompt_len + t + 1)
    return total


def counted_flops(cfg: Dict, counters: Dict) -> float:
    """Operations only the device's own counts can give: none here (every
    expert is held, so a token's routed work is known)."""
    return 0.0


def tail_bytes_per_beam(cfg: Dict) -> int:
    """What one beam carries between positions: each conv layer's last
    ``conv_L_cache - 1`` inputs of the convolution."""
    n_conv, _a, _d, _m = layer_counts(cfg)
    return n_conv * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * BYTES


def step_weight_bytes(cfg: Dict, rows: float) -> float:
    """Weights one decode position of ``rows`` beam rows must read at
    least: everything outside the routed experts once, and each expert that
    some row is expected to choose (an expert is missed with probability
    (1 - 1/num_experts)^(rows x top-k) over an even router)."""
    _c, _a, _d, n_moe = layer_counts(cfg)
    E = cfg["num_experts"]
    missed = (1.0 - 1.0 / E) ** (rows * cfg["num_experts_per_tok"])
    return BYTES * (param_count(cfg)
                    - n_moe * E * missed * expert_params(cfg))


def step_slot_bytes(cfg: Dict, prompt_len: float, gen_len: float, beam: int
                    ) -> float:
    """What one occupied slot's position must move at least besides the
    weights, with ``gen_len`` generated positions (this one in): each
    beam's convolution tails read and written, the prompt's keys and values
    once (the beams share them) and each beam's generated ones."""
    _c, n_attn, _d, _m = layer_counts(cfg)
    kv_dim = 2 * cfg["num_key_value_heads"] * head_dim(cfg)
    return (2.0 * beam * tail_bytes_per_beam(cfg)
            + BYTES * kv_dim * n_attn * (prompt_len + beam * gen_len))


def derived_counters(cfg: Dict, counters: Dict) -> Dict:
    """Counters that are a device count times a size: ``expert_bytes_moved``
    = the held experts some row of a decode position routed to
    (``moe_experts_read``, counted on the device over expert layers and
    positions) x one expert's bytes. Over ``step_min_bytes`` it is the
    share of what the steps must move that is expert weights. Nothing to
    count from (a program without the counter) gives nothing."""
    if "moe_experts_read" not in counters:
        return {}
    return {"expert_bytes_moved":
            BYTES * expert_params(cfg) * counters["moe_experts_read"]}
