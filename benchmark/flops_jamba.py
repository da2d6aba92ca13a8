"""Operations and bytes Jamba2-3B's forward pass needs, from the
configuration file's sizes: what the mfu and roofline metrics of its cell
divide by.

Counted is what the algorithm needs at the LEAST: real prompt tokens (bucket
padding is not counted); **matrix products only** — the recurrence's
elementwise work (an exp, three products and a sum a state element a token)
is not the MXU's and stays out of the numerator, as norms and softmaxes do;
of prefill attention the causal half; each weight read once a position; of
the caches the occupied beams' recurrent state READ AND WRITTEN (a position
rewrites it whole) and the keys and values inside the context of the slots
occupied. A program that scans padded tokens, updates idle lanes, or reads a
whole arena, therefore reads LOW, never over 100 %.
A multiply-add is 2 operations."""

from __future__ import annotations

from typing import Dict

BYTES = 2        # bfloat16 weights, keys and values, convolution tail
STATE_BYTES = 4  # the recurrent state as the configuration states it:
#                  float32 (``assumed`` (e)); the least bytes count that


def head_dim(cfg: Dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def d_inner(cfg: Dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def layer_counts(cfg: Dict):
    """(Mamba layers, attention layers)."""
    attn = sum(i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
               for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - attn, attn


def mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def mamba_matrix_params(cfg: Dict) -> int:
    """One Mamba mixer's four matrices: W_in, W_x, W_dt, W_out."""
    d, di = cfg["hidden_size"], d_inner(cfg)
    R, N = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    return d * 2 * di + di * (R + 2 * N) + R * di + di * d


def mamba_other_params(cfg: Dict) -> int:
    """What a Mamba mixer holds besides: convolution and bias, the three
    inner gains, b_dt, A_log, D."""
    di, R, N = d_inner(cfg), cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    return (cfg["mamba_d_conv"] + 1) * di + R + 2 * N + di + N * di + di


def attn_proj_params(cfg: Dict) -> int:
    """One attention layer's W_q, W_o and W_k, W_v."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    return d * hd * 2 * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def param_count(cfg: Dict) -> int:
    """Every parameter, from the sizes alone (nothing is allocated); the
    embedding once: it is the head too."""
    n_ssm, n_attn = layer_counts(cfg)
    d = cfg["hidden_size"]
    return (n_ssm * (mamba_matrix_params(cfg) + mamba_other_params(cfg))
            + n_attn * attn_proj_params(cfg)
            + cfg["num_hidden_layers"] * (mlp_params(cfg) + 2 * d)
            + cfg["vocab_size"] * d + d)


def fixed_params(cfg: Dict) -> int:
    """Parameters every token's pass multiplies by, the head left out."""
    n_ssm, n_attn = layer_counts(cfg)
    return (n_ssm * mamba_matrix_params(cfg)
            + n_attn * attn_proj_params(cfg)
            + cfg["num_hidden_layers"] * mlp_params(cfg))


def pair_flops(cfg: Dict) -> float:
    """Operations one (query, key) pair costs over all query heads: the
    score and the value product."""
    return 4.0 * cfg["num_attention_heads"] * head_dim(cfg)


def prefill_flops(cfg: Dict, length: int) -> float:
    """One prompt of ``length`` real tokens through every layer (no head:
    prefill predicts nothing)."""
    _s, n_attn = layer_counts(cfg)
    return (2.0 * fixed_params(cfg) * length
            + pair_flops(cfg) * n_attn * length * (length + 1) / 2.0)


def decode_row_flops(cfg: Dict, context: float) -> float:
    """One position of one beam with ``context`` cached tokens before and
    at it, head included."""
    _s, n_attn = layer_counts(cfg)
    return (2.0 * fixed_params(cfg) + pair_flops(cfg) * n_attn * context
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def request_flops(cfg: Dict, prompt_len: int, positions: int, beam: int
                  ) -> float:
    total = prefill_flops(cfg, prompt_len)
    for t in range(positions):
        total += beam * decode_row_flops(cfg, prompt_len + t + 1)
    return total


def counted_flops(cfg: Dict, counters: Dict) -> float:
    """Operations only the device's own counts can give (another
    architecture's routed experts): none here."""
    return 0.0


def state_bytes_per_beam(cfg: Dict) -> int:
    """What one beam carries between positions over all Mamba layers: the
    state (d_inner x d_state, float32) and the convolution's tail."""
    n_ssm, _a = layer_counts(cfg)
    di = d_inner(cfg)
    return n_ssm * di * (cfg["mamba_d_state"] * STATE_BYTES
                         + (cfg["mamba_d_conv"] - 1) * BYTES)


def step_weight_bytes(cfg: Dict, rows: float) -> float:
    """Weights one decode position must read at least: every one, once
    (the embedding's rows a token needs are inside the head's read)."""
    return float(BYTES * param_count(cfg))


def step_slot_bytes(cfg: Dict, prompt_len: float, gen_len: float, beam: int
                    ) -> float:
    """What one occupied slot's position must move at least besides the
    weights, with ``gen_len`` generated positions (this one in): each
    beam's recurrent state read and written, the prompt's keys and values
    once (the beams share them) and each beam's generated ones."""
    _s, n_attn = layer_counts(cfg)
    kv_dim = 2 * cfg["num_key_value_heads"] * head_dim(cfg)
    return (2.0 * beam * state_bytes_per_beam(cfg)
            + BYTES * kv_dim * n_attn * (prompt_len + beam * gen_len))


def derived_counters(cfg: Dict, counters: Dict) -> Dict:
    """Counters that are a device count times a size THE ARENA has:
    ``state_bytes_moved`` = the slot-beams whose state the steps updated
    (``state_rows``, counted on the device) x what one beam lane of the
    arena's state leaves holds (``kv_bytes_per_slot_state``: their shapes
    and dtypes as declared, over the slot's beams), read and written. A
    state kept in fewer bytes moves the number; lanes a step moves without
    need do not (they are not ``state_rows``): it is the share of the
    step's LEAST bytes that is state, at this traffic's occupancy.
    Nothing to count from gives nothing."""
    if "state_rows" not in counters \
            or "kv_bytes_per_slot_state" not in counters:
        return {}
    a_beam = counters["kv_bytes_per_slot_state"] // cfg["beam_size"]
    return {"state_bytes_moved": 2 * a_beam * counters["state_rows"]}
