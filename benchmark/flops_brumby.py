"""Operations and bytes Brumby-14B-Base's forward pass needs, from the
configuration file's sizes: what the mfu and roofline metrics of its cell
divide by.

Counted is what the algorithm needs at the LEAST: real prompt tokens (bucket
padding is not counted); **matrix products only** — the gates, norms, decays
and the normaliser's sums are not the MXU's and stay out of the numerator;
of a prompt's retention, a layer, the state once plus the lesser of the two
forms' products for that length (the attention form's causal half, or every
token's query read against the running state); each weight read once a
position; of the caches, each occupied slot's prompt state ONCE (its beams
share it) and the beams' own generated positions. A program that computes
padded tokens, reads idle slots' states, or reads a whole pool, therefore
reads LOW, never over 100 %. A multiply-add is 2 operations."""

from __future__ import annotations

from typing import Dict

BYTES = 2        # bfloat16 weights, prompt state, generated keys and values
F32 = 4          # the normaliser z and the gates' sums: float32


def state_dim(cfg: Dict) -> int:
    """D: a key's degree-2 features, head_dim (head_dim + 1) / 2."""
    return cfg["head_dim"] * (cfg["head_dim"] + 1) // 2


def proj_params(cfg: Dict) -> int:
    """One layer's W_q, W_o and W_k, W_v."""
    return cfg["hidden_size"] * cfg["head_dim"] * 2 * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg: Dict) -> int:
    """A layer: projections, the gates' projection and bias, SwiGLU, two
    norms of the stream and q_norm / k_norm."""
    d, KV = cfg["hidden_size"], cfg["num_key_value_heads"]
    return (proj_params(cfg) + d * KV + KV + mlp_params(cfg) + 2 * d
            + 2 * cfg["head_dim"])


def param_count(cfg: Dict) -> int:
    """Every parameter, from the sizes alone (nothing is allocated): the
    layers, the embedding and the untied head, the final norm."""
    d = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * cfg["vocab_size"] * d + d)


def fixed_params(cfg: Dict) -> int:
    """Parameters every token's pass multiplies by, the head left out."""
    return cfg["num_hidden_layers"] * (
        proj_params(cfg) + cfg["hidden_size"] * cfg["num_key_value_heads"]
        + mlp_params(cfg))


def retention_prefill_flops(cfg: Dict, length: int) -> float:
    """One layer's retention over a prompt of ``length`` tokens at the
    least: the state once (``phi(K)^T [V | 1]``, every key/value head) plus
    the lesser of the attention form's causal pairs (score and value
    product, every query head) and the recurrent form's query reads
    (``phi(q)^T S``, every query head and token)."""
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    D = state_dim(cfg)
    pairs = 4.0 * hd * H * length * (length + 1) / 2.0
    reads = 2.0 * H * D * hd * length
    return 2.0 * KV * D * hd * length + min(pairs, reads)


def prefill_flops(cfg: Dict, length: int) -> float:
    """One prompt of ``length`` real tokens through every layer (no head:
    prefill predicts nothing)."""
    return (2.0 * fixed_params(cfg) * length
            + cfg["num_hidden_layers"] * retention_prefill_flops(cfg, length))


def decode_row_flops(cfg: Dict, gen_len: float) -> float:
    """One position of one beam with ``gen_len`` generated positions (this
    one in), head included: the prompt's state read through the query's
    features and the beam's own positions, every layer."""
    H, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return (2.0 * fixed_params(cfg)
            + cfg["num_hidden_layers"] * (2.0 * H * state_dim(cfg) * hd
                                          + 4.0 * H * hd * gen_len)
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def request_flops(cfg: Dict, prompt_len: int, positions: int, beam: int
                  ) -> float:
    total = prefill_flops(cfg, prompt_len)
    for t in range(positions):
        total += beam * decode_row_flops(cfg, t + 1)
    return total


def counted_flops(cfg: Dict, counters: Dict) -> float:
    """Operations only the device's own counts can give (another
    architecture's routed experts): none here."""
    return 0.0


def state_bytes_per_slot(cfg: Dict) -> int:
    """A prompt's state over all layers: S (KV x D x hd) as stored and the
    normaliser z (KV x D, float32)."""
    return (cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * state_dim(cfg) * (cfg["head_dim"] * BYTES + F32))


def step_weight_bytes(cfg: Dict, rows: float) -> float:
    """Weights one decode position must read at least: the layers, the
    final norm and the head once, and the embedding's rows of the
    ``rows`` tokens it embeds."""
    d = cfg["hidden_size"]
    return BYTES * (cfg["num_hidden_layers"] * layer_params(cfg)
                    + cfg["vocab_size"] * d + d + rows * d)


def step_slot_bytes(cfg: Dict, prompt_len: float, gen_len: float, beam: int
                    ) -> float:
    """What one occupied slot's position must move at least besides the
    weights, with ``gen_len`` generated positions (this one in): the
    prompt's state once (the beams share it; ``prompt_len`` does not enter)
    and each beam's own positions' keys, values and gate sums."""
    KV, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    return (state_bytes_per_slot(cfg) + beam * gen_len
            * cfg["num_hidden_layers"] * KV * (2 * hd * BYTES + F32))


def derived_counters(cfg: Dict, counters: Dict) -> Dict:
    """Counters that are a device count times a size THE ARENA has:
    ``state_bytes_moved`` = the slot-layers whose prompt state a position
    read (``state_reads``, counted on the device) x what one layer of the
    arena's state leaves holds a slot (``kv_bytes_per_slot_state`` over
    the layers: their shapes and dtypes as declared). A state kept in
    fewer bytes moves the number; the idle slots a step reads without need
    do not (they are not ``state_reads``): it is the share of the step's
    LEAST bytes that is prompt state. Nothing to count from gives
    nothing."""
    if "state_reads" not in counters \
            or "kv_bytes_per_slot_state" not in counters:
        return {}
    a_layer = counters["kv_bytes_per_slot_state"] // cfg["num_hidden_layers"]
    return {"state_bytes_moved": a_layer * counters["state_reads"]}
