"""Trinity-Mini (``arch="afmoe"``): a decoder whose attention layers come in
two kinds — ``sliding_attention`` (a 2,048-token window, rotary positions)
and ``full_attention`` (the whole context, no positions at all), every 4th —
over grouped-query heads, with leading dense SwiGLU layers and routed-expert
layers of many small experts after them, as its published ``config.json``
(``model_type: afmoe``) describes it (config.AfmoeConfig holds the keys).

Plain functions over a parameter tree, as model/axk1.py, whose pieces this
module shares (``rms_norm``, ``mm``, ``rotate``, ``swiglu``, the grouped
products ``routed_experts``): the slot engine (decode/slot_model.py) calls
:func:`prefill` once a request and :func:`decode_step` once a position. The
layer equations, ``x`` the residual stream:

- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``, float32. Embedding:
  ``x = E[token] * sqrt(hidden_size)`` (``mup_enabled``).
- Block: ``x = x + N2(Attn(N1(x)))``; ``x = x + N4(MLP(N3(x)))`` — four
  norms a layer. Final RMSNorm, untied head, log-softmax.
- Attention: ``q = h W_q`` (H heads), ``k = h W_k``, ``v = h W_v`` (KV
  heads), ``g = h W_g``; q and k RMSNorm'ed over their head dim (one gain
  vector each); query head i reads key/value head ``i // (H / KV)``;
  scores ``q k^T / sqrt(head_dim)``; output ``(P v) * sigmoid(g)``, then
  ``W_o``. A window layer rotates q and k (``rope_theta`` over the whole
  head, pairs (i, i + head_dim/2), no scaling) and lets position i see j
  with ``0 <= i - j < sliding_window``; a full layer rotates nothing and
  sees every ``j <= i``.
  **Cached per token per layer: ``[k | v]`` after the norm (and the
  rotation, where there is one).** A full layer keeps a prompt whole; a
  window layer only its last ``sliding_window`` positions, as a ring that
  holds position p at entry ``p mod sliding_window``
  (:func:`ring_positions`, :func:`window_ring`). A PROMPT's cache lies
  position-minor, keys and values apart, an array a layer
  (:func:`prompt_layout`): a decode position's two products then read it
  as it lies (with positions before the 1,024 values the chip's compiler
  copied the whole arena into this layout at every step dispatch, and
  copied every slice it took of a leaf that stacks layers or [k | v]).
  Prefill scores a block of queries against the keys it can see: the keys
  up to its span's end on a full layer, ``window + block`` keys on a window
  layer (a band, never P x P).
- Dense layer: ``W_down(silu(h W_gate) * (h W_up))``.
- Expert layer: ``s = sigmoid(h W_r)`` over ALL ``num_experts``; chosen =
  top-k of ``s + b`` (``b`` chooses and never weighs; equal scores go to
  the lower index); ``w_e = s_e / (sum_chosen s + 1e-20) * route_scale``;
  output = shared expert + the weighted experts this engine holds. No
  token is dropped (model/axk1.routed_experts).

Compute runs in ``dtype`` (bfloat16 on the chip) with float32 accumulation;
norms, softmax, router scores and the log-softmax are float32.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from fira_tpu.config import FULL, SLIDING, AfmoeConfig
from fira_tpu.model.axk1 import (COUNTERS as MOE_COUNTERS, mm, moe_counters,
                                 rms_norm, rotate, routed_experts, swiglu)

# the counters a call returns, in this order: the expert layer's four
# (model/axk1.COUNTERS), then the keys a step's attention is ASKED to cover
# (occupied slots x layers x the keys inside window or context) and the
# same with every layer full
COUNTERS = MOE_COUNTERS + ("attn_keys_read", "attn_keys_context")

# queries a block of prefill attention: no (heads, P, P) tensor exists
ATTN_Q_BLOCK = 128

# deviation of the selection bias weights from a seed draw (it moves some
# picks; a trained model's comes from its load balancing)
ROUTER_BIAS_STD = 0.02


# --- parameters -----------------------------------------------------------

def layer_is_dense(lm: AfmoeConfig, layer: int) -> bool:
    return layer < lm.num_dense_layers


def layer_window(lm: AfmoeConfig, layer: int):
    """The layer's window, None on a full layer."""
    return lm.sliding_window if lm.layer_types[layer] == SLIDING else None


def layer_rotates(lm: AfmoeConfig, layer: int) -> bool:
    """Rotary positions go with the window: a full layer has none."""
    return lm.layer_types[layer] == SLIDING


def param_shapes(lm: AfmoeConfig) -> Dict:
    """{name: shape} tree of the parameters this engine holds."""
    d, H, KV, hd = (lm.hidden_size, lm.num_attention_heads,
                    lm.num_key_value_heads, lm.head_dim)
    m, E = lm.moe_intermediate_size, lm.experts_held
    layers = []
    for i in range(lm.num_hidden_layers):
        p = {
            "attn_norm": (d,), "post_attn_norm": (d,), "mlp_norm": (d,),
            "post_mlp_norm": (d,), "w_q": (d, H * hd), "w_k": (d, KV * hd),
            "w_v": (d, KV * hd), "w_g": (d, H * hd), "w_o": (H * hd, d),
            "q_norm": (hd,), "k_norm": (hd,),
        }
        if layer_is_dense(lm, i):
            I = lm.intermediate_size
            p.update(w_gate=(d, I), w_up=(d, I), w_down=(I, d))
        else:
            ms = m * lm.num_shared_experts
            p.update(router=(d, lm.num_experts),
                     router_bias=(lm.num_experts,),
                     shared_gate=(d, ms), shared_up=(d, ms),
                     shared_down=(ms, d), experts_gate=(E, d, m),
                     experts_up=(E, d, m), experts_down=(E, m, d))
        layers.append(p)
    return {"embed": (lm.vocab_size, d), "layers": layers,
            "final_norm": (d,), "head": (d, lm.vocab_size)}


def init_params(lm: AfmoeConfig, seed: int, dtype=jnp.bfloat16):
    """Seeded random weights, in ``dtype`` from creation: matrices normal
    with deviation fan_in^-0.5, norm gains near 1, the selection bias
    N(0, ROUTER_BIAS_STD^2), the embedding with deviation hidden^-0.5 (so
    the sqrt(hidden) the stream is scaled by leaves it at unit size). One
    jitted call."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(lm), is_leaf=lambda s: isinstance(s, tuple))

    def make(key):
        out = []
        for i, (path, shape) in enumerate(paths):
            name = path[-1].key
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name == "router_bias":
                w = ROUTER_BIAS_STD * w
            elif len(shape) == 1:
                w = 1.0 + 0.1 * w
            elif name == "embed":
                w = w * (lm.hidden_size ** -0.5)
            else:
                w = w * (shape[-2] ** -0.5)
            out.append(w.astype(dtype))
        return out
    built = jax.jit(make)(jax.random.PRNGKey(seed))  # firacheck: allow[DRIVER-REG] one set-up call that builds the weights on the device; this module dispatches nothing in a loop — the engine (decode/engine.py, registered) jits and drives its programs
    return jax.tree_util.tree_unflatten(treedef, built)


# --- pieces ---------------------------------------------------------------

def rope_cos_sin(lm: AfmoeConfig, positions):
    """(..., head_dim) cos and sin at integer ``positions``, float32: plain
    rotary frequencies ``theta^(-2i / head_dim)``, no scaling."""
    hd = lm.head_dim
    inv_freq = float(lm.rope_theta) ** (
        -jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def embed(params, lm: AfmoeConfig, tokens, dtype):
    x = params["embed"][tokens]
    if lm.mup_enabled:
        x = x.astype(jnp.float32) * math.sqrt(lm.hidden_size)
    return x.astype(dtype)


def _projections(p, h, cos, sin, lm: AfmoeConfig, dtype):
    """h (..., d) normed -> q (..., KV, H/KV, hd), what is cached [k | v]
    (..., kv_dim), the output gate's logits (..., H * hd). ``cos`` / ``sin``
    (..., hd), None on a layer that rotates nothing."""
    H, KV, hd = lm.num_attention_heads, lm.num_key_value_heads, lm.head_dim
    lead = h.shape[:-1]
    q = rms_norm(mm(h, p["w_q"], dtype).reshape(lead + (H, hd)),
                 p["q_norm"], lm.rms_norm_eps)
    k = rms_norm(mm(h, p["w_k"], dtype).reshape(lead + (KV, hd)),
                 p["k_norm"], lm.rms_norm_eps)
    if cos is not None:
        q = rotate(q, cos[..., None, :], sin[..., None, :])
        k = rotate(k, cos[..., None, :], sin[..., None, :])
    kv = jnp.concatenate([k.astype(dtype).reshape(lead + (KV * hd,)),
                          mm(h, p["w_v"], dtype, dtype)], -1)
    return (q.astype(dtype).reshape(lead + (KV, H // KV, hd)), kv,
            mm(h, p["w_g"], dtype, dtype))


def _keys_values(kv, lm: AfmoeConfig):
    """What is cached (..., kv_dim) -> keys, values (..., KV, hd)."""
    KV, hd = lm.num_key_value_heads, lm.head_dim
    lead = kv.shape[:-1]
    return (kv[..., :KV * hd].reshape(lead + (KV, hd)),
            kv[..., KV * hd:].reshape(lead + (KV, hd)))


def _gate_and_project(p, o, g, dtype):
    """The heads' output (..., H * hd) times sigmoid of the gate, then
    ``W_o``."""
    o = o.astype(jnp.float32) * jax.nn.sigmoid(g.astype(jnp.float32))
    return mm(o, p["w_o"], dtype)


def attend_prefill(q, k, v, window, dtype):
    """Causal attention of a batch of prompts, a block of queries at a
    time: q (B, P, KV, G, hd), k and v (B, P, KV, hd) -> the heads' output
    (B, P, KV * G * hd) in ``dtype``. ``window`` None: every j <= i (up to
    four spans of query blocks, each with its own key extent); else a band
    of ``window + block`` keys a block, never P x P. Keys past a prompt's
    end lie after every real query, so the causal mask alone keeps them
    out of real rows. Shared with model/jamba.py (one key/value head, no
    window)."""
    B, P, KV, G, hd = q.shape
    Qb = min(ATTN_Q_BLOCK, P)
    if P % Qb:
        raise ValueError(f"a prompt bucket of {P} tokens is not a whole "
                         f"number of {Qb}-query attention blocks")
    scale = hd ** -0.5

    def span(first: int, blocks: int, keys: int, first_key):
        """``blocks`` query blocks from block ``first``, each against
        ``keys`` keys from ``first_key(block)``: the keys a query cannot
        see for its position are not even scored."""
        def block(i):
            at = first_key(i)
            qs = jax.lax.dynamic_slice_in_dim(q, i * Qb, Qb, 1)
            ks = jax.lax.dynamic_slice_in_dim(k, at, keys, 1)
            vs = jax.lax.dynamic_slice_in_dim(v, at, keys, 1)
            s = jnp.einsum("bqngd,bknd->bngqk", qs, ks,
                           preferred_element_type=jnp.float32)
            back = (i * Qb + jnp.arange(Qb))[:, None] \
                - (at + jnp.arange(keys))[None, :]
            seen = back >= 0
            if window is not None:
                seen = seen & (back < window)
            s = jnp.where(seen, s * scale, -jnp.inf)
            # the softmax's division waits until after the values'
            # product, as in model/axk1.mla_prefill
            e = jnp.exp(s - jnp.max(s, -1, keepdims=True))
            total = jnp.sum(e, -1)                          # (B, n, g, Qb)
            o = jnp.einsum("bngqk,bknd->bqngd", e.astype(dtype), vs,
                           preferred_element_type=jnp.float32)
            return (o / jnp.transpose(total, (0, 3, 1, 2))[..., None]
                    ).astype(dtype)
        o = jax.lax.map(block, first + jnp.arange(blocks))
        return jnp.moveaxis(o, 0, 1).reshape(B, blocks * Qb, KV * G * hd)

    n_blocks = P // Qb
    if window is not None:
        # a band: a block's last query sees back to its first key, its
        # first query as far as window - 1 before itself
        keys = min(P, window + Qb)
        return span(0, n_blocks, keys, lambda i: jnp.clip(
            (i + 1) * Qb - keys, 0, P - keys))
    # up to four spans of query blocks, each with its own key extent:
    # the last scores every key, the first a quarter of them
    per = -(-n_blocks // min(4, n_blocks))
    return jnp.concatenate(
        [span(at, min(per, n_blocks - at),
              (at + min(per, n_blocks - at)) * Qb, lambda i: 0)
         for at in range(0, n_blocks, per)], axis=1)


def attention_prefill(p, h, cos, sin, window, lm: AfmoeConfig, dtype):
    """Causal attention over a batch of prompts (:func:`attend_prefill`).
    h (B, P, d) normed; ``window`` None on a full layer; ``cos`` / ``sin``
    None on a layer that rotates nothing. -> (attention output (B, P, d)
    float32, what is cached (B, P, kv_dim))."""
    q, kv, g = _projections(p, h, cos, sin, lm, dtype)
    k, v = _keys_values(kv, lm)
    with jax.named_scope("attn.full.prefill" if window is None
                         else "attn.window.prefill"):
        o = attend_prefill(q, k, v, window, dtype)
        return _gate_and_project(p, o, g, dtype), kv


def ring_positions(prompt_len, window: int):
    """The prompt position each entry of a slot's ring holds: entry r holds
    the LAST position p < prompt_len with ``p mod window == r``; negative
    where the prompt is too short to have filled it. prompt_len (S,) ->
    (S, window) int32."""
    last = prompt_len[:, None] - 1
    return last - (last - jnp.arange(window)[None, :]) % window


def window_ring(kv, lengths, window: int):
    """Each prompt's last ``window`` positions in RING order: entry r is
    position :func:`ring_positions` says (whatever lies at position 0 where
    the prompt never reached entry r: a decode position masks it). A bucket
    shorter than the window fills the ring's first entries only, where
    entry r is position r. kv (B, P, c) -> (B, min(P, window), c)."""
    n = min(kv.shape[1], window)
    idx = jnp.maximum(ring_positions(lengths, window)[:, :n], 0)
    return jnp.take_along_axis(kv, idx[..., None], axis=1)


def prompt_layout(kv):
    """What a layer caches of prompts (B, P, kv_dim) -> (keys, values),
    each (B, kv_dim / 2, P): apart, positions last."""
    B, P, c = kv.shape
    kv = jnp.transpose(kv.reshape(B, P, 2, c // 2), (2, 0, 3, 1))
    return kv[0], kv[1]


def attend_decode(q, prompt_kv, prompt_seen, gen_kv, gen_seen, dtype):
    """One position of every beam of every slot. q (S, K, KV, G, hd);
    prompt_kv: keys and values (S, KV * hd, P) each (:func:`prompt_layout`),
    shared by a slot's beams, entry j seen where prompt_seen (S, P);
    gen_kv (S, K, T, 2 * KV * hd) with this position's in; gen_seen (S, T).
    -> the heads' output (S, K, KV * G * hd) float32. Shared with
    model/jamba.py."""
    S, K, KV, _G, hd = q.shape
    k_p, v_p = (x.reshape(S, KV, hd, -1) for x in prompt_kv)
    lead = gen_kv.shape[:-1]
    k_g = gen_kv[..., :KV * hd].reshape(lead + (KV, hd))
    v_g = gen_kv[..., KV * hd:].reshape(lead + (KV, hd))
    scale = hd ** -0.5
    s_p = jnp.einsum("skngd,sndp->skngp", q, k_p,
                     preferred_element_type=jnp.float32)
    s_g = jnp.einsum("skngd,sktnd->skngt", q, k_g,
                     preferred_element_type=jnp.float32)
    s_p = jnp.where(prompt_seen[:, None, None, None, :], s_p * scale,
                    -jnp.inf)
    s_g = jnp.where(gen_seen[:, None, None, None, :], s_g * scale, -jnp.inf)
    # one softmax over [prompt | generated] without joining the two, as
    # model/axk1.mla_decode (this position's own key is always seen)
    top = jnp.maximum(jnp.max(s_p, -1), jnp.max(s_g, -1))[..., None]
    e_p, e_g = jnp.exp(s_p - top), jnp.exp(s_g - top)
    denom = jnp.sum(e_p, -1) + jnp.sum(e_g, -1)
    o = jnp.einsum("skngp,sndp->skngd", e_p.astype(dtype), v_p,
                   preferred_element_type=jnp.float32)
    o = o + jnp.einsum("skngt,sktnd->skngd", e_g.astype(dtype), v_g,
                       preferred_element_type=jnp.float32)
    return (o / denom[..., None]).reshape(S, K, -1)


def attention_decode(p, q, g, prompt_kv, prompt_seen, gen_kv, gen_seen,
                     lm: AfmoeConfig, dtype):
    """:func:`attend_decode`, then the output gate and ``W_o`` -> (S, K,
    d) float32."""
    o = attend_decode(q, prompt_kv, prompt_seen, gen_kv, gen_seen, dtype)
    return _gate_and_project(p, o, g, dtype)


def route(scores, bias, lm: AfmoeConfig):
    """scores (N, num_experts) float32, bias (num_experts,) -> (ids (N, k),
    weights (N, k)): top-k of ``scores + bias``, weighed by ``scores``
    alone. ``n_group = topk_group = 1``: no group limit. Equal sums go to
    the lower index."""
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32)[None, :],
                           lm.num_experts_per_tok)
    w = jnp.take_along_axis(scores, ids, 1)
    if lm.route_norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return ids, w * lm.route_scale


def moe_layer(p, x, valid, lm: AfmoeConfig, dtype):
    """x (N, d) normed -> (shared + held routed part (N, d) float32, the
    expert layer's four counters int32)."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        ids, weights = route(scores, p["router_bias"], lm)
    with jax.named_scope("moe.shared"):
        shared = swiglu(x, p["shared_gate"], p["shared_up"],
                        p["shared_down"], dtype)
    with jax.named_scope("moe.experts"):
        routed, loads = routed_experts(p, x, ids, weights, valid, lm, dtype)
    return shared + routed, moe_counters(lm, valid, loads)


def _mlp(p, h, valid, layer: int, lm: AfmoeConfig, dtype):
    """h (N, d) normed -> (output (N, d) float32, the four counters)."""
    if layer_is_dense(lm, layer):
        return (swiglu(h, p["w_gate"], p["w_up"], p["w_down"], dtype),
                jnp.zeros((len(MOE_COUNTERS),), jnp.int32))
    return moe_layer(p, h, valid, lm, dtype)


def _residual(x, add, gain, lm: AfmoeConfig, dtype):
    """``x + RMSNorm(add)``: the norm after a sub-block (N2, N4)."""
    return (x.astype(jnp.float32)
            + rms_norm(add, gain, lm.rms_norm_eps)).astype(dtype)


def _block_mlp(p, x, valid, layer: int, lm: AfmoeConfig, dtype):
    """The second half of a block over rows x (..., d) -> (x, counters)."""
    h = rms_norm(x, p["mlp_norm"], lm.rms_norm_eps).astype(dtype)
    f, c = _mlp(p, h.reshape(-1, h.shape[-1]), valid, layer, lm, dtype)
    return _residual(x, f.reshape(x.shape), p["post_mlp_norm"], lm,
                     dtype), c


# --- the two programs -----------------------------------------------------

def _trunk(params, lm: AfmoeConfig, tokens, lengths, dtype):
    """Every layer over whole prompts. -> (the last residual stream
    (B, P, d), what each layer caches [(B, P, kv_dim)] * L, counters)."""
    B, P = tokens.shape
    cos, sin = rope_cos_sin(lm, jnp.arange(P))
    valid = (jnp.arange(P)[None, :] < lengths[:, None]).reshape(-1)
    x = embed(params, lm, tokens, dtype)
    kvs, counters = [], jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    for i, p in enumerate(params["layers"]):
        rot = (cos, sin) if layer_rotates(lm, i) else (None, None)
        h = rms_norm(x, p["attn_norm"], lm.rms_norm_eps).astype(dtype)
        a, kv = attention_prefill(p, h, *rot, layer_window(lm, i), lm,
                                  dtype)
        kvs.append(kv)
        x = _residual(x, a, p["post_attn_norm"], lm, dtype)
        x, c = _block_mlp(p, x, valid, i, lm, dtype)
        counters = counters + c
    return x, kvs, counters


def lm_head(params, x, lm: AfmoeConfig, dtype):
    with jax.named_scope("lm_head"):
        h = rms_norm(x, params["final_norm"], lm.rms_norm_eps)
        return jax.nn.log_softmax(mm(h, params["head"], dtype), -1)


def _all_counters(moe, keys_read=0, keys_context=0):
    return jnp.concatenate([moe, jnp.stack([
        jnp.asarray(keys_read, jnp.int32),
        jnp.asarray(keys_context, jnp.int32)])])


def prefill(params, lm: AfmoeConfig, tokens, lengths, dtype
            ) -> Tuple[List, List, jnp.ndarray]:
    """tokens (B, P) int32, real up to lengths (B,). -> (a (keys, values)
    pair a full layer, the prompts whole: (B, kv_dim / 2, P) each; a pair
    a window layer, the rings: (B, kv_dim / 2, min(P, window)), only each
    prompt's last ``window`` positions, in ring order; counters). No
    logits: the first prediction is the first decode position's."""
    _x, kvs, counters = _trunk(params, lm, tokens, lengths, dtype)
    full = [prompt_layout(kvs[i]) for i in lm.layers_of(FULL)]
    rings = [prompt_layout(window_ring(kvs[i], lengths, lm.sliding_window))
             for i in lm.layers_of(SLIDING)]
    return full, rings, _all_counters(counters)


def forward_logp(params, lm: AfmoeConfig, tokens, lengths, dtype):
    """The whole forward pass without a cache: log-probabilities (B, P, V)
    of the token after each position."""
    x, _kvs, _counters = _trunk(params, lm, tokens, lengths, dtype)
    return lm_head(params, x, lm, dtype)


def decode_step(params, lm: AfmoeConfig, tok, gen_pos, prompt_full,
                prompt_win, prompt_len, pool, block_tab, active, dtype):
    """One position of every beam of every slot. tok (S, K) int32: each
    beam's token at its slot's generated position gen_pos (S,), absolute
    position prompt_len + gen_pos; prompt_full: a (keys, values) pair a
    full layer, (S, kv_dim / 2, P_max) each: the prompts, whole; prompt_win:
    a pair a window layer, (S, kv_dim / 2, window): the rings; pool (L, blocks, K, block, kv_dim): every
    layer's generated positions; block_tab (S, W), already the sentinel
    (= blocks) in rows that must neither read nor write; active (S,) bool.
    -> (log-probabilities (S, K, V) float32, pool, counters)."""
    S, K = tok.shape
    BS, Wt = pool.shape[3], block_tab.shape[1]
    W = lm.sliding_window
    cos, sin = rope_cos_sin(lm, prompt_len + gen_pos)            # (S, hd)
    cos, sin = cos[:, None, :], sin[:, None, :]
    blk = jnp.take_along_axis(block_tab, (gen_pos // BS)[:, None], 1)[:, 0]
    off = gen_pos % BS
    back = gen_pos[:, None] - jnp.arange(Wt * BS)[None, :]
    gen_seen = {None: back >= 0, W: (back >= 0) & (back < W)}
    # a prompt position p is this position's key while (prompt_len +
    # gen_pos) - p < window: the ring's oldest entries go dark one by one
    # as the generated position advances
    ring = ring_positions(prompt_len, W)
    prompt_seen = {
        None: jnp.arange(prompt_full[0][0].shape[-1])[None, :]
        < prompt_len[:, None],
        W: (ring >= 0) & ((prompt_len + gen_pos)[:, None] - ring < W)}
    context = jnp.where(active, prompt_len + gen_pos + 1, 0)
    n_full = len(lm.layers_of(FULL))
    keys_read = jnp.sum(n_full * context + (lm.num_hidden_layers - n_full)
                        * jnp.minimum(context, W))
    valid = jnp.repeat(active, K)
    x = embed(params, lm, tok, dtype)
    counters = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    next_full, next_win = iter(prompt_full), iter(prompt_win)
    for i, p in enumerate(params["layers"]):
        window = layer_window(lm, i)
        prompt = next(next_full if window is None else next_win)
        h = rms_norm(x, p["attn_norm"], lm.rms_norm_eps).astype(dtype)
        q, kv, g = _projections(
            p, h, *((cos, sin) if layer_rotates(lm, i) else (None, None)),
            lm, dtype)
        pool = pool.at[i, blk, :, off, :].set(kv, mode="drop")
        gen = pool[i][block_tab]                  # (S, Wt, K, BS, kv_dim)
        gen = jnp.moveaxis(gen, 2, 1).reshape(S, K, Wt * BS, -1)
        with jax.named_scope("attn.full.decode" if window is None
                             else "attn.window.decode"):
            a = attention_decode(p, q, g, prompt, prompt_seen[window], gen,
                                 gen_seen[window], lm, dtype)
        x = _residual(x, a, p["post_attn_norm"], lm, dtype)
        x, c = _block_mlp(p, x, valid, i, lm, dtype)
        counters = counters + c
    return (lm_head(params, x, lm, dtype), pool,
            _all_counters(counters, keys_read,
                          lm.num_hidden_layers * jnp.sum(context)))
