"""LFM2-8B-A1B (``arch="lfm2"``): a decoder whose token mixers come in two
kinds — a GATED SHORT CONVOLUTION (``"conv"``: three taps, no state space,
no activation) and grouped-query attention with q/k norms and rotary
positions (``"full_attention"``) — each followed by a dense SwiGLU in the
leading ``num_dense_layers`` layers and by 32 sigmoid-routed experts with a
selection bias after them, no shared expert, as its published
``config.json`` (``model_type: lfm2_moe``) describes it (config.Lfm2Config
holds the keys).

Plain functions over a parameter tree, as model/jamba.py, whose pieces this
module shares (``rms_norm``, ``mm``, ``rotate``, ``swiglu`` and the grouped
products ``routed_experts`` of model/axk1; the router ``route``, the rotary
tables, the blocked causal attention and the decode attention of
model/afmoe; the tied head of model/jamba): the slot engine
(decode/slot_model.py) calls :func:`prefill` once a request and
:func:`decode_step` once a position. The layer equations, ``x`` the
residual stream, ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``:

- Block: ``x = x + Mixer(N1(x))``; ``x = x + FFN(N2(x))``. Final RMSNorm,
  logits ``h E^T`` with the embedding matrix (tied head), log-softmax.
- Short convolution, token t (d = hidden_size, taps = ``conv_L_cache``)::

      [B_t | C_t | u_t] = h_t W_in            W_in (d, 3d), split so
      v_t               = B_t * u_t
      c_t               = sum_j w_conv[j] * v_{t-(taps-1)+j}   v_{<0} = 0
      out_t             = (C_t * c_t) W_out

  **Carried between positions: the tail ``(v_{t-1}, v_t)`` a beam lane**
  (taps - 1 = 2 vectors of d), d LAST (``(taps - 1, rows, d)``): the chip
  tiles an array's last two axes, and a trailing axis of 2 taps would be
  padded to 128.
- Attention layer: ``q = RMSNorm_head(h W_q)``, ``k = RMSNorm_head(h W_k)``
  (one gain vector over ``head_dim`` each), both rotated (``rope_theta``,
  pairs (i, i + head_dim/2), positions from 0), ``v = h W_v``; query head i
  reads key/value head ``i // (H / KV)``; scores ``q k^T / sqrt(head_dim)``,
  causal softmax, then ``W_o``. Cached per token: ``[k | v]`` after norm and
  rotation, a prompt's positions last and keys and values apart (as
  model/jamba.attention_prefill hands them over).
- Dense layer: ``W_down(silu(h W_gate) * (h W_up))``.
- Expert layer: ``s = sigmoid(h W_r)`` over all ``num_experts``; chosen =
  top-k of ``s + b`` (``b`` the expert bias: it chooses and never weighs);
  ``w_e = s_e / sum_chosen s * routed_scaling_factor``; output = the
  weighted experts, no shared expert. No token is dropped.

Prefill convolves a padded bucket at once: padded positions lie after every
real one, so no real position reads them, and the tail a request hands over
is taken at ITS length. Compute runs in ``dtype`` (bfloat16 on the chip)
with float32 accumulation; norms, router, softmax and log-softmax are
float32. ``v_t`` is rounded to ``dtype`` before the convolution reads it, in
prefill and in decode alike, so that the tail a step reads is the value the
prompt's own convolution read.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from fira_tpu.config import CONV, Lfm2Config
from fira_tpu.model.afmoe import (attend_decode, attend_prefill,
                                  rope_cos_sin, route)
from fira_tpu.model.axk1 import (COUNTERS as MOE_COUNTERS, mm, moe_counters,
                                 rms_norm, rotate, routed_experts, swiglu)
from fira_tpu.model.jamba import lm_head

# the counters a call returns, in this order: the expert layer's four
# (model/axk1.COUNTERS), then the held experts that at least one row of a
# DECODE position routed to, summed over expert layers and positions — what
# a step must read of the experts (a prefill adds 0 here: its bytes are not
# the step's)
COUNTERS = MOE_COUNTERS + ("moe_experts_read",)

# deviation of the expert bias from a seed draw: it changes the chosen set
# of 15-22 % of the rows at the published widths (8 draws over unit-size
# rows), so that a router that left it out would choose other experts for
# many tokens (a trained model's comes from its load balancing)
EXPERT_BIAS_STD = 0.01


# --- parameters -----------------------------------------------------------

def layer_is_dense(lm: Lfm2Config, layer: int) -> bool:
    return layer < lm.num_dense_layers


def layer_is_conv(lm: Lfm2Config, layer: int) -> bool:
    return lm.layer_types[layer] == CONV


def param_shapes(lm: Lfm2Config) -> Dict:
    """{name: shape} tree of the parameters this engine holds."""
    d, H, KV, hd = (lm.hidden_size, lm.num_attention_heads,
                    lm.num_key_value_heads, lm.head_dim)
    m, E = lm.moe_intermediate_size, lm.num_experts
    layers = []
    for i in range(lm.num_hidden_layers):
        p = {"op_norm": (d,), "ffn_norm": (d,)}
        if layer_is_conv(lm, i):
            p.update(conv_in=(d, 3 * d), conv_w=(lm.conv_L_cache, d),
                     conv_out=(d, d))
        else:
            p.update(w_q=(d, H * hd), w_k=(d, KV * hd), w_v=(d, KV * hd),
                     w_o=(H * hd, d), q_norm=(hd,), k_norm=(hd,))
        if layer_is_dense(lm, i):
            I = lm.intermediate_size
            p.update(w_gate=(d, I), w_up=(d, I), w_down=(I, d))
        else:
            p.update(router=(d, lm.num_experts),
                     expert_bias=(lm.num_experts,), experts_gate=(E, d, m),
                     experts_up=(E, d, m), experts_down=(E, m, d))
        layers.append(p)
    return {"embed": (lm.vocab_size, d), "layers": layers,
            "final_norm": (d,)}


def init_params(lm: Lfm2Config, seed: int, dtype=jnp.bfloat16):
    """Seeded random weights, in ``dtype`` from creation: matrices normal
    with deviation fan_in^-0.5 (the convolution's fan-in is its taps), gains
    1 + 0.1 N(0, 1), the expert bias N(0, EXPERT_BIAS_STD^2), embedding rows
    N(0, 1 / hidden): it is the head too. One jitted call."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(lm), is_leaf=lambda s: isinstance(s, tuple))

    def make(key):
        out = []
        for i, (path, shape) in enumerate(paths):
            name = path[-1].key
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name == "expert_bias":
                w = EXPERT_BIAS_STD * w
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


# --- the short convolution ------------------------------------------------

def _gates_and_input(p, h, dtype):
    """h (..., d) normed -> (C (..., d), v = B * u (..., d)), both
    ``dtype``."""
    d = h.shape[-1]
    bcu = mm(h, p["conv_in"], dtype, dtype)
    v = (bcu[..., :d].astype(jnp.float32)
         * bcu[..., 2 * d:].astype(jnp.float32)).astype(dtype)
    return bcu[..., d:2 * d], v


def _convolve(p, taps, gate, dtype):
    """The depthwise convolution from its taps, oldest first, each (...,
    d), gated by C -> the mixer's output (..., d) float32."""
    w = p["conv_w"].astype(jnp.float32)
    acc = sum(w[j] * v.astype(jnp.float32) for j, v in enumerate(taps))
    return mm(gate.astype(jnp.float32) * acc, p["conv_out"], dtype)


def conv_prefill(p, h, lengths, lm: Lfm2Config, dtype):
    """h (B, P, d) normed, real up to lengths (B,). -> (mixer output
    (B, P, d) float32, the tail at each prompt's OWN length: v at lengths -
    (taps - 1) .. lengths - 1, (taps - 1, B, d) ``dtype``, zeros before the
    prompt's start)."""
    P, n = h.shape[1], lm.conv_L_cache - 1
    with jax.named_scope("conv.prefill"):
        gate, v = _gates_and_input(p, h, dtype)
        padded = jnp.pad(v, ((0, 0), (n, 0), (0, 0)))     # v_s at s + n
        tail = jnp.moveaxis(jax.vmap(
            lambda v_b, at: jax.lax.dynamic_slice_in_dim(v_b, at, n, 0)
        )(padded, lengths), 1, 0)
        return _convolve(p, [padded[:, j:j + P] for j in range(n + 1)],
                         gate, dtype), tail


def conv_step(p, h, tail, active, dtype):
    """One position of n rows (a row a beam). h (n, d) normed; tail
    (taps - 1, n, d): the tail each row CONTINUES FROM (its parent's);
    active (n,) bool. -> (mixer output (n, d) float32, the tail with v_t
    shifted in; a row that is not active keeps its own)."""
    with jax.named_scope("conv.step"):
        gate, v = _gates_and_input(p, h, dtype)
        new_tail = jnp.where(active[None, :, None],
                             jnp.concatenate([tail[1:], v[None]], 0), tail)
        return _convolve(p, list(tail) + [v], gate, dtype), new_tail


# --- attention, feed-forward ----------------------------------------------

def _qkv(p, h, cos, sin, lm: Lfm2Config, dtype):
    """h (..., d) normed; cos / sin (..., hd) -> q (..., KV, H / KV, hd),
    keys and values (..., KV * hd) each, ``dtype``: q and k normed a head,
    then rotated."""
    H, KV, hd = lm.num_attention_heads, lm.num_key_value_heads, lm.head_dim
    lead = h.shape[:-1]
    q = rms_norm(mm(h, p["w_q"], dtype).reshape(lead + (H, hd)),
                 p["q_norm"], lm.norm_eps)
    k = rms_norm(mm(h, p["w_k"], dtype).reshape(lead + (KV, hd)),
                 p["k_norm"], lm.norm_eps)
    q = rotate(q, cos[..., None, :], sin[..., None, :])
    k = rotate(k, cos[..., None, :], sin[..., None, :])
    return (q.astype(dtype).reshape(lead + (KV, H // KV, hd)),
            k.astype(dtype).reshape(lead + (KV * hd,)),
            mm(h, p["w_v"], dtype, dtype))


def attention_prefill(p, h, cos, sin, lm: Lfm2Config, dtype):
    """h (B, P, d) normed -> (attention output (B, P, d) float32, what is
    cached: keys and values (B, KV * hd, P) each, positions last)."""
    KV, hd = lm.num_key_value_heads, lm.head_dim
    q, k, v = _qkv(p, h, cos, sin, lm, dtype)
    heads = k.shape[:-1] + (KV, hd)
    with jax.named_scope("attn.full.prefill"):
        o = attend_prefill(q, k.reshape(heads), v.reshape(heads), None,
                           dtype)
        return (mm(o, p["w_o"], dtype),
                (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)))


def moe_layer(p, x, valid, lm: Lfm2Config, dtype):
    """x (N, d) normed -> (the weighted experts (N, d) float32, COUNTERS'
    five int32). No shared expert."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        ids, weights = route(scores, p["expert_bias"], lm)
    with jax.named_scope("moe.experts"):
        out, loads = routed_experts(p, x, ids, weights, valid, lm, dtype)
    return out, jnp.concatenate([moe_counters(lm, valid, loads),
                                 jnp.sum(loads > 0, dtype=jnp.int32)[None]])


def _ffn(p, x, valid, layer: int, lm: Lfm2Config, dtype):
    """The second half of a block over the residual stream x (..., d) ->
    (x, counters)."""
    h = rms_norm(x, p["ffn_norm"], lm.norm_eps).astype(dtype)
    h = h.reshape(-1, h.shape[-1])
    if layer_is_dense(lm, layer):
        with jax.named_scope("mlp"):
            f = swiglu(h, p["w_gate"], p["w_up"], p["w_down"], dtype)
        c = jnp.zeros((len(COUNTERS),), jnp.int32)
    else:
        f, c = moe_layer(p, h, valid, lm, dtype)
    return (x.astype(jnp.float32) + f.reshape(x.shape)).astype(dtype), c


# --- the two programs -----------------------------------------------------

def _trunk(params, lm: Lfm2Config, tokens, lengths, dtype):
    """Every layer over whole prompts. -> (the last residual stream
    (B, P, d), [tail] a conv layer, [(keys, values)] an attention layer,
    counters)."""
    B, P = tokens.shape
    cos, sin = rope_cos_sin(lm, jnp.arange(P))
    valid = (jnp.arange(P)[None, :] < lengths[:, None]).reshape(-1)
    x = params["embed"][tokens].astype(dtype)
    tails, kvs = [], []
    counters = jnp.zeros((len(COUNTERS),), jnp.int32)
    for i, p in enumerate(params["layers"]):
        h = rms_norm(x, p["op_norm"], lm.norm_eps).astype(dtype)
        if layer_is_conv(lm, i):
            a, tail = conv_prefill(p, h, lengths, lm, dtype)
            tails.append(tail)
        else:
            a, kv = attention_prefill(p, h, cos, sin, lm, dtype)
            kvs.append(kv)
        x, c = _ffn(p, (x.astype(jnp.float32) + a).astype(dtype), valid, i,
                    lm, dtype)
        counters = counters + c
    return x, tails, kvs, counters


def prefill(params, lm: Lfm2Config, tokens, lengths, dtype
            ) -> Tuple[List, List, jnp.ndarray]:
    """tokens (B, P) int32, real up to lengths (B,). -> (the tail a conv
    layer (taps - 1, B, d) AT ``lengths``; a (keys, values) pair an
    attention layer, the prompts whole, (B, kv_dim / 2, P) each; counters,
    ``moe_experts_read`` 0). No logits: the first prediction is the first
    decode position's."""
    _x, tails, kvs, counters = _trunk(params, lm, tokens, lengths, dtype)
    return tails, kvs, counters.at[COUNTERS.index("moe_experts_read")].set(0)


def forward_logp(params, lm: Lfm2Config, tokens, lengths, dtype):
    """The whole forward pass without a cache: log-probabilities (B, P, V)
    of the token after each position."""
    x, _t, _kv, _c = _trunk(params, lm, tokens, lengths, dtype)
    return lm_head(params, x, lm, dtype)


def decode_step(params, lm: Lfm2Config, tok, gen_pos, conv, parent,
                prompt_kv, prompt_len, pool, block_tab, active, dtype):
    """One position of every beam of every slot. tok (S, K) int32: each
    beam's token at its slot's generated position gen_pos (S,), absolute
    position prompt_len + gen_pos; conv: a tail (taps - 1, S * K, d) a conv
    layer, row s * K + k beam LANE k of slot s; parent (S, K): the lane
    whose tail beam k continues from (the last selection's source beam) —
    lane ``parent[s, k]`` is read and the update written to lane k;
    prompt_kv: a (keys, values) pair an attention layer, (S, kv_dim / 2,
    P_max) each; pool (attention layers, blocks, K, block, kv_dim): their
    generated positions; block_tab (S, W), already the sentinel in rows
    that must neither read nor write; active (S,). -> (log-probabilities
    (S, K, V) float32, conv, pool, counters)."""
    S, K = tok.shape
    BS, Wt = pool.shape[3], block_tab.shape[1]
    lanes = jnp.arange(K, dtype=jnp.int32)[None, :]
    # an inactive slot's beams read their OWN lanes and (conv_step) write
    # them back as they were
    src = (jnp.arange(S, dtype=jnp.int32)[:, None] * K
           + jnp.where(active[:, None], parent, lanes)).reshape(-1)
    rows_active = jnp.repeat(active, K)
    cos, sin = rope_cos_sin(lm, prompt_len + gen_pos)            # (S, hd)
    cos, sin = cos[:, None, :], sin[:, None, :]
    blk = jnp.take_along_axis(block_tab, (gen_pos // BS)[:, None], 1)[:, 0]
    off = gen_pos % BS
    gen_seen = gen_pos[:, None] - jnp.arange(Wt * BS)[None, :] >= 0
    prompt_seen = (jnp.arange(prompt_kv[0][0].shape[-1])[None, :]
                   < prompt_len[:, None])
    x = params["embed"][tok].astype(dtype)                  # (S, K, d)
    conv = list(conv)
    counters = jnp.zeros((len(COUNTERS),), jnp.int32)
    j_conv = j_attn = 0
    for i, p in enumerate(params["layers"]):
        h = rms_norm(x, p["op_norm"], lm.norm_eps).astype(dtype)
        if layer_is_conv(lm, i):
            a, conv[j_conv] = conv_step(
                p, h.reshape(S * K, -1), jnp.take(conv[j_conv], src, axis=1),
                rows_active, dtype)
            a = a.reshape(S, K, -1)
            j_conv += 1
        else:
            q, k, v = _qkv(p, h, cos, sin, lm, dtype)
            pool = pool.at[j_attn, blk, :, off, :].set(
                jnp.concatenate([k, v], -1), mode="drop")
            gen = pool[j_attn][block_tab]         # (S, Wt, K, BS, kv_dim)
            gen = jnp.moveaxis(gen, 2, 1).reshape(S, K, Wt * BS, -1)
            with jax.named_scope("attn.full.decode"):
                a = mm(attend_decode(q, prompt_kv[j_attn], prompt_seen, gen,
                                     gen_seen, dtype), p["w_o"], dtype)
            j_attn += 1
        x, c = _ffn(p, (x.astype(jnp.float32) + a).astype(dtype),
                    rows_active, i, lm, dtype)
        counters = counters + c
    return lm_head(params, x, lm, dtype), conv, pool, counters
