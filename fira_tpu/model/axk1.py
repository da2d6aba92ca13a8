"""A.X-K1 (``arch="axk1"``): a decoder of latent-attention (MLA) blocks with
one leading dense SwiGLU layer and routed-expert layers after it, as its
published ``config.json`` describes it (config.LMConfig holds the keys).

Plain functions over a parameter tree, no module state: the slot engine
(decode/slot_model.py) calls :func:`prefill` once a request and
:func:`decode_step` once a position. The layer equations, with ``x`` the
residual stream:

- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``, in float32.
- MLA. ``c_q = RMSNorm(x W_dq)``; ``[q_nope | q_rope] = c_q W_uq`` a head;
  ``[c_kv | k_rope] = x W_dkv``, ``c_kv = RMSNorm(c_kv)``; ``[k_nope | v] =
  c_kv W_ukv`` a head; ``q_rope`` and ``k_rope`` rotated (``k_rope`` one
  head shared by all), YaRN frequencies; scale ``(nope + rope)^-0.5 m^2``.
  **Cached per token per layer: ``[c_kv | rotated k_rope]``.** Prefill is
  *materialised* (``k_nope`` and ``v`` expanded from ``c_kv``, blocked over
  queries so no (heads, P, P) score tensor exists); a decode position is
  *absorbed* (``q_nope W_uk^T`` scored against ``c_kv`` itself, ``P c_kv``
  then ``W_uv``): a row reads ``latent_dim`` values a cached token.
- Dense layer: ``W_down(silu(x W_gate) * (x W_up))``.
- Expert layer: ``s = sigmoid(x W_r)`` over ALL ``n_routed_experts``;
  group-limited top-k (:func:`route`); weights ``s_e / sum_chosen s`` times
  ``routed_scaling_factor``; output = shared expert + the weighted experts
  **this engine holds** (``experts_held`` from ``expert_offset``). What the
  absent experts would add is left out: that is another chip's part of the
  sum, and nothing here stands in for it. No token is dropped: the grouped
  product loops until every assignment to a held expert is computed.

Compute runs in ``dtype`` (bfloat16 on the chip) with float32 accumulation;
norms, softmax, router scores and the log-softmax are float32.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fira_tpu.config import LMConfig

# the counters a call returns, in this order (decode/slot_model.py adds
# them into the arena; EngineStats carries them under these names)
COUNTERS = ("moe_assignments", "moe_assignments_held", "moe_held_load_max",
            "moe_rows_expert_major")

# queries a block of prefill attention: a bucket is scored in blocks of this
# many (a bucket under it in one), so no (heads, P, P) tensor exists
ATTN_Q_BLOCK = 128


# --- parameters -----------------------------------------------------------

def layer_is_dense(lm: LMConfig, layer: int) -> bool:
    return layer < lm.first_k_dense_replace


def param_shapes(lm: LMConfig) -> Dict:
    """{name: shape} tree of the parameters this engine holds."""
    d, H = lm.hidden_size, lm.num_attention_heads
    qk = lm.qk_nope_head_dim + lm.qk_rope_head_dim
    m, E = lm.moe_intermediate_size, lm.experts_held
    layers = []
    for i in range(lm.num_hidden_layers):
        p = {
            "attn_norm": (d,), "w_dq": (d, lm.q_lora_rank),
            "q_norm": (lm.q_lora_rank,),
            "w_uq": (lm.q_lora_rank, H * qk),
            "w_dkv": (d, lm.latent_dim), "kv_norm": (lm.kv_lora_rank,),
            "w_ukv": (lm.kv_lora_rank,
                      H * (lm.qk_nope_head_dim + lm.v_head_dim)),
            "w_o": (H * lm.v_head_dim, d), "mlp_norm": (d,),
        }
        if layer_is_dense(lm, i):
            I = lm.intermediate_size
            p.update(w_gate=(d, I), w_up=(d, I), w_down=(I, d))
        else:
            ms = m * lm.n_shared_experts
            p.update(router=(d, lm.n_routed_experts),
                     shared_gate=(d, ms), shared_up=(d, ms),
                     shared_down=(ms, d), experts_gate=(E, d, m),
                     experts_up=(E, d, m), experts_down=(E, m, d))
        layers.append(p)
    return {"embed": (lm.vocab_size, d), "layers": layers,
            "final_norm": (d,), "head": (d, lm.vocab_size)}


def init_params(lm: LMConfig, seed: int, dtype=jnp.bfloat16):
    """Seeded random weights, in ``dtype`` from creation (4.8 B parameters
    cannot exist in float32 on one chip): matrices normal with deviation
    fan_in^-0.5, norm gains near 1. One jitted call."""
    shapes = param_shapes(lm)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))

    def make(key):
        out = []
        for i, shape in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if len(shape) == 1:
                w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                w = jax.random.normal(k, shape, jnp.float32) \
                    * (shape[-2] ** -0.5)
            out.append(w.astype(dtype))
        return out
    built = jax.jit(make)(jax.random.PRNGKey(seed))  # firacheck: allow[DRIVER-REG] one set-up call that builds the weights on the device; this module dispatches nothing in a loop — the engine (decode/engine.py, registered) jits and drives its programs
    return jax.tree_util.tree_unflatten(treedef, built)


# --- pieces ---------------------------------------------------------------

def rms_norm(x, g, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y * g.astype(jnp.float32)


def mm(x, w, dtype, out=jnp.float32):
    """``x @ w`` in ``dtype`` with float32 accumulation, stored as ``out``."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=out)


def rope_inv_freq(lm: LMConfig) -> np.ndarray:
    """YaRN: blend of ``theta^(-2i/dim)`` and that over ``factor`` by the
    linear ramp between the dims that turn ``beta_fast`` and ``beta_slow``
    times over the original context."""
    dim, base = lm.qk_rope_head_dim, float(lm.rope_theta)
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / lm.rope_factor
    orig = lm.rope_original_max_position_embeddings

    def correction_dim(turns: float) -> float:
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(lm.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(lm.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(lm: LMConfig) -> float:
    m = yarn_mscale(lm.rope_factor, lm.rope_mscale_all_dim)
    return (lm.qk_nope_head_dim + lm.qk_rope_head_dim) ** -0.5 * m * m


def rope_cos_sin(lm: LMConfig, positions):
    """(..., rope_dim) cos and sin at integer ``positions``, float32."""
    ang = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(rope_inv_freq(lm))
    ang = jnp.concatenate([ang, ang], -1)
    scale = yarn_mscale(lm.rope_factor, lm.rope_mscale) \
        / yarn_mscale(lm.rope_factor, lm.rope_mscale_all_dim)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rotate(x, cos, sin):
    """Rotary embedding, half-split pairs (i, i + dim/2), float32."""
    x = x.astype(jnp.float32)
    h = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., h:], x[..., :h]], -1)
    return x * cos + turned * sin


def gated(g, u, dtype):
    """silu(g) * u in float32 from the products as ``dtype`` holds them."""
    return (jax.nn.silu(g.astype(jnp.float32))
            * u.astype(jnp.float32)).astype(dtype)


def swiglu(x, w_gate, w_up, w_down, dtype):
    # the two wide products leave the MXU's float32 accumulator as
    # ``dtype``: at 8,192 tokens x 18,432 a float32 copy of each is 0.6 GB
    g, u = mm(x, w_gate, dtype, dtype), mm(x, w_up, dtype, dtype)
    return mm(gated(g, u, dtype), w_down, dtype)


def _queries(p, x, cos, sin, lm: LMConfig, dtype):
    """x (..., d) normed -> q_nope (..., H, nope), rotated q_rope."""
    H, dn, dr = (lm.num_attention_heads, lm.qk_nope_head_dim,
                 lm.qk_rope_head_dim)
    c_q = rms_norm(mm(x, p["w_dq"], dtype), p["q_norm"], lm.rms_norm_eps)
    q = mm(c_q, p["w_uq"], dtype, dtype).reshape(
        x.shape[:-1] + (H, dn + dr))
    q_rope = rotate(q[..., dn:], cos[..., None, :], sin[..., None, :])
    return q[..., :dn].astype(dtype), q_rope.astype(dtype)


def _latent(p, x, cos, sin, lm: LMConfig, dtype):
    """x (..., d) normed -> what is cached: [c_kv | rotated k_rope]."""
    r = lm.kv_lora_rank
    ckv = mm(x, p["w_dkv"], dtype)
    c_kv = rms_norm(ckv[..., :r], p["kv_norm"], lm.rms_norm_eps)
    k_rope = rotate(ckv[..., r:], cos, sin)
    return jnp.concatenate([c_kv, k_rope], -1).astype(dtype)


def _w_ukv(p, lm: LMConfig):
    H, dn = lm.num_attention_heads, lm.qk_nope_head_dim
    w = p["w_ukv"].reshape(lm.kv_lora_rank, H, dn + lm.v_head_dim)
    return w[..., :dn], w[..., dn:]          # W_uk, W_uv: (r, H, *)


def mla_prefill(p, x, cos, sin, lm: LMConfig, dtype):
    """Materialised causal attention over a batch of prompts. x (B, P, d)
    normed. -> (attention output (B, P, d) float32, latents (B, P, 576)).
    Keys past a prompt's end lie after every real query, so the causal
    mask alone keeps them out of real rows."""
    with jax.named_scope("mla.prefill"):
        B, P, _ = x.shape
        H, dv, r = lm.num_attention_heads, lm.v_head_dim, lm.kv_lora_rank
        q_nope, q_rope = _queries(p, x, cos, sin, lm, dtype)
        lat = _latent(p, x, cos, sin, lm, dtype)
        w_uk, w_uv = _w_ukv(p, lm)
        c_kv, k_rope = lat[..., :r], lat[..., r:]
        k_nope = jnp.einsum("bkr,rhd->bkhd", c_kv, w_uk.astype(dtype),
                            preferred_element_type=dtype)
        v = jnp.einsum("bkr,rhd->bkhd", c_kv, w_uv.astype(dtype),
                       preferred_element_type=dtype)
        Qb = min(ATTN_Q_BLOCK, P)
        if P % Qb:
            raise ValueError(f"a prompt bucket of {P} tokens is not a whole "
                             f"number of {Qb}-query attention blocks")
        scale = softmax_scale(lm)

        def span(first: int, blocks: int):
            """``blocks`` query blocks from block ``first``, against the
            keys up to the span's end: a query sees no later key, so the
            keys past it are not even scored."""
            kn = (first + blocks) * Qb
            k_n, k_r, vv = k_nope[:, :kn], k_rope[:, :kn], v[:, :kn]
            kpos = jnp.arange(kn)

            def block(i):
                qn = jax.lax.dynamic_slice_in_dim(q_nope, i * Qb, Qb, 1)
                qr = jax.lax.dynamic_slice_in_dim(q_rope, i * Qb, Qb, 1)
                s = jnp.einsum("bqhd,bkhd->bhqk", qn, k_n,
                               preferred_element_type=jnp.float32)
                s = s + jnp.einsum("bqhd,bkd->bhqk", qr, k_r,
                                   preferred_element_type=jnp.float32)
                seen = kpos[None, :] <= (i * Qb + jnp.arange(Qb))[:, None]
                s = jnp.where(seen, s * scale, -jnp.inf)
                # the softmax's division waits until after the values'
                # product, on (Qb, dv) instead of (Qb, keys): the weights
                # leave their fusion once, as ``dtype``
                e = jnp.exp(s - jnp.max(s, -1, keepdims=True))
                total = jnp.sum(e, -1)                           # (B, H, Qb)
                o = jnp.einsum("bhqk,bkhd->bqhd", e.astype(dtype), vv,
                               preferred_element_type=jnp.float32)
                return (o / jnp.moveaxis(total, 1, 2)[..., None]
                        ).astype(dtype)
            o = jax.lax.map(block, first + jnp.arange(blocks))
            return jnp.moveaxis(o, 0, 1).reshape(B, blocks * Qb, H * dv)

        # up to four spans of query blocks, each with its own key extent:
        # the last scores every key, the first a quarter of them
        n_blocks = P // Qb
        spans = min(4, n_blocks)
        per = -(-n_blocks // spans)
        o = jnp.concatenate(
            [span(at, min(per, n_blocks - at))
             for at in range(0, n_blocks, per)], axis=1)
        return mm(o, p["w_o"], dtype), lat


def mla_decode(p, x, cos, sin, prompt_lat, prompt_len, gen_lat, gen_seen,
               lm: LMConfig, dtype):
    """Absorbed attention for one position of every beam of every slot.
    x (S, K, d) normed; prompt_lat (S, P, 576), shared by a slot's beams,
    real up to prompt_len (S,); gen_lat (S, K, T, 576) with this
    position's latent already in; gen_seen (S, T) bool.
    -> attention output (S, K, d) float32."""
    with jax.named_scope("mla.decode"):
        S, K, _ = x.shape
        r = lm.kv_lora_rank
        q_nope, q_rope = _queries(p, x, cos, sin, lm, dtype)
        w_uk, w_uv = _w_ukv(p, lm)
        q_abs = jnp.einsum("skhd,rhd->skhr", q_nope, w_uk.astype(dtype),
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_abs.astype(dtype), q_rope], -1)
        scale = softmax_scale(lm)
        s_p = jnp.einsum("skhc,spc->skhp", q, prompt_lat,
                         preferred_element_type=jnp.float32)
        s_g = jnp.einsum("skhc,sktc->skht", q, gen_lat,
                         preferred_element_type=jnp.float32)
        P = prompt_lat.shape[1]
        seen_p = jnp.arange(P)[None, :] < prompt_len[:, None]       # (S, P)
        s_p = jnp.where(seen_p[:, None, None, :], s_p * scale, -jnp.inf)
        s_g = jnp.where(gen_seen[:, None, None, :], s_g * scale, -jnp.inf)
        # one softmax over [prompt | generated] without joining the two:
        # a common maximum, each side's exponentials, one denominator
        # (position 0 of the generated side is always seen, so it is finite)
        top = jnp.maximum(jnp.max(s_p, -1), jnp.max(s_g, -1))[..., None]
        e_p, e_g = jnp.exp(s_p - top), jnp.exp(s_g - top)
        denom = jnp.sum(e_p, -1) + jnp.sum(e_g, -1)
        # values over the whole latent (its rotary tail is dropped after):
        # slicing c_kv out of the cache first would copy the cache
        o = jnp.einsum("skhp,spc->skhc", e_p.astype(dtype), prompt_lat,
                       preferred_element_type=jnp.float32)
        o = o + jnp.einsum("skht,sktc->skhc", e_g.astype(dtype), gen_lat,
                           preferred_element_type=jnp.float32)
        o = o[..., :r] / denom[..., None]
        o = jnp.einsum("skhr,rhd->skhd", o.astype(dtype), w_uv.astype(dtype),
                       preferred_element_type=jnp.float32)
        return mm(o.reshape(S, K, -1), p["w_o"], dtype)


def route(scores, lm: LMConfig):
    """scores (N, n_routed_experts) float32 -> (ids (N, k), weights (N, k)).

    ``topk_method: "none"`` beside ``n_group`` / ``topk_group`` is READ as
    the group-limited top-k without a score-correction bias (the
    DeepSeek-V3 form less its bias): a group's score is the sum of its two
    largest ``s``, the ``topk_group`` best groups stay, top-k of ``s``
    inside them. Equal scores go to the lower index, groups and experts
    alike."""
    N, E = scores.shape
    G, k = lm.n_group, lm.num_experts_per_tok
    grouped = scores.reshape(N, G, E // G)
    group_score = jnp.sum(jax.lax.top_k(grouped, min(2, E // G))[0], -1)
    _, keep = jax.lax.top_k(group_score, lm.topk_group)          # (N, tg)
    kept = jnp.zeros((N, G), bool).at[jnp.arange(N)[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(kept, E // G, axis=1), scores, 0.0)
    _, ids = jax.lax.top_k(masked, k)
    w = jnp.take_along_axis(scores, ids, 1)
    if lm.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return ids, w * lm.routed_scaling_factor


# an expert layer's stacked expert matrices, (experts_held, in, out) each
EXPERT_MATRICES = ("experts_gate", "experts_up", "experts_down")

# rows one pass of the grouped product holds at the most: a prefill
# dispatch that holds every expert (16,384 tokens x top-8 = 131,072
# assignments) takes several passes of this many rows, in expert order,
# instead of one whose float32 result alone is 1.3 GB
EXPERT_CHUNK_ROWS_MAX = 32768

# a held expert's expected rows a pass at or under which the grouped
# product runs EXPERT-MAJOR whatever the loads (:func:`expert_capacity`):
# one MXU tile's height. Every decode position is under it (24, 9 and ~11
# rows at the three expert cells) and every prefill far over (256 to
# 1,024), where the loads choose the tiling (:func:`expert_major_engages`)
EXPERT_MAJOR_ROWS = 128


def expert_chunk_rows(lm, n_tokens: int) -> int:
    """Rows one pass of the grouped product holds: a quarter over the
    expected assignments to held experts, a multiple of 128 (of 512 from
    2,048 up), never more than every token choosing only held experts nor
    than EXPERT_CHUNK_ROWS_MAX. ``lm``: any key block with
    ``num_experts_per_tok``, ``experts_held``, ``n_routed_experts``."""
    k = lm.num_experts_per_tok
    expect = n_tokens * k * lm.experts_held / lm.n_routed_experts
    unit = 512 if expect >= 2048 else 128
    rows = min(int(math.ceil(1.25 * expect / unit)) * unit,
               EXPERT_CHUNK_ROWS_MAX)
    return max(8, min(rows, n_tokens * min(k, lm.experts_held)))


def expert_capacity(lm, n_tokens: int) -> int:
    """Rows of EACH held expert one expert-major pass holds — 2.5 x the
    expected rows an expert (``expert_chunk_rows / experts_held``), in
    whole bfloat16 tiles of 16 rows: 64 | 32 | 32 at LFM2's, Trinity-Mini's
    and A.X-K1's decode positions (PERF.md §6: the fastest capacity
    measured at the last two, within 4 % of it at LFM2's with room for a
    busier expert; a capacity under the busiest load costs a second pass
    that reads every expert again) — or 0 where that expectation is over
    EXPERT_MAJOR_ROWS and the loads choose the tiling
    (:func:`expert_major_engages`)."""
    per = expert_chunk_rows(lm, n_tokens) / lm.experts_held
    if per > EXPERT_MAJOR_ROWS:
        return 0
    return int(math.ceil(2.5 * per / 16)) * 16


def prefill_capacity(lm, n_tokens: int) -> int:
    """Rows of each held expert one expert-major pass holds where the
    expectation is over EXPERT_MAJOR_ROWS: a row-major pass's rows shared
    evenly (``expert_chunk_rows / experts_held``), rounded DOWN to whole
    tiles of 16 rows, so that a pass of every held expert computes no more
    rows than a row-major pass — 1,024 | 256 | 416 at LFM2's,
    Trinity-Mini's and A.X-K1's prefill dispatches."""
    return max(16, expert_chunk_rows(lm, n_tokens) // lm.experts_held
               // 16 * 16)


def expert_major_engages(lm, n_tokens: int, loads):
    """On the device, for a call whose expectation is over
    EXPERT_MAJOR_ROWS: whether the expert-major passes of
    :func:`prefill_capacity` compute no more rows than the row-major
    passes would — ``ceil(max(loads) / C) E C <= ceil(n_held / M) M``.
    A busy expert costs a pass over EVERY expert there; padding and an
    even load leave the row-major passes' last one part empty."""
    C, M = prefill_capacity(lm, n_tokens), expert_chunk_rows(lm, n_tokens)
    passes = -(-jnp.max(loads) // C)
    return passes * (lm.experts_held * C) <= -(-jnp.sum(loads) // M) * M


def routed_experts(p, x, ids, weights, valid, lm, dtype):
    """The held experts' part of the routed sum (shared with
    model/afmoe.py and model/lfm2.py: ``lm`` is any hashable key block with
    ``num_experts_per_tok``, ``experts_held``, ``expert_offset``). x (N, d)
    normed; ids / weights (N, k); valid (N,) bool — padding takes no
    expert's time. Assignments to held experts are sorted by expert and
    computed a pass at a time, as many passes as the imbalance asks: none is
    dropped. Two tilings of a pass:

    - EXPERT-MAJOR: pass i holds ranks [i C, (i + 1) C) of every held
      expert, (E, C, d), one batched product an expert matrix — each
      expert's weights stream from HBM once a pass and meet all of its rows;
    - ROW-MAJOR: a chunk of rows in expert order a pass through grouped
      products (``jax.lax.ragged_dot``), never a (tokens x experts) masked
      product.

    Few rows an expert (every decode position, :func:`expert_capacity`):
    expert-major, one pass unless an expert has over C. Many (every
    prefill): a ``lax.cond`` on the loads — expert-major in passes of
    :func:`prefill_capacity` where that computes no more rows than the
    row-major passes (:func:`expert_major_engages`), else row-major. A
    prefill's call is traced and lowered once a program, not once a layer
    (``_prefill_grouped``): its layers share their shapes, and the cond's
    two branches at every layer cost set-up time.

    -> (out (N, d) float32, per-expert loads (experts_held,) int32)."""
    if expert_capacity(lm, x.shape[0]):
        return _grouped(p, x, ids, weights, valid, lm, dtype)
    return _prefill_grouped({n: p[n] for n in EXPERT_MATRICES}, x, ids,
                            weights, valid, lm, dtype)


def _grouped(p, x, ids, weights, valid, lm, dtype):
    N, d = x.shape
    k, E = lm.num_experts_per_tok, lm.experts_held
    local = ids - lm.expert_offset
    held = valid[:, None] & (local >= 0) & (local < E)
    key = jnp.where(held, local, E).reshape(-1).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)      # (N*k,)
    loads = jnp.sum(key[:, None] == jnp.arange(E)[None, :], 0,
                    dtype=jnp.int32)
    ends = jnp.cumsum(loads)
    starts, n_held = ends - loads, ends[-1]
    w_flat = weights.reshape(-1)
    xc = x.astype(dtype)
    wg, wu, wd = (p[n].astype(dtype) for n in EXPERT_MATRICES)

    def expert_major(C):
        # pass i: ranks [i C, (i + 1) C) of every expert, while any has them
        step, total = C, jnp.max(loads)
        rank = jnp.arange(C, dtype=jnp.int32)[None, :]

        def rows(i):
            r = i * C + rank                                     # (1, C)
            sel = order[jnp.minimum(starts[:, None] + r, N * k - 1)]
            return sel.reshape(-1), (r < loads[:, None]).reshape(-1)

        def products(xs, i):
            def bmm(a, w):
                return jnp.einsum("ecd,edm->ecm", a.reshape(E, C, -1), w,
                                  preferred_element_type=jnp.float32)

            def stored(y):
                # rounded to ``dtype`` as ``ragged_dot`` stores gate and
                # up: the chip's compiler drops a bare cast pair (float32
                # -> dtype -> float32) inside a fusion
                f = jnp.finfo(dtype)
                return jax.lax.reduce_precision(y, f.nexp, f.nmant
                                                ).astype(dtype)
            g, u = stored(bmm(xs, wg)), stored(bmm(xs, wu))
            return bmm(gated(g, u, dtype), wd).reshape(E * C, d)
        return passes(step, total, rows, products)

    def row_major(M):
        # pass i: sorted rows [i M, (i + 1) M), while any is held
        step, total = M, n_held
        padded = jnp.concatenate([order, jnp.zeros((M,), jnp.int32)])

        def rows(i):
            at = i * M
            return (jax.lax.dynamic_slice_in_dim(padded, at, M),
                    at + jnp.arange(M) < n_held)

        def products(xs, i):
            at = i * M
            sizes = jnp.clip(ends - at, 0, M) - jnp.clip(starts - at, 0, M)
            g = jax.lax.ragged_dot(xs, wg, sizes, preferred_element_type=dtype)
            u = jax.lax.ragged_dot(xs, wu, sizes, preferred_element_type=dtype)
            return jax.lax.ragged_dot(gated(g, u, dtype), wd, sizes,
                                      preferred_element_type=jnp.float32)
        return passes(step, total, rows, products)

    def passes(step, total, rows, products):
        def more(carry):
            return carry[0] * step < total

        def one_pass(carry):
            i, out = carry
            sel, real = rows(i)
            tok = sel // k
            y = products(xc[tok], i)
            # rows past an expert's load hold whatever the product left there
            y = jnp.where(real[:, None], y * w_flat[sel][:, None], 0.0)
            return i + 1, out.at[jnp.where(real, tok, N)].add(y, mode="drop")

        return jax.lax.while_loop(
            more, one_pass, (jnp.int32(0), jnp.zeros((N, d), jnp.float32)))[1]

    C = expert_capacity(lm, N)
    if C:
        return expert_major(C), loads
    out = jax.lax.cond(expert_major_engages(lm, N, loads),
                       lambda: expert_major(prefill_capacity(lm, N)),
                       lambda: row_major(expert_chunk_rows(lm, N)))
    return out, loads


_prefill_grouped = jax.jit(_grouped, static_argnames=("lm", "dtype"))


def moe_counters(lm, valid, loads):
    """The first four of COUNTERS for one expert layer's call over rows
    ``valid`` (N,) whose held experts took ``loads``: assignments, those to
    held experts, the busiest held expert's load, and the held assignments
    an expert-major pass computed (a decode position's: each expert's
    first C; a prefill's: every one where :func:`expert_major_engages`,
    none where the row-major passes ran)."""
    N = valid.shape[0]
    C = expert_capacity(lm, N)
    return jnp.stack([
        jnp.sum(valid, dtype=jnp.int32) * lm.num_experts_per_tok,
        jnp.sum(loads), jnp.max(loads),
        jnp.sum(jnp.minimum(loads, C)) if C else jnp.where(
            expert_major_engages(lm, N, loads), jnp.sum(loads), 0)])


def moe_layer(p, x, valid, lm: LMConfig, dtype):
    """x (N, d) normed -> (shared + held routed part (N, d) float32,
    counters (4,) int32 in COUNTERS' order)."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        ids, weights = route(scores, lm)
    with jax.named_scope("moe.shared"):
        shared = swiglu(x, p["shared_gate"], p["shared_up"],
                        p["shared_down"], dtype)
    with jax.named_scope("moe.experts"):
        routed, loads = routed_experts(p, x, ids, weights, valid, lm, dtype)
    return shared + routed, moe_counters(lm, valid, loads)


def _mlp(p, h, valid, layer: int, lm: LMConfig, dtype):
    """h (N, d) normed -> (output (N, d) float32, counters)."""
    if layer_is_dense(lm, layer):
        return (swiglu(h, p["w_gate"], p["w_up"], p["w_down"], dtype),
                jnp.zeros((len(COUNTERS),), jnp.int32))
    return moe_layer(p, h, valid, lm, dtype)


# --- the two programs -----------------------------------------------------

def _trunk(params, lm: LMConfig, tokens, lengths, dtype):
    """Every layer over whole prompts, materialised attention. -> (the
    last residual stream (B, P, d), latents (L, B, P, 576), counters)."""
    B, P = tokens.shape
    cos, sin = rope_cos_sin(lm, jnp.arange(P))
    valid = (jnp.arange(P)[None, :] < lengths[:, None]).reshape(-1)
    x = params["embed"][tokens].astype(dtype)
    lats, counters = [], jnp.zeros((len(COUNTERS),), jnp.int32)
    for i, p in enumerate(params["layers"]):
        h = rms_norm(x, p["attn_norm"], lm.rms_norm_eps).astype(dtype)
        a, lat = mla_prefill(p, h, cos, sin, lm, dtype)
        lats.append(lat)
        x = (x.astype(jnp.float32) + a).astype(dtype)
        h = rms_norm(x, p["mlp_norm"], lm.rms_norm_eps).astype(dtype)
        f, c = _mlp(p, h.reshape(B * P, -1), valid, i, lm, dtype)
        counters = counters + c
        x = (x.astype(jnp.float32) + f.reshape(B, P, -1)).astype(dtype)
    return x, jnp.stack(lats), counters


def lm_head(params, x, lm: LMConfig, dtype):
    with jax.named_scope("lm_head"):
        h = rms_norm(x, params["final_norm"], lm.rms_norm_eps)
        return jax.nn.log_softmax(mm(h, params["head"], dtype), -1)


def prefill(params, lm: LMConfig, tokens, lengths, dtype
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens (B, P) int32, real up to lengths (B,). -> (latents
    (L, B, P, 576) in ``dtype``, counters (4,) int32). No logits: the
    first prediction is the first decode position's."""
    _x, lats, counters = _trunk(params, lm, tokens, lengths, dtype)
    return lats, counters


def forward_logp(params, lm: LMConfig, tokens, lengths, dtype):
    """The whole forward pass without a cache: log-probabilities (B, P, V)
    of the token after each position."""
    x, _lats, _counters = _trunk(params, lm, tokens, lengths, dtype)
    return lm_head(params, x, lm, dtype)


def decode_step(params, lm: LMConfig, tok, gen_pos, prompt_lat, prompt_len,
                pool, block_tab, active, dtype):
    """One position of every beam of every slot. tok (S, K) int32: each
    beam's token at its slot's generated position gen_pos (S,), absolute
    position prompt_len + gen_pos; prompt_lat (L, S, P, 576); pool
    (L, blocks, K, block, 576): the generated positions' latents;
    block_tab (S, W), already the sentinel (= blocks) in rows that must
    neither read nor write; active (S,) bool.
    -> (log-probabilities (S, K, V) float32, pool, counters)."""
    S, K = tok.shape
    BS, W = pool.shape[3], block_tab.shape[1]
    cos, sin = rope_cos_sin(lm, prompt_len + gen_pos)            # (S, 64)
    cos, sin = cos[:, None, :], sin[:, None, :]
    blk = jnp.take_along_axis(block_tab, (gen_pos // BS)[:, None], 1)[:, 0]
    off = gen_pos % BS
    gen_seen = jnp.arange(W * BS)[None, :] <= gen_pos[:, None]
    valid = jnp.repeat(active, K)
    x = params["embed"][tok].astype(dtype)
    counters = jnp.zeros((len(COUNTERS),), jnp.int32)
    for i, p in enumerate(params["layers"]):
        h = rms_norm(x, p["attn_norm"], lm.rms_norm_eps).astype(dtype)
        lat = _latent(p, h, cos, sin, lm, dtype)                 # (S, K, 576)
        pool = pool.at[i, blk, :, off, :].set(lat, mode="drop")
        gen = pool[i][block_tab]                     # (S, W, K, BS, 576)
        gen = jnp.moveaxis(gen, 2, 1).reshape(S, K, W * BS, -1)
        a = mla_decode(p, h, cos, sin, prompt_lat[i], prompt_len, gen,
                       gen_seen, lm, dtype)
        x = (x.astype(jnp.float32) + a).astype(dtype)
        h = rms_norm(x, p["mlp_norm"], lm.rms_norm_eps).astype(dtype)
        f, c = _mlp(p, h.reshape(S * K, -1), valid, i, lm, dtype)
        counters = counters + c
        x = (x.astype(jnp.float32) + f.reshape(S, K, -1)).astype(dtype)
    return lm_head(params, x, lm, dtype), pool, counters
