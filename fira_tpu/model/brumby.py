"""Brumby-14B-Base (``arch="brumby"``): a decoder whose every layer replaces
attention's softmax with POWER RETENTION — gated, normalised attention of
degree 2, whose recurrent form carries a state of fixed size built from the
keys and values themselves — around a Qwen3 block (q/k RMSNorm a head,
rotary positions, SwiGLU, untied head), as its published ``config.json``
(``model_type: brumby``) describes it (config.BrumbyConfig holds the keys).

Plain functions over a parameter tree, as model/afmoe.py and model/jamba.py,
whose pieces this module shares (``rms_norm``, ``mm``, ``rotate``,
``swiglu``; the rotary frequencies of afmoe): the slot engine
(decode/slot_model.py) calls :func:`prefill` once a request and
:func:`decode_step` once a position. The layer equations, ``x`` the
residual stream:

- Block: ``x = x + W_o R(N1(x))``; ``x = x + MLP(N2(x))``; ``N`` RMSNorm
  with a gain. Final RMSNorm, logits ``h W_head`` (untied), log-softmax.
- Retention ``R``, token t, query head h over key/value head g = h // (H /
  KV), head size d::

      q_t = rope(RMSNorm_q(W_q x_t)_h, t);  k_t = rope(RMSNorm_k(W_k x_t)_g, t)
      v_t = (W_v x_t)_g;                    gam_t = log_sigmoid(w_g . x_t + b_g)
      w_{t,s} = exp(G_t - G_s) (q_t . k_s / sqrt d)^2   s <= t,  G = cumsum(gam)
      y_t = sum_s w_{t,s} v_s / (sum_s w_{t,s} + eps)

  The same numbers in recurrent form, with the symmetric square ``phi``
  (:func:`features`: ``phi(q) . phi(k) = (q . k / sqrt d)^2``, D =
  d(d+1)/2 entries)::

      S_t = e^{gam_t} S_{t-1} + phi(k_t) v_t^T    z_t = e^{gam_t} z_{t-1} + phi(k_t)
      y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

- **What a slot carries: the prompt's state ``(S, z)`` a key/value head a
  layer, at the prompt's own length — (D, d) and (D,) whatever the prompt's
  length — and nothing else of the prompt.** A beam continues from it along
  its own tokens: with ``c_t`` the gates' sum since the prompt's end on the
  beam's own path, a decode position is exactly::

      y_t = [e^{c_t} phi(q_t)^T S + sum_{s>=P} e^{c_t - c_s} (q_t.k_s/sqrt d)^2 v_s]
            / [e^{c_t} phi(q_t)^T z + sum_{s>=P} e^{c_t - c_s} (q_t.k_s/sqrt d)^2 + eps]

  (the gate is a scalar a head and token, so the prompt's part only decays).
  The state is read, never written, by a step: ONE copy a slot serves its
  beams (a slot's 3 beams x 5 query heads of a group are 15 rows of one
  product over it); the beams' own positions — k, v and ``c_s`` — lie in
  the engine's paged pool and follow the beams as every pool does.

Prefill computes the attention form in blocks of queries
(:func:`attend_prefill`), then the state once (:func:`prompt_state`). A
padded position has ``gam = 0`` and ``k = 0``: it neither decays nor enters
the state, so the state at the bucket's end IS the state at the prompt's
length. Compute runs in ``dtype`` (bfloat16 on the chip) with float32
accumulation; the gates' sums, the normaliser ``z``, norms and log-softmax
are float32; ``S`` is accumulated in float32 and stored in ``dtype``, once.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fira_tpu.config import BrumbyConfig
from fira_tpu.model.afmoe import rope_cos_sin
from fira_tpu.model.axk1 import mm, rms_norm, rotate, swiglu

# the counters a step returns, in this order: slot-layers whose prompt state
# a position read (occupied slots x layers), and the beams' own positions
# attended (occupied slots x beams x generated positions x layers)
COUNTERS = ("state_reads", "own_keys_read")

# queries a block of the prefill's attention form: no (heads, P, P) tensor
# exists. The attention form was chosen over a scan of chunks that carries
# (S, z) (the recurrent form across chunks, the attention form inside one)
# by a microbenchmark on the chip, one layer at the cell's shapes, ms at
# 8 x 2,048 | 4 x 4,096 | 2 x 8,192 | 1 x 16,384: this form 35.0 | 36.7 |
# 44.5 | 70.9; chunks of 64 166.5 | 152.3 | 147.6 | 164.5, of 128 134.9 |
# 135.4 | 121.3 | 159.5, of 256 127.7 | 128.3 | 123.6 | 152.7 (PERF.md
# section 6). The chunked form's products are fewer above ~8,256 tokens,
# but every chunk forms the features of its queries (D values a query head)
# and moves the carried state through HBM; this form's float32 passes over
# its weights grow with P^2 and stayed cheaper up to the longest bucket.
ATTN_Q_BLOCK = 128
# tokens of a bucket whose features phi(k) (D values a key/value head) exist
# at once while the prompt's state is summed: 2,048 tokens are 270 MB at the
# published widths, and a bucket of 16,384 is 8 trips of the sum's loop
STATE_TOKENS = 2048
SQRT2 = math.sqrt(2.0)


# --- parameters -----------------------------------------------------------

def param_shapes(lm: BrumbyConfig) -> Dict:
    """{name: shape} tree of the parameters."""
    d, H, KV, hd, I = (lm.hidden_size, lm.num_attention_heads,
                       lm.num_key_value_heads, lm.head_dim,
                       lm.intermediate_size)
    layer = {"attn_norm": (d,), "w_q": (d, H * hd), "w_k": (d, KV * hd),
             "w_v": (d, KV * hd), "w_o": (H * hd, d), "q_norm": (hd,),
             "k_norm": (hd,), "w_ret_gate": (d, KV), "b_ret_gate": (KV,),
             "mlp_norm": (d,), "w_gate": (d, I), "w_up": (d, I),
             "w_down": (I, d)}
    return {"embed": (lm.vocab_size, d),
            "layers": [dict(layer) for _ in range(lm.num_hidden_layers)],
            "final_norm": (d,), "head": (d, lm.vocab_size)}


def gate_bias(heads: int) -> np.ndarray:
    """The retention gates' biases from a seed-free rule: ``1 -
    sigmoid(b_g)`` from 1/64 to 1/8,192, geometric over the key/value heads
    (half-lives of ~44 to ~5,700 tokens), so that a long prompt is neither
    forgotten at once nor never."""
    e = 2.0 ** -np.linspace(6.0, 13.0, heads)
    return np.log((1.0 - e) / e).astype(np.float32)


def init_leaf(name: str, shape, key, hidden_size: int):
    """One seeded leaf, float32: ``b_ret_gate`` :func:`gate_bias`; gains
    1 + 0.1 N(0, 1) (a gain of exactly 1 would hide a gain the program
    forgot); embedding rows N(0, 1 / hidden); matrices, the head among them,
    N(0, 1 / fan_in)."""
    if name == "b_ret_gate":
        return jnp.asarray(gate_bias(shape[0]))
    w = jax.random.normal(key, shape, jnp.float32)
    if len(shape) == 1:
        return 1.0 + 0.1 * w
    if name == "embed":
        return w * (hidden_size ** -0.5)
    return w * (shape[-2] ** -0.5)


def init_params(lm: BrumbyConfig, seed: int, dtype=jnp.bfloat16):
    """Seeded random weights (:func:`init_leaf`), in ``dtype`` from
    creation. One jitted call."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(lm), is_leaf=lambda s: isinstance(s, tuple))

    def make(key):
        return [init_leaf(path[-1].key, shape, jax.random.fold_in(key, i),
                          lm.hidden_size).astype(dtype)
                for i, (path, shape) in enumerate(paths)]
    built = jax.jit(make)(jax.random.PRNGKey(seed))  # firacheck: allow[DRIVER-REG] one set-up call that builds the weights on the device; this module dispatches nothing in a loop — the engine (decode/engine.py, registered) jits and drives its programs
    return jax.tree_util.tree_unflatten(treedef, built)


# --- the feature map and the two forms of retention -----------------------

def features(u):
    """phi(u): u (..., d) float32 -> (..., d(d+1)/2) with ``phi(q) .
    phi(k) = (q . k)^2 / d``. Every product ``u_i u_j`` of a pair i <= j
    once, off the diagonal times sqrt 2, laid out by the pair's cyclic
    distance o: row o is ``u * roll(u, -o)`` (o = 0 .. d/2 - 1, d entries
    each), then the d/2 pairs (i, i + d/2) — rotations of the lane axis and
    products, no gather."""
    d = u.shape[-1]
    h = d // 2
    rows = [u * u] + [SQRT2 * u * jnp.roll(u, -o, axis=-1)
                      for o in range(1, h)] + [SQRT2 * u[..., :h] * u[..., h:]]
    return jnp.concatenate(rows, -1) * (d ** -0.5)


def attend_prefill(q, k, v, G, eps: float, dtype):
    """Retention's attention form over a batch of prompts, a block of
    queries at a time: q (B, P, KV, R, hd), k and v (B, P, KV, hd), G (B, P,
    KV) float32 the gates' running sums. -> the heads' output (B, P, KV * R
    * hd) in ``dtype``. Up to four spans of query blocks, each scoring the
    keys up to its end (as model/afmoe.attend_prefill): weights ``exp(G_t -
    G_s) (q . k / sqrt d)^2``, the decay computed once a key/value head for
    its R query heads; the normaliser divides after the values' product."""
    B, P, KV, R, hd = q.shape
    Qb = min(ATTN_Q_BLOCK, P)
    if P % Qb:
        raise ValueError(f"a prompt bucket of {P} tokens is not a whole "
                         f"number of {Qb}-query blocks")
    scale = hd ** -0.5
    Gt = jnp.moveaxis(G, 1, 2)                               # (B, KV, P)

    def span(first: int, blocks: int, keys: int):
        ks, vs, Gk = k[:, :keys], v[:, :keys], Gt[:, :, :keys]

        def block(i):
            qs = jax.lax.dynamic_slice_in_dim(q, i * Qb, Qb, 1)
            Gq = jax.lax.dynamic_slice_in_dim(Gt, i * Qb, Qb, 2)
            s = jnp.einsum("bqngd,bknd->bngqk", qs, ks,
                           preferred_element_type=jnp.float32) * scale
            back = (i * Qb + jnp.arange(Qb))[:, None] \
                - jnp.arange(keys)[None, :]
            decay = jnp.exp(jnp.where(back >= 0, Gq[..., :, None]
                                      - Gk[..., None, :], -jnp.inf))
            w = s * s * decay[:, :, None]                 # (B, n, r, Qb, k)
            total = jnp.sum(w, -1)                        # (B, n, r, Qb)
            o = jnp.einsum("bngqk,bknd->bqngd", w.astype(dtype), vs,
                           preferred_element_type=jnp.float32)
            return (o / (jnp.transpose(total, (0, 3, 1, 2))[..., None]
                         + eps)).astype(dtype)
        o = jax.lax.map(block, first + jnp.arange(blocks))
        return jnp.moveaxis(o, 0, 1).reshape(B, blocks * Qb, KV * R * hd)

    n_blocks = P // Qb
    per = -(-n_blocks // min(4, n_blocks))
    return jnp.concatenate(
        [span(at, min(per, n_blocks - at), (at + min(per, n_blocks - at))
              * Qb) for at in range(0, n_blocks, per)], axis=1)


def prompt_state(k, v, G, dtype):
    """The state each prompt leaves: k, v (B, P, KV, hd) in ``dtype`` (k 0
    at padded positions), G (B, P, KV) float32 (flat over them). -> (S (B,
    KV, D, hd) float32, z (B, KV, D) float32) =
    ``sum_s e^{G_end - G_s} phi(k_s) [v_s | 1]``, summed over blocks of at
    most :data:`STATE_TOKENS` tokens of the bucket (a scan: no block's state
    is kept)."""
    B, P, KV, hd = k.shape
    n = min(P, max(1, STATE_TOKENS // B))
    while P % n:                # the largest block under it that tiles P
        n -= 1
    w = jnp.exp(G[:, -1:] - G)                               # (B, P, KV)

    def blocks(a):                         # (B, P, ...) -> (P / n, B, n, ...)
        return jnp.moveaxis(a.reshape((B, P // n, n) + a.shape[2:]), 1, 0)

    def add(carry, xs):
        S, z = carry
        kb, vb, wb = xs
        f = features(kb.astype(jnp.float32))              # (B, n, KV, D)
        S = S + jnp.einsum("bsgD,bsgv->bgDv", f.astype(dtype),
                           (wb[..., None] * vb).astype(dtype),
                           preferred_element_type=jnp.float32)
        z = z + jnp.einsum("bsgD,bsg->bgD", f, wb,
                           precision=jax.lax.Precision.HIGHEST)
        return (S, z), None
    D = hd * (hd + 1) // 2
    (S, z), _ = jax.lax.scan(
        add, (jnp.zeros((B, KV, D, hd), jnp.float32),
              jnp.zeros((B, KV, D), jnp.float32)),
        (blocks(k), blocks(v), blocks(w)))
    return S, z


def prompt_weight(c):
    """``e^{c_t}``: what is left at a generated position of the prompt's
    state, ``c_t`` (..., KV) the gates' sum since the prompt's end."""
    return jnp.exp(c)


def real_positions(P: int, lengths):
    """(B, P) bool: the positions of a padded bucket that hold a prompt's
    own tokens. Everywhere else the gate is 0 and the key 0, so the state
    a request hands over is the one at ITS length, not its bucket's."""
    return jnp.arange(P)[None, :] < lengths[:, None]


# --- a layer's pieces -----------------------------------------------------

def _projections(p, h, cos, sin, lm: BrumbyConfig, dtype):
    """h (..., d) normed -> q (..., KV, H/KV, hd), k (..., KV, hd), v
    (..., KV, hd) in ``dtype``, the log-gates (..., KV) float32. q and k
    RMSNorm'ed a head (a gain each) and rotated; ``cos`` / ``sin`` (...,
    hd)."""
    H, KV, hd = lm.num_attention_heads, lm.num_key_value_heads, lm.head_dim
    lead = h.shape[:-1]
    q = rms_norm(mm(h, p["w_q"], dtype).reshape(lead + (H, hd)),
                 p["q_norm"], lm.rms_norm_eps)
    k = rms_norm(mm(h, p["w_k"], dtype).reshape(lead + (KV, hd)),
                 p["k_norm"], lm.rms_norm_eps)
    q = rotate(q, cos[..., None, :], sin[..., None, :])
    k = rotate(k, cos[..., None, :], sin[..., None, :])
    v = mm(h, p["w_v"], dtype, dtype).reshape(lead + (KV, hd))
    gam = jax.nn.log_sigmoid(mm(h, p["w_ret_gate"], dtype)
                             + p["b_ret_gate"].astype(jnp.float32))
    return (q.astype(dtype).reshape(lead + (KV, H // KV, hd)),
            k.astype(dtype), v, gam)


def _mlp(p, x, lm: BrumbyConfig, dtype):
    """The second half of a block over the residual stream x (..., d)."""
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["mlp_norm"], lm.rms_norm_eps).astype(dtype)
        f = swiglu(h.reshape(-1, h.shape[-1]), p["w_gate"], p["w_up"],
                   p["w_down"], dtype)
        return (x.astype(jnp.float32) + f.reshape(x.shape)).astype(dtype)


def lm_head(params, x, lm: BrumbyConfig, dtype):
    with jax.named_scope("lm_head"):
        h = rms_norm(x, params["final_norm"], lm.rms_norm_eps)
        return jax.nn.log_softmax(mm(h, params["head"], dtype), -1)


# --- the two programs -----------------------------------------------------

def _trunk(params, lm: BrumbyConfig, tokens, lengths, dtype):
    """Every layer over whole prompts. -> (the last residual stream
    (B, P, d), [(S, z)] a layer at each prompt's own length)."""
    B, P = tokens.shape
    cos, sin = rope_cos_sin(lm, jnp.arange(P))
    real = real_positions(P, lengths)
    x = params["embed"][tokens].astype(dtype)
    states = []
    for p in params["layers"]:
        h = rms_norm(x, p["attn_norm"], lm.rms_norm_eps).astype(dtype)
        q, k, v, gam = _projections(p, h, cos, sin, lm, dtype)
        gam = jnp.where(real[..., None], gam, 0.0)
        k = jnp.where(real[..., None, None], k, jnp.zeros((), k.dtype))
        G = jnp.cumsum(gam, axis=1)
        with jax.named_scope("ret.prefill.intra"):
            o = attend_prefill(q, k, v, G, lm.retention_eps, dtype)
        with jax.named_scope("ret.prefill.state"):
            states.append(prompt_state(k, v, G, dtype))
        x = (x.astype(jnp.float32) + mm(o, p["w_o"], dtype)).astype(dtype)
        x = _mlp(p, x, lm, dtype)
    return x, states


def prefill(params, lm: BrumbyConfig, tokens, lengths, dtype
            ) -> Tuple[List, List, jnp.ndarray]:
    """tokens (B, P) int32, real up to lengths (B,). -> (the prompt's state
    a layer (B, KV, D, hd) in ``dtype``, rounded once; its normaliser a
    layer (B, KV, D) float32; counters: a prefill adds none). No logits: the
    first prediction is the first decode position's."""
    _x, states = _trunk(params, lm, tokens, lengths, dtype)
    return ([S.astype(dtype) for S, _z in states], [z for _S, z in states],
            jnp.zeros((len(COUNTERS),), jnp.int32))


def forward_logp(params, lm: BrumbyConfig, tokens, lengths, dtype):
    """The whole forward pass without a cache: log-probabilities (B, P, V)
    of the token after each position."""
    x, _states = _trunk(params, lm, tokens, lengths, dtype)
    return lm_head(params, x, lm, dtype)


def decode_step(params, lm: BrumbyConfig, tok, gen_pos, states, norms,
                prompt_len, pool, gates, block_tab, active, dtype):
    """One position of every beam of every slot. tok (S, K) int32: each
    beam's token at its slot's generated position gen_pos (S,), absolute
    position prompt_len + gen_pos; states: the prompt's state a layer (S,
    KV, D, hd), norms: its normaliser a layer (S, KV, D) — shared by the
    slot's beams and only read; pool (L, blocks, K, block, 2 KV hd): every
    layer's generated positions' [k | v]; gates (L, blocks, K, block, KV)
    float32: the gates' sum since the prompt's end at each of them;
    block_tab (S, W), already the sentinel in rows that must neither read
    nor write; active (S,) bool. -> (log-probabilities (S, K, V) float32,
    pool, gates, counters)."""
    S, K = tok.shape
    BS, Wt = pool.shape[3], block_tab.shape[1]
    KV, hd = lm.num_key_value_heads, lm.head_dim
    R = lm.num_attention_heads // KV
    T = Wt * BS
    cos, sin = rope_cos_sin(lm, prompt_len + gen_pos)            # (S, hd)
    cos, sin = cos[:, None, :], sin[:, None, :]
    blk = jnp.take_along_axis(block_tab, (gen_pos // BS)[:, None], 1)[:, 0]
    off = gen_pos % BS
    at = jnp.arange(T)[None, :]
    seen = (gen_pos[:, None] >= at)[:, None, None, :]          # (S, 1, 1, T)
    now = (gen_pos[:, None] == at)[:, None, :, None]           # (S, 1, T, 1)
    prev = (gen_pos[:, None] - 1 == at)[:, None, :, None]
    scale = hd ** -0.5
    x = params["embed"][tok].astype(dtype)                  # (S, K, d)
    for i, p in enumerate(params["layers"]):
        h = rms_norm(x, p["attn_norm"], lm.rms_norm_eps).astype(dtype)
        q, k, v, gam = _projections(p, h, cos, sin, lm, dtype)
        # c_t = c_{t-1} + gam_t on the beam's own path (the pool moved with
        # the last selection); 0 before the first generated position
        c_gen = jnp.moveaxis(gates[i][block_tab], 2, 1).reshape(S, K, T, KV)
        c = jnp.sum(jnp.where(prev, c_gen, 0.0), axis=2) + gam   # (S, K, KV)
        c_gen = jnp.where(now, c[:, :, None, :], c_gen)
        gates = gates.at[i, blk, :, off, :].set(c, mode="drop")
        pool = pool.at[i, blk, :, off, :].set(
            jnp.concatenate([k.reshape(S, K, -1), v.reshape(S, K, -1)], -1),
            mode="drop")
        gen = jnp.moveaxis(pool[i][block_tab], 2, 1).reshape(S, K, T, -1)
        with jax.named_scope("ret.decode.state"):
            # a slot's K beams x R query heads are K R rows of ONE product
            # over each key/value head's state
            f = features(jnp.transpose(q.astype(jnp.float32), (0, 2, 1, 3, 4))
                         .reshape(S, KV, K * R, hd))      # (S, KV, K R, D)
            num_p = jnp.einsum("sgrD,sgDv->sgrv", f.astype(dtype), states[i],
                               preferred_element_type=jnp.float32)
            den_p = jnp.einsum("sgrD,sgD->sgr", f, norms[i],
                               precision=jax.lax.Precision.HIGHEST)
            num_p = jnp.transpose(num_p.reshape(S, KV, K, R, hd),
                                  (0, 2, 1, 3, 4))
            den_p = jnp.transpose(den_p.reshape(S, KV, K, R), (0, 2, 1, 3))
        with jax.named_scope("ret.decode.own"):
            k_g = gen[..., :KV * hd].reshape(S, K, T, KV, hd)
            v_g = gen[..., KV * hd:].reshape(S, K, T, KV, hd)
            s = jnp.einsum("skngd,sktnd->skngt", q, k_g,
                           preferred_element_type=jnp.float32) * scale
            decay = jnp.exp(jnp.where(
                seen, c[..., None] - jnp.moveaxis(c_gen, 2, 3), -jnp.inf))
            w = s * s * decay[:, :, :, None, :]         # (S, K, KV, R, T)
            num_g = jnp.einsum("skngt,sktnd->skngd", w.astype(dtype), v_g,
                               preferred_element_type=jnp.float32)
            e_c = prompt_weight(c)[..., None]                   # (S, K, KV, 1)
            y = (e_c[..., None] * num_p + num_g) \
                / (e_c * den_p + jnp.sum(w, -1) + lm.retention_eps)[..., None]
        x = (x.astype(jnp.float32)
             + mm(y.reshape(S, K, -1), p["w_o"], dtype)).astype(dtype)
        x = _mlp(p, x, lm, dtype)
    L = lm.num_hidden_layers
    counters = jnp.stack([
        jnp.sum(active, dtype=jnp.int32) * L,
        jnp.sum(jnp.where(active, gen_pos + 1, 0)).astype(jnp.int32)
        * (K * L)])
    return lm_head(params, x, lm, dtype), pool, gates, counters
