"""Plain reference for LFM2-8B-A1B (LiquidAI/LFM2-8B-A1B ``config.json``,
``model_type: lfm2_moe``): the decoder's forward pass in straightforward
``jax.numpy``, float32 at ``Precision.HIGHEST`` (every product names it).
The short convolution is written out over whole sequences, attention is
materialised under the causal mask, nothing is cached, nothing is batched;
imports nothing of ``fira_tpu``. Widths come from the benchmark's
configuration file, weights are the benchmark's own bfloat16 tree
(``weights_lfm2.py``), upcast where they are used: each layer is one jitted
call that takes that layer's leaves, so no float32 copy of the model exists.

The equations, ``x`` the residual stream, ``eps`` = ``norm_eps``:

- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g`` (the gain g, not 1 + g).
  Embedding ``x = E[token]``, no scale.
- Layer i: ``h = x + Op_i(N1(x))``; ``x = h + FFN_i(N2(h))``. Final
  RMSNorm; logits ``x E^T`` (the head tied to the embedding); log-softmax
  over the whole vocabulary.
- ``layer_types[i] == "conv"`` (taps = ``conv_L_cache``, no bias)::

      [B | C | u] = z W_in          W_in (d, 3d), split in that order
      v_t         = B_t * u_t
      c_t         = sum_{j=0..taps-1} w_conv[j] * v_{t-(taps-1)+j}, v_{<0} = 0
      Op          = (C_t * c_t) W_out

  no activation anywhere.
- ``"full_attention"``: ``q = RMSNorm_head(z W_q)``, ``k = RMSNorm_head(z
  W_k)`` (a gain vector over ``head_dim`` each), both rotated (``rope_theta``,
  pairs (i, i + head_dim/2), positions from 0), ``v = z W_v``; query head i
  reads key/value head ``i // (heads / kv heads)``; softmax(q k^T /
  sqrt(head_dim)) v under the causal mask; ``W_o``.
- FFN, i < ``num_dense_layers``: ``W_down(silu(y W_gate) * (y W_up))``.
  Otherwise ``s = sigmoid(y W_r)`` over all ``num_experts``; chosen = top-k
  of ``s + b`` (``b`` the expert bias, ``use_expert_bias``: it chooses and
  never weighs; equal sums go to the lower index); ``w_e = s_e / (sum_chosen
  s + 1e-6) * routed_scaling_factor`` (``norm_topk_prob``); the weighted sum
  of the chosen experts' SwiGLUs. No shared expert, no token dropped.

Departures and readings, each also true of the system under test: what the
catalog's row does not itself state is listed under ``assumed`` in the
configuration file ((a) the tied head, (b) the gain, (c) the split order and
no bias or activation, (d) the q/k norms before rotary with half-split
pairs, (e) the router's 1e-6, (f) the dense width as given, (g) the weights'
draw). Beyond them:

- Attention is computed a block of queries at a time (a ``lax.map``) so that
  a (heads, T, T) float32 score tensor need not exist; each query's row is
  the whole softmax over ALL T keys under the mask.
- **An expert is computed on the tokens that chose it**, not on all of them:
  the (token, expert) assignments are sorted by expert and walked in chunks
  of ``EXPERT_CHUNK`` rows, each chunk of ONE expert (its last chunk
  part-filled and masked), added into its tokens' rows times their weights.
  The sum is the definition's, in another order.
- A request's beams are scored one pass each over [prompt | beam]: a
  convolution over a packed sequence would read one beam's tokens into the
  next beam's first positions.

``mode`` picks how matrix products are computed, which makes the same code
the benchmark's control: ``"f32"`` (the reference) or ``"fp8"`` (operands
rounded to float8_e4m3fn, float32 accumulation: the nearest precision below
the bfloat16 the configuration states).
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

_OPERAND = {"fp8": jnp.float8_e4m3fn}
Q_BLOCK = 128        # queries whose scores exist at a time (at most)
EXPERT_CHUNK = 256   # rows of one expert computed at a time
ROUTER_EPS = 1e-6
CONV = "conv"


def _round(x, mode: str):
    if mode == "f32":
        return x.astype(jnp.float32)
    return x.astype(_OPERAND[mode]).astype(jnp.float32)


def mm(eq: str, a, b, mode: str):
    return jnp.einsum(eq, _round(a, mode), _round(b, mode),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def short_conv(p, z, cfg: Dict, mode: str):
    """z (T, d) normed -> the gated short convolution's output (T, d)."""
    T, d = z.shape
    taps = cfg["conv_L_cache"]
    bcu = mm("td,de->te", z, p["conv_in"], mode)
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    v = jnp.concatenate([jnp.zeros((taps - 1, d)), b * u], 0)
    w = p["conv_w"].astype(jnp.float32)                   # (taps, d)
    conv = sum(w[j] * v[j:j + T] for j in range(taps))
    return mm("td,de->te", c * conv, p["conv_out"], mode)


def rope_tables(cfg: Dict, T: int, hd: int):
    inv_freq = jnp.asarray(float(cfg["rope_theta"]) ** (
        -np.arange(0, hd, 2, dtype=np.float64) / hd), jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]


def rotate(x, cos, sin):
    h = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., h:], x[..., :h]], -1) * sin


def attention(p, z, cfg: Dict, mode: str):
    """z (T, d) normed -> causal GQA attention's output (T, d)."""
    T, d = z.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = d // H, cfg["norm_eps"]
    cos, sin = rope_tables(cfg, T, hd)
    q = rotate(rms_norm(mm("td,dh->th", z, p["w_q"], mode).reshape(T, H, hd),
                        p["q_norm"], eps), cos, sin)
    k = rotate(rms_norm(mm("td,dh->th", z, p["w_k"], mode).reshape(T, KV, hd),
                        p["k_norm"], eps), cos, sin)
    v = mm("td,dh->th", z, p["w_v"], mode).reshape(T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)      # query head i reads i // (H/KV)
    v = jnp.repeat(v, H // KV, axis=1)
    qb = math.gcd(T, Q_BLOCK)

    def rows(at):               # the whole softmax of qb queries
        s = mm("qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, at, qb, 0),
               k, mode) * hd ** -0.5
        seen = (at + jnp.arange(qb))[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(seen[None], s, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v, mode)
    o = jax.lax.map(rows, jnp.arange(0, T, qb)).reshape(T, H * hd)
    return mm("th,hd->td", o, p["w_o"], mode)


def swiglu(x, w_gate, w_up, w_down, mode: str):
    g = mm("td,dm->tm", x, w_gate, mode)
    u = mm("td,dm->tm", x, w_up, mode)
    return mm("tm,md->td", jax.nn.silu(g) * u, w_down, mode)


def _top(x, k: int):
    """Indices of the k largest along the last axis, equal values lowest
    index first."""
    return jnp.argsort(-x, axis=-1, stable=True)[..., :k]


def route(scores, bias, cfg: Dict):
    """scores (T, E) float32, bias (E,) -> (ids (T, k), weights (T, k)):
    the top-k of ``scores + bias``, weighed by ``scores`` alone."""
    ids = _top(scores + bias.astype(jnp.float32)[None, :],
               cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, ids, 1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + ROUTER_EPS)
    return ids, w * cfg["routed_scaling_factor"]


def expert_layer(p, y, cfg: Dict, mode: str):
    T, _d = y.shape
    k, C = cfg["num_experts_per_tok"], EXPERT_CHUNK
    E = p["experts_gate"].shape[0]
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", y, p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    ids, w = route(scores, p["expert_bias"], cfg)
    # the assignments in expert order
    flat = ids.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    loads = jnp.sum(flat[:, None] == jnp.arange(E)[None, :], 0)
    first = jnp.cumsum(loads) - loads               # an expert's first row
    chunks = -(-loads // C)                         # chunks an expert takes
    chunk_end = jnp.cumsum(chunks)
    order = jnp.concatenate([order, jnp.zeros((C,), order.dtype)])
    w_flat = w.reshape(-1)

    def one_chunk(j, out):
        e = jnp.minimum(jnp.searchsorted(chunk_end, j, side="right"), E - 1)
        at = first[e] + (j - (chunk_end[e] - chunks[e])) * C
        real = (at + jnp.arange(C) < first[e] + loads[e]) \
            & (j < chunk_end[-1])
        sel = jax.lax.dynamic_slice_in_dim(order, jnp.minimum(at, T * k), C)
        tok = sel // k
        f = swiglu(y[tok], p["experts_gate"][e], p["experts_up"][e],
                   p["experts_down"][e], mode)
        f = jnp.where(real[:, None], f * w_flat[sel][:, None], 0.0)
        return out.at[jnp.where(real, tok, T)].add(f, mode="drop")
    return jax.lax.fori_loop(0, -(-T * k // C) + E, one_chunk,
                             jnp.zeros_like(y))


def block(p, x, cfg: Dict, mode: str, kind: str, dense: bool):
    eps = cfg["norm_eps"]
    op = short_conv if kind == CONV else attention
    h = x + op(p, rms_norm(x, p["op_norm"], eps), cfg, mode)
    y = rms_norm(h, p["ffn_norm"], eps)
    return h + (swiglu(y, p["w_gate"], p["w_up"], p["w_down"], mode) if dense
                else expert_layer(p, y, cfg, mode))


@functools.lru_cache(maxsize=None)
def _jitted(cfg_key, mode: str):
    cfg = dict(cfg_key)
    return (jax.jit(lambda p, x, kind, dense: block(p, x, cfg, mode, kind,
                                                    dense),
                    static_argnums=(2, 3)),
            jax.jit(lambda g, embed, x: jax.nn.log_softmax(
                mm("td,vd->tv", rms_norm(x, g, cfg["norm_eps"]), embed,
                   mode), -1)))


def _key(cfg: Dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def forward(cfg: Dict, params, tokens, mode: str = "f32", rows=None):
    """tokens (T,) int -> log-probabilities (T, V) float32 (of ``rows``, a
    slice, where given): row t is the distribution of the token after
    ``tokens[t]``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    layer, head = _jitted(_key(cfg), mode)
    x = params["embed"][tokens].astype(jnp.float32)
    for i, (p, kind) in enumerate(zip(params["layers"], cfg["layer_types"])):
        x = layer(p, x, kind, i < cfg["num_dense_layers"])  # a layer at a time
    if rows is not None:
        x = x[rows]
    return head(params["final_norm"], params["embed"], x)


def score_request(cfg: Dict, params, prompt, beams, beam: int,
                  mode: str = "f32", probe_ids=None, pad_to: int = 0
                  ) -> Dict[str, np.ndarray]:
    """One request: ``prompt`` (P,) ids; ``beams`` (R, n + 1) ids, each a
    beam's <start> and n tokens. One forward pass A BEAM over [prompt |
    beam], padded at its END to ``pad_to`` tokens where larger (what comes
    after a token cannot reach it). For each beam and each of its n
    predictions -> the log-probability of the served token, of the
    ``beam``-th best, the ``beam`` best ids, the log-probabilities at
    ``probe_ids`` (R, n, m) and the served token's rank (0 = best, at most
    ``beam - 1``)."""
    prompt = np.asarray(prompt, np.int32)
    beams = np.asarray(beams, np.int32)
    P, (R, n1) = len(prompt), beams.shape
    n = n1 - 1
    T = max(P + n, int(pad_to))
    logp = []
    for r in range(R):
        tokens = np.zeros((T,), np.int32)
        tokens[:P], tokens[P:P + n] = prompt, beams[r, :n]
        logp.append(forward(cfg, params, tokens, mode,
                            rows=slice(P, P + n)))
    logp = jnp.stack(logp)                                  # (R, n, V)
    nxt = jnp.asarray(beams[:, 1:])
    logp_token = jnp.take_along_axis(logp, nxt[..., None], -1)[..., 0]
    top_vals, top_ids = jax.lax.top_k(logp, beam)
    out = {"logp_token": logp_token, "logp_kth": top_vals[..., -1],
           "top_ids": top_ids,
           "rank": jnp.minimum(jnp.sum(logp > logp_token[..., None], -1),
                               beam - 1)}
    if probe_ids is not None:
        out["logp_probe"] = jnp.take_along_axis(
            logp, jnp.asarray(probe_ids), -1)
    return {k: np.asarray(v) for k, v in out.items()}
