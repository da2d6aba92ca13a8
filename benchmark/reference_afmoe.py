"""Plain reference for Trinity-Mini (arcee-ai/Trinity-Mini ``config.json``,
``model_type: afmoe``): the decoder's forward pass in straightforward
``jax.numpy``, float32 at ``Precision.HIGHEST``. Materialised attention
under a mask, no cache, no kernels, no batching; imports nothing of
``fira_tpu``. Widths come from the benchmark's configuration file, weights
are the benchmark's own bfloat16 tree (``weights_afmoe.py``), upcast where
they are used: each layer is one jitted call that takes that layer's
bfloat16 leaves.

The equations, ``x`` the residual stream, ``eps`` = ``rms_norm_eps``:

- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``. Embedding: ``x = E[token]
  * sqrt(hidden_size)`` (``mup_enabled``).
- Block: ``x = x + N2(Attn(N1(x)))``; ``x = x + N4(MLP(N3(x)))``: four
  RMSNorms a layer (input, post-attention, pre-MLP, post-MLP). Final
  RMSNorm, untied head, log-softmax over the whole vocabulary.
- Attention: ``q = h W_q`` (``num_attention_heads`` of ``head_dim``), ``k =
  h W_k``, ``v = h W_v`` (``num_key_value_heads``), ``g = h W_g``; q and k
  each RMSNorm'ed over ``head_dim`` (one gain vector for q, one for k); no
  biases. Query head i reads key/value head ``i // (heads / kv heads)``.
  Scores ``q k^T / sqrt(head_dim)``, softmax over the keys allowed, output
  ``(P v) * sigmoid(g)``, then ``W_o``.
  ``sliding_attention`` layers: q and k rotated (``rope_theta`` over all of
  ``head_dim``, no scaling); position i sees j with ``0 <= i - j <
  sliding_window``. ``full_attention`` layers: NOT rotated; every j <= i.
- Dense layer (index < ``num_dense_layers``): ``W_down(silu(h W_gate) * (h
  W_up))``.
- Expert layer: ``s = sigmoid(h W_r)`` over all ``num_experts``, float32;
  chosen = top-k of ``s + b`` (``b`` the per-expert selection bias: it
  chooses and never weighs; equal sums go to the lower index); ``w_e = s_e /
  (sum_chosen s + 1e-20) * route_scale`` (``route_norm``); output = shared
  expert + sum over the chosen experts **that the configuration holds**
  (``experts_held`` from ``expert_offset``; all of them in the benchmark's
  file). ``n_group = topk_group = 1``: no group limit.

Departures and readings, each also true of the system under test:

- What the catalog's row does not itself state comes from the published
  model code and is listed under ``assumed`` in the configuration file: the
  output gate and its place, the q/k norms, no rotary on full layers, the
  four norms a layer, the selection bias.
- The rotary pairs are (i, i + head_dim/2); a published checkpoint's pairing
  is a permutation of the projection's columns, which with weights drawn
  from a seed is the same model.
- Attention is computed a block of queries at a time (a ``lax.map``) so
  that a (heads, T, T) float32 score tensor need not exist; each query's
  row is the whole softmax over ALL T keys under the mask, so the values
  are those of the unblocked form (no band is cut out: a window layer
  scores every key and masks).
- **An expert is computed on the tokens that chose it**, not on all of
  them (at 128 experts, top-8, every expert over every token is 16 times
  the routed work): the (token, expert) assignments are sorted by expert
  and walked in chunks of ``EXPERT_CHUNK`` rows, each chunk of ONE expert
  (its last chunk part-filled and masked); every chunk is a plain SwiGLU
  with that expert's matrices, added into its tokens' rows times their
  weights. The sum is the definition's, in another order.
- A request's two beams are scored in ONE forward pass over [prompt |
  beam A | beam B]: a token sees the prompt and its own beam's earlier
  tokens, and a beam's positions continue the prompt's (``seen`` and
  ``positions`` of :func:`forward`); the window is counted in those
  positions. The head is computed only on the rows asked for (``rows``):
  (16,640 x 200,192) float32 logits would be 13 GB.

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
SLIDING = "sliding_attention"


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


def rope_tables(cfg: Dict, positions):
    hd = cfg["head_dim"]
    inv_freq = jnp.asarray(float(cfg["rope_theta"]) ** (
        -np.arange(0, hd, 2, dtype=np.float64) / hd), jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, cos, sin):
    h = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., h:], x[..., :h]], -1) * sin


def attention(p, x, positions, seen, cfg: Dict, mode: str, kind: str):
    """x (T, d) normed; seen (T, T) bool: row i may attend column j (the
    causal order and the beams' segments); a window layer also asks that
    ``positions[i] - positions[j] < sliding_window``."""
    T = x.shape[0]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = rms_norm(mm("td,dh->th", x, p["w_q"], mode).reshape(T, H, hd),
                 p["q_norm"], eps)
    k = rms_norm(mm("td,dh->th", x, p["w_k"], mode).reshape(T, KV, hd),
                 p["k_norm"], eps)
    v = mm("td,dh->th", x, p["w_v"], mode).reshape(T, KV, hd)
    gate = jax.nn.sigmoid(mm("td,dh->th", x, p["w_g"], mode))
    if kind == SLIDING:
        cos, sin = rope_tables(cfg, positions)
        q = rotate(q, cos[:, None], sin[:, None])
        k = rotate(k, cos[:, None], sin[:, None])
        seen = seen & (positions[:, None] - positions[None, :]
                       < cfg["sliding_window"])
    k = jnp.repeat(k, H // KV, axis=1)      # query head i reads i // (H/KV)
    v = jnp.repeat(v, H // KV, axis=1)
    qb = math.gcd(T, Q_BLOCK)

    def rows(at):               # the whole softmax of qb queries
        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, at, qb, 0)
        s = mm("qhd,khd->hqk", cut(q), k, mode) * hd ** -0.5
        s = jnp.where(cut(seen)[None], s, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v, mode)
    o = jax.lax.map(rows, jnp.arange(0, T, qb)).reshape(T, H * hd)
    return mm("th,hd->td", o * gate, p["w_o"], mode)


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
    if cfg["route_norm"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids, w * cfg["route_scale"]


def expert_layer(p, x, cfg: Dict, mode: str):
    T, d = x.shape
    k, C = cfg["num_experts_per_tok"], EXPERT_CHUNK
    held = p["experts_gate"].shape[0]
    off = int(cfg.get("expert_offset", 0))
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x, p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    ids, w = route(scores, p["router_bias"], cfg)
    y = swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"], mode)
    # the assignments to the experts held, in expert order; the others last
    local = (ids - off).reshape(-1)
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True)
    loads = jnp.sum(local[:, None] == jnp.arange(held)[None, :], 0)
    first = jnp.cumsum(loads) - loads               # an expert's first row
    chunks = -(-loads // C)                         # chunks an expert takes
    chunk_end = jnp.cumsum(chunks)
    order = jnp.concatenate([order, jnp.zeros((C,), order.dtype)])
    w_flat = w.reshape(-1)

    def one_chunk(j, y):
        e = jnp.minimum(jnp.searchsorted(chunk_end, j, side="right"),
                        held - 1)
        at = first[e] + (j - (chunk_end[e] - chunks[e])) * C
        real = (at + jnp.arange(C) < first[e] + loads[e]) \
            & (j < chunk_end[-1])
        sel = jax.lax.dynamic_slice_in_dim(order, jnp.minimum(
            at, T * k), C)
        tok = sel // k
        out = swiglu(x[tok], p["experts_gate"][e], p["experts_up"][e],
                     p["experts_down"][e], mode)
        out = jnp.where(real[:, None], out * w_flat[sel][:, None], 0.0)
        return y.at[jnp.where(real, tok, T)].add(out, mode="drop")
    return jax.lax.fori_loop(0, -(-T * k // C) + held, one_chunk, y)


def block(p, x, positions, seen, cfg: Dict, mode: str, kind: str):
    eps = cfg["rms_norm_eps"]
    a = attention(p, rms_norm(x, p["attn_norm"], eps), positions, seen, cfg,
                  mode, kind)
    x = x + rms_norm(a, p["post_attn_norm"], eps)
    h = rms_norm(x, p["mlp_norm"], eps)
    f = expert_layer(p, h, cfg, mode) if "router" in p else \
        swiglu(h, p["w_gate"], p["w_up"], p["w_down"], mode)
    return x + rms_norm(f, p["post_mlp_norm"], eps)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_key, mode: str):
    cfg = dict(cfg_key)
    return (jax.jit(lambda p, x, pos, seen, kind: block(
        p, x, pos, seen, cfg, mode, kind), static_argnums=(4,)),
            jax.jit(lambda g, head, x: jax.nn.log_softmax(
                mm("td,dv->tv", rms_norm(x, g, cfg["rms_norm_eps"]), head,
                   mode), -1)))


def _key(cfg: Dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def forward(cfg: Dict, params, tokens, mode: str = "f32",
            positions=None, seen=None, rows=None):
    """tokens (T,) int -> log-probabilities (T, V) float32 (of ``rows``, a
    slice, where given): row t is the distribution of the token after
    ``tokens[t]``. ``positions`` (T,) default to 0..T-1 and ``seen`` (T, T)
    to the causal mask; a caller that packs several continuations of one
    prompt passes its own."""
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    if positions is None:
        positions = jnp.arange(T)
    if seen is None:
        seen = jnp.tril(jnp.ones((T, T), bool))
    positions, seen = jnp.asarray(positions), jnp.asarray(seen)
    layer, head = _jitted(_key(cfg), mode)
    x = params["embed"][tokens].astype(jnp.float32)
    if cfg["mup_enabled"]:
        x = x * math.sqrt(cfg["hidden_size"])
    for p, kind in zip(params["layers"], cfg["layer_types"]):
        x = layer(p, x, positions, seen, kind)      # a layer at a time
    if rows is not None:
        x = x[rows]
    return head(params["final_norm"], params["head"], x)


def score_request(cfg: Dict, params, prompt, beams, beam: int,
                  mode: str = "f32", probe_ids=None, pad_to: int = 0
                  ) -> Dict[str, np.ndarray]:
    """One request: ``prompt`` (P,) ids; ``beams`` (R, n + 1) ids, each a
    beam's <start> and n tokens. One forward pass over [prompt | beam 0 |
    ... | beam R-1], padded to ``pad_to`` tokens where larger (padding is
    seen by nothing and sees only itself). For each beam and each of its n
    predictions -> the log-probability of the served token, of the
    ``beam``-th best, the ``beam`` best ids, the log-probabilities at
    ``probe_ids`` (R, n, m) and the served token's rank (0 = best, at most
    ``beam - 1``)."""
    prompt = np.asarray(prompt, np.int32)
    beams = np.asarray(beams, np.int32)
    P, (R, n1) = len(prompt), beams.shape
    n = n1 - 1
    T = max(P + R * n, int(pad_to))
    tokens = np.zeros((T,), np.int32)
    positions = np.zeros((T,), np.int32)
    seg = np.full((T,), -1, np.int32)
    tokens[:P], positions[:P], seg[:P] = prompt, np.arange(P), 0
    for r in range(R):
        at = P + r * n
        tokens[at:at + n] = beams[r, :n]
        positions[at:at + n] = P + np.arange(n)
        seg[at:at + n] = r + 1
    order = np.arange(T)
    seen = (order[None, :] <= order[:, None]) & (
        (seg[None, :] == 0) | (seg[None, :] == seg[:, None])) \
        & (seg[:, None] >= 0)
    seen |= np.eye(T, dtype=bool)
    logp = forward(cfg, params, tokens, mode, positions, seen,
                   rows=slice(P, P + R * n))
    logp = logp.reshape(R, n, -1)
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
