"""Plain reference for Brumby-14B-Base (manifestai/Brumby-14B-Base
``config.json``, ``model_type: brumby``): the decoder's forward pass in
straightforward ``jax.numpy``, float32 at ``Precision.HIGHEST``. **Retention
runs in its attention form** — every (query, key) weight written out, in
blocks of queries so that the weights of one block fit — nothing is cached,
nothing is batched, no feature map; imports nothing of ``fira_tpu``. Widths
come from the benchmark's configuration file, weights are the benchmark's
own bfloat16 tree (``weights_brumby.py``), upcast where they are used: each
layer is one jitted call that takes that layer's leaves.

The equations, ``x`` the residual stream, ``eps`` = ``rms_norm_eps``:

- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``. Embedding ``x = E[token]``.
- Layer: ``x = x + W_o R(N1(x))``; ``x = x + W_down(silu(h W_gate) * (h
  W_up))`` with ``h = N2(x)``. Final RMSNorm; logits ``h W_head`` (untied);
  log-softmax over the whole vocabulary.
- Retention ``R`` over ``num_attention_heads`` query heads and
  ``num_key_value_heads`` key/value heads of ``head_dim``, query head h
  reading key/value head ``h // (H / KV)``, token t::

      q_t = rope(RMSNorm(W_q x_t)_h) with the gain q_norm; k_t likewise (k_norm)
      v_t = (W_v x_t)_g;  gam_t = log_sigmoid(x_t W_ret_gate + b_ret_gate)_g
      w_{t,s} = exp(sum_{r=s+1..t} gam_r) * (q_t . k_s / sqrt(head_dim))^2
      y_t = sum_{s<=t} w_{t,s} v_s / (sum_{s<=t} w_{t,s} + retention_eps)

  rope: ``rope_theta``, pairs (i, i + head_dim / 2), position t from 0.

Departures and readings, each also true of the system under test: what the
catalog's row does not itself state is listed under ``assumed`` in the
configuration file (the degree, the gate, the normaliser, positions, dtypes,
the weights' distribution).

``mode``: ``"f32"`` is the reference; ``"fp8"`` rounds the operands of
every matrix product to float8_e4m3fn (float32 accumulation) where the
configuration states bfloat16 — the benchmark's control.

:func:`recurrent_state` is the recurrent form, token by token, in its own
layout (pairs i <= j in row order): what a slot should carry after a
prompt, which only the tests read.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

_OPERAND = {"fp8": jnp.float8_e4m3fn}   # a mode's matrix-product operands
QUERY_BLOCK = 128                       # queries whose weights exist at once


def _round(x, mode: str):
    if mode not in _OPERAND:
        return x.astype(jnp.float32)
    return x.astype(_OPERAND[mode]).astype(jnp.float32)


def mm(eq: str, a, b, mode: str):
    return jnp.einsum(eq, _round(a, mode), _round(b, mode),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def rope(x, theta: float):
    """x (T, heads, hd), rotated at positions 0..T-1: pairs (i, i + hd/2)."""
    T, _n, hd = x.shape
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(np.concatenate([ang, ang], -1)), jnp.float32)
    sin = jnp.asarray(np.sin(np.concatenate([ang, ang], -1)), jnp.float32)
    half = hd // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def qkvg(p, x, cfg: Dict, mode: str):
    """x (T, d) normed -> q (T, H, hd), k, v (T, KV, hd), gam (T, KV)."""
    T = x.shape[0]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = rms_norm(mm("td,dh->th", x, p["w_q"], mode).reshape(T, H, hd),
                 p["q_norm"], eps)
    k = rms_norm(mm("td,dh->th", x, p["w_k"], mode).reshape(T, KV, hd),
                 p["k_norm"], eps)
    v = mm("td,dh->th", x, p["w_v"], mode).reshape(T, KV, hd)
    gam = jax.nn.log_sigmoid(mm("td,dg->tg", x, p["w_ret_gate"], mode)
                             + p["b_ret_gate"].astype(jnp.float32))
    return rope(q, theta), rope(k, theta), v, gam


def retention(q, k, v, gam, eps: float, mode: str):
    """The attention form: q (T, H, hd), k, v (T, KV, hd), gam (T, KV) ->
    y (T, H, hd), a block of QUERY_BLOCK queries at a time (T a multiple of
    it, or less)."""
    T, H, hd = q.shape
    r = H // k.shape[1]
    kk, vv = jnp.repeat(k, r, axis=1), jnp.repeat(v, r, axis=1)
    G = jnp.repeat(jnp.cumsum(gam, 0), r, axis=1).T              # (H, T)
    Qb = min(QUERY_BLOCK, T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Qb, Qb, 0)
        Gq = jax.lax.dynamic_slice_in_dim(G, i * Qb, Qb, 1)
        s = mm("qhd,khd->hqk", qb, kk, mode) * hd ** -0.5
        seen = (i * Qb + jnp.arange(Qb))[:, None] >= jnp.arange(T)[None, :]
        decay = jnp.exp(jnp.where(seen[None], Gq[:, :, None] - G[:, None, :],
                                  -jnp.inf))
        w = s * s * decay                                       # (H, Qb, T)
        y = mm("hqk,khd->qhd", w, vv, mode)
        return y / (jnp.sum(w, -1).T[..., None] + eps)
    y = jax.lax.map(block, jnp.arange(T // Qb))
    return y.reshape(T, H, hd)


def block(p, x, cfg: Dict, mode: str):
    eps = cfg["rms_norm_eps"]
    T = x.shape[0]
    q, k, v, gam = qkvg(p, rms_norm(x, p["attn_norm"], eps), cfg, mode)
    y = retention(q, k, v, gam, cfg["retention_eps"], mode)
    x = x + mm("th,hd->td", y.reshape(T, -1), p["w_o"], mode)
    h = rms_norm(x, p["mlp_norm"], eps)
    g = mm("td,dm->tm", h, p["w_gate"], mode)
    u = mm("td,dm->tm", h, p["w_up"], mode)
    return x + mm("tm,md->td", jax.nn.silu(g) * u, p["w_down"], mode)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_key, mode: str):
    cfg = dict(cfg_key)
    return (jax.jit(lambda p, x: block(p, x, cfg, mode)),
            jax.jit(lambda g, head, x: jax.nn.log_softmax(
                mm("td,dv->tv", rms_norm(x, g, cfg["rms_norm_eps"]), head,
                   mode), -1)))


def _key(cfg: Dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def forward(cfg: Dict, params, tokens, mode: str = "f32", rows=None):
    """tokens (T,) int -> log-probabilities (T, V) float32 (of ``rows``, a
    slice, where given): row t is the distribution of the token after
    ``tokens[t]``. Past QUERY_BLOCK tokens the pass is padded at its END to
    a multiple of it (what comes after a token cannot reach it)."""
    tokens = np.asarray(tokens, np.int32)
    T = len(tokens)
    if T > QUERY_BLOCK and T % QUERY_BLOCK:
        tokens = np.concatenate([tokens, np.zeros(
            (QUERY_BLOCK - T % QUERY_BLOCK,), np.int32)])
    layer, head = _jitted(_key(cfg), mode)
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for p in params["layers"]:
        x = layer(p, x)                                  # a layer at a time
    x = x[:T] if rows is None else x[rows]
    return head(params["final_norm"], params["head"], x)


def features(u):
    """The degree-2 feature map in the reference's own layout: u (..., d)
    -> (u_i u_j, times sqrt 2 where i < j)_{i <= j} / sqrt d."""
    d = u.shape[-1]
    i, j = np.triu_indices(d)
    return u[..., i] * u[..., j] * jnp.asarray(
        np.where(i == j, 1.0, np.sqrt(2.0)), jnp.float32) * d ** -0.5


def recurrent_state(cfg: Dict, params, tokens):
    """The recurrent form over ``tokens`` (T,), token by token: each
    layer's ``(S, z)`` past the LAST token, S (KV, D, hd), z (KV, D) in
    :func:`features`' layout — ``S_t = e^{gam_t} S_{t-1} + phi(k_t)
    v_t^T``, ``z_t = e^{gam_t} z_{t-1} + phi(k_t)`` from zero."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    out = []
    for p in params["layers"]:
        _q, k, v, gam = qkvg(p, rms_norm(x, p["attn_norm"], eps), cfg, "f32")

        def token(carry, xs):
            S, z = carry
            k_t, v_t, g_t = xs
            f = features(k_t)                                    # (KV, D)
            a = jnp.exp(g_t)[:, None]
            return (a[..., None] * S + f[..., None] * v_t[:, None, :],
                    a * z + f), None
        KV, hd = k.shape[1], k.shape[2]
        D = hd * (hd + 1) // 2
        (S, z), _ = jax.lax.scan(token, (jnp.zeros((KV, D, hd)),
                                         jnp.zeros((KV, D))), (k, v, gam))
        out.append((S, z))
        x = block(p, x, cfg, "f32")
    return out


def score_request(cfg: Dict, params, prompt, beams, beam: int,
                  mode: str = "f32", probe_ids=None, pad_to: int = 0
                  ) -> Dict[str, np.ndarray]:
    """One request: ``prompt`` (P,) ids; ``beams`` (R, n + 1) ids, each a
    beam's <start> and n tokens. One forward pass A BEAM over [prompt |
    beam], padded at its END to ``pad_to`` tokens where larger. For each
    beam and each of its n predictions -> the log-probability of the served
    token, of the ``beam``-th best, the ``beam`` best ids, the
    log-probabilities at ``probe_ids`` (R, n, m) and the served token's rank
    (0 = best, at most ``beam - 1``)."""
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
