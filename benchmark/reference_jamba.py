"""Plain reference for Jamba2-3B (ai21labs/AI21-Jamba2-3B ``config.json``,
``model_type: jamba``): the decoder's forward pass in straightforward
``jax.numpy``, float32 at ``Precision.HIGHEST``. **The recurrence runs token
by token** (a ``lax.scan`` over t that carries ``H``), attention is
materialised under the causal mask, nothing is cached, nothing is batched;
imports nothing of ``fira_tpu``. Widths come from the benchmark's
configuration file, weights are the benchmark's own bfloat16 tree
(``weights_jamba.py``), upcast where they are used: each layer is one jitted
call that takes that layer's leaves.

The equations, ``x`` the residual stream, ``eps`` = ``rms_norm_eps``:

- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``. Embedding ``x = E[token]``.
- Layer i: ``x = x + Mixer(N1(x))``; ``x = x + MLP(N2(x))``; the mixer is
  attention iff ``i % attn_layer_period == attn_layer_offset``, else Mamba;
  the MLP is ``W_down(silu(h W_gate) * (h W_up))`` everywhere
  (``num_experts`` 1). Final RMSNorm; logits ``h E^T`` (tied head);
  log-softmax over the whole vocabulary.
- Mamba (d_inner = ``mamba_expand`` x hidden, N = ``mamba_d_state``, R =
  ``mamba_dt_rank``, taps = ``mamba_d_conv``), token t::

      [u_t | z_t]   = W_in h_t
      c_t           = silu(b_conv + sum_j w_conv[j] * u_{t-(taps-1)+j})
      [d_t|B_t|C_t] = W_x c_t;   d, B, C each RMSNorm'ed (a gain each)
      Delta_t       = softplus(W_dt d_t + b_dt)
      H_t           = exp(Delta_t (x) A) * H_{t-1} + (Delta_t * c_t) (x) B_t
      y_t           = H_t C_t + D * c_t            A = -exp(a_log), H_-1 = 0
      out_t         = W_out (y_t * silu(z_t))

  with ``u_s = 0`` for s < 0. ``H`` is (d_inner, N) here, as the equations
  have it; the tree stores ``a_log`` and ``conv_w`` transposed.
- Attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``hidden / heads``; scores ``q
  k^T / sqrt(head_dim)``, causal softmax, ``W_o``. No positions of any
  kind, no bias, no gate, no norm of q or k.

Departures and readings, each also true of the system under test: what the
catalog's row does not itself state is listed under ``assumed`` in the
configuration file (layer order, the three inner norms, no positions, the
head's size, dtypes, the weights' distribution).

``mode`` makes the same code the benchmark's two controls, each the nearest
precision below one the configuration states: ``"f32"`` is the reference;
``"fp8"`` rounds the operands of every matrix product to float8_e4m3fn
(float32 accumulation) where the configuration states bfloat16, the
recurrence float32 as in the reference; ``"state_bf16"`` keeps the products
exact and rounds the recurrent state ``H`` to bfloat16 after every token,
where the configuration states a float32 state (``assumed`` (e)).

A request's beams are scored one pass each over [prompt | beam]: a
recurrent state cannot be shared between two continuations under a mask, as
keys and values can.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

_OPERAND = {"fp8": jnp.float8_e4m3fn}   # a mode's matrix-product operands
# a mode's recurrent state: (exponent bits, mantissa bits) it is rounded to.
# ``lax.reduce_precision``, not a cast there and back: the chip's compiler
# takes a float32 -> bfloat16 -> float32 pair for excess precision it may
# keep and removes it (the control then read 0.0 on every number, PR 34)
_STATE = {"state_bf16": (8, 7)}


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


def is_attention(cfg: Dict, layer: int) -> bool:
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def mamba(p, x, cfg: Dict, mode: str):
    """x (T, d) normed -> (mixer output (T, d), (H_T (d_inner, N), the last
    taps - 1 inputs of the convolution (taps - 1, d_inner)))."""
    T = x.shape[0]
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    N, R, taps = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    eps = cfg["rms_norm_eps"]
    uz = mm("td,de->te", x, p["w_in"], mode)
    u, z = uz[:, :di], uz[:, di:]
    w = p["conv_w"].astype(jnp.float32)                     # (taps, di)
    before = jnp.concatenate([jnp.zeros((taps - 1, di)), u], 0)
    c = p["conv_b"].astype(jnp.float32) + sum(
        w[j] * before[j:j + T] for j in range(taps))
    c = jax.nn.silu(c)
    dbc = mm("te,er->tr", c, p["w_x"], mode)
    d = rms_norm(dbc[:, :R], p["dt_norm"], eps)
    Bm = rms_norm(dbc[:, R:R + N], p["b_norm"], eps)
    Cm = rms_norm(dbc[:, R + N:], p["c_norm"], eps)
    delta = jax.nn.softplus(mm("tr,re->te", d, p["w_dt"], mode)
                            + p["b_dt"].astype(jnp.float32))
    A = -jnp.exp(p["a_log"].astype(jnp.float32)).T           # (di, N)

    def token(H, xs):
        delta_t, c_t, B_t, C_t = xs
        H = jnp.exp(delta_t[:, None] * A) * H \
            + (delta_t * c_t)[:, None] * B_t[None, :]
        if mode in _STATE:
            H = jax.lax.reduce_precision(H, *_STATE[mode])
        return H, jnp.einsum("dn,n->d", H, C_t,
                             precision=jax.lax.Precision.HIGHEST)
    H, y = jax.lax.scan(token, jnp.zeros((di, N), jnp.float32),
                        (delta, c, Bm, Cm))
    y = y + p["d_skip"].astype(jnp.float32) * c
    out = mm("te,ed->td", y * jax.nn.silu(z), p["w_out"], mode)
    return out, (H, before[T:T + taps - 1])


def attention(p, x, cfg: Dict, mode: str):
    """x (T, d) normed -> (attention output (T, d), (keys, values) (T, KV,
    hd) each)."""
    T = x.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    q = mm("td,dh->th", x, p["w_q"], mode).reshape(T, H, hd)
    k = mm("td,dh->th", x, p["w_k"], mode).reshape(T, KV, hd)
    v = mm("td,dh->th", x, p["w_v"], mode).reshape(T, KV, hd)
    kk = jnp.repeat(k, H // KV, axis=1)     # query head i reads i // (H/KV)
    vv = jnp.repeat(v, H // KV, axis=1)
    s = mm("qhd,khd->hqk", q, kk, mode) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = mm("hqk,khd->qhd", jax.nn.softmax(s, -1), vv, mode)
    return mm("th,hd->td", o.reshape(T, H * hd), p["w_o"], mode), (k, v)


def swiglu(x, w_gate, w_up, w_down, mode: str):
    g = mm("td,dm->tm", x, w_gate, mode)
    u = mm("td,dm->tm", x, w_up, mode)
    return mm("tm,md->td", jax.nn.silu(g) * u, w_down, mode)


def block(p, x, cfg: Dict, mode: str, attn: bool):
    eps = cfg["rms_norm_eps"]
    mixer = attention if attn else mamba
    a, left = mixer(p, rms_norm(x, p["mixer_norm"], eps), cfg, mode)
    x = x + a
    return x + swiglu(rms_norm(x, p["mlp_norm"], eps), p["w_gate"],
                      p["w_up"], p["w_down"], mode), left


@functools.lru_cache(maxsize=None)
def _jitted(cfg_key, mode: str):
    cfg = dict(cfg_key)
    return (jax.jit(lambda p, x, attn: block(p, x, cfg, mode, attn),
                    static_argnums=(2,)),
            jax.jit(lambda g, embed, x: jax.nn.log_softmax(
                mm("td,vd->tv", rms_norm(x, g, cfg["rms_norm_eps"]), embed,
                   mode), -1)))


def _key(cfg: Dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def forward(cfg: Dict, params, tokens, mode: str = "f32", rows=None,
            with_state: bool = False):
    """tokens (T,) int -> log-probabilities (T, V) float32 (of ``rows``, a
    slice, where given): row t is the distribution of the token after
    ``tokens[t]``. ``with_state``: also what each layer would carry past
    the LAST token — (H, tail) a Mamba layer, (keys, values) an attention
    layer — which only the tests read."""
    tokens = jnp.asarray(tokens, jnp.int32)
    layer, head = _jitted(_key(cfg), mode)
    x = params["embed"][tokens].astype(jnp.float32)
    left = []
    for i, p in enumerate(params["layers"]):
        x, l = layer(p, x, is_attention(cfg, i))    # a layer at a time
        left.append(l)
    if rows is not None:
        x = x[rows]
    logp = head(params["final_norm"], params["embed"], x)
    return (logp, left) if with_state else logp


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
