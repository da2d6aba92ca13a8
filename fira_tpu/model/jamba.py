"""Jamba2-3B (``arch="jamba"``): a decoder whose layers come in two kinds —
Mamba-1 state-space layers, whose memory of the sequence is a RECURRENT
STATE of fixed size, and every ``attn_layer_period``-th an attention layer
over one shared key/value head, without positions of any kind (the Mamba
layers order the sequence) — each followed by a dense SwiGLU, as its
published ``config.json`` (``model_type: jamba``) describes it
(config.JambaConfig holds the keys).

Plain functions over a parameter tree, as model/axk1.py and model/afmoe.py,
whose pieces this module shares (``rms_norm``, ``mm``, ``swiglu``; the
blocked causal attention and the decode attention of afmoe): the slot
engine (decode/slot_model.py) calls :func:`prefill` once a request and
:func:`decode_step` once a position. The layer equations, ``x`` the
residual stream:

- Block: ``x = x + Mixer(N1(x))``; ``x = x + MLP(N2(x))``; ``N`` RMSNorm
  with a gain. Final RMSNorm, logits ``h E^T`` with the embedding matrix
  (``tie_word_embeddings``), log-softmax.
- Mamba mixer, token t of one sequence (d_inner = ``mamba_expand`` x d,
  N = ``mamba_d_state``, R = ``mamba_dt_rank``)::

      [u_t | z_t]   = W_in h_t
      c_t           = SiLU(b_conv + sum_j w_conv[j] * u_{t-3+j})   j = 0..3
      [d_t|B_t|C_t] = W_x c_t;  each RMSNorm'ed with a gain of its own
      Delta_t       = softplus(W_dt d_t + b_dt)
      H_t           = exp(Delta_t (x) A) * H_{t-1} + (Delta_t * c_t) (x) B_t
      y_t           = H_t C_t + D * c_t;      A = -exp(A_log)
      out_t         = W_out (y_t * SiLU(z_t))

  **Carried between positions: ``H_t`` (N x d_inner, float32) and the
  convolution's tail ``(u_{t-2}, u_{t-1}, u_t)``.** Both lie with d_inner
  LAST (``(N, d_inner)``, ``(3, d_inner)``): the chip tiles an array's last
  two axes by (8, 128), and a trailing axis of 16 would be padded to 128 —
  eight times the bytes.
- Attention layer: ``q = h W_q`` (H heads), ``k = h W_k``, ``v = h W_v``
  (KV heads: one), scores ``q k^T / sqrt(head_dim)``, causal softmax, then
  ``W_o``. No rotation, no bias, no gate, no norm of q or k. Cached per
  token: ``[k | v]``, a prompt's positions last and keys and values apart
  (model/afmoe.prompt_layout).

Prefill is a scan over time (:func:`selective_scan`): a padded position has
``Delta = 0`` — ``exp(0) H + 0`` leaves the state where the prompt's last
token put it — and lies after every real one, so neither the state nor the
tail a request hands over knows its bucket. Compute runs in ``dtype``
(bfloat16 on the chip) with float32 accumulation; the recurrence, norms,
softplus, softmax and log-softmax are float32.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fira_tpu.config import JambaConfig
from fira_tpu.model.afmoe import attend_decode, attend_prefill
from fira_tpu.model.axk1 import mm, rms_norm, swiglu

# the counters a step returns, in this order: slot-beams whose recurrent
# state a position updated (occupied slots x beams), and the keys the
# attention layers were ASKED to cover (occupied slots x attention layers x
# the context)
COUNTERS = ("state_rows", "attn_keys_read")

# Tokens of ONE row of the prefill scan. A bucket's (B, P) prompts are cut
# into B * P / SCAN_CHUNK rows that are scanned side by side from a zero
# state, token by token; what each row's start state adds is put in
# afterwards (selective_scan). Measured on the chip at the cell's shapes, one
# layer's scan alone, ms at 16 x 256 | 4 x 1,024 | 1 x 4,096 (PERF.md section
# 6, PR 34): token by token over the whole bucket, unrolled 1: 5.6 | 8.2 |
# 10.6, unrolled 8: 4.0 | 3.9 | 4.9; rows of 256, unrolled 16: 3.3 | 4.9 |
# 5.2; rows of 128, unrolled 8: 5.2 | 5.3 | 5.1; an associative scan in
# chunks of 64: 87 | 73 | 9.3. Every sequential form runs at its HBM
# roofline (the state crosses the loop's boundary once a token-row: 0.65 MB,
# 3.3 ms for 4,096 of them). In the engine's own programs, over the cell's
# mix of buckets (three dispatches of eight are 1 x 4,096): token by token
# 232 ms a prefill dispatch, rows of 128 264.4, rows of 256 265.8 (the two
# row lengths are level; the start states' pass is what both pay). WHAT
# RULES THE FASTEST FORM OUT IS THE BENCHMARK'S TRACER: the profiler records
# every op of every trip (3.9 events a token of a row a layer + 16,000 a
# pass for all else, 50-60 us of ``stop_trace`` each in bulk) and
# benchmark/common.Tracer gives a trace 120 s past the window's end to be
# handed over. Rows of 128 are 29,000 events a pass (24 passes: 0.69 M, 42
# s), rows of 256 42,000 (1.0 M, 53 s) — both traced whole at the cell's 8
# s; token by token the cell's dispatches average 2,800 trips, ~300,000
# events EACH, some 7 M in 8 s: six minutes of ``stop_trace`` by these
# rates, not tried. The cell's baseline carries those ~32 ms a dispatch
# until the scan is one kernel (one event a layer; ROADMAP M4 (b)).
SCAN_CHUNK = 128
# tokens a trip of the scan's loop (lax.scan ``unroll``)
SCAN_UNROLL = 16

DT_MIN, DT_MAX = 1e-3, 1e-1   # the step size a seed's b_dt draws (log-uniform)


# --- parameters -----------------------------------------------------------

def param_shapes(lm: JambaConfig) -> Dict:
    """{name: shape} tree of the parameters."""
    d, di, N, R = (lm.hidden_size, lm.d_inner, lm.mamba_d_state,
                   lm.mamba_dt_rank)
    H, KV, hd, I = (lm.num_attention_heads, lm.num_key_value_heads,
                    lm.head_dim, lm.intermediate_size)
    layers = []
    for i in range(lm.num_hidden_layers):
        p = {"mixer_norm": (d,), "mlp_norm": (d,), "w_gate": (d, I),
             "w_up": (d, I), "w_down": (I, d)}
        if lm.layer_is_attention(i):
            p.update(w_q=(d, H * hd), w_k=(d, KV * hd), w_v=(d, KV * hd),
                     w_o=(H * hd, d))
        else:
            p.update(w_in=(d, 2 * di), conv_w=(lm.mamba_d_conv, di),
                     conv_b=(di,), w_x=(di, R + 2 * N), dt_norm=(R,),
                     b_norm=(N,), c_norm=(N,), w_dt=(R, di), b_dt=(di,),
                     a_log=(N, di), d_skip=(di,), w_out=(di, d))
        layers.append(p)
    return {"embed": (lm.vocab_size, d), "layers": layers,
            "final_norm": (d,)}


def _inverse_softplus_of_a_step(key, shape):
    """``b_dt``: softplus^-1 of a step drawn log-uniform on [DT_MIN,
    DT_MAX]."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (np.log(DT_MAX) - np.log(DT_MIN)) + np.log(DT_MIN))
    return dt + jnp.log(-jnp.expm1(-dt))


def init_leaf(name: str, shape, key, hidden_size: int):
    """One seeded leaf, float32, as the family initialises it: ``a_log`` =
    log(1..N) a channel and ``d_skip`` = 1 (no draw); ``b_dt`` the inverse
    softplus of a log-uniform step on [DT_MIN, DT_MAX], so that the
    recurrence neither forgets at once nor never; gains 1 + 0.1 N(0, 1) (a
    gain of exactly 1 would hide a gain the program forgot); ``conv_b`` 0.1
    N(0, 1); matrices N(0, 1 / fan_in) (the convolution's fan-in is its
    d_conv taps); embedding rows N(0, 1 / hidden): it is the head too, and a
    normed state times such rows gives logits of unit size."""
    if name == "a_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
    if name == "d_skip":
        return jnp.ones(shape, jnp.float32)
    if name == "b_dt":
        return _inverse_softplus_of_a_step(key, shape)
    w = jax.random.normal(key, shape, jnp.float32)
    if name == "conv_b":
        return 0.1 * w
    if len(shape) == 1:
        return 1.0 + 0.1 * w
    if name == "embed":
        return w * (hidden_size ** -0.5)
    return w * (shape[-2] ** -0.5)


def init_params(lm: JambaConfig, seed: int, dtype=jnp.bfloat16):
    """Seeded random weights (:func:`init_leaf`), in ``dtype`` from
    creation. One jitted call."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(lm), is_leaf=lambda s: isinstance(s, tuple))

    def make(key):
        out = []
        for i, (path, shape) in enumerate(paths):
            out.append(init_leaf(path[-1].key, shape,
                                 jax.random.fold_in(key, i),
                                 lm.hidden_size).astype(dtype))
        return out
    built = jax.jit(make)(jax.random.PRNGKey(seed))  # firacheck: allow[DRIVER-REG] one set-up call that builds the weights on the device; this module dispatches nothing in a loop — the engine (decode/engine.py, registered) jits and drives its programs
    return jax.tree_util.tree_unflatten(treedef, built)


# --- the Mamba mixer ------------------------------------------------------

def _dt_b_c(p, c, lm: JambaConfig, dtype):
    """c (..., d_inner) -> (Delta (..., d_inner), B (..., N), C (..., N)),
    float32: ``W_x``, Jamba's three inner RMSNorms, ``W_dt`` and the
    softplus."""
    R, N, eps = lm.mamba_dt_rank, lm.mamba_d_state, lm.rms_norm_eps
    dbc = mm(c, p["w_x"], dtype)
    d = rms_norm(dbc[..., :R], p["dt_norm"], eps)
    Bm = rms_norm(dbc[..., R:R + N], p["b_norm"], eps)
    Cm = rms_norm(dbc[..., R + N:], p["c_norm"], eps)
    delta = jax.nn.softplus(mm(d, p["w_dt"], dtype)
                            + p["b_dt"].astype(jnp.float32))
    return delta, Bm, Cm


def _step_state(H, delta, dx, Bm, A):
    """One position of the recurrence. H (..., N, di); delta, dx = Delta *
    c (..., di); Bm (..., N); A (N, di). -> H_t."""
    return (jnp.exp(delta[..., None, :] * A) * H
            + dx[..., None, :] * Bm[..., :, None])


def selective_scan(delta, x, Bm, Cm, A):
    """The recurrence over whole prompts. delta, x (B, P, di) float32
    (``delta`` 0 at padded positions), Bm, Cm (B, P, N), A (N, di). ->
    (``H_t C_t`` (B, P, di) float32, the last state (B, N, di)).

    The (B, P) tokens are cut into rows of ``SCAN_CHUNK``; every row is
    scanned token by token FROM A ZERO STATE, all rows side by side (a
    ``lax.scan`` over time whose carry is the rows' states: no (P, N, di)
    tensor exists). The recurrence is linear in the state, so a row that
    really starts from ``H0`` differs by ``exp(A * cumsum(Delta)_t) * H0``
    at its token t: the rows' true start states follow from their zero-state
    ends by a short scan over the rows of a prompt, and one fused pass adds
    each token's share of it to ``y``. Exponents are sums of ``Delta * A``
    <= 0: nothing can overflow, and nothing is divided."""
    B, P, di = x.shape
    N = A.shape[0]
    Lc = min(SCAN_CHUNK, P)
    if P % Lc:
        raise ValueError(f"a prompt bucket of {P} tokens is not a whole "
                         f"number of {Lc}-token scan rows")
    n = P // Lc

    def rows(a):                # (B, P, c) -> time first: (Lc, B * n, c)
        return jnp.moveaxis(a.reshape(B * n, Lc, a.shape[-1]), 1, 0)

    def token(H, xs):
        d_t, dx_t, b_t, c_t = xs
        H = _step_state(H, d_t, dx_t, b_t, A)
        return H, jnp.sum(H * c_t[:, :, None], axis=1)
    with jax.named_scope("ssm.scan"):
        ends, y = jax.lax.scan(
            token, jnp.zeros((B * n, N, di), jnp.float32),
            (rows(delta), rows(delta * x), rows(Bm), rows(Cm)),
            unroll=min(SCAN_UNROLL, Lc))
        y = jnp.moveaxis(y, 0, 1).reshape(B, P, di)
        if n == 1:
            return y, ends
        # a row's decay over its whole length, then the rows of a prompt
        # in order: start_{j+1} = decay_j * start_j + end_j
        cum = jnp.cumsum(delta.reshape(B, n, Lc, di), axis=2)
        decay = jnp.exp(cum[:, :, -1, None, :] * A)         # (B, n, N, di)
        ends = ends.reshape(B, n, N, di)

        def row(H, xs):
            dec, end = xs
            return dec * H + end, H
        last, starts = jax.lax.scan(
            row, jnp.zeros((B, N, di), jnp.float32),
            (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(ends, 1, 0)))
        starts = jnp.moveaxis(starts, 0, 1)                 # (B, n, N, di)
        carried = jnp.sum(
            jnp.exp(cum[:, :, :, None, :] * A) * starts[:, :, None]
            * Cm.reshape(B, n, Lc, N)[..., None], axis=3)
        return y + carried.reshape(B, P, di), last


def real_positions(P: int, lengths):
    """(B, P) bool: the positions of a padded bucket that hold a prompt's
    own tokens. Everywhere else ``Delta`` is 0, so the state a request
    hands over is the one at ITS length, not its bucket's."""
    return jnp.arange(P)[None, :] < lengths[:, None]


def _conv(p, taps, dtype):
    """The depthwise convolution from its ``mamba_d_conv`` taps, oldest
    first, each (..., di) -> c (..., di) in ``dtype``."""
    w = p["conv_w"].astype(jnp.float32)
    acc = p["conv_b"].astype(jnp.float32)
    for j, u in enumerate(taps):
        acc = acc + w[j] * u.astype(jnp.float32)
    return jax.nn.silu(acc).astype(dtype)


def mamba_prefill(p, h, lengths, lm: JambaConfig, dtype):
    """h (B, P, d) normed, real up to lengths (B,). -> (mixer output
    (B, P, d) float32, the state at each prompt's OWN length (B, N, di)
    float32, the convolution's tail there (taps - 1, B, di) ``dtype``)."""
    P = h.shape[1]
    di, taps = lm.d_inner, lm.mamba_d_conv
    with jax.named_scope("ssm.in_proj"):
        uz = mm(h, p["w_in"], dtype, dtype)
        u, z = uz[..., :di], uz[..., di:]
    with jax.named_scope("ssm.conv"):
        padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
        c = _conv(p, [padded[:, j:j + P] for j in range(taps)], dtype)
        # the tail a decode position continues from: u at lengths - 3 ..
        # lengths - 1 (``padded`` holds u_s at s + taps - 1; zeros before
        # the prompt's start)
        tail = jnp.moveaxis(jax.vmap(
            lambda u_b, n: jax.lax.dynamic_slice_in_dim(u_b, n, taps - 1, 0)
        )(padded, lengths), 1, 0)
    delta, Bm, Cm = _dt_b_c(p, c, lm, dtype)
    delta = jnp.where(real_positions(P, lengths)[..., None], delta, 0.0)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    x = c.astype(jnp.float32)
    y, H = selective_scan(delta, x, Bm, Cm, A)
    with jax.named_scope("ssm.out_proj"):
        y = (y + p["d_skip"].astype(jnp.float32) * x) \
            * jax.nn.silu(z.astype(jnp.float32))
        return mm(y, p["w_out"], dtype), H, tail


def mamba_step(p, h, H, tail, active, lm: JambaConfig, dtype):
    """One position of n rows (a row a beam). h (n, d) normed; H (n, N, di)
    and tail (taps - 1, n, di): the state each row CONTINUES FROM (its
    parent's); active (n,) bool. -> (mixer output (n, d) float32, H_t,
    the tail with u_t shifted in). A row that is not active keeps both:
    ``Delta = 0`` is ``exp(0) H + 0``."""
    di = lm.d_inner
    with jax.named_scope("ssm.in_proj"):
        uz = mm(h, p["w_in"], dtype, dtype)
        u, z = uz[..., :di], uz[..., di:]
    with jax.named_scope("ssm.conv"):
        c = _conv(p, list(tail) + [u], dtype)
        new_tail = jnp.where(active[None, :, None],
                             jnp.concatenate([tail[1:], u[None]], 0), tail)
    delta, Bm, Cm = _dt_b_c(p, c, lm, dtype)
    with jax.named_scope("ssm.step"):
        delta = jnp.where(active[:, None], delta, 0.0)
        A = -jnp.exp(p["a_log"].astype(jnp.float32))
        x = c.astype(jnp.float32)
        H = _step_state(H, delta, delta * x, Bm, A)
        y = jnp.sum(H * Cm[:, :, None], axis=1) \
            + p["d_skip"].astype(jnp.float32) * x
    with jax.named_scope("ssm.out_proj"):
        y = y * jax.nn.silu(z.astype(jnp.float32))
        return mm(y, p["w_out"], dtype), H, new_tail


# --- attention, feed-forward, head ----------------------------------------

def _qkv(p, h, lm: JambaConfig, dtype):
    """h (..., d) normed -> q (..., KV, H / KV, hd), keys and values (...,
    KV * hd) each."""
    H, KV, hd = lm.num_attention_heads, lm.num_key_value_heads, lm.head_dim
    q = mm(h, p["w_q"], dtype, dtype).reshape(
        h.shape[:-1] + (KV, H // KV, hd))
    return q, mm(h, p["w_k"], dtype, dtype), mm(h, p["w_v"], dtype, dtype)


def attention_prefill(p, h, lm: JambaConfig, dtype):
    """h (B, P, d) normed -> (attention output (B, P, d) float32, what is
    cached: keys and values (B, KV * hd, P) each, positions last, as a
    decode position's two products read them (model/afmoe.prompt_layout's
    layout))."""
    KV, hd = lm.num_key_value_heads, lm.head_dim
    q, k, v = _qkv(p, h, lm, dtype)
    heads = k.shape[:-1] + (KV, hd)
    with jax.named_scope("attn.full.prefill"):
        o = attend_prefill(q, k.reshape(heads), v.reshape(heads), None,
                           dtype)
        return (mm(o, p["w_o"], dtype),
                (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)))


def _mlp(p, x, lm: JambaConfig, dtype):
    """The second half of a block over the residual stream x (..., d)."""
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["mlp_norm"], lm.rms_norm_eps).astype(dtype)
        f = swiglu(h.reshape(-1, h.shape[-1]), p["w_gate"], p["w_up"],
                   p["w_down"], dtype)
        return (x.astype(jnp.float32) + f.reshape(x.shape)).astype(dtype)


def lm_head(params, x, lm: JambaConfig, dtype):
    """The tied head: the embedding matrix, transposed."""
    with jax.named_scope("lm_head"):
        h = rms_norm(x, params["final_norm"], lm.rms_norm_eps)
        logits = jnp.einsum("...d,vd->...v", h.astype(dtype),
                            params["embed"].astype(dtype),
                            preferred_element_type=jnp.float32)
        return jax.nn.log_softmax(logits, -1)


# --- the two programs -----------------------------------------------------

def _trunk(params, lm: JambaConfig, tokens, lengths, dtype):
    """Every layer over whole prompts. -> (the last residual stream
    (B, P, d), [state] and [tail] a Mamba layer, [(keys, values)] an
    attention layer)."""
    x = params["embed"][tokens].astype(dtype)
    states, tails, kvs = [], [], []
    for i, p in enumerate(params["layers"]):
        h = rms_norm(x, p["mixer_norm"], lm.rms_norm_eps).astype(dtype)
        if lm.layer_is_attention(i):
            a, kv = attention_prefill(p, h, lm, dtype)
            kvs.append(kv)
        else:
            a, H, tail = mamba_prefill(p, h, lengths, lm, dtype)
            states.append(H)
            tails.append(tail)
        x = _mlp(p, (x.astype(jnp.float32) + a).astype(dtype), lm, dtype)
    return x, states, tails, kvs


def prefill(params, lm: JambaConfig, tokens, lengths, dtype
            ) -> Tuple[List, List, List, jnp.ndarray]:
    """tokens (B, P) int32, real up to lengths (B,). -> (the state a Mamba
    layer (B, N, di) float32 and the tail (taps - 1, B, di), both AT
    ``lengths``; a (keys, values) pair an attention layer, the prompts
    whole, (B, kv_dim / 2, P) each; counters: a prefill adds none). No
    logits: the first prediction is the first decode position's."""
    _x, states, tails, kvs = _trunk(params, lm, tokens, lengths, dtype)
    return states, tails, kvs, jnp.zeros((len(COUNTERS),), jnp.int32)


def forward_logp(params, lm: JambaConfig, tokens, lengths, dtype):
    """The whole forward pass without a cache: log-probabilities (B, P, V)
    of the token after each position."""
    x, _s, _t, _kv = _trunk(params, lm, tokens, lengths, dtype)
    return lm_head(params, x, lm, dtype)


def decode_step(params, lm: JambaConfig, tok, gen_pos, ssm, conv, parent,
                prompt_kv, prompt_len, pool, block_tab, active, dtype):
    """One position of every beam of every slot. tok (S, K) int32: each
    beam's token at its slot's generated position gen_pos (S,); ssm: a
    state (S * K, N, di) a Mamba layer, conv: a tail (taps - 1, S * K, di)
    a Mamba layer, row s * K + k beam LANE k of slot s; parent (S, K): the
    lane whose state beam k continues from (the last selection's source
    beam) — the update of lane ``parent[s, k]`` is written to lane k, so
    the state follows the beams in the one read and one write the
    recurrence needs anyway; prompt_kv: a (keys, values) pair an attention
    layer, (S, kv_dim / 2, P_max) each; pool (attention layers, blocks, K,
    block, kv_dim): their generated positions; block_tab (S, W), already
    the sentinel in rows that must neither read nor write; active (S,).
    -> (log-probabilities (S, K, V) float32, ssm, conv, pool, counters)."""
    S, K = tok.shape
    BS, Wt = pool.shape[3], block_tab.shape[1]
    lanes = jnp.arange(K, dtype=jnp.int32)[None, :]
    # an inactive slot's beams read their OWN lanes and (mamba_step) write
    # them back as they were
    src = (jnp.arange(S, dtype=jnp.int32)[:, None] * K
           + jnp.where(active[:, None], parent, lanes)).reshape(-1)
    rows_active = jnp.repeat(active, K)
    blk = jnp.take_along_axis(block_tab, (gen_pos // BS)[:, None], 1)[:, 0]
    off = gen_pos % BS
    gen_seen = gen_pos[:, None] - jnp.arange(Wt * BS)[None, :] >= 0
    prompt_seen = (jnp.arange(prompt_kv[0][0].shape[-1])[None, :]
                   < prompt_len[:, None])
    context = jnp.where(active, prompt_len + gen_pos + 1, 0)
    x = params["embed"][tok].astype(dtype)                  # (S, K, d)
    ssm, conv = list(ssm), list(conv)
    j_ssm = j_attn = 0
    for i, p in enumerate(params["layers"]):
        h = rms_norm(x, p["mixer_norm"], lm.rms_norm_eps).astype(dtype)
        if lm.layer_is_attention(i):
            q, k, v = _qkv(p, h, lm, dtype)
            pool = pool.at[j_attn, blk, :, off, :].set(
                jnp.concatenate([k, v], -1), mode="drop")
            gen = pool[j_attn][block_tab]         # (S, Wt, K, BS, kv_dim)
            gen = jnp.moveaxis(gen, 2, 1).reshape(S, K, Wt * BS, -1)
            with jax.named_scope("attn.full.decode"):
                a = mm(attend_decode(q, prompt_kv[j_attn], prompt_seen, gen,
                                     gen_seen, dtype), p["w_o"], dtype)
            j_attn += 1
        else:
            a, ssm[j_ssm], conv[j_ssm] = mamba_step(
                p, h.reshape(S * K, -1), jnp.take(ssm[j_ssm], src, axis=0),
                jnp.take(conv[j_ssm], src, axis=1), rows_active, lm, dtype)
            a = a.reshape(S, K, -1)
            j_ssm += 1
        x = _mlp(p, (x.astype(jnp.float32) + a).astype(dtype), lm, dtype)
    counters = jnp.stack([
        jnp.sum(active, dtype=jnp.int32) * K,
        jnp.sum(context).astype(jnp.int32) * len(lm.attention_layers)])
    return lm_head(params, x, lm, dtype), ssm, conv, pool, counters
