"""Plain reference for A.X-K1 (skt/A.X-K1 ``config.json``, ``model_type:
axk1``): the decoder's forward pass in straightforward ``jax.numpy``,
float32 at ``Precision.HIGHEST``. Materialised attention only, a loop over
experts, no cache, no batching; imports nothing of ``fira_tpu``. Widths come
from the benchmark's configuration file, weights are the benchmark's own
bfloat16 tree (``weights_axk1.py``), upcast a layer at a time: each layer is
one jitted call that takes that layer's bfloat16 leaves (a whole float32
copy is 19.4 GB).

The equations, ``x`` the residual stream, ``eps`` = ``rms_norm_eps``:

- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.
- MLA: ``c_q = RMSNorm(x W_dq)``; ``[q_nope | q_rope] = c_q W_uq`` a head;
  ``[c_kv | k_rope] = x W_dkv``, ``c_kv = RMSNorm(c_kv)``; ``[k_nope | v] =
  c_kv W_ukv`` a head; ``q_rope``, ``k_rope`` rotated, ``k_rope`` one head
  shared by all; frequencies YaRN's blend of ``theta^(-2i/dim)`` and that
  over ``factor`` by the linear ramp between ``beta_fast`` and ``beta_slow``
  turns over ``original_max_position_embeddings``; softmax scale
  ``(nope + rope)^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``;
  cos/sin times ``mscale / mscale_all_dim``'s two m (1 here); causal
  softmax over ``q_nope k_nope^T + q_rope k_rope^T``; ``concat_heads(P v)
  W_o``; no biases.
- Dense layer (the first ``first_k_dense_replace``): ``W_down(silu(x W_gate)
  * (x W_up))``.
- Expert layer: ``s = sigmoid(x W_r)`` over all published experts; selection
  below; weights ``s_e / sum_chosen s`` times ``routed_scaling_factor``;
  output = shared expert + sum over the chosen experts **that the
  configuration holds** (``n_routed_experts`` from ``expert_offset``); what
  the absent ones would add is left out, as it is in the program: it is
  another chip's share of the sum.
- Pre-norm residual blocks, final RMSNorm, untied head, log-softmax over the
  vocabulary held.

Departures and readings, each also true of the system under test:

- ``topk_method: "none"`` beside ``n_group`` / ``topk_group`` is READ as the
  group-limited top-k of DeepSeek-V3 without its score-correction bias
  (``seq_aux: true`` is the auxiliary-loss alternative to that bias): a
  group's score is the sum of its two largest ``s``, the ``topk_group``
  best groups stay, top-k of ``s`` inside them. The other reading (plain
  top-k of ``s``) would change the set chosen and nothing else. Equal
  scores go to the lower index.
- The rotary pairs are (i, i + dim/2); the published code pairs (2i, 2i+1)
  after a permutation of the projection's columns, which with weights drawn
  from a seed is the same model.
- Attention is computed a block of queries at a time (a ``lax.map``) so
  that a (heads, 4096, 4096) float32 score tensor need not exist; each
  query's row is the whole softmax, so the values are those of the
  unblocked form. The loop over the experts held is a ``fori_loop``: the
  same sum, compiled once instead of twelve times.
- A request's two beams are scored in ONE forward pass over [prompt |
  beam A | beam B]: a token sees the prompt and its own beam's earlier
  tokens, and a beam's positions continue the prompt's (``seen`` and
  ``positions`` of :func:`forward`). That is the forward pass of each
  [prompt | beam] sequence, with the prompt's rows computed once.

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
Q_BLOCK = 128     # queries whose scores exist at a time (at most)


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


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(cfg: Dict, positions):
    rs = cfg["rope_scaling"]
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = base ** (-i / dim)
    inter = extra / rs["factor"]

    def correction_dim(turns):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = jnp.asarray(inter * ramp + extra * (1 - ramp), jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], -1)
    m = yarn_mscale(rs["factor"], rs["mscale"]) \
        / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def rotate(x, cos, sin):
    h = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., h:], x[..., :h]], -1) * sin


def attention(p, x, positions, seen, cfg: Dict, mode: str):
    """x (T, d) normed; seen (T, T) bool: row i attends column j."""
    T = x.shape[0]
    H, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    cos, sin = rope_tables(cfg, positions)
    c_q = rms_norm(mm("td,dr->tr", x, p["w_dq"], mode), p["q_norm"], eps)
    q = mm("tr,rh->th", c_q, p["w_uq"], mode).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], rotate(q[..., dn:], cos[:, None], sin[:, None])
    ckv = mm("td,dr->tr", x, p["w_dkv"], mode)
    c_kv = rms_norm(ckv[:, :r], p["kv_norm"], eps)
    k_rope = rotate(ckv[:, r:], cos, sin)                       # (T, dr)
    kv = mm("tr,rh->th", c_kv, p["w_ukv"], mode).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    scale = (dn + dr) ** -0.5 * m * m
    qb = math.gcd(T, Q_BLOCK)

    def rows(at):               # the whole softmax of qb queries
        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, at, qb, 0)
        s = mm("qhd,khd->hqk", cut(q_nope), k_nope, mode) \
            + mm("qhd,kd->hqk", cut(q_rope), k_rope, mode)
        s = jnp.where(cut(seen)[None], s * scale, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v, mode)
    o = jax.lax.map(rows, jnp.arange(0, T, qb)).reshape(T, H * dv)
    return mm("th,hd->td", o, p["w_o"], mode)


def swiglu(x, w_gate, w_up, w_down, mode: str):
    g = mm("td,dm->tm", x, w_gate, mode)
    u = mm("td,dm->tm", x, w_up, mode)
    return mm("tm,md->td", jax.nn.silu(g) * u, w_down, mode)


def _top(x, k: int):
    """Indices of the k largest along the last axis, equal values lowest
    index first."""
    return jnp.argsort(-x, axis=-1, stable=True)[..., :k]


def route(scores, cfg: Dict):
    """scores (T, E) -> chosen (T, E) bool, weights (T, E) float32 (0 where
    not chosen)."""
    T, E = scores.shape
    G, k = cfg["n_group"], cfg["num_experts_per_tok"]
    grouped = scores.reshape(T, G, E // G)
    two = jnp.take_along_axis(grouped, _top(grouped, min(2, E // G)), -1)
    keep = _top(two.sum(-1), cfg["topk_group"])                  # (T, tg)
    kept = jnp.any(keep[..., None] == jnp.arange(G), axis=1)     # (T, G)
    masked = jnp.where(jnp.repeat(kept, E // G, axis=1), scores, 0.0)
    ids = _top(masked, k)
    chosen = jnp.any(ids[..., None] == jnp.arange(E), axis=1)    # (T, E)
    w = jnp.where(chosen, scores, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, w * cfg["routed_scaling_factor"]


def expert_layer(p, x, cfg: Dict, mode: str):
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x, p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _chosen, w = route(scores, cfg)
    y = swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"], mode)
    off = int(cfg.get("expert_offset", 0))

    def add_expert(e, y):                          # the experts held
        w_e = jax.lax.dynamic_index_in_dim(w, off + e, 1)        # (T, 1)
        return y + w_e * swiglu(x, p["experts_gate"][e], p["experts_up"][e],
                                p["experts_down"][e], mode)
    return jax.lax.fori_loop(0, cfg["n_routed_experts"], add_expert, y)


def block(p, x, positions, seen, cfg: Dict, mode: str):
    eps = cfg["rms_norm_eps"]
    x = x + attention(p, rms_norm(x, p["attn_norm"], eps), positions, seen,
                      cfg, mode)
    h = rms_norm(x, p["mlp_norm"], eps)
    if "router" in p:
        return x + expert_layer(p, h, cfg, mode)
    return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], mode)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_key, mode: str):
    cfg = dict(cfg_key[0])
    cfg["rope_scaling"] = dict(cfg_key[1])
    return (jax.jit(lambda p, x, pos, seen: block(p, x, pos, seen, cfg, mode)),
            jax.jit(lambda g, head, x: jax.nn.log_softmax(
                mm("td,dv->tv", rms_norm(x, g, cfg["rms_norm_eps"]), head,
                   mode), -1)))


def _key(cfg: Dict):
    flat = tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))
    return flat, tuple(sorted(cfg["rope_scaling"].items()))


def forward(cfg: Dict, params, tokens, mode: str = "f32",
            positions=None, seen=None):
    """tokens (T,) int -> log-probabilities (T, V) float32: row t is the
    distribution of the token after ``tokens[t]``. ``positions`` (T,)
    default to 0..T-1 and ``seen`` (T, T) to the causal mask; a caller that
    packs several continuations of one prompt passes its own."""
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    if positions is None:
        positions = jnp.arange(T)
    if seen is None:
        seen = jnp.tril(jnp.ones((T, T), bool))
    layer, head = _jitted(_key(cfg), mode)
    x = params["embed"][tokens].astype(jnp.float32)
    for p in params["layers"]:                      # a layer at a time
        x = layer(p, x, jnp.asarray(positions), jnp.asarray(seen))
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
    logp = forward(cfg, params, tokens, mode, positions, seen)
    logp = logp[P:P + R * n].reshape(R, n, -1)
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
