"""Plain reference for FIRA (ICSE 2022): GCN graph encoder, Transformer
decoder, dual copy head, loss, gradients and Adam, in straightforward
``jax.numpy``.

Written from the published description (reference ``Model.py`` /
``gnn_transformer.py`` / ``combination_layer.py`` / ``run_model.py``) and
imports nothing of ``fira_tpu``. Widths come from the benchmark's config
file; weights are the benchmark's own (``weights.py``), addressed by the
checkpoint names the paper's modules carry.

Everything elementwise runs in float32. ``mode`` picks how matrix products
are computed, which is what makes the same code the benchmark's control:

- ``"f32"`` — float32 operands at ``Precision.HIGHEST`` (the reference);
- ``"fp8"`` — operands rounded to float8_e4m3fn, float32 accumulation: the
  nearest precision below the bfloat16 the configurations state.

Departures from the paper's code, each also true of the system under test:
the dead modules (``Encoder.lstm``, ``combination_list1``, ``gate_fc``) are
left out; dropout is not applied (the configuration files state rate 0).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

_OPERAND = {"fp8": jnp.float8_e4m3fn}
NEG = -1e9
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def _round(x, mode: str):
    """Round a matmul operand to the mode's storage type, back in float32
    so the product itself is exact and only the operands are coarse."""
    if mode == "f32":
        return x.astype(jnp.float32)
    return x.astype(_OPERAND[mode]).astype(jnp.float32)


def mm(eq: str, a, b, mode: str):
    return jnp.einsum(eq, _round(a, mode), _round(b, mode),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def dense(p, x, mode: str):
    y = mm("...i,io->...o", x, p["kernel"], mode)
    return y + p["bias"] if "bias" in p else y


def layer_norm(p, x, eps: float = 1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def position_encoding(length: int, d: int) -> np.ndarray:
    """Interleaved sin/cos: for each frequency j the pair (sin, cos) sits
    side by side (gnn_transformer.py:10-19)."""
    pos = np.zeros((length, d), dtype=np.float32)
    i = np.arange(length)[:, None].astype(np.float64)
    j = np.arange(d // 2)[None, :].astype(np.float64)
    angle = i / np.power(10000.0, 2.0 * j / d)
    pos[:, 0::2] = np.sin(angle)
    pos[:, 1::2] = np.cos(angle)
    return pos


def embed_padded(table, ids):
    """padding_idx=0: pad rows contribute exactly zero."""
    ids = ids.astype(jnp.int32)
    return table[ids] * (ids != 0)[..., None].astype(jnp.float32)


def dense_adjacency(senders, receivers, values, n: int):
    B = senders.shape[0]
    b = jnp.arange(B, dtype=jnp.int32)[:, None]
    adj = jnp.zeros((B, n, n), jnp.float32)
    return adj.at[b, senders.astype(jnp.int32),
                  receivers.astype(jnp.int32)].add(values.astype(jnp.float32))


def combination(p, query, key, value, heads: int, mode: str):
    """Attention-free two-channel gate (combination_layer.py): per element
    softmax over the pair (q*k, q*v)/sqrt(d_head), mixing k and v."""
    d = query.shape[-1]
    q = dense(p["q_proj"], query, mode)
    k = dense(p["k_proj"], key, mode)
    v = dense(p["v_proj"], value, mode)
    scale = 1.0 / np.sqrt(d // heads)
    w0 = jax.nn.sigmoid((q * k - q * v) * scale)
    out = dense(p["out_proj"], w0 * k + (1.0 - w0) * v, mode)
    return layer_norm(p["norm"], out + query)


def gcn(p, x, adj, mode: str):
    h = dense(p["fc1"], x, mode)
    h = mm("bij,bjd->bid", adj, h, mode)
    h = dense(p["fc2"], h, mode)
    return layer_norm(p["norm"], h + x)


def attention(p, query, memory, mask, heads: int, mode: str,
              causal: bool = False):
    """Post-LN multi-head attention with additive -1e9 masking
    (gnn_transformer.py:124-161). ``mask``: (B, kv) validity."""
    B, Tq, d = query.shape
    dh = d // heads

    def split(x):
        return x.reshape(B, x.shape[1], heads, dh).transpose(0, 2, 1, 3)

    q = split(dense(p["q_proj"], query, mode))
    k = split(dense(p["k_proj"], memory, mode))
    v = split(dense(p["v_proj"], memory, mode))
    w = mm("bhqd,bhkd->bhqk", q, k, mode) / np.sqrt(dh)
    w = jnp.where(mask[:, None, None, :], w, NEG)
    if causal:
        tri = jnp.tril(jnp.ones((Tq, memory.shape[1]), bool))
        w = jnp.where(tri[None, None], w, NEG)
    w = jax.nn.softmax(w, axis=-1)
    out = mm("bhqk,bhkd->bhqd", w, v, mode)
    out = out.transpose(0, 2, 1, 3).reshape(B, Tq, d)
    return layer_norm(p["norm"], dense(p["out_proj"], out, mode) + query)


def feed_forward(p, x, mode: str):
    h = jax.nn.relu(dense(p["fc1"], x, mode))
    return layer_norm(p["norm"], dense(p["fc2"], h, mode) + x)


def encode(params, batch, cfg: Dict, mode: str):
    """-> ([diff || sub-token] states (B, sou+sub, d), validity mask)."""
    p = params["encoder"]
    sou, sub, L = cfg["sou_len"], cfg["sub_token_len"], cfg["num_layers"]
    d, heads = cfg["embedding_dim"], cfg["num_head"]
    n = sou + sub + cfg["ast_change_len"]
    adj = dense_adjacency(batch["senders"], batch["receivers"],
                          batch["values"], n)
    word = p["word_embed"]["embedding"]
    x_diff = embed_padded(word, batch["diff"]) + position_encoding(sou, d)
    mark = embed_padded(p["mark_embed"]["embedding"], batch["diff_mark"])
    graph = jnp.concatenate([
        x_diff, embed_padded(word, batch["sub_token"]),
        embed_padded(p["ast_change_embed"]["embedding"],
                     batch["ast_change"])], axis=1)
    for i in range(L):
        top = graph[:, :sou]
        top = combination(p[f"combination_{i}"], top, top, mark, heads, mode)
        graph = jnp.concatenate([top, graph[:, sou:]], axis=1)
        graph = gcn(p[f"gcn_{i}"], graph, adj, mode)
    states = graph[:, :sou + sub]
    mask = jnp.concatenate([batch["diff"] != 0, batch["sub_token"] != 0],
                           axis=1)
    return states, mask


def decode(params, states, mask, tar, cfg: Dict, mode: str):
    """Teacher-forced decoder over the whole target prefix."""
    p = params["decoder"]
    T = tar.shape[1]
    heads = cfg["num_head"]
    tar = tar.astype(jnp.int32)
    x = p["embed"]["embedding"][tar] + position_encoding(
        cfg["tar_len"], cfg["embedding_dim"])[None, :T]
    tar_mask = (tar != 0).at[:, 0].set(True)  # <start> is always attended
    for i in range(cfg["num_layers"]):
        x = attention(p[f"self_attn_{i}"], x, x, tar_mask, heads, mode,
                      causal=True)
        x = attention(p[f"cross_attn_{i}"], x, states, mask, heads, mode)
        x = feed_forward(p[f"ffn_{i}"], x, mode)
    return x


def copy_scores(p, states, tar_emb, mode: str):
    src = dense(p["src_proj"], states, mode)
    tgt = dense(p["tgt_proj"], tar_emb, mode)
    inter = jnp.tanh(src[:, None, :, :] + tgt[:, :, None, :])
    return mm("btsd,d->bts", inter, p["score"]["kernel"][:, 0], mode) \
        + p["score"]["bias"][0]


def dist_parts(params, batch, tar, cfg: Dict, mode: str):
    """(generation softmax over the vocabulary, copy softmax over source
    positions, 2-way gate) at every target position (Model.py:52-64)."""
    states, mask = encode(params, batch, cfg, mode)
    tar_emb = decode(params, states, mask, tar, cfg, mode)
    gen = jax.nn.softmax(dense(params["out_fc"], tar_emb, mode), axis=-1)
    scores = jax.checkpoint(functools.partial(copy_scores, mode=mode))(
        params["copy_net"], states, tar_emb)
    copy = jax.nn.softmax(jnp.where(mask[:, None, :], scores, NEG), axis=-1)
    gate = jax.nn.softmax(dense(params["copy_net"]["gate"], tar_emb, mode),
                          axis=-1)
    return gen, copy, gate


def nll_sum_count(params, batch, cfg: Dict, mode: str):
    """Training forward: (summed negative log-likelihood, label count)
    (Model.py:66-84); the caller normalises sum / count over the batch."""
    tar = batch["msg"]
    gen, copy, gate = dist_parts(params, batch, tar, cfg, mode)
    lab = batch["msg_tar"].astype(jnp.int32)
    label = jnp.concatenate([lab[:, 1:], jnp.zeros_like(lab[:, :1])], axis=1)
    V = cfg["vocab_size"]
    is_gen = label < V
    gi = jnp.where(is_gen, label, 0)[..., None]
    ci = jnp.clip(label - V, 0, copy.shape[-1] - 1)[..., None]
    pg = jnp.take_along_axis(gen, gi, axis=-1)[..., 0] * gate[..., 0]
    pc = jnp.take_along_axis(copy, ci, axis=-1)[..., 0] * gate[..., 1]
    nll = -jnp.log(jnp.clip(jnp.where(is_gen, pg, pc), 1e-10, 1.0))
    live = label != 0
    return jnp.where(live, nll, 0.0).sum(), live.sum()


# --------------------------------------------------------------------------
# training: loss, gradients, Adam — in blocks of rows so that it fits
# --------------------------------------------------------------------------

def _blocks(batch, block: int):
    B = batch["diff"].shape[0]
    if B % block:
        raise ValueError(f"batch {B} does not divide into blocks of {block}")
    return {k: v.reshape((B // block, block) + v.shape[1:])
            for k, v in batch.items()}


def loss_and_grads(params, batch, cfg: Dict, mode: str, block: int):
    """Loss = sum(nll) / count over the WHOLE batch, and its gradient,
    accumulated block by block (the count carries no gradient)."""
    def body(carry, blk):
        g_acc, nll_acc, cnt_acc = carry
        (nll, cnt), g = jax.value_and_grad(
            lambda p: nll_sum_count(p, blk, cfg, mode), has_aux=True)(params)
        g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
        return (g_acc, nll_acc + nll, cnt_acc + cnt), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    (g, nll, cnt), _ = jax.lax.scan(
        body, (zero, jnp.zeros(()), jnp.zeros((), jnp.int32)),
        _blocks(batch, block))
    denom = jnp.maximum(cnt, 1).astype(jnp.float32)
    return nll / denom, jax.tree_util.tree_map(lambda x: x / denom, g)


def adam_update(params, mu, nu, grads, t, lr: float):
    """torch.optim.Adam defaults (run_model.py:396): betas (0.9, 0.999),
    eps 1e-8 outside the root, bias-corrected; ``t`` counts from 1."""
    mu = jax.tree_util.tree_map(
        lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = jax.tree_util.tree_map(
        lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
    c1 = 1 - ADAM_B1 ** t
    c2 = 1 - ADAM_B2 ** t
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        params, mu, nu)
    return params, mu, nu


def make_train_steps(cfg: Dict, mode: str, block: int, lr: float):
    """jitted ``(params, stacked batches (K, B, ...)) -> per-step losses,
    every step's per-leaf gradient norm (K,), Adam's first moment after the K
    steps, params after them``: K plain optimizer steps from a fresh
    optimizer."""
    def run(params, stacked):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

        def body(carry, batch):
            p, mu, nu, t = carry
            loss, g = loss_and_grads(p, batch, cfg, mode, block)
            p, mu, nu = adam_update(p, mu, nu, g, t, lr)
            gnorm = jax.tree_util.tree_map(
                lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), g)
            return (p, mu, nu, t + 1.0), (loss, gnorm)

        (p, mu, _nu, _t), (losses, gnorms) = jax.lax.scan(
            body, (params, zeros, zeros, jnp.float32(1.0)), stacked)
        return {"losses": losses, "mu": mu, "params": p,
                "grad_norms": gnorms}
    return jax.jit(run)


# --------------------------------------------------------------------------
# decoding: score served beams, teacher-forced
# --------------------------------------------------------------------------

def make_beam_scorer(cfg: Dict, mode: str, beam: int):
    """jitted ``(params, prompts, tokens (B, T), probe_ids (B, T, n)) ->`` per
    position: the log of the served token's probability (its best way:
    generated, or copied from any source position holding that word), the
    log of the ``beam``-th largest entry of the fused distribution, that
    distribution's ``beam`` best entries as fused ids, the log of its
    entries at ``probe_ids``, and the served token's rank in it (0 = best,
    at most ``beam - 1``). Position t predicts tokens[:, t+1]."""
    V = cfg["vocab_size"]

    def run(params, prompts, tokens, probe_ids):
        tokens = tokens.astype(jnp.int32)
        gen, copy, gate = dist_parts(params, prompts, tokens, cfg, mode)
        fused = jnp.concatenate(
            [gate[..., 0:1] * gen, gate[..., 1:2] * copy], axis=-1)
        nxt = jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])],
                              axis=1)                      # (B, T)
        p_gen = jnp.take_along_axis(
            fused[..., :V], jnp.clip(nxt, 0, V - 1)[..., None], axis=-1)[..., 0]
        src = jnp.concatenate([prompts["diff"], prompts["sub_token"]],
                              axis=1).astype(jnp.int32)    # (B, S)
        same = src[:, None, :] == nxt[:, :, None]          # (B, T, S)
        p_copy = jnp.where(same, fused[..., V:], 0.0).max(-1)
        top_vals, top_ids = jax.lax.top_k(fused, beam)
        tiny = jnp.float32(1e-38)

        def log(x):
            return jnp.log(jnp.maximum(x, tiny))
        p_token = jnp.maximum(p_gen, p_copy)
        rank = jnp.minimum(jnp.sum(fused > p_token[..., None], axis=-1),
                           beam - 1)
        return {"logp_token": log(p_token), "rank": rank,
                "logp_kth": log(top_vals[..., -1]),
                "top_ids": top_ids,
                "logp_probe": log(jnp.take_along_axis(fused, probe_ids,
                                                      axis=-1))}
    return jax.jit(run)
