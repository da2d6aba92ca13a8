"""The grouped expert products' two tilings (model/axk1.routed_experts,
shared by model/afmoe.py and model/lfm2.py): EXPERT-MAJOR where a pass
expects few rows an expert (every decode position), ROW-MAJOR through
``jax.lax.ragged_dot`` where it expects many (every prefill). The choice is
the shape's (``expert_capacity``), never an option.

At the three expert cells' own key blocks — LFM2's top-4 of 32 all held,
Trinity-Mini's top-8 of 128, A.X-K1's 12 held of 192 from an
``expert_offset`` — and their decode rows (192, 144, 192), at a tiny width:
the expert-major form against a plain per-expert float32 loop and against
the row-major form on the same inputs; one expert taking every row, experts
with none, padding rows, loads over the capacity (a second pass). The
counter ``moe_rows_expert_major``: every held assignment of a decode
position, none of a prefill.

Tolerances: in float32 the forms differ from the loop only in the order of
sums (a few 1e-7 on outputs of size ~1): 1e-5. In bfloat16 the two forms
round the same products at the same points (gate and up in bfloat16, down
in float32, float32 accumulation), so they agree to float32's sums: 1e-5
relative to the largest output."""

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lfm2_util import weights as lfm2_weights
from fira_tpu.config import get_config
from fira_tpu.model import axk1, lfm2

F32 = jnp.float32
TOL = 1e-5
D, M = 64, 32          # a tiny width; the key blocks are the cells' own

# (key block, decode rows) of the three cells that run routed_experts
BLOCKS = {
    "lfm2-top4-of-32": (SimpleNamespace(
        num_experts_per_tok=4, experts_held=32, n_routed_experts=32,
        expert_offset=0), 192),
    "afmoe-top8-of-128": (SimpleNamespace(
        num_experts_per_tok=8, experts_held=128, n_routed_experts=128,
        expert_offset=0), 144),
    "axk1-12-held-of-192": (SimpleNamespace(
        num_experts_per_tok=8, experts_held=12, n_routed_experts=192,
        expert_offset=36), 192),
}


def _experts(E, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"experts_gate": jax.random.normal(k[0], (E, D, M)) * D ** -0.5,
            "experts_up": jax.random.normal(k[1], (E, D, M)) * D ** -0.5,
            "experts_down": jax.random.normal(k[2], (E, M, D)) * M ** -0.5}


def _routing(lm, N, seed=1):
    """Top-k of random scores over the router's whole width, the K = 3
    beams of a slot alike as a decode position's are."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    sc = (jnp.repeat(jax.random.normal(k1, (N // 3, lm.n_routed_experts)),
                     3, 0)
          + 0.1 * jax.random.normal(k2, (N, lm.n_routed_experts)))
    w, ids = jax.lax.top_k(jax.nn.sigmoid(sc), lm.num_experts_per_tok)
    x = jax.random.normal(k3, (N, D))
    return x, ids.astype(jnp.int32), w / jnp.sum(w, -1, keepdims=True)


def _plain(p, x, ids, w, valid, lm):
    """The held experts' weighted sum, one (row, pick) at a time in
    float32: no sort, no pass, no tiling."""
    x, ids, w, valid = (np.asarray(a) for a in (x, ids, w, valid))
    g, u, dn = (np.asarray(p[n], np.float64) for n in
                ("experts_gate", "experts_up", "experts_down"))
    out = np.zeros(x.shape, np.float64)
    for r in range(x.shape[0]):
        for j in range(ids.shape[1]):
            e = int(ids[r, j]) - lm.expert_offset
            if valid[r] and 0 <= e < lm.experts_held:
                a, b = x[r] @ g[e], x[r] @ u[e]
                h = a / (1.0 + np.exp(-a)) * b
                out[r] += w[r, j] * (h @ dn[e])
    return out


def _run(p, x, ids, w, valid, lm, dtype=F32):
    return jax.jit(lambda p, x, i, w, v: axk1.routed_experts(
        p, x, i, w, v, lm, dtype))(p, x, ids, w, valid)


def _row_major(monkeypatch, *args, **kw):
    """The same call with the expert-major form ruled out, as a prefill's
    group sizes rule it out."""
    with monkeypatch.context() as m:
        m.setattr(axk1, "EXPERT_MAJOR_ROWS", 0)
        return _run(*args, **kw)


def _gap(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b))))


@pytest.mark.parametrize("block", list(BLOCKS))
def test_decode_rows_run_expert_major_and_equal_the_plain_loop(block,
                                                               monkeypatch):
    lm, N = BLOCKS[block]
    C = axk1.expert_capacity(lm, N)
    assert 0 < C < N and C % 16 == 0
    p = _experts(lm.experts_held)
    x, ids, w = _routing(lm, N)
    valid = jnp.ones((N,), bool)
    out, loads = _run(p, x, ids, w, valid, lm)
    want = _plain(p, x, ids, w, valid, lm)
    assert _gap(out, want) < TOL
    # every held assignment is in one load, and the loop agrees with
    # ragged_dot's passes on the same rows
    held = (ids - lm.expert_offset >= 0) & (ids - lm.expert_offset
                                            < lm.experts_held)
    assert int(loads.sum()) == int(held.sum())
    out_rm, loads_rm = _row_major(monkeypatch, p, x, ids, w, valid, lm)
    assert loads_rm.tolist() == loads.tolist()
    assert _gap(out, out_rm) < TOL


@pytest.mark.parametrize("block", list(BLOCKS))
def test_bfloat16_forms_agree(block, monkeypatch):
    """The chip's dtype: gate and up rounded to bfloat16, down in float32,
    float32 accumulation, in both forms."""
    lm, N = BLOCKS[block]
    p = {k: v.astype(jnp.bfloat16) for k, v in _experts(lm.experts_held,
                                                         seed=4).items()}
    x, ids, w = _routing(lm, N, seed=5)
    valid = jnp.ones((N,), bool)
    bf = jnp.bfloat16
    out, _ = _run(p, x.astype(bf), ids, w, valid, lm, bf)
    out_rm, _ = _row_major(monkeypatch, p, x.astype(bf), ids, w, valid,
                           lm, bf)
    assert out.dtype == out_rm.dtype == F32
    scale = float(jnp.abs(out_rm).max())
    assert _gap(out, out_rm) < TOL * scale


@pytest.mark.parametrize("block", list(BLOCKS))
def test_one_expert_takes_every_row_and_the_others_none(block):
    """Every row picks the same held experts (one of them first for all),
    so those experts' loads are the whole batch — several passes of C —
    and every other held expert has none. Half the rows are padding: they
    take no expert's time and get no output."""
    lm, N = BLOCKS[block]
    k, E, off = lm.num_experts_per_tok, lm.experts_held, lm.expert_offset
    C = axk1.expert_capacity(lm, N)
    picks = [off + e for e in range(min(k, E))] \
        + [lm.n_routed_experts - 1 - j for j in range(k - min(k, E))]
    ids = jnp.tile(jnp.asarray(picks, jnp.int32), (N, 1))
    p = _experts(E, seed=2)
    x, _ids, w = _routing(lm, N, seed=3)
    valid = jnp.arange(N) < N // 2
    out, loads = _run(p, x, ids, w, valid, lm)
    chosen = [e - off for e in picks if 0 <= e - off < E]
    assert loads.tolist() == [N // 2 if e in chosen else 0
                              for e in range(E)]
    assert N // 2 > C                      # the loop's second pass ran
    assert _gap(out, _plain(p, x, ids, w, valid, lm)) < TOL
    assert float(jnp.abs(out[N // 2:]).max()) == 0.0


@pytest.mark.parametrize("block", list(BLOCKS))
def test_a_load_one_over_the_capacity_is_computed(block):
    """The busiest expert gets exactly C + 1 rows, the rest spread: the
    row at rank C is the only one of the second pass."""
    lm, N = BLOCKS[block]
    k, E, off = lm.num_experts_per_tok, lm.experts_held, lm.expert_offset
    C = axk1.expert_capacity(lm, N)
    x, ids, w = _routing(lm, N, seed=6)
    # expert `off` first for rows 0..C, in no other pick
    first = jnp.where(jnp.arange(N) <= C, off, off + 1)
    rest = jnp.where(ids[:, 1:] == off, off + 2, ids[:, 1:])
    ids = jnp.concatenate([first[:, None], rest], 1).astype(jnp.int32)
    valid = jnp.ones((N,), bool)
    p = _experts(E, seed=7)
    out, loads = _run(p, x, ids, w, valid, lm)
    assert int(loads[0]) == C + 1
    assert _gap(out, _plain(p, x, ids, w, valid, lm)) < TOL


@pytest.mark.parametrize("cell,decode,prefill,capacity", [
    ("lfm2-8b-a1b-l12", 192, 16384, 64),
    ("trinity-mini-l5", 144, 16384, 32),
    ("axk1-ep16", 192, 8192, 32),
])
def test_the_shape_rule_engages_at_decode_and_keeps_ragged_dot_in_prefill(
        cell, decode, prefill, capacity):
    """The cells' decode rows expect 24 | 9 | ~11 rows a held expert, their
    prefill dispatches (always the cell's whole token budget) 1,024 | 256 |
    ~427: the first are expert-major with 2.5 x the expectation, in tiles
    of 16 rows, as the capacity; the second row-major."""
    lm = get_config(cell).lm
    per = axk1.expert_chunk_rows(lm, decode) / lm.experts_held
    assert per <= axk1.EXPERT_MAJOR_ROWS
    assert axk1.expert_capacity(lm, decode) == capacity \
        == 16 * math.ceil(2.5 * per / 16)
    assert (axk1.expert_chunk_rows(lm, prefill) / lm.experts_held
            > axk1.EXPERT_MAJOR_ROWS)
    assert axk1.expert_capacity(lm, prefill) == 0


@pytest.mark.parametrize("block", list(BLOCKS))
def test_the_counter_is_each_experts_first_capacity_of_rows(block):
    lm, N = BLOCKS[block]
    C = axk1.expert_capacity(lm, N)
    loads = jnp.asarray([0, 1, C, C + 5] + [2] * (lm.experts_held - 4))
    valid = jnp.ones((N,), bool)
    c = dict(zip(axk1.COUNTERS, axk1.moe_counters(lm, valid, loads).tolist()))
    assert c["moe_rows_expert_major"] == int(loads.sum()) - 5
    assert c["moe_assignments_held"] == int(loads.sum())
    assert c["moe_held_load_max"] == C + 5
    # a prefill's many rows an expert: none
    big = jnp.ones((16384,), bool)
    c = dict(zip(axk1.COUNTERS, axk1.moe_counters(lm, big, loads).tolist()))
    assert c["moe_rows_expert_major"] == 0


def test_a_decode_step_counts_every_held_assignment_and_a_prefill_none():
    """lfm2-tiny through its own programs: a decode position of 2 slots x 3
    beams (6 rows, capacity 8: no expert can spill) computes every held
    assignment expert-major; a prefill of 8 x 128 tokens expects 256 rows a
    held expert and computes none so."""
    lm = get_config("lfm2-tiny").lm
    params = lfm2_weights(lm)
    B, P = 8, 128
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, P), 4, lm.vocab_size)
    lengths = jnp.full((B,), P, jnp.int32)
    assert axk1.expert_capacity(lm, B * P) == 0
    tails, kvs, counters = jax.jit(lambda p, t, n: lfm2.prefill(
        p, lm, t, n, F32))(params, tok, lengths)
    c = dict(zip(lfm2.COUNTERS, counters.tolist()))
    assert c["moe_assignments_held"] > 0
    assert c["moe_rows_expert_major"] == 0
    S, K = 2, 3
    assert axk1.expert_capacity(lm, S * K) >= S * K
    n_attn = len(kvs)
    BS, W = 4, 2
    kv_dim = kvs[0][0].shape[1] * 2
    pool = jnp.zeros((n_attn, S * W + 1, K, BS, kv_dim), F32)
    conv = [jnp.repeat(t[:, :S], K, axis=1) for t in tails]
    prompt_kv = [(k[:S], v[:S]) for k, v in kvs]
    _lp, _conv, _pool, counters = jax.jit(
        lambda p, *a: lfm2.decode_step(p, lm, *a, F32))(
        params, tok[:S, :K], jnp.zeros((S,), jnp.int32), conv,
        jnp.zeros((S, K), jnp.int32), prompt_kv, lengths[:S], pool,
        jnp.arange(S * W, dtype=jnp.int32).reshape(S, W),
        jnp.ones((S,), bool))
    c = dict(zip(lfm2.COUNTERS, counters.tolist()))
    n_moe = lm.num_hidden_layers - lm.num_dense_layers
    assert c["moe_assignments_held"] == S * K * lm.num_experts_per_tok * n_moe
    assert c["moe_rows_expert_major"] == c["moe_assignments_held"]
