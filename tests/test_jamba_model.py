"""Jamba2-3B (fira_tpu/model/jamba.py) against the plain reference
(benchmark/reference_jamba.py) at ``jamba-tiny``: seeded random weights,
log-probabilities, recurrent states and caches, never sampled tokens.

Tolerances. Program and reference both run float32 here, so what separates
them is the order of sums: the program scans rows of ``SCAN_CHUNK`` tokens
from a zero state and adds each row's start state afterwards, where the
reference walks the sequence token by token; attention is blocked; a decode
position's softmax is two-sided. That is a few 1e-6 on log-probabilities of
size ~4 and on states of size ~1. The limit is 1e-4 — twenty times that, and
a thousand times under what the float8 control reads (asserted below), so
computing in a lower precision fails it; each planted fault reads over
1e-2."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jamba_util import ref_cfg, weights
from benchmark import reference_jamba as ref
from benchmark import weights_jamba
from fira_tpu.config import get_config
from fira_tpu.decode.slot_model import JambaSlotModel, StepView
from fira_tpu.model import jamba

TOL = 1e-4
F32 = jnp.float32


@pytest.fixture(scope="module")
def tiny():
    lm = get_config("jamba-tiny").lm
    return lm, ref_cfg(lm), weights(lm)


@pytest.fixture(autouse=True)
def short_scan_rows(monkeypatch):
    """Scan rows of 8 tokens: a bucket of 32 is then 4 rows a prompt, whose
    start states the scan has to hand from row to row, as a bucket of 4,096
    does at rows of 128."""
    monkeypatch.setattr(jamba, "SCAN_CHUNK", 8)
    monkeypatch.setattr(jamba, "SCAN_UNROLL", 4)


def _tokens(lm, shape, seed=1):
    return np.array(jax.random.randint(jax.random.PRNGKey(seed), shape, 4,
                                       lm.vocab_size))


def test_full_forward_pass_matches_the_reference_and_float8_does_not(tiny):
    """Prompts shorter than a scan row, not a multiple of it, and as long
    as the bucket, in one padded batch."""
    lm, rc, params = tiny
    tok = _tokens(lm, (3, 32))
    lengths = np.asarray([5, 19, 32])
    logp = jax.jit(lambda p, t, n: jamba.forward_logp(p, lm, t, n, F32))(
        params, tok, jnp.asarray(lengths))
    for b, n in enumerate(lengths):
        want = ref.forward(rc, params, tok[b, :n])
        # the padded tail of a prompt moves nothing before it
        assert float(jnp.abs(logp[b, :n] - want).max()) < TOL, b
    low = ref.forward(rc, params, tok[2], "fp8")
    assert float(jnp.abs(low - want).max()) > 1000 * TOL


def _left_gaps(lm, rc, left, states, tails, kvs, b, n):
    """The worst gap between what prefill hands over for row ``b`` and what
    the reference would carry past token n - 1."""
    worst, j_ssm, j_attn = 0.0, 0, 0
    for i, l in enumerate(left):
        if ref.is_attention(rc, i):
            k, v = (x.reshape(n, -1).T for x in l)      # (kv_dim / 2, n)
            got_k, got_v = kvs[j_attn]
            worst = max(worst, float(jnp.abs(got_k[b, :, :n] - k).max()),
                        float(jnp.abs(got_v[b, :, :n] - v).max()))
            j_attn += 1
        else:
            H, tail = l
            worst = max(worst, float(jnp.abs(states[j_ssm][b] - H.T).max()),
                        float(jnp.abs(tails[j_ssm][:, b] - tail).max()))
            j_ssm += 1
    assert (j_ssm, j_attn) == (len(lm.mamba_layers),
                               len(lm.attention_layers))
    return worst


def test_prefill_hands_over_state_tail_and_keys_at_each_prompts_own_length(
        tiny):
    """Lengths that are no multiple of the scan's rows (8), one shorter than
    the convolution's 3-token tail, in ONE bucket of 32 with other lengths:
    the state, the tail and the keys and values are the reference's at each
    prompt's own last token, whatever pads the bucket."""
    lm, rc, params = tiny
    tok = _tokens(lm, (4, 32), seed=2)
    lengths = np.asarray([13, 2, 27, 32])
    states, tails, kvs, counters = jax.jit(
        lambda p, t, n: jamba.prefill(p, lm, t, n, F32))(
        params, tok, jnp.asarray(lengths))
    assert counters.tolist() == [0, 0]
    for b, n in enumerate(lengths):
        _logp, left = ref.forward(rc, params, tok[b, :n], with_state=True)
        assert _left_gaps(lm, rc, left, states, tails, kvs, b, n) < TOL, b
    # the same prompt ALONE in a padded bucket, and in a longer bucket
    for P in (32, 64):
        alone = np.zeros((1, P), np.int32)
        alone[0, :13] = tok[0, :13]
        s1, t1, kv1, _c = jamba.prefill(params, lm, jnp.asarray(alone),
                                        jnp.asarray([13]), F32)
        for a, b in zip(s1, states):
            assert float(jnp.abs(a[0] - b[0]).max()) < TOL
        for a, b in zip(t1, tails):
            assert float(jnp.abs(a[:, 0] - b[:, 0]).max()) < TOL
        assert float(jnp.abs(kv1[0][0][0, :, :13]
                             - kvs[0][0][0, :, :13]).max()) < TOL


def test_a_state_taken_at_the_buckets_end_is_caught(tiny, monkeypatch):
    """The fault the padding invites: ``Delta`` not zeroed at padded
    positions, so the scan runs on to the bucket's end."""
    lm, rc, params = tiny
    tok = _tokens(lm, (1, 32), seed=2)
    _logp, left = ref.forward(rc, params, tok[0, :13], with_state=True)
    monkeypatch.setattr(jamba, "real_positions", lambda P, lengths:
                        jnp.ones((lengths.shape[0], P), bool))
    states, tails, kvs, _c = jamba.prefill(params, lm, jnp.asarray(tok),
                                           jnp.asarray([13]), F32)
    assert _left_gaps(lm, rc, left, states, tails, kvs, 0, 13) > 100 * TOL


@pytest.mark.parametrize("chunk,unroll", [(4, 1), (8, 8), (16, 5), (64, 8)])
def test_scan_rows_of_any_length_equal_the_token_by_token_recurrence(
        monkeypatch, chunk, unroll):
    """Rows shorter than, equal to and longer than a loop trip; one row a
    prompt (64: no start states to hand over)."""
    monkeypatch.setattr(jamba, "SCAN_CHUNK", chunk)
    monkeypatch.setattr(jamba, "SCAN_UNROLL", unroll)
    B, P, di, N = 2, 64, 24, 16
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    delta = jax.nn.softplus(jax.random.normal(k[0], (B, P, di)) - 2)
    delta = delta.at[1, 37:].set(0.0)                  # a padded tail
    x = jax.random.normal(k[1], (B, P, di))
    Bm, Cm = (jax.random.normal(kk, (B, P, N)) for kk in k[2:])
    A = -jnp.exp(jnp.broadcast_to(jnp.log(jnp.arange(1.0, N + 1))[:, None],
                                  (N, di)))
    H = jnp.zeros((B, N, di))
    ys = []
    for t in range(P):
        H = jnp.exp(delta[:, t, None, :] * A) * H \
            + (delta[:, t] * x[:, t])[:, None, :] * Bm[:, t, :, None]
        ys.append(jnp.sum(H * Cm[:, t, :, None], 1))
        if t == 36:
            H_at_37 = H
    y, last = jamba.selective_scan(delta, x, Bm, Cm, A)
    assert float(jnp.abs(y - jnp.stack(ys, 1)).max()) < 1e-5
    assert float(jnp.abs(last - H).max()) < 1e-5
    # the padded row's state stayed where its last real token left it
    assert float(jnp.abs(last[1] - H_at_37[1]).max()) < 1e-5


def _through_the_arena(lm, params, tok, plen, n_gen, parents):
    """Prefill, the slot model's own insert, then ``n_gen`` positions
    teacher-forced one at a time through the state leaves, the prompt
    arena and the pool. ``tok``: two continuations of each slot's prompt
    that are equal for their first ``SPLIT`` generated tokens. Both lanes
    of a slot are fed continuation 0 up to there (as a fresh slot's beams
    share one history), then lane k continuation k — and WHICH LANE holds
    which continuation is switched by ``parents[g]`` (S, K) before
    position g, as a selection would: the state has to follow. ->
    (log-probabilities (n_gen, S, K, V), the continuation each lane was
    fed at each position, the arena)."""
    cfg = get_config("jamba-tiny", lm=lm, engine_slots=2, beam_size=2)
    S, K, T, BS = 2, 2, cfg.tar_len, 4
    sm = JambaSlotModel(None, cfg, S, BS, S * T // BS)
    chunk = jax.jit(sm.prefill)(params, {
        "tokens": jnp.asarray(tok[0][:, :32]), "lengths": jnp.asarray(plen)})
    state = {n: jnp.zeros(leaf.shape, leaf.dtype) + (
        3.0 if n.startswith(("prompt_k_", "ssm_state", "conv_state"))
        else 0) for n, leaf in sm.leaves(chunk).items()}   # a dirty arena
    state.update(sm.insert(state, chunk, jnp.arange(S), 1))
    tab = jnp.arange(S * T // BS).reshape(S, T // BS)
    step = jax.jit(lambda st, view: sm.step(params, st, view))
    out, fed = [], []
    flat = np.zeros((S * K, T), np.int32)
    seq = np.zeros((S, K), np.int64)           # the continuation a lane holds
    parent = np.zeros((S, K), np.int32)        # a fresh slot: lane 0
    for g in range(n_gen):
        if g:
            parent = np.asarray(parents[g])
            seq = np.take_along_axis(seq, parent, 1)
            if g == SPLIT:
                seq = np.tile(np.arange(K), (S, 1))
            # the engine moves tokens and the pool with the selection
            flat = flat.reshape(S, K, T)[np.arange(S)[:, None], parent
                                         ].reshape(S * K, T)
            state["kv_pool"] = jnp.take_along_axis(
                state["kv_pool"].reshape(-1, S, T // BS, K, BS, lm.kv_dim),
                jnp.asarray(parent)[None, :, None, :, None, None], 3
            ).reshape(state["kv_pool"].shape)
        for s in range(S):
            for k in range(K):
                flat[s * K + k, g] = tok[seq[s, k]][s, plen[s] + g]
        pos = jnp.full((S,), g)
        (logp,), writes = step(state, StepView(
            flat=jnp.asarray(flat), pos_c=pos, pos_bk=jnp.repeat(pos, K),
            active=jnp.ones((S,), bool), tab_step=tab,
            parent=jnp.asarray(parent)))
        state.update(writes)
        out.append(np.asarray(logp).reshape(S, K, -1))
        fed.append(seq.copy())
    return np.stack(out), fed, state


SPLIT = 3   # generated tokens the two continuations of a prompt share


def _arena_case(lm):
    """Two message continuations of each of two prompts (16 and 13 tokens)
    that part after SPLIT generated tokens; the lanes are crossed and
    uncrossed after that, and slot 0 once hands both lanes lane 1's
    state."""
    plen, n_gen = np.asarray([16, 13]), 12
    tok = [_tokens(lm, (2, 48), seed=4), _tokens(lm, (2, 48), seed=9)]
    for s, n in enumerate(plen):
        tok[1][s, :n + SPLIT] = tok[0][s, :n + SPLIT]
    same, cross = [[0, 1], [0, 1]], [[1, 0], [1, 0]]
    parents = [None, [[0, 0]] * 2, [[1, 0], [0, 0]], same, same, cross,
               same, cross, cross, [[1, 1], [0, 1]], same, cross]
    return tok, plen, n_gen, parents


def _worst(got, fed, refs, plen):
    return max(float(np.abs(got[g, s, k]
                            - refs[fed[g][s, k]][s][plen[s] + g]).max())
               for g in range(got.shape[0]) for s in range(got.shape[1])
               for k in range(got.shape[2]))


def test_prefill_then_decode_through_the_arena_with_parents_that_change(
        tiny, monkeypatch):
    """Every position's log-probabilities are the reference's full forward
    pass over [prompt | the sequence this lane was fed], while the lanes'
    parents change at six of twelve positions (one slot once hands BOTH
    lanes the same parent). Then the fault: ``parent`` ignored, every lane
    continuing from its own old state."""
    lm, rc, params = tiny
    tok, plen, n_gen, parents = _arena_case(lm)
    refs = [[ref.forward(rc, params, t[s, :plen[s] + n_gen])
             for s in range(2)] for t in tok]
    got, fed, state = _through_the_arena(lm, params, tok, plen, n_gen,
                                         parents)
    assert _worst(got, fed, refs, plen) < TOL
    # both slots active at every position: 2 slots x 2 beams a position;
    # one attention layer asked for each slot's whole context
    ctx = sum(int(n) + g + 1 for n in plen for g in range(n_gen))
    assert state["counters"].tolist() == [2 * 2 * n_gen, ctx]

    inner = jamba.decode_step
    monkeypatch.setattr(jamba, "decode_step", lambda *a, **k: inner(
        *a[:6], jnp.broadcast_to(jnp.arange(2), (2, 2)), *a[7:], **k))
    bad, fed, _state = _through_the_arena(lm, params, tok, plen, n_gen,
                                          parents)
    assert _worst(bad, fed, refs, plen) > 100 * TOL


def test_an_inactive_slot_keeps_its_state_its_tail_and_its_pool(tiny):
    lm, _rc, params = tiny
    tok, plen, _n, _p = _arena_case(lm)
    got, _fed, state = _through_the_arena(lm, params, tok, plen, 2,
                                          [None, [[0, 0]] * 2])
    cfg = get_config("jamba-tiny", lm=lm, engine_slots=2, beam_size=2)
    sm = JambaSlotModel(None, cfg, 2, 4, 2 * cfg.tar_len // 4)
    flat = jnp.full((4, cfg.tar_len), 7, jnp.int32)
    pos = jnp.full((2,), 2)
    tab = jnp.arange(8).reshape(2, 4)
    active = jnp.asarray([True, False])
    (_logp,), writes = sm.step(params, state, StepView(
        flat=flat, pos_c=pos, pos_bk=jnp.repeat(pos, 2), active=active,
        tab_step=jnp.where(active[:, None], tab, 8),
        parent=jnp.asarray([[1, 0], [1, 0]])))
    for name, new in writes.items():
        if name.startswith("ssm_state"):
            assert bool(jnp.all(new[2:] == state[name][2:])), name
            assert not bool(jnp.all(new[:2] == state[name][:2])), name
        elif name.startswith("conv_state"):
            assert bool(jnp.all(new[:, 2:] == state[name][:, 2:])), name
    assert bool(jnp.all(writes["kv_pool"][:, 4:] == state["kv_pool"][:, 4:]))
    assert writes["counters"].tolist()[0] == state["counters"].tolist()[0] + 2


def test_parameter_tree_is_the_benchmarks_and_bfloat16_from_creation(tiny):
    lm, rc, _params = tiny
    assert jamba.param_shapes(lm) == weights_jamba.param_shapes(rc)
    params = jamba.init_params(lm, 0)
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree_util.tree_leaves(params))
    p0 = params["layers"][0]
    A = -np.exp(np.asarray(p0["a_log"], np.float32))
    assert np.allclose(A[:, 0], -np.arange(1, 17), rtol=1e-2)
    assert np.all(np.asarray(p0["d_skip"], np.float32) == 1.0)
    step = np.log1p(np.exp(np.asarray(p0["b_dt"], np.float32)))
    assert 0.9e-3 < step.min() and step.max() < 1.1e-1
    # layer 1 of the tiny preset is its attention layer
    assert "w_q" in params["layers"][1] and "w_in" not in params["layers"][1]
    # the published sizes, counted from shapes: 3,029.3 M
    full = get_config("jamba2-3b").lm
    n = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        jamba.param_shapes(full), is_leaf=lambda s: isinstance(s, tuple)))
    assert n == 3_029_337_472 and full.attention_layers == (7, 21)
