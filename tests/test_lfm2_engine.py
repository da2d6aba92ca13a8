"""LFM2-8B-A1B (fira_tpu/model/lfm2.py behind
decode/slot_model.Lfm2SlotModel) against the plain reference
(benchmark/reference_lfm2.py) at ``lfm2-tiny``: seeded random weights,
log-probabilities and convolution tails, never sampled tokens. The full
forward pass; the short convolution's tail continued token by token; prefill
then decode through the arena with parents that change, and through the
engine against a plain beam search; the expert bias; the parameter tree and
its count; the expert counters; the refusals; the names the benchmark's
readers find.

Tolerances. Program and reference both run float32 here, so what separates
them is the order of sums: the program's attention is blocked and its decode
softmax two-sided, its experts are grouped products, its router divides by
``sum + 1e-20`` where the reference has ``+ 1e-6`` (a relative 2e-6 on a
weight). That is a few 1e-6 on log-probabilities of size ~4 (4e-6 read).
The limit is 1e-4 — twenty times that, and a thousand times under what the
float8 control reads (asserted below), so computing in a lower precision
fails it; each planted fault reads over 1e-2."""

import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lfm2_util import ref_cfg, weights
from benchmark import flops_lfm2, weights_lfm2
from benchmark import reference_lfm2 as ref
from fira_tpu.config import (ARCH_TABLE, Lfm2Config, arch_errors,
                             config_errors, get_config, lfm2_tiny)
from fira_tpu.data import buckets
from fira_tpu.data.feeder import Feeder
from fira_tpu.decode.engine import SlotEngine
from fira_tpu.decode.slot_model import Lfm2SlotModel, StepView
from fira_tpu.model import lfm2

TOL = 1e-4
F32 = jnp.float32
EOS, START = 1, 2


@pytest.fixture(scope="module")
def tiny():
    lm = get_config("lfm2-tiny").lm
    return lm, ref_cfg(lm), weights(lm)


def _tokens(lm, shape, seed=1):
    return np.array(jax.random.randint(jax.random.PRNGKey(seed), shape, 4,
                                       lm.vocab_size))


def _forward(lm, params, tok, lengths):
    return jax.jit(lambda p, t, n: lfm2.forward_logp(p, lm, t, n, F32))(
        params, tok, jnp.asarray(lengths))


def test_full_forward_pass_matches_the_reference_and_float8_does_not(tiny):
    """Prompts of 2 tokens (under the convolution's 3 taps), of 19 and as
    long as the bucket, in one padded batch."""
    lm, rc, params = tiny
    tok = _tokens(lm, (3, 32))
    lengths = np.asarray([2, 19, 32])
    logp = _forward(lm, params, tok, lengths)
    for b, n in enumerate(lengths):
        want = ref.forward(rc, params, tok[b, :n])
        # the padded tail of a prompt moves nothing before it
        assert float(jnp.abs(logp[b, :n] - want).max()) < TOL, b
    low = ref.forward(rc, params, tok[2], "fp8")
    assert float(jnp.abs(low - want).max()) > 1000 * TOL


def test_the_convolutions_tail_continued_token_by_token_is_the_whole_convolution(
        tiny):
    """``conv_prefill`` over a prompt of n tokens in a padded bucket, then
    ``conv_step`` a token at a time from the tail it handed over, gives
    what ``conv_prefill`` gives over the whole sequence at once, output and
    tail alike; a row that is not active keeps its tail."""
    lm, _rc, params = tiny
    p = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 32, lm.hidden_size))
    whole, whole_tail = lfm2.conv_prefill(p, h, jnp.asarray([32, 32]), lm,
                                          F32)
    for n in (1, 2, 13):
        out, tail = lfm2.conv_prefill(p, h, jnp.asarray([n, 32]), lm, F32)
        assert float(jnp.abs(out[0, :n] - whole[0, :n]).max()) < 1e-5
        assert tail.shape == (lm.conv_L_cache - 1, 2, lm.hidden_size)
        tail = tail[:, :1]
        for t in range(n, 32):
            y, tail = lfm2.conv_step(p, h[:1, t], tail, jnp.asarray([True]),
                                     F32)
            assert float(jnp.abs(y[0] - whole[0, t]).max()) < 1e-5, (n, t)
        assert float(jnp.abs(tail - whole_tail[:, :1]).max()) < 1e-6
    _y, kept = lfm2.conv_step(p, h[:1, 0], whole_tail[:, :1],
                              jnp.asarray([False]), F32)
    assert bool(jnp.all(kept == whole_tail[:, :1]))
    # a prompt of one token: the tail's older entry is the zero before it
    _out, tail = lfm2.conv_prefill(p, h, jnp.asarray([1, 32]), lm, F32)
    assert bool(jnp.all(tail[0, 0] == 0)) and bool(jnp.any(tail[1, 0] != 0))


def test_prefill_hands_over_tails_and_keys_at_each_prompts_own_length(tiny):
    """The same prompt alone in buckets of 32 and 64, and among others:
    tails, keys and values at its own length; the counters count its real
    tokens only, and no expert read (a prefill's bytes are not a step's)."""
    lm, _rc, params = tiny
    tok = _tokens(lm, (3, 32), seed=2)
    lengths = np.asarray([13, 2, 27])
    tails, kvs, counters = lfm2.prefill(params, lm, jnp.asarray(tok),
                                        jnp.asarray(lengths), F32)
    n_moe = lm.num_hidden_layers - lm.num_dense_layers
    c = dict(zip(lfm2.COUNTERS, counters.tolist()))
    assert c["moe_assignments"] == c["moe_assignments_held"] \
        == lengths.sum() * lm.num_experts_per_tok * n_moe
    assert c["moe_experts_read"] == 0
    for P in (32, 64):
        alone = np.zeros((1, P), np.int32)
        alone[0, :13] = tok[0, :13]
        t1, kv1, _c = lfm2.prefill(params, lm, jnp.asarray(alone),
                                   jnp.asarray([13]), F32)
        for a, b in zip(t1, tails):
            assert float(jnp.abs(a[:, 0] - b[:, 0]).max()) < TOL
        assert float(jnp.abs(kv1[0][0][0, :, :13]
                             - kvs[0][0][0, :, :13]).max()) < TOL
    assert len(tails) == 4 and len(kvs) == 1


def _through_the_arena(lm, params, tok, plen, n_gen, parents):
    """Prefill, the slot model's own insert, then ``n_gen`` positions
    teacher-forced one at a time through the tail leaves, the prompt arena
    and the pool. ``tok``: two continuations of each slot's prompt that are
    equal for their first ``SPLIT`` generated tokens. Both lanes of a slot
    are fed continuation 0 up to there (as a fresh slot's beams share one
    history), then lane k continuation k — and WHICH LANE holds which
    continuation is switched by ``parents[g]`` (S, K) before position g, as
    a selection would: the tails have to follow. -> (log-probabilities
    (n_gen, S, K, V), the continuation each lane was fed at each position,
    the arena)."""
    cfg = get_config("lfm2-tiny", lm=lm, engine_slots=2, beam_size=2)
    S, K, T, BS = 2, 2, cfg.tar_len, 4
    sm = Lfm2SlotModel(None, cfg, S, BS, S * T // BS)
    chunk = jax.jit(sm.prefill)(params, {
        "tokens": jnp.asarray(tok[0][:, :32]), "lengths": jnp.asarray(plen)})
    state = {n: jnp.zeros(leaf.shape, leaf.dtype) + (
        3.0 if n.startswith(("prompt_k_", "conv_tail")) else 0)
        for n, leaf in sm.leaves(chunk).items()}          # a dirty arena
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
    tail."""
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


def test_prefill_then_decode_reads_each_beams_parents_tail(tiny,
                                                           monkeypatch):
    """Every position's log-probabilities are the reference's full forward
    pass re-run over [prompt | the sequence this lane was fed], while the
    lanes' parents change at six of twelve positions (one slot once hands
    BOTH lanes the same parent). Then the fault: ``parent`` ignored, every
    lane continuing from its own old tail."""
    lm, rc, params = tiny
    tok, plen, n_gen, parents = _arena_case(lm)
    refs = [[ref.forward(rc, params, t[s, :plen[s] + n_gen])
             for s in range(2)] for t in tok]
    got, fed, state = _through_the_arena(lm, params, tok, plen, n_gen,
                                         parents)
    assert _worst(got, fed, refs, plen) < TOL
    c = dict(zip(lfm2.COUNTERS, state["counters"].tolist()))
    n_moe, k = 3, lm.num_experts_per_tok
    # the prompts' assignments (insert) and 2 slots x 2 beams a position
    assert c["moe_assignments"] == n_moe * k * (plen.sum() + 4 * n_gen)
    # 4 rows choose at least k and at most 8 experts a layer and position
    assert n_moe * k * n_gen <= c["moe_experts_read"] <= n_moe * 8 * n_gen

    inner = lfm2.decode_step
    monkeypatch.setattr(lfm2, "decode_step", lambda *a, **kw: inner(
        *a[:5], jnp.broadcast_to(jnp.arange(2), (2, 2)), *a[6:], **kw))
    bad, fed, _state = _through_the_arena(lm, params, tok, plen, n_gen,
                                          parents)
    assert _worst(bad, fed, refs, plen) > 100 * TOL


def test_an_inactive_slot_keeps_its_tails_and_its_pool(tiny):
    lm, _rc, params = tiny
    tok, plen, _n, _p = _arena_case(lm)
    _got, _fed, state = _through_the_arena(lm, params, tok, plen, 2,
                                           [None, [[0, 0]] * 2])
    cfg = get_config("lfm2-tiny", lm=lm, engine_slots=2, beam_size=2)
    sm = Lfm2SlotModel(None, cfg, 2, 4, 2 * cfg.tar_len // 4)
    flat = jnp.full((4, cfg.tar_len), 7, jnp.int32)
    pos = jnp.full((2,), 2)
    tab = jnp.arange(8).reshape(2, 4)
    active = jnp.asarray([True, False])
    (_logp,), writes = sm.step(params, state, StepView(
        flat=flat, pos_c=pos, pos_bk=jnp.repeat(pos, 2), active=active,
        tab_step=jnp.where(active[:, None], tab, 8),
        parent=jnp.asarray([[1, 0], [1, 0]])))
    for name, new in writes.items():
        if name.startswith("conv_tail"):
            assert bool(jnp.all(new[:, 2:] == state[name][:, 2:])), name
            assert not bool(jnp.all(new[:, :2] == state[name][:, :2])), name
    assert bool(jnp.all(writes["kv_pool"][:, 4:] == state["kv_pool"][:, 4:]))
    got = dict(zip(lfm2.COUNTERS, writes["counters"].tolist()))
    was = dict(zip(lfm2.COUNTERS, state["counters"].tolist()))
    assert got["moe_assignments"] == was["moe_assignments"] \
        + 3 * 2 * lm.num_experts_per_tok      # one slot's 2 rows, 3 layers


def test_expert_bias_changes_the_chosen_set_and_never_the_weights(tiny):
    """Top-k of ``s + b`` differs from top-k of ``s`` on the seeded bias for
    some rows (not all); the weights are ``s / sum s`` of what was chosen,
    the bias nowhere in them — in the program's router (model/afmoe.route,
    shared with Trinity-Mini) and in the reference's."""
    lm, rc, params = tiny
    p = params["layers"][3]
    x = jax.random.normal(jax.random.PRNGKey(2), (256, lm.hidden_size))
    s = jax.nn.sigmoid(x @ p["router"])
    ids, w = lfm2.route(s, p["expert_bias"], lm)
    plain, _ = lfm2.route(s, jnp.zeros_like(p["expert_bias"]), lm)
    moved = int(jnp.sum(jnp.any(jnp.sort(ids, -1) != jnp.sort(plain, -1),
                                -1)))
    assert 0 < moved < 128, moved
    chosen = jnp.take_along_axis(s, ids, 1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(chosen / chosen.sum(-1, keepdims=True)),
        rtol=1e-6)
    rids, rw = ref.route(s, p["expert_bias"], rc)
    assert (np.sort(np.asarray(rids), -1) == np.sort(np.asarray(ids), -1)
            ).all()
    np.testing.assert_allclose(np.sort(np.asarray(rw), -1),
                               np.sort(np.asarray(w), -1), rtol=1e-5)
    # the published widths' draw moves a fifth of the rows' sets, not all
    W = jax.random.normal(jax.random.PRNGKey(4), (2048, 32)) * 2048 ** -0.5
    b = lfm2.EXPERT_BIAS_STD * jax.random.normal(jax.random.PRNGKey(5), (32,))
    s = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(6), (4096, 2048))
                       @ W)
    full = get_config("lfm2-8b-a1b-l12").lm
    a, _ = lfm2.route(s, b, full)
    z, _ = lfm2.route(s, jnp.zeros_like(b), full)
    share = float(jnp.mean(jnp.any(jnp.sort(a, -1) != jnp.sort(z, -1), -1)))
    assert 0.1 < share < 0.3, share


def test_a_router_that_drops_the_bias_fails_the_comparison(tiny,
                                                           monkeypatch):
    """The selection bias left out of ``afmoe.route``: other experts for
    some tokens, and log-probabilities far outside the tolerance."""
    lm, rc, params = tiny
    tok = _tokens(lm, (1, 32), seed=6)
    want = ref.forward(rc, params, tok[0])
    inner = lfm2.route
    monkeypatch.setattr(lfm2, "route", lambda s, b, lm: inner(
        s, jnp.zeros_like(b), lm))
    bad = _forward(lm, params, tok, [32])
    assert float(jnp.abs(bad[0] - want).max()) > 100 * TOL


def test_parameter_tree_is_the_benchmarks_and_its_count_the_published(tiny):
    """The program's tree equals ``weights_lfm2.param_shapes`` at the tiny
    preset and at the cut (shapes only: nothing is allocated); the count
    from the sizes is 3,928,728,256 at twelve layers and 8.34 B at the
    published twenty-four; bfloat16 from creation."""
    lm, rc, _params = tiny
    assert lfm2.param_shapes(lm) == weights_lfm2.param_shapes(rc)
    params = lfm2.init_params(lm, 0)
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree_util.tree_leaves(params))
    assert "conv_in" in params["layers"][0] and "w_gate" in params["layers"][0]
    assert "w_q" in params["layers"][2] and "router" in params["layers"][2]
    assert "shared_gate" not in params["layers"][2]      # no shared expert
    # the cut and the published 24 layers (Lfm2Config's defaults)
    for full, want in ((get_config("lfm2-8b-a1b-l12").lm, 3_928_728_256),
                       (Lfm2Config(), 8_339_930_560)):
        rc_full = dict(ref_cfg(full), layer_types=list(full.layer_types))
        shapes = lfm2.param_shapes(full)
        assert shapes == weights_lfm2.param_shapes(rc_full)
        n = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            shapes, is_leaf=lambda s: isinstance(s, tuple)))
        assert n == flops_lfm2.param_count(rc_full) == want
    assert abs(8_339_930_560 - 8.34e9) < 1e6
    assert get_config("lfm2-8b-a1b-l12").lm.layers_of("full_attention") \
        == (2, 6, 10)


def _requests():
    """Seven prompts over all three buckets, each with its own limit: with
    3 slots they sit at mixed depths and the arena is refilled twice."""
    rng = np.random.default_rng(2)
    lens = [5, 12, 21, 30, 44, 61, 9]
    prompts = [rng.integers(4, 64, n, dtype=np.int32) for n in lens]
    return prompts, np.asarray([3, 7, 11, 15, 5, 9, 13], np.int32)


def _drain(eng, cfg, reqs):
    tasks = buckets.prompt_tasks(cfg.lm, ((i, p, int(m)) for i, (p, m)
                                          in enumerate(zip(*reqs))))
    with Feeder(tasks, num_workers=0, depth=2) as feed:
        return list(eng.run(feed))


def _last_logp(rc, params, seq):
    """The reference's distribution after ``seq``, the pass padded at its
    end to a multiple of 16 tokens (what comes after a token cannot reach
    it): five shapes to compile, not one a length."""
    n = len(seq)
    tokens = np.zeros((-(-n // 16) * 16,), np.int32)
    tokens[:n] = seq
    return np.asarray(ref.forward(rc, params, tokens,
                                  rows=slice(n - 1, n)))[0]


def plain_beam_search(rc, params, prompt, n: int, K: int):
    """A beam search as a textbook has it, over the reference's
    log-probabilities: no cache, no tail carried, the whole sequence through
    the reference at every step. -> (the most probable beam's tokens after
    <start>, its log-probability, the positions at which some beam did NOT
    continue the beam of its own index)."""
    beams = [([START], 0.0, False)]
    moved = 0
    for _ in range(n):
        cands = []
        for b, (toks, lp, fin) in enumerate(beams):
            if fin:
                cands.append((lp, b, None))
                continue
            logp = _last_logp(rc, params, np.concatenate([prompt, toks]))
            for t in np.argsort(-logp, kind="stable")[:K]:
                cands.append((lp + float(logp[t]), b, int(t)))
        cands.sort(key=lambda c: -c[0])
        new = []
        for lp, b, t in cands[:K]:
            toks, _, _fin = beams[b]
            new.append((toks, lp, True) if t is None
                       else (toks + [t], lp, t == EOS))
        if len(beams) == K:
            moved += any(b != k for k, (_lp, b, _t) in enumerate(cands[:K]))
        beams = new
        if all(b[2] for b in beams):
            break
    best = max(beams, key=lambda b: b[1])
    return best[0][1:], best[1], moved


def test_engine_equals_a_plain_beam_search_over_the_reference(tmp_path):
    """Mixed buckets through 3 slots (slots are reused: a tail lane holds
    another request's tail when a new one is seated). The engine's served
    beam is the plain search's token for token, stops at the request's own
    limit, and its log-probability is the plain search's to 1e-3: float32
    both, sums of up to 15 log-probabilities that agree to ~1e-5 each, the
    candidates of a position ~1e-2 apart. The beams' parents are NOT the
    identity at several positions of several requests. The arena's bytes
    follow its leaves by kind, and the device's expert counts arrive."""
    from fira_tpu.decode.runner import run_lm_test

    cfg = get_config("lfm2-tiny", engine_slots=3)
    lm, rc = cfg.lm, ref_cfg(cfg.lm)
    params = weights(lm, seed=5)
    reqs = _requests()
    eng = SlotEngine(None, params, cfg)
    eng.prewarm(buckets.prompt_warm_batches(lm))
    items = {int(it.host["_positions"][it.row]): it
             for it in _drain(eng, cfg, reqs)}
    assert sorted(items) == list(range(7))
    moved_at = []
    for i, (prompt, n) in enumerate(zip(*reqs)):
        it = items[i]
        served = int(np.argmax(it.probs))
        got = [int(t) for t in it.tokens[served][1:n + 1]]
        want, logp, moved = plain_beam_search(rc, params, prompt, int(n),
                                              cfg.beam_size)
        assert got[:len(want)] == want, i
        assert not any(got[len(want):])            # only after an <eos>
        assert abs(float(it.probs[served]) - logp) < 1e-3, i
        moved_at.append(moved)
    assert sum(m >= 3 for m in moved_at) >= 3, moved_at
    out = run_lm_test(cfg, out_dir=str(tmp_path), params=params,
                      requests=reqs)
    lines = open(out["output_path"]).read().splitlines()
    for i, n in enumerate(reqs[1]):
        it = items[i]
        assert [int(t) for t in lines[i].split()] == [
            int(t) for t in it.tokens[int(np.argmax(it.probs))][1:n + 1]]
    e = out["engine"]
    K, f32 = cfg.beam_size, 4
    # 4 conv layers x 2 taps x d a beam lane, float32 at the tiny preset
    tails = 4 * (lm.conv_L_cache - 1) * lm.hidden_size * f32
    assert e["kv_bytes_per_slot_state"] == K * tails
    assert e["kv_bytes_per_slot_full"] == lm.prompt_len_max * lm.kv_dim * f32
    assert e["kv_bytes_per_slot_window"] == 0
    assert e["kv_bytes_per_slot"] == (
        K * tails + e["kv_bytes_per_slot_full"]
        + K * cfg.tar_len * lm.kv_dim * f32)
    assert e["moe_assignments"] == e["moe_assignments_held"] > 0
    assert 0 < e["moe_experts_read"] and e["moe_held_load_max"] > 0


def test_arena_holds_a_tail_a_beam_lane_beside_the_attention_layers_cache():
    """A leaf a conv layer for its tail, rows folded (slot, lane) and the
    hidden size last, followed by ``parent``; the attention layer's prompt
    leaves shared by the beams; the pool's layer axis counts ATTENTION
    layers; no recurrent state; the scopes the trace is read by."""
    cfg = get_config("lfm2-tiny", engine_slots=2)
    eng = SlotEngine(None, weights(cfg.lm), cfg)
    eng.prewarm(buckets.prompt_warm_batches(cfg.lm))
    lm, K = cfg.lm, cfg.beam_size
    leaves = eng._leaves
    assert sorted(n for n in leaves if n.startswith("conv")) == [
        f"conv_tail{j}" for j in range(4)]
    assert not any("ssm" in n for n in leaves)
    for j in range(4):
        leaf = leaves[f"conv_tail{j}"]
        assert leaf.shape == (lm.conv_L_cache - 1, 2 * K, lm.hidden_size)
        assert leaf.kv and leaf.kv_kind == "state" and leaf.reorder is None
    for n in ("prompt_k_full0", "prompt_v_full0"):
        assert leaves[n].shape == (2, lm.kv_dim // 2, 64)
        assert leaves[n].kv_kind == "full" and leaves[n].reorder is None
    assert "prompt_k_full1" not in leaves
    assert leaves["kv_pool"].shape == (1, eng._pool_blocks, K,
                                       eng._block_size, lm.kv_dim)
    assert leaves["kv_pool"].reorder == "pool"
    assert eng.smodel.beam_parent and not eng.smodel.beam_ancestry
    assert eng._state["parent"].shape == (2, K)
    assert eng.smodel.prefill_budget == 1
    assert eng.smodel.arena_counters == lfm2.COUNTERS
    text = jax.jit(lambda p, st: eng._step_fn(p, st)).lower(
        eng._decode_params, eng._state).as_text(debug_info=True)
    names = " ".join(set(re.findall(r'loc\("([^"]*)"', text)))
    for scope in ("conv.step", "attn.full.decode", "moe.route",
                  "moe.experts", "mlp", "lm_head", "kv_reorder"):
        assert scope in names, scope
    wire = {k: v for k, v in buckets.prompt_warm_batches(lm)[1][0].items()
            if not k.startswith("_")}
    text = jax.jit(lambda p, b: eng._prefill_fn(p, b)).lower(
        eng.params, wire).as_text(debug_info=True)
    names = " ".join(set(re.findall(r'loc\("([^"]*)"', text)))
    for scope in ("conv.prefill", "attn.full.prefill", "moe.route",
                  "moe.experts", "mlp"):
        assert scope in names, scope
    assert eng._step.__name__ == "_step_fn"
    assert eng._prefill.__name__ == "_prefill_fn"


def test_one_table_says_what_lfm2_is():
    """config.ARCH_TABLE names the model module and the slot model; the
    engine holds no architecture's name."""
    import inspect

    from fira_tpu.decode import engine, slot_model

    assert ARCH_TABLE["lfm2"].model == "fira_tpu.model.lfm2"
    assert "lfm2" not in inspect.getsource(engine).replace(
        "model/lfm2.COUNTERS", "")
    assert isinstance(slot_model.for_config(
        None, get_config("lfm2-tiny"), 2, 4, 8), slot_model.Lfm2SlotModel)
    assert config_errors(get_config("lfm2-8b-a1b-l12")) == []


REFUSED = {
    "prefix_cache": dict(prefix_cache=True),
    "spec_decode": dict(spec_decode="draft"),
    "int8w": dict(serve_precision="int8w"),
    "bf16-weight-tier": dict(serve_precision="bf16"),
    "kv_dtype": dict(kv_dtype="bf16"),
    "engine_replicas": dict(engine_replicas=2, engine_slots=4),
    "serve/disagg.py": dict(serve_tiers="prefill-pool"),
    "non-engine beam": dict(decode_engine=False),
    "graph buckets": dict(buckets=((16, 400, 12),)),
    "beam_compat_prob_space": dict(beam_compat_prob_space=True),
    "buckets": dict(decode_tar_buckets=True),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_unsupported_combinations_are_refused_by_name(what):
    """What ``arch_errors`` refuses for axk1, afmoe, jamba and brumby it
    refuses for lfm2 through the same lines."""
    cfg = lfm2_tiny(**REFUSED[what])
    errs = config_errors(cfg)
    assert errs and all("lfm2" in e for e in errs), errs
    assert [e.replace("lfm2", "jamba") for e in errs] == config_errors(
        get_config("jamba-tiny", **REFUSED[what]))
    if what != "non-engine beam":
        with pytest.raises(ValueError, match="lfm2"):
            SlotEngine(None, None, cfg)


@pytest.mark.parametrize("command", ["train", "serve", "message"])
def test_cli_commands_it_does_not_run_exit_2_with_its_name(command, capsys):
    from fira_tpu import cli

    assert arch_errors(lfm2_tiny(), command)
    rc = cli.main([command, "--engine", "--config", "lfm2-tiny"]
                  + (["x.diff"] if command == "message" else []))
    assert rc == 2
    err = capsys.readouterr().err
    assert f"arch 'lfm2' does not support cli {command}" in err


def test_cli_test_without_engine_is_refused_and_with_it_runs(tmp_path,
                                                             capsys):
    from fira_tpu import cli

    assert cli.main(["test", "--config", "lfm2-tiny",
                     "--out-dir", str(tmp_path)]) == 2
    assert "non-engine beam" in capsys.readouterr().err
    assert cli.main(["test", "--engine", "--config", "lfm2-tiny",
                     "--out-dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "prompt buckets: 3 engine prefill programs pre-warmed" in out
    assert len(open(tmp_path / "output_lfm2").read().splitlines()) == 64
    spans = [json.loads(l) for l in open(tmp_path / "spans.jsonl")]
    assert any(s.get("name") == "engine.prefill" for s in spans)


@pytest.mark.parametrize("bad,word", [
    (dict(conv_bias=True), "conv_bias"),
    (dict(use_expert_bias=False), "use_expert_bias"),
    (dict(num_attention_heads=5), "num_attention_heads"),
    (dict(layer_types=("conv",) * 4), "layer_types"),
    (dict(prompt_buckets=(32, 16)), "prompt_buckets"),
])
def test_a_key_block_that_cannot_be_is_named(bad, word):
    lm = dataclasses.replace(get_config("lfm2-tiny").lm, **bad)
    assert any(word in e for e in config_errors(
        get_config("lfm2-tiny", lm=lm)))
    assert any("config.Lfm2Config" in e for e in config_errors(
        get_config("lfm2-tiny", lm=get_config("jamba-tiny").lm)))
