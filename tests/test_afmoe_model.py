"""Trinity-Mini (fira_tpu/model/afmoe.py) against the plain reference
(benchmark/reference_afmoe.py) at ``afmoe-tiny``: seeded random weights,
log-probabilities and layer outputs, never sampled tokens.

Tolerances. Program and reference both run float32 here, so what separates
them is the order of sums (blocked and banded attention, grouped experts,
the two-sided softmax of a decode position): a few 1e-6 on
log-probabilities of size ~4. The limit is 1e-4 — twenty times that, and a
thousand times under what the float8 control reads (asserted below), so
computing in a lower precision fails it; each planted fault of the cache
test reads over 1e-2."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from afmoe_util import ref_cfg, small_query_blocks, weights  # noqa: F401
from benchmark import reference_afmoe as ref
from fira_tpu.config import FULL, SLIDING, get_config
from fira_tpu.decode.slot_model import AfmoeSlotModel, StepView
from fira_tpu.model import afmoe, axk1

TOL = 1e-4
F32 = jnp.float32


@pytest.fixture(scope="module")
def tiny():
    lm = get_config("afmoe-tiny").lm
    return lm, ref_cfg(lm), weights(lm)


def _tokens(lm, shape, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 4,
                                         lm.vocab_size))


def test_full_forward_pass_matches_the_reference_and_float8_does_not(tiny):
    """(a) Prompts shorter than, as long as and three times the window of
    8, in one padded batch: a window layer scores bands of 12 keys."""
    lm, rc, params = tiny
    W = lm.sliding_window
    tok = _tokens(lm, (3, 32))
    lengths = np.asarray([W - 3, W, 3 * W])
    logp = jax.jit(lambda p, t, n: afmoe.forward_logp(p, lm, t, n, F32))(
        params, tok, jnp.asarray(lengths))
    for b, n in enumerate(lengths):
        want = ref.forward(rc, params, tok[b, :n])
        # the padded tail of a prompt moves nothing before it
        assert float(jnp.abs(logp[b, :n] - want).max()) < TOL, b
    low = ref.forward(rc, params, tok[2, :3 * W], "fp8")
    assert float(jnp.abs(low - want).max()) > 1000 * TOL


def _through_the_cache(lm, params, tok, plen, n_gen):
    """Prefill, the slot model's own insert, then ``n_gen`` positions
    teacher-forced one at a time through ring + whole arena + pool, both
    beams of both slots. -> log-probabilities (n_gen, S, K, V)."""
    cfg = get_config("afmoe-tiny", lm=lm, engine_slots=2, beam_size=2)
    S, K, T, BS = 2, 2, cfg.tar_len, 4
    sm = AfmoeSlotModel(None, cfg, S, BS, S * T // BS)
    bucket = 32
    chunk = jax.jit(sm.prefill)(params, {
        "tokens": jnp.asarray(tok[:, :bucket]),
        "lengths": jnp.asarray(plen)})
    state = {n: jnp.zeros(leaf.shape, leaf.dtype)
             for n, leaf in sm.leaves(chunk).items()}
    # dirty caches: a stale entry that a mask lets through would show
    state = {n: x + 3.0 if n.startswith("prompt_k_") else x
             for n, x in state.items()}
    state.update(sm.insert(state, chunk, jnp.arange(S), 1))
    tab = jnp.arange(S * T // BS).reshape(S, T // BS)
    step = jax.jit(lambda st, view: sm.step(params, st, view))
    out = []
    flat = np.zeros((S * K, T), np.int32)
    for g in range(n_gen):
        for s in range(S):
            flat[s * K:(s + 1) * K, g] = tok[s, plen[s] + g]
        pos = jnp.full((S,), g)
        (logp,), writes = step(state, StepView(
            flat=jnp.asarray(flat), pos_c=pos, pos_bk=jnp.repeat(pos, K),
            active=jnp.ones((S,), bool), tab_step=tab))
        state.update(writes)
        out.append(np.asarray(logp).reshape(S, K, -1))
    return np.stack(out), state


def _worst_gap(got, refs, plen):
    return max(float(np.abs(got[g, s, k] - refs[s][plen[s] + g]).max())
               for g in range(got.shape[0]) for s in range(got.shape[1])
               for k in range(got.shape[2]))


def test_prefill_then_decode_through_ring_arena_and_pool(tiny, monkeypatch):
    """(b) Prompts of 27 and 13 tokens (window 8: the first is longer than
    three windows) and 12 generated positions, so generation crosses a
    further window's edge and generated keys themselves fall out of the
    window: every position's log-probabilities are the reference's full
    forward pass over [prompt | beam]. Then the four faults, each planted
    alone, each failing the same tolerance."""
    lm, rc, params = tiny
    tok = _tokens(lm, (2, 48), seed=4)
    plen, n_gen = np.asarray([27, 13]), 12
    refs = [ref.forward(rc, params, tok[s, :plen[s] + n_gen])
            for s in range(2)]
    got, state = _through_the_cache(lm, params, tok, plen, n_gen)
    assert _worst_gap(got, refs, plen) < TOL
    # the attention counters: both slots active at every position, a full
    # layer asked for its whole context, a window layer for at most 8 keys
    ctx = sum(int(n) + g + 1 for n in plen for g in range(n_gen))
    win = sum(min(int(n) + g + 1, 8) for n in plen for g in range(n_gen))
    keys = afmoe.COUNTERS.index("attn_keys_read")
    assert state["counters"][keys:].tolist() == [ctx + 4 * win, 5 * ctx]

    # 1: the ring written one entry off
    inner = afmoe.window_ring
    monkeypatch.setattr(afmoe, "window_ring", lambda kv, n, w: jnp.roll(
        inner(kv, n, w), 1, axis=1))
    bad, _ = _through_the_cache(lm, params, tok, plen, n_gen)
    assert _worst_gap(bad, refs, plen) > 100 * TOL
    monkeypatch.undo()
    # 2: a window off by one, either way
    for w in (lm.sliding_window - 1, lm.sliding_window + 1):
        off = [ref.forward(dict(rc, sliding_window=w), params,
                           tok[s, :plen[s] + n_gen]) for s in range(2)]
        assert _worst_gap(got, off, plen) > 100 * TOL
    # 3: rotary applied to the full layer too
    monkeypatch.setattr(afmoe, "layer_rotates", lambda lm, i: True)
    bad, _ = _through_the_cache(lm, params, tok, plen, n_gen)
    assert _worst_gap(bad, refs, plen) > 100 * TOL
    # 4: rotary left off the window layers
    monkeypatch.setattr(afmoe, "layer_rotates", lambda lm, i: False)
    bad, _ = _through_the_cache(lm, params, tok, plen, n_gen)
    assert _worst_gap(bad, refs, plen) > 100 * TOL


def test_ring_positions_on_hand_made_lengths():
    """Entry r of a ring holds the last prompt position congruent to r;
    negative where the prompt never reached it."""
    pos = np.asarray(afmoe.ring_positions(jnp.asarray([5, 8, 21]), 8))
    assert pos[0].tolist() == [0, 1, 2, 3, 4, -3, -2, -1]
    assert pos[1].tolist() == list(range(8))
    assert pos[2].tolist() == [16, 17, 18, 19, 20, 13, 14, 15]
    # the ring a prefill hands over: the last 8 positions, each at its
    # position mod 8; a bucket under the window fills the first entries
    kv = jnp.arange(32.0).reshape(1, 32, 1).repeat(3, 0)
    ring = afmoe.window_ring(kv, jnp.asarray([5, 8, 21]), 8)
    assert ring[2, :, 0].tolist() == [16, 17, 18, 19, 20, 13, 14, 15]
    assert ring[0, :5, 0].tolist() == [0, 1, 2, 3, 4]
    short = afmoe.window_ring(kv[:, :4], jnp.asarray([3, 4, 2]), 8)
    assert short.shape == (3, 4, 1) and short[1, :, 0].tolist() == [0, 1, 2, 3]
    # keys and values apart, positions last
    x = jnp.arange(2 * 3 * 64.0).reshape(2, 3, 64)
    keys, values = afmoe.prompt_layout(x)
    assert keys.shape == values.shape == (2, 32, 3)
    assert values[0, :, 2].tolist() == x[0, 2, 32:].tolist()


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """(c) THE SHARE TEST. 8 routed experts cut into 2 shares of 4 by
    ``expert_offset``: each share routes over all 8, computes its own
    experts' part plus the shared expert; the two routed parts plus the
    shared expert counted ONCE equal the uncut reference's layer."""
    lm, rc, params = tiny
    p = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(11), (40, lm.hidden_size))
    want = ref.expert_layer(p, x, rc, "f32")
    shared = axk1.swiglu(x, p["shared_gate"], p["shared_up"],
                         p["shared_down"], F32)
    total, held = shared, 0
    for i in range(2):
        lm_i = dataclasses.replace(lm, experts_held=4, expert_offset=4 * i)
        p_i = dict(p, **{k: p[k][4 * i:4 * i + 4] for k in
                         ("experts_gate", "experts_up", "experts_down")})
        out, counters = afmoe.moe_layer(p_i, x, jnp.ones((40,), bool), lm_i,
                                        F32)
        # the share alone is what the reference gives for the same share
        rc_i = dict(rc, expert_offset=4 * i)
        assert float(jnp.abs(out - ref.expert_layer(p_i, x, rc_i, "f32")
                             ).max()) < TOL
        total = total + (out - shared)
        held += int(counters[1])
        assert int(counters[0]) == 40 * lm.num_experts_per_tok
    assert held == 40 * lm.num_experts_per_tok     # every assignment, once
    assert float(jnp.abs(total - want).max()) < TOL


def test_router_bias_chooses_and_never_weighs_ties_included(tiny):
    """(d) Top-2 of ``s + b`` differs from top-2 of ``s`` on the seeded
    bias for some tokens (not all); the weights are ``s / sum s x
    route_scale`` of what was chosen, the bias nowhere in them; equal sums
    go to the lower index, in the program and in the reference."""
    lm, rc, params = tiny
    p = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (256, lm.hidden_size))
    s = jax.nn.sigmoid(x @ p["router"])
    ids, w = afmoe.route(s, p["router_bias"], lm)
    plain, _ = afmoe.route(s, jnp.zeros_like(p["router_bias"]), lm)
    moved = int(jnp.sum(jnp.any(jnp.sort(ids, -1) != jnp.sort(plain, -1),
                                -1)))
    assert 0 < moved < 128, moved
    chosen = jnp.take_along_axis(s, ids, 1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(chosen / chosen.sum(-1, keepdims=True)
                                  * lm.route_scale), rtol=1e-6)
    rids, rw = ref.route(s, p["router_bias"], rc)
    assert (np.sort(np.asarray(rids), -1) == np.sort(np.asarray(ids), -1)
            ).all()
    np.testing.assert_allclose(np.sort(np.asarray(rw), -1),
                               np.sort(np.asarray(w), -1), rtol=1e-6)
    # hand-made: a bias lifts expert 5 over expert 1's higher score, and
    # expert 5 is then weighed by its OWN score; equal sums: lower index
    t = np.full((2, 8), 0.1, np.float32)
    t[0, [1, 2, 5]] = [0.6, 0.9, 0.5]
    b = np.zeros((8,), np.float32)
    b[5] = 0.2
    ids, w = afmoe.route(jnp.asarray(t), jnp.asarray(b), lm)
    assert sorted(ids[0].tolist()) == [2, 5]
    order = np.argsort(np.asarray(ids[0]))
    np.testing.assert_allclose(np.asarray(w[0])[order], lm.route_scale
                               * np.asarray([0.9, 0.5]) / 1.4, rtol=1e-6)
    assert sorted(ids[1].tolist()) == [0, 5]       # 0.1 + 0.2, then a tie
    rids, _ = ref.route(jnp.asarray(t), jnp.asarray(b), rc)
    assert sorted(rids[1].tolist()) == [0, 5]


def test_no_token_is_dropped_when_one_expert_takes_every_token(tiny):
    """(d) A routing where expert 3 takes every token and the second pick
    spreads: the grouped product (shared with A.X-K1) loops until every
    assignment is computed."""
    lm, _rc, params = tiny
    p = params["layers"][2]
    N = 96
    x = jax.random.normal(jax.random.PRNGKey(5), (N, lm.hidden_size))
    ids = jnp.stack([jnp.full((N,), 3), (jnp.arange(N) % 7 + 4) % 8], 1
                    ).astype(jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(6), (N, 2)) + 0.5
    out, loads = jax.jit(lambda x, ids, w: axk1.routed_experts(
        p, x, ids, w, jnp.ones((N,), bool), lm, F32))(x, ids, w)
    assert int(loads[3]) >= N and int(loads.sum()) == 2 * N
    want = jnp.zeros_like(out)
    for j in range(2):
        for e in range(8):
            pick = (ids[:, j] == e)[:, None]
            want = want + jnp.where(pick, w[:, j, None] * axk1.swiglu(
                x, p["experts_gate"][e], p["experts_up"][e],
                p["experts_down"][e], F32), 0.0)
    assert float(jnp.abs(out - want).max()) < TOL


def test_grouped_product_passes_are_capped_and_cover_a_whole_prefill():
    """A prefill dispatch of the real cell sends 131,072 assignments to
    128 held experts: passes of EXPERT_CHUNK_ROWS_MAX rows, not one of
    163,840; a decode position of 144 rows is one pass of all its
    assignments. A.X-K1's own sizes are as they were."""
    lm = get_config("trinity-mini-l5").lm
    assert axk1.expert_chunk_rows(lm, 16384) == axk1.EXPERT_CHUNK_ROWS_MAX
    assert axk1.expert_chunk_rows(lm, 144) == 144 * 8
    ax = get_config("axk1-ep16").lm
    assert axk1.expert_chunk_rows(ax, 8192) == 5120
    assert axk1.expert_chunk_rows(ax, 192) == 128


def test_parameter_tree_is_the_benchmarks_and_bfloat16_from_creation(tiny):
    lm, rc, params = tiny
    from benchmark import weights_afmoe

    assert afmoe.param_shapes(lm) == weights_afmoe.param_shapes(rc)
    own = afmoe.init_params(lm, 0)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree_util.tree_leaves(own))
    assert jax.tree_util.tree_map(lambda x: tuple(x.shape), own) \
        == afmoe.param_shapes(lm)
    # the selection bias is small and centred; gains are near one
    bias = np.asarray(own["layers"][1]["router_bias"], np.float32)
    assert np.abs(bias).max() < 0.1 and abs(float(
        np.asarray(own["layers"][1]["mlp_norm"], np.float32).mean()) - 1) < 0.1
    assert lm.layers_of(FULL) == (2,) and lm.layers_of(SLIDING) == (0, 1, 3, 4)
