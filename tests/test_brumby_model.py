"""Brumby-14B-Base (fira_tpu/model/brumby.py) against the plain reference
(benchmark/reference_brumby.py) at ``brumby-tiny``: seeded random weights,
log-probabilities and prompt states, never sampled tokens.

Tolerances. Program and reference both run float32 here, so what separates
them is the order of sums and the algorithm: the program computes retention
in blocks of queries and a prompt's state from the keys' features in blocks
of tokens, a decode position from the prompt's state; the reference writes
out every weight, and its state walks the prompt token by token. That is a
few 1e-6 on log-probabilities of size ~6 and a few 1e-5 on states' readings
of size ~50-100. The limits are 1e-4 on log-probabilities and 1e-3 on the
states' readings — twenty times that, and a thousand times under what the
float8 control reads (asserted below), so computing in a lower precision
fails them; each planted fault reads over 100 times the limit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brumby_util import ref_cfg, weights
from benchmark import reference_brumby as ref
from benchmark import weights_brumby
from fira_tpu.config import get_config
from fira_tpu.decode.slot_model import BrumbySlotModel, StepView, permute_pool
from fira_tpu.model import brumby

TOL = 1e-4          # log-probabilities
STATE_TOL = 1e-3    # a state read through 300 probes' features
F32 = jnp.float32


@pytest.fixture(scope="module")
def tiny():
    lm = get_config("brumby-tiny").lm
    return lm, ref_cfg(lm), weights(lm)


@pytest.fixture(autouse=True)
def short_blocks(monkeypatch):
    """Query blocks of 8 and state blocks of 16 tokens a bucket: a bucket
    of 32 is then 4 query blocks in 4 spans and several trips of the
    state's sum, as a bucket of 16,384 is at the published sizes."""
    monkeypatch.setattr(brumby, "ATTN_Q_BLOCK", 8)
    monkeypatch.setattr(brumby, "STATE_TOKENS", 16)


def _tokens(lm, shape, seed=1):
    return np.array(jax.random.randint(jax.random.PRNGKey(seed), shape, 4,
                                       lm.vocab_size))


def test_full_forward_pass_matches_the_reference_and_float8_does_not(tiny):
    """Prompts shorter than a query block, not a multiple of it, and as
    long as the bucket, in one padded batch."""
    lm, rc, params = tiny
    tok = _tokens(lm, (3, 32))
    lengths = np.asarray([5, 19, 32])
    logp = jax.jit(lambda p, t, n: brumby.forward_logp(p, lm, t, n, F32))(
        params, tok, jnp.asarray(lengths))
    for b, n in enumerate(lengths):
        want = ref.forward(rc, params, tok[b, :n])
        # the padded tail of a prompt moves nothing before it
        assert float(jnp.abs(logp[b, :n] - want).max()) < TOL, b
    low = ref.forward(rc, params, tok[2], "fp8")
    assert float(jnp.abs(low - want).max()) > 1000 * TOL


def _state_gap(rc, params, states, norms, tokens, b):
    """The worst gap between the state prefill hands over for row ``b``
    and the reference's recurrent form past the prompt's last token, read
    through the features of 300 random probes (each side in its own
    layout: equal readings for probes that span the features are equal
    states)."""
    u = jax.random.normal(jax.random.PRNGKey(5), (300, rc["head_dim"]))
    worst = 0.0
    for j, (S, z) in enumerate(ref.recurrent_state(rc, params, tokens)):
        fr, fp = ref.features(u), brumby.features(u)
        for want, got in ((jnp.einsum("uD,gDv->ugv", fr, S),
                           jnp.einsum("uD,gDv->ugv", fp, states[j][b])),
                          (jnp.einsum("uD,gD->ug", fr, z),
                           jnp.einsum("uD,gD->ug", fp, norms[j][b]))):
            worst = max(worst, float(jnp.abs(got - want).max()))
    return worst


def test_prefill_hands_over_the_state_at_each_prompts_own_length(tiny):
    """Lengths that are no multiple of a block, in ONE bucket of 32 with
    other lengths: S and z are the recurrent form's, token by token, at
    each prompt's own last token, whatever pads the bucket."""
    lm, rc, params = tiny
    tok = _tokens(lm, (4, 32), seed=2)
    lengths = np.asarray([13, 2, 27, 32])
    states, norms, counters = jax.jit(
        lambda p, t, n: brumby.prefill(p, lm, t, n, F32))(
        params, tok, jnp.asarray(lengths))
    assert counters.tolist() == [0, 0]
    assert states[0].shape == (4, 2, lm.state_dim, lm.head_dim) \
        and norms[1].shape == (4, 2, lm.state_dim)
    for b, n in enumerate(lengths):
        assert _state_gap(rc, params, states, norms, tok[b, :n], b) \
            < STATE_TOL, b
    # the same prompt ALONE in a padded bucket, and in a longer bucket
    for P in (32, 64):
        alone = np.zeros((1, P), np.int32)
        alone[0, :13] = tok[0, :13]
        s1, n1, _c = brumby.prefill(params, lm, jnp.asarray(alone),
                                    jnp.asarray([13]), F32)
        for a, b in zip(s1 + n1, states + norms):
            assert float(jnp.abs(a[0] - b[0]).max()) < STATE_TOL


def test_a_state_taken_at_the_buckets_end_is_caught(tiny, monkeypatch):
    """The fault the padding invites: the gate and the key not zeroed at
    padded positions, so the state runs on to the bucket's end."""
    lm, rc, params = tiny
    tok = _tokens(lm, (1, 32), seed=2)
    monkeypatch.setattr(brumby, "real_positions", lambda P, lengths:
                        jnp.ones((lengths.shape[0], P), bool))
    states, norms, _c = brumby.prefill(params, lm, jnp.asarray(tok),
                                       jnp.asarray([13]), F32)
    assert _state_gap(rc, params, states, norms, tok[0, :13], 0) \
        > 100 * STATE_TOL


def test_the_feature_map_is_the_squared_scaled_product():
    u, w = (jax.random.normal(jax.random.PRNGKey(i), (7, 16))
            for i in (0, 1))
    want = jnp.sum(u * w, -1) ** 2 / 16
    for phi in (brumby.features, ref.features):
        f = phi(u)
        assert f.shape == (7, 136)
        assert float(jnp.abs(jnp.sum(f * phi(w), -1) - want).max()) < 1e-5
    # every pair once: the program's layout is a permutation of the
    # reference's, entry by entry
    a, b = np.sort(np.asarray(brumby.features(u)), -1), \
        np.sort(np.asarray(ref.features(u)), -1)
    assert np.abs(a - b).max() < 1e-6


SPLIT = 3   # generated tokens the two continuations of a prompt share


def _through_the_arena(lm, params, tok, plen, n_gen, srcs):
    """Prefill, the slot model's own insert, then ``n_gen`` positions
    teacher-forced one at a time through the state leaves and the pool.
    ``tok``: two continuations of each slot's prompt that are equal for
    their first SPLIT generated tokens. Both lanes of a slot are fed
    continuation 0 up to there, then lane k continuation k — and WHICH LANE
    holds which continuation is switched by ``srcs[g]`` (S, K) before
    position g, as a selection would: the pool moves with it as the engine
    moves it (permute_pool), and the prompt's state stays where it is. ->
    (log-probabilities (n_gen, S, K, V), the continuation each lane was
    fed at each position, the arena)."""
    cfg = get_config("brumby-tiny", lm=lm, engine_slots=2, beam_size=2)
    S, K, T, BS = 2, 2, cfg.tar_len, 4
    sm = BrumbySlotModel(None, cfg, S, BS, S * T // BS)
    chunk = jax.jit(sm.prefill)(params, {
        "tokens": jnp.asarray(tok[0][:, :32]), "lengths": jnp.asarray(plen)})
    state = {n: jnp.zeros(leaf.shape, leaf.dtype) + (
        3.0 if n.startswith(("ret_", "kv_pool", "gen_gate")) else 0)
        for n, leaf in sm.leaves(chunk).items()}           # a dirty arena
    state.update(sm.insert(state, chunk, jnp.arange(S), 1))
    tab = jnp.arange(S * T // BS).reshape(S, T // BS)
    step = jax.jit(lambda st, view: sm.step(params, st, view))
    out, fed = [], []
    flat = np.zeros((S * K, T), np.int32)
    seq = np.zeros((S, K), np.int64)           # the continuation a lane holds
    for g in range(n_gen):
        if g:
            src = np.asarray(srcs[g])
            seq = np.take_along_axis(seq, src, 1)
            if g == SPLIT:
                seq = np.tile(np.arange(K), (S, 1))
            flat = flat.reshape(S, K, T)[np.arange(S)[:, None], src
                                         ].reshape(S * K, T)
            for name in ("kv_pool", "gen_gate"):
                state[name] = permute_pool(state[name], tab, jnp.asarray(src))
        for s in range(S):
            for k in range(K):
                flat[s * K + k, g] = tok[seq[s, k]][s, plen[s] + g]
        pos = jnp.full((S,), g)
        (logp,), writes = step(state, StepView(
            flat=jnp.asarray(flat), pos_c=pos, pos_bk=jnp.repeat(pos, K),
            active=jnp.ones((S,), bool), tab_step=tab))
        assert not any(n.startswith("ret_") for n in writes)
        state.update(writes)
        out.append(np.asarray(logp).reshape(S, K, -1))
        fed.append(seq.copy())
    return np.stack(out), fed, state


def _arena_case(lm):
    """Two message continuations of each of two prompts (16 and 13 tokens)
    that part after SPLIT generated tokens; the lanes are crossed and
    uncrossed after that, and slot 0 once hands both lanes lane 1's
    history."""
    plen, n_gen = np.asarray([16, 13]), 12
    tok = [_tokens(lm, (2, 48), seed=4), _tokens(lm, (2, 48), seed=9)]
    for s, n in enumerate(plen):
        tok[1][s, :n + SPLIT] = tok[0][s, :n + SPLIT]
    same, cross = [[0, 1], [0, 1]], [[1, 0], [1, 0]]
    srcs = [None, [[0, 0]] * 2, [[1, 0], [0, 0]], same, same, cross,
            same, cross, cross, [[1, 1], [0, 1]], same, cross]
    return tok, plen, n_gen, srcs


def _worst(got, fed, refs, plen):
    return max(float(np.abs(got[g, s, k]
                            - refs[fed[g][s, k]][s][plen[s] + g]).max())
               for g in range(got.shape[0]) for s in range(got.shape[1])
               for k in range(got.shape[2]))


def _refs(rc, params, tok, plen, n_gen):
    return [[ref.forward(rc, params, t[s, :plen[s] + n_gen])
             for s in range(2)] for t in tok]


def test_prefill_then_decode_through_the_arena_on_logits(tiny):
    """Every position's log-probabilities are the reference's full forward
    pass over [prompt | the sequence this lane was fed], while the lanes'
    sources change at six of twelve positions (one slot once hands BOTH
    lanes the same history): the prompt's state is read by both lanes as
    it lies, their own positions follow them in the pool."""
    lm, rc, params = tiny
    tok, plen, n_gen, srcs = _arena_case(lm)
    got, fed, state = _through_the_arena(lm, params, tok, plen, n_gen, srcs)
    assert _worst(got, fed, _refs(rc, params, tok, plen, n_gen), plen) < TOL
    # both slots active at every position: 2 slots x 2 layers a position
    # read a prompt state; each beam attended g + 1 own positions a layer
    own = 2 * 2 * 2 * sum(g + 1 for g in range(n_gen))
    assert state["counters"].tolist() == [2 * 2 * n_gen, own]


@pytest.mark.parametrize("fault", ["no_decay", "no_normaliser"])
def test_a_fault_of_the_prompts_part_is_caught(tiny, monkeypatch, fault):
    """The prompt's state read at a generated position without its decay
    ``e^{c_t}``, or without its normaliser ``z``."""
    lm, rc, params = tiny
    tok, plen, n_gen, srcs = _arena_case(lm)
    if fault == "no_decay":
        monkeypatch.setattr(brumby, "prompt_weight", jnp.ones_like)
    else:
        inner = brumby.decode_step
        monkeypatch.setattr(brumby, "decode_step", lambda *a: inner(
            *a[:5], [jnp.zeros_like(z) for z in a[5]], *a[6:]))
    bad, fed, _state = _through_the_arena(lm, params, tok, plen, n_gen, srcs)
    assert _worst(bad, fed, _refs(rc, params, tok, plen, n_gen), plen) \
        > 100 * TOL


def test_an_inactive_slot_keeps_its_pool_and_the_step_writes_no_state(tiny):
    lm, _rc, params = tiny
    tok, plen, _n, _s = _arena_case(lm)
    _got, _fed, state = _through_the_arena(lm, params, tok, plen, 2,
                                           [None, [[0, 0]] * 2])
    cfg = get_config("brumby-tiny", lm=lm, engine_slots=2, beam_size=2)
    sm = BrumbySlotModel(None, cfg, 2, 4, 2 * cfg.tar_len // 4)
    flat = jnp.full((4, cfg.tar_len), 7, jnp.int32)
    pos = jnp.full((2,), 2)
    tab = jnp.arange(8).reshape(2, 4)
    active = jnp.asarray([True, False])
    (_logp,), writes = sm.step(params, state, StepView(
        flat=flat, pos_c=pos, pos_bk=jnp.repeat(pos, 2), active=active,
        tab_step=jnp.where(active[:, None], tab, 8)))
    assert set(writes) == {"kv_pool", "gen_gate", "counters"}
    for name in ("kv_pool", "gen_gate"):
        assert bool(jnp.all(writes[name][:, 4:] == state[name][:, 4:]))
        assert not bool(jnp.all(writes[name][:, :4] == state[name][:, :4]))
    assert (writes["counters"] - state["counters"]).tolist() == [
        2, 2 * 2 * 3]


def test_parameter_tree_is_the_benchmarks_and_bfloat16_from_creation(tiny):
    lm, rc, _params = tiny
    assert brumby.param_shapes(lm) == weights_brumby.param_shapes(rc)
    params = brumby.init_params(lm, 0)
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree_util.tree_leaves(params))
    # the gates' biases: 1 - sigmoid(b) from 1/64 to 1/8,192 over the heads
    full = get_config("brumby-14b-l4").lm
    forget = 1.0 / (1.0 + np.exp(brumby.gate_bias(full.num_key_value_heads)))
    assert np.allclose(forget, 2.0 ** -np.arange(6, 14), rtol=1e-4)
    assert np.array_equal(brumby.gate_bias(8), weights_brumby.gate_bias(8))
    # the published sizes, counted from shapes: 2,877.2 M at four layers,
    # 14.77 B at forty
    def count(lm):
        return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            brumby.param_shapes(lm), is_leaf=lambda s: isinstance(s, tuple)))
    assert count(full) == 2_877_241_376
    assert count(BrumbyConfigAt40()) == 14_769_945_920
    assert full.state_dim == 8256


def BrumbyConfigAt40():
    from fira_tpu.config import BrumbyConfig

    return BrumbyConfig()
