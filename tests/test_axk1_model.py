"""A.X-K1 (fira_tpu/model/axk1.py) against the plain reference
(benchmark/reference_axk1.py) at ``axk1-tiny``: seeded random weights,
log-probabilities and layer outputs, never sampled tokens.

Tolerances. Program and reference both run float32 here, so what separates
them is the order of sums (blocked attention, grouped experts, absorbed
products): a few 1e-6 on log-probabilities of size ~6. The limit is 1e-4 —
twenty times that, and 20,000 times under what the float8 control reads
(over 2.0, asserted below): computing in a lower precision fails it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from axk1_util import ref_cfg, small_query_blocks, weights  # noqa: F401
from benchmark import reference_axk1 as ref
from fira_tpu.config import get_config
from fira_tpu.model import axk1

TOL = 1e-4
F32 = jnp.float32


@pytest.fixture(scope="module")
def tiny():
    lm = get_config("axk1-tiny").lm
    return lm, ref_cfg(lm), weights(lm)


def _tokens(lm, shape, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 4,
                                         lm.vocab_size))


def test_full_forward_pass_matches_the_reference_and_float8_does_not(tiny):
    lm, rc, params = tiny
    tok = _tokens(lm, (2, 32))
    lengths = jnp.asarray([32, 20])
    logp = jax.jit(lambda p, t, n: axk1.forward_logp(p, lm, t, n, F32))(
        params, tok, lengths)
    r0 = ref.forward(rc, params, tok[0])
    r1 = ref.forward(rc, params, tok[1, :20])
    assert float(jnp.abs(logp[0] - r0).max()) < TOL
    # the padded tail of the second prompt moves nothing before it
    assert float(jnp.abs(logp[1, :20] - r1).max()) < TOL
    low = ref.forward(rc, params, tok[0], "fp8")
    assert float(jnp.abs(low - r0).max()) > 1000 * TOL


def test_prefill_then_every_decode_position_through_the_latent_cache(tiny):
    """Prefill a prompt, then teacher-force the rest one position at a time
    through the paged latent pool: every position's log-probabilities are
    the reference's full forward pass's, for both beams of both slots."""
    lm, rc, params = tiny
    S, K, T, BS, L = 2, 2, 16, 4, lm.num_hidden_layers
    tok = _tokens(lm, (S, 32))
    plen = np.asarray([16, 12])
    lat, _ = jax.jit(lambda p, t, n: axk1.prefill(p, lm, t, n, F32))(
        params, tok[:, :16], jnp.asarray(plen))
    prompt_lat = jnp.zeros((L, S, 64, lm.latent_dim)).at[:, :, :16].set(lat)
    pool = jnp.zeros((L, S * T // BS, K, BS, lm.latent_dim))
    tab = jnp.arange(S * T // BS).reshape(S, T // BS)
    step = jax.jit(lambda p, tk, g, pool, lat, n, tab: axk1.decode_step(
        p, lm, tk, g, lat, n, pool, tab, jnp.ones((S,), bool), F32))
    refs = [ref.forward(rc, params, tok[s, :plen[s] + 10]) for s in range(S)]
    for g in range(10):
        tk = np.stack([[tok[s, plen[s] + g]] * K for s in range(S)])
        logp, pool, _c = step(params, jnp.asarray(tk), jnp.full((S,), g),
                              pool, prompt_lat, jnp.asarray(plen), tab)
        for s in range(S):
            for k in range(K):
                gap = jnp.abs(logp[s, k] - refs[s][plen[s] + g]).max()
                assert float(gap) < TOL, (g, s, k)


def test_absorbed_attention_equals_materialised(tiny):
    """The two MLA paths on the same sequence: the absorbed form's output
    at the last position (scored against the latents themselves) is the
    materialised form's last row. Float32 both, only the order of the
    products differs: 1e-5 on outputs of size ~1."""
    lm, _rc, params = tiny
    p = params["layers"][1]
    P = 32
    x = jax.random.normal(jax.random.PRNGKey(7), (1, P, lm.hidden_size))
    cos, sin = axk1.rope_cos_sin(lm, jnp.arange(P))
    full, lat = axk1.mla_prefill(p, x, cos, sin, lm, F32)
    # decode the last position: the prompt is the first P-1 latents
    prompt = jnp.zeros((1, 64, lm.latent_dim)).at[:, :P - 1].set(lat[:, :P - 1])
    gen = jnp.zeros((1, 1, 4, lm.latent_dim)).at[:, :, 0].set(lat[:, P - 1])
    seen = jnp.asarray([[True, False, False, False]])
    out = axk1.mla_decode(p, x[:, P - 1:], cos[P - 1:][None], sin[P - 1:][None],
                          prompt, jnp.asarray([P - 1]), gen, seen, lm, F32)
    assert float(jnp.abs(out[0, 0] - full[0, P - 1]).max()) < 1e-5


def test_router_on_a_hand_made_score_table_ties_included(tiny):
    """4 groups of 4, keep 2 groups (by the sum of a group's two best),
    top-4 inside them; equal scores go to the lower index — groups and
    experts alike — in the program and in the reference."""
    lm, rc, _params = tiny
    s = np.full((3, 16), 0.1, np.float32)
    # row 0: groups 1 and 3 win (0.9 + 0.8, 0.7 + 0.7); inside them the four
    # best are 4, 5, 12, 13 — 13 ties 14 and wins by index
    s[0, [4, 5]] = [0.9, 0.8]
    s[0, [12, 13, 14]] = [0.7, 0.7, 0.7]
    # row 1: all equal: groups 0 and 1 by index, experts 0..3
    # row 2: group 2's lone 0.95 (0.95 + 0.1) loses to groups 0 and 3 at
    # 0.6 + 0.6: the best single expert is NOT chosen
    s[2, 8] = 0.95
    s[2, [0, 1, 14, 15]] = 0.6
    ids, w = axk1.route(jnp.asarray(s), lm)
    assert sorted(ids[0].tolist()) == [4, 5, 12, 13]
    assert sorted(ids[1].tolist()) == [0, 1, 2, 3]
    assert sorted(ids[2].tolist()) == [0, 1, 14, 15]
    # weights: s / sum of the chosen s, times routed_scaling_factor
    np.testing.assert_allclose(float(w[1].sum()), lm.routed_scaling_factor,
                               rtol=1e-6)
    order = np.argsort(np.asarray(ids[0]))
    np.testing.assert_allclose(
        np.asarray(w[0])[order],
        lm.routed_scaling_factor * np.asarray([0.9, 0.8, 0.7, 0.7]) / 3.1,
        rtol=1e-6)
    chosen, rw = ref.route(jnp.asarray(s), rc)
    for row in range(3):
        assert sorted(np.nonzero(np.asarray(chosen[row]))[0].tolist()) \
            == sorted(ids[row].tolist())
        np.testing.assert_allclose(
            np.asarray(rw[row])[np.asarray(ids[row])], np.asarray(w[row]),
            rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """THE SHARE TEST. 16 routed experts cut into 4 shares of 4: each share
    routes over all 16, computes its own experts' part plus the shared
    expert; the four routed parts plus the shared expert counted ONCE equal
    the uncut reference's layer output."""
    lm, rc, params = tiny
    p = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(11), (40, lm.hidden_size))
    want = ref.expert_layer(p, x, rc, "f32")
    shared = axk1.swiglu(x, p["shared_gate"], p["shared_up"],
                         p["shared_down"], F32)
    total, held = shared, 0
    for i in range(4):
        lm_i = dataclasses.replace(lm, experts_held=4, expert_offset=4 * i)
        p_i = dict(p, **{k: p[k][4 * i:4 * i + 4] for k in
                         ("experts_gate", "experts_up", "experts_down")})
        out, counters = axk1.moe_layer(p_i, x, jnp.ones((40,), bool), lm_i,
                                       F32)
        # the share alone is what the reference gives for the same share
        rc_i = dict(rc, n_routed_experts=4, expert_offset=4 * i)
        assert float(jnp.abs(out - ref.expert_layer(p_i, x, rc_i, "f32")
                             ).max()) < TOL
        total = total + (out - shared)
        held += int(counters[1])
        assert int(counters[0]) == 40 * lm.num_experts_per_tok
    assert held == 40 * lm.num_experts_per_tok     # every assignment, once
    assert float(jnp.abs(total - want).max()) < TOL


@pytest.mark.parametrize("picks", [[0, 1, 2, 3], [0, 5, 9, 13]],
                         ids=["all-held-two-passes", "one-expert-takes-all"])
def test_no_token_is_dropped_whatever_the_imbalance(tiny, picks,
                                                    monkeypatch):
    """Every token picks the same experts, under every tiling of the
    grouped product. With 4 of 16 held, 64 tokens expect 32 rows a held
    expert: expert-major, a capacity of 80 rows an expert, one pass even
    for an expert that takes every token. With a prefill's group sizes
    (forced here) the loads choose: passes of 32 rows of every held expert
    where those compute no more rows than the row-major passes (all four
    held experts at 64 rows: two passes of 128 rows either way), else
    row-major (one expert at 64: one pass of 128 rows against two of four
    experts); and row-major forced, a pass holds 128 rows; 64 tokens x 4
    held picks are 256 assignments: a second pass. Nothing is dropped
    either way. With one held expert taking every token, its load is the
    whole batch."""
    lm, _rc, params = tiny
    lm4 = dataclasses.replace(lm, experts_held=4, expert_offset=0)
    p = {k: v[:4] if k.startswith("experts_") else v
         for k, v in params["layers"][1].items()}
    N = 64
    assert axk1.expert_chunk_rows(lm4, N) == 128
    x = jax.random.normal(jax.random.PRNGKey(5), (N, lm.hidden_size))
    ids = jnp.tile(jnp.asarray(picks, jnp.int32), (N, 1))
    w = jax.random.uniform(jax.random.PRNGKey(6), (N, 4)) + 0.5
    assert axk1.expert_capacity(lm4, N) == 80
    want = 0.0
    for j, e in enumerate(picks):
        if e < 4:
            want = want + w[:, j, None] * axk1.swiglu(
                x, p["experts_gate"][e], p["experts_up"][e],
                p["experts_down"][e], F32)
    assert axk1.prefill_capacity(lm4, N) == 32
    engages = axk1.expert_major_engages
    for rows_max, rule in ((axk1.EXPERT_MAJOR_ROWS, engages), (0, engages),
                           (0, lambda *a: False)):
        monkeypatch.setattr(axk1, "EXPERT_MAJOR_ROWS", rows_max)
        monkeypatch.setattr(axk1, "expert_major_engages", rule)
        jax.clear_caches()      # a prefill's products are traced once a shape
        out, loads = axk1.routed_experts(p, x, ids, w, jnp.ones((N,), bool),
                                         lm4, F32)
        assert loads.tolist() == [N if e in picks else 0 for e in range(4)]
        assert float(jnp.abs(out - want).max()) < TOL
    jax.clear_caches()
    assert bool(engages(lm4, N, loads)) == (picks == [0, 1, 2, 3])
    # padding takes no expert's time: invalid tokens are not routed
    half = jnp.arange(N) < N // 2
    out2, loads2 = axk1.routed_experts(p, x, ids, w, half, lm4, F32)
    assert int(loads2.sum()) == int(loads.sum()) // 2
    assert float(jnp.abs(out2[N // 2:]).max()) == 0.0


def test_parameter_tree_is_the_benchmarks_and_bfloat16_from_creation(tiny):
    lm, rc, params = tiny
    from benchmark import weights_axk1

    assert axk1.param_shapes(lm) == weights_axk1.param_shapes(rc)
    own = axk1.init_params(lm, 0)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree_util.tree_leaves(own))
    assert jax.tree_util.tree_map(lambda x: tuple(x.shape), own) \
        == axk1.param_shapes(lm)
