"""The grouped expert products' two tilings (model/axk1.routed_experts,
shared by model/afmoe.py and model/lfm2.py): EXPERT-MAJOR where a pass
expects few rows an expert (every decode position: the shape's choice,
``expert_capacity``); where it expects many (every prefill) a ``lax.cond``
on the loads — expert-major in passes of ``prefill_capacity`` where that
computes no more rows than the ROW-MAJOR passes through
``jax.lax.ragged_dot`` would (``expert_major_engages``), else row-major.
Never an option.

At the three expert cells' own key blocks — LFM2's top-4 of 32 all held,
Trinity-Mini's top-8 of 128, A.X-K1's 12 held of 192 from an
``expert_offset`` — at their decode rows (192, 144, 192) and at a prefill
size (2,048, 4,096, 4,096 rows), at a tiny width: each form against a plain
per-expert float32 loop and against the other on the same inputs; one
expert taking every row, experts with none, padding rows, loads over the
capacity (a second pass), an even prefill load, one expert at 4.3 x the
mean, a load exactly at the rows-computed boundary. The counter
``moe_rows_expert_major``: every held assignment of a decode position, and
of a prefill call where the expert-major branch ran, none where the
row-major one did.

Tolerances: in float32 the forms differ from the loop only in the order of
sums (a few 1e-7 on outputs of size ~1): 1e-5. In bfloat16 the two forms
round the same products at the same points (gate and up in bfloat16, down
in float32, float32 accumulation), so they agree to float32's sums: 1e-5
relative to the largest output."""

import dataclasses
import math

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

@dataclasses.dataclass(frozen=True)
class Block:
    """The key block routed_experts reads (hashable, as the models' own)."""
    num_experts_per_tok: int
    experts_held: int
    n_routed_experts: int
    expert_offset: int


# (key block, decode rows) of the three cells that run routed_experts
BLOCKS = {
    "lfm2-top4-of-32": (Block(
        num_experts_per_tok=4, experts_held=32, n_routed_experts=32,
        expert_offset=0), 192),
    "afmoe-top8-of-128": (Block(
        num_experts_per_tok=8, experts_held=128, n_routed_experts=128,
        expert_offset=0), 144),
    "axk1-12-held-of-192": (Block(
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
    sc = (jnp.repeat(jax.random.normal(k1, (-(-N // 3),
                                            lm.n_routed_experts)), 3, 0)[:N]
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


def _patched_run(monkeypatch, patches, *args, **kw):
    """``_run`` with ``(object, name, value)`` patches in place. A
    prefill's grouped products are traced once a shape (and kept: axk1's
    ``_prefill_grouped``), so JAX's caches are cleared on both sides: the
    call traces the patched code, and no later call reuses it."""
    jax.clear_caches()
    try:
        with monkeypatch.context() as m:
            for obj, name, value in patches:
                m.setattr(obj, name, value)
            return _run(*args, **kw)
    finally:
        jax.clear_caches()


def _row_major(monkeypatch, *args, **kw):
    """The same call with the expert-major form ruled out, as a prefill's
    group sizes and a skewed load rule it out."""
    return _patched_run(monkeypatch, [
        (axk1, "EXPERT_MAJOR_ROWS", 0),
        (axk1, "expert_major_engages", lambda *a: False)], *args, **kw)


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


@pytest.mark.parametrize("cell,decode,prefill,capacity,prefill_c", [
    ("lfm2-8b-a1b-l12", 192, 16384, 64, 1024),
    ("trinity-mini-l5", 144, 16384, 32, 256),
    ("axk1-ep16", 192, 8192, 32, 416),
])
def test_the_shape_rule_engages_at_decode_and_keeps_ragged_dot_in_prefill(
        cell, decode, prefill, capacity, prefill_c):
    """The cells' decode rows expect 24 | 9 | ~11 rows a held expert, their
    prefill dispatches (always the cell's whole token budget) 1,024 | 256 |
    ~427: the first are expert-major by shape with 2.5 x the expectation,
    in tiles of 16 rows, as the capacity; the second keep ``ragged_dot``
    as the branch their loads fall back to, beside expert-major passes of
    the row-major pass's rows shared evenly, in whole tiles of 16 rows
    rounded down: one pass of every expert computes no more rows than one
    row-major pass."""
    lm = get_config(cell).lm
    per = axk1.expert_chunk_rows(lm, decode) / lm.experts_held
    assert per <= axk1.EXPERT_MAJOR_ROWS
    assert axk1.expert_capacity(lm, decode) == capacity \
        == 16 * math.ceil(2.5 * per / 16)
    M = axk1.expert_chunk_rows(lm, prefill)
    assert M / lm.experts_held > axk1.EXPERT_MAJOR_ROWS
    assert axk1.expert_capacity(lm, prefill) == 0
    assert axk1.prefill_capacity(lm, prefill) == prefill_c
    assert prefill_c % 16 == 0
    assert lm.experts_held * prefill_c <= M \
        < lm.experts_held * (prefill_c + 16)


@pytest.mark.parametrize("block", list(BLOCKS))
def test_the_counter_is_each_experts_first_capacity_of_rows(block):
    """A decode position counts each expert's first C rows; a prefill-size
    call counts every held assignment where the expert-major branch runs
    (these few rows an expert: it does) and none where the row-major one
    does (one expert holding every row: a pass of every expert a C)."""
    lm, N = BLOCKS[block]
    C = axk1.expert_capacity(lm, N)
    loads = jnp.asarray([0, 1, C, C + 5] + [2] * (lm.experts_held - 4))
    valid = jnp.ones((N,), bool)
    c = dict(zip(axk1.COUNTERS, axk1.moe_counters(lm, valid, loads).tolist()))
    assert c["moe_rows_expert_major"] == int(loads.sum()) - 5
    assert c["moe_assignments_held"] == int(loads.sum())
    assert c["moe_held_load_max"] == C + 5
    # a prefill's many rows an expert: all of them, or none
    big = jnp.ones((16384,), bool)
    assert axk1.expert_capacity(lm, 16384) == 0
    c = dict(zip(axk1.COUNTERS, axk1.moe_counters(lm, big, loads).tolist()))
    assert c["moe_rows_expert_major"] == int(loads.sum())
    one = loads.at[0].set(16384 * min(lm.num_experts_per_tok,
                                      lm.experts_held) // 2)
    c = dict(zip(axk1.COUNTERS, axk1.moe_counters(lm, big, one).tolist()))
    assert c["moe_assignments_held"] == int(one.sum())
    assert c["moe_rows_expert_major"] == 0


def test_a_decode_step_counts_every_held_assignment_and_a_prefill_none():
    """lfm2-tiny through its own programs: a decode position of 2 slots x 3
    beams (6 rows, capacity 8: no expert can spill) computes every held
    assignment expert-major; a prefill of 8 x 128 whole prompts expects
    256 rows a held expert, a row-major pass holds every assignment and an
    expert-major pass would have to hold the busiest expert's, over the
    mean: it computes none so (the padded prefills' case is
    test_lfm2_tiny_prefill_counts_the_expert_major_branch)."""
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


# --- prefill-size calls: the loads choose the tiling ------------------------

# rows of a prefill-size call at each key block: over EXPERT_MAJOR_ROWS
# expected rows a held expert (256 | 256 | ~213)
PREFILL = {"lfm2-top4-of-32": 2048, "afmoe-top8-of-128": 4096,
           "axk1-12-held-of-192": 4096}


def _held_loads(lm, ids, valid):
    local = np.asarray(ids) - lm.expert_offset
    held = (np.asarray(valid)[:, None] & (local >= 0)
            & (local < lm.experts_held))
    return np.bincount(local[held], minlength=lm.experts_held)


def _engages(lm, N, loads) -> bool:
    """The rule in plain integers: expert-major passes of C compute no
    more rows than the row-major passes of M."""
    C, M = axk1.prefill_capacity(lm, N), axk1.expert_chunk_rows(lm, N)
    loads = np.asarray(loads)
    return (-(-int(loads.max()) // C) * lm.experts_held * C
            <= -(-int(loads.sum()) // M) * M)


def _poisoned(monkeypatch, form, *args, **kw):
    """The call with the OTHER form's products made NaN: a finite output
    says ``form`` ran (both branches of the cond are traced; one runs)."""
    if form == "expert-major":
        obj, name = jax.lax, "ragged_dot"
    else:
        obj, name = jnp, "einsum"
    real = getattr(obj, name)
    return _patched_run(monkeypatch, [
        (obj, name, lambda *a, **k: real(*a, **k) * jnp.nan)], *args, **kw)


def _counted(lm, valid, loads):
    return dict(zip(axk1.COUNTERS, axk1.moe_counters(
        lm, jnp.asarray(valid), jnp.asarray(loads)).tolist()))


def _exact(lm, N, per):
    """ids (N, k) and valid (N,): every held expert picked by exactly
    ``per`` rows — assignment t to held expert t mod E, row t // k, so no
    row picks one twice — and the rows after them padding whose picks (the
    first held expert, k times) would load that expert if they counted."""
    k, E, off = lm.num_experts_per_tok, lm.experts_held, lm.expert_offset
    assert E * per % k == 0
    rows = E * per // k
    t = np.arange(N * k).reshape(N, k)
    ids = np.where(t < E * per, off + t % E, off).astype(np.int32)
    return jnp.asarray(ids), jnp.arange(N) < rows


@pytest.mark.parametrize("block", list(BLOCKS))
def test_an_even_prefill_load_runs_expert_major_and_equals_the_plain_loop(
        block, monkeypatch):
    """Random routing over the router's width with the last 40 % of rows
    padding: the busiest expert sits under one prefill capacity while the
    row-major pass is 40 % empty or more, so the expert-major branch runs
    (the row-major products poisoned, the output is finite) and equals the
    plain float32 loop."""
    lm, _ = BLOCKS[block]
    N = PREFILL[block]
    assert axk1.expert_capacity(lm, N) == 0
    p = _experts(lm.experts_held, seed=8)
    x, ids, w = _routing(lm, N, seed=9)
    valid = jnp.arange(N) < (N * 3) // 5
    loads = _held_loads(lm, ids, valid)
    assert _engages(lm, N, loads)
    out, got = _poisoned(monkeypatch, "expert-major", p, x, ids, w, valid, lm)
    assert got.tolist() == loads.tolist()
    assert _gap(out, _plain(p, x, ids, w, valid, lm)) < TOL
    c = _counted(lm, valid, loads)
    assert c["moe_rows_expert_major"] == c["moe_assignments_held"] \
        == int(loads.sum())


@pytest.mark.parametrize("block", list(BLOCKS))
def test_one_expert_at_four_times_the_mean_runs_row_major(block,
                                                         monkeypatch):
    """Trinity-Mini's prefill skew: the first held expert is the first pick
    of 3 rows in 5 (over 4.3 x the mean), so expert-major passes
    would cost a pass of EVERY expert for each capacity of its rows: the
    row-major branch runs (the expert-major products poisoned, the output
    is finite), with the same outputs as the plain loop and as the
    expert-major form on the same inputs."""
    lm, _ = BLOCKS[block]
    N = PREFILL[block]
    off, E = lm.expert_offset, lm.experts_held
    p = _experts(E, seed=10)
    x, ids, w = _routing(lm, N, seed=11)
    busy = jnp.arange(N) % 5 < 3
    rest = jnp.where(ids[:, 1:] == off, off + 1, ids[:, 1:])
    ids = jnp.concatenate([jnp.where(busy, off, ids[:, 0])[:, None], rest],
                          1).astype(jnp.int32)
    valid = jnp.ones((N,), bool)
    loads = _held_loads(lm, ids, valid)
    assert loads.max() >= 4.3 * loads.mean()
    assert not _engages(lm, N, loads)
    out, got = _poisoned(monkeypatch, "row-major", p, x, ids, w, valid, lm)
    assert got.tolist() == loads.tolist()
    assert _gap(out, _plain(p, x, ids, w, valid, lm)) < TOL
    out_em, _ = _patched_run(monkeypatch, [
        (axk1, "expert_major_engages", lambda *a: True)],
        p, x, ids, w, valid, lm)
    assert _gap(out, out_em) < TOL
    assert _counted(lm, valid, loads)["moe_rows_expert_major"] == 0


@pytest.mark.parametrize("block", list(BLOCKS))
@pytest.mark.parametrize("over", [0, 1], ids=["at", "one-over"])
def test_a_load_at_the_rows_computed_boundary(block, over, monkeypatch):
    """Every held expert exactly one prefill capacity C of rows: one
    expert-major pass computes E C rows, as many as the row-major pass
    (LFM2's and Trinity-Mini's blocks, where E C = M) or fewer (A.X-K1's,
    where C is rounded down): expert-major. Move one row to another expert
    and that expert holds C + 1: a second pass of every expert, over the
    row-major pass — row-major. Either way the plain loop's outputs, and
    the counter names the branch that ran."""
    lm, _ = BLOCKS[block]
    N = PREFILL[block]
    C, M = axk1.prefill_capacity(lm, N), axk1.expert_chunk_rows(lm, N)
    E, k = lm.experts_held, lm.num_experts_per_tok
    ids, valid = _exact(lm, N, C)
    if over:
        # row 0 holds held experts 0 .. k-1: its first pick to expert k
        ids = ids.at[0, 0].set(lm.expert_offset + k % E)
    loads = _held_loads(lm, ids, valid)
    assert loads.max() == C + over and loads.sum() == E * C <= M
    assert _engages(lm, N, loads) == (not over)
    p = _experts(E, seed=12)
    x, _ids, w = _routing(lm, N, seed=13)
    form = "row-major" if over else "expert-major"
    out, got = _poisoned(monkeypatch, form, p, x, ids, w, valid, lm)
    assert got.tolist() == loads.tolist()
    assert _gap(out, _plain(p, x, ids, w, valid, lm)) < TOL
    c = _counted(lm, valid, loads)
    assert c["moe_rows_expert_major"] == (0 if over else E * C)


@pytest.mark.parametrize("block", list(BLOCKS))
def test_prefill_padding_rows_take_no_experts_time(block, monkeypatch):
    """The boundary load of the test above with every padding row picking
    the first held expert k times: counted, they would load it far past
    one capacity and send the call row-major. They are not: the loads are
    the real rows' alone, the expert-major branch runs, and padding rows
    get no output."""
    lm, _ = BLOCKS[block]
    N = PREFILL[block]
    C = axk1.prefill_capacity(lm, N)
    ids, valid = _exact(lm, N, C)
    pad = ~np.asarray(valid)
    loads = _held_loads(lm, ids, valid)
    assert loads.tolist() == [C] * lm.experts_held
    assert _engages(lm, N, loads)
    p = _experts(lm.experts_held, seed=14)
    x, _ids, w = _routing(lm, N, seed=15)
    out, got = _poisoned(monkeypatch, "expert-major", p, x, ids, w, valid, lm)
    assert got.tolist() == loads.tolist()
    assert _gap(out, _plain(p, x, ids, w, valid, lm)) < TOL
    if pad.any():
        assert float(jnp.abs(out[pad]).max()) == 0.0
    else:
        # every row is real at this block: half of them padding instead
        half = jnp.arange(N) < N // 2
        out2, got2 = _run(p, x, ids, w, half, lm)
        assert got2.tolist() == _held_loads(lm, ids, half).tolist()
        assert float(jnp.abs(out2[N // 2:]).max()) == 0.0
        assert _gap(out2, _plain(p, x, ids, w, half, lm)) < TOL


def test_lfm2_tiny_prefill_counts_the_expert_major_branch():
    """lfm2-tiny's own prefill over 8 prompts of 64 real tokens in a bucket
    of 128: half of a row-major pass is padding, the busiest expert of each
    layer under one capacity — every held assignment is computed
    expert-major and counted so; the same prompts whole count none."""
    lm = get_config("lfm2-tiny").lm
    params = lfm2_weights(lm)
    B, P = 8, 128
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, P), 4, lm.vocab_size)
    run = jax.jit(lambda p, t, n: lfm2.prefill(p, lm, t, n, F32)[2])
    c = dict(zip(lfm2.COUNTERS, run(params, tok, jnp.full((B,), P // 2,
                                                         jnp.int32)).tolist()))
    n_moe = lm.num_hidden_layers - lm.num_dense_layers
    assert c["moe_assignments_held"] == B * P // 2 * lm.num_experts_per_tok \
        * n_moe
    assert c["moe_rows_expert_major"] == c["moe_assignments_held"]
    c = dict(zip(lfm2.COUNTERS, run(params, tok, jnp.full((B,), P,
                                                         jnp.int32)).tolist()))
    assert c["moe_rows_expert_major"] == 0
