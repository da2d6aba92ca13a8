"""Jitted batched beam search.

The reference's decoder loop (/root/reference/run_model.py:187-380) is pure
Python: per step x per beam it re-runs the full decoder on the padded
prefix, fuses gen+copy probabilities, multiplies by the running beam
probability (PROBABILITIES, not log-probs, :271), appends finished-beam
sentinel probabilities (:281-298), takes one global top-k (:305-310), and
resolves copy ids to source token ids at beam-extension time (:334-337).

This rebuild runs the whole thing as ONE compiled program: beams fold into
the batch dim, `lax.scan` drives the tar_len-1 steps, and an exact top-k
(:func:`top_k`: K reduce passes, never a sort) replaces the reference's
sort. Two accumulation modes:

- compat (default, cfg.beam_compat_prob_space=True): probability-space
  accumulation with the reference's exact candidate construction —
  finished beams contribute a -1-masked distribution PLUS a sentinel entry
  carrying their probability, so selection order is bit-for-bit the
  reference's (needed for +-0.3 BLEU parity, SURVEY.md hard-part 2);
- log-space: the numerically sound default for long targets; identical
  argmax behavior until probabilities underflow.

Semantic note vs the reference: the reference skips a beam only when it is
finished for EVERY batch item (cal_beam, :229-247) and compacts the sentinel
list (:286-296); per item that yields exactly the candidate set built here
(active beams: dist x prob; finished beams: -1-mask + sentinel), so the
fixed-shape formulation selects the same beams without data-dependent
control flow. The reference's early loop exit (:276-279) defaults to
running all steps here — finished beams are fixed points of the update —
and comes back as cfg.beam_early_exit: a `lax.while_loop` that stops one
settling step after every beam finishes, bit-exact vs the full scan (see
:func:`_run_steps`).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fira_tpu.config import FiraConfig
from fira_tpu.data.vocab import EOS_ID, START_ID
from fira_tpu.model.model import FiraModel


def _resolve_copy(tok, diff, sub_token, cfg: FiraConfig):
    """Copy-id -> source token id (run_model.py:334-337), vectorized.

    tok: (B, K) candidate ids over the fused output space;
    diff: (B, sou_len); sub_token: (B, sub_token_len).
    """
    V = cfg.vocab_size
    sub_pos = jnp.clip(tok - V - cfg.sou_len, 0, cfg.sub_token_len - 1)
    diff_pos = jnp.clip(tok - V, 0, cfg.sou_len - 1)
    from_sub = jnp.take_along_axis(sub_token, sub_pos, axis=1)
    from_diff = jnp.take_along_axis(diff, diff_pos, axis=1)
    return jnp.where(
        tok >= V + cfg.sou_len, from_sub,
        jnp.where(tok >= V, from_diff, tok),
    )


def step_valid_mask(flat, s, T: int):
    """Cached-decode per-position validity, shared by the batched beam and
    the slot engine's step program (decode/engine.py): real (nonzero)
    prefix tokens, position 0 (<start>) always attended, causally
    restricted to positions <= ``s``. ``s`` is a traced scalar (batch
    beam: every row at the same depth) or a (B,) vector (engine: each row
    at its slot's own depth) — identical per-row math either way, which is
    one leg of the engine's bit-exactness argument. The same mask guards
    the PAGED cache reads: positions a slot never wrote (stale pool
    blocks included) are exactly -1e9-masked, and exp(-1e9 - m)
    underflows to 0.0 in every stable softmax dtype, so unwritten block
    contents multiply a hard zero — the reason freed blocks are unmapped,
    never zeroed (tests/test_paged_kv.py pins it)."""
    base = (flat != 0).at[:, 0].set(True)
    s = jnp.asarray(s)
    lim = s[:, None] if s.ndim else s
    return base & (jnp.arange(T)[None, :] <= lim)


def top_beam_token(tokens, pos):
    """Top-beam token at per-row position ``pos`` — the emitted token of
    the step that just advanced row b to ``pos[b]`` (selection's top_k
    returns candidates prob-descending, so beam 0 IS the running best
    beam after every step). Shared by the slot engine's verify program
    (decode/spec.py): a drafted token is accepted exactly when it equals
    this value. tokens: (B, K, T); pos: (B,) int32 clamped by the caller
    to a legal column."""
    top = tokens[:, 0, :]
    return jnp.take_along_axis(top, pos[:, None], axis=1)[:, 0]


def scatter_token(flat, pos, tok):
    """Write ``tok[b]`` at row b's own column ``pos[b]`` — the per-row
    vector twin of :func:`_selection_tail`'s top-beam append, shared by
    the spec drafters (decode/spec.py) rolling a single-beam prefix
    forward. flat: (B, T) int32; pos/tok: (B,) int32."""
    return flat.at[jnp.arange(flat.shape[0]), pos].set(tok)


def _init_beam(B: int, cfg: FiraConfig):
    """Initial (tokens, probs, finished) carry + the masked/pad value."""
    K, T = cfg.beam_size, cfg.tar_len
    tokens0 = jnp.zeros((B, K, T), jnp.int32).at[:, :, 0].set(START_ID)
    if cfg.beam_compat_prob_space:
        # beam 0 prob 1, others 0 (run_model.py:216-221)
        probs0 = jnp.tile(jnp.asarray([1.0] + [0.0] * (K - 1), jnp.float32),
                          (B, 1))
        neg = jnp.float32(-1.0)  # reference's masked/-pad value (:273,294)
    else:
        probs0 = jnp.tile(
            jnp.asarray([0.0] + [-np.inf] * (K - 1), jnp.float32), (B, 1)
        )
        neg = jnp.float32(-np.inf)
    finished0 = jnp.zeros((B, K), bool)
    return tokens0, probs0, finished0, neg


def _order_key(bits):
    """A float's bit pattern (as a signed integer of its width) -> the
    integer that ascends exactly as `lax.top_k` ranks floats, on the CPU
    and on the chip: IEEE total order (-NaN < -inf < ... < -0.0 < +0.0 <
    ... < +inf < NaN). An involution: applied to a key it gives the bit
    pattern back."""
    n = bits.dtype.itemsize * 8
    return bits ^ ((bits >> (n - 1)) & jnp.iinfo(bits.dtype).max)


def _better(a, b):
    """(key, index) reduction monoid of :func:`top_k`: the larger key
    wins, equal keys go to the lower index."""
    (ak, ai), (bk, bi) = a, b
    a_wins = (ak > bk) | ((ak == bk) & (ai < bi))
    return jnp.where(a_wins, ak, bk), jnp.where(a_wins, ai, bi)


def top_k(x, k: int):
    """Exact ``jax.lax.top_k(x, k)`` over the last axis without a sort:
    bit for bit the same ``(values, indices)`` — values descending and
    **equal values in ascending index order** (`lax.top_k` is stable),
    which the early-exit fixed point (:func:`_run_steps`) and the
    byte-for-byte decode contracts rest on.

    ``k`` is static and small (the beam size), so selection is k unrolled
    passes, each ONE fused (key, index) max-reduce over ``x``: pass j
    finds the best element ranked strictly after pass j-1's pick in
    (value descending, index ascending) order. What ranks at or before
    that pick is masked inside the pass (nothing is written) to (lowest
    key, width), which loses to every live element, a real -inf included
    (lower index), so rows of ties and rows of -inf come out in
    `lax.top_k`'s order. On the chip `lax.top_k` is a full stable sort of
    every row beside an index tensor of the same size: at the
    vocabulary's width over half of the serve step, where the k passes
    run at the memory's bandwidth (PERF.md §6, PR 27)."""
    width = x.shape[-1]
    if not 0 < k <= width:
        raise ValueError(f"top_k: k={k} outside 1..{width}")
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise TypeError(f"top_k ranks floats by their bits, not {x.dtype}")
    axis = x.ndim - 1
    int_t = jnp.dtype(f"int{x.dtype.itemsize * 8}")
    key = _order_key(jax.lax.bitcast_convert_type(x, int_t))
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    lowest = (jnp.asarray(jnp.iinfo(int_t).min, int_t), jnp.int32(width))
    keys, idxs = [], []
    for j in range(k):
        live = (key, iota)
        if j:
            pk, pi = keys[-1][..., None], idxs[-1][..., None]
            gone = (key > pk) | ((key == pk) & (iota <= pi))
            live = (jnp.where(gone, lowest[0], key),
                    jnp.where(gone, lowest[1], iota))
        pk, pi = jax.lax.reduce(live, lowest, _better, (axis,))
        keys.append(pk)
        idxs.append(pi)
    vals = jax.lax.bitcast_convert_type(
        _order_key(jnp.stack(keys, axis=-1)), x.dtype)
    return vals, jnp.stack(idxs, axis=-1)


def _selection_tail(cand, ids, tokens, probs, finished, s, batch,
                    cfg: FiraConfig, neg):
    """Shared selection tail for :func:`_select` and
    :func:`_select_factored`: mask finished beams, append their sentinel
    entries, one global :func:`top_k` over K*W + K candidates, decode
    sentinels vs real candidates, write the chosen token at position s+1
    (run_model.py:267-310). Equal candidates come out lowest index first
    (:func:`top_k`'s guarantee): a beam's candidates before the next
    beam's, every real candidate before the sentinels, sentinels in beam
    order — the order :func:`_run_steps`' fixed point needs.

    cand: (B, K, W) candidate scores already in the selection space.
    ids: None when W is the fused output space itself (token id = index
    within the beam's W); else a (B, K, W) table of fused-space ids to
    gather the chosen token from (the factored path's per-side top-k
    candidates).

    ``s`` may be a scalar (every row at the same position — the batch beam
    scan) or a (B,) vector (each row at its OWN position — the slot-refill
    engine, decode/engine.py, whose slots hold samples mid-flight at mixed
    depths). The two forms run the identical per-row math: the vector path
    only swaps the shared s+1 column write for a per-row gather/scatter."""
    B, K, W = cand.shape
    cand = jnp.where(finished[:, :, None], neg, cand)
    sentinel = jnp.where(finished, probs, neg)          # (B, K)
    allc = jnp.concatenate([cand.reshape(B, K * W), sentinel], axis=1)
    top_vals, top_idx = top_k(allc, K)                  # (B, K)

    is_sent = top_idx >= K * W
    src_beam = jnp.where(is_sent, top_idx - K * W, top_idx // W)
    if ids is None:
        tok = jnp.where(is_sent, 0, top_idx % W)
    else:
        tok = jnp.take_along_axis(
            ids.reshape(B, K * W), jnp.where(is_sent, 0, top_idx), axis=1)
        tok = jnp.where(is_sent, 0, tok)
    if batch is not None:   # None: no copy side, a candidate is its token
        tok = _resolve_copy(tok, batch["diff"], batch["sub_token"], cfg)

    new_tokens = jnp.take_along_axis(tokens, src_beam[:, :, None], axis=1)
    if jnp.ndim(s) == 0:
        keep = new_tokens[:, :, s + 1]  # finished beams keep their padding
        new_tokens = new_tokens.at[:, :, s + 1].set(
            jnp.where(is_sent, keep, tok)
        )
    else:
        # per-row position: row b writes its own column s[b]+1 (clamped
        # rows — engine slots already done/idle — are blended away by the
        # caller, so their garbage write never lands in live state)
        b_idx = jnp.arange(B)[:, None]
        k_idx = jnp.arange(K)[None, :]
        sp1 = (s + 1)[:, None]
        keep = new_tokens[b_idx, k_idx, sp1]
        new_tokens = new_tokens.at[b_idx, k_idx, sp1].set(
            jnp.where(is_sent, keep, tok)
        )
    new_finished = jnp.where(is_sent, True, tok == EOS_ID)
    return new_tokens, top_vals, new_finished, src_beam


def _select_factored(gen, copy, gate, tokens, probs, finished, s, batch,
                     cfg: FiraConfig, neg):
    """Beam-selection round from the distribution FACTORS.

    gen: (B, K, vocab) generation softmax; copy: (B, K, sou+sub) copy
    softmax; gate: (B, K, 2). The fused distribution is
    [gate0*gen || gate1*copy], so each beam's global top-K lies in the
    union of its per-side top-Ks — selection runs over 2K candidates per
    beam (6 for beam 3) instead of the 25,020-way assembled tensor. Each
    side's K best come from :func:`top_k` (K reduce passes, no sort of
    the vocabulary), equal probabilities lowest index first — real on
    peaked rows, most of which is exactly 0.0. Same candidate math as
    :func:`_select` (prob- or log-space, finished-beam sentinels); only
    tie-breaking among exactly-equal probabilities ACROSS the two sides
    can differ from the fused scan order."""
    B, K, V = gen.shape
    gv, gi = top_k(gen, K)                              # (B, K, K)
    cv, ci = top_k(copy, K)
    side_vals = jnp.concatenate(
        [gv * gate[:, :, 0:1], cv * gate[:, :, 1:2]], axis=-1)  # (B, K, 2K)
    side_ids = jnp.concatenate([gi, ci + V], axis=-1)   # fused-space ids

    if cfg.beam_compat_prob_space:
        cand = side_vals * probs[:, :, None]
    else:
        cand = jnp.log(jnp.clip(side_vals, 1e-10, 1.0)) + probs[:, :, None]
    return _selection_tail(cand, side_ids, tokens, probs, finished, s,
                           batch, cfg, neg)


def _select(dist, tokens, probs, finished, s, batch, cfg: FiraConfig, neg,
            log_input: bool = False):
    """One beam-selection round given this step's fused distribution.

    dist: (B, K, V_out) probability-space distribution at position ``s``.
    Implements the reference's candidate construction exactly: active beams
    contribute dist x prob (prob- or log-space), finished beams are masked
    to ``neg`` and contribute a sentinel entry carrying their own
    probability; one global top-k over K*V_out + K candidates
    (run_model.py:267-310). Returns (new_tokens, new_probs, new_finished,
    src_beam). ``log_input``: ``dist`` already holds log-probabilities (a
    model whose head is one log-softmax, log-space beams only)."""
    if log_input:
        cand = dist + probs[:, :, None]
    elif cfg.beam_compat_prob_space:
        cand = dist * probs[:, :, None]
    else:
        cand = jnp.log(jnp.clip(dist, 1e-10, 1.0)) + probs[:, :, None]
    return _selection_tail(cand, None, tokens, probs, finished, s,
                           batch, cfg, neg)


def _run_steps(step, carry0, T: int, early_exit: bool):
    """Drive the per-position beam step over positions 0..T-2.

    early_exit=False: plain `lax.scan` (always T-1 steps — the parity
    default). early_exit=True: `lax.while_loop` that stops once every beam
    of every item is finished AND one settling step has run after
    saturation. The settling step matters for bit-exactness: the first
    all-finished step re-sorts beams prob-descending via the sentinel
    top-k; after it the state is an element-wise fixed point — every real
    candidate is masked to ``neg``, the K sentinels are already
    descending, and :func:`top_k` returns equal values lowest index
    first, so it picks the sentinels in place, ties included — and
    skipping the remaining steps changes nothing. `finished` is carry[2]
    in both beam variants.

    Returns (final_carry, steps_run) — steps_run is a traced scalar under
    early exit (T-1 exactly otherwise)."""
    if not early_exit:
        carry, _ = jax.lax.scan(step, carry0, jnp.arange(T - 1))
        return carry, jnp.int32(T - 1)

    def cond(state):
        s, settled, carry = state
        return (s < T - 1) & ~(settled & jnp.all(carry[2]))

    def body(state):
        s, settled, carry = state
        new_carry, _ = step(carry, s)
        return s + 1, jnp.all(carry[2]), new_carry

    s, _, carry = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.asarray(False), carry0))
    return carry, s


def beam_search(model: FiraModel, params, batch: Dict[str, jnp.ndarray],
                cfg: FiraConfig, with_steps: bool = False,
                ) -> Tuple[jnp.ndarray, ...]:
    """Returns (tokens (B, beam, tar_len) with copy ids already resolved,
    scores (B, beam)). The best beam is argmax(scores) (run_model.py:351).
    with_steps=True appends the number of decode positions actually run
    (a scalar; < tar_len-1 only under cfg.beam_early_exit).

    Jit this via `make_beam_step` below or wrap in jax.jit at the call site;
    everything inside is fixed-shape.
    """
    K, T, V_out = cfg.beam_size, cfg.tar_len, cfg.output_vocab_size
    B = batch["diff"].shape[0]

    states, mask = model.apply({"params": params}, batch,
                               method=FiraModel.encode)
    # fold beams into batch for the decoder: (B*K, ...)
    states_k = jnp.repeat(states, K, axis=0)
    mask_k = jnp.repeat(mask, K, axis=0)

    tokens0, probs0, finished0, neg = _init_beam(B, cfg)

    def step(carry, s):
        tokens, probs, finished = carry
        flat = tokens.reshape(B * K, T)
        # active prefixes all have length s+1; pad mask = positions <= s for
        # active beams, < own length for finished (their tail is 0-padded, and
        # they are masked out of selection anyway)
        tar_mask = flat != 0
        tar_mask = tar_mask.at[:, 0].set(True)  # position 0 is <start>: always attended
        if cfg.beam_factored_topk:
            gen, copy, gate = model.apply(
                {"params": params}, states_k, mask_k, flat, tar_mask,
                method=FiraModel.dist_parts,
            )
            new_tokens, new_probs, new_finished, _ = _select_factored(
                gen[:, s, :].reshape(B, K, -1),
                copy[:, s, :].reshape(B, K, -1),
                gate[:, s, :].reshape(B, K, 2),
                tokens, probs, finished, s, batch, cfg, neg)
            return (new_tokens, new_probs, new_finished), None
        fused = model.apply(
            {"params": params}, states_k, mask_k, flat, tar_mask,
            method=FiraModel.fused_probs,
        )  # (B*K, T, V_out)
        dist = fused[:, s, :].reshape(B, K, V_out)
        new_tokens, new_probs, new_finished, _ = _select(
            dist, tokens, probs, finished, s, batch, cfg, neg)
        return (new_tokens, new_probs, new_finished), None

    (tokens, probs, _), steps = _run_steps(
        step, (tokens0, probs0, finished0), T, cfg.beam_early_exit)
    return (tokens, probs, steps) if with_steps else (tokens, probs)


def beam_search_cached(model: FiraModel, params, batch: Dict[str, jnp.ndarray],
                       cfg: FiraConfig, with_steps: bool = False,
                       ) -> Tuple[jnp.ndarray, ...]:
    """KV-cached beam search: identical selection semantics to
    :func:`beam_search` (the equivalence is pinned by
    tests/test_train_decode.py), but each scan step decodes ONE position via
    per-layer self-attention caches, with cross-attention K/V and the copy
    head's source projection computed once per batch — O(T) decoder work
    overall instead of the reference's O(T^2) full re-decode per step
    (run_model.py:256; SURVEY.md §7 build-plan 6).

    The cache is beam-gathered with the same src_beam permutation as the
    token prefixes each step, so reshuffled beams keep consistent histories.
    """
    K, T, V_out = cfg.beam_size, cfg.tar_len, cfg.output_vocab_size
    B = batch["diff"].shape[0]
    L, H = cfg.num_layers, cfg.num_head
    d_head = cfg.embedding_dim // H

    states, mask = model.apply({"params": params}, batch,
                               method=FiraModel.encode)
    mask_k = jnp.repeat(mask, K, axis=0)
    # project once per ITEM, then replicate per beam — beams share encoder
    # states, so projecting after the beam fold would do K-fold duplicate
    # matmuls (the raw states themselves are not needed per step at all)
    cross_k, cross_v, src_proj = model.apply(
        {"params": params}, states, method=FiraModel.decode_init)
    cross_k = jnp.repeat(cross_k, K, axis=1)   # (L, B*K, H, S, d_head)
    cross_v = jnp.repeat(cross_v, K, axis=1)
    src_proj = jnp.repeat(src_proj, K, axis=0)

    tokens0, probs0, finished0, neg = _init_beam(B, cfg)
    cache0 = jnp.zeros((L, B * K, H, T, d_head), states.dtype)

    def step(carry, s):
        tokens, probs, finished, k_cache, v_cache = carry
        flat = tokens.reshape(B * K, T)
        # same per-position validity rule as the full-prefix path's pad
        # mask, restricted causally to positions <= s
        valid = step_valid_mask(flat, s, T)
        tok_in = jax.lax.dynamic_slice_in_dim(flat, s, 1, axis=1)  # (B*K, 1)
        if cfg.beam_factored_topk:
            gen, copy, gate, k_cache, v_cache = model.apply(
                {"params": params}, mask_k, tok_in, s,
                k_cache, v_cache, cross_k, cross_v, src_proj,
                valid[:, None, None, :],
                method=FiraModel.dist_parts_step,
            )
            new_tokens, new_probs, new_finished, src_beam = _select_factored(
                gen[:, 0, :].reshape(B, K, -1),
                copy[:, 0, :].reshape(B, K, -1),
                gate[:, 0, :].reshape(B, K, 2),
                tokens, probs, finished, s, batch, cfg, neg)
        else:
            fused, k_cache, v_cache = model.apply(
                {"params": params}, mask_k, tok_in, s,
                k_cache, v_cache, cross_k, cross_v, src_proj,
                valid[:, None, None, :],
                method=FiraModel.fused_probs_step,
            )  # (B*K, 1, V_out)
            dist = fused[:, 0, :].reshape(B, K, V_out)
            new_tokens, new_probs, new_finished, src_beam = _select(
                dist, tokens, probs, finished, s, batch, cfg, neg)
        # permute cached histories to follow their beams: (L, B, K, ...)
        idx = src_beam[None, :, :, None, None, None]

        def gather_cache(c):
            c = c.reshape(L, B, K, H, T, d_head)
            c = jnp.take_along_axis(c, idx, axis=2)
            return c.reshape(L, B * K, H, T, d_head)

        return (new_tokens, new_probs, new_finished,
                gather_cache(k_cache), gather_cache(v_cache)), None

    (tokens, probs, *_), steps = _run_steps(
        step, (tokens0, probs0, finished0, cache0, cache0), T,
        cfg.beam_early_exit)
    return (tokens, probs, steps) if with_steps else (tokens, probs)


def make_beam_search(model: FiraModel, cfg: FiraConfig,
                     with_steps: bool = False):
    """jit-compiled beam search closure over (params, batch); KV-cached by
    default (cfg.beam_kv_cache), full-prefix re-decode otherwise.
    with_steps=True makes the closure return (tokens, probs, steps_run)."""
    impl = beam_search_cached if cfg.beam_kv_cache else beam_search
    return jax.jit(lambda params, batch: impl(model, params, batch, cfg,
                                              with_steps=with_steps))


def eos_biased_params(params, delta: float = 8.0):
    """A paramset whose generation head is biased hard toward EOS, so every
    beam finishes within a few positions. Test/bench utility: saturates the
    beam_early_exit path deterministically (tests/test_beam_early_exit.py
    pins exactness with it; tpu_decode_bench.py uses it for the best-case
    `_saturated` rows). Shared here so the out_fc param path and the bias
    magnitude cannot drift between the two."""
    from fira_tpu.data.vocab import EOS_ID

    bias = np.asarray(params["out_fc"]["bias"]).copy()
    bias[EOS_ID] += delta
    return {**params,
            "out_fc": {**params["out_fc"], "bias": jnp.asarray(bias)}}
