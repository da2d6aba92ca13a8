"""Speculative copy-head draft-and-verify decode for the slot engine.

The slot engine (decode/engine.py) dispatches one step program per emitted
beam position per round: with ingest unthrottled and the fleet self-healing,
that dispatch cadence IS the serving ceiling. FIRA's dual copy mechanism
makes commit-message tokens unusually draftable — a large fraction is copied
verbatim from the diff — so a near-free DRAFTER proposes ``k`` tokens per
live slot and ONE fixed-shape VERIFY program advances up to k beam positions
per dispatch, accepting the longest drafted prefix that the real beam math
agrees with (Leviathan et al., ICML 2023; Chen et al. 2023 — PAPERS.md
"Speculative decoding").

Exactness is BY CONSTRUCTION, not by comparison tolerance. The verify
program is a ``lax.while_loop`` whose body is the engine's own
``_one_step`` — the identical per-position HLO the plain step runs — gated
per row: frame 0 advances every live slot unconditionally (progress >= 1,
exactly the plain step), frame j+1 advances only rows whose frame-j emitted
top-beam token (beam.top_beam_token; selection's top_k is prob-descending,
so beam 0 is the running best) equalled ``drafts[:, j]``. The loop exits
early once no gated row remains (the engine twin of beam._run_steps's
early-exit predicate). Every position the verify advances therefore ran the
exact step math the plain engine would have run, and every position it did
NOT advance is simply run later by a subsequent dispatch — so tokens, probs,
and file bytes are invariant to ``k``, the acceptance pattern, the harvest
cadence, and the replica count (tests/test_spec.py pins all of it).
"Rollback" of rejected tails is free: a frozen row's state — its ancestry
rows among it — is blended to its old values (the plain step's own
inactive-row discipline) and its block table is sentinel-masked (no
append), so the row RESUMES with its history intact.

Drafter tiers (cfg.spec_decode):

- ``copy``: the copy-head distribution ALONE — pointer scores from the
  cached source projections (state["src_proj"], computed once at prefill
  and held a row a slot) against the raw target embedding proxy
  (model.copy_draft_scores: embed + position row, NO decoder layer).
  Near-free: k tiny matvec/tanh passes per dispatch. Rides FIRA's measured
  verbatim-copy fraction.
- ``draft``: a greedy argmax roll of the existing cached step program on
  each slot's TOP BEAM only — 1/beam of the step's decoder rows, against
  a dense scratch view of beam 0's history (layers.gather_block_kv_beam,
  lane by lane through the engine's ancestry table; the real pool is
  never written by a drafter), stepped by the dense per-row
  ``FiraModel.fused_probs_step_multi``. Costlier, higher acceptance on
  generated spans.

Both tiers emit RESOLVED vocab ids (beam._resolve_copy — the same id space
the beam stores at extension time), so drafted-vs-emitted comparison is a
plain int equality. Draft quality moves only the acceptance rate, never
output bytes.

Program family: ``engine_draft[k<k>...]`` + ``engine_verify[k<k>...]``, one
fixed-(S, k) member each, declared in the compile-guard family next to the
step/insert/harvest programs (replica tags compose: ``engine_verify[k4.r1]``)
— zero post-warmup retraces with spec armed.

Low-precision serving tiers (decode/quant.py) compose with NO code here:
the drafter's scratch caches come off the pool through
gather_block_kv_beam, which upcasts at the gather, and the engine
wraps the drafter so the int8w weight tier dequantizes at the draft trace
top exactly like the step/verify programs. Draft math under a tier is
acceptance-only — the verify body is still the engine's own step program on
the engine's own params, so the within-tier exactness argument above is
unchanged: accepted prefixes are bit-identical to that tier's plain decode
(labels carry the tier suffix, e.g. ``engine_verify[k4.bf16kv.int8w.r1]``).
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from fira_tpu.config import FiraConfig
from fira_tpu.decode import paging
from fira_tpu.decode.beam import (_resolve_copy, scatter_token,
                                  step_valid_mask, top_beam_token)
from fira_tpu.model.layers import gather_block_kv_beam
from fira_tpu.model.model import FiraModel

DRAFT_LABEL = "engine_draft"
VERIFY_LABEL = "engine_verify"

SPEC_TIERS = ("off", "copy", "draft")

# plain step dispatches run after a verify whose drafts ALL missed, before
# re-arming: a stalled drafter (e.g. mid rare-token span) should not pay a
# draft+verify dispatch per emitted token. Scheduling only — output bytes
# are invariant to the cooldown by the exactness argument above.
STALL_COOLDOWN = 4


def spec_errors(cfg: FiraConfig) -> List[str]:
    """Parse-time validation for the speculative-decode knobs (the
    paging.paging_errors convention: named-knob messages, CLI exit 2).

    - ``spec_decode`` must be one of {off, copy, draft};
    - spec requires ``decode_engine`` (the drafter/verify programs are
      members of the slot engine's program family — there is nothing to
      arm on the batched-beam path);
    - ``engine_spec_k`` must fit the smallest declared decode tar budget:
      1 <= k <= min(tar) - 1 (a verify window past the budget could never
      accept its tail — the <start> column is not generated).
    """
    errs: List[str] = []
    if cfg.spec_decode not in SPEC_TIERS:
        errs.append(
            f"spec_decode {cfg.spec_decode!r} not in {set(SPEC_TIERS)}")
        return errs
    if cfg.spec_decode == "off":
        return errs
    if not cfg.decode_engine:
        errs.append(
            f"spec_decode={cfg.spec_decode!r} requires decode_engine: the "
            f"drafter/verify programs extend the slot engine's program "
            f"family (enable decode_engine or set spec_decode='off')")
    k = int(cfg.engine_spec_k)
    budget = min(paging.declared_decode_tars(cfg)) - 1
    if not 1 <= k <= budget:
        errs.append(
            f"engine_spec_k {k} outside [1, {budget}]: the verify window "
            f"must fit the smallest declared decode tar budget "
            f"({budget + 1} positions, decode_tar_buckets/tar_len) minus "
            f"the <start> column")
    return errs


def copy_biased_params(params, delta: float = 6.0,
                       target_blind: bool = False):
    """A paramset whose gen/copy gate leans hard toward the COPY side, so
    decode emits mostly copied source tokens — the regime the ``copy``
    drafter exists for. Test/bench utility (the beam.eos_biased_params
    convention; shared here so the copy_net param paths cannot drift
    between the spec tests and the bench legs).

    ``target_blind=True`` additionally zeroes the copy head's target
    projection, making pointer scores a pure function of the cached source
    projection: the drafter's raw-embedding proxy then scores EXACTLY what
    the real step scores, so copy-tier acceptance saturates — the
    deterministic best case the acceptance-sweep tests pin. (Exactness of
    the OUTPUT never depends on any of this — only the acceptance rate
    moves.)"""
    import numpy as np

    cn = params["copy_net"]
    bias = np.asarray(cn["gate"]["bias"]).copy()
    bias[0] -= delta
    bias[1] += delta
    new_cn = {**cn, "gate": {**cn["gate"], "bias": jnp.asarray(bias)}}
    if target_blind:
        new_cn["tgt_proj"] = {
            **cn["tgt_proj"],
            "kernel": jnp.zeros_like(cn["tgt_proj"]["kernel"])}
    return {**params, "copy_net": new_cn}


def make_drafter(model: FiraModel, cfg: FiraConfig, slots: int):
    """Build the (params, state) -> (S, k) int32 drafter for this engine's
    tier/geometry. Pure function of the engine state — drafters never write
    real state (the scratch caches of the ``draft`` tier live and die in
    the scan carry), so the engine jits the result WITHOUT donation and the
    verify that follows donates the untouched arena as usual."""
    T = cfg.tar_len
    L = cfg.num_layers
    V = cfg.vocab_size
    k = int(cfg.engine_spec_k)
    tier = cfg.spec_decode

    def resolve(choice, state):
        """Fused-space choice -> resolved vocab id, the id space the beam
        stores (beam._resolve_copy over this slot arena's sources)."""
        return _resolve_copy(choice[:, None], state["diff"],
                             state["sub_token"], cfg)[:, 0]

    def roll(state, body):
        """Drive one drafter micro-step k times from each slot's top-beam
        token at its current depth; stack proposals to (S, k)."""
        pos0 = jnp.minimum(state["pos"], T - 2)
        flat0 = state["tokens"][:, 0, :]            # (S, T) resolved ids
        tok0 = jnp.take_along_axis(flat0, pos0[:, None], axis=1)[:, 0]
        return body(flat0, tok0, pos0)

    if tier == "copy":

        def drafter(params, state):
            def body(flat0, tok0, pos0):
                def step(carry, _):
                    tok, p = carry
                    # the source side is a row a slot, as the arena holds it
                    scores = model.apply(
                        {"params": params}, state["src_mask"],
                        state["src_proj"], tok[:, None], p,
                        method=FiraModel.copy_draft_scores)
                    choice = V + jnp.argmax(
                        scores[:, 0, :], axis=-1).astype(jnp.int32)
                    nxt = resolve(choice, state)
                    return (nxt, jnp.minimum(p + 1, T - 2)), nxt

                _, drafts = jax.lax.scan(step, (tok0, pos0), None, length=k)
                return drafts.T                     # (k, S) -> (S, k)

            return roll(state, body)

        return drafter

    assert tier == "draft", tier

    def drafter(params, state):
        # dense SCRATCH view of each slot's top beam: the pool is read
        # once per draft and never written (sentinel table rows of
        # idle/done slots clamp to garbage the validity mask zeroes).
        # Beam 0's history does not lie in lane 0: it is followed
        # through the engine's ancestry table, position by position
        tab, anc = state["block_tab"], state["ancestry"]
        P = state["k_pool"].shape[0] // L            # blocks a layer
        k_sc = jnp.stack([
            gather_block_kv_beam(state["k_pool"], tab + l * P, anc, 0,
                                 cfg.num_head)
            for l in range(L)])
        v_sc = jnp.stack([
            gather_block_kv_beam(state["v_pool"], tab + l * P, anc, 0,
                                 cfg.num_head)
            for l in range(L)])

        def body(flat0, tok0, pos0):
            def step(carry, _):
                flat, p, kc, vc = carry
                valid = step_valid_mask(flat, p, T)
                tok_in = jnp.take_along_axis(flat, p[:, None], axis=1)
                # the source side is a row a slot in the arena: the top
                # beam's dense step reads the leaves themselves
                fused, kc, vc = model.apply(
                    {"params": params}, state["src_mask"], tok_in, p, kc,
                    vc, state["cross_k"], state["cross_v"],
                    state["src_proj"], valid[:, None, None, :],
                    method=FiraModel.fused_probs_step_multi)
                nxt = resolve(
                    jnp.argmax(fused[:, 0, :], axis=-1).astype(jnp.int32),
                    state)
                p2 = jnp.minimum(p + 1, T - 2)
                return (scatter_token(flat, p2, nxt), p2, kc, vc), nxt

            _, drafts = jax.lax.scan(
                step, (flat0, pos0, k_sc, v_sc), None, length=k)
            return drafts.T

        return roll(state, body)

    return drafter


def run_verify(step_gated, state, drafts, k: int, tar_len: int):
    """The draft-and-verify acceptance loop: up to ``k`` gated exact step
    frames in one dispatch.

    ``step_gated(st, gate)`` is the engine's ``_one_step`` partially
    applied over params — (state', active-row count). Frame 0 runs every
    live row (gate starts all-True: exactly the plain step, so one verify
    dispatch NEVER does less than one plain dispatch); frame j+1 keeps a
    row gated in only while frame j's emitted top-beam token equalled
    ``drafts[:, j]`` and the row did not settle. The loop exits as soon as
    no gated row remains — a fully-missed draft costs exactly one plain
    step's frames.

    Returns (state', occ_entry, counters) with counters =
    [tested, matched, iters]: row-frames advanced (the plain dispatches
    this verify replaced, occ_entry of them owed anyway), drafted-token
    agreements, and while-loop iterations (device-compute honesty: each
    frame costs one plain step's FLOPs). All three ride back as ONE stacked
    device vector the engine drains at harvest — its designated sync
    boundary — so spec metering adds no host sync."""
    S = drafts.shape[0]
    active0 = state["live"] & ~state["done"]
    occ_entry = jnp.sum(active0.astype(jnp.int32))
    z = jnp.int32(0)

    def cond(carry):
        st, gate, j, _tested, _matched = carry
        return (j < k) & jnp.any(st["live"] & ~st["done"] & gate)

    def body(carry):
        st, gate, j, tested, matched = carry
        act = st["live"] & ~st["done"] & gate
        pos_c = jnp.minimum(st["pos"], tar_len - 2)
        st2, occ = step_gated(st, gate)
        emitted = top_beam_token(st2["tokens"], pos_c + 1)
        draft_j = jax.lax.dynamic_slice_in_dim(drafts, j, 1, axis=1)[:, 0]
        match = act & (emitted == draft_j)
        # rows that were not stepped this frame keep their gate: their
        # fate was already decided (or they are idle/done and act-masked)
        gate = jnp.where(act, match, gate)
        return (st2, gate, j + 1, tested + occ,
                matched + jnp.sum(match.astype(jnp.int32)))

    st, _gate, iters, tested, matched = jax.lax.while_loop(
        cond, body, (state, jnp.ones((S,), bool), z, z, z))
    return st, occ_entry, jnp.stack([tested, matched, iters])
