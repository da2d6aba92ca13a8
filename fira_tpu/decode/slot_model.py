"""The seam between the slot engine and a model (ROADMAP D3, the part a
second architecture needs).

decode/engine.py schedules slots, beams, the paged pool and the harvest;
what it asks of a model is this protocol, and nothing model-specific is
bound by name in the engine any more:

- ``prefill(params, batch) -> chunk``: what one packed batch of new
  requests leaves behind for its slots (a dict of device arrays, rows along
  each leaf's request axis). Traced inside the engine's ``_prefill_fn``.
- ``leaves(chunk) -> {name: Leaf}``: the arena's model-owned leaves, each
  with shape, dtype and whether it is a per-beam block pool whose
  contents the engine moves with ``src_beam`` after every selection
  (``reorder="pool"``) or not (``reorder=None``: shared by a slot's
  beams, or per beam LANE and read through the ancestry table). ``kv``
  marks what ``kv_bytes_per_slot`` counts (decode/paging.py follows
  these declarations).
- ``insert(state, chunk, sid, fresh) -> {name: leaf}``:
  scatter chunk rows into slots ``sid`` (sentinel = dropped).
- ``step(params, state, view) -> (parts, writes)``: one position for every
  beam of every slot, at each slot's own depth; ``view`` carries the
  engine's per-dispatch cache view (tokens, positions, the active mask,
  the sentinel-masked block table). ``parts`` go to ``select``; ``writes``
  are the updated arena leaves, not yet reordered.
- ``prefill_budget``: the prefill dispatches ``SlotEngine.run`` admits
  between two step dispatches; 0 = as many as the free slots ask for.
- ``beam_ancestry``: True for a model whose per-beam pools are written
  once, each beam into its own lane, and never reordered: the engine then
  keeps ``ancestry`` (S, K, T) — the lane that holds position t of beam
  k's history — follows the beams in IT after every selection, and hands
  it to ``step`` in the view. False: per-beam leaves are moved as their
  ``reorder`` says, and no table is allocated or carried.
- ``beam_parent``: True for a model with per-beam RECURRENT STATE
  (``Leaf.kv_kind="state"``): a beam's state IS its history, so after a
  selection beam k must continue from its source beam's state. The engine
  then keeps ``parent`` (S, K) — the last selection's ``src_beam``, all 0
  on a fresh slot (its beams share the one state prefill left in lane 0)
  — and hands it to ``step``, which reads lane ``parent[s, k]`` as it
  computes what it writes to lane k: the state follows the beams inside
  the one read and one write a position needs anyway.
- ``select(parts, tokens, probs, finished, pos, state, neg)``: the beam
  selection for this model's distribution -> (tokens, probs, finished,
  src_beam). FIRA's copy head selects from the factors
  (``beam._select_factored``); a model with one log-softmax goes through
  ``beam._select``.

:class:`FiraSlotModel` is the first implementation: ONE arena (the source
side — cross K/V, copy projection, mask — once a slot, shared by its
beams; the paged pools, written once and followed by ancestry), one step
(``FiraModel.dist_parts_step_paged``: a slot's K beams are K queries
against what the slot holds) and one selection
(``beam._select_factored``), whatever the batched beam's knobs say.
:class:`LMSlotModel` (``arch="axk1"``, model/axk1.py) is the second;
:class:`AfmoeSlotModel` (``arch="afmoe"``, model/afmoe.py) the third, and
the first whose prompt leaves differ BY LAYER TYPE (``Leaf.kv_kind``: a
whole prompt a full layer, a ring a window layer, a leaf a layer);
:class:`JambaSlotModel` (``arch="jamba"``, model/jamba.py) the fourth, and
the first with leaves that are not keys and values at all: a recurrent
state a beam lane beside two attention layers' K/V (``beam_parent``);
:class:`BrumbySlotModel` (``arch="brumby"``, model/brumby.py) the fifth,
whose prompt leaves a state of fixed size ONCE A SLOT, read-only in the step
and shared by the beams, beside the beams' own positions in the pool;
:class:`Lfm2SlotModel` (``arch="lfm2"``, model/lfm2.py) the sixth: Jamba's
arena with a short convolution's two-token tail a beam lane in place of the
Mamba leaves, beside routed experts.
config.ARCH_TABLE says which class an ``arch`` gets.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from fira_tpu.config import ARCH_TABLE, CONV, FULL, SLIDING, FiraConfig
from fira_tpu.decode import quant
from fira_tpu.decode.beam import _select, _select_factored, step_valid_mask
from fira_tpu.model.layers import pool_block_rows


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One model-owned arena leaf, as the engine allocates it."""

    shape: Tuple[int, ...]
    dtype: np.dtype
    reorder: Optional[str] = None   # "pool": per beam, block contents
    #                                 moved by src_beam; None: never moved
    #                                 (shared by the beams, or per lane)
    kv: bool = False                # counted by kv_bytes_per_slot
    kv_shape: Optional[Tuple[int, ...]] = None  # of a ``kv`` leaf whose
    #                                 stored shape pads its values: the
    #                                 shape they fill, which is what
    #                                 kv_bytes_per_slot counts
    kv_kind: str = ""               # of a ``kv`` leaf that holds PROMPTS:
    #                                 "full" (a prompt kept whole) or
    #                                 "window" (a ring of its last
    #                                 positions); or "state": a state of
    #                                 fixed size whatever the prompt's
    #                                 length — a beam LANE's, rewritten
    #                                 whole at every position
    #                                 (``beam_parent``: Jamba's), or a
    #                                 SLOT's, written once at insert, only
    #                                 read by a step and shared by the
    #                                 slot's beams (``reorder=None``:
    #                                 Brumby's); paging adds them up by it


class StepView(NamedTuple):
    """What the engine hands a step: the slots' beams and where they are."""

    flat: jnp.ndarray       # (S*K, T) tokens, beams folded into rows
    pos_c: jnp.ndarray      # (S,) position of each slot, clamped legal
    pos_bk: jnp.ndarray     # (S*K,) the same, a row
    active: jnp.ndarray     # (S,) live and not done (and not gated off)
    tab_step: jnp.ndarray   # (S, W) block table, the sentinel in rows
    #                         that must not read or write
    ancestry: Optional[jnp.ndarray] = None  # (S, K, T) beam lane holding
    #                                  position t of beam k's history, this
    #                                  position already each beam's own lane
    #                                  (a model with ``beam_ancestry`` only)
    parent: Optional[jnp.ndarray] = None    # (S, K) the lane whose state
    #                                  beam k continues from: the last
    #                                  selection's source beam, 0 on a fresh
    #                                  slot (a model with ``beam_parent``)


def permute_pool(pool, tab_step, src_beam):
    """Move block CONTENTS within each active slot's own block set so that
    cached histories follow their beams (table entries stay put: a slot's
    grant is host-owned from insert to harvest). pool: (L, P, K, ...);
    ``src_beam``: (S, K). Scatter targets are disjoint across slots
    because grants never overlap; sentinel rows (idle/done) drop.

    Three passes over every block of every active slot, every position:
    only a pool declared ``reorder="pool"`` pays them — A.X-K1's
    ``lat_pool`` (0.10 GB, no such op among its step's longest). FIRA's
    pools are written once and never moved: its beams follow by the
    engine's ancestry table (``beam_ancestry``)."""
    # (1, S, 1, K, 1...): the beam axis of the gathered blocks
    idx = src_beam[(None, slice(None), None, slice(None))
                   + (None,) * (pool.ndim - 3)]
    blocks = pool[:, tab_step]           # (L, S, W, K, ...)
    blocks = jnp.take_along_axis(blocks, idx, axis=3)
    return pool.at[:, tab_step].set(blocks, mode="drop")


class FiraSlotModel:
    """FIRA behind the seam: encoder prefill, cross K/V and copy
    projection ONCE A SLOT (the slot's K beams read them as K queries; the
    batched beam repeats them K-fold, the arena never does), the decoder
    step over the paged pools, the selection from the distribution's
    factors."""

    insert_by_geometry = False
    arena_counters: Tuple[str, ...] = ()
    prefill_budget = 0      # a prefill is cheap beside a step: unpaced
    # the pools are written once, each beam into its own lane, and never
    # moved: the engine keeps which lane holds each position of each
    # beam's history and the step reads through that table
    beam_ancestry = True
    beam_parent = False     # no recurrent state

    def __init__(self, model, cfg: FiraConfig, slots: int,
                 block_size: int, pool_blocks: int):
        self.model, self.cfg, self.slots = model, cfg, slots
        self.block_size, self.pool_blocks = block_size, pool_blocks

    def chunk_rows(self, chunk) -> int:
        return int(chunk["diff"].shape[0])

    def prefill(self, params, batch):
        """Per-batch preamble of the cached batched beam up to its
        per-beam replication: encode once, then per-layer cross K/V +
        copy-head source projection, a row a request. Identical program
        prefix => identical values."""
        from fira_tpu.model.model import FiraModel

        cfg, model = self.cfg, self.model
        states, mask = model.apply({"params": params}, batch,
                                   method=FiraModel.encode)
        out = {"src_mask": mask, "diff": batch["diff"],
               "sub_token": batch["sub_token"]}
        out["cross_k"], out["cross_v"], out["src_proj"] = model.apply(
            {"params": params}, states, method=FiraModel.decode_init)
        # dtype marker only: fresh slots seed their self-attention cache
        # at the ENCODER STATE dtype, exactly like the batched beam's
        # cache0 (which may be wider than the compute dtype under
        # stable_residual) — unless the low-precision KV tier pins the
        # arena narrower (cfg.kv_dtype="bf16", decode/quant.py): the
        # arena allocates the pools at this dtype and the HBM accounting
        # follows it
        out["cache_seed"] = jnp.zeros(
            (), quant.kv_seed_dtype(cfg, states.dtype))
        return out

    def leaves(self, chunk) -> Dict[str, Leaf]:
        cfg = self.cfg
        S, K = self.slots, cfg.beam_size
        L, H = cfg.num_layers, cfg.num_head
        d_head = cfg.embedding_dim // H
        ck, sp = chunk["cross_k"], chunk["src_proj"]
        cd = chunk["cache_seed"].dtype
        P, BS = self.pool_blocks, self.block_size
        G = pool_block_rows(K, BS, cd)
        pool = Leaf((L * P, G, H * d_head), cd, kv=True,
                    kv_shape=(L * P, K * BS, H * d_head))
        return {
            "diff": Leaf((S,) + chunk["diff"].shape[1:],
                         chunk["diff"].dtype),
            "sub_token": Leaf((S,) + chunk["sub_token"].shape[1:],
                              chunk["sub_token"].dtype),
            "src_mask": Leaf((S,) + chunk["src_mask"].shape[1:],
                             np.dtype(bool)),
            # the source side, once a slot: its beams are K queries
            "cross_k": Leaf((L, S) + ck.shape[2:], ck.dtype),
            "cross_v": Leaf((L, S) + ck.shape[2:], ck.dtype),
            "src_proj": Leaf((S,) + sp.shape[1:], sp.dtype),
            # per beam LANE, not per beam: no reorder (beam_ancestry); a
            # block a (layer, pool block), a row a (lane, position), its
            # heads side by side, the rows whole sublane tiles: the
            # runtime lays this out as the step's scan computes in it
            # (docs/DECODE_ENGINE.md "Paged KV arena")
            "k_pool": pool,
            "v_pool": pool,
        }

    def insert(self, state, chunk, sid, fresh) -> Dict:
        """No cache zeroing (the engine's INVARIANT): the pools are
        untouched here."""
        new = {}
        for f in ("diff", "sub_token", "src_mask", "src_proj"):
            new[f] = state[f].at[sid].set(chunk[f], mode="drop")
        for f in ("cross_k", "cross_v"):
            new[f] = state[f].at[:, sid].set(chunk[f], mode="drop")
        return new

    def step(self, params, state, view: StepView):
        from fira_tpu.model.model import FiraModel

        cfg = self.cfg
        flat, pos_bk = view.flat, view.pos_bk
        # same per-row validity rule as beam_search_cached, at the
        # per-slot position vector (beam.step_valid_mask) — this mask
        # is also what makes unwritten/stale POOL blocks read as an
        # exact 0.0 contribution, so fresh slots need no zeroed cache
        valid = step_valid_mask(flat, pos_bk, cfg.tar_len)
        tok_in = jnp.take_along_axis(flat, pos_bk[:, None], axis=1)
        # the factors come back a slot a row, its beams second: what the
        # selection takes
        gen, copy, gate, k_new, v_new = self.model.apply(
            {"params": params}, state["src_mask"], tok_in, pos_bk,
            state["k_pool"], state["v_pool"], view.tab_step, view.ancestry,
            state["cross_k"], state["cross_v"], state["src_proj"],
            valid[:, None, None, :],
            method=FiraModel.dist_parts_step_paged)
        return (gen, copy, gate), {"k_pool": k_new, "v_pool": v_new}

    def select(self, parts, tokens, probs, finished, pos_c, state, neg):
        gen, copy, gate = parts
        return _select_factored(
            gen, copy, gate, tokens, probs, finished, pos_c,
            {"diff": state["diff"], "sub_token": state["sub_token"]},
            self.cfg, neg)


class LMSlotModel:
    """A.X-K1 behind the seam (model/axk1.py). Per slot: the prompt's
    latents ``[L, P_max, 576]``, SHARED by the slot's beams and written by
    prefill at insert; per beam: the generated positions' latents in the
    engine's paged pool, block layout ``(L, blocks, K, block, 576)``,
    reordered like every pool. ``counters`` accumulates model/axk1.COUNTERS
    on the device; the harvest reads it with its own reads."""

    insert_by_geometry = True      # a chunk is as long as its bucket
    beam_ancestry = False          # lat_pool is reordered (permute_pool)
    beam_parent = False            # no recurrent state
    # one prefill dispatch of prompts (8,192 padded tokens at the published
    # widths) outweighs a step dispatch three times: refilling every free
    # slot first would stall the seated slots for seconds and seat whole
    # waves in lockstep, so the engine alternates one prefill with one step
    prefill_budget = 1

    def __init__(self, model, cfg: FiraConfig, slots: int,
                 block_size: int, pool_blocks: int):
        # ``model``: None (plain functions over the parameter tree)
        from fira_tpu.model import axk1

        self.cfg, self.lm, self.slots = cfg, cfg.lm, slots
        self.block_size, self.pool_blocks = block_size, pool_blocks
        self.dtype = jnp.dtype(cfg.compute_dtype)
        self.arena_counters = axk1.COUNTERS

    def chunk_rows(self, chunk) -> int:
        return int(chunk["lengths"].shape[0])

    def prefill(self, params, batch):
        from fira_tpu.model import axk1

        lat, counters = axk1.prefill(params, self.lm, batch["tokens"],
                                     batch["lengths"], self.dtype)
        return {"lat": lat, "lengths": batch["lengths"],
                "counters": counters}

    def leaves(self, chunk) -> Dict[str, Leaf]:
        lm, S, K = self.lm, self.slots, self.cfg.beam_size
        L, dt = lm.num_hidden_layers, chunk["lat"].dtype
        return {
            "prompt_lat": Leaf((L, S, lm.prompt_len_max, lm.latent_dim), dt,
                               kv=True),
            "prompt_len": Leaf((S,), np.dtype(np.int32)),
            "lat_pool": Leaf((L, self.pool_blocks, K, self.block_size,
                              lm.latent_dim), dt, reorder="pool", kv=True),
            "counters": Leaf((len(self.arena_counters),),
                             np.dtype(np.int32)),
        }

    def insert(self, state, chunk, sid, fresh) -> Dict:
        P = chunk["lat"].shape[2]
        return {
            "prompt_lat": state["prompt_lat"].at[:, sid, :P].set(
                chunk["lat"], mode="drop"),
            "prompt_len": state["prompt_len"].at[sid].set(
                chunk["lengths"].astype(jnp.int32), mode="drop"),
            # a chunk's own counts enter once, with its first rows
            "counters": state["counters"] + chunk["counters"] * fresh,
        }

    def step(self, params, state, view: StepView):
        from fira_tpu.model import axk1

        S, K = self.slots, self.cfg.beam_size
        tok = jnp.take_along_axis(view.flat, view.pos_bk[:, None], axis=1)
        logp, pool, counters = axk1.decode_step(
            params, self.lm, tok.reshape(S, K), view.pos_c,
            state["prompt_lat"], state["prompt_len"], state["lat_pool"],
            view.tab_step, view.active, self.dtype)
        return (logp,), {"lat_pool": pool,
                         "counters": state["counters"] + counters}

    def select(self, parts, tokens, probs, finished, pos_c, state, neg):
        # no copy head: the generation log-softmax is the whole candidate
        # space, and a token id is itself (batch=None: nothing to resolve)
        return _select(parts[0], tokens, probs, finished, pos_c, None,
                       self.cfg, neg, log_input=True)


class AfmoeSlotModel(LMSlotModel):
    """Trinity-Mini behind the seam (model/afmoe.py). Per slot, BY LAYER
    TYPE: a full layer keeps the prompt's keys and values whole
    (``prompt_k_full<j>`` / ``prompt_v_full<j>``, as long as the longest
    bucket), a window layer a ring of its last ``sliding_window`` positions
    (``prompt_k_win<j>`` / ``prompt_v_win<j>``: position p at entry ``p mod
    sliding_window``, the order prefill hands them over in); both shared by
    the slot's beams, positions last, a leaf a layer and side (a step then
    reads each as it lies; of a leaf that stacked them the chip's compiler
    copied every slice it took, the whole arena a dispatch). Per beam:
    every layer's generated positions in the engine's paged pool, reordered
    like A.X-K1's (``reorder="pool"``: 3 beams x 64 positions x 5 layers
    are 2 MB a slot, so the three passes of ``permute_pool`` are 0.3 GB a
    position beside 7.7 GB of weights; an ancestry table would save them
    and cost the step a gather through it). What is inherited is what does
    not differ: the pacing, the chunk's rows, the selection."""

    def __init__(self, model, cfg: FiraConfig, slots: int,
                 block_size: int, pool_blocks: int):
        from fira_tpu.model import afmoe

        super().__init__(model, cfg, slots, block_size, pool_blocks)
        self.arena_counters = afmoe.COUNTERS
        lm = self.lm

        def pairs(stem: str, kind: str):
            return [(f"prompt_k_{stem}{j}", f"prompt_v_{stem}{j}")
                    for j in range(len(lm.layers_of(kind)))]
        # what the chunk calls a kind of prompt cache -> (Leaf.kv_kind, its
        # length, a (keys' leaf, values' leaf) pair a layer of that kind)
        self._prompt_leaves = {
            "kv_full": ("full", lm.prompt_len_max, pairs("full", FULL)),
            "kv_ring": ("window", lm.sliding_window, pairs("win", SLIDING)),
        }

    def prefill(self, params, batch):
        from fira_tpu.model import afmoe

        full, rings, counters = afmoe.prefill(
            params, self.lm, batch["tokens"], batch["lengths"], self.dtype)
        return {"kv_full": full, "kv_ring": rings,
                "lengths": batch["lengths"], "counters": counters}

    def leaves(self, chunk) -> Dict[str, Leaf]:
        lm, S, K = self.lm, self.slots, self.cfg.beam_size
        dt, c = chunk["kv_full"][0][0].dtype, lm.kv_dim
        out = {name: Leaf((S, c // 2, length), dt, kv=True, kv_kind=kind)
               for kind, length, pairs in self._prompt_leaves.values()
               for pair in pairs for name in pair}
        out.update({
            "prompt_len": Leaf((S,), np.dtype(np.int32)),
            "kv_pool": Leaf((lm.num_hidden_layers, self.pool_blocks, K,
                             self.block_size, c), dt, reorder="pool",
                            kv=True),
            "counters": Leaf((len(self.arena_counters),),
                             np.dtype(np.int32)),
        })
        return out

    def insert(self, state, chunk, sid, fresh) -> Dict:
        # a chunk's prompts are as long as their bucket; its rings came in
        # ring order (entry r holds position p, p mod window == r), a
        # bucket under the window filling the first entries only
        new = {
            "prompt_len": state["prompt_len"].at[sid].set(
                chunk["lengths"].astype(jnp.int32), mode="drop"),
            "counters": state["counters"] + chunk["counters"] * fresh,
        }
        for key, (_kind, _length, pairs) in self._prompt_leaves.items():
            for pair, sides in zip(pairs, chunk[key]):
                for name, x in zip(pair, sides):
                    new[name] = state[name].at[sid, :, :x.shape[-1]].set(
                        x, mode="drop")
        return new

    def step(self, params, state, view: StepView):
        from fira_tpu.model import afmoe

        S, K = self.slots, self.cfg.beam_size
        tok = jnp.take_along_axis(view.flat, view.pos_bk[:, None], axis=1)
        full, win = ([(state[k], state[v]) for k, v in pairs]
                     for _kind, _length, pairs
                     in self._prompt_leaves.values())
        logp, pool, counters = afmoe.decode_step(
            params, self.lm, tok.reshape(S, K), view.pos_c, full, win,
            state["prompt_len"], state["kv_pool"], view.tab_step,
            view.active, self.dtype)
        return (logp,), {"kv_pool": pool,
                         "counters": state["counters"] + counters}


class JambaSlotModel(LMSlotModel):
    """Jamba2-3B behind the seam (model/jamba.py). Per beam LANE, a leaf a
    Mamba layer: the recurrent state ``ssm_state<j>`` (S * K, d_state,
    d_inner) float32 and the convolution's tail ``conv_state<j>`` (taps -
    1, S * K, d_inner), row ``s * K + k`` lane k of slot s —
    ``kv_kind="state"``: of fixed size whatever the prompt's length, not
    paged, rewritten whole at every position. d_inner lies last and the
    lanes are folded into the rows because the chip tiles an array's last
    two axes by (8, 128) (bfloat16: 16): a trailing d_state of 16 would be
    padded eightfold, a second-to-last axis of 3 taps or 3 beams fivefold.
    A leaf a layer, so that a step REPLACES each whole (the lane a beam
    reads is its parent's, the lane it writes its own: no update in place
    could be right, and a stacked leaf would be copied for it).

    ``insert`` writes a request's ONE state into lane 0 of its slot, once:
    the engine seats a slot with ``parent`` all 0, so its K beams — which
    share one history until the first selection — all continue from that
    lane (writing all K lanes would triple the insert's 28 MB a slot for
    nothing). Per slot, shared by the beams: the two attention layers'
    prompt keys and values whole (``prompt_k_full<j>`` / ``prompt_v_full<j>``,
    ``"full"``); per beam, paged and reordered as Trinity-Mini's: the
    attention layers' generated positions (``kv_pool``, whose layer axis
    counts ATTENTION layers)."""

    beam_parent = True

    def __init__(self, model, cfg: FiraConfig, slots: int,
                 block_size: int, pool_blocks: int):
        from fira_tpu.model import jamba

        super().__init__(model, cfg, slots, block_size, pool_blocks)
        self.arena_counters = jamba.COUNTERS
        n_ssm, n_attn = (len(self.lm.mamba_layers),
                         len(self.lm.attention_layers))
        self._ssm = [f"ssm_state{j}" for j in range(n_ssm)]
        self._conv = [f"conv_state{j}" for j in range(n_ssm)]
        self._prompt = [(f"prompt_k_full{j}", f"prompt_v_full{j}")
                        for j in range(n_attn)]

    def prefill(self, params, batch):
        from fira_tpu.model import jamba

        states, tails, kvs, counters = jamba.prefill(
            params, self.lm, batch["tokens"], batch["lengths"], self.dtype)
        return {"ssm": states, "conv": tails, "kv_full": kvs,
                "lengths": batch["lengths"], "counters": counters}

    def leaves(self, chunk) -> Dict[str, Leaf]:
        lm, S, K = self.lm, self.slots, self.cfg.beam_size
        dt, c = chunk["conv"][0].dtype, lm.kv_dim
        out = {n: Leaf((S * K, lm.mamba_d_state, lm.d_inner),
                       np.dtype(np.float32), kv=True, kv_kind="state")
               for n in self._ssm}
        out.update({n: Leaf((lm.mamba_d_conv - 1, S * K, lm.d_inner), dt,
                            kv=True, kv_kind="state") for n in self._conv})
        out.update({n: Leaf((S, c // 2, lm.prompt_len_max), dt, kv=True,
                            kv_kind="full")
                    for pair in self._prompt for n in pair})
        out.update({
            "prompt_len": Leaf((S,), np.dtype(np.int32)),
            "kv_pool": Leaf((len(self._prompt), self.pool_blocks, K,
                             self.block_size, c), dt, reorder="pool",
                            kv=True),
            "counters": Leaf((len(self.arena_counters),),
                             np.dtype(np.int32)),
        })
        return out

    def insert(self, state, chunk, sid, fresh) -> Dict:
        lane0 = sid * self.cfg.beam_size    # the sentinel stays out of range
        new = {
            "prompt_len": state["prompt_len"].at[sid].set(
                chunk["lengths"].astype(jnp.int32), mode="drop"),
            "counters": state["counters"] + chunk["counters"] * fresh,
        }
        for name, H in zip(self._ssm, chunk["ssm"]):
            new[name] = state[name].at[lane0].set(H, mode="drop")
        for name, tail in zip(self._conv, chunk["conv"]):
            new[name] = state[name].at[:, lane0].set(tail, mode="drop")
        for pair, sides in zip(self._prompt, chunk["kv_full"]):
            for name, x in zip(pair, sides):
                new[name] = state[name].at[sid, :, :x.shape[-1]].set(
                    x, mode="drop")
        return new

    def step(self, params, state, view: StepView):
        from fira_tpu.model import jamba

        S, K = self.slots, self.cfg.beam_size
        tok = jnp.take_along_axis(view.flat, view.pos_bk[:, None], axis=1)
        logp, ssm, conv, pool, counters = jamba.decode_step(
            params, self.lm, tok.reshape(S, K), view.pos_c,
            [state[n] for n in self._ssm], [state[n] for n in self._conv],
            view.parent, [(state[k], state[v]) for k, v in self._prompt],
            state["prompt_len"], state["kv_pool"], view.tab_step,
            view.active, self.dtype)
        writes = {"kv_pool": pool, "counters": state["counters"] + counters}
        writes.update(zip(self._ssm, ssm))
        writes.update(zip(self._conv, conv))
        return (logp,), writes


class BrumbySlotModel(LMSlotModel):
    """Brumby-14B-Base behind the seam (model/brumby.py). Per slot, a leaf a
    layer: the prompt's retention state ``ret_state<j>`` (S, KV, D, hd) in
    the compute dtype and its normaliser ``ret_norm<j>`` (S, KV, D) float32
    — ``kv_kind="state"``, of fixed size whatever the prompt's length,
    written once by ``insert`` and only READ by a step, one copy shared by
    the slot's beams (``reorder=None``; no ``beam_parent``: a beam
    continues from the prompt along its own tokens, and what differs
    between beams lies in the pool). The head size lies last: the chip
    tiles an array's last two axes. No prompt keys or values are kept at
    all: the prompt is its state. Per beam, paged and reordered as
    Trinity-Mini's:
    every layer's generated positions' [k | v] (``kv_pool``) and the gates'
    sum since the prompt's end at each of them (``gen_gate``, float32)."""

    def __init__(self, model, cfg: FiraConfig, slots: int,
                 block_size: int, pool_blocks: int):
        from fira_tpu.model import brumby

        super().__init__(model, cfg, slots, block_size, pool_blocks)
        self.arena_counters = brumby.COUNTERS
        L = self.lm.num_hidden_layers
        self._state = [f"ret_state{j}" for j in range(L)]
        self._norm = [f"ret_norm{j}" for j in range(L)]

    def prefill(self, params, batch):
        from fira_tpu.model import brumby

        states, norms, counters = brumby.prefill(
            params, self.lm, batch["tokens"], batch["lengths"], self.dtype)
        return {"state": states, "norm": norms, "lengths": batch["lengths"],
                "counters": counters}

    def leaves(self, chunk) -> Dict[str, Leaf]:
        lm, S, K = self.lm, self.slots, self.cfg.beam_size
        KV, hd, D = lm.num_key_value_heads, lm.head_dim, lm.state_dim
        L, dt = lm.num_hidden_layers, chunk["state"][0].dtype
        out = {n: Leaf((S, KV, D, hd), dt, kv=True, kv_kind="state")
               for n in self._state}
        out.update({n: Leaf((S, KV, D), np.dtype(np.float32), kv=True,
                            kv_kind="state") for n in self._norm})
        pool = (L, self.pool_blocks, K, self.block_size)
        out.update({
            "prompt_len": Leaf((S,), np.dtype(np.int32)),
            "kv_pool": Leaf(pool + (lm.kv_dim,), dt, reorder="pool",
                            kv=True),
            "gen_gate": Leaf(pool + (KV,), np.dtype(np.float32),
                             reorder="pool", kv=True),
            "counters": Leaf((len(self.arena_counters),),
                             np.dtype(np.int32)),
        })
        return out

    def insert(self, state, chunk, sid, fresh) -> Dict:
        new = {
            "prompt_len": state["prompt_len"].at[sid].set(
                chunk["lengths"].astype(jnp.int32), mode="drop"),
            "counters": state["counters"] + chunk["counters"] * fresh,
        }
        for names, key in ((self._state, "state"), (self._norm, "norm")):
            for name, x in zip(names, chunk[key]):
                new[name] = state[name].at[sid].set(x, mode="drop")
        return new

    def step(self, params, state, view: StepView):
        from fira_tpu.model import brumby

        S, K = self.slots, self.cfg.beam_size
        tok = jnp.take_along_axis(view.flat, view.pos_bk[:, None], axis=1)
        logp, pool, gates, counters = brumby.decode_step(
            params, self.lm, tok.reshape(S, K), view.pos_c,
            [state[n] for n in self._state], [state[n] for n in self._norm],
            state["prompt_len"], state["kv_pool"], state["gen_gate"],
            view.tab_step, view.active, self.dtype)
        # the prompt's state is not among the writes: a step never changes it
        return (logp,), {"kv_pool": pool, "gen_gate": gates,
                         "counters": state["counters"] + counters}


class Lfm2SlotModel(JambaSlotModel):
    """LFM2-8B-A1B behind the seam (model/lfm2.py): Jamba's arena without
    its recurrent state. Per beam LANE, a leaf a conv layer: the short
    convolution's tail ``conv_tail<j>`` (taps - 1, S * K, hidden) — the last
    two ``v = B * u`` a lane, 8 KB a lane a layer at the published widths —
    ``kv_kind="state"``, read through ``parent`` and rewritten whole at
    every position, as Jamba's ``conv_state<j>``; ``insert`` (Jamba's) writes
    a request's tails into lane 0 of its slot only. Per slot, shared by the
    beams: the attention layers' prompt keys and values whole
    (``prompt_k_full<j>`` / ``prompt_v_full<j>``, ``"full"``); per beam,
    paged and reordered: their generated positions (``kv_pool``, whose
    layer axis counts ATTENTION layers). ``arena_counters``: the expert
    layer's four and ``moe_experts_read`` (model/lfm2.COUNTERS)."""

    def __init__(self, model, cfg: FiraConfig, slots: int,
                 block_size: int, pool_blocks: int):
        from fira_tpu.model import lfm2

        LMSlotModel.__init__(self, model, cfg, slots, block_size,
                             pool_blocks)
        self.arena_counters = lfm2.COUNTERS
        self._ssm = []                  # no recurrent state: tails only
        self._conv = [f"conv_tail{j}"
                      for j in range(len(self.lm.layers_of(CONV)))]
        self._prompt = [(f"prompt_k_full{j}", f"prompt_v_full{j}")
                        for j in range(len(self.lm.layers_of(FULL)))]

    def prefill(self, params, batch):
        from fira_tpu.model import lfm2

        tails, kvs, counters = lfm2.prefill(
            params, self.lm, batch["tokens"], batch["lengths"], self.dtype)
        return {"ssm": [], "conv": tails, "kv_full": kvs,
                "lengths": batch["lengths"], "counters": counters}

    def leaves(self, chunk) -> Dict[str, Leaf]:
        lm, S, K = self.lm, self.slots, self.cfg.beam_size
        dt, c = chunk["conv"][0].dtype, lm.kv_dim
        out = {n: Leaf((lm.conv_L_cache - 1, S * K, lm.hidden_size), dt,
                       kv=True, kv_kind="state") for n in self._conv}
        out.update({n: Leaf((S, c // 2, lm.prompt_len_max), dt, kv=True,
                            kv_kind="full")
                    for pair in self._prompt for n in pair})
        out.update({
            "prompt_len": Leaf((S,), np.dtype(np.int32)),
            "kv_pool": Leaf((len(self._prompt), self.pool_blocks, K,
                             self.block_size, c), dt, reorder="pool",
                            kv=True),
            "counters": Leaf((len(self.arena_counters),),
                             np.dtype(np.int32)),
        })
        return out

    def step(self, params, state, view: StepView):
        from fira_tpu.model import lfm2

        S, K = self.slots, self.cfg.beam_size
        tok = jnp.take_along_axis(view.flat, view.pos_bk[:, None], axis=1)
        logp, conv, pool, counters = lfm2.decode_step(
            params, self.lm, tok.reshape(S, K), view.pos_c,
            [state[n] for n in self._conv], view.parent,
            [(state[k], state[v]) for k, v in self._prompt],
            state["prompt_len"], state["kv_pool"], view.tab_step,
            view.active, self.dtype)
        writes = {"kv_pool": pool, "counters": state["counters"] + counters}
        writes.update(zip(self._conv, conv))
        return (logp,), writes


def for_config(model, cfg: FiraConfig, slots: int, block_size: int,
               pool_blocks: int):
    """The slot model config.ARCH_TABLE names for ``cfg.arch``."""
    return globals()[ARCH_TABLE[cfg.arch].slot_model](
        model, cfg, slots, block_size, pool_blocks)
