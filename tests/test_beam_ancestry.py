"""Beam ancestry in place of the self-KV reorder (decode/engine.py,
model/layers.lane_mask; docs/DECODE_ENGINE.md "Paged KV arena").

FIRA's paged pools are written once — beam k's new K/V into LANE k of the
slot's tail block — and never moved; what follows the beams after a
selection is the engine's ``ancestry`` table (S, K, T): the lane that holds
position t of beam k's history. Pinned here, at fira-tiny on the CPU:

- the entries the step's mask selects out of a slot's blocks ARE the dense
  per-beam cache rebuilt from (pool, ``block_tab``, ``ancestry``), bit for
  bit (a pure re-indexing), and so is the drafter's top-beam scratch;
- that dense cache is a whole-sequence stripe cache reordered by
  ``src_beam`` after every selection (the batched beam's discipline, kept
  in numpy beside the engine), position by position, over a drain with
  mixed settle depths and dirty re-granted blocks;
- served tokens equal the batched beam's bitwise and probs to float32
  rounding (the attention sums a beam's keys among the exact zeros of the
  other lanes: another order, the same terms), at beam 1 and 3, in
  probability and in log space, at the production harvest cadence;
- nothing as large as a pool layer is touched under the ``kv_reorder``
  scope of the lowered step (and the detector sees the reorder when a
  model declares one);
- a verify-frozen row resumes with its history intact.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beam_util import beam_outputs
from fira_tpu.config import fira_tiny
from fira_tpu.data.batching import make_batch
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.feeder import Feeder
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.decode import slot_model
from fira_tpu.decode.beam import eos_biased_params
from fira_tpu.decode.engine import SlotEngine
from fira_tpu.decode.runner import _decode_tasks
from fira_tpu.model import layers
from fira_tpu.model.model import FiraModel
from fira_tpu.train.state import init_state

# as tests/test_engine.py states it: float32 last bits, not a looser match
PAGED_PROBS_RTOL = 1e-5


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("ancestry_corpus"))
    write_corpus_dir(data_dir, n_commits=40, seed=29)
    cfg = fira_tiny(batch_size=8, test_batch_size=6, decode_engine=True)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    batch = make_batch(dataset.splits["train"], np.arange(6), cfg)
    params = init_state(FiraModel(cfg), cfg, batch).params
    # moderate EOS bias: mixed settle depths, so slots are harvested and
    # refilled mid-stream and freed blocks are re-granted dirty
    return cfg, dataset, eos_biased_params(params, delta=4.0)


HEADS = fira_tiny().num_head


def blocked(state, name: str) -> np.ndarray:
    """The paged pool ``name`` — (L*P, G, H*d_head), a block a (layer,
    pool block), a row a (lane, position), G = K*BS rounded up to whole
    sublane tiles — as its blocks (L, P, K, H, BS, d_head)."""
    pool = np.asarray(state[name])
    _S, K, T = state["ancestry"].shape
    BS = T // state["block_tab"].shape[1]
    L = fira_tiny().num_layers
    LP, _G, HD = pool.shape
    return pool[:, :K * BS].reshape(L, LP // L, K, BS, HEADS,
                                    HD // HEADS).transpose(0, 1, 2, 4, 3, 5)


def dense_view(state, s: int, name: str) -> np.ndarray:
    """Slot ``s``'s per-beam cache (L, K, H, T, d_head) out of the paged
    pool ``name``, through the block table and the ancestry table."""
    pool = blocked(state, name)
    _L, P, _K, _H, BS, _dh = pool.shape
    tab = np.minimum(np.asarray(state["block_tab"])[s], P - 1)
    anc = np.asarray(state["ancestry"])[s]                  # (K, T)
    t = np.arange(anc.shape[1])[None, :]
    blocks = pool[:, tab]                                   # (L,W,K,H,BS,dh)
    view = blocks[:, t // BS, anc, :, t % BS, :]            # (K,T,L,H,dh)
    return view.transpose(2, 0, 3, 1, 4)


def test_dense_view_through_ancestry_is_the_permuted_stripe_cache(setup):
    """Beside the engine, in numpy, each request keeps a whole-sequence
    stripe cache (L, K, H, T, d_head) the way the batched beam does: a
    step's new K/V goes into row k at its position, then the rows are
    gathered by that step's ``src_beam`` — the SELECTION's own, taken from
    what ``smodel.select`` returned in that dispatch, not read back out of
    the table under test. What the engine reads through (pool,
    ``block_tab``, ``ancestry``) is that cache at every depth of every
    request, bit for bit — the pool itself never moved."""
    cfg0, dataset, params = setup
    cfg = dataclasses.replace(cfg0, engine_harvest_every=1, engine_slots=5)
    model, K = FiraModel(cfg), cfg0.beam_size
    eng = SlotEngine(model, params, cfg)
    stripes, depth_of, checked = {}, {}, set()
    inner = eng.harvest
    # the step program is traced at its first dispatch, through this
    # wrapper: every dispatch then hands its (S, K) src_beam to the host
    selected = []
    select = eng.smodel.select

    def spying_select(*args):
        out = select(*args)
        jax.debug.callback(lambda sb: selected.append(np.array(sb)), out[3])
        return out

    eng.smodel.select = spying_select

    def harvest():
        # after EVERY step dispatch (cadence 1: one position)
        state = jax.device_get(eng._state)
        jax.effects_barrier()
        P, BS = eng._pool_blocks, eng._block_size
        for s, (pid, _host, _row) in eng._busy.items():
            pos = int(state["pos"][s])
            if pos == depth_of.get(pid, 0):
                continue                # settled: it did not step
            assert pos == depth_of.get(pid, 0) + 1
            depth_of[pid] = pos
            p = pos - 1                 # the position this step wrote
            tab = np.minimum(state["block_tab"][s], P - 1)
            src_beam = selected[-1][s]              # of the last dispatch
            # lane k held beam k at p, so the table's new column is it
            np.testing.assert_array_equal(state["ancestry"][s, :, p],
                                          src_beam)
            for kv in "kv":
                pool = blocked(state, f"{kv}_pool")  # (L,P,K,H,BS,dh)
                L, _P, _K, H, _BS, dh = pool.shape
                c = stripes.setdefault(
                    (pid, kv), np.zeros((L, K, H, cfg.tar_len, dh),
                                        pool.dtype))
                c[:, :, :, p] = pool[:, tab[p // BS], :, :, p % BS]
                stripes[(pid, kv)] = c = c[:, src_beam]     # the permute
                got = dense_view(state, s, f"{kv}_pool")
                np.testing.assert_array_equal(
                    got[:, :, :, :pos].view(np.int32),
                    c[:, :, :, :pos].view(np.int32))
            checked.add((pid, pos))
        return inner()

    eng.harvest = harvest
    # lifo: a request lands in the slot freed last, and the pool's blocks
    # go round the free list between requests
    tasks, _ = _decode_tasks(dataset.splits["train"], cfg)
    got = {}
    with Feeder(tasks, num_workers=0, depth=1) as feed:
        for it in eng.run(feed, refill_order="lifo"):
            got[it.position] = (it.tokens, it.probs)
    assert "ancestry" in eng._state and eng.smodel.beam_ancestry
    assert eng._leaves["k_pool"].reorder is None        # never moved
    # blocks were re-granted: more grants than the pool has blocks
    assert eng.stats.slots_refilled * eng._table_width > eng._pool_blocks
    assert {pid for pid, _pos in checked} == set(got)
    assert len(selected) == eng.stats.step_dispatches
    assert len({pos for _pid, pos in checked}) > 3      # mixed depths
    # the beams really were re-sorted: some history lies outside its lane
    anc = np.asarray(eng._state["ancestry"])
    assert (anc != np.arange(K)[None, :, None]).any()
    want = beam_outputs(model, params, dataset.splits["train"], cfg)
    assert got.keys() == want.keys()
    for pos in got:
        np.testing.assert_array_equal(got[pos][0], want[pos][0])
        np.testing.assert_allclose(got[pos][1], want[pos][1],
                                   rtol=PAGED_PROBS_RTOL, atol=0)


def _mid_drain_state(setup, slots=4):
    """A paged engine's state between two dispatches, with at least two
    live rows at depth >= 2, one of them with re-sorted beams (history
    outside its own lane). -> (engine, host state, that row, live rows)."""
    cfg0, dataset, params = setup
    cfg = dataclasses.replace(cfg0, engine_harvest_every=1,
                              engine_slots=slots)
    eng = SlotEngine(FiraModel(cfg), params, cfg)
    tasks, _ = _decode_tasks(dataset.splits["train"], cfg)
    with Feeder(tasks, num_workers=0, depth=1) as feed:
        run = eng.run(feed)
        for _ in run:
            st = jax.device_get(eng._state)
            live = np.flatnonzero(st["live"] & ~st["done"] & (st["pos"] >= 2))
            mixed = [s for s in live if (
                st["ancestry"][s, :, :st["pos"][s]]
                != np.arange(cfg.beam_size)[:, None]).any()]
            if mixed and live.size >= 2:
                break
        run.close()
    r = int(mixed[0])
    return eng, st, r, np.array([r] + [s for s in live if s != r])


def test_the_mask_selects_the_dense_view_bit_for_bit(setup):
    """What the step attends for beam q of slot s — the entries of
    ``gather_block_kv``'s key axis that ``lane_mask`` leaves — is, in
    order, the written prefix of the dense per-beam cache; the drafter's
    ``gather_block_kv_beam`` through the table is beam 0's."""
    eng, st, _r, live = _mid_drain_state(setup)
    K, T = eng.cfg.beam_size, eng.cfg.tar_len
    BS, W = eng._block_size, eng._table_width
    S = eng.slots
    written = np.arange(T)[None, None, :] < st["pos"][:, None, None]
    valid = np.broadcast_to(written, (S, K, T))
    G = st["k_pool"].shape[1]
    assert G == layers.pool_block_rows(K, BS, st["k_pool"].dtype) >= K * BS
    mask = np.asarray(layers.lane_mask(
        jnp.asarray(st["ancestry"]), jnp.asarray(valid), BS, G))[:, 0]
    assert mask.shape == (S, K, W * G)
    for name in ("k_pool", "v_pool"):
        for l in range(eng.cfg.num_layers):
            tab = jnp.asarray(st["block_tab"]) + l * eng._pool_blocks
            keys = np.asarray(layers.gather_block_kv(
                jnp.asarray(st[name]), tab, HEADS))
            top = np.asarray(layers.gather_block_kv_beam(
                jnp.asarray(st[name]), tab, jnp.asarray(st["ancestry"]), 0,
                HEADS))
            for s in live:
                p = int(st["pos"][s])
                dense = dense_view(st, s, name)[l]          # (K,H,T,dh)
                for q in range(K):
                    assert mask[s, q].sum() == p
                    # key order is (block, lane, offset, the block's
                    # unwritten rows): one entry a position, so within a
                    # block positions stay in order only lane by lane —
                    # sort the picks by position
                    picks = np.flatnonzero(mask[s, q])
                    w, rest = np.divmod(picks, G)
                    t = w * BS + rest % BS
                    got = keys[s][:, picks[np.argsort(t)]]  # (H,p,dh)
                    np.testing.assert_array_equal(
                        got.view(np.int32),
                        dense[q][:, :p].view(np.int32))
                np.testing.assert_array_equal(
                    top[s][:, :p].view(np.int32),
                    dense[0][:, :p].view(np.int32))


@pytest.mark.parametrize("log_space", (False, True), ids=("prob", "log"))
@pytest.mark.parametrize("beam", (1, 3))
def test_served_beams_equal_the_batched_beam(setup, beam, log_space):
    """The production cadence (4 positions a dispatch, the scan form of
    the step), slots reused, one beam (no lane to follow) and three, both
    score spaces: tokens bitwise, probs to float32 rounding."""
    cfg0, dataset, params = setup
    cfg = dataclasses.replace(cfg0, beam_size=beam,
                              beam_compat_prob_space=not log_space,
                              engine_harvest_every=4, engine_slots=4)
    model = FiraModel(cfg)
    eng = SlotEngine(model, params, cfg)
    tasks, _ = _decode_tasks(dataset.splits["train"], cfg)
    with Feeder(tasks, num_workers=0, depth=1) as feed:
        got = {it.position: (it.tokens, it.probs) for it in eng.run(feed)}
    assert eng._state["ancestry"].shape == (4, beam, cfg.tar_len)
    want = beam_outputs(model, params, dataset.splits["train"], cfg)
    assert got.keys() == want.keys()
    assert len(got) == len(dataset.splits["train"])
    for pos in got:
        assert got[pos][0].shape == (beam, cfg.tar_len)
        np.testing.assert_array_equal(got[pos][0], want[pos][0])
        np.testing.assert_allclose(got[pos][1], want[pos][1],
                                   rtol=PAGED_PROBS_RTOL, atol=0)


# --------------------------------------------------------------------------
# no pool-sized op under kv_reorder in the lowered step
# --------------------------------------------------------------------------

_LOC_DEF = re.compile(r'^(#loc\d+) = loc\("([^"]*)"', re.M)
_LOC_USE = re.compile(r"loc\((#loc\d+)\)\s*$")
_TENSOR = re.compile(r"tensor<([0-9x]+)x[a-z][a-z0-9]*>")


def _reorder_ops_at_least(text: str, numel: int):
    """Lines of a lowered program (``as_text(debug_info=True)``) under the
    ``kv_reorder`` scope that read or write a tensor of ``numel`` elements
    or more."""
    scoped = {loc for loc, name in _LOC_DEF.findall(text)
              if "kv_reorder" in name}
    assert scoped, "no kv_reorder scope in the lowered step"
    hits = []
    for line in text.splitlines():
        use = _LOC_USE.search(line)
        if not use or use.group(1) not in scoped:
            continue
        sizes = [int(np.prod([int(d) for d in t.split("x")]))
                 for t in _TENSOR.findall(line)]
        if any(n >= numel for n in sizes):
            hits.append(line.strip()[:200])
    return hits


def _lowered_step(setup):
    cfg0, dataset, params = setup
    cfg = dataclasses.replace(cfg0, engine_slots=4)
    eng = SlotEngine(FiraModel(cfg), params, cfg)
    warm = make_batch(dataset.splits["train"], np.arange(0), cfg,
                      batch_size=cfg.test_batch_size)
    wire = {k: v for k, v in warm.items() if not k.startswith("_")}
    eng._ensure_state(eng._prefill(eng.params, wire))
    text = eng._step.lower(eng._decode_params, eng._state).as_text(
        debug_info=True)
    layer = eng._state["k_pool"].size // cfg.num_layers
    return text, layer


def test_step_lowers_without_a_pool_sized_reorder(setup, monkeypatch):
    text, layer = _lowered_step(setup)
    assert _reorder_ops_at_least(text, layer) == []
    # what follows the beams is there, and it is S x K x T ints
    assert re.search(r"kv_reorder/[^\"]*take_along_axis", text)
    # the detector sees what it guards against: the same engine over a
    # model that declares its pools reordered gathers and scatters them
    leaves = slot_model.FiraSlotModel.leaves

    def reordered(self, chunk):
        out = leaves(self, chunk)
        for name in ("k_pool", "v_pool"):
            out[name] = dataclasses.replace(out[name], reorder="pool")
        return out

    # ``permute_pool`` moves a pool's blocks, (L, P, K, ...): it is handed
    # FIRA's blocks lane by lane, and the slices and reshapes there and
    # back add no op the counts below look for
    permute = slot_model.permute_pool

    def permute_blocks(pool, tab_step, src_beam):
        K, W = src_beam.shape[1], tab_step.shape[1]
        BS, L = setup[0].tar_len // W, setup[0].num_layers
        LP, _G, HD = pool.shape
        blocks = pool[:, :K * BS].reshape(L, LP // L, K, BS, HD)
        moved = permute(blocks, tab_step, src_beam)
        return jnp.concatenate([moved.reshape(LP, K * BS, HD),
                                pool[:, K * BS:]], axis=1)

    monkeypatch.setattr(slot_model.FiraSlotModel, "leaves", reordered)
    monkeypatch.setattr(slot_model, "permute_pool", permute_blocks)
    text, layer = _lowered_step(setup)
    hits = _reorder_ops_at_least(text, layer)
    # a pool: the gather of every slot's blocks, the beam axis taken by
    # src_beam, and the scatter back (a region op: its types close it)
    assert sum("stablehlo.gather" in h for h in hits) == 2
    assert sum("call @take_along_axis" in h for h in hits) == 2
    assert sum(h.startswith("})") for h in hits) == 2


# --------------------------------------------------------------------------
# a verify-frozen row resumes
# --------------------------------------------------------------------------

def test_gated_row_keeps_its_history_and_resumes(setup):
    eng, st0, r, live = _mid_drain_state(setup)
    step = jax.jit(lambda st, gate: eng._one_step(eng._decode_params, st,
                                                  gate)[0])
    everyone = jnp.ones((eng.slots,), bool)
    frozen = everyone.at[r].set(False)

    def row(st, upto):
        return (st["tokens"][r], st["probs"][r], st["ancestry"][r],
                int(st["pos"][r]),
                dense_view(st, r, "k_pool")[:, :, :, :upto],
                dense_view(st, r, "v_pool")[:, :, :, :upto])

    def same(a, b):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    p0 = int(st0["pos"][r])
    st1 = jax.device_get(step(st0, frozen))
    assert int(st1["pos"][r]) == p0                      # it did not move
    assert (st1["pos"][live[1:]] == st0["pos"][live[1:]] + 1).all()
    same(row(st1, p0), row(st0, p0))                     # history intact
    # resumed, it lands where the ungated step would have put it
    st2 = jax.device_get(step(st1, everyone))
    ref = jax.device_get(step(st0, everyone))
    assert int(ref["pos"][r]) == p0 + 1
    same(row(st2, p0 + 1), row(ref, p0 + 1))
