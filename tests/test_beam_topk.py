"""Beam selection's exact top-K (decode/beam.top_k): K reduce passes in
place of the vocabulary-wide sort that `jax.lax.top_k` is on the chip.

Two contracts:

- BIT-IDENTICAL to `jax.lax.top_k` — values AND indices, ties lowest index
  first — on every kind of row selection sees: random, peaked with most of
  the row exactly 0.0, all equal, -inf-padded (a finished beam's `neg`
  candidates), fewer distinct values than K; at the widths of every call
  (the K*2K+K tail, the copy side, the vocabulary, the unfactored fused
  row), under jit with the (B, K) leading batch selection has;
- the sort cannot come back unseen: the lowered programs that select beams
  (`_select_factored`, `_select`, the engine's step) hold no sort and no
  top_k op over an operand as wide as the vocabulary.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fira_tpu.config import fira_tiny
from fira_tpu.data.batching import make_batch
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.decode import beam
from fira_tpu.decode.engine import SlotEngine
from fira_tpu.model.model import FiraModel
from fira_tpu.train.state import init_state

KS = (1, 3, 8)
# the selection tail at beam 3 (3*6+3), the copy side (sou + sub), the
# vocabulary, the unfactored fused row at beam 3 (3*25,020+3)
WIDTHS = (21, 370, 24650, 75063)
ROW_KINDS = ("random", "peaked_zeros", "all_equal", "neg_inf_padded",
             "few_distinct")
B = 2


def _rows(kind: str, k: int, width: int) -> np.ndarray:
    rng = np.random.default_rng([k, width, ROW_KINDS.index(kind)])
    x = rng.standard_normal((B, k, width)).astype(np.float32)
    if kind == "peaked_zeros":
        # an EOS-biased softmax: a few survivors, the rest underflowed
        x = np.array(jax.nn.softmax(jnp.asarray(x * 400.0), axis=-1))
        assert (x == 0.0).mean() > 0.5
    elif kind == "all_equal":
        x[:] = 0.25
    elif kind == "neg_inf_padded":
        # a finished beam's row: every candidate `neg`, or all but a few
        x[0] = -np.inf
        x[1, :, 2:] = -np.inf
    elif kind == "few_distinct":
        # two values only: fewer distinct than K for K = 3 and 8
        x = rng.integers(0, 2, x.shape).astype(np.float32)
    return x


def _assert_same(got, want):
    (gv, gi), (wv, wi) = got, want
    assert gv.dtype == wv.dtype and gi.dtype == wi.dtype
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    # bit for bit: -0.0 / +0.0 and NaN payloads included
    np.testing.assert_array_equal(
        np.asarray(gv).view(np.int32), np.asarray(wv).view(np.int32))


@pytest.mark.parametrize("kind", ROW_KINDS)
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("k", KS)
def test_top_k_equals_lax_top_k(k, width, kind):
    x = jnp.asarray(_rows(kind, k, width))
    got = jax.jit(functools.partial(beam.top_k, k=k))(x)
    assert got[0].shape == (B, k, k) and got[1].shape == (B, k, k)
    _assert_same(got, jax.lax.top_k(x, k))


def test_top_k_ranks_in_total_order_like_lax_top_k():
    """Outside what selection feeds it, still `lax.top_k`'s order: NaN
    over +inf, +0.0 over -0.0, -NaN under -inf; eager and on a vector."""
    neg_nan = np.frombuffer(np.uint32(0xFFC00000).tobytes(), np.float32)[0]
    x = jnp.asarray(np.array(
        [0.0, -0.0, neg_nan, np.inf, -np.inf, np.nan, 1.0, -0.0, 0.0,
         np.nan, 3.0], np.float32))
    _assert_same(beam.top_k(x, x.shape[0]), jax.lax.top_k(x, x.shape[0]))


def test_top_k_rejects_what_it_cannot_rank():
    with pytest.raises(ValueError, match="top_k"):
        beam.top_k(jnp.zeros((2, 3)), 4)
    with pytest.raises(ValueError, match="top_k"):
        beam.top_k(jnp.zeros((2, 3)), 0)
    with pytest.raises(TypeError, match="top_k"):
        beam.top_k(jnp.zeros((2, 3), jnp.int32), 1)


# --------------------------------------------------------------------------
# no sort in the programs that select beams
# --------------------------------------------------------------------------

_RANKING_OP = re.compile(r"\b(sort|top_k|topk|approx_top_k)\b", re.I)
_TENSOR = re.compile(r"tensor<([0-9x]+)x[a-z][a-z0-9]*>")


def _wide_ranking_ops(text: str, width: int):
    """Lines of a lowered program that hold a sort or a top-k op with an
    operand or result of ``width`` or more along some axis."""
    hits = []
    for line in text.splitlines():
        if not _RANKING_OP.search(line.split("loc(")[0]):
            continue
        dims = [int(d) for t in _TENSOR.findall(line) for d in t.split("x")]
        if any(d >= width for d in dims):
            hits.append(line.strip()[:200])
    return hits


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("topk_corpus"))
    write_corpus_dir(data_dir, n_commits=16, seed=3)
    cfg = fira_tiny(batch_size=4, test_batch_size=4, decode_engine=True,
                    engine_slots=4, beam_early_exit=True)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    split = dataset.splits["train"]
    batch = make_batch(split, np.arange(4), cfg, batch_size=4)
    params = init_state(FiraModel(cfg), cfg, batch).params
    return cfg, split, params


def _selection_args(cfg, width):
    S, K, T = 4, cfg.beam_size, cfg.tar_len
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    dist = tuple(sds((S, K, w), f32) for w in width)
    batch = {"diff": sds((S, cfg.sou_len), i32),
             "sub_token": sds((S, cfg.sub_token_len), i32)}
    return dist, (sds((S, K, T), i32), sds((S, K), f32),
                  sds((S, K), jnp.bool_), sds((S,), i32), batch)


def _lower_select_factored(cfg):
    (gen, copy), rest = _selection_args(
        cfg, (cfg.vocab_size, cfg.sou_len + cfg.sub_token_len))
    gate = jax.ShapeDtypeStruct(gen.shape[:2] + (2,), jnp.float32)
    fn = functools.partial(beam._select_factored, cfg=cfg,
                           neg=jnp.float32(-np.inf))
    return jax.jit(fn).lower(gen, copy, gate, *rest).as_text()


def _lower_select(cfg):
    (dist,), rest = _selection_args(cfg, (cfg.output_vocab_size,))
    fn = functools.partial(beam._select, cfg=cfg, neg=jnp.float32(-np.inf))
    return jax.jit(fn).lower(dist, *rest).as_text()


@pytest.mark.parametrize("lower", (_lower_select_factored, _lower_select),
                         ids=("select_factored", "select"))
def test_selection_lowers_without_a_vocabulary_sort(tiny, lower,
                                                    monkeypatch):
    cfg = tiny[0]
    assert _wide_ranking_ops(lower(cfg), cfg.vocab_size) == []
    # the detector sees what it guards against: the same program over the
    # sort-backed oracle trips it
    monkeypatch.setattr(beam, "top_k", jax.lax.top_k)
    assert _wide_ranking_ops(lower(cfg), cfg.vocab_size)


@pytest.mark.parametrize("harvest_every,beam", [
    (4, 3),     # the production step (a scan of 4 positions)
    (1, 3),     # the plain one-position form (the spec verify's body)
    (4, 1),     # a single beam: K = 1 pass a side
], ids=("scan4_beam3", "plain_beam3", "scan4_beam1"))
def test_engine_step_lowers_without_a_vocabulary_sort(tiny, harvest_every,
                                                      beam):
    cfg0, split, params = tiny
    cfg = dataclasses.replace(cfg0, engine_harvest_every=harvest_every,
                              beam_size=beam)
    eng = SlotEngine(FiraModel(cfg), params, cfg, slots=cfg.engine_slots)
    warm = make_batch(split, np.arange(0), cfg,
                      batch_size=cfg.test_batch_size)
    wire = {k: v for k, v in warm.items() if not k.startswith("_")}
    eng._ensure_state(eng._prefill(eng.params, wire))
    text = eng._step.lower(eng._decode_params, eng._state).as_text(
        debug_info=True)
    assert "topk/" in text           # selection's scope: it is in there
    assert _wide_ranking_ops(text, cfg.vocab_size) == []
