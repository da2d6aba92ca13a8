"""The source side of FIRA's arena is held ONCE A SLOT (ISSUE 33).

``cross_k`` / ``cross_v`` / ``src_proj`` are what a request's encoder pass
leaves for its decode; a slot's K beams read them as K queries of one
attention (and one copy-score pass), so neither the prefilled chunk nor the
arena nor any value inside the step program holds them K times. Pinned here:

- the chunk has C rows and the arena ``slots`` rows, at K = 3 and K = 8;
- no value of the traced ``_step_fn`` has the K-fold shape — the jaxpr is
  walked through its scans and calls, so the repeat cannot come back as a
  broadcast inside the program;
- a payload is its chunk's row, and a prefix-cache hit (``extract_payloads``
  -> ``build_chunk`` -> insert) leaves the three leaves bit-equal to a fresh
  prefill's.

The values themselves (tokens bitwise, probs to float32 rounding against the
batched beam, which keeps its ``jnp.repeat``) are tests/test_paged_kv.py's.
"""

import dataclasses

import jax
import numpy as np
import pytest

from fira_tpu.config import fira_tiny
from fira_tpu.data.batching import make_batch
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.decode import prefix_cache
from fira_tpu.decode.engine import SlotEngine
from fira_tpu.model.layers import pool_block_rows
from fira_tpu.model.model import FiraModel
from fira_tpu.train.state import init_state

SLOTS, CHUNK = 5, 4          # S*K = 15 | 40: no other axis of fira-tiny
SOURCE_LEAVES = ("cross_k", "cross_v", "src_proj")


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("slot_source_corpus"))
    write_corpus_dir(data_dir, n_commits=16, seed=31)
    cfg = fira_tiny(batch_size=8, test_batch_size=CHUNK, decode_engine=True,
                    engine_slots=SLOTS)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    batch = make_batch(dataset.splits["train"], np.arange(CHUNK), cfg)
    params = init_state(FiraModel(cfg), cfg, batch).params
    return cfg, dataset, params


def _engine(setup, beam: int):
    cfg0, dataset, params = setup
    cfg = dataclasses.replace(cfg0, beam_size=beam)
    eng = SlotEngine(FiraModel(cfg), params, cfg)
    split = dataset.splits["train"]
    host = make_batch(split, np.arange(CHUNK), cfg, batch_size=CHUNK)
    wire = {k: v for k, v in host.items() if not k.startswith("_")}
    return cfg, eng, wire


def _insert(eng, state, chunk, slot_ids):
    C, W = len(slot_ids), eng._table_width
    ids = np.asarray(slot_ids, np.int32)
    blocks = (ids[:, None] * W + np.arange(W)[None, :]).astype(np.int32)
    return eng._insert(state, chunk, ids,
                       np.full((C,), eng.cfg.tar_len, np.int32), blocks,
                       eng._fresh_arg(True))


@pytest.mark.parametrize("beam", (3, 8))
def test_chunk_has_a_row_a_request_and_the_arena_a_row_a_slot(setup, beam):
    cfg, eng, wire = _engine(setup, beam)
    chunk = eng._prefill(eng.params, wire)
    L, H = cfg.num_layers, cfg.num_head
    src_len = cfg.sou_len + cfg.sub_token_len
    d = cfg.embedding_dim
    assert chunk["cross_k"].shape == (L, CHUNK, H, src_len, d // H)
    assert chunk["cross_v"].shape == chunk["cross_k"].shape
    assert chunk["src_proj"].shape == (CHUNK, src_len, d)
    eng._ensure_state(chunk)
    assert eng._state["cross_k"].shape == (L, SLOTS, H, src_len, d // H)
    assert eng._state["cross_v"].shape == eng._state["cross_k"].shape
    assert eng._state["src_proj"].shape == (SLOTS, src_len, d)
    assert eng._state["src_mask"].shape == (SLOTS, src_len)
    # the per-beam leaves did not shrink with them
    assert eng._state["k_pool"].shape == (
        L * eng._pool_blocks,
        pool_block_rows(beam, eng._block_size, eng._state["k_pool"].dtype),
        d)
    assert eng._state["ancestry"].shape == (SLOTS, beam, cfg.tar_len)


def _shapes(jaxpr, out):
    """Every value's shape in a jaxpr, through scans, conds and calls."""
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        out.add(tuple(v.aval.shape))
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out.add(tuple(v.aval.shape))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _shapes(inner, out)
    return out


@pytest.mark.parametrize("beam", (3, 8))
def test_no_value_of_the_step_holds_the_source_k_times(setup, beam):
    """The K-fold tensor must not come back inside the program: no value of
    the traced step (scan body and calls walked) has S*K rows of whole
    source keys, values or projections."""
    cfg, eng, wire = _engine(setup, beam)
    eng._ensure_state(eng._prefill(eng.params, wire))
    closed = jax.make_jaxpr(eng._step_fn)(eng._decode_params, eng._state)
    shapes = _shapes(closed.jaxpr, set())
    H, d = cfg.num_head, cfg.embedding_dim
    src_len = cfg.sou_len + cfg.sub_token_len
    B = SLOTS * beam
    # the walk does see inside the scan: the K queries' scores are there
    assert (SLOTS, H, beam, src_len) in shapes
    assert (SLOTS, beam, src_len, d) in shapes       # the tanh volume
    k_fold = [s for s in shapes
              if s[-4:] == (B, H, src_len, d // H)      # cross K/V rows
              or s[-3:] == (B, src_len, d)              # src_proj rows
              or s[-4:] == (B, 1, src_len, d)]          # ... as one target
    assert k_fold == []


def _chunk_host(chunk):
    return {f: np.asarray(jax.device_get(chunk[f]))
            for f in prefix_cache.ARTIFACT_FIELDS}


@pytest.mark.parametrize("beam", (3, 8))
def test_a_payload_is_the_chunks_row_and_rebuilds_it_bitwise(setup, beam):
    _cfg, eng, wire = _engine(setup, beam)
    host = _chunk_host(eng._prefill(eng.params, wire))
    rows = [0, 2, 3]
    payloads = prefix_cache.extract_payloads(host, rows)
    for r in rows:
        p = payloads[r]
        assert p["cross_k"].shape == host["cross_k"][:, :1].shape
        assert p["src_proj"].shape == host["src_proj"][:1].shape
        np.testing.assert_array_equal(p["cross_k"][:, 0],
                                      host["cross_k"][:, r])
        np.testing.assert_array_equal(p["cross_v"][:, 0],
                                      host["cross_v"][:, r])
        np.testing.assert_array_equal(p["src_proj"][0], host["src_proj"][r])
    # a payload's bytes are one row's: beam-independent, what the cache
    # charges its budget and counts as saved
    one_row = sum(host[f][:, :1].nbytes for f in ("cross_k", "cross_v")) \
        + sum(host[f][:1].nbytes for f in ("src_proj", "src_mask", "diff",
                                           "sub_token"))
    assert prefix_cache.payload_nbytes(payloads[0]) == one_row \
        + payloads[0]["seed"].nbytes
    built = prefix_cache.build_chunk(payloads, CHUNK)
    assert set(built) == set(host)
    for f, want in host.items():
        assert built[f].shape == want.shape and built[f].dtype == want.dtype
        if want.ndim == 0:
            continue
        axis = 1 if f in ("cross_k", "cross_v") else 0
        for r in range(CHUNK):
            got = np.take(built[f], r, axis=axis)
            ref = np.take(want, r, axis=axis)
            np.testing.assert_array_equal(
                got, ref if r in rows else np.zeros_like(ref))


def test_cache_hit_leaves_the_arena_as_a_fresh_prefill_does(setup):
    """extract_payloads -> build_chunk -> insert against prefill -> insert,
    into the same slots of two arenas: the three source leaves bit-equal."""
    _cfg, eng, wire = _engine(setup, 3)
    chunk = eng._prefill(eng.params, wire)
    eng._ensure_state(chunk)
    slot_ids = [4, 0, SLOTS, 2]                    # row 2 not seated
    seated = [r for r, s in enumerate(slot_ids) if s < SLOTS]
    fresh = jax.device_get(_insert(eng, eng._state, chunk, slot_ids))
    payloads = prefix_cache.extract_payloads(_chunk_host(chunk), seated)
    rebuilt = jax.device_put(prefix_cache.build_chunk(payloads, CHUNK))
    assert jax.tree_util.tree_structure(rebuilt) \
        == jax.tree_util.tree_structure(dict(chunk))
    eng2 = SlotEngine(eng.model, eng.params, eng.cfg)
    eng2._ensure_state(rebuilt)
    hit = jax.device_get(_insert(eng2, eng2._state, rebuilt, slot_ids))
    for name in SOURCE_LEAVES + ("src_mask", "diff", "sub_token"):
        np.testing.assert_array_equal(hit[name], fresh[name])
    for r in seated:                               # and they are the rows
        np.testing.assert_array_equal(
            fresh["cross_k"][:, slot_ids[r]],
            np.asarray(chunk["cross_k"])[:, r])
        np.testing.assert_array_equal(
            fresh["src_proj"][slot_ids[r]], np.asarray(chunk["src_proj"])[r])
