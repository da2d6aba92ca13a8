"""Paged KV arena (decode/paging.py + decode/engine.py block tables).

Pins the ISSUE-7 contract (docs/DECODE_ENGINE.md "Paged KV arena"):

- the engine's tokens are per-sample BIT-EXACT vs the batched beam's
  whole-sequence cache at every KV block size and under an undersized
  pool, and its probs equal to float32 rounding (the self-attention
  sums over all beam lanes under the ancestry mask — ISSUE 29), and
  run_test file bytes are identical (single engine AND 2-replica fleet)
  with zero post-warmup compiles;
- scheduling stays deterministic when the pool is UNDERSIZED: admission
  is head-of-line on block reservations, so output bytes are a pure
  function of the stream, pool size included;
- the no-zeroing INVARIANT: insert does not touch the pools (freed
  blocks are unmapped, never zeroed — beam.step_valid_mask makes
  unwritten positions an exact 0.0), and a dirty arena reused across
  streams stays bit-exact, so a zeroing scatter cannot silently appear;
- parse-time paging-knob validation (decode/paging.paging_errors): named
  -knob messages, CLI exit 2, and the fleet's per-replica pool split.
"""

import dataclasses

import numpy as np
import pytest

from beam_util import beam_outputs
from fira_tpu.analysis import sanitizer
from fira_tpu.config import fira_tiny
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.feeder import Feeder
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.decode import engine as engine_lib
from fira_tpu.decode import paging
from fira_tpu.decode.beam import eos_biased_params
from fira_tpu.decode.runner import _decode_tasks, run_test
from fira_tpu.model.model import FiraModel
from fira_tpu.parallel import fleet as fleet_lib
from fira_tpu.train.state import init_state


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("paged_corpus"))
    write_corpus_dir(data_dir, n_commits=40, seed=23)
    cfg = fira_tiny(batch_size=8, test_batch_size=6)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    from fira_tpu.data.batching import make_batch

    batch = make_batch(dataset.splits["train"], np.arange(6), cfg)
    params = init_state(FiraModel(cfg), cfg, batch).params
    # moderate EOS bias: mixed settle depths => real harvest/refill churn,
    # so freed blocks actually return to the pool and get re-granted dirty
    return cfg, dataset, data_dir, eos_biased_params(params, delta=4.0)


def _engine_outputs(model, params, dataset, cfg, **engine_kw):
    """{split position: (tokens, probs)} from one engine drain."""
    data = dataset.splits["train"]
    eng = engine_lib.SlotEngine(model, params, cfg, **engine_kw)
    tasks, _ = _decode_tasks(data, cfg)
    out = {}
    with Feeder(tasks, num_workers=0, depth=1) as feed:
        for it in eng.run(feed):
            out[it.position] = (it.tokens, it.probs)
    assert len(out) == len(data)
    return out, eng


# the bound tests/test_engine.py states, and why
PAGED_PROBS_RTOL = 1e-5


# (kv_block_size, pool blocks as a share of full residency); tar_len 12
GEOMETRIES = {
    "auto": (0, 1.0),            # 6 positions a block, 2 blocks a sequence
    "block3": (3, 1.0),          # 4 blocks a sequence
    "block-tar": (12, 1.0),      # one block a sequence
    "block3-half-pool": (3, 0.5),  # admission waits for harvested blocks
}


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_paged_bit_exact_vs_whole_sequence_cache(setup, geometry):
    """The engine over its block pool == the batched beam over whole-
    sequence cache stripes, per sample, at every block size and under an
    undersized pool: tokens bitwise, probs to float32 rounding
    (PAGED_PROBS_RTOL) — the ROADMAP-4 regression contract."""
    cfg0, dataset, _dir, eos_params = setup
    block, share = GEOMETRIES[geometry]
    bs = block or paging.resolve_block_size(cfg0)
    W = paging.blocks_per_seq(cfg0.tar_len, bs)
    slots = cfg0.test_batch_size
    cfg = dataclasses.replace(
        cfg0, kv_block_size=block,
        kv_pool_blocks=0 if share == 1.0 else int(slots * W * share))
    model = FiraModel(cfg)

    paged_out, eng = _engine_outputs(model, eos_params, dataset, cfg)
    st = eng.stats.summary()
    assert st["kv_block_size"] == bs and eng._table_width == W
    assert st["pool_blocks"] == int(slots * W * share)
    # the machine-recorded HBM claim follows the pool, not the slots
    assert st["kv_bytes_per_slot"] == paging.kv_bytes_per_slot(
        cfg, block_size=bs, pool_blocks=st["pool_blocks"], slots=slots,
        itemsize=paging.kv_itemsize(cfg))
    assert 0.0 < st["pool_utilization"] <= 1.0
    assert 0 < st["peak_blocks"] <= st["pool_blocks"]
    if share < 1.0:
        assert st["peak_blocks"] > st["pool_blocks"] - W   # the cap binds
    # every grant came back: nothing leaked, nothing doubled
    assert eng.allocator_invariants() == []
    assert len(eng._free_blocks) == eng._pool_blocks

    want = beam_outputs(model, eos_params, dataset.splits["train"], cfg)
    assert paged_out.keys() == want.keys()
    for pos in paged_out:
        np.testing.assert_array_equal(paged_out[pos][0], want[pos][0])
        # the paged step attends a slot's beams over all lanes of its
        # blocks under the ancestry mask: the same keys and values per
        # beam, the exact zeros of the others summed in another order
        np.testing.assert_allclose(paged_out[pos][1], want[pos][1],
                                   rtol=PAGED_PROBS_RTOL, atol=0)


@pytest.mark.parametrize("beam", (3, 8))
def test_k_beams_as_k_queries_of_the_slots_one_source(setup, beam):
    """The source side is held once a slot and its K beams attend it as K
    queries (ISSUE 33) where the batched beam repeats it K-fold and runs K
    rows of one query: same tokens bitwise, probs to float32 rounding, at
    K = 3 and K = 8, the production cadence (4 positions a dispatch), with
    slots at mixed depths and refilled in between."""
    cfg0, dataset, _dir, eos_params = setup
    slots = 5
    # a weaker EOS bias than the fixture's (4.0 - 1.0): about half the
    # requests settle within 3-4 positions, the rest run on to 12, so
    # refilled slots start while their neighbours are 4 and 8 deep
    eos_params = eos_biased_params(eos_params, delta=-1.0)
    cfg = dataclasses.replace(cfg0, beam_size=beam, engine_harvest_every=4)
    model = FiraModel(cfg)
    data = dataset.splits["train"]
    eng = engine_lib.SlotEngine(model, eos_params, cfg, slots=slots)
    tasks, _ = _decode_tasks(data, cfg)
    got, depths = {}, set()
    with Feeder(tasks, num_workers=0, depth=1) as feed:
        for it in eng.run(feed):
            got[it.position] = (it.tokens, it.probs)
            st = eng._state
            live = np.asarray(st["live"] & ~st["done"])
            if live.sum() >= 2:
                depths.add(len(set(np.asarray(st["pos"])[live].tolist())))
    assert eng._state["cross_k"].shape[1] == slots
    assert eng._state["src_proj"].shape[0] == slots
    # slots were reused (more requests than seats, several prefills) and
    # the live ones stood at different positions while others settled
    assert len(got) == len(data) > 3 * slots and eng.stats.prefills > 2
    assert max(depths) >= 2

    want = beam_outputs(model, eos_params, data, cfg)
    assert got.keys() == want.keys()
    for pos in got:
        assert got[pos][0].shape == (beam, cfg.tar_len)
        np.testing.assert_array_equal(got[pos][0], want[pos][0])
        np.testing.assert_allclose(got[pos][1], want[pos][1],
                                   rtol=PAGED_PROBS_RTOL, atol=0)


def test_paged_file_identical_zero_retraces_single_and_fleet(setup, tmp_path):
    """run_test bytes + BLEU: engine == batched beam on a BUCKETED stream,
    with zero post-warmup compiles under the armed sanitizer for the
    single engine AND the 2-replica fleet (the step/insert programs live
    under the SAME declared label family)."""
    cfg0, dataset, _dir, eos_params = setup
    cfg = dataclasses.replace(cfg0, buckets=((16, 400, 12),),
                              decode_engine=True)
    model = FiraModel(cfg)
    ref = run_test(model, eos_params, dataset,
                   dataclasses.replace(cfg, decode_engine=False),
                   out_dir=str(tmp_path / "batched"), split="train")
    ref_bytes = open(ref["output_path"], "rb").read()

    with sanitizer.sanitize(nans=False, infs=False) as guard:
        one = run_test(model, eos_params, dataset, cfg,
                       out_dir=str(tmp_path / "paged1"), guard=guard,
                       split="train")
        assert guard.compiles_after_warmup() == 0
    assert open(one["output_path"], "rb").read() == ref_bytes
    assert one["sentence_bleu"] == ref["sentence_bleu"]
    assert one["engine"]["pool_blocks"] > 0
    assert one["engine"]["kv_bytes_per_slot"] > 0

    with sanitizer.sanitize(nans=False, infs=False) as guard:
        two = run_test(model, eos_params, dataset,
                       dataclasses.replace(cfg, engine_replicas=2),
                       out_dir=str(tmp_path / "paged2"), guard=guard,
                       split="train")
        assert guard.compiles_after_warmup() == 0
    assert open(two["output_path"], "rb").read() == ref_bytes
    eng = two["engine"]
    assert eng["replicas"] == 2
    # per-chip pools total across the fleet; utilization stays a mean over
    # each replica's own dispatches
    assert eng["pool_blocks"] == 2 * one["engine"]["pool_blocks"]
    assert eng["kv_bytes_per_slot"] == one["engine"]["kv_bytes_per_slot"]
    assert 0.0 < eng["pool_utilization"] <= 1.0


def test_undersized_pool_head_of_line_deterministic(setup, tmp_path):
    """A pool SMALLER than full residency (here: a third) forces
    reservation-based admission — at most pool/W slots live at once — yet
    output bytes are identical: refill is head-of-line on the block free
    list, so admission order is a pure function of the stream."""
    cfg0, dataset, _dir, eos_params = setup
    cfg = dataclasses.replace(cfg0, decode_engine=True, engine_slots=6)
    model = FiraModel(cfg)
    ref = run_test(model, eos_params, dataset, cfg,
                   out_dir=str(tmp_path / "full"), split="train")
    W = paging.blocks_per_seq(cfg.tar_len, paging.resolve_block_size(cfg))
    pool = 2 * W  # room for TWO of the six slots
    small = run_test(model, eos_params, dataset,
                     dataclasses.replace(cfg, kv_pool_blocks=pool),
                     out_dir=str(tmp_path / "small"), split="train")
    assert (open(small["output_path"], "rb").read()
            == open(ref["output_path"], "rb").read())
    st = small["engine"]
    assert st["pool_blocks"] == pool
    assert 0 < st["peak_blocks"] <= pool
    # the block cap binds: full residency would seat all six slots
    assert ref["engine"]["peak_blocks"] > pool


def test_insert_never_zeroes_cache_and_dirty_arena_reuse(setup, tmp_path):
    """The comment-backed INVARIANT of engine._insert_fn: insert must not
    touch the K/V pools — a seated slot gets new block GRANTS (table
    rows), nothing else (stale positions are -1e9-masked to an exact 0.0
    by beam.step_valid_mask). Pinned by object identity through an EAGER
    insert, so a zeroing scatter fails here even before any output
    diverges — plus bit-exact file bytes from a deliberately DIRTY arena
    reused across streams."""
    cfg0, dataset, _dir, eos_params = setup
    data = dataset.splits["train"]

    model = FiraModel(cfg0)
    eng = engine_lib.SlotEngine(model, eos_params, cfg0)
    tasks, _ = _decode_tasks(data, cfg0)
    with Feeder(tasks, num_workers=0, depth=1) as feed:
        for _ in eng.run(feed):
            pass
    state = eng._state  # dirty: every slot has decoded real samples
    from fira_tpu.data.batching import make_batch

    host = make_batch(data, np.arange(cfg0.test_batch_size), cfg0,
                      batch_size=cfg0.test_batch_size)
    chunk = eng._prefill(eng.params, host)
    C = host["valid"].shape[0]
    slot_ids = np.arange(C, dtype=np.int32)
    limits = np.full((C,), cfg0.tar_len, np.int32)
    W = eng._table_width
    block_rows = np.arange(C * W, dtype=np.int32).reshape(C, W)
    new = eng._insert_fn(state, chunk, slot_ids, limits, block_rows)
    for f in ("k_pool", "v_pool"):
        assert new[f] is state[f], (
            f"insert touched {f}: the no-zeroing invariant broke — "
            f"freed blocks must be unmapped, never zeroed")

    # dirty-arena reuse: second drain of the SAME engine starts from pools
    # full of the first drain's values; bytes must not change
    cfg = dataclasses.replace(cfg0, decode_engine=True)
    model = FiraModel(cfg)
    runs = []
    eng = engine_lib.SlotEngine(model, eos_params, cfg)
    for _ in range(2):
        tasks, _ = _decode_tasks(data, cfg)
        got = {}
        with Feeder(tasks, num_workers=0, depth=1) as feed:
            for it in eng.run(feed):
                got[it.position] = (it.tokens.tobytes(), it.probs.tobytes())
        runs.append(got)
    assert runs[0] == runs[1]


@pytest.mark.parametrize("bad", ("rows", "width"))
def test_step_refuses_a_pool_of_another_geometry(setup, bad):
    """A pool is (L*P, G, H*d_head): blocks that are not P of every
    layer, a block whose rows cannot hold its K lanes x BS positions, or
    a row that does not hold the heads, are a geometry mismatch the step
    names before it traces any attention."""
    import jax

    from fira_tpu.data.batching import make_batch
    from fira_tpu.model.layers import pool_block_rows

    cfg, dataset, _dir, params = setup
    eng = engine_lib.SlotEngine(FiraModel(cfg), params, cfg)
    host = make_batch(dataset.splits["train"], np.arange(0), cfg,
                      batch_size=cfg.test_batch_size)
    wire = {k: v for k, v in host.items() if not k.startswith("_")}
    arena = eng.arena_shapes(jax.eval_shape(eng._prefill_fn, params, wire))
    K, BS = cfg.beam_size, eng._block_size
    blocks, rows, width = arena["k_pool"].shape
    assert blocks == cfg.num_layers * eng._pool_blocks and width == \
        cfg.embedding_dim
    assert rows == pool_block_rows(K, BS, arena["k_pool"].dtype) >= K * BS
    shape = {"rows": (blocks, K * BS - 1, width),
             "width": (blocks, rows, width // 2)}[bad]
    for name in ("k_pool", "v_pool"):
        arena[name] = jax.ShapeDtypeStruct(shape, arena[name].dtype)
    want = {"rows": f"a block of {K * BS - 1} rows must hold {K} lanes x "
                    f"{BS} positions",
            "width": f"a pool row of {width // 2} must hold the "
                     f"{cfg.num_head} heads"}[bad]
    with pytest.raises(ValueError, match="paged cache geometry mismatch"
                       ) as err:
        jax.eval_shape(eng._step_fn, eng._decode_params, arena)
    assert want in str(err.value)


# --------------------------------------------------------------------------
# knob resolution + parse-time validation
# --------------------------------------------------------------------------

def test_auto_block_size_and_byte_accounting():
    assert paging.auto_block_size((12,)) == 6
    assert paging.auto_block_size((8, 12)) == 4    # gcd 4, cap 4
    assert paging.auto_block_size((30,)) == 15
    assert paging.auto_block_size((30, 64)) == 2   # gcd 2
    assert paging.auto_block_size((7,)) == 1       # prime: always valid
    assert paging.blocks_per_seq(30, 15) == 2
    assert paging.blocks_per_seq(31, 15) == 3
    cfg = fira_tiny()
    bs = paging.resolve_block_size(cfg)
    W = paging.blocks_per_seq(cfg.tar_len, bs)
    slots = 8
    # itemsize comes from the serving tier, not a literal 4 (docs/
    # DECODE_ENGINE.md "Low-precision tiers")
    isz = paging.kv_itemsize(cfg)
    assert isz == 4  # fira_tiny defaults kv_dtype="f32"
    assert paging.kv_itemsize(cfg.replace(kv_dtype="bf16")) == 2
    # full residency: the pool commits exactly a whole-sequence stripe's
    # bytes a slot — K and V, every layer, beam, head and position
    stripe = (2 * cfg.num_layers * cfg.beam_size * cfg.embedding_dim
              * cfg.tar_len * isz)
    assert paging.kv_bytes_per_slot(
        cfg, block_size=bs, pool_blocks=slots * W, slots=slots,
        itemsize=isz) == stripe
    # half the pool: half the committed HBM per slot
    assert paging.kv_bytes_per_slot(
        cfg, block_size=bs, pool_blocks=slots * W // 2,
        slots=slots, itemsize=isz) == stripe // 2


def test_paging_errors_named_knob_messages():
    base = fira_tiny().replace(decode_engine=True)

    assert paging.paging_errors(base) == []  # auto knobs always admissible
    # no engine => nothing to validate
    assert paging.paging_errors(base.replace(decode_engine=False,
                                             kv_block_size=5)) == []
    # the whole-sequence arena is gone: asking for it is refused by name,
    # with or without the engine; the batched beam's knobs do not reach
    # the engine's arena at all
    for asked in (base, base.replace(decode_engine=False)):
        errs = paging.paging_errors(asked.replace(engine_paged_kv=False))
        assert len(errs) == 1 and errs[0].startswith("engine_paged_kv False")
    assert paging.paging_errors(base.replace(beam_kv_cache=False,
                                             beam_factored_topk=True)) == []
    errs = paging.paging_errors(base.replace(beam_kv_cache=False,
                                             kv_block_size=5))
    assert len(errs) == 1 and "kv_block_size 5" in errs[0]

    errs = paging.paging_errors(base.replace(kv_block_size=5))
    assert len(errs) == 1 and "does not divide decode tar budget 12" in errs[0]

    # under decode_tar_buckets every bucket tar joins the declared set
    tarred = base.replace(buckets=((16, 400, 8),), decode_tar_buckets=True)
    errs = paging.paging_errors(tarred.replace(kv_block_size=6))
    assert len(errs) == 1 and "budget 8" in errs[0]
    assert paging.paging_errors(tarred.replace(kv_block_size=4)) == []

    # pool floors: slots x ceil(smallest tar / block), then one worst-case
    # sample (the no-livelock floor); fira_tiny test_batch_size=8, W=2
    errs = paging.paging_errors(base.replace(kv_pool_blocks=10))
    assert len(errs) == 1 and "every slot servable" in errs[0]
    errs = paging.paging_errors(base.replace(engine_slots=1,
                                             kv_pool_blocks=1))
    assert any("livelock" in e for e in errs)
    assert paging.paging_errors(base.replace(kv_pool_blocks=16)) == []

    # the fleet splits the pool TOTAL evenly, like engine_slots
    errs = paging.paging_errors(base.replace(engine_replicas=2,
                                             kv_pool_blocks=7))
    assert len(errs) == 1 and "engine_replicas 2" in errs[0]
    assert paging.paging_errors(base.replace(engine_replicas=2,
                                             engine_slots=8,
                                             kv_pool_blocks=16)) == []


def test_cli_exits_2_on_paging_knobs(setup, tmp_path, monkeypatch, capsys):
    """Parse-time rejection with named-knob messages — not a mid-run
    shape error (the exit-2 contract of parallel.mesh/fleet)."""
    from fira_tpu import cli, config

    _cfg, _dataset, data_dir, _params = setup
    base = ["test", "--data-dir", data_dir, "--config", "fira-tiny",
            "--engine", "--out-dir", str(tmp_path / "o")]
    assert cli.main(base + ["--kv-block-size", "5"]) == 2
    assert cli.main(base + ["--kv-pool-blocks", "10"]) == 2
    assert cli.main(base + ["--engine-replicas", "2",
                            "--kv-pool-blocks", "7"]) == 2
    # the CLI has no flag for the arena any more (argparse's own exit 2)
    with pytest.raises(SystemExit) as e:
        cli.main(base + ["--kv-paged", "off"])
    assert e.value.code == 2
    # a configuration that still asks for the whole-sequence arena is
    # refused at parse time, by the knob's name
    monkeypatch.setitem(
        config.NAMED_CONFIGS, "fira-tiny-unpaged",
        lambda **kw: fira_tiny(engine_paged_kv=False, **kw))
    capsys.readouterr()
    base[base.index("fira-tiny")] = "fira-tiny-unpaged"
    assert cli.main(base) == 2
    assert "engine_paged_kv False" in capsys.readouterr().err
    # valid knobs get PAST parse-time validation: the run then fails on
    # the missing checkpoint (rc 1)
    base[base.index("fira-tiny-unpaged")] = "fira-tiny"
    assert cli.main(base + ["--kv-block-size", "3"]) == 1


def test_fleet_pool_split_per_replica(setup):
    cfg0, _dataset, _dir, params = setup
    cfg = dataclasses.replace(cfg0, decode_engine=True)
    model = FiraModel(cfg)
    fleet = fleet_lib.EngineFleet(
        model, params, dataclasses.replace(cfg, kv_pool_blocks=8),
        replicas=2)
    assert [e._pool_blocks for e in fleet.engines] == [4, 4]
    with pytest.raises(ValueError, match="kv_pool_blocks 7"):
        fleet_lib.EngineFleet(
            model, params, dataclasses.replace(cfg, kv_pool_blocks=7),
            replicas=2)
    # the parse-time split check is OWNED by paging_errors (the CLI runs
    # it right after fleet_divisibility_errors, which must NOT duplicate
    # the message)
    bad = dataclasses.replace(cfg, engine_replicas=2, kv_pool_blocks=7)
    assert [e for e in fleet_lib.fleet_divisibility_errors(bad)
            if "kv_pool_blocks" in e] == []
    assert any("kv_pool_blocks 7" in e for e in paging.paging_errors(bad))
