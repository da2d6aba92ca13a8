"""The edge ladder (data/buckets.edge_ladder; docs/BUCKETING.md "The
default: the edge ladder"): with ``cfg.buckets = ()`` a TRAIN dispatch
pads its COO rows to the least rung of ``max_edges / 2^k`` that holds its
commits — AST tail and target length full — while the decode table stays
the full geometry alone.

Pinned here: the ladder of each preset; the plan puts every group on the
least rung that holds its widest commit, and one fused dispatch at that
rung equals full pad's bit for bit (K losses, parameters), by the ladder
and by the same rung declared; the decode side is untouched; train()
declares exactly the populated rungs and an undeclared rung still raises;
a commit over ``max_edges`` still raises in ``make_batch``; the feeder's
``edge_slots`` / ``edges`` on the span and in ``stats()``; the split's
extents are measured once.
"""

import time

import numpy as np
import pytest

import jax

from fira_tpu.analysis import sanitizer
from fira_tpu.config import fira_full, fira_large, fira_tiny
from fira_tpu.data import buckets as B
from fira_tpu.data import grouping as G
from fira_tpu.data.batching import make_batch
from fira_tpu.data.feeder import Feeder
from fira_tpu.data.synthetic import make_memory_split
from fira_tpu.model.model import FiraModel
from fira_tpu.train import step as step_lib
from fira_tpu.train.state import init_state
from fira_tpu.utils import profiling


@pytest.fixture(scope="module")
def corpus():
    # the synthetic commits of fira-tiny carry 146-170 edges: one rung
    # (256) of the preset's ladder, two (160, 320) of a 320-slot bound
    cfg, split, _ = make_memory_split(fira_tiny(), 48, seed=11)
    return cfg, split


@pytest.mark.parametrize("preset,rungs", [
    (fira_full, (768, 1536, 3072, 6144)),
    (fira_large, (768, 1536, 3072, 6144)),
    (fira_tiny, (128, 256, 512)),
], ids=["fira-full", "fira-large", "fira-tiny"])
def test_ladder_of_each_preset(preset, rungs):
    cfg = preset()
    ladder = B.edge_ladder(cfg)
    assert tuple(g.max_edges for g in ladder) == rungs
    assert ladder[-1] == B.full_geom(cfg)
    for g in ladder:
        assert B._validated(cfg, g) == g
        # the AST tail and the target length are the user's table to cut
        assert (g.ast_len, g.tar_len) == (cfg.ast_change_len, cfg.tar_len)
    # the next halving would not hold the geometry's self-loops
    assert ladder[0].max_edges // 2 < cfg.graph_len
    # training takes it where no table is declared, and only there
    assert B.train_table(cfg) == ladder
    declared = cfg.replace(buckets=((cfg.ast_change_len // 2,
                                     cfg.max_edges // 2, cfg.tar_len),))
    assert B.train_table(declared) == B.bucket_table(declared)


def test_ladder_of_an_odd_bound_and_of_a_bound_under_the_floor():
    cfg = fira_tiny(max_edges=333)          # floor: 80 self-loops
    assert [g.max_edges for g in B.edge_ladder(cfg)] == [83, 166, 333]
    tight = fira_tiny(max_edges=100)
    assert B.edge_ladder(tight) == (B.full_geom(tight),)


def test_plan_puts_every_group_on_the_least_rung(corpus):
    cfg0, split = corpus
    cfg = cfg0.replace(max_edges=320)       # rungs 80, 160, 320
    ext = B.sample_extents(split, cfg)
    assert ext.edges.min() <= 160 < ext.edges.max(), "two rungs populated"
    plan = G.grouped_plan(split, cfg, batch_size=4, group_size=2,
                          shuffle=True, seed=3, epoch=0)
    assert {e.geom.max_edges for e in plan} == {160, 320}
    assert any(e.pad_to == 2 for e in plan)
    cover = np.sort(np.concatenate([c for e in plan for c in e.chunks]))
    np.testing.assert_array_equal(cover, np.arange(len(split)))
    for e in plan:
        assert e.geom in B.edge_ladder(cfg)
        for c in e.chunks:
            edges = ext.edges[c]
            # bucket-homogeneous: each commit is on ITS least rung
            assert (edges <= e.geom.max_edges).all()
            assert (edges > e.geom.max_edges // 2).all()
    assert G.plan_programs(plan) == sorted(
        {(e.geom, e.pad_to) for e in plan})
    # a declared table still rules where there is one
    declared = cfg.replace(buckets=((16, 256, 8),))
    plan_d = G.grouped_plan(split, declared, batch_size=4, group_size=2,
                            shuffle=True, seed=3, epoch=0)
    assert {e.geom for e in plan_d} <= set(B.bucket_table(declared))


@pytest.fixture(scope="module")
def fused(corpus):
    """ONE jitted K = 2 device loop and its start state, shared by both
    routes to the rung (jit keys its programs by shape)."""
    cfg, split = corpus
    model = FiraModel(cfg)
    state = init_state(model, cfg,
                       make_batch(split, np.arange(4), cfg, batch_size=4))
    return state, jax.jit(step_lib.make_multi_step(model, cfg))


@pytest.mark.parametrize("route", ["ladder", "declared"])
def test_one_fused_dispatch_equals_full_pad_bit_for_bit(corpus, fused,
                                                        route):
    cfg0, split = corpus
    state, multi = fused
    rung = B.BucketGeom(cfg0.ast_change_len, 256, cfg0.tar_len)
    cfg = cfg0 if route == "ladder" else cfg0.replace(buckets=(tuple(rung),))
    plan = G.grouped_plan(split, cfg, batch_size=4, group_size=2,
                          shuffle=True, seed=3, epoch=0)
    entry = plan[0]
    assert entry.pad_to == 2 and entry.geom == rung
    (task,) = G.grouped_assembly_tasks(split, [entry], cfg, batch_size=4)
    short = {k: v for k, v in task().items() if not k.startswith("_")}
    full = G.stack_group([make_batch(split, c, cfg0, batch_size=4)
                          for c in entry.chunks])
    assert short["senders"].shape == (2, 4, 256)
    assert full["senders"].shape == (2, 4, cfg0.max_edges)
    for k in full:
        if k not in ("senders", "receivers", "values"):
            np.testing.assert_array_equal(short[k], full[k])
    s_short, m_short = multi(state, short)
    s_full, m_full = multi(state, full)
    np.testing.assert_array_equal(np.asarray(m_short["loss"]),
                                  np.asarray(m_full["loss"]))
    assert np.asarray(m_short["loss"]).shape == (2,)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b),
        jax.device_get(s_short.params), jax.device_get(s_full.params))


def test_decode_side_is_the_full_geometry_alone(corpus):
    from fira_tpu.decode.engine import SlotEngine
    from fira_tpu.decode.runner import _decode_tasks

    cfg, split = corpus
    full = B.full_geom(cfg)
    assert B.decode_table(cfg) == B.bucket_table(cfg) == (full,)
    tasks, table = _decode_tasks(split, cfg)
    assert table is None
    for task in tasks:
        batch = task()
        assert batch["senders"].shape == (cfg.test_batch_size, cfg.max_edges)
        assert "_tag" not in batch
    # the engine's prefill is lowered for that wire: max_edges slots a row
    model = FiraModel(cfg)
    warm = make_batch(split, np.arange(0), cfg,
                      batch_size=cfg.test_batch_size)
    params = init_state(model, cfg, warm).params
    eng = SlotEngine(model, params, cfg, slots=4)
    text = eng._prefill.lower(eng.params, warm).as_text()
    assert f"tensor<{cfg.test_batch_size}x{cfg.max_edges}xi16>" in text
    assert f"x{cfg.max_edges // 2}xi16>" not in text


def test_train_declares_the_populated_rungs_and_no_other(tmp_path):
    from fira_tpu.data.dataset import FiraDataset
    from fira_tpu.data.synthetic import write_corpus_dir
    from fira_tpu.train.loop import train

    data_dir = str(tmp_path / "corpus")
    write_corpus_dir(data_dir, n_commits=28, seed=7)
    cfg = fira_tiny(epochs=1, batch_size=4, test_batch_size=4,
                    dev_start_epoch=99, max_edges=320)
    ds = FiraDataset(data_dir, cfg)
    split = ds.splits["train"]
    table = B.edge_ladder(ds.cfg)
    assert [g.max_edges for g in table] == [80, 160, 320]
    populated = {table[b] for b in np.unique(B.assign_buckets(
        B.sample_extents(split, ds.cfg), table))}
    assert populated == {table[1], table[2]}, "fixture: two of three rungs"
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        result = train(ds, ds.cfg, out_dir=str(tmp_path / "out"),
                       ckpt_dir=str(tmp_path / "ckpt"), epochs=1,
                       resume=False, guard=guard)
    assert result.epochs_run == 1
    assert guard.compiles_after_warmup() == 0
    assert guard._declared == {"dev_step"} | {
        f"train_step[{B.geom_tag(g)}]" for g in populated}
    # every dispatch carried its rung's label, each warmed before the epoch
    steps = {k: v for k, v in guard._seen.items()
             if k.startswith("train_step")}
    assert set(steps) == {f"train_step[{B.geom_tag(g)}]" for g in populated}
    assert all(n >= 2 for n in steps.values())
    # the unpopulated rung was never compiled and a dispatch on it raises
    with pytest.raises(sanitizer.RetraceError, match="declared"):
        guard.step(f"train_step[{B.geom_tag(table[0])}]")
    # the fill counter: the run's dispatches shipped their rungs' slots
    fed = result.feeder
    assert 0 < fed["edges"] <= fed["edge_slots"]
    assert fed["edge_slots"] % 4 == 0
    assert fed["edge_slots"] < fed["batches"] * 4 * 320     # not full pad
    assert fed["edges"] / fed["edge_slots"] > 0.5


def test_a_commit_over_max_edges_still_raises(corpus):
    cfg0, split = corpus
    ext = B.sample_extents(split, cfg0)
    bound = int(np.median(ext.edges))
    cfg = cfg0.replace(max_edges=bound)
    over = np.where(ext.edges > bound)[0]
    assert len(over), "fixture: commits over the bound"
    table = B.edge_ladder(cfg)
    # no rung admits it: it falls to the last one, the admission bound
    assert (B.assign_buckets(ext, table)[over] == len(table) - 1).all()
    plan = G.grouped_plan(split, cfg, batch_size=4, group_size=1)
    raised = 0
    for entry, task in zip(plan, G.grouped_assembly_tasks(
            split, plan, cfg, batch_size=4)):
        if np.isin(entry.chunks[0], over).any():
            with pytest.raises(ValueError, match="> max_edges"):
                task()
            raised += 1
        else:
            task()
    assert raised


def test_feeder_counts_the_edge_slots_it_ships(corpus):
    cfg0, split = corpus
    cfg = cfg0.replace(max_edges=320)
    ext = B.sample_extents(split, cfg)
    plan = G.grouped_plan(split, cfg, batch_size=4, group_size=2,
                          shuffle=True, seed=3, epoch=0)
    mark = time.perf_counter()     # by time: a full ring keeps its length
    with Feeder(G.grouped_assembly_tasks(split, plan, cfg, batch_size=4),
                num_workers=2, depth=3, put=False) as feed:
        items = list(feed)
        stats = feed.stats()
    spans = sorted((e for e in profiling.events()
                    if e.name == "feeder.assemble" and e.t_start >= mark),
                   key=lambda e: e.t_start)
    assert len(spans) == len(items) == len(plan)
    want = {}
    for entry, item in zip(plan, items):
        slots = max(1, entry.pad_to) * 4 * entry.geom.max_edges
        edges = int(sum(ext.edges[c].sum() for c in entry.chunks))
        assert (item.edge_slots, item.edges) == (slots, edges)
        assert item.host["senders"].size == slots
        want[(slots, edges)] = want.get((slots, edges), 0) + 1
    got = {}
    for e in spans:
        key = (e.ids["edge_slots"], e.ids["edges"])
        got[key] = got.get(key, 0) + 1
    assert got == want
    assert stats["edge_slots"] == sum(i.edge_slots for i in items)
    assert stats["edges"] == ext.edges.sum() == sum(i.edges for i in items)
    # at the admission bound the same commits fill far less of the wire
    full_slots = len(plan) and sum(
        max(1, e.pad_to) * 4 * cfg.max_edges for e in plan)
    assert stats["edge_slots"] < full_slots
    # a batch without edges (a token model's prompts) counts nothing
    lm_batch = {"tokens": np.zeros((2, 8), np.int32),
                "valid": np.ones((2,), bool)}
    with Feeder([lambda: lm_batch], num_workers=0, put=False) as feed:
        (item,) = list(feed)
        assert (item.edge_slots, item.edges) == (0, 0)
        assert feed.stats()["edge_slots"] == 0.0


def test_extents_are_measured_once_a_split(corpus, monkeypatch):
    cfg, _ = corpus
    _, split, _ = make_memory_split(fira_tiny(), 16, seed=5)  # a fresh split
    calls = []
    measure = B._measure_extents
    monkeypatch.setattr(B, "_measure_extents",
                        lambda s, c: calls.append(1) or measure(s, c))
    for epoch in range(3):
        G.grouped_plan(split, cfg, batch_size=4, group_size=2, shuffle=True,
                       seed=1, epoch=epoch)
    assert len(calls) == 1
    assert B.sample_extents(split, cfg) is B.sample_extents(split, cfg)
    # another node layout of the same split is another measurement
    B.sample_extents(split, cfg.replace(ast_change_len=16))
    assert len(calls) == 2
