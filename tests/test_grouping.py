"""Grouped bucket-homogeneous dispatch (data/grouping.py).

The composition contract of ISSUE 4, pinned:

1. SCHEDULING — the grouped plan is a pure function of (seed, epoch,
   bucket table, group size, accum); chunk FORMATION is group-size
   invariant; ``group_size == 1`` reproduces the legacy packers exactly
   (bucketed: ``buckets.packed_plan``; unbucketed: ``epoch_index_chunks``);
   fused tails fall back to per-step entries, accum tails pad to the
   stacked shape; the feeder delivers the identical stream for any worker
   count.
2. BIT-EXACTNESS — grouped-bucketed training (fused lax.scan over
   bucket-homogeneous K-stacks) produces BITWISE-identical params,
   per-step losses, and per-sample losses to per-step bucketed dispatch of
   the same chunk stream (which is already bit-exact against full pad,
   tests/test_buckets.py); the accum tail's all-invalid micro-batches
   contribute nothing at bucket geometry just as they don't at full pad.
3. COMPILE DISCIPLINE — train() with buckets x fused pre-warms the whole
   (geometry x entrypoint x group-size) family and runs a gated epoch with
   ZERO post-warmup compiles under the sanitizer; the divisibility footgun
   warns loudly; --profile-dir profiles the REAL grouped program.
"""

import os

import numpy as np
import pytest

import jax

from fira_tpu.analysis import sanitizer
from fira_tpu.config import fira_tiny
from fira_tpu.data import buckets as B
from fira_tpu.data import grouping as G
from fira_tpu.data.batching import epoch_index_chunks, make_batch
from fira_tpu.data.feeder import Feeder
from fira_tpu.data.synthetic import make_memory_split
from fira_tpu.model.model import FiraModel
from fira_tpu.train import step as step_lib
from fira_tpu.train.state import init_state


@pytest.fixture(scope="module")
def corpus():
    cfg, split, _ = make_memory_split(fira_tiny(), 48, seed=11)
    return cfg, split


# two buckets + the full fallback, same family test_buckets.py exercises —
# tight enough that the fallback gets members at this corpus
TABLE_SPEC = ((8, 192, 8), (16, 256, 8))


def _table(cfg):
    return B.bucket_table(cfg.replace(buckets=TABLE_SPEC))


def test_grouped_plan_determinism_coverage_and_tails(corpus):
    cfg, split = corpus
    table = _table(cfg)
    for gs, accum in ((1, False), (2, False), (3, False), (2, True)):
        p1 = G.grouped_plan(split, cfg, batch_size=8, group_size=gs,
                            accum=accum, shuffle=True, seed=3, epoch=2,
                            table=table)
        p2 = G.grouped_plan(split, cfg, batch_size=8, group_size=gs,
                            accum=accum, shuffle=True, seed=3, epoch=2,
                            table=table)
        assert len(p1) == len(p2)
        for a, b in zip(p1, p2):
            assert a.geom == b.geom and a.pad_to == b.pad_to
            assert len(a.chunks) == len(b.chunks)
            for c1, c2 in zip(a.chunks, b.chunks):
                np.testing.assert_array_equal(c1, c2)
        # every sample exactly once, whatever the grouping
        cover = np.sort(np.concatenate([c for e in p1 for c in e.chunks]))
        np.testing.assert_array_equal(cover, np.arange(len(split)))
        # shape rules: fused groups are exactly gs full chunks; fused
        # leftovers (and everything at gs=1) are per-step; accum entries
        # are all stacked to gs with at most one short (tail) group/bucket
        for e in p1:
            if accum:
                assert e.pad_to == gs and 1 <= len(e.chunks) <= gs
            elif e.pad_to > 1:
                assert len(e.chunks) == e.pad_to == gs
                assert all(len(c) == 8 for c in e.chunks)
            else:
                assert len(e.chunks) == 1


def test_chunk_formation_is_group_size_invariant(corpus):
    """Grouping only PACKAGES chunks — the sample->chunk walk is identical
    for every group size (the determinism half of the composition
    contract: same (seed, epoch) sample stream for any group size)."""
    cfg, split = corpus
    table = _table(cfg)

    def by_bucket(plan):
        out = {}
        for e in plan:
            out.setdefault(e.geom, []).extend(
                tuple(c.tolist()) for c in e.chunks)
        return out

    plans = [G.grouped_plan(split, cfg, batch_size=8, group_size=gs,
                            accum=accum, shuffle=True, seed=3, epoch=0,
                            table=table)
             for gs, accum in ((1, False), (2, False), (4, False),
                               (2, True))]
    ref = by_bucket(plans[0])
    for p in plans[1:]:
        assert by_bucket(p) == ref


def test_group_size_one_reproduces_legacy_packers(corpus):
    cfg, split = corpus
    table = _table(cfg)
    # bucketed: the packed_plan greedy walk, entry for entry
    ref = B.packed_plan(split, cfg, batch_size=8, shuffle=True, seed=3,
                        epoch=2, table=table)
    new = G.grouped_plan(split, cfg, batch_size=8, group_size=1,
                         shuffle=True, seed=3, epoch=2, table=table)
    assert len(ref) == len(new)
    for (c, g), e in zip(ref, new):
        assert e.geom == g and e.pad_to == 1 and len(e.chunks) == 1
        np.testing.assert_array_equal(e.chunks[0], c)
    # ONE geometry (the full one, as a table of its own): the sequential
    # epoch chunking, the wire fields byte-identical to plain make_batch
    chunks = epoch_index_chunks(len(split), cfg, batch_size=8, shuffle=True,
                                seed=5, epoch=1)
    plan = G.grouped_plan(split, cfg, batch_size=8, group_size=1,
                          shuffle=True, seed=5, epoch=1,
                          table=(B.full_geom(cfg),))
    assert len(plan) == len(chunks)
    tasks = list(G.grouped_assembly_tasks(split, plan, cfg, batch_size=8))
    for task, c in zip(tasks, chunks):
        got = task()
        want = make_batch(split, c, cfg, batch_size=8)
        # the host-only fields ride along (the feeder strips them)
        assert set(got) - set(want) == {"_positions", "_tag"}
        assert got["_tag"] == B.geom_tag(B.full_geom(cfg))
        np.testing.assert_array_equal(got["_positions"], c)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])
            assert got[k].dtype == want[k].dtype
    # cfg.buckets = () WITHOUT a table is the edge ladder, not full pad:
    # the same samples, every chunk on the least rung that holds its
    # widest commit, AST and target axes full
    ladder = G.grouped_plan(split, cfg, batch_size=8, group_size=1,
                            shuffle=True, seed=5, epoch=1)
    ext = B.sample_extents(split, cfg)
    np.testing.assert_array_equal(
        np.sort(np.concatenate([e.chunks[0] for e in ladder])),
        np.arange(len(split)))
    for e in ladder:
        assert e.geom in B.edge_ladder(cfg)
        assert (e.geom.ast_len, e.geom.tar_len) == (cfg.ast_change_len,
                                                    cfg.tar_len)
        widest = int(ext.edges[e.chunks[0]].max())
        assert e.geom.max_edges // 2 < widest <= e.geom.max_edges


def test_feeder_stream_identical_across_worker_counts(corpus):
    cfg, split = corpus
    table = _table(cfg)
    plan = G.grouped_plan(split, cfg, batch_size=8, group_size=2,
                          shuffle=True, seed=3, epoch=0, table=table)

    def stream(workers):
        tasks = G.grouped_assembly_tasks(split, plan, cfg, batch_size=8)
        with Feeder(tasks, num_workers=workers, depth=3, put=False) as feed:
            return [item.host for item in feed]

    a = stream(0)
    b = stream(2)
    assert len(a) == len(b) == len(plan)
    for ba, bb in zip(a, b):
        assert set(ba) == set(bb)
        for k in ba:
            if k == "_tag":
                assert ba[k] == bb[k]
            else:
                np.testing.assert_array_equal(ba[k], bb[k])
    # stacked items carry the 2-D valid the loop keys grouped dispatch on
    assert any(item["valid"].ndim == 2 for item in a)


def test_grouped_fused_bit_exact_vs_per_step_bucketed(corpus):
    """The acceptance pin: grouped-bucketed training (bucket-homogeneous
    K-stacks through the fused lax.scan) is BIT-exact — params, per-step
    losses, per-sample losses — against per-step bucketed dispatch of the
    same chunk stream."""
    cfg0, split = corpus
    cfg = cfg0.replace(buckets=TABLE_SPEC)
    table = B.bucket_table(cfg)
    plan = G.grouped_plan(split, cfg, batch_size=8, group_size=2,
                          shuffle=True, seed=3, epoch=0, table=table)
    assert any(e.pad_to > 1 for e in plan), "plan must contain groups"
    assert any(e.pad_to == 1 for e in plan), "plan must contain tails"

    model = FiraModel(cfg)
    state0 = init_state(model, cfg,
                        make_batch(split, np.arange(8), cfg, batch_size=8))
    step = jax.jit(step_lib.make_train_step(model, cfg))
    multi = jax.jit(step_lib.make_multi_step(model, cfg))

    s_seq, losses_seq = state0, []
    for e in plan:
        for c in e.chunks:
            s_seq, m = step(s_seq, make_batch(split, c, cfg, batch_size=8,
                                              geom=e.geom))
            losses_seq.append(float(m["loss"]))

    s_grp, losses_grp = state0, []
    for e in plan:
        if e.pad_to > 1:
            stacked = G.stack_group(
                [make_batch(split, c, cfg, batch_size=8, geom=e.geom)
                 for c in e.chunks], pad_to=e.pad_to)
            s_grp, m = multi(s_grp, stacked)
            losses_grp.extend(
                np.asarray(jax.device_get(m["loss"])).tolist())
        else:
            s_grp, m = step(s_grp, make_batch(split, e.chunks[0], cfg,
                                              batch_size=8, geom=e.geom))
            losses_grp.append(float(m["loss"]))

    np.testing.assert_array_equal(np.asarray(losses_seq),
                                  np.asarray(losses_grp))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b),
        jax.device_get(s_seq.params), jax.device_get(s_grp.params))
    # per-sample losses under the final params: one deterministic program,
    # both paths' params produce float-identical values per sample
    probe = make_batch(split, np.arange(4), cfg, batch_size=4)
    for i in range(2):
        row = {k: v[i : i + 1] for k, v in probe.items()}
        nll_a, cnt_a = model.apply({"params": s_seq.params}, row,
                                   deterministic=True)
        nll_b, cnt_b = model.apply({"params": s_grp.params}, row,
                                   deterministic=True)
        assert float(nll_a) == float(nll_b) and float(cnt_a) == float(cnt_b)


def test_grouped_accum_tail_pads_all_invalid_at_bucket_geometry(corpus):
    """An accum tail group at bucket geometry: the all-invalid pad
    micro-batches contribute nothing, so the padded A-stack takes EXACTLY
    the optimizer step the plain per-step program takes on the real batch
    alone — same (sum, count) normalization, now at bucket geometry (the
    full-pad version of this pin is
    test_accum_tail_padding_matches_plain_step)."""
    cfg0, split = corpus
    cfg = cfg0.replace(dropout_rate=0.0, gcn_dropout_rate=0.0,
                       buckets=TABLE_SPEC)
    table = B.bucket_table(cfg)
    ext = B.sample_extents(split, cfg)
    geom = table[1]
    members = np.where(B.assign_buckets(ext, table) <= 1)[0][:8]
    real = make_batch(split, members, cfg, batch_size=8, geom=geom)

    model = FiraModel(cfg)
    state = init_state(model, cfg, real)
    accum = jax.jit(step_lib.make_accum_step(model, cfg))
    s_pad, m_pad = accum(state, G.stack_group([real], pad_to=3))

    plain = jax.jit(step_lib.make_train_step(model, cfg))
    s_plain, m_plain = plain(state, real)
    np.testing.assert_allclose(float(m_pad["loss"]), float(m_plain["loss"]),
                               rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-3, atol=1e-5),
        jax.device_get(s_pad.params), jax.device_get(s_plain.params))


@pytest.fixture(scope="module")
def tiny_dataset(tmp_path_factory):
    from fira_tpu.data.dataset import FiraDataset
    from fira_tpu.data.synthetic import write_corpus_dir

    data_dir = str(tmp_path_factory.mktemp("grouping_corpus"))
    write_corpus_dir(data_dir, n_commits=28, seed=7)
    cfg = fira_tiny(epochs=1, batch_size=8, test_batch_size=4,
                    dev_start_epoch=0, dev_every_batches=4)
    return FiraDataset(data_dir, cfg)


def test_train_fused_buckets_zero_retraces_and_profiles_real_program(
        tiny_dataset, tmp_path):
    """End-to-end composition: train() with buckets x fused_steps pre-warms
    the (geometry x entrypoint x group) family, runs a dev-gated epoch with
    ZERO post-warmup compiles, and --profile-dir traces the REAL grouped
    program (no silent per-step downgrade) while recording the grouped-
    annotation note in TrainResult.warnings."""
    from fira_tpu.train.loop import train

    ds = tiny_dataset
    cfg = ds.cfg.replace(buckets=((16, 256, 8),), fused_steps=2)
    profile_dir = str(tmp_path / "trace")
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        result = train(ds, cfg, out_dir=str(tmp_path / "out"),
                       ckpt_dir=str(tmp_path / "ckpt"), epochs=1,
                       resume=False, guard=guard, profile_dir=profile_dir,
                       profile_steps=2)
    assert result.epochs_run == 1
    assert guard.compiles_after_warmup() == 0
    seen = set(guard._seen)
    # the family has all three entry points, grouped labels carry (geom, K)
    assert any(lbl.startswith("grouped_step[") and lbl.endswith(".g2]")
               for lbl in seen)
    assert any(lbl.startswith("train_step[") for lbl in seen)
    assert any(lbl.startswith("dev_step[") for lbl in seen)
    # an undeclared (geom, K) member raises at its dispatch
    with pytest.raises(sanitizer.RetraceError, match="declared"):
        guard.step(sanitizer.program_label("grouped_step", "a99.e999.t99", 2))
    # the real grouped program was profiled: trace written, note recorded
    assert os.path.isdir(profile_dir) and os.listdir(profile_dir)
    assert any("grouped" in w for w in result.warnings)


def test_train_accum_buckets_composes(tiny_dataset, tmp_path):
    """accum_steps x buckets: one optimizer step per bucket-homogeneous
    A-group (tails padded all-invalid), zero post-warmup compiles, and the
    per-step train program is never dispatched."""
    from fira_tpu.train.loop import train

    ds = tiny_dataset
    cfg = ds.cfg.replace(buckets=((16, 256, 8),), accum_steps=2,
                         dev_start_epoch=99)
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        result = train(ds, cfg, out_dir=str(tmp_path / "out"),
                       ckpt_dir=str(tmp_path / "ckpt"), epochs=1,
                       resume=False, guard=guard)
    assert result.epochs_run == 1
    assert guard.compiles_after_warmup() == 0
    assert not any(lbl.startswith("train_step") for lbl in guard._seen)
    assert all(lbl.endswith(".g2]") for lbl in guard._seen
               if lbl.startswith("grouped_step"))


def test_fused_cadence_divisibility_warns(tiny_dataset, tmp_path):
    """fused_steps not dividing dev_every_batches is the documented
    gate-staleness footgun (config.py) — train() must warn loudly and
    record it in TrainResult.warnings (epochs=0 keeps this compile-free)."""
    from fira_tpu.train.loop import train

    ds = tiny_dataset
    cfg = ds.cfg.replace(fused_steps=3, dev_every_batches=4)
    result = train(ds, cfg, out_dir=str(tmp_path / "out"),
                   ckpt_dir=str(tmp_path / "ckpt"), epochs=0, resume=False)
    assert any("does not divide" in w for w in result.warnings)
    cfg_ok = ds.cfg.replace(fused_steps=2, dev_every_batches=4)
    result_ok = train(ds, cfg_ok, out_dir=str(tmp_path / "out2"),
                      ckpt_dir=str(tmp_path / "ckpt2"), epochs=0,
                      resume=False)
    assert not any("does not divide" in w for w in result_ok.warnings)
