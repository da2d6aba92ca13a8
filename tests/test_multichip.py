"""Multi-chip composition of the production train stack (ISSUE 6).

Runs on the virtual 8-device CPU mesh (tests/conftest.py). Pins:

- the correctness contract: train() on an n_data=1 mesh is BITWISE equal
  (params + per-sample losses) to the single-chip path;
- the compile discipline: buckets x fused x mesh pre-warms the whole
  (geometry x entrypoint x K x mesh) family, then ZERO post-warmup
  compiles under the armed sanitizer;
- the feeder contract: the deterministic (seed, epoch) grouped stream is
  byte-stable across worker counts AND mesh sizes (n_data in {1, 2, 4}),
  and the shared sharding callable (parallel.mesh.feed_shardings) routes
  mixed-geometry bucketed streams to the right per-item sharding on a
  2-device mesh, with per-shard slices equal to the host rows;
- the parse-time divisibility gate: named-bucket errors from
  parallel.mesh.divisibility_errors, ValueError from train(), exit 2
  from the CLI.
"""

import numpy as np
import pytest

import jax

from fira_tpu.analysis import sanitizer
from fira_tpu.config import fira_tiny
from fira_tpu.data import buckets as B
from fira_tpu.data import grouping as G
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.feeder import Feeder
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.model.model import FiraModel
from fira_tpu.parallel import mesh as pmesh
from fira_tpu.train.loop import train

TABLE_SPEC = ((8, 192, 8), (16, 256, 8))


@pytest.fixture(scope="module")
def tiny_dataset(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("multichip_corpus"))
    write_corpus_dir(data_dir, n_commits=28, seed=9)
    cfg = fira_tiny(epochs=1, batch_size=8, test_batch_size=4,
                    dev_start_epoch=99)
    return FiraDataset(data_dir, cfg)


def _per_sample_losses(model, params, dataset, n=3):
    from fira_tpu.data.batching import make_batch

    probe = make_batch(dataset.splits["train"], np.arange(n),
                       dataset.cfg, batch_size=n)
    out = []
    for i in range(n):
        row = {k: v[i : i + 1] for k, v in probe.items()}
        nll, cnt = model.apply({"params": params}, row, deterministic=True)
        out.append((float(nll), float(cnt)))
    return out


def test_mesh_n_data1_bitwise_equals_single_chip(tiny_dataset, tmp_path):
    """THE acceptance pin: the n_data=1 mesh path reproduces the
    single-chip grouped path bitwise — params and per-sample losses."""
    ds = tiny_dataset
    cfg = ds.cfg.replace(buckets=TABLE_SPEC, fused_steps=2)
    ref = train(ds, cfg, out_dir=str(tmp_path / "a"),
                ckpt_dir=str(tmp_path / "ca"), epochs=1, resume=False)
    mesh = pmesh.make_mesh(n_data=1, n_model=1)
    got = train(ds, cfg, mesh=mesh, out_dir=str(tmp_path / "b"),
                ckpt_dir=str(tmp_path / "cb"), epochs=1, resume=False)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        jax.device_get(ref.state.params), jax.device_get(got.state.params))
    model = FiraModel(ds.cfg)
    assert (_per_sample_losses(model, jax.device_get(ref.state.params), ds)
            == _per_sample_losses(model, jax.device_get(got.state.params),
                                  ds))


def test_mesh_grouped_buckets_zero_retraces(tiny_dataset, tmp_path):
    """buckets x fused x 2-device mesh: the pre-warmed (geometry x
    entrypoint x K) family runs a full epoch with ZERO post-warmup
    compiles, sharded groups and all."""
    ds = tiny_dataset
    cfg = ds.cfg.replace(buckets=((16, 256, 8),), fused_steps=2)
    mesh = pmesh.make_mesh(n_data=2, n_model=1)
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        result = train(ds, cfg, mesh=mesh, out_dir=str(tmp_path / "out"),
                       ckpt_dir=str(tmp_path / "ckpt"), epochs=1,
                       resume=False, guard=guard)
    assert result.epochs_run == 1
    assert guard.compiles_after_warmup() == 0
    assert any(lbl.startswith("grouped_step[") for lbl in guard._seen)


def test_feeder_stream_byte_stable_across_workers_and_mesh_sizes(
        tiny_dataset):
    """The per-shard determinism contract: one (seed, epoch) grouped
    stream, byte-identical for any worker count and any n_data — the mesh
    only changes WHERE rows land, never which rows ship in which order."""
    ds = tiny_dataset
    cfg = ds.cfg.replace(buckets=TABLE_SPEC)
    split = ds.splits["train"]
    table = B.bucket_table(cfg)
    plan = G.grouped_plan(split, cfg, batch_size=8, group_size=2,
                          shuffle=True, seed=5, epoch=1, table=table)

    def stream(workers, n_data):
        mesh = (pmesh.make_mesh(n_data=n_data, n_model=1)
                if n_data else None)
        tasks = G.grouped_assembly_tasks(split, plan, cfg, batch_size=8)
        with Feeder(tasks, num_workers=workers, depth=3,
                    sharding=pmesh.feed_shardings(mesh)) as feed:
            return [item.host for item in feed]

    ref = stream(0, 0)
    for workers, n_data in ((2, 1), (0, 2), (2, 4)):
        got = stream(workers, n_data)
        assert len(got) == len(ref) == len(plan)
        for ba, bb in zip(ref, got):
            assert set(ba) == set(bb)
            for k in ba:
                if k == "_tag":
                    assert ba[k] == bb[k]
                else:
                    np.testing.assert_array_equal(ba[k], bb[k])


def test_feed_shardings_mixed_geometry_on_two_device_mesh(tiny_dataset):
    """The callable-sharding regression (satellite 2): a two-bucket
    grouped stream on a 2-device mesh ships every item pre-sharded with
    the right spec per SHAPE — K-stacks P(None, data), per-step batches
    P(data) — and each device's shard is exactly its slice of the host
    rows."""
    ds = tiny_dataset
    cfg = ds.cfg.replace(buckets=TABLE_SPEC)
    split = ds.splits["train"]
    table = B.bucket_table(cfg)
    plan = G.grouped_plan(split, cfg, batch_size=8, group_size=2,
                          shuffle=True, seed=3, epoch=0, table=table)
    mesh = pmesh.make_mesh(n_data=2, n_model=1)
    tasks = G.grouped_assembly_tasks(split, plan, cfg, batch_size=8)
    geoms_seen = set()
    saw_stacked = saw_per_step = False
    with Feeder(tasks, num_workers=2, depth=3,
                sharding=pmesh.feed_shardings(mesh)) as feed:
        for item in feed:
            geoms_seen.add(item.host["_tag"])
            stacked = item.host["valid"].ndim == 2
            arr = item.device["msg"]
            spec = arr.sharding.spec
            if stacked:  # scan axis replicated, batch axis on data
                assert spec[0] is None and spec[1] == pmesh.DATA_AXIS, spec
            else:
                assert spec[0] == pmesh.DATA_AXIS, spec
            # per-shard rows == the host rows that shard owns
            host = item.host["msg"]
            axis = 1 if stacked else 0
            half = host.shape[axis] // 2
            shards = sorted(arr.addressable_shards,
                            key=lambda s: s.index[axis].start or 0)
            lo = np.take(host, range(0, half), axis=axis)
            hi = np.take(host, range(half, 2 * half), axis=axis)
            np.testing.assert_array_equal(np.asarray(shards[0].data), lo)
            np.testing.assert_array_equal(np.asarray(shards[1].data), hi)
            saw_stacked |= stacked
            saw_per_step |= not stacked
    assert saw_stacked and saw_per_step
    assert len(geoms_seen) >= 2  # genuinely mixed-geometry stream


def test_divisibility_errors_name_buckets_and_train_raises(tiny_dataset,
                                                           tmp_path):
    cfg = tiny_dataset.cfg.replace(buckets=TABLE_SPEC, batch_size=9)
    errs = pmesh.divisibility_errors(cfg, 2)
    # one named message per bucket (2 declared + the full fallback)
    assert len(errs) == 3
    assert any("a8.e192.t8" in e for e in errs)
    assert all("batch_size 9" in e and "n_data=2" in e for e in errs)
    assert pmesh.divisibility_errors(cfg.replace(batch_size=8), 2) == []
    assert pmesh.divisibility_errors(cfg, 1) == []  # single chip: anything
    with pytest.raises(ValueError, match="divisibility"):
        train(tiny_dataset, cfg, mesh=pmesh.make_mesh(n_data=2, n_model=1),
              out_dir=str(tmp_path / "o"), ckpt_dir=str(tmp_path / "c"),
              epochs=1, resume=False)


def test_cli_exits_2_on_mesh_and_fleet_divisibility(tiny_dataset,
                                                    tmp_path, monkeypatch):
    """Parse-time rejection, exit 2 — not a mid-run XLA reshape error."""
    from fira_tpu import cli

    data_dir = tiny_dataset.data_dir
    rc = cli.main(["train", "--data-dir", data_dir, "--config", "fira-tiny",
                   "--batch-size", "9", "--mesh", "2x1",
                   "--out-dir", str(tmp_path / "o")])
    assert rc == 2
    rc = cli.main(["test", "--data-dir", data_dir, "--config", "fira-tiny",
                   "--engine", "--engine-replicas", "3",
                   "--engine-slots", "8",
                   "--out-dir", str(tmp_path / "o2")])
    assert rc == 2
