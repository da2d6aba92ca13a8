"""Profiling hooks: trace context produces a loadable artifact; Meter math."""

import glob
import os
import time

import jax
import jax.numpy as jnp

from fira_tpu.utils import profiling


class TestTrace:
    def test_trace_writes_artifacts(self, tmp_path):
        log_dir = str(tmp_path / "trace")
        with profiling.trace(log_dir):
            with profiling.step_annotation(0):
                jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
        files = glob.glob(os.path.join(log_dir, "**", "*"), recursive=True)
        assert any(f.endswith(".xplane.pb") for f in files), files

    def test_trace_none_is_noop(self):
        with profiling.trace(None):
            pass  # must not require jax profiler state


class TestMeter:
    def test_throughput_and_warmup(self):
        m = profiling.Meter(warmup=1)
        m.start()
        for _ in range(4):
            time.sleep(0.01)
            m.tick(n_items=5)
        s = m.summary()
        # first interval (warmup) discarded: 3 measured steps
        assert s["steps"] == 3
        assert s["items_per_sec"] > 0
        assert s["p50_step_ms"] >= 10 * 0.5
        assert s["p99_step_ms"] >= s["p50_step_ms"]

    def test_pause_excludes_interval(self):
        m = profiling.Meter(warmup=0)
        m.start()
        m.tick()
        m.pause()
        time.sleep(0.05)  # excluded
        m.start()
        m.tick()
        s = m.summary()
        assert s["steps"] == 2
        assert s["p99_step_ms"] < 50

    def test_empty_summary(self):
        s = profiling.Meter().summary()
        assert s["steps"] == 0
        assert s["feed_stall_frac"] == 0.0

    def test_feed_stall_attribution(self):
        m = profiling.Meter(warmup=0)
        m.start()
        time.sleep(0.02)
        m.tick(1, stall_s=0.01)   # half the interval was feed stall
        time.sleep(0.02)
        m.tick(1)                 # none of this one was
        s = m.summary()
        assert 0.0 < s["feed_stall_frac"] < 1.0
        assert s["feed_stall_ms_per_step"] >= 10 * 0.5 / 2
        # a warmup interval's stall is discarded with its interval
        m2 = profiling.Meter(warmup=1)
        m2.start()
        m2.tick(1, stall_s=5.0)
        time.sleep(0.01)
        m2.tick(1, stall_s=0.0)
        assert m2.summary()["feed_stall_frac"] == 0.0

    def test_feed_stall_frac_capped_at_one(self):
        m = profiling.Meter(warmup=0)
        m.start()
        m.tick(1, stall_s=99.0)  # clock skew must not report frac > 1
        assert m.summary()["feed_stall_frac"] == 1.0

    def test_paused_property_brackets_what_is_not_train_time(self):
        m = profiling.Meter(warmup=0)
        assert m.paused                       # never started
        m.start()
        assert not m.paused
        m.tick(4)
        m.pause()                             # epoch end: checkpoint save,
        assert m.paused                       # next feeder's pipeline fill
        time.sleep(0.05)
        m.start()                             # the next epoch's first batch
        time.sleep(0.01)
        m.tick(4)
        s = m.summary()
        assert s["steps"] == 2
        assert s["p99_step_ms"] < 50


def test_train_meter_leaves_out_checkpoint_save_and_feeder_start(
        tmp_path, monkeypatch):
    """PERF.md (PR 21): the meter's interval counted ``save_latest`` and the
    next epoch's feeder start, and printed 333 commits/s where the dispatch
    did 2,767. With a save that takes 0.4 s, no measured interval may hold
    it, and the stall fed to the meter may not hold a pipeline fill."""
    from fira_tpu.config import fira_tiny
    from fira_tpu.data.dataset import FiraDataset
    from fira_tpu.data.synthetic import write_corpus_dir
    from fira_tpu.train import loop
    from fira_tpu.train.state import CheckpointManager

    data_dir = str(tmp_path / "corpus")
    os.makedirs(data_dir)
    write_corpus_dir(data_dir, n_commits=32, seed=11)
    cfg = fira_tiny(batch_size=8, test_batch_size=4, dev_start_epoch=99)
    dataset = FiraDataset(data_dir, cfg)

    meters = []

    class Recording(profiling.Meter):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            meters.append(self)

    save = CheckpointManager.save_latest

    def slow_save(self, *a, **kw):
        time.sleep(0.4)
        return save(self, *a, **kw)

    monkeypatch.setattr(loop.profiling, "Meter", Recording)
    monkeypatch.setattr(CheckpointManager, "save_latest", slow_save)
    result = loop.train(dataset, out_dir=str(tmp_path / "out"), epochs=4,
                        ckpt_dir=str(tmp_path / "ckpt"))
    (meter,) = meters
    assert result.epochs_run == 4
    # two ticks an epoch (the batch-0 log line, the epoch's end); the
    # first interval holds the compile and is the warm-up one
    assert len(meter._intervals) == 2 * 4 - 1
    assert max(meter._intervals) < 0.4, meter._intervals
    assert meter.paused                       # left paused after the last save
    # and the spans of a training run are in the ring
    names = {e.name for e in profiling.events()}
    assert {"train.init_state", "feeder.assemble", "feeder.put",
            "feeder.next"} <= names
