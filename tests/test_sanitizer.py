"""Runtime-sanitizer tests: compile capture, the per-program compile-count
guard, debug_nans wiring, and the compile-count REGRESSION pin — a few
fused and unfused tiny-config train steps must trigger ZERO post-warmup
compilations (the RETRACE invariant at runtime, not just statically).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fira_tpu.analysis import sanitizer
from fira_tpu.config import fira_tiny
from fira_tpu.data.batching import make_batch
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.model.model import FiraModel
from fira_tpu.train import step as step_lib
from fira_tpu.train.state import init_state


def test_compile_capture_counts_compilations():
    with sanitizer.compile_capture() as watcher:
        f = jax.jit(lambda x: x * 2.0)
        f(jnp.ones((3,)))
        first = watcher.count
        f(jnp.ones((3,)))          # cached: no new compile
        same = watcher.count
        f(jnp.ones((4,)))          # new shape: recompiles
        grown = watcher.count
    assert first >= 1
    assert same == first
    assert grown > same
    assert watcher.messages and watcher.messages[0].startswith("Compiling")


def test_guard_allows_warmup_then_raises_on_retrace():
    with sanitizer.compile_capture() as watcher:
        guard = sanitizer.CompileGuard(watcher)
        f = jax.jit(lambda x: x + 1.0)
        f(jnp.ones((2,)))
        guard.step("f")            # warmup: compilation allowed
        f(jnp.ones((2,)))
        guard.step("f")            # steady state: no compile, fine
        f(jnp.ones((5,)))          # shape drift -> recompile
        with pytest.raises(sanitizer.RetraceError, match="program 'f'"):
            guard.step("f")


def test_guard_is_per_label():
    """A second program's warmup compile must not trip the first label —
    the fused-steps epoch tail legitimately compiles late."""
    with sanitizer.compile_capture() as watcher:
        guard = sanitizer.CompileGuard(watcher)
        f = jax.jit(lambda x: x + 1.0)
        g = jax.jit(lambda x: x * 3.0)
        f(jnp.ones((2,)))
        guard.step("f")
        g(jnp.ones((2,)))          # late first dispatch of another program
        guard.step("g")            # its own warmup: no raise
        f(jnp.ones((2,)))
        guard.step("f")


def test_sanitize_restores_config_and_catches_nans():
    prev = jax.config.jax_debug_nans
    with sanitizer.sanitize() as guard:
        assert guard is not None
        assert jax.config.jax_debug_nans
        with pytest.raises(FloatingPointError):
            jax.jit(lambda x: jnp.log(x))(jnp.float32(-1.0))
    assert jax.config.jax_debug_nans == prev


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("san_corpus"))
    write_corpus_dir(data_dir, n_commits=24, seed=11)
    cfg = fira_tiny(batch_size=4)
    dataset = FiraDataset(data_dir, cfg)
    return dataset


def test_guard_wiring_through_train_loop(tiny, tmp_path):
    """End-to-end: train() threads the guard through every dispatch site
    (train_step + dev_step labels) without tripping on a healthy run —
    pins the label placement, not just CompileGuard mechanics. With no
    declared bucket table the train program carries its edge rung's tag
    (data/buckets.edge_ladder) and the dev gate the plain full-geometry
    label."""
    from fira_tpu.train.loop import train

    dataset = tiny
    cfg = dataset.cfg.replace(epochs=1, dev_start_epoch=0,
                              dev_every_batches=4)
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        result = train(dataset, cfg, out_dir=str(tmp_path / "out"),
                       epochs=1, resume=False, guard=guard)
    assert result.epochs_run == 1
    # both programs dispatched >1 time and the guard saw them
    rungs = {lbl: n for lbl, n in guard._seen.items()
             if lbl.startswith("train_step[")}
    assert rungs and sum(rungs.values()) >= 2 + len(rungs)
    assert "train_step" not in guard._seen
    assert guard._seen.get("dev_step", 0) >= 1
    assert guard.compiles_after_warmup() == 0


def test_compile_count_regression_unfused_and_fused(tiny):
    """The one-compile contract over the real train step: N dispatches of
    each program = exactly its warmup compiles, zero after."""
    dataset = tiny
    cfg = dataset.cfg
    model = FiraModel(cfg)
    split = dataset.splits["train"]
    rng = np.random.RandomState(0)

    def fresh_batch():
        idx = rng.choice(len(split), cfg.batch_size, replace=True)
        return make_batch(split, idx, cfg)

    state = init_state(model, cfg, fresh_batch())
    with sanitizer.compile_capture() as watcher:
        guard = sanitizer.CompileGuard(watcher)
        step = jax.jit(step_lib.make_train_step(model, cfg))
        for i in range(3):
            state, metrics = step(state, fresh_batch())
            np.asarray(jax.device_get(metrics["loss"]))
            # step_counting records instead of raising, so the assert below
            # pins the accounting itself (the raise path has its own test)
            extra = guard.step_counting("train_step")
            assert extra == 0, f"unfused step {i} recompiled"

        multi = jax.jit(step_lib.make_multi_step(model, cfg))
        for i in range(2):
            stacked = step_lib.stack_batches([fresh_batch(), fresh_batch()])
            state, metrics = multi(state, stacked)
            np.asarray(jax.device_get(metrics["loss"]))
            extra = guard.step_counting("grouped_step")
            assert extra == 0, f"fused dispatch {i} recompiled"
        assert guard.compiles_after_warmup() == 0
        assert watcher.count > 0, "capture saw no compiles at all — inert"


# --------------------------------------------------------------------------
# ThreadGuard: the runtime lock-discipline sanitizer (static twin:
# SHARED-MUT). Armed, a guarded structure's mutation without the owning
# lock raises at the mutating line; unarmed, nothing is ever wrapped.
# --------------------------------------------------------------------------

import collections
import threading

from fira_tpu.ingest.cache import IngestCache


def test_thread_guard_lockless_mutation_raises_and_locked_passes():
    tg = sanitizer.ThreadGuard()
    lock = tg.lock(threading.Lock(), "L")
    d = tg.wrap({}, lock, "D")
    with pytest.raises(sanitizer.LockDisciplineError) as ei:
        d["x"] = 1
    assert "without holding its owning lock" in str(ei.value)
    assert tg.violations and tg.violations[0]["structure"] == "D"
    with lock:
        d["x"] = 1           # the disciplined write
        d.pop("x")
        d.setdefault("y", 2)
    assert dict(d) == {"y": 2}
    # Counter increments route through __setitem__ — the unlocked-
    # increment bug class the FaultInjector.fired fix addressed
    c = tg.wrap(collections.Counter(), lock, "C")
    with pytest.raises(sanitizer.LockDisciplineError):
        c["site"] += 1
    with lock:
        c["site"] += 1
    assert c["site"] == 1


def test_thread_guard_cross_thread_violation_names_the_thread():
    tg = sanitizer.ThreadGuard()
    lock = tg.lock(threading.Lock(), "L")
    d = tg.wrap({}, lock, "D")
    box = {}

    def worker():
        try:
            d["k"] = 1   # no lock held on THIS thread
        except sanitizer.LockDisciplineError as e:
            box["err"] = str(e)

    with lock:  # holding it on the MAIN thread must not authorize others
        t = threading.Thread(target=worker, name="rogue")
        t.start()
        t.join()
    assert "rogue" in box["err"]


def test_thread_guard_records_lock_order_inversion():
    tg = sanitizer.ThreadGuard()
    a = tg.lock(threading.Lock(), "A")
    b = tg.lock(threading.Lock(), "B")
    with a:
        with b:
            pass
    assert not tg.inversions   # one consistent order: no inversion
    with b:
        with a:
            pass
    assert len(tg.inversions) == 1
    assert tg.summary()["inversions"]


def test_thread_guard_unarmed_is_plain_and_armed_wraps():
    # unarmed: plain structures, nothing to pay
    c = IngestCache(entries=4)
    assert type(c._lru) is collections.OrderedDict
    assert not isinstance(c._lock, sanitizer._GuardedLock)
    # armed: construction wraps; the class's own locked paths still work
    with sanitizer.thread_guarding() as tg:
        g = IngestCache(entries=4)
        assert isinstance(g._lock, sanitizer._GuardedLock)
        g.put("d", {"x": np.zeros(3, np.int32)})
        out, outcome = g.take("d")
        assert outcome == "hit" and out is not None
        # a lock-bypassing mutation raises AT the mutating line
        with pytest.raises(sanitizer.LockDisciplineError):
            g._lru["evil"] = None
        assert tg.violations
    # guard restored off: new constructions are plain again
    assert type(IngestCache(entries=4)._lru) is collections.OrderedDict


def test_thread_guard_feeder_ordered_channel_guarded():
    """The feeder's worker<->consumer ready channel works under the
    guard (every real write site already holds the condition) and the
    stream stays byte-order identical."""
    with sanitizer.thread_guarding():
        tasks = ((lambda i=i: {"valid": np.ones(2, bool),
                               "payload": np.full(3, i)}) for i in range(8))
        from fira_tpu.data.feeder import Feeder

        with Feeder(tasks, num_workers=3, depth=2, put=False) as feed:
            order = [item.index for item in feed]
    assert order == list(range(8))


# ---------------------------------------------------------------------------
# LeakGuard — the resource-lifecycle sanitizer (firacheck v3 runtime half)
# ---------------------------------------------------------------------------


def test_leak_guard_assert_clean_names_the_acquire_site():
    with sanitizer.leak_guarding() as lg:
        lg.note_acquire("block", "engine@0:7", what="paged block 7")
        with pytest.raises(sanitizer.LeakError) as ei:
            lg.assert_clean("test teardown")
        msg = str(ei.value)
        # the error carries the WHAT, the (kind, key), the acquire site
        # (this file), and the discipline being enforced
        assert "paged block 7" in msg
        assert "block 'engine@0:7'" in msg
        assert "test_sanitizer.py" in msg
        assert "RES-LEAK discipline" in msg
        lg.note_release("block", "engine@0:7")
        lg.assert_clean("test teardown")  # balanced ledger passes
        s = lg.summary()
        assert s["acquires"] == 1 and s["releases"] == 1
        assert s["open"] == 0 and s["unmatched_releases"] == 0


def test_leak_guard_feeder_threads_check_in_and_out():
    from fira_tpu.data.feeder import Feeder

    tasks = ((lambda i=i: {"valid": np.ones(2, bool),
                           "payload": np.full(3, i)}) for i in range(6))
    with sanitizer.leak_guarding() as lg:
        with Feeder(tasks, num_workers=2, depth=2, put=False) as feed:
            order = [item.index for item in feed]
        # close() joined every pipeline thread -> the ledger balances
        lg.assert_clean("feeder teardown")
        assert lg.summary()["acquires"] >= 2
    assert order == list(range(6))


def test_leak_guard_unjoined_thread_raises_at_teardown():
    gate = threading.Event()
    with sanitizer.leak_guarding() as lg:
        t = threading.Thread(target=gate.wait, daemon=True)
        t.start()
        lg.track_thread(t, what="planted worker thread")
        with pytest.raises(sanitizer.LeakError) as ei:
            lg.assert_clean("planted teardown")
        assert "planted worker thread" in str(ei.value)
        gate.set()
        t.join()
        lg.note_joined(t)
        lg.assert_clean("planted teardown")  # joined -> clean


def test_leak_guard_watchdog_abandonment_is_sanctioned():
    """A blown dispatch ABANDONS its daemon thread by design
    (docs/FAULTS.md) — the ledger records the sanction instead of
    calling it a leak at teardown."""
    from fira_tpu.robust.watchdog import WatchdogTimeout, run_with_watchdog

    release = threading.Event()
    with sanitizer.leak_guarding() as lg:
        with pytest.raises(WatchdogTimeout):
            run_with_watchdog(release.wait, 0.05, label="test-hang")
        lg.assert_clean("watchdog teardown")  # abandoned != leaked
        s = lg.summary()
        assert s["abandoned"] == 1 and s["open"] == 0
    release.set()


def test_leak_guard_unarmed_owners_carry_none_and_allocate_no_guard(
        monkeypatch):
    """The zero-overhead contract: unarmed, owners capture None at
    construction and every acquire/release site is one is-None branch —
    no LeakGuard (and no ledger) is ever allocated."""
    from fira_tpu.data.feeder import Feeder

    created = []
    orig_init = sanitizer.LeakGuard.__init__

    def spy(self, *a, **k):
        created.append(self)
        return orig_init(self, *a, **k)

    monkeypatch.setattr(sanitizer.LeakGuard, "__init__", spy)
    assert sanitizer.leak_guard() is None
    tasks = ((lambda i=i: {"valid": np.ones(2, bool),
                           "payload": np.full(3, i)}) for i in range(4))
    with Feeder(tasks, num_workers=2, depth=2, put=False) as feed:
        order = [item.index for item in feed]
    assert order == list(range(4))
    assert feed._leaks is None
    assert not created
