"""Slot-refill continuous-batching decode engine (decode/engine.py).

Pins the engine's whole contract:

- per-sample equality with the batched beam in all four of ITS kv-cache x
  factored-topk forms (tokens bitwise, probs to float32 rounding), at the
  engine shapes production varies: slots, harvest cadence, KV block size,
  score space — the equivalence the DECODE_PERF_KNOBS preset rides on;
- scheduler determinism: identical output file bytes for any prefill-queue
  depth, feeder worker count, and refill order;
- the ordered streaming writer (decode/stream.py): contiguous-prefix
  flushing, atomic completion, and the crash contract (a kill mid-run
  leaves a parseable plain prefix of the final file);
- the compile-guard story: the (geometry x {prefill, step, insert})
  program family warms once, then zero post-warmup compiles;
- the harvest's readback: no program of its own and one transfer a
  harvest of what the step wrote out, however many rows settled, for both
  model families;
- the pass order: the next chunk's prefill is queued behind the step in
  flight, before the harvest's read, and seats what the admit-first order
  seated.
"""

import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest

from beam_util import beam_outputs
from fira_tpu.analysis import sanitizer
from fira_tpu.config import fira_tiny
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.feeder import Feeder
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.decode import engine as engine_lib
from fira_tpu.decode.beam import eos_biased_params
from fira_tpu.decode.runner import _decode_tasks, run_test
from fira_tpu.decode.stream import OrderedStreamWriter
from fira_tpu.model.model import FiraModel
from fira_tpu.train.state import init_state


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("corpus"))
    write_corpus_dir(data_dir, n_commits=40, seed=13)
    cfg = fira_tiny(batch_size=8, test_batch_size=6)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    from fira_tpu.data.batching import make_batch

    batch = make_batch(dataset.splits["train"], np.arange(6), cfg)
    params = init_state(FiraModel(cfg), cfg, batch).params
    # moderate EOS bias: beams settle at MIXED depths across samples (the
    # schedule the refill loop exists for), yet well before tar_len-1 —
    # the engine path is exercised non-vacuously in a few steps/slot
    return cfg, dataset, params, eos_biased_params(params, delta=4.0)


MODES = [
    # the BATCHED beam's (kv_cache, factored_topk): the oracle's four
    # forms. The engine reads neither knob — it has one form.
    (True, False),
    (True, True),
    (False, False),
    (False, True),
]

# the engine's shape, as production varies it
SHAPES = {
    # the defaults: slots = the batch, harvest every 4, automatic KV block
    # size, probability-space scores
    "as-batch": dict(),
    # what the benchmark's cells differ in: slots != batch, harvest every
    # position, one KV block a sequence, log-space scores
    "own-shape": dict(engine_slots=4, engine_harvest_every=1,
                      kv_block_size=12, beam_compat_prob_space=False),
}


# float32 rounding, for the one place the engine's arithmetic is not the
# batched beam's sum for sum: the arena's self-attention runs a slot's K
# beams over all K lanes of its blocks under the ancestry mask
# (model.Decoder.decode_step_paged), so its softmax and value product add
# their exact zeros in another order. Observed 7e-8 relative at fira-tiny;
# the bound leaves room for 30 positions x 8 layers of such last bits.
PAGED_PROBS_RTOL = 1e-5


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kv,fac", MODES)
def test_engine_bit_exact_per_sample(setup, kv, fac, shape):
    """Engine (tokens, probs) == batched beam (tokens, probs), per sample,
    against every kv-cache x factored-topk form of the batched beam:
    tokens bitwise, probs to float32 rounding (PAGED_PROBS_RTOL)."""
    cfg0, dataset, _params, eos_params = setup
    cfg = dataclasses.replace(cfg0, beam_kv_cache=kv, beam_factored_topk=fac,
                              **SHAPES[shape])
    model = FiraModel(cfg)
    data = dataset.splits["train"]  # the big split: several batches, real refill pressure

    # batched-beam reference, keyed by split position
    expected = beam_outputs(model, eos_params, data, cfg)

    eng = engine_lib.SlotEngine(model, eos_params, cfg)
    tasks2, _ = _decode_tasks(data, cfg)
    seen = set()
    with Feeder(tasks2, num_workers=0, depth=1) as feed:
        for it in eng.run(feed):
            assert it.position not in seen
            seen.add(it.position)
            ref_toks, ref_probs = expected[it.position]
            np.testing.assert_array_equal(it.tokens, ref_toks)
            np.testing.assert_allclose(it.probs, ref_probs,
                                       rtol=PAGED_PROBS_RTOL, atol=0)
    assert seen == set(expected)
    assert eng.slots == (cfg.engine_slots or cfg.test_batch_size)
    assert eng.stats.kv_block_size == (cfg.kv_block_size or 6)
    assert eng.stats.commits == len(data)
    # the engine must actually retire+refill mid-flight, not run one
    # monolithic pass: with mixed settle depths there are more refill
    # dispatches than the initial fill alone
    assert eng.stats.slots_refilled == len(data)
    assert 0.0 < eng.stats.slot_occupancy <= 1.0


# --------------------------------------------------------------------------
# the harvest's readback: one gather and one transfer, whatever settled
# --------------------------------------------------------------------------

# a FIRA drain stream with repeats, so in-flight duplicates coalesce onto
# a leader's seat as followers (decode/prefix_cache.py)
REPEAT_CHUNKS = [np.array([0, 1, 2, 3]), np.array([0, 1, 2, 3]),
                 np.array([4, 5, 0, 1]), np.array([2, 3, 4, 5])]


class HarvestProbe:
    """Wraps ONE engine's ``harvest``: chooses which seated slots a
    harvest sees as settled (``mode``), copies the arena before the
    harvest, counts the program dispatches inside it and holds every item
    against the arena rows it must equal.

    ``one``: a harvest sees at most one of the slots that settled (the
    others stay seated and done, and settle at later harvests);
    ``every``: a harvest over a full arena sees every slot settled;
    ``several``: the schedule's own rows. The harvest reads the mask the
    step wrote out; the mask the device keeps is put back after the
    harvest, with the harvested slots done."""

    def __init__(self, eng, mode):
        self.eng, self.mode = eng, mode
        self.rows_a_read = []
        self.held = []          # (item, its arena tokens, its arena probs)
        self.followers = 0
        self.dispatches = 0     # programs dispatched inside a harvest
        self._harvest = eng.harvest
        eng.harvest = self.harvest
        self._programs = _spy_programs(eng, self._count)

    def _count(self, _name):
        self.dispatches += 1

    def harvest(self):
        eng = self.eng
        done = np.array(eng._state["done"])
        assert np.array_equal(np.array(eng._pending_out["done"]), done)
        busy = sorted(eng._busy)
        settled = [s for s in busy if done[s]]
        if self.mode == "one":
            settled = settled[:1]
        elif self.mode == "every" and len(busy) == eng.slots:
            settled = busy
        seen = np.zeros_like(done)
        seen[settled] = True
        eng._pending_out = dict(eng._pending_out, done=jnp.asarray(seen))
        arena_t = np.array(eng._state["tokens"])
        arena_p = np.array(eng._state["probs"])
        slot_of = {pid: s for s, (pid, _h, _r) in eng._busy.items()}
        owed = {pid for pid, s in slot_of.items() if seen[s]}
        for leader, fl in eng._followers.items():
            if leader in owed:
                for fpos, _fh, _fr in fl:
                    slot_of[fpos] = slot_of[leader]
                    owed.add(fpos)
                    self.followers += 1
        before = self.dispatches
        items = self._harvest()
        eng._state = dict(eng._state, done=jnp.asarray(done | seen))
        # the harvest reads what the step wrote out: no program of its own
        assert self.dispatches == before
        if settled:
            self.rows_a_read.append(len(settled))
        assert sorted(it.position for it in items) == sorted(owed)
        first = {}
        for it in items:
            s = slot_of[it.position]
            assert it.tokens.dtype == np.int32 and it.probs.dtype == np.float32
            assert it.tokens.tobytes() == arena_t[s].tobytes()
            assert it.probs.tobytes() == arena_p[s].tobytes()
            lead = first.setdefault(s, it)   # a leader comes before its followers
            assert it.tokens is lead.tokens and it.probs is lead.probs
            self.held.append((it, arena_t[s].copy(), arena_p[s].copy()))
        return items

    def close(self):
        del self.eng.harvest
        for name, prog in self._programs.items():
            setattr(self.eng, name, prog)


def _spy_programs(eng, on_call):
    """Route every jitted program the engine holds through ``on_call(name)``.
    -> {name: the program}, to put back."""
    progs = {n: v for n, v in vars(eng).items()
             if callable(v) and hasattr(v, "lower")}
    for n, prog in progs.items():

        def spy(*a, _n=n, _prog=prog, **kw):
            on_call(_n)
            return _prog(*a, **kw)
        setattr(eng, n, spy)
    assert {"_prefill", "_step", "_insert"} <= set(progs)
    return progs


@pytest.fixture(scope="module")
def harvest_engines(setup):
    """One engine a model and arena, shared by the cases below (a run
    starts from ``begin_stream`` and a fresh ``EngineStats``)."""
    cfg0, dataset, _params, eos_params = setup
    made = {}

    def get(name):
        if name in made:
            return made[name]
        if name == "axk1":
            from fira_tpu.config import get_config
            from fira_tpu.model import axk1

            cfg = get_config("axk1-tiny", engine_slots=4)
            params = axk1.init_params(cfg.lm, 5, jnp.dtype(cfg.compute_dtype))
            eng = engine_lib.SlotEngine(None, params, cfg)
        else:
            cfg = dataclasses.replace(cfg0, engine_slots=4,
                                      prefix_cache=name == "fira-followers")
            eng = engine_lib.SlotEngine(FiraModel(cfg), eos_params, cfg)
        made[name] = eng
        return eng
    return get


def _harvest_feed(name, eng, dataset):
    cfg = eng.cfg
    if name == "axk1":
        from fira_tpu.data import buckets
        from fira_tpu.data.synthetic import make_prompt_requests

        prompts, limits = make_prompt_requests(
            14, vocab_size=cfg.lm.vocab_size, seed=2, min_len=8, max_len=64,
            limits=(3, 3, 7, 11))
        return buckets.prompt_tasks(cfg.lm, (
            (i, p, int(m)) for i, (p, m) in enumerate(zip(prompts, limits))))
    data = dataset.splits["train"]
    if name == "fira-followers":
        from fira_tpu.data.feeder import assembly_tasks

        return assembly_tasks(data, REPEAT_CHUNKS, cfg, batch_size=4)
    return _decode_tasks(data, cfg)[0]


@pytest.mark.parametrize("name,mode", [
    ("fira", "one"), ("fira", "several"), ("fira", "every"),
    ("fira-followers", "several"),
    ("axk1", "one"), ("axk1", "several"), ("axk1", "every")])
def test_harvest_reads_all_settled_rows_at_once(setup, harvest_engines,
                                                name, mode):
    """A harvest dispatches no program, whatever settled: it takes the
    settled rows from the step's own outputs, one transfer a harvest;
    every item's tokens and probs are bitwise the arena's rows of its slot
    as they stood before the harvest, followers hold their leader's very
    arrays, and all of them outlive every later dispatch (which donates
    the arena): copies, not views of a donated buffer."""
    _cfg, dataset, _params, _eos = setup
    eng = harvest_engines(name)
    eng.stats = engine_lib.EngineStats(slots=eng.slots)
    probe = HarvestProbe(eng, mode)
    try:
        with Feeder(_harvest_feed(name, eng, dataset), num_workers=0,
                    depth=1) as feed:
            positions = [it.position for it in eng.run(feed)]
    finally:
        probe.close()
    st = eng.stats
    assert len(positions) == len(set(positions)) == st.commits > eng.slots
    # the items survived every dispatch since their harvest
    assert len(probe.held) == st.commits
    for it, toks, probs in probe.held:
        assert it.tokens.tobytes() == toks.tobytes()
        assert it.probs.tobytes() == probs.tobytes()
    reads = probe.rows_a_read
    assert st.harvest_reads == len(reads) > 0
    assert st.harvest_reads <= st.harvest_row_reads == sum(reads)
    assert st.harvest_row_reads == st.commits - st.dedup_fanout
    assert st.dedup_fanout == probe.followers
    # every harvest's one transfer carries every slot's rows
    assert st.harvest_bytes_read == st.step_dispatches * (
        eng._state["tokens"].nbytes + eng._state["probs"].nbytes)
    s = st.summary()
    assert s["harvest_reads"] == st.harvest_reads
    if mode == "one":
        assert set(reads) == {1}
    elif mode == "every":
        assert max(reads) == eng.slots
    else:
        assert 2 <= max(reads)
    if name == "fira-followers":
        assert probe.followers > 0


def test_harvest_dispatches_no_program_and_declares_none(setup):
    """Under the armed compile guard, a drain's family is the prefill, the
    step and the insert: nothing named for a harvest is declared or
    dispatched, a harvest that settles rows runs no program, and nothing
    compiles after the warm-up."""
    from fira_tpu.data.batching import make_batch

    cfg0, dataset, _params, eos_params = setup
    cfg = dataclasses.replace(cfg0, engine_slots=4)
    data = dataset.splits["train"]
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        eng = engine_lib.SlotEngine(FiraModel(cfg), eos_params, cfg,
                                    guard=guard)
        family = eng.labels()
        assert family == ["engine_prefill", "engine_step", "engine_insert"]
        guard.declare(family)
        eng.prewarm([(make_batch(data, np.arange(0), cfg,
                                 batch_size=cfg.test_batch_size), None)])
        calls = []
        in_harvest = []
        inner = eng.harvest

        def harvest():
            mark = len(calls)
            items = inner()
            in_harvest.append((len(items), len(calls) - mark))
            return items
        eng.harvest = harvest
        _spy_programs(eng, calls.append)
        with Feeder(_decode_tasks(data, cfg)[0], num_workers=0,
                    depth=1) as feed:
            n = sum(1 for _ in eng.run(feed))
        assert guard.compiles_after_warmup() == 0
    assert n == len(data)
    assert set(guard._seen) == set(family)
    assert sum(1 for rows, _ in in_harvest if rows) > 1
    assert all(dispatched == 0 for _rows, dispatched in in_harvest)
    assert calls.count("_step") == eng.stats.step_dispatches \
        == len(in_harvest)


def _drive_admit_first(eng, feed):
    """The admit-first pass order — admit -> refill -> step -> harvest, the
    fleet's and the serve loop's — driven by hand through the same four
    methods; -> the items in the order they settled."""
    eng.begin_stream()
    it, exhausted, out = iter(feed), False, []
    while True:
        while not exhausted and eng.wants_input():
            try:
                item = next(it)
            except StopIteration:
                exhausted = True
                break
            eng.admit(item.host, item.index, item.device)
        eng.refill()
        if not eng.in_flight():
            if exhausted:
                return out
            continue
        eng.step_dispatch()
        out += eng.harvest()


@pytest.fixture(scope="module")
def ahead_drain(setup):
    """One tiny drain stream through one engine twice: in the admit-first
    order by hand, then through ``run`` with its step dispatches, harvests
    and prefill programs logged in the order they happened."""
    cfg0, dataset, _params, eos_params = setup
    data = dataset.splits["train"]
    eng = engine_lib.SlotEngine(FiraModel(cfg0), eos_params, cfg0)
    with Feeder(_decode_tasks(data, cfg0)[0], num_workers=0,
                depth=1) as feed:
        first = {it.position: it for it in _drive_admit_first(eng, feed)}
    first_stats = dataclasses.replace(eng.stats)
    eng.stats = engine_lib.EngineStats(slots=eng.slots)
    order = []
    for meth in ("step_dispatch", "harvest"):
        inner = getattr(eng, meth)

        def traced(*a, _m=meth, _inner=inner, **kw):
            order.append(_m)
            return _inner(*a, **kw)
        setattr(eng, meth, traced)
    _spy_programs(eng, lambda name: order.append(name)
                  if name == "_prefill" else None)
    with Feeder(_decode_tasks(data, cfg0)[0], num_workers=0,
                depth=1) as feed:
        items = list(eng.run(feed))
    return {"first": first, "first_stats": first_stats,
            "items": items, "stats": eng.stats, "order": order}


def test_run_queues_the_next_prefill_behind_the_step(ahead_drain):
    """Every prefill ``run`` dispatches once its first step is out comes
    after a step dispatch and before the harvest that reads that step:
    the device has it queued when the step ends. ``prefills_ahead``
    counts exactly those."""
    order, st = ahead_drain["order"], ahead_drain["stats"]
    first_step = order.index("step_dispatch")
    last = None
    for ev in order[first_step:]:
        if ev == "_prefill":
            assert last == "step_dispatch"   # queued behind a step
        else:
            last = ev
    ahead = order[first_step:].count("_prefill")
    assert st.prefills_ahead == ahead > 0
    assert st.prefills == order.count("_prefill")
    assert st.summary()["prefills_ahead"] == ahead


def test_run_seats_what_the_admit_first_order_seated(ahead_drain):
    """On a steady drain the rows staged ahead cover every slot a harvest
    frees (no top-up), and ``run``'s order seats exactly what admit ->
    refill -> step -> harvest seated: the same occupied slot-steps, step
    dispatches and prefills, and the same beams request for request."""
    st, pst = ahead_drain["stats"], ahead_drain["first_stats"]
    first, items = ahead_drain["first"], ahead_drain["items"]
    assert st.prefills_topup == 0 and st.summary()["prefills_topup"] == 0
    assert st.occupied_slot_steps == pst.occupied_slot_steps > 0
    assert st.step_dispatches == pst.step_dispatches
    assert st.prefills == pst.prefills
    assert sorted(it.position for it in items) == sorted(first)
    for it in items:
        assert it.tokens.tobytes() == first[it.position].tokens.tobytes()
        assert it.probs.tobytes() == first[it.position].probs.tobytes()


def test_engine_run_test_file_identical_and_zero_retraces(setup, tmp_path):
    """run_test --engine writes the byte-identical output file (and BLEU)
    of the batched-beam path on a BUCKETED stream, under the armed
    sanitizer: the declared (geometry x {prefill, step, insert}) family
    warms once, then zero post-warmup compiles."""
    cfg0, dataset, _params, eos_params = setup
    # a two-entry decode bucket family for the tiny geometry (+ implicit
    # full fallback); tar is pinned full by decode_table regardless
    cfg = dataclasses.replace(cfg0, buckets=((16, 400, 12),))
    model = FiraModel(cfg)

    off = run_test(model, eos_params, dataset,
                   dataclasses.replace(cfg, decode_engine=False),
                   out_dir=str(tmp_path / "off"), split="train")
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        on = run_test(model, eos_params, dataset,
                      dataclasses.replace(cfg, decode_engine=True),
                      out_dir=str(tmp_path / "on"), guard=guard,
                      split="train")
        assert guard.compiles_after_warmup() == 0
    assert open(off["output_path"]).read() == open(on["output_path"]).read()
    assert off["sentence_bleu"] == on["sentence_bleu"]
    assert on["engine"]["commits"] == len(dataset.splits["train"])
    # no stray .partial / tagged tail left behind on a clean completion
    assert not os.path.exists(on["output_path"] + ".partial")
    assert not os.path.exists(on["output_path"] + ".partial.tail")


def test_engine_determinism_any_queue_depth_and_refill_order(setup, tmp_path):
    """Same (seed, corpus, slot count) => identical output file bytes for
    any prefill-queue depth, any feeder worker count, either refill
    order, and any harvest cadence."""
    cfg0, dataset, _params, eos_params = setup
    cfg = dataclasses.replace(cfg0, decode_engine=True)
    model = FiraModel(cfg)

    variants = [
        dict(prefill_depth=1, workers=0, refill_order="fifo", cadence=1),
        dict(prefill_depth=3, workers=2, refill_order="fifo", cadence=4),
        dict(prefill_depth=2, workers=1, refill_order="lifo", cadence=3),
    ]
    outputs = []
    for i, v in enumerate(variants):
        c = dataclasses.replace(cfg,
                                engine_prefill_depth=v["prefill_depth"],
                                engine_harvest_every=v["cadence"],
                                feeder_workers=v["workers"])
        m = run_test(model, eos_params, dataset, c,
                     out_dir=str(tmp_path / f"v{i}"), split="train",
                     refill_order=v["refill_order"])
        outputs.append(open(m["output_path"]).read())
    assert outputs[0] == outputs[1] == outputs[2]
    with pytest.raises(ValueError, match="refill_order"):
        next(iter(engine_lib.SlotEngine(model, eos_params, cfg).run(
            [], refill_order="random")))


def test_engine_slot_count_decoupled_from_batch(setup, tmp_path):
    """S need not equal the packed batch size: a smaller arena (heavy
    partial-chunk insert pressure) and a larger one both write the exact
    batched-path bytes."""
    cfg0, dataset, _params, eos_params = setup
    cfg = dataclasses.replace(cfg0, decode_engine=True)
    model = FiraModel(cfg)
    ref = run_test(model, eos_params, dataset,
                   dataclasses.replace(cfg, decode_engine=False),
                   out_dir=str(tmp_path / "ref"), split="train")
    ref_text = open(ref["output_path"]).read()
    for slots in (4, 10):
        m = run_test(model, eos_params, dataset, cfg,
                     out_dir=str(tmp_path / f"s{slots}"), split="train",
                     engine_slots=slots)
        assert open(m["output_path"]).read() == ref_text, slots
        assert m["engine"]["slots"] == slots


def test_engine_retires_early_on_settled_beams(setup):
    """With EOS-biased params every slot settles in a few positions: the
    engine's total step count must come in far below the batched full-scan
    budget (batches x tar_len-1) — the continuous-batching win exists."""
    cfg0, dataset, _params, eos_params = setup
    cfg = dataclasses.replace(cfg0, beam_kv_cache=True)
    model = FiraModel(cfg)
    data = dataset.splits["train"]  # the big split: several batches, real refill pressure
    eng = engine_lib.SlotEngine(model, eos_params, cfg)
    tasks, _ = _decode_tasks(data, cfg)
    with Feeder(tasks, num_workers=0, depth=1) as feed:
        for _ in eng.run(feed):
            pass
    n_batches = -(-len(data) // cfg.test_batch_size)
    full_budget = n_batches * (cfg.tar_len - 1)
    assert 0 < eng.stats.steps < full_budget, eng.stats.summary()
    assert eng.stats.prefills == n_batches


# --------------------------------------------------------------------------
# ordered streaming writer
# --------------------------------------------------------------------------

def test_stream_writer_flushes_contiguous_prefix(tmp_path):
    path = str(tmp_path / "out")
    w = OrderedStreamWriter(path)
    w.add(2, "c\n")
    w.add(0, "a\n")
    w.flush()
    # position 1 missing: only the [0] prefix may be on disk
    assert open(path + ".partial").read() == "a\n"
    assert w.written == 1 and w.pending == 1
    w.add(1, "b\n")
    w.flush()
    assert open(path + ".partial").read() == "a\nb\nc\n"
    assert w.close() == path
    assert open(path).read() == "a\nb\nc\n"
    assert not os.path.exists(path + ".partial")
    assert not os.path.exists(path + ".partial.tail")


def test_stream_writer_rejects_duplicates_and_gaps(tmp_path):
    w = OrderedStreamWriter(str(tmp_path / "out"))
    w.add(0, "a\n")
    with pytest.raises(ValueError, match="duplicate"):
        w.add(0, "again\n")
    w.add(2, "c\n")
    with pytest.raises(RuntimeError, match="gap"):
        w.close()  # position 1 never arrived
    # the flushed prefix survives the failed close
    assert open(str(tmp_path / "out") + ".partial").read() == "a\n"


def test_stream_writer_detects_suffix_truncation(tmp_path):
    """Tail-of-split samples that never arrive leave no interior gap; the
    expected-count check refuses to rename the truncated file."""
    w = OrderedStreamWriter(str(tmp_path / "out"), expected=3)
    w.add(0, "a\n")
    w.add(1, "b\n")
    with pytest.raises(RuntimeError, match="never decoded"):
        w.close()
    assert not os.path.exists(str(tmp_path / "out"))
    assert open(str(tmp_path / "out") + ".partial").read() == "a\nb\n"


def test_stream_writer_close_after_abort_raises(tmp_path):
    """close() after an abort must not report success: the final file was
    never produced, only the .partial recovery pair exists."""
    w = OrderedStreamWriter(str(tmp_path / "out"))
    w.add(0, "a\n")
    w.abort()
    with pytest.raises(RuntimeError, match="aborted"):
        w.close()
    # a failed close (gap/truncation) aborts internally — retrying it
    # must keep raising, never hand back the nonexistent final path
    w2 = OrderedStreamWriter(str(tmp_path / "out2"), expected=2)
    w2.add(0, "a\n")
    with pytest.raises(RuntimeError, match="never decoded"):
        w2.close()
    with pytest.raises(RuntimeError, match="aborted"):
        w2.close()


def test_stream_writer_crash_leaves_parseable_prefix(tmp_path):
    """Context-manager exception path == the kill contract: .partial holds
    the plain contiguous prefix, no rename."""
    path = str(tmp_path / "out")
    with pytest.raises(RuntimeError, match="boom"):
        with OrderedStreamWriter(path) as w:
            w.add(0, "a\n")
            w.add(1, "b\n")
            w.add(5, "f\n")  # above the gap: must NOT reach disk
            raise RuntimeError("boom")
    assert not os.path.exists(path)
    assert open(path + ".partial").read() == "a\nb\n"
    # the above-gap line is on disk too, position-tagged: a crash costs
    # nothing that was decoded
    assert open(path + ".partial.tail").read() == "5\tf\n"


def test_engine_kill_mid_run_leaves_parseable_prefix(setup, tmp_path):
    """A crash mid-decode (here: the BLEU scorer dying partway) leaves
    output_fira.partial = a byte-exact prefix of the completed run's
    file."""
    import fira_tpu.decode.runner as runner_mod

    cfg0, dataset, _params, eos_params = setup
    cfg = dataclasses.replace(cfg0, decode_engine=True)
    model = FiraModel(cfg)
    full = run_test(model, eos_params, dataset, cfg,
                    out_dir=str(tmp_path / "full"), split="train")
    full_lines = open(full["output_path"]).read().splitlines(keepends=True)

    calls = {"n": 0}
    real = runner_mod.nltk_sentence_bleu

    def dying(*a, **k):
        calls["n"] += 1
        if calls["n"] > 9:
            raise RuntimeError("killed mid-run")
        return real(*a, **k)

    runner_mod.nltk_sentence_bleu = dying
    try:
        with pytest.raises(RuntimeError, match="killed mid-run"):
            run_test(model, eos_params, dataset, cfg,
                     out_dir=str(tmp_path / "killed"), split="train")
    finally:
        runner_mod.nltk_sentence_bleu = real
    out_path = os.path.join(str(tmp_path / "killed"), "output_fira")
    assert not os.path.exists(out_path)  # never renamed
    partial = open(out_path + ".partial").read().splitlines(keepends=True)
    assert len(partial) < len(full_lines)
    assert partial == full_lines[: len(partial)]
    # every decoded-but-unflushed line survives in the tagged tail, in its
    # final form
    if os.path.exists(out_path + ".partial.tail"):
        for tagged in open(out_path + ".partial.tail"):
            pos_s, line = tagged.split("\t", 1)
            assert line == full_lines[int(pos_s)]
