"""Cross-request prefix cache + in-flight dedup (decode/prefix_cache.py;
docs/DECODE_ENGINE.md "Prefix cache & dedup").

Pins the ISSUE-11 contract:

- BIT-EXACTNESS: a cache-hit or deduped response equals its cold run —
  tokens AND probs, at every engine shape (slots, cadence, beams,
  block size, pool size); serve/drain output bytes are identical cache-on vs
  cache-off (the ``--prefix-cache off`` equivalence comparator), and
  cache-off itself is byte-identical to pre-PR behavior (zero cache
  counters, no digests computed);
- DEDUP FAN-OUT: byte-identical in-flight requests coalesce into ONE
  seat with N output records, each keeping its own arrival stamps;
- LRU EVICTION under an undersized cache stays deterministic (bytes
  unchanged, evictions metered);
- REFCOUNTED ALLOCATOR: grants release on harvest AND retire (free list
  returns to baseline, no block granted twice — the tier-1 invariant
  check), and a shed follower detaches without killing the seat;
- zero post-warmup retraces with the cache armed (lookups are host-side;
  a hit re-enters via device_put — no new program geometry);
- parse-time knob validation with named messages and CLI exit 2.
"""

import dataclasses

import numpy as np
import pytest

from fira_tpu import cli
from fira_tpu.analysis import sanitizer
from fira_tpu.config import fira_tiny
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.feeder import Feeder, assembly_tasks
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.decode import engine as engine_lib
from fira_tpu.decode import paging, prefix_cache
from fira_tpu.decode.beam import eos_biased_params
from fira_tpu.model.model import FiraModel
from fira_tpu.serve import poisson_times, serve_split
from fira_tpu.train.state import init_state


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("prefix_corpus"))
    write_corpus_dir(data_dir, n_commits=24, seed=13)
    cfg = fira_tiny(batch_size=8, test_batch_size=4, decode_engine=True)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    from fira_tpu.data.batching import make_batch

    batch = make_batch(dataset.splits["train"], np.arange(4), cfg)
    params = init_state(FiraModel(cfg), cfg, batch).params
    return cfg, dataset, data_dir, eos_biased_params(params, delta=4.0)


# a drain stream with REPEATS: in-flight duplicates (within/adjacent
# chunks) and cross-chunk repeats of already-harvested samples — both
# reuse mechanisms fire on it. The engine stages chunks ahead of the
# harvest, so two fresh chunks stand between the last repeat and its
# originals: it arrives once they have been harvested and cached
REPEAT_CHUNKS = [np.array([0, 1, 2, 3]), np.array([0, 1, 2, 3]),
                 np.array([4, 5, 0, 1]), np.array([2, 3, 4, 5]),
                 np.array([6, 7, 8, 9]), np.array([10, 11, 12, 13]),
                 np.array([0, 1, 2, 3])]


def _drain(model, params, dataset, cfg):
    """{stream position: (tokens, probs)} over the repeated chunk stream."""
    data = dataset.splits["train"]
    eng = engine_lib.SlotEngine(model, params, cfg)
    out = {}
    with Feeder(assembly_tasks(data, REPEAT_CHUNKS, cfg, batch_size=4),
                num_workers=0, depth=1) as feed:
        for it in eng.run(feed):
            out[it.position] = (it.tokens.tobytes(), it.probs.tobytes())
    assert len(out) == sum(len(c) for c in REPEAT_CHUNKS)
    return out, eng


# the engine shapes a cached seat has to fit, as production varies them
SHAPES = {
    "defaults": dict(),
    "r8": dict(engine_harvest_every=8),
    "slots3": dict(engine_slots=3),              # != the chunks' 4 rows
    "beam1-log": dict(beam_size=1, beam_compat_prob_space=False),
    "block-tar-slots6": dict(kv_block_size=12, engine_slots=6),
    "block3-half-pool": dict(kv_block_size=3, kv_pool_blocks=8),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_cache_hit_bit_exact_vs_cold(setup, shape):
    """The regression contract: cache-on output (tokens AND probs) is
    bitwise equal to cache-off on a repeated stream, at every engine shape
    (slots, harvest cadence, beams and score space, KV block size, an
    undersized pool) — and the reuse actually happened (hits + coalesced
    deliveries + saved dispatches metered)."""
    cfg0, dataset, _dir, params = setup
    cfg = dataclasses.replace(cfg0, **SHAPES[shape])
    model = FiraModel(cfg)
    cold, cold_eng = _drain(model, params, dataset, cfg)
    warm, warm_eng = _drain(model, params, dataset,
                            dataclasses.replace(cfg, prefix_cache=True))
    assert cold == warm
    st = warm_eng.stats
    assert st.cache_hits > 0
    assert st.dedup_fanout > 0
    assert st.prefills_saved > 0
    assert st.prefills < cold_eng.stats.prefills
    assert st.cache_hbm_bytes_saved > 0
    s = st.summary()
    assert 0.0 < s["cache_hit_rate"] <= 1.0
    # the comparator run carries ZERO cache state — pre-PR behavior
    assert cold_eng.stats.cache_hits == cold_eng.stats.cache_misses == 0
    assert cold_eng.stats.dedup_fanout == 0
    assert cold_eng._cache is None
    # allocator drained back to baseline, no grant leaked or doubled
    assert warm_eng.allocator_invariants() == []
    assert len(warm_eng._free_blocks) == warm_eng._pool_blocks
    assert warm_eng._block_refs == {}


def test_serve_dedup_fanout_records_one_seat(setup, tmp_path):
    """Burst of byte-identical requests: one seat decodes, N records
    deliver — each request keeps its own identity (distinct positions,
    own stamps, ``coalesced_into`` naming the leader), output bytes equal
    the cache-off run, and the engine seated far fewer rows than the
    request count."""
    cfg0, dataset, _dir, params = setup
    model = FiraModel(cfg0)
    n, distinct = 30, 6
    mix = np.array([i % distinct for i in range(n)])
    burst = np.zeros(n)
    ref = serve_split(model, params, dataset, cfg0, arrival_times=burst,
                      out_dir=str(tmp_path / "off"), split="train",
                      clock="virtual", request_mix=mix)
    m = serve_split(model, params, dataset,
                    dataclasses.replace(cfg0, prefix_cache=True),
                    arrival_times=burst, out_dir=str(tmp_path / "on"),
                    split="train", clock="virtual", request_mix=mix)
    assert (open(m["output_path"], "rb").read()
            == open(ref["output_path"], "rb").read())
    sv = m["serve"]
    assert sv["completed"] == n
    assert sv["dedup_coalesced"] > 0
    assert sv["dedup_groups"] > 0
    assert sv["dedup_fanout_max"] >= 2
    recs = m["request_records"]
    followers = [r for r in recs if r["coalesced_into"] is not None]
    assert len(followers) == sv["dedup_coalesced"]
    assert all(r["status"] == "done" for r in recs)
    # N records, distinct positions, own lifecycle stamps
    assert len({r["position"] for r in recs}) == n
    for r in followers:
        assert r["coalesced_into"] != r["position"]
        assert r["done_t"] >= r["arrival_t"]
    # one seat per GROUP: seated rows = leaders only, not all N requests
    assert m["engine"]["slots_refilled"] < n
    assert m["engine"]["slots_refilled"] + sv["dedup_coalesced"] >= n


def test_serve_repeats_bytes_equal_and_dispatches_drop(setup, tmp_path):
    """Spaced repeated traffic (repeats arrive after their original
    completed => prefill-cache hits rather than coalescing): bytes equal
    cache-off while prefill dispatches drop and the hit rate is metered
    — the serve_metrics-level claim of the bench acceptance row."""
    cfg0, dataset, _dir, params = setup
    model = FiraModel(cfg0)
    n = 30
    mix = np.array([i % 6 for i in range(n)])
    times = poisson_times(n, rate=0.5, seed=3)
    ref = serve_split(model, params, dataset, cfg0, arrival_times=times,
                      out_dir=str(tmp_path / "off"), split="train",
                      clock="virtual", request_mix=mix)
    m = serve_split(model, params, dataset,
                    dataclasses.replace(cfg0, prefix_cache=True),
                    arrival_times=times, out_dir=str(tmp_path / "on"),
                    split="train", clock="virtual", request_mix=mix,
                    metrics_path=str(tmp_path / "serve_metrics.json"))
    assert (open(m["output_path"], "rb").read()
            == open(ref["output_path"], "rb").read())
    eng = m["engine"]
    assert eng["prefills"] < ref["engine"]["prefills"]
    assert eng["cache_hits"] > 0 and eng["prefills_saved"] > 0
    assert eng["cache_hbm_bytes_saved"] > 0
    # hit-rate / HBM-saved land in the committed metrics artifact
    import json

    with open(tmp_path / "serve_metrics.json") as f:
        rec = json.load(f)
    assert rec["engine"]["cache_hit_rate"] > 0
    assert rec["engine"]["cache_hbm_bytes_saved"] > 0


def test_serve_zero_retraces_with_cache_armed(setup, tmp_path):
    """The no-new-program-geometry claim, machine-checked: a bucketed
    serve over repeated traffic with the cache armed compiles nothing
    after warmup — cache lookups are host-side and a hit re-enters
    through device_put into the SAME insert program."""
    cfg0, dataset, _dir, params = setup
    cfg = dataclasses.replace(cfg0, buckets=((16, 400, 12),),
                              prefix_cache=True)
    model = FiraModel(cfg)
    n = 24
    mix = np.array([i % 5 for i in range(n)])
    times = poisson_times(n, rate=0.5, seed=3)
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        m = serve_split(model, params, dataset, cfg, arrival_times=times,
                        out_dir=str(tmp_path / "serve"), split="train",
                        clock="virtual", guard=guard, request_mix=mix)
        assert guard.compiles_after_warmup() == 0
    assert m["engine"]["cache_hits"] > 0
    assert m["serve"]["completed"] == n


def test_lru_eviction_under_undersized_cache_deterministic(setup):
    """An LRU sized below the working set evicts (metered) yet output
    stays bit-identical — a miss is only ever a re-prefill."""
    cfg0, dataset, _dir, params = setup
    model = FiraModel(cfg0)
    cold, _ = _drain(model, params, dataset, cfg0)
    tiny, tiny_eng = _drain(
        model, params, dataset,
        dataclasses.replace(cfg0, prefix_cache=True,
                            prefix_cache_entries=2))
    assert cold == tiny
    assert tiny_eng.stats.cache_evictions > 0
    assert tiny_eng.cache_len() <= 2


def test_refcount_release_on_harvest_and_retire(setup):
    """Grants release through the refcounted path on BOTH exits: a full
    drain (harvest) returns every block at refcount zero, and retire()
    releases a mid-flight engine's grants rather than scribbling the
    free list — with the requeue payloads still covering every owed
    request, coalesced followers included."""
    cfg0, dataset, _dir, params = setup
    cfg = dataclasses.replace(cfg0, prefix_cache=True)
    model = FiraModel(cfg)
    data = dataset.splits["train"]
    # harvest path: the bit-exactness test drains fully; here retire
    # mid-flight with duplicates in the arena
    eng = engine_lib.SlotEngine(model, params, cfg)
    feed = Feeder(assembly_tasks(data, REPEAT_CHUNKS, cfg, batch_size=4),
                  num_workers=0, depth=1, put=False)
    it = iter(feed)
    eng.begin_stream()
    for _ in range(3):
        item = next(it)
        eng.admit(item.host, item.index, None)
    eng.refill()
    assert eng.in_flight() > 0
    granted = eng._pool_blocks - len(eng._free_blocks)
    assert granted > 0
    assert eng.allocator_invariants() == []
    owed = set(eng.pending_positions())
    # duplicates coalesced: owed positions exceed seated+staged rows
    assert len(owed) > eng.in_flight() + eng.staged_rows
    payloads = eng.retire()
    feed.close()
    assert len(eng._free_blocks) == eng._pool_blocks
    assert eng._block_refs == {}
    assert eng.allocator_invariants() == []
    requeued = set()
    for p in payloads:
        v = np.asarray(p["valid"], dtype=bool)
        requeued.update(int(x) for x in np.asarray(p["_positions"])[v])
    assert requeued == owed  # followers survive dedup into the requeue


def test_shed_follower_detaches_leader_survives(setup, tmp_path):
    """Deadline-shed followers detach without killing the leader's seat:
    the leader (and every surviving follower) still completes with
    correct bytes; shed followers hold empty lines."""
    cfg0, dataset, _dir, params = setup
    model = FiraModel(cfg0)
    n = 24
    mix = np.array([i % 3 for i in range(n)])   # heavy duplication
    burst = np.zeros(n)
    cfg = dataclasses.replace(cfg0, prefix_cache=True, engine_slots=2,
                              serve_deadline_steps=3)
    m = serve_split(model, params, dataset, cfg, arrival_times=burst,
                    out_dir=str(tmp_path / "dl"), split="train",
                    clock="virtual", request_mix=mix)
    sv = m["serve"]
    assert sv["completed"] + sv["shed_deadline"] == n
    assert sv["completed"] > 0
    recs = m["request_records"]
    done_by_sample = {}
    lines = open(m["output_path"]).read().split("\n")
    for r in recs:
        if r["status"] == "done":
            done_by_sample.setdefault(int(mix[r["position"]]),
                                      set()).add(lines[r["position"]])
        else:
            assert lines[r["position"]] == ""
    # every completed duplicate of a sample holds the SAME line
    for sample, outs in done_by_sample.items():
        assert len(outs) == 1, f"sample {sample} diverged: {outs}"


def test_cache_off_is_inert_and_unstamped(setup):
    """The comparator contract: prefix_cache=False computes no digests,
    builds no cache, and admits exactly as before this PR."""
    cfg0, dataset, _dir, params = setup
    from fira_tpu.decode.runner import _decode_tasks

    eng = engine_lib.SlotEngine(FiraModel(cfg0), params, cfg0)
    assert eng._cache is None
    tasks, _ = _decode_tasks(dataset.splits["train"], cfg0)
    first = next(iter(tasks))()
    assert "_digests" not in first
    # and ON stamps worker-side through the same task path
    cfg_on = dataclasses.replace(cfg0, prefix_cache=True)
    tasks_on, _ = _decode_tasks(dataset.splits["train"], cfg_on)
    stamped = next(iter(tasks_on))()
    digs = stamped["_digests"]
    assert len(digs) == stamped["valid"].shape[0]
    assert all(d is not None for d, v in zip(digs, stamped["valid"]) if v)


def test_digest_is_content_addressed():
    """Identical payload bytes => identical digest; any field, dtype, or
    shape change => different digest (keyed blake2b, shape/dtype salted)."""
    host = {"diff": np.arange(12, dtype=np.int16).reshape(2, 6),
            "msg": np.ones((2, 3), np.int16),
            "valid": np.array([True, True]),
            "_positions": np.array([5, 6])}
    a = prefix_cache.payload_digests(host)
    b = prefix_cache.payload_digests(dict(host, _positions=np.array([9, 1])))
    assert a == b          # host-only fields don't address content
    host2 = dict(host, diff=host["diff"].copy())
    host2["diff"][1, 0] += 1
    c = prefix_cache.payload_digests(host2)
    assert a[0] == c[0] and a[1] != c[1]
    d = prefix_cache.payload_digests(
        dict(host, diff=host["diff"].astype(np.int32)))
    assert d[0] != a[0]    # dtype participates
    pad = prefix_cache.payload_digests(
        dict(host, valid=np.array([True, False])))
    assert pad[1] is None  # pad rows carry no digest


def test_prefix_cache_lru_unit():
    cache = prefix_cache.PrefixCache(2)
    p = {"diff": np.arange(4, dtype=np.int16),
         "sub_token": np.arange(3, dtype=np.int16)}
    assert cache.put("a", p) == 0
    assert cache.put("b", p) == 0
    assert cache.contains("a") and cache.take("a")[1] == "hit"  # touch a
    assert cache.put("c", p) == 1          # evicts b (LRU)
    assert not cache.contains("b")
    assert cache.contains("a") and cache.contains("c")
    assert cache.take("zzz") == (None, "miss")
    assert cache.nbytes > 0
    with pytest.raises(ValueError, match=">= 1"):
        prefix_cache.PrefixCache(0)


def test_prefix_cache_byte_budget():
    """The host-RAM bound: entries evict LRU-first until payload bytes
    fit; a single over-budget entry still lives (capacity degrades to
    one, the cache never refuses to serve); the byte meter tracks
    puts, refreshes, and clears exactly."""
    p = {"diff": np.arange(64, dtype=np.int16)}      # 128 bytes
    per = prefix_cache.payload_nbytes(p)
    cache = prefix_cache.PrefixCache(100, max_bytes=2 * per)
    assert cache.put("a", p) == 0
    assert cache.put("b", p) == 0
    assert cache.nbytes == 2 * per
    assert cache.put("c", p) == 1          # byte budget evicts oldest
    assert not cache.contains("a")
    assert cache.nbytes == 2 * per
    assert cache.put("c", p) == 0          # refresh: no double count
    assert cache.nbytes == 2 * per
    big = {"diff": np.arange(4096, dtype=np.int16)}  # alone over budget
    assert cache.put("big", big) == 2
    assert cache.contains("big") and len(cache) == 1
    cache.clear()
    assert cache.nbytes == 0
    with pytest.raises(ValueError, match=">= 0"):
        prefix_cache.PrefixCache(2, max_bytes=-1)


def test_dedup_flood_respects_queue_cap(setup, tmp_path):
    """Backpressure survives dedup: a burst of ONE hot digest against a
    bounded queue sheds past-cap followers (each fan-out group is
    bounded by the cap) instead of pinning unbounded payloads on one
    leader — and the served requests still complete byte-correct."""
    cfg0, dataset, _dir, params = setup
    n = 24
    mix = np.zeros(n, dtype=np.int64)     # every request the same sample
    cfg = dataclasses.replace(cfg0, prefix_cache=True, serve_queue_cap=4)
    m = serve_split(FiraModel(cfg0), params, dataset, cfg,
                    arrival_times=np.zeros(n),
                    out_dir=str(tmp_path / "flood"), split="train",
                    clock="virtual", request_mix=mix)
    sv = m["serve"]
    assert sv["shed_queue_full"] > 0
    assert sv["completed"] + sv["shed_queue_full"] == n
    assert sv["dedup_coalesced"] <= cfg.serve_queue_cap
    lines = open(m["output_path"]).read().split("\n")
    done = {lines[r["position"]] for r in m["request_records"]
            if r["status"] == "done"}
    assert len(done) == 1                  # one sample, one output line


def test_bucketed_drain_stamps_digests_worker_side(setup):
    """The composed production path (buckets x prefix_cache) hashes
    payloads on the feeder workers, not the scheduler thread: bucketed
    decode tasks arrive pre-stamped."""
    from fira_tpu.decode.runner import _decode_tasks

    cfg0, dataset, _dir, _params = setup
    cfg = dataclasses.replace(cfg0, buckets=((16, 400, 12),),
                              prefix_cache=True)
    tasks, table = _decode_tasks(dataset.splits["train"], cfg)
    assert table is not None
    batch = next(iter(tasks))()
    digs = batch["_digests"]
    assert all((d is not None) == bool(v)
               for d, v in zip(digs, batch["valid"]))


def test_prefix_cache_errors_and_cli_exit2(setup, tmp_path):
    cfg = fira_tiny()
    assert paging.prefix_cache_errors(cfg) == []   # off: nothing to check
    errs = paging.prefix_cache_errors(cfg.replace(prefix_cache=True))
    assert len(errs) == 1 and "decode engine" in errs[0]
    errs = paging.prefix_cache_errors(
        cfg.replace(prefix_cache=True, decode_engine=True,
                    prefix_cache_entries=0))
    assert len(errs) == 1 and "prefix_cache_entries" in errs[0]
    assert paging.prefix_cache_errors(
        cfg.replace(prefix_cache=True, decode_engine=True)) == []

    _cfg, _dataset, data_dir, _params = setup
    base = ["test", "--data-dir", data_dir, "--config", "fira-tiny",
            "--out-dir", str(tmp_path / "o")]
    # cache without the engine path: named message, exit 2
    assert cli.main(base + ["--prefix-cache", "on"]) == 2
    # zero-capacity LRU: named message, exit 2
    assert cli.main(base + ["--engine", "--prefix-cache", "on",
                            "--prefix-cache-entries", "0"]) == 2
    # serve defaults the cache ON and validates its capacity knob
    assert cli.main(["serve", "--data-dir", data_dir, "--config",
                     "fira-tiny", "--serve-rate", "5",
                     "--out-dir", str(tmp_path / "o"),
                     "--prefix-cache-entries", "-1"]) == 2
