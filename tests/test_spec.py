"""Speculative copy-head draft-and-verify decode (decode/spec.py).

Pins the spec contract (docs/DECODE_ENGINE.md "Speculative drafting"):

- accepted output BIT-EXACT (tokens AND probs, file bytes) vs plain
  engine decode — for both drafter tiers, at the engine's default shape
  and at one of its own (slots, cadence, block size, score space);
- file bytes invariant to the draft length k, the harvest cadence, and
  the replica count — the acceptance pattern is scheduling, never output;
- real work: acceptances > 0 on draftable streams (the copy tier
  saturates under copy_biased_params(target_blind=True)), stall cooldown
  falls back to plain dispatches when the drafter cannot see the stream;
- zero post-warmup compiles with the spec programs declared in the
  engine's compile-guard family;
- parse-time validation: named-knob messages, CLI exit 2, default off.
"""

import dataclasses

import jax
import numpy as np
import pytest

from fira_tpu.analysis import sanitizer
from fira_tpu.config import fira_tiny
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.feeder import Feeder
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.decode import engine as engine_lib
from fira_tpu.decode import spec as spec_lib
from fira_tpu.decode.beam import eos_biased_params
from fira_tpu.decode.runner import _decode_tasks, run_test
from fira_tpu.model.model import FiraModel
from fira_tpu.train.state import init_state


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("spec_corpus"))
    write_corpus_dir(data_dir, n_commits=40, seed=13)
    cfg = fira_tiny(batch_size=8, test_batch_size=6)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    from fira_tpu.data.batching import make_batch

    batch = make_batch(dataset.splits["train"], np.arange(6), cfg)
    params = init_state(FiraModel(cfg), cfg, batch).params
    # moderate EOS bias: mixed settle depths (the engine's real regime),
    # still enough emitted positions for drafts to land or miss
    return cfg, dataset, data_dir, params, eos_biased_params(params,
                                                             delta=4.0)


def _engine_outputs(model, params, cfg, dataset):
    """Run the engine over the train split; return ({pos: (toks, probs)},
    stats)."""
    data = dataset.splits["train"]
    eng = engine_lib.SlotEngine(model, params, cfg)
    tasks, _ = _decode_tasks(data, cfg)
    out = {}
    with Feeder(tasks, num_workers=0, depth=1) as feed:
        for it in eng.run(feed):
            out[it.position] = (it.tokens, it.probs)
    return out, eng.stats


# the engine's shape under the drafter, as production varies it
SHAPES = {
    "defaults": dict(),
    "own-shape": dict(engine_slots=4, engine_harvest_every=1,
                      kv_block_size=3, beam_compat_prob_space=False),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("tier", ("copy", "draft"))
def test_spec_bit_exact_per_sample(setup, tier, shape):
    """Spec-on (tokens, probs) == spec-off (tokens, probs), per sample,
    bitwise — acceptance moves scheduling only, never output."""
    cfg0, dataset, _dir, _params, eos_params = setup
    base = dataclasses.replace(cfg0, decode_engine=True, **SHAPES[shape])
    model = FiraModel(base)
    ref, ref_stats = _engine_outputs(model, eos_params, base, dataset)
    got, stats = _engine_outputs(
        model, eos_params,
        dataclasses.replace(base, spec_decode=tier, engine_spec_k=4),
        dataset)
    assert set(got) == set(ref)
    for p in ref:
        np.testing.assert_array_equal(got[p][0], ref[p][0])
        np.testing.assert_array_equal(got[p][1], ref[p][1])
    # the spec path really ran: every non-cooldown dispatch drafted+verified
    assert stats.verify_dispatches > 0
    assert stats.drafted >= 4 * stats.verify_dispatches  # k per occupied slot
    assert stats.commits == ref_stats.commits == len(dataset.splits["train"])
    if tier == "draft":
        # the greedy full-step roll tracks the real beam well enough to
        # land real acceptances on this stream (observed ~0.25)
        assert stats.accepted > 0
        # accepted frames beyond one-per-slot are exactly the saved ones
        assert stats.steps_saved > 0
        # dispatch-ledger steps never exceed plain; a tie happens when
        # the savings land inside one harvest-cadence chunk
        assert stats.steps <= ref_stats.steps


def test_spec_file_bytes_invariant_to_k_cadence_and_paging(setup, tmp_path):
    """run_test file bytes: plain engine == spec for k in {2, 4, 8}, any
    harvest cadence and any KV block size — under the armed sanitizer with
    the draft/verify programs declared in the guard family (zero
    post-warmup compiles)."""
    cfg0, dataset, _dir, _params, eos_params = setup
    # a bucketed stream: the declared-family story (guard.declare over
    # eng.labels) only arms on a bucket table, as in test_engine
    cfg = dataclasses.replace(cfg0, decode_engine=True,
                              buckets=((16, 400, 12),))
    model = FiraModel(cfg)
    ref = run_test(model, eos_params, dataset, cfg,
                   out_dir=str(tmp_path / "ref"), split="train")
    ref_bytes = open(ref["output_path"], "rb").read()
    variants = [
        dict(spec_decode="draft", engine_spec_k=2, engine_harvest_every=1),
        dict(spec_decode="draft", engine_spec_k=8, engine_harvest_every=3),
        dict(spec_decode="copy", engine_spec_k=4, kv_block_size=3),
    ]
    for i, v in enumerate(variants):
        c = dataclasses.replace(cfg, **v)
        with sanitizer.sanitize(nans=False, infs=False) as guard:
            m = run_test(model, eos_params, dataset, c,
                         out_dir=str(tmp_path / f"v{i}"), guard=guard,
                         split="train")
            assert guard.compiles_after_warmup() == 0, v
        assert open(m["output_path"], "rb").read() == ref_bytes, v
        k = v["engine_spec_k"]
        seen = set(guard._seen)
        assert any(s.startswith(f"{spec_lib.DRAFT_LABEL}[k{k}")
                   for s in seen), seen
        assert any(s.startswith(f"{spec_lib.VERIFY_LABEL}[k{k}")
                   for s in seen), seen
        assert m["engine"]["verify_dispatches"] > 0
        assert m["sentence_bleu"] == ref["sentence_bleu"]
    # an undeclared spec geometry raises at its dispatch
    with pytest.raises(sanitizer.RetraceError, match="declared"):
        guard.step(f"{spec_lib.VERIFY_LABEL}[k99]")


def test_spec_copy_tier_acceptance_saturates_when_target_blind(setup):
    """copy_biased_params(target_blind=True) makes the copy drafter's
    proxy scores EXACTLY the real step's copy scores: acceptance
    saturates, the step count collapses below the plain twin's, and the
    output still matches that twin bit-for-bit."""
    cfg0, dataset, _dir, params, _eos = setup
    # NO eos bias here: early-settling rows truncate drafts mid-accept
    # (frames past a row's EOS can never be accepted), which caps the
    # measured rate well below the drafter's true hit rate
    biased = spec_lib.copy_biased_params(params, delta=9.0,
                                         target_blind=True)
    base = dataclasses.replace(cfg0, decode_engine=True)
    model = FiraModel(base)
    ref, ref_stats = _engine_outputs(model, biased, base, dataset)
    # k sized to this stream's mean accept run (~2 frames): the matched
    # frames are a property of the stream, so a longer k only dilutes
    # acceptance_rate with never-acceptable draft-tail frames
    got, stats = _engine_outputs(
        model, biased,
        dataclasses.replace(base, spec_decode="copy", engine_spec_k=3),
        dataset)
    for p in ref:
        np.testing.assert_array_equal(got[p][0], ref[p][0])
        np.testing.assert_array_equal(got[p][1], ref[p][1])
    assert stats.accepted > 0
    assert stats.acceptance_rate > 0.5, stats.summary()
    assert stats.steps < ref_stats.steps
    assert stats.steps_per_commit < ref_stats.steps_per_commit


def test_spec_stall_cooldown_falls_back_to_plain(setup):
    """A drafter that cannot see the stream (random-init copy head on a
    generated-token regime) accepts (near-)nothing: the engine must fall
    back to plain dispatches on the cooldown — output still exact, and
    verify dispatches strictly rarer than step dispatches."""
    cfg0, dataset, _dir, _params, eos_params = setup
    base = dataclasses.replace(cfg0, decode_engine=True)
    model = FiraModel(base)
    ref, _ref_stats = _engine_outputs(model, eos_params, base, dataset)
    got, stats = _engine_outputs(
        model, eos_params,
        dataclasses.replace(base, spec_decode="copy", engine_spec_k=4),
        dataset)
    for p in ref:
        np.testing.assert_array_equal(got[p][0], ref[p][0])
        np.testing.assert_array_equal(got[p][1], ref[p][1])
    # a random-init head is (near-)blind: the odd lucky frame is fine,
    # sustained acceptance is not
    assert stats.acceptance_rate < 0.05, stats.summary()
    # STALL_COOLDOWN plain dispatches follow every all-miss verify
    assert stats.verify_dispatches < stats.step_dispatches


def test_spec_fleet_replica_invariance(setup, tmp_path):
    """A 2-replica fleet with spec armed writes the single-engine plain
    path's bytes; the fleet summary aggregates the spec counters and
    reports per-replica acceptance."""
    cfg0, dataset, _dir, _params, eos_params = setup
    cfg = dataclasses.replace(cfg0, decode_engine=True)
    model = FiraModel(cfg)
    ref = run_test(model, eos_params, dataset, cfg,
                   out_dir=str(tmp_path / "one"), split="train")
    m = run_test(model, eos_params, dataset,
                 dataclasses.replace(cfg, spec_decode="draft",
                                     engine_replicas=2),
                 out_dir=str(tmp_path / "two"), split="train")
    assert (open(m["output_path"], "rb").read()
            == open(ref["output_path"], "rb").read())
    eng = m["engine"]
    assert eng["replicas"] == 2
    assert eng["verify_dispatches"] > 0
    assert eng["drafted"] >= eng["accepted"] >= 0
    assert len(eng["per_replica_acceptance"]) == 2


def test_spec_errors_named_knob_messages():
    base = fira_tiny().replace(decode_engine=True)

    assert spec_lib.spec_errors(base) == []  # default off: nothing to check
    assert spec_lib.spec_errors(base.replace(spec_decode="off",
                                             engine_spec_k=999)) == []

    errs = spec_lib.spec_errors(base.replace(spec_decode="turbo"))
    assert len(errs) == 1 and "spec_decode" in errs[0]

    errs = spec_lib.spec_errors(
        base.replace(decode_engine=False, spec_decode="copy"))
    assert len(errs) == 1 and "requires decode_engine" in errs[0]

    # k must fit the smallest declared decode tar budget minus <start>
    errs = spec_lib.spec_errors(base.replace(spec_decode="draft",
                                             engine_spec_k=0))
    assert len(errs) == 1 and "engine_spec_k" in errs[0]
    errs = spec_lib.spec_errors(base.replace(spec_decode="draft",
                                             engine_spec_k=99))
    assert len(errs) == 1 and "tar budget" in errs[0]
    assert spec_lib.spec_errors(base.replace(spec_decode="draft",
                                             engine_spec_k=2)) == []

    # under decode_tar_buckets the smallest bucket tar tightens the bound
    tarred = base.replace(buckets=((16, 400, 6),), decode_tar_buckets=True)
    errs = spec_lib.spec_errors(tarred.replace(spec_decode="copy",
                                               engine_spec_k=8))
    assert len(errs) == 1 and "[1, 5]" in errs[0]


def test_cli_exits_2_on_spec_knobs(setup, tmp_path):
    """Parse-time rejection with named-knob messages — not a silent
    no-op or a mid-run error (the paging_errors exit-2 contract)."""
    from fira_tpu import cli

    _cfg, _dataset, data_dir, _params, _eos = setup
    base = ["test", "--data-dir", data_dir, "--config", "fira-tiny",
            "--out-dir", str(tmp_path / "o")]
    # spec without the engine path: named message, not a plain decode
    assert cli.main(base + ["--spec-decode", "copy"]) == 2
    # k past the declared tar budget
    assert cli.main(base + ["--engine", "--spec-decode", "draft",
                            "--spec-k", "99"]) == 2
    # an unknown tier dies in argparse choices (also exit 2)
    with pytest.raises(SystemExit) as exc:
        cli.main(base + ["--engine", "--spec-decode", "turbo"])
    assert exc.value.code == 2
    # valid spec knobs get PAST parse-time validation: the run then fails
    # on the missing checkpoint (rc 1), not on knob admission
    rc = cli.main(base + ["--engine", "--spec-decode", "copy",
                          "--spec-k", "2"])
    assert rc == 1


# --------------------------------------------------------------------------
# slow sweeps (excluded from the tier-1 gate)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_spec_acceptance_sweep_slow(setup):
    """Acceptance across the gate-bias sweep: output bytes pinned at every
    point while the recorded acceptance rate moves with draftability —
    the machine-recorded (not assumed) acceptance the bench rows cite."""
    cfg0, dataset, _dir, params, _eos = setup
    base = dataclasses.replace(cfg0, decode_engine=True)
    model = FiraModel(base)
    rates = []
    for delta in (0.0, 3.0, 6.0):
        biased = spec_lib.copy_biased_params(
            eos_biased_params(params, delta=4.0), delta=delta,
            target_blind=True)
        ref, _ = _engine_outputs(model, biased, base, dataset)
        for k in (2, 4, 8):
            got, stats = _engine_outputs(
                model, biased,
                dataclasses.replace(base, spec_decode="copy",
                                    engine_spec_k=k), dataset)
            for p in ref:
                np.testing.assert_array_equal(got[p][0], ref[p][0])
                np.testing.assert_array_equal(got[p][1], ref[p][1])
            rates.append((delta, k, stats.acceptance_rate))
    # the hard-biased copy regime must dominate the unbiased one
    hard = [r for d, _k, r in rates if d == 6.0]
    soft = [r for d, _k, r in rates if d == 0.0]
    assert min(hard) > max(soft), rates


@pytest.mark.slow
def test_spec_saturated_fleet_slow(setup, tmp_path):
    """Saturated copy-tier acceptance on a multi-replica fleet: bytes
    equal to the plain single engine, spec counters aggregate across
    replicas, and every replica drafts."""
    cfg0, dataset, _dir, params, _eos = setup
    biased = spec_lib.copy_biased_params(
        eos_biased_params(params, delta=4.0), delta=9.0, target_blind=True)
    cfg = dataclasses.replace(cfg0, decode_engine=True)
    model = FiraModel(cfg)
    ref = run_test(model, biased, dataset, cfg,
                   out_dir=str(tmp_path / "one"), split="train")
    for n_rep in (2, 3):
        m = run_test(model, biased, dataset,
                     dataclasses.replace(cfg, spec_decode="copy",
                                         engine_replicas=n_rep),
                     out_dir=str(tmp_path / f"rep{n_rep}"), split="train")
        assert (open(m["output_path"], "rb").read()
                == open(ref["output_path"], "rb").read())
        eng = m["engine"]
        assert eng["replicas"] == n_rep
        assert eng["acceptance_rate"] > 0.5
        assert len(eng["per_replica_acceptance"]) == n_rep
        assert all(r > 0 for r in eng["per_replica_acceptance"])
