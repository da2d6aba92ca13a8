"""A.X-K1 through the slot engine (decode/engine.py behind
decode/slot_model.py): the engine's beams against a plain beam search over
the reference's log-probabilities, per-request limits, the refusals, and the
names the benchmark's readers find the engine's programs by."""

import dataclasses
import re

import jax
import numpy as np
import pytest

from axk1_util import ref_cfg, small_query_blocks, weights  # noqa: F401
from benchmark import reference_axk1 as ref
from fira_tpu.config import (arch_errors, axk1_tiny, config_errors,
                             get_config)
from fira_tpu.data.synthetic import make_prompt_requests
from fira_tpu.decode.engine import SlotEngine
from fira_tpu.decode.runner import run_lm_test

EOS, START = 1, 2


def plain_beam_search(rc, params, prompt, n: int, K: int):
    """A beam search as a textbook has it, over the reference's
    log-probabilities: no cache, no batching, the whole sequence through
    the reference at every step. -> the most probable beam's tokens after
    <start>."""
    beams = [([START], 0.0, False)]
    for _ in range(n):
        cands = []
        for b, (toks, lp, fin) in enumerate(beams):
            if fin:
                cands.append((lp, b, None))
                continue
            logp = np.asarray(ref.forward(
                rc, params, np.concatenate([prompt, toks])))[-1]
            for t in np.argsort(-logp, kind="stable")[:K]:
                cands.append((lp + float(logp[t]), b, int(t)))
        cands.sort(key=lambda c: -c[0])
        new = []
        for lp, b, t in cands[:K]:
            toks, _, _fin = beams[b]
            new.append((toks, lp, True) if t is None
                       else (toks + [t], lp, t == EOS))
        beams = new
        if all(b[2] for b in beams):
            break
    return max(beams, key=lambda b: b[1])[0][1:]


def test_engine_equals_a_plain_beam_search_over_the_reference(tmp_path):
    """Ten requests of mixed prompt length through 3 slots (so slots are
    reused and prompts of every bucket share the arena), each with its OWN
    position limit: the engine's served beam is the plain search's, token
    for token, and stops at the request's limit. Float32 program against
    float32 reference: the 3 best candidates of a position lie ~1e-2 apart
    and the two compute the same sums to ~1e-6, so ranks agree."""
    cfg = get_config("axk1-tiny", engine_slots=3)
    lm, rc = cfg.lm, ref_cfg(cfg.lm)
    params = weights(lm, seed=5)
    reqs = make_prompt_requests(10, vocab_size=lm.vocab_size, seed=2,
                                min_len=8, max_len=64, limits=(3, 7, 11, 15))
    out = run_lm_test(cfg, out_dir=str(tmp_path), params=params,
                      requests=reqs)
    lines = open(out["output_path"]).read().splitlines()
    assert len(lines) == 10
    for i, (prompt, n) in enumerate(zip(*reqs)):
        got = [int(t) for t in lines[i].split()]
        assert len(got) == int(n)                  # its own limit, honoured
        want = plain_beam_search(rc, params, prompt, int(n), cfg.beam_size)
        assert got[:len(want)] == want, i
        assert not any(got[len(want):])            # only after an <eos>
    eng = out["engine"]
    assert eng["commits"] == 10 and eng["prompt_tokens"] == sum(
        len(p) for p in reqs[0])
    assert eng["prompt_tokens_padded"] >= eng["prompt_tokens"]
    assert eng["prefills"] > 0
    # every real token and every live beam row chose top-k experts in each
    # of the two expert layers; all 16 are held at this preset
    assert eng["moe_assignments"] == eng["moe_assignments_held"] > 0
    assert eng["moe_assignments"] % (2 * lm.num_experts_per_tok) == 0
    assert 0 < eng["moe_held_load_max"] <= eng["moe_assignments_held"]
    # the arena's bytes follow the declared leaves: the prompt latents of
    # one slot plus its share of the generated-position pool
    item = 4       # float32 at this preset
    per_slot = lm.num_hidden_layers * lm.latent_dim * item * (
        lm.prompt_len_max + cfg.beam_size * cfg.tar_len)
    assert eng["kv_bytes_per_slot"] == per_slot


def test_prefill_budget_paces_admission_and_leaves_the_output(tmp_path,
                                                              monkeypatch):
    """``LMSlotModel.prefill_budget`` is 1: once slots are seated, never
    two prefill dispatches without a step between them — the seated slots
    wait for one prefill, not for every free slot's. Unpaced (0, FIRA's),
    the same stream refills every free slot first. Scheduling only: both
    write the same lines."""
    events = []
    for name in ("admit", "step_dispatch"):
        inner = getattr(SlotEngine, name)

        def spy(self, *a, _inner=inner, _name=name, **kw):
            events.append(_name)
            return _inner(self, *a, **kw)
        monkeypatch.setattr(SlotEngine, name, spy)
    from fira_tpu.decode.slot_model import FiraSlotModel, LMSlotModel

    assert (FiraSlotModel.prefill_budget, LMSlotModel.prefill_budget) == (0, 1)
    cfg = get_config("axk1-tiny", engine_slots=4)
    lm = cfg.lm
    params = weights(lm, seed=5)
    reqs = make_prompt_requests(12, vocab_size=lm.vocab_size, seed=3,
                                min_len=8, max_len=64, limits=(3, 7, 11, 15))
    lines, most = {}, {}
    for budget in (1, 0):
        del events[:]
        monkeypatch.setattr(LMSlotModel, "prefill_budget", budget)
        out = run_lm_test(cfg, out_dir=str(tmp_path / str(budget)),
                          params=params, requests=reqs)
        lines[budget] = open(out["output_path"]).read()
        runs = re.findall(r"a+", "".join(e[0] for e in events))
        most[budget] = max(len(r) for r in runs)
    assert most[1] == 1 and most[0] > 1, most
    assert lines[1] == lines[0] and len(lines[1].splitlines()) == 12


def test_arena_leaves_say_what_follows_the_beams():
    """The prompt's latents are SHARED by a slot's beams (one copy a slot,
    no beam axis); the generated positions' pool is per beam and reordered."""
    cfg = get_config("axk1-tiny", engine_slots=2)
    eng = SlotEngine(None, weights(cfg.lm), cfg)
    from fira_tpu.data import buckets

    eng.prewarm(buckets.prompt_warm_batches(cfg.lm))
    lm, K = cfg.lm, cfg.beam_size
    leaves = eng._leaves
    assert leaves["prompt_lat"].reorder is None and leaves["prompt_lat"].kv
    assert leaves["prompt_lat"].shape == (lm.num_hidden_layers, 2,
                                          lm.prompt_len_max, lm.latent_dim)
    assert leaves["lat_pool"].reorder == "pool"
    assert leaves["lat_pool"].shape[2:] == (K, eng._block_size,
                                            lm.latent_dim)
    # one prefill and one insert program a bucket, one step
    tags = [t for _b, t in buckets.prompt_warm_batches(lm)]
    fam = eng.labels_for_tags(tags)
    assert [f for f in fam if f.startswith("engine_prefill")] \
        == [f"engine_prefill[{t}]" for t in tags]
    assert [f for f in fam if f.startswith("engine_insert")] \
        == [f"engine_insert[{t}]" for t in tags]


def test_lat_pool_follows_src_beam_through_permute_pool_not_ancestry(
        monkeypatch):
    """FIRA's pools follow their beams by the engine's ancestry table; this
    model did not ask for one: its state holds no such leaf, and its step
    still moves ``lat_pool``'s block contents by ``src_beam``."""
    from fira_tpu.data import buckets
    from fira_tpu.decode import slot_model

    cfg = get_config("axk1-tiny", engine_slots=2)
    eng = SlotEngine(None, weights(cfg.lm), cfg)
    eng.prewarm(buckets.prompt_warm_batches(cfg.lm))
    assert eng.smodel.beam_ancestry is False
    assert "ancestry" not in eng._state
    assert eng._leaves["lat_pool"].reorder == "pool"
    moved = []
    inner = slot_model.permute_pool

    def spy(pool, tab_step, src_beam):
        moved.append((pool.shape, src_beam.shape))
        return inner(pool, tab_step, src_beam)

    monkeypatch.setattr(slot_model, "permute_pool", spy)
    # a fresh function: jit would serve the prewarmed step's trace
    text = jax.jit(lambda p, st: eng._step_fn(p, st)).lower(
        eng._decode_params, eng._state).as_text(debug_info=True)
    K = cfg.beam_size
    assert moved == [(eng._state["lat_pool"].shape, (2, K))]
    names = set(re.findall(r'loc\("(kv_reorder/[^"]*)"', text))
    assert any("gather" in n for n in names)
    assert any("scatter" in n for n in names)


@pytest.mark.parametrize("arch", ["fira", "axk1"])
def test_program_names_the_readers_find(arch):
    """benchmark/readers/module_time.py finds the engine's programs in the
    device trace by these names (``jit__step_fn(<fingerprint>)`` ...), for
    both architectures; the train step's is pinned here beside them."""
    if arch == "axk1":
        cfg = get_config("axk1-tiny", engine_slots=2)
        eng = SlotEngine(None, weights(cfg.lm), cfg)
    else:
        from fira_tpu.data.batching import make_batch
        from fira_tpu.data.synthetic import make_memory_split
        from fira_tpu.model.model import FiraModel

        cfg = get_config("fira-tiny", decode_engine=True, engine_slots=2)
        cfg, split, _v = make_memory_split(cfg, 8, seed=0)
        model = FiraModel(cfg)
        batch = make_batch(split, np.arange(2), cfg,
                           batch_size=cfg.test_batch_size)
        wire = {k: v for k, v in batch.items() if not k.startswith("_")}
        params = model.init(jax.random.PRNGKey(0), wire,
                            deterministic=True)["params"]
        eng = SlotEngine(model, params, cfg)
    names = {fn: jax.jit(getattr(eng, fn)).__name__
             for fn in ("_step_fn", "_prefill_fn", "_insert_fn")}
    assert names == {"_step_fn": "_step_fn", "_prefill_fn": "_prefill_fn",
                     "_insert_fn": "_insert_fn"}
    # the engine's own jitted handles are those methods' (XLA names the
    # module jit_<name>)
    assert eng._step.__name__ == "_step_fn"
    assert eng._prefill.__name__ == "_prefill_fn"
    assert eng._insert.__name__ == "_insert_fn"
    from fira_tpu.train import step as train_step

    src = open(train_step.__file__).read()
    assert re.search(r"def multi_step\(", src)


REFUSED = {
    "prefix_cache": dict(prefix_cache=True),
    "spec_decode": dict(spec_decode="draft"),
    "int8w": dict(serve_precision="int8w"),
    "bf16-weight-tier": dict(serve_precision="bf16"),
    "kv_dtype": dict(kv_dtype="bf16"),
    "engine_replicas": dict(engine_replicas=2, engine_slots=4),
    "serve/disagg.py": dict(serve_tiers="prefill-pool"),
    "non-engine beam": dict(decode_engine=False),
    "graph buckets": dict(buckets=((16, 400, 12),)),
    "beam_compat_prob_space": dict(beam_compat_prob_space=True),
    "buckets": dict(decode_tar_buckets=True),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_unsupported_combinations_are_refused_by_name(what):
    cfg = axk1_tiny(**REFUSED[what])
    errs = config_errors(cfg)
    assert errs and all("axk1" in e for e in errs), errs
    word = {"int8w": "int8w", "bf16-weight-tier": "serve_precision",
            "non-engine beam": "non-engine",
            "serve/disagg.py": "serve/disagg.py",
            "graph buckets": "buckets / decode_tar_buckets",
            "buckets": "decode_tar_buckets"}.get(what, what)
    assert any(word in e for e in errs), errs
    if what != "non-engine beam":
        with pytest.raises(ValueError, match="axk1"):
            SlotEngine(None, None, cfg)


@pytest.mark.parametrize("command", ["train", "serve", "message"])
def test_cli_commands_it_does_not_run_exit_2_with_its_name(command, capsys):
    from fira_tpu import cli

    assert arch_errors(axk1_tiny(), command)
    rc = cli.main([command, "--engine", "--config", "axk1-tiny"]
                  + (["x.diff"] if command == "message" else []))
    assert rc == 2
    err = capsys.readouterr().err
    assert f"arch 'axk1' does not support cli {command}" in err


def test_cli_test_without_engine_is_refused_and_with_it_runs(tmp_path,
                                                             capsys):
    from fira_tpu import cli

    assert cli.main(["test", "--config", "axk1-tiny",
                     "--out-dir", str(tmp_path)]) == 2
    assert "non-engine beam" in capsys.readouterr().err
    assert cli.main(["test", "--engine", "--config", "axk1-tiny",
                     "--out-dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "prompt buckets: 3 engine prefill programs pre-warmed" in out
    assert len(open(tmp_path / "output_axk1").read().splitlines()) == 64
    import json

    spans = [json.loads(l) for l in open(tmp_path / "spans.jsonl")]
    pre = [s for s in spans if s.get("name") == "engine.prefill"]
    assert pre and set(pre[0]["ids"]) == {"bucket", "requests", "tokens",
                                          "padded_tokens"}
    assert pre[0]["ids"]["bucket"] in ("p16", "p32", "p64")


def test_fira_takes_no_lm_block_and_unknown_arch_is_named():
    assert any("lm block" in e for e in config_errors(
        get_config("fira-tiny", lm=get_config("axk1-tiny").lm)))
    assert any("arch 'gpt'" in e for e in config_errors(
        get_config("fira-tiny", arch="gpt")))
    assert config_errors(get_config("fira-tiny")) == []
    bad = dataclasses.replace(get_config("axk1-tiny").lm, expert_offset=14)
    assert any("expert_offset" in e
               for e in config_errors(get_config("axk1-tiny", lm=bad)))
