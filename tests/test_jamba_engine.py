"""Jamba2-3B through the slot engine (decode/engine.py behind
decode/slot_model.JambaSlotModel): the engine's beams against a plain beam
search over the reference's log-probabilities on requests whose beams change
parents, at mixed depths with refills in between; the arena's leaves and
bytes by kind; the state counters on a hand-made stream; the refusals; and
the names the benchmark's readers find the engine's programs by."""

import json
import re

import jax
import numpy as np
import pytest

from jamba_util import ref_cfg, weights
from benchmark import reference_jamba as ref
from fira_tpu.config import (ARCH_TABLE, arch_errors, config_errors,
                             get_config, jamba_tiny)
from fira_tpu.data import buckets
from fira_tpu.data.feeder import Feeder
from fira_tpu.decode.engine import SlotEngine
from fira_tpu.decode.runner import run_lm_test

EOS, START = 1, 2


def _last_logp(rc, params, seq):
    """The reference's distribution after ``seq``, the pass padded at its
    end to a multiple of 16 tokens (what comes after a token cannot reach
    it): five shapes to compile, not one a length."""
    n = len(seq)
    tokens = np.zeros((-(-n // 16) * 16,), np.int32)
    tokens[:n] = seq
    return np.asarray(ref.forward(rc, params, tokens,
                                  rows=slice(n - 1, n)))[0]


def plain_beam_search(rc, params, prompt, n: int, K: int):
    """A beam search as a textbook has it, over the reference's
    log-probabilities: no cache, no state carried, the whole sequence
    through the reference at every step. -> (the most probable beam's
    tokens after <start>, its log-probability, the positions at which some
    beam did NOT continue the beam of its own index)."""
    beams = [([START], 0.0, False)]
    moved = 0
    for _ in range(n):
        cands = []
        for b, (toks, lp, fin) in enumerate(beams):
            if fin:
                cands.append((lp, b, None))
                continue
            logp = _last_logp(rc, params, np.concatenate([prompt, toks]))
            for t in np.argsort(-logp, kind="stable")[:K]:
                cands.append((lp + float(logp[t]), b, int(t)))
        cands.sort(key=lambda c: -c[0])
        new = []
        for lp, b, t in cands[:K]:
            toks, _, _fin = beams[b]
            new.append((toks, lp, True) if t is None
                       else (toks + [t], lp, t == EOS))
        if len(beams) == K:
            moved += any(b != k for k, (_lp, b, _t) in enumerate(cands[:K]))
        beams = new
        if all(b[2] for b in beams):
            break
    best = max(beams, key=lambda b: b[1])
    return best[0][1:], best[1], moved


def _requests():
    """Seven prompts over all three buckets, each with its own limit: with
    3 slots they sit at mixed depths and the arena is refilled twice."""
    rng = np.random.default_rng(2)
    lens = [5, 12, 21, 30, 44, 61, 9]
    prompts = [rng.integers(4, 64, n, dtype=np.int32) for n in lens]
    return prompts, np.asarray([3, 7, 11, 15, 5, 9, 13], np.int32)


def _drain(eng, cfg, reqs):
    tasks = buckets.prompt_tasks(cfg.lm, ((i, p, int(m)) for i, (p, m)
                                          in enumerate(zip(*reqs))))
    with Feeder(tasks, num_workers=0, depth=2) as feed:
        return list(eng.run(feed))


def test_engine_equals_a_plain_beam_search_over_the_reference(tmp_path):
    """Mixed buckets through 3 slots (slots are reused: a state lane holds
    another request's state when a new one is seated). The engine's served
    beam is the plain search's token for token, stops at the request's own
    limit, and its log-probability is the plain search's to 1e-3: float32
    both, sums of up to 15 log-probabilities that agree to ~1e-5 each, the
    candidates of a position ~1e-2 apart. The beams' parents are NOT the
    identity at several positions of several requests — where a state left
    on its old lane would continue the wrong history."""
    cfg = get_config("jamba-tiny", engine_slots=3)
    lm, rc = cfg.lm, ref_cfg(cfg.lm)
    params = weights(lm, seed=5)
    reqs = _requests()
    eng = SlotEngine(None, params, cfg)
    eng.prewarm(buckets.prompt_warm_batches(lm))
    items = {int(it.host["_positions"][it.row]): it
             for it in _drain(eng, cfg, reqs)}
    assert sorted(items) == list(range(7))
    moved_at = []
    for i, (prompt, n) in enumerate(zip(*reqs)):
        it = items[i]
        served = int(np.argmax(it.probs))
        got = [int(t) for t in it.tokens[served][1:n + 1]]
        want, logp, moved = plain_beam_search(rc, params, prompt, int(n),
                                              cfg.beam_size)
        assert got[:len(want)] == want, i
        assert not any(got[len(want):])            # only after an <eos>
        assert abs(float(it.probs[served]) - logp) < 1e-3, i
        moved_at.append(moved)
    assert sum(m >= 3 for m in moved_at) >= 3, moved_at
    st = eng.stats
    assert st.commits == 7 and st.slots_refilled == 7 and st.refills >= 3
    # the same requests through the runner's path, bytes of the output file
    out = run_lm_test(cfg, out_dir=str(tmp_path), params=params,
                      requests=reqs)
    lines = open(out["output_path"]).read().splitlines()
    for i, n in enumerate(reqs[1]):
        it = items[i]
        assert [int(t) for t in lines[i].split()] == [
            int(t) for t in it.tokens[int(np.argmax(it.probs))][1:n + 1]]
    e = out["engine"]
    assert e["prompt_tokens"] == sum(len(p) for p in reqs[0])
    # the arena's bytes follow the declared leaves BY KIND: the state does
    # not grow with the prompt; one attention layer keeps the longest
    # bucket whole and its share of the generated positions' pool
    K, f32 = cfg.beam_size, 4
    state = 3 * K * lm.d_inner * (lm.mamba_d_state * 4 + 3 * f32)
    assert e["kv_bytes_per_slot_state"] == state
    assert e["kv_bytes_per_slot_full"] == lm.prompt_len_max * lm.kv_dim * f32
    assert e["kv_bytes_per_slot_window"] == 0
    assert e["kv_bytes_per_slot"] == (
        state + e["kv_bytes_per_slot_full"]
        + K * cfg.tar_len * lm.kv_dim * f32)
    assert e["state_rows"] > 0 and e["attn_keys_read"] > 0


def test_state_counters_equal_the_hand_count_on_a_hand_made_stream():
    """``state_rows``: every position of every occupied slot updates K
    beams' state; ``attn_keys_read``: the one attention layer was asked for
    p + t + 1 keys at a request's t-th position. Each request alone tells
    the positions it ran (the device's own count of occupied slot-steps);
    all of them together through 2 slots count the same."""
    cfg = get_config("jamba-tiny", engine_slots=2, engine_harvest_every=4)
    eng = SlotEngine(None, weights(cfg.lm, seed=5), cfg)
    eng.prewarm(buckets.prompt_warm_batches(cfg.lm))
    rng = np.random.default_rng(7)
    lens, limits = [3, 7, 26, 50], [6, 15, 10, 4]
    prompts = [rng.integers(4, 64, n, dtype=np.int32) for n in lens]

    def mark():
        s = eng.stats
        return np.asarray([s.occupied_slot_steps, s.state_rows,
                           s.attn_keys_read])
    ran, rows, keys = [], 0, 0
    for p, m in zip(prompts, limits):
        at = mark()
        assert len(_drain(eng, cfg, ([p], [m]))) == 1
        n, r, k = (mark() - at).tolist()
        assert 0 < n <= m and r == cfg.beam_size * n
        assert k == sum(len(p) + t + 1 for t in range(n))
        ran.append(n)
        rows, keys = rows + r, keys + k
    at = mark()
    assert len(_drain(eng, cfg, (prompts, limits))) == 4
    assert (mark() - at).tolist() == [sum(ran), rows, keys]
    assert eng.stats.summary()["state_rows"] == eng.stats.state_rows


def test_arena_holds_state_a_beam_lane_beside_the_attention_layers_cache():
    """A leaf a Mamba layer for the state and one for the tail, rows folded
    (slot, lane) and d_inner last; the attention layer's prompt leaves
    shared by the beams; the pool's layer axis counts ATTENTION layers; the
    engine carries ``parent`` and no ancestry."""
    cfg = get_config("jamba-tiny", engine_slots=2)
    eng = SlotEngine(None, weights(cfg.lm), cfg)
    eng.prewarm(buckets.prompt_warm_batches(cfg.lm))
    lm, K = cfg.lm, cfg.beam_size
    leaves = eng._leaves
    assert sorted(n for n in leaves if "state" in n) == sorted(
        [f"ssm_state{j}" for j in range(3)]
        + [f"conv_state{j}" for j in range(3)])
    for j in range(3):
        ssm, conv = leaves[f"ssm_state{j}"], leaves[f"conv_state{j}"]
        assert ssm.shape == (2 * K, lm.mamba_d_state, lm.d_inner)
        assert ssm.dtype == np.float32          # whatever the compute dtype
        assert conv.shape == (lm.mamba_d_conv - 1, 2 * K, lm.d_inner)
        for leaf in (ssm, conv):
            assert leaf.kv and leaf.kv_kind == "state"
            assert leaf.reorder is None         # followed by ``parent``
    for n in ("prompt_k_full0", "prompt_v_full0"):
        assert leaves[n].shape == (2, lm.kv_dim // 2, 64)
        assert leaves[n].kv_kind == "full" and leaves[n].reorder is None
    assert "prompt_k_full1" not in leaves
    assert leaves["kv_pool"].shape == (1, eng._pool_blocks, K,
                                       eng._block_size, lm.kv_dim)
    assert leaves["kv_pool"].reorder == "pool"
    assert eng.smodel.beam_parent and not eng.smodel.beam_ancestry
    assert eng._state["parent"].shape == (2, K)
    assert "ancestry" not in eng._state
    assert eng.smodel.prefill_budget == 1
    # the other architectures carry no parent
    other = SlotEngine(None, None, get_config("fira-tiny"))
    assert other.smodel.beam_parent is False
    tags = [t for _b, t in buckets.prompt_warm_batches(lm)]
    fam = eng.labels_for_tags(tags)
    assert [f for f in fam if f.startswith("engine_prefill")] \
        == [f"engine_prefill[{t}]" for t in tags]
    # the scopes the trace is read by
    text = jax.jit(lambda p, st: eng._step_fn(p, st)).lower(
        eng._decode_params, eng._state).as_text(debug_info=True)
    names = " ".join(set(re.findall(r'loc\("([^"]*)"', text)))
    for scope in ("ssm.in_proj", "ssm.conv", "ssm.step", "ssm.out_proj",
                  "attn.full.decode", "mlp", "lm_head", "kv_reorder"):
        assert scope in names, scope
    wire = {k: v for k, v in buckets.prompt_warm_batches(lm)[1][0].items()
            if not k.startswith("_")}
    text = jax.jit(lambda p, b: eng._prefill_fn(p, b)).lower(
        eng.params, wire).as_text(debug_info=True)
    names = " ".join(set(re.findall(r'loc\("([^"]*)"', text)))
    for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.out_proj",
                  "attn.full.prefill", "mlp"):
        assert scope in names, scope


def test_one_table_says_what_an_arch_is():
    """config.ARCH_TABLE is read by the slot model's choice, the runner's
    weights and the refusals: the engine holds no architecture's name."""
    import inspect

    from fira_tpu.decode import engine, slot_model

    assert set(ARCH_TABLE) == {"fira", "axk1", "afmoe", "jamba", "brumby",
                               "lfm2"}
    for name, arch in ARCH_TABLE.items():
        assert hasattr(slot_model, arch.slot_model), name
    src = inspect.getsource(engine)
    assert "cfg.arch" not in src and ".arch ==" not in src
    assert "jamba" not in src.replace("model/jamba.COUNTERS", "")
    assert isinstance(slot_model.for_config(
        None, get_config("jamba-tiny"), 2, 4, 8), slot_model.JambaSlotModel)


def test_program_names_the_readers_find():
    """``jit__prefill_fn`` / ``jit__insert_fn`` / ``jit__step_fn`` for the
    third token architecture, as the benchmark's readers find them."""
    cfg = get_config("jamba-tiny", engine_slots=2)
    eng = SlotEngine(None, weights(cfg.lm), cfg)
    assert eng._step.__name__ == "_step_fn"
    assert eng._prefill.__name__ == "_prefill_fn"
    assert eng._insert.__name__ == "_insert_fn"
    eng.prewarm(buckets.prompt_warm_batches(cfg.lm))
    text = eng._step.lower(eng._decode_params, eng._state).as_text()
    assert "jit__step_fn" in text
    wire = {k: v for k, v in buckets.prompt_warm_batches(cfg.lm)[0][0]
            .items() if not k.startswith("_")}
    assert "jit__prefill_fn" in eng._prefill.lower(eng.params,
                                                   wire).as_text()


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
    """What ``arch_errors`` refuses for axk1 and afmoe it refuses for jamba
    through the same lines."""
    cfg = jamba_tiny(**REFUSED[what])
    errs = config_errors(cfg)
    assert errs and all("jamba" in e for e in errs), errs
    assert [e.replace("jamba", "afmoe") for e in errs] == config_errors(
        get_config("afmoe-tiny", **REFUSED[what]))
    if what != "non-engine beam":
        with pytest.raises(ValueError, match="jamba"):
            SlotEngine(None, None, cfg)


@pytest.mark.parametrize("command", ["train", "serve", "message"])
def test_cli_commands_it_does_not_run_exit_2_with_its_name(command, capsys):
    from fira_tpu import cli

    assert arch_errors(jamba_tiny(), command)
    rc = cli.main([command, "--engine", "--config", "jamba-tiny"]
                  + (["x.diff"] if command == "message" else []))
    assert rc == 2
    err = capsys.readouterr().err
    assert f"arch 'jamba' does not support cli {command}" in err


def test_cli_test_without_engine_is_refused_and_with_it_runs(tmp_path,
                                                             capsys):
    from fira_tpu import cli

    assert cli.main(["test", "--config", "jamba-tiny",
                     "--out-dir", str(tmp_path)]) == 2
    assert "non-engine beam" in capsys.readouterr().err
    assert cli.main(["test", "--engine", "--config", "jamba-tiny",
                     "--out-dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "prompt buckets: 3 engine prefill programs pre-warmed" in out
    assert len(open(tmp_path / "output_jamba").read().splitlines()) == 64
    spans = [json.loads(l) for l in open(tmp_path / "spans.jsonl")]
    pre = [s for s in spans if s.get("name") == "engine.prefill"]
    assert pre and set(pre[0]["ids"]) == {"bucket", "requests", "tokens",
                                          "padded_tokens"}


@pytest.mark.parametrize("bad,word", [
    (dict(num_experts=4), "num_experts"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(num_attention_heads=5), "num_attention_heads"),
    (dict(attn_layer_period=8, attn_layer_offset=5), "attn_layer_period"),
    (dict(prompt_buckets=(32, 16)), "prompt_buckets"),
])
def test_a_key_block_that_cannot_be_is_named(bad, word):
    import dataclasses

    lm = dataclasses.replace(get_config("jamba-tiny").lm, **bad)
    assert any(word in e for e in config_errors(
        get_config("jamba-tiny", lm=lm)))


def test_each_arch_takes_its_own_key_block_only():
    a, j = get_config("afmoe-tiny").lm, get_config("jamba-tiny").lm
    assert any("config.JambaConfig" in e for e in config_errors(
        get_config("jamba-tiny", lm=a)))
    assert any("config.AfmoeConfig" in e for e in config_errors(
        get_config("afmoe-tiny", lm=j)))
    assert config_errors(get_config("jamba2-3b")) == []
    assert get_config("jamba2-3b").lm.attention_layers == (7, 21)
    assert len(get_config("jamba2-3b").lm.mamba_layers) == 26
