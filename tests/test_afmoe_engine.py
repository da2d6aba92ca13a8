"""Trinity-Mini through the slot engine (decode/engine.py behind
decode/slot_model.AfmoeSlotModel): the engine's beams against a plain beam
search over the reference's log-probabilities with per-request limits and
mixed buckets in one run, the arena's leaves and bytes by layer type, the
attention counters on a hand-made stream, the refusals, and the names the
benchmark's readers find the engine's programs by."""

import json
import re

import jax
import numpy as np
import pytest

from afmoe_util import ref_cfg, small_query_blocks, weights  # noqa: F401
from benchmark import reference_afmoe as ref
from fira_tpu.config import (ARCH_TABLE, afmoe_tiny, arch_errors,
                             config_errors, get_config)
from fira_tpu.data import buckets
from fira_tpu.data.feeder import Feeder
from fira_tpu.decode.engine import SlotEngine
from fira_tpu.decode.runner import run_lm_test

EOS, START = 1, 2


def _last_logp(rc, params, seq):
    """The reference's distribution after ``seq``, the pass padded to a
    multiple of 16 tokens (padding sees only itself): five shapes to
    compile, not one a length."""
    n = len(seq)
    T = -(-n // 16) * 16
    tokens = np.zeros((T,), np.int32)
    tokens[:n] = seq
    order = np.arange(T)
    seen = (order[None, :] <= order[:, None]) & (order[None, :] < n) \
        & (order[:, None] < n) | np.eye(T, dtype=bool)
    return np.asarray(ref.forward(rc, params, tokens, "f32",
                                  np.where(order < n, order, 0), seen,
                                  rows=slice(n - 1, n)))[0]


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
            logp = _last_logp(rc, params, np.concatenate([prompt, toks]))
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


def _requests():
    """Seven prompts over all three buckets — shorter than the window of 8,
    a few windows long, and two of the longest bucket, which dispatch ONE
    request a prefill (64 padded tokens) — each with its own limit."""
    rng = np.random.default_rng(2)
    lens = [5, 12, 21, 30, 44, 61, 9]
    prompts = [rng.integers(4, 64, n, dtype=np.int32) for n in lens]
    return prompts, np.asarray([3, 7, 11, 15, 5, 9, 13], np.int32)


def test_engine_equals_a_plain_beam_search_over_the_reference(tmp_path):
    """(e) Mixed buckets through 3 slots (slots are reused; prompts of
    every bucket share the arena, rings and whole prompts alike): the
    engine's served beam is the plain search's, token for token, and stops
    at the request's own limit. Float32 both: the two compute the same
    sums to ~1e-6 and the candidates of a position lie ~1e-2 apart."""
    cfg = get_config("afmoe-tiny", engine_slots=3)
    lm, rc = cfg.lm, ref_cfg(cfg.lm)
    params = weights(lm, seed=5)
    reqs = _requests()
    assert lm.bucket_rows(64) == 1
    out = run_lm_test(cfg, out_dir=str(tmp_path), params=params,
                      requests=reqs)
    lines = open(out["output_path"]).read().splitlines()
    assert len(lines) == len(reqs[0])
    for i, (prompt, n) in enumerate(zip(*reqs)):
        got = [int(t) for t in lines[i].split()]
        assert len(got) == int(n)                  # its own limit, honoured
        want = plain_beam_search(rc, params, prompt, int(n), cfg.beam_size)
        assert got[:len(want)] == want, i
        assert not any(got[len(want):])            # only after an <eos>
    eng = out["engine"]
    assert eng["commits"] == 7 and eng["prompt_tokens"] == sum(
        len(p) for p in reqs[0])
    assert eng["moe_assignments"] == eng["moe_assignments_held"] > 0
    # the arena's bytes follow the declared leaves, BY LAYER TYPE: one full
    # layer keeps the longest bucket whole, four window layers a ring of 8;
    # every layer its share of the generated positions' pool
    item, c = 4, lm.kv_dim                         # float32 at this preset
    assert eng["kv_bytes_per_slot_full"] == 1 * lm.prompt_len_max * c * item
    assert eng["kv_bytes_per_slot_window"] == 4 * lm.sliding_window * c * item
    assert eng["kv_bytes_per_slot"] == (
        eng["kv_bytes_per_slot_full"] + eng["kv_bytes_per_slot_window"]
        + 5 * cfg.beam_size * cfg.tar_len * c * item)
    # five full caches would be five times the full layer's
    assert 5 * eng["kv_bytes_per_slot_full"] > 3 * (
        eng["kv_bytes_per_slot_full"] + eng["kv_bytes_per_slot_window"])
    assert 0 < eng["attn_keys_read"] < eng["attn_keys_context"]


def _drain(eng, cfg, reqs):
    tasks = buckets.prompt_tasks(cfg.lm, ((i, p, int(m)) for i, (p, m)
                                          in enumerate(zip(*reqs))))
    with Feeder(tasks, num_workers=0, depth=2) as feed:
        return list(eng.run(feed))


def test_attention_counters_equal_the_hand_count_on_a_hand_made_stream():
    """(e) ``attn_keys_read / attn_keys_context``: a request of prompt
    length p that ran n positions asked, at its t-th, a full layer for
    p + t + 1 keys and each of four window layers for min(p + t + 1, 8);
    with every layer full it would have asked 5 (p + t + 1). Each request
    alone tells the positions it ran (the device's own count of occupied
    slot-steps); all of them together through 2 slots count the same."""
    cfg = get_config("afmoe-tiny", engine_slots=2, engine_harvest_every=4)
    eng = SlotEngine(None, weights(cfg.lm, seed=5), cfg)
    eng.prewarm(buckets.prompt_warm_batches(cfg.lm))
    rng = np.random.default_rng(7)
    lens, limits = [3, 7, 26, 50], [6, 15, 10, 4]
    prompts = [rng.integers(4, 64, n, dtype=np.int32) for n in lens]

    def grown(before):
        s = eng.stats
        return (s.occupied_slot_steps - before[0],
                s.attn_keys_read - before[1],
                s.attn_keys_context - before[2])

    def mark():
        s = eng.stats
        return (s.occupied_slot_steps, s.attn_keys_read, s.attn_keys_context)

    def hand(p, n):
        ctx = [p + t + 1 for t in range(n)]
        return (sum(c + 4 * min(c, 8) for c in ctx), 5 * sum(ctx))
    ran, read, context = [], 0, 0
    for p, m in zip(prompts, limits):
        at = mark()
        assert len(_drain(eng, cfg, ([p], [m]))) == 1
        n, r, c = grown(at)
        assert 0 < n <= m and (r, c) == hand(len(p), n)
        ran.append(n)
        read, context = read + r, context + c
    at = mark()
    assert len(_drain(eng, cfg, (prompts, limits))) == 4
    assert grown(at) == (sum(ran), read, context)
    # short prompts read nearly everything, long ones a fraction
    assert hand(3, 4)[0] == hand(3, 4)[1]
    assert hand(50, 4)[0] * 3 < hand(50, 4)[1]


def test_arena_leaves_differ_by_layer_type():
    """The full layer's prompt leaf is as long as the longest bucket, the
    window layers' as long as the window; both are shared by a slot's
    beams; the generated positions' pool holds every layer and is
    reordered. One prefill and one insert program a bucket, one step."""
    cfg = get_config("afmoe-tiny", engine_slots=2)
    eng = SlotEngine(None, weights(cfg.lm), cfg)
    eng.prewarm(buckets.prompt_warm_batches(cfg.lm))
    lm, K = cfg.lm, cfg.beam_size
    leaves = eng._leaves
    prompts = sorted(n for n in leaves if n.startswith("prompt_")
                     and n != "prompt_len")
    assert prompts == ["prompt_k_full0"] + [f"prompt_k_win{j}" for j in
                                            range(4)] + ["prompt_v_full0"] \
        + [f"prompt_v_win{j}" for j in range(4)]
    for n in prompts:
        full = "full" in n
        assert leaves[n].shape == (2, lm.kv_dim // 2, 64 if full else 8)
        assert leaves[n].kv_kind == ("full" if full else "window")
        assert leaves[n].reorder is None and leaves[n].kv
    assert leaves["kv_pool"].kv_kind == ""
    assert leaves["kv_pool"].reorder == "pool"
    assert leaves["kv_pool"].shape == (5, eng._pool_blocks, K,
                                       eng._block_size, lm.kv_dim)
    assert eng.smodel.beam_ancestry is False and "ancestry" not in eng._state
    assert eng.smodel.prefill_budget == 1
    tags = [t for _b, t in buckets.prompt_warm_batches(lm)]
    fam = eng.labels_for_tags(tags)
    assert [f for f in fam if f.startswith("engine_prefill")] \
        == [f"engine_prefill[{t}]" for t in tags]
    assert [f for f in fam if f.startswith("engine_insert")] \
        == [f"engine_insert[{t}]" for t in tags]
    # the scopes the trace is read by
    text = jax.jit(lambda p, st: eng._step_fn(p, st)).lower(
        eng._decode_params, eng._state).as_text(debug_info=True)
    names = " ".join(set(re.findall(r'loc\("([^"]*)"', text)))
    for scope in ("attn.window.decode", "attn.full.decode", "moe.route",
                  "moe.experts", "moe.shared", "lm_head", "kv_reorder"):
        assert scope in names, scope
    wire = {k: v for k, v in buckets.prompt_warm_batches(lm)[1][0].items()
            if not k.startswith("_")}
    text = jax.jit(lambda p, b: eng._prefill_fn(p, b)).lower(
        eng.params, wire).as_text(debug_info=True)
    names = " ".join(set(re.findall(r'loc\("([^"]*)"', text)))
    assert "attn.window.prefill" in names and "attn.full.prefill" in names


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
    assert isinstance(slot_model.for_config(
        None, get_config("afmoe-tiny"), 2, 4, 8), slot_model.AfmoeSlotModel)
    assert type(slot_model.for_config(
        None, get_config("axk1-tiny"), 2, 4, 8)) is slot_model.LMSlotModel


def test_program_names_the_readers_find():
    cfg = get_config("afmoe-tiny", engine_slots=2)
    eng = SlotEngine(None, weights(cfg.lm), cfg)
    assert eng._step.__name__ == "_step_fn"
    assert eng._prefill.__name__ == "_prefill_fn"
    assert eng._insert.__name__ == "_insert_fn"


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
    cfg = afmoe_tiny(**REFUSED[what])
    errs = config_errors(cfg)
    assert errs and all("afmoe" in e for e in errs), errs
    word = {"int8w": "int8w", "bf16-weight-tier": "serve_precision",
            "non-engine beam": "non-engine",
            "serve/disagg.py": "serve/disagg.py",
            "graph buckets": "buckets / decode_tar_buckets",
            "buckets": "decode_tar_buckets"}.get(what, what)
    assert any(word in e for e in errs), errs
    if what != "non-engine beam":
        with pytest.raises(ValueError, match="afmoe"):
            SlotEngine(None, None, cfg)


@pytest.mark.parametrize("command", ["train", "serve", "message"])
def test_cli_commands_it_does_not_run_exit_2_with_its_name(command, capsys):
    from fira_tpu import cli

    assert arch_errors(afmoe_tiny(), command)
    rc = cli.main([command, "--engine", "--config", "afmoe-tiny"]
                  + (["x.diff"] if command == "message" else []))
    assert rc == 2
    err = capsys.readouterr().err
    assert f"arch 'afmoe' does not support cli {command}" in err


def test_cli_test_without_engine_is_refused_and_with_it_runs(tmp_path,
                                                             capsys):
    from fira_tpu import cli

    assert cli.main(["test", "--config", "afmoe-tiny",
                     "--out-dir", str(tmp_path)]) == 2
    assert "non-engine beam" in capsys.readouterr().err
    assert cli.main(["test", "--engine", "--config", "afmoe-tiny",
                     "--out-dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "prompt buckets: 3 engine prefill programs pre-warmed" in out
    assert len(open(tmp_path / "output_afmoe").read().splitlines()) == 64
    spans = [json.loads(l) for l in open(tmp_path / "spans.jsonl")]
    pre = [s for s in spans if s.get("name") == "engine.prefill"]
    assert pre and set(pre[0]["ids"]) == {"bucket", "requests", "tokens",
                                          "padded_tokens"}


@pytest.mark.parametrize("bad,word", [
    (dict(expert_offset=6), "expert_offset"),
    (dict(layer_types=("sliding_attention",) * 4), "layer_types"),
    (dict(num_key_value_heads=3), "num_key_value_heads"),
    (dict(prompt_buckets=(32, 16)), "prompt_buckets"),
])
def test_a_key_block_that_cannot_be_is_named(bad, word):
    import dataclasses

    lm = dataclasses.replace(get_config("afmoe-tiny").lm, **bad)
    assert any(word in e for e in config_errors(
        get_config("afmoe-tiny", lm=lm)))


def test_each_arch_takes_its_own_key_block_only():
    a, t = get_config("axk1-tiny").lm, get_config("afmoe-tiny").lm
    assert any("config.AfmoeConfig" in e for e in config_errors(
        get_config("afmoe-tiny", lm=a)))
    assert any("config.LMConfig" in e for e in config_errors(
        get_config("axk1-tiny", lm=t)))
    assert any("lm block" in e for e in config_errors(
        get_config("fira-tiny", lm=t)))
    assert config_errors(get_config("trinity-mini-l5")) == []
