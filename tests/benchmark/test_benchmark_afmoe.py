"""The benchmark's files for Trinity-Mini: the ``drain_lm_afmoe`` driver
through ``run.run`` on the CPU at a tiny manifest of its own
(``tiny_afmoe/``: the ``afmoe-tiny`` preset), the float8 control and a
fault against the tiny limits, the operation and byte counts on hand-made
inputs, every new per-layer metric read from a hand-made trace, and the
real configuration and traffic files against the catalog's row and the
issue's table."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))
TINY = os.path.join(HERE, "tiny_afmoe")
CELL = "afmoe-tiny.drain-long-diffs"
REAL = "trinity-mini-l5.drain-long-diffs"

from afmoe_util import small_query_blocks  # noqa: E402,F401
from benchmark import check, flops_afmoe  # noqa: E402


@pytest.fixture(scope="module")
def result():
    """One run of the tiny cell with the control's readings beside it; its
    run files go to a directory of this module's own."""
    import tempfile

    from benchmark import run

    keep, run.OUT_DIR = run.OUT_DIR, tempfile.mkdtemp(prefix="bench_afmoe_")
    try:
        args = run._args(["--workload", CELL, "--seed", "3000000019",
                          "--seconds", "0.5", "--trace", "0", "--allow-cpu"])
        return run.run(args, os.path.join(TINY, "BENCHMARK.json"), TINY,
                       extra=("control",))
    finally:
        run.OUT_DIR = keep


def test_driver_runs_the_cell_on_the_cpu_and_is_correct(result):
    """(f) The tiny cell end to end under --allow-cpu."""
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    c, info = result["info"]["counters"], result["info"]
    assert c["prompt_tokens"] <= c["prompt_tokens_padded"]
    assert c["moe_assignments_held"] == c["moe_assignments"] > 0   # all held
    assert c["flops"] > 0 and c["prefill_flops"] > 0 and c["step_min_bytes"] > 0
    # window layers were asked for fewer keys than full ones would be
    assert 0 < c["attn_keys_read"] < c["attn_keys_context"]
    assert c["kv_bytes_per_slot"] > c["kv_bytes_per_slot_full"] \
        > c["kv_bytes_per_slot_window"] > 0
    # the two prompt leaves at their different lengths
    arena = info["arena"]
    assert arena["prompt_k_full0"][0] == arena["prompt_v_full0"][0] \
        == [4, 32, 64]
    assert arena["prompt_k_win3"][0] == arena["prompt_v_win0"][0] \
        == [4, 32, 8]
    # at least half of what was checked went through the rings (window 8),
    # the longest finished prompt among it
    checked = info["checked_prompt_len"]
    assert sum(n > 8 for n in checked) * 2 >= len(checked) == 6
    assert max(checked) == info["prompt_len"]["max"]


def test_float8_control_fails_the_tiny_limits(result):
    limits = check.load_limits(TINY, CELL)
    low = result["info"]["extra_numbers"]["control_fp8"]
    for name, limit in limits.items():
        assert result["check"][name]["value"] <= limit
        assert low[name] > 100 * limit, (name, low[name])
    assert not check.judge(low, limits)["correct"]


def test_a_token_altered_at_harvest_fails_the_tiny_limits():
    """(f) The fault: one served token swapped after the engine produced
    it, for the id the reference ranks LAST there (over 64 ids a random one
    lands inside a beam of 3 too often for a test)."""
    import jax.numpy as jnp

    from benchmark import reference_afmoe, weights_afmoe
    from benchmark.drivers import drain_lm_afmoe as drv
    from fira_tpu.data import buckets
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.data.synthetic import make_prompt_requests
    from fira_tpu.decode.engine import SlotEngine

    with open(os.path.join(TINY, "configs", "afmoe-tiny.json")) as f:
        config = json.load(f)
    traffic = {"engine_slots": 2, "feeder_workers": 0, "feeder_depth": 2}
    cfg = drv.program_cfg(config, traffic, seed=1)
    drv.check_param_tree(cfg, config)
    params = weights_afmoe.make_params(config, 1, jnp.float32)
    reqs = make_prompt_requests(2, vocab_size=config["vocab_size"], seed=4,
                                min_len=8, max_len=32, limits=(6, 9))
    eng = SlotEngine(None, params, cfg)
    eng.prewarm(buckets.prompt_warm_batches(cfg.lm))
    tasks = buckets.prompt_tasks(cfg.lm, ((i, p, int(m)) for i, (p, m)
                                          in enumerate(zip(*reqs))))
    with Feeder(tasks, num_workers=0, depth=2) as feed:
        items = list(eng.run(feed))
    samples = [(it.host["tokens"][it.row, :it.host["lengths"][it.row]],
                int(it.host["_limits"][it.row]) - 1, it.tokens.copy(),
                it.probs) for it in items]
    limits = check.load_limits(TINY, CELL)
    sound = drv.lm_check(config, params, samples, cfg.beam_size, 16)
    assert check.judge(sound["numbers"], limits)["correct"]
    prompt, _n, tokens, probs = samples[0]
    served = int(np.argmax(probs))
    seq = np.concatenate([prompt, tokens[served, :3]])
    worst = int(np.argmin(np.asarray(
        reference_afmoe.forward(config, params, seq))[-1][4:])) + 4
    tokens[served, 3] = worst
    bad = drv.lm_check(config, params, samples, cfg.beam_size, 16)
    assert not check.judge(bad["numbers"], limits)["correct"]
    assert bad["numbers"]["prob_gap"] > 100 * limits["prob_gap"]
    assert bad["numbers"]["topk_gap"] > 100 * limits["topk_gap"]
    # the reading the real cell's ``topk_gap`` limit is set under
    read = drv.lm_check(config, params, samples, cfg.beam_size, 16,
                        extra=("wrong_token",), seed=11)["wrong_token"]
    assert read["_where"]["requests"] == 2 and read["prob_gap"] > 0


def _real():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini-l5.json")) as f:
        return json.load(f)


def test_operation_and_byte_counts_on_hand_made_inputs():
    cfg = _real()
    from benchmark import weights_afmoe

    # ISSUE 32's own count: attention 27.26 M a layer, the dense layer's
    # SwiGLU 37.75 M, an expert 6.29 M, 4,241.5 M in all (+ the gains)
    attn = flops_afmoe.attn_proj_params(cfg)
    assert attn == 2048 * 128 * (3 * 32 + 2 * 4) == 27262976
    assert flops_afmoe.expert_params(cfg) == 3 * 2048 * 1024
    matrices = (5 * attn + 3 * 2048 * 6144
                + 4 * (2048 * 128 + 129 * 3 * 2048 * 1024)
                + 2 * 200192 * 2048)
    gains = 5 * (4 * 2048 + 2 * 128) + 4 * 128 + 2048
    assert weights_afmoe.param_count(cfg) == matrices + gains
    assert matrices == pytest.approx(4241.5e6, rel=1e-4)
    assert 2 * weights_afmoe.param_count(cfg) == pytest.approx(8.48e9,
                                                               rel=1e-3)
    # 0.80 GFLOP a token outside attention, with the router's 8 picks
    per_token = 2 * flops_afmoe.fixed_params(cfg) + flops_afmoe.routed_flops(
        cfg, flops_afmoe.expected_held_assignments(cfg, 1))
    assert per_token == pytest.approx(0.80e9, rel=0.01)
    assert flops_afmoe.expected_held_assignments(cfg, 16384) == 4 * 16384 * 8
    # the LEAST attention: a window layer's query counts min(i + 1, 2048)
    assert flops_afmoe.attended_pairs(cfg, 100) == 5 * 100 * 101 / 2
    p = 16384
    full, win = p * (p + 1) / 2, 2048 * 2049 / 2 + (p - 2048) * 2048
    assert flops_afmoe.attended_pairs(cfg, p) == full + 4 * win
    assert win < full / 4               # a window layer: under a quarter
    # ISSUE's plan: 2.2 TFLOP for the full layer, 4 x 0.55 for the windows
    assert flops_afmoe.pair_flops(cfg) * full == pytest.approx(2.2e12, rel=.01)
    assert flops_afmoe.pair_flops(cfg) * win == pytest.approx(0.51e12, rel=.02)
    # padding is not counted; an assignment costs one expert's products
    assert flops_afmoe.prefill_flops(cfg, 1000, 1.0) \
        - flops_afmoe.prefill_flops(cfg, 1000, 0.0) == 2 * 3 * 2048 * 1024
    # a decode row: past the window only the full layer's keys grow
    d = flops_afmoe.decode_row_flops(cfg, 5001, 0) \
        - flops_afmoe.decode_row_flops(cfg, 5000, 0)
    assert d == 4 * 32 * 128
    d = flops_afmoe.decode_row_flops(cfg, 101, 0) \
        - flops_afmoe.decode_row_flops(cfg, 100, 0)
    assert d == 5 * 4 * 32 * 128
    # bytes: 144 rows x 8 picks reach every expert: every weight but the
    # embedding's 0.82 GB, of which a position gathers 144 rows
    assert flops_afmoe.step_weight_bytes(cfg, 144) == pytest.approx(
        8.48e9 - 2 * 200192 * 2048, rel=0.01)
    none = flops_afmoe.step_weight_bytes(cfg, 0)
    assert none == 2 * (flops_afmoe.fixed_params(cfg) + 2048 * 200192)
    # cache: 2,048 bytes a token a layer; a long prompt's window layers
    # read the window, not the prompt
    assert 2 * flops_afmoe.kv_dim(cfg) == 2048
    assert flops_afmoe.step_kv_bytes(cfg, 10000, 10, 3) == 2048 * (
        (10000 + 30) + 4 * (2038 + 30))
    assert flops_afmoe.step_kv_bytes(cfg, 100, 10, 3) == 2048 * 5 * 130
    # a slot: 33.6 MB for the full layer, 4.2 MB a ring, 50.3 MB
    assert 16384 * 2048 + 4 * 2048 * 2048 == pytest.approx(50.3e6, rel=1e-3)


def test_every_new_layer_metric_reads_a_number_from_hand_made_inputs(
        monkeypatch):
    """(f) All thirteen, through ``run.read_layer_metrics`` and the real
    manifest: a hand-made trace, counters and spans."""
    from benchmark import run, trace_reduce
    from benchmark.readers import counter_over_module, span_stat

    ms = 1_000_000
    ops = [("%first", 1 * ms, 1 * ms), ("%fusion.1", 5 * ms, 40 * ms),
           ("%fusion.2", 50 * ms, 10 * ms), ("%fusion.3", 70 * ms, 8 * ms),
           ("%last", 98 * ms, 1 * ms)]
    modules = [("jit__prefill_fn(11)", 5 * ms, 40 * ms),
               ("jit__step_fn(9)", 50 * ms, 10 * ms),
               ("jit__prefill_fn(12)", 70 * ms, 8 * ms)]
    trace = trace_reduce.reduce_events(ops, modules,
                                       [("bench.window", 0, 100 * ms)])
    counters = {"prefill_flops": 2e12, "prefills": 2, "step_min_bytes": 4e9,
                "step_dispatches": 1, "flops": 1e13, "slots": 48,
                "steps": 4, "occupied_slot_steps": 96,
                "attn_keys_read": 45, "attn_keys_context": 100,
                "moe_held_load_max": 30, "moe_assignments_held": 1280,
                "prompt_pad_tokens": 25, "prompt_tokens_padded": 100}
    monkeypatch.setattr(counter_over_module, "_peak",
                        lambda name: {"bf16_flops_per_s": 1e14,
                                      "hbm_bytes_per_s": 8e11}[name])
    monkeypatch.setattr(span_stat, "program_spans", lambda: [
        ("engine.prewarm", 0.0, 61.0), ("engine.run", 70.0, 100.0),
        ("engine.harvest.read", 99.0, 99.004),
        ("engine.harvest.read", 80.0, 80.5)])      # before the window
    got = run.read_layer_metrics(
        run.load_manifest(os.path.join(ROOT, "BENCHMARK.json")), REAL,
        {"counters": counters, "records": [], "trace": trace,
         "window_s": 10.0, "peak_flops": 1e14})
    want = {"engine_step_device_ms": 10.0, "prefill_device_ms": 24.0,
            "prefill_busy_share": 100 * 48 / 60, "decode_mfu": 1.0,
            "prefill_mfu": 100 * 1e12 / 0.024 / 1e14,
            "engine_step_hbm_roofline": 50.0,
            "window_keys_read_share": 45.0,
            "moe_held_load_max_over_mean": 3.0,
            "prompt_padding_share": 25.0, "slot_occupancy": 50.0,
            "device_idle_share": 40.0, "harvest_read_ms": 4.0,
            "prewarm_s": 61.0}
    assert set(got) == {f"{k}.drain-long-diffs" for k in want}
    for k, v in want.items():
        assert got[f"{k}.drain-long-diffs"]["value"] == pytest.approx(v), k
    # a program without the counters (the parent): the metric is left out
    bare = {k: v for k, v in counters.items() if not k.startswith("attn_")}
    got = run.read_layer_metrics(
        run.load_manifest(os.path.join(ROOT, "BENCHMARK.json")), REAL,
        {"counters": bare, "records": [], "trace": None, "window_s": 10.0,
         "peak_flops": 1e14})
    assert "window_keys_read_share.drain-long-diffs" not in got


def test_configuration_file_keeps_the_published_widths():
    """Every number of the catalog's row under the same key, but the three
    cuts, which ``reduced`` names with their published values beside."""
    cfg = _real()
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "moe_intermediate_size": 1024, "n_group": 1,
        "num_attention_heads": 32, "num_expert_groups": 1,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "route_scale": 2.826, "sliding_window": 2048,
        "topk_group": 1, "vocab_size": 200192}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["rope_scaling"] is None and cfg["mup_enabled"] is True
    assert cfg["route_norm"] is True and cfg["model_type"] == "afmoe"
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (5, 1)
    S, F = "sliding_attention", "full_attention"
    assert cfg["layer_types"] == [S, S, F, S, S]
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["published"]["num_dense_layers"] == 2
    assert (cfg["experts_held"], cfg["expert_offset"]) == (128, 0)
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    assert set(cfg["changed_from_source"]) == set(cfg["reduced"])
    assert [k[0] for k in sorted(cfg["assumed"]) if k[1] == "_"] \
        == list("abcdefgh")
    # the program's preset is this file, and takes the benchmark's weights
    from benchmark.drivers import drain_lm_afmoe as drv
    from fira_tpu.config import get_config

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "drain-long-diffs.json")) as f:
        traffic = json.load(f)
    prog = drv.program_cfg(cfg, traffic, seed=1)
    assert prog.lm == get_config("trinity-mini-l5").lm
    assert prog.engine_slots == 48 and prog.beam_size == 3
    assert (prog.engine_harvest_every, prog.engine_prefill_depth) == (4, 2)
    assert [prog.lm.bucket_rows(b) for b in prog.lm.prompt_buckets] \
        == [16, 8, 4, 2, 1]
    drv.check_param_tree(prog, cfg)
    assert set(check.load_limits(os.path.join(ROOT, "benchmark"), REAL)) \
        == {"prob_gap", "topk_mean", "topk_gap"}


def test_traffic_is_the_issues_table_and_is_dealt_in_rounds():
    from benchmark.drivers.drain_lm_afmoe import pick, reference_length
    from fira_tpu.data.synthetic import make_prompt_requests

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "drain-long-diffs.json")) as f:
        t = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "drain-diffs.json")) as f:
        before = json.load(f)
    table = {"driver": "drain_lm_afmoe", "engine_slots": 48,
             "requests": 640, "content_seed": 1, "prompt_min_len": 512,
             "prompt_max_len": 16384, "round_size": 20,
             "max_new_tokens": [16, 32, 48, 63], "check_requests": 16,
             "reference_pad": 1024, "trace_seconds": 8.0}
    for k in ("kv_pool_blocks", "feeder_workers", "feeder_depth",
              "warm_turnovers", "trace_start_s"):
        table[k] = before[k]
    assert {k: v for k, v in t.items() if k != "why"} == table
    prompts, max_new = make_prompt_requests(
        640, vocab_size=200192, seed=1, min_len=512, max_len=16384,
        round_size=20, limits=(16, 32, 48, 63))
    lens = np.asarray([len(p) for p in prompts])
    assert lens.min() >= 512 and lens.max() < 16384
    for r in range(32):
        rl, rm = lens[20 * r:20 * r + 20], max_new[20 * r:20 * r + 20]
        for o in range(5):      # 4 prompts an octave, each limit once
            inside = (rl >= 512 * 2 ** o) & (rl < 1024 * 2 ** o)
            assert inside.sum() == 4
            assert sorted(rm[inside].tolist()) == [16, 32, 48, 63]
    assert 4200 < lens.mean() < 5000 and float(np.mean(max_new)) == 39.75
    assert 0.55 < float(np.mean(lens > 2048)) < 0.65   # three octaves of five
    # the reference compiles one shape a bucket
    assert {reference_length(p, 2 * n, 1024) for p in (600, 1024)
            for n in (16, 63)} == {1280}
    assert reference_length(16383, 126, 1024) == 16640
    # the sample: the longest, and at least half beyond the window
    items = list(range(40))
    lengths = [100 + 7 * i if i % 4 else 3000 + i for i in items]
    got = pick(items, 16, 5, lambda i: lengths[i], 2048)
    assert len(got) == len(set(got)) == 16
    assert max(lengths[i] for i in got) == max(lengths)
    assert sum(lengths[i] > 2048 for i in got) >= 8
    assert len(pick(items[:3], 16, 5, lambda i: lengths[i], 2048)) == 3
