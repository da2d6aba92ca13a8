"""The benchmark's files for Jamba2-3B: the ``drain_tokens`` driver (its
weights, reference and counts named by the configuration file) through
``run.run`` on the CPU at a tiny manifest of its own (``tiny_jamba/``: the
``jamba-tiny`` preset), the float8 control and two faults of the
recurrent-state mechanism against the tiny limits, the operation and byte
counts on hand-made inputs, every per-layer metric of the cell read from a
hand-made trace, and the real configuration and traffic files against the
catalog's row and the issue's table."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny_jamba")
CELL = "jamba-tiny.drain-small-diffs"
REAL = "jamba2-3b.drain-small-diffs"

from benchmark import check, flops_jamba  # noqa: E402


@pytest.fixture(scope="module")
def result():
    """One run of the tiny cell with both controls' readings beside it; its
    run files go to a directory of this module's own."""
    import tempfile

    from benchmark import run

    keep, run.OUT_DIR = run.OUT_DIR, tempfile.mkdtemp(prefix="bench_jamba_")
    try:
        args = run._args(["--workload", CELL, "--seed", "3000000019",
                          "--seconds", "0.5", "--trace", "0", "--allow-cpu"])
        return run.run(args, os.path.join(TINY, "BENCHMARK.json"), TINY,
                       extra=("control", "control_state"))
    finally:
        run.OUT_DIR = keep


def test_driver_runs_the_cell_on_the_cpu_and_is_correct(result):
    """The tiny cell end to end under --allow-cpu."""
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    c, info = result["info"]["counters"], result["info"]
    assert c["prompt_tokens"] <= c["prompt_tokens_padded"]
    assert c["flops"] > 0 and c["prefill_flops"] > 0 and c["step_min_bytes"] > 0
    # the device's own counts: 3 beams' state a position of an occupied
    # slot, and from them the bytes the steps moved
    assert c["state_rows"] == 3 * c["occupied_slot_steps"] > 0
    assert c["attn_keys_read"] > 0
    with open(os.path.join(TINY, "configs", "jamba-tiny.json")) as f:
        config = json.load(f)
    # ... at the bytes the ARENA's state leaves hold a beam lane (float32
    # here, tail included: the tiny preset computes in float32)
    lane = sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
               for name, (shape, dtype) in info["arena"].items()
               if name.startswith(("ssm_state", "conv_state"))) // (4 * 3)
    assert c["kv_bytes_per_slot_state"] == 3 * lane
    assert c["state_bytes_moved"] == 2 * lane * c["state_rows"]
    assert lane == 3 * 128 * (16 + 3) * 4 >= flops_jamba.state_bytes_per_beam(
        config)
    assert c["kv_bytes_per_slot"] > c["kv_bytes_per_slot_state"] \
        > c["kv_bytes_per_slot_full"] > 0 == c["kv_bytes_per_slot_window"]
    arena = info["arena"]
    assert arena["ssm_state2"] == [[12, 16, 128], "float32"]
    assert arena["conv_state0"][0] == [3, 12, 128]
    assert arena["prompt_k_full0"][0] == [4, 16, 64]
    assert arena["kv_pool"][0][0] == 1 and arena["parent"][0] == [4, 3]
    checked = info["checked_prompt_len"]
    assert len(checked) == 6 and max(checked) == info["prompt_len"]["max"]


@pytest.mark.parametrize("control, over", [
    ("control_fp8", {"prob_gap": 100, "topk_gap": 100}),
    ("control_state_bf16", {"prob_gap": 10})])
def test_a_control_fails_the_tiny_limits(result, control, over):
    """The reference in the precision below one the configuration states,
    put in the program's place: float8 products for bfloat16 (every limit,
    a hundred times over), a bfloat16 recurrent state for float32 (only the
    state is rounded: the served beam's probability moves by 0.023 against
    a limit of 0.001, and no pick changes over 120 positions)."""
    limits = check.load_limits(TINY, CELL)
    low = result["info"]["extra_numbers"][control]
    for name, limit in limits.items():
        assert result["check"][name]["value"] <= limit
    for name, times in over.items():
        assert low[name] > times * limits[name], (name, low[name])
    assert not check.judge(low, limits)["correct"]


def _served(config, fault=None):
    """Four requests through 2 slots of the tiny preset (mixed buckets,
    limits of 9-15 positions, the arena refilled once), with ``fault``
    planted in the program. -> (samples for ``lm_check``, cfg, params)."""
    import jax.numpy as jnp

    from benchmark import weights_jamba
    from benchmark.drivers import drain_tokens as drv
    from fira_tpu.data import buckets
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.data.synthetic import make_prompt_requests
    from fira_tpu.decode.engine import SlotEngine
    from fira_tpu.model import jamba

    traffic = {"engine_slots": 2, "feeder_workers": 0, "feeder_depth": 2}
    cfg = drv.program_cfg(config, traffic, seed=1)
    drv.check_param_tree(cfg, config, weights_jamba)
    params = weights_jamba.make_params(config, 1, jnp.float32)
    reqs = make_prompt_requests(4, vocab_size=config["vocab_size"], seed=4,
                                min_len=8, max_len=60, limits=(9, 12, 15))
    keep = {}
    if fault == "parent_ignored":
        # the state left on its old lane after a selection
        inner = keep["decode_step"] = jamba.decode_step
        jamba.decode_step = lambda *a, **k: inner(
            *a[:6], jnp.broadcast_to(jnp.arange(a[6].shape[1]), a[6].shape),
            *a[7:], **k)
    elif fault == "bucket_end":
        # the state taken at the bucket's end instead of the prompt's
        keep["real_positions"] = jamba.real_positions
        jamba.real_positions = lambda P, lengths: jnp.ones(
            (lengths.shape[0], P), bool)
    try:
        eng = SlotEngine(None, params, cfg)
        eng.prewarm(buckets.prompt_warm_batches(cfg.lm))
        tasks = buckets.prompt_tasks(cfg.lm, ((i, p, int(m)) for i, (p, m)
                                              in enumerate(zip(*reqs))))
        with Feeder(tasks, num_workers=0, depth=2) as feed:
            items = list(eng.run(feed))
    finally:
        for name, fn in keep.items():
            setattr(jamba, name, fn)
    return [(it.host["tokens"][it.row, :it.host["lengths"][it.row]],
             int(it.host["_limits"][it.row]) - 1, it.tokens.copy(),
             it.probs) for it in items], cfg, params


@pytest.fixture(scope="module")
def tiny_config():
    with open(os.path.join(TINY, "configs", "jamba-tiny.json")) as f:
        return json.load(f)


def _check(config, samples, cfg, params, **kw):
    from benchmark import reference_jamba, weights_jamba
    from benchmark.drivers import drain_tokens as drv

    return drv.lm_check(config, params, samples, cfg.beam_size, 16,
                        weights_jamba, reference_jamba, **kw)


@pytest.mark.parametrize("fault", ["parent_ignored", "bucket_end"])
def test_a_fault_of_the_state_mechanism_fails_the_tiny_limits(tiny_config,
                                                              fault):
    """Two faults of THIS mechanism, each planted alone in the program and
    read by the check the cell is judged with: what the engine then serves
    is not what the reference's full forward pass gives those tokens."""
    limits = check.load_limits(TINY, CELL)
    sound = _check(tiny_config, *_served(tiny_config))
    assert check.judge(sound["numbers"], limits)["correct"]
    bad = _check(tiny_config, *_served(tiny_config, fault))
    assert not check.judge(bad["numbers"], limits)["correct"]
    assert bad["numbers"]["prob_gap"] > 100 * limits["prob_gap"]


def test_a_token_altered_at_harvest_fails_the_tiny_limits(tiny_config):
    """One served token swapped after the engine produced it, for the id
    the reference ranks LAST there (over 64 ids a random one lands inside a
    beam of 3 too often for a test)."""
    from benchmark import reference_jamba

    samples, cfg, params = _served(tiny_config)
    limits = check.load_limits(TINY, CELL)
    prompt, _n, tokens, probs = samples[0]
    served = int(np.argmax(probs))
    seq = np.concatenate([prompt, tokens[served, :3]])
    worst = int(np.argmin(np.asarray(reference_jamba.forward(
        tiny_config, params, seq))[-1][4:])) + 4
    tokens[served, 3] = worst
    bad = _check(tiny_config, samples, cfg, params)
    assert not check.judge(bad["numbers"], limits)["correct"]
    assert bad["numbers"]["topk_gap"] > 100 * limits["topk_gap"]
    # the reading the real cell's ``topk_gap`` limit is set under
    read = _check(tiny_config, samples, cfg, params, extra=("wrong_token",),
                  seed=11)["wrong_token"]
    assert read["_where"]["requests"] == 4 and read["prob_gap"] > 0


def _real():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        return json.load(f)


def test_operation_and_byte_counts_on_hand_made_inputs():
    cfg = _real()
    from benchmark import weights_jamba
    from fira_tpu.config import get_config
    from fira_tpu.model import jamba

    # ISSUE 34's own count, reproduced from the sizes alone: mixer 41.24 M,
    # SwiGLU 62.91 M, a Mamba layer 104.16 M, an attention layer 76.68 M
    # (projections 13.76 M), embedding = head 167.77 M once: 3,029.3 M
    mixer = flops_jamba.mamba_matrix_params(cfg) \
        + flops_jamba.mamba_other_params(cfg)
    assert mixer == 41_241_792
    assert flops_jamba.mlp_params(cfg) == 3 * 2560 * 8192 == 62_914_560
    assert mixer + 62_914_560 + 2 * 2560 == 104_161_472
    assert flops_jamba.attn_proj_params(cfg) == 13_762_560
    assert flops_jamba.layer_counts(cfg) == (26, 2)
    # ... equal to the benchmark's tree and the program's, from SHAPES:
    # nothing is allocated
    assert flops_jamba.param_count(cfg) == weights_jamba.param_count(cfg) \
        == 3_029_337_472
    import jax

    assert flops_jamba.param_count(cfg) == sum(
        int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            jamba.param_shapes(get_config("jamba2-3b").lm),
            is_leaf=lambda s: isinstance(s, tuple)))
    assert 2 * flops_jamba.param_count(cfg) == pytest.approx(6.06e9, rel=1e-3)
    # 5.72 GFLOP a token of matrix products: 26 x 208.1 M + 2 x 153.4 M
    assert 2 * (flops_jamba.mamba_matrix_params(cfg) + 62_914_560) \
        == pytest.approx(208.1e6, rel=1e-3)
    assert 2 * (13_762_560 + 62_914_560) == pytest.approx(153.4e6, rel=1e-3)
    assert 2 * flops_jamba.fixed_params(cfg) == pytest.approx(5.72e9,
                                                              rel=1e-3)
    # a dispatch of 4,096 real tokens: 23.4 TFLOP + the causal half of two
    # attention layers; padding is not counted, the scan's elementwise
    # work is in no term
    p = 4096
    assert flops_jamba.prefill_flops(cfg, p) == (
        2.0 * flops_jamba.fixed_params(cfg) * p
        + 2 * 4 * 20 * 128 * p * (p + 1) / 2)
    assert 2.0 * flops_jamba.fixed_params(cfg) * p == pytest.approx(
        23.4e12, rel=2e-3)
    assert flops_jamba.prefill_flops(cfg, 1000) < flops_jamba.prefill_flops(
        cfg, 1024)
    # a decode row: only the two attention layers' keys grow with context
    d = flops_jamba.decode_row_flops(cfg, 1001) \
        - flops_jamba.decode_row_flops(cfg, 1000)
    assert d == 2 * 4 * 20 * 128
    assert flops_jamba.request_flops(cfg, 100, 2, 3) == (
        flops_jamba.prefill_flops(cfg, 100)
        + 3 * flops_jamba.decode_row_flops(cfg, 101)
        + 3 * flops_jamba.decode_row_flops(cfg, 102))
    assert flops_jamba.counted_flops(cfg, {}) == 0.0
    # bytes: 9.32 MB a beam whatever the prompt, read AND written; 1,024 B
    # of keys and values a token for both attention layers
    assert flops_jamba.state_bytes_per_beam(cfg) == 26 * 5120 * (16 * 4
                                                                 + 3 * 2) \
        == 9_318_400
    assert flops_jamba.step_weight_bytes(cfg, 78) == 2 * 3_029_337_472
    assert flops_jamba.step_slot_bytes(cfg, 1000, 10, 3) == (
        2 * 3 * 9_318_400 + 1024 * (1000 + 30))
    assert flops_jamba.step_slot_bytes(cfg, 4000, 10, 3) \
        - flops_jamba.step_slot_bytes(cfg, 1000, 10, 3) == 1024 * 3000
    # a slot: 27.96 MB of state + 4.19 MB of prompt keys and values
    assert 3 * 9_318_400 == pytest.approx(27.96e6, rel=1e-3)
    assert 4096 * 1024 == pytest.approx(4.19e6, rel=2e-3)
    # the state's share of what a position must move: a fifth at 26 seated
    # ... counted at what the arena's leaves hold a slot, not at a constant
    # of the file: a state kept in bfloat16 would halve it
    moved = 2 * 78 * 9_318_400
    assert flops_jamba.derived_counters(
        cfg, {"state_rows": 78, "kv_bytes_per_slot_state": 3 * 9_318_400}) \
        == {"state_bytes_moved": moved}
    assert moved / (6.06e9 + moved) == pytest.approx(0.19, abs=0.01)
    halved = 3 * 26 * 5120 * (16 * 2 + 3 * 2)
    assert flops_jamba.derived_counters(
        cfg, {"state_rows": 78, "kv_bytes_per_slot_state": halved}) \
        == {"state_bytes_moved": 2 * 78 * halved // 3}
    assert flops_jamba.derived_counters(cfg, {"state_rows": 78}) == {}


def test_every_layer_metric_of_the_cell_reads_a_number_from_hand_made_inputs(
        monkeypatch):
    """All twelve, through ``run.read_layer_metrics`` and the real
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
                "step_dispatches": 1, "flops": 1e13, "slots": 64,
                "steps": 4, "occupied_slot_steps": 128,
                "state_rows": 384, "state_bytes_moved": 1e9,
                "prompt_pad_tokens": 25, "prompt_tokens_padded": 100}
    monkeypatch.setattr(counter_over_module, "_peak",
                        lambda name: {"bf16_flops_per_s": 1e14,
                                      "hbm_bytes_per_s": 8e11}[name])
    monkeypatch.setattr(span_stat, "program_spans", lambda: [
        ("engine.prewarm", 0.0, 61.0), ("engine.run", 70.0, 100.0),
        ("engine.harvest.read", 99.0, 99.004),
        ("engine.harvest.read", 80.0, 80.5)])      # before the window
    manifest = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    got = run.read_layer_metrics(
        manifest, REAL, {"counters": counters, "records": [], "trace": trace,
                         "window_s": 10.0, "peak_flops": 1e14})
    want = {"engine_step_device_ms": 10.0, "prefill_device_ms": 24.0,
            "prefill_busy_share": 100 * 48 / 60, "decode_mfu": 1.0,
            "prefill_mfu": 100 * 1e12 / 0.024 / 1e14,
            "engine_step_hbm_roofline": 50.0, "state_bytes_share": 25.0,
            "prompt_padding_share": 25.0, "slot_occupancy": 50.0,
            "device_idle_share": 40.0, "harvest_read_ms": 4.0,
            "prewarm_s": 61.0}
    assert set(got) == {f"{k}.drain-small-diffs" for k in want}
    for k, v in want.items():
        assert got[f"{k}.drain-small-diffs"]["value"] == pytest.approx(v), k
    # a program without the counter (the parent): the metric is left out,
    # nothing raises
    bare = {k: v for k, v in counters.items() if not k.startswith("state_")}
    got = run.read_layer_metrics(
        manifest, REAL, {"counters": bare, "records": [], "trace": None,
                         "window_s": 10.0, "peak_flops": 1e14})
    assert "state_bytes_share.drain-small-diffs" not in got
    layer = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    for k in ("state_bytes_share", "prefill_mfu", "engine_step_hbm_roofline"):
        assert layer[f"{k}.drain-small-diffs"] \
            == "state-space and attention decoder model/jamba.py"


def test_configuration_file_keeps_every_published_key_and_cuts_nothing():
    """Every key of the catalog's row under the same key with the same
    value; ``reduced`` empty; (a)-(g) assumed, each with its other
    reading."""
    cfg = _real()
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    for k, v in published.items():
        assert cfg[k] == v and type(cfg[k]) is type(v), k
    assert cfg["reduced"] == [] and cfg["changed_from_source"] == {}
    assert cfg["source"] == ("https://huggingface.co/ai21labs/"
                             "AI21-Jamba2-3B/blob/main/config.json")
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    assert cfg["parameters"]["total"] == 3_029_337_472
    assert cfg["parameters"]["bytes_bfloat16"] == 2 * 3_029_337_472
    assert [k[0] for k in sorted(cfg["assumed"]) if k[1] == "_"] \
        == list("abcdefg")
    assert all("other reading" in cfg["assumed"][k] or k[0] in "fg"
               for k in cfg["assumed"] if k[1] == "_")
    assert cfg["prompt_buckets"] == [256, 512, 1024, 2048, 4096]
    # the program's preset is this file, and takes the benchmark's weights
    from benchmark import weights_jamba
    from benchmark.drivers import drain_tokens as drv
    from fira_tpu.config import get_config

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "drain-small-diffs.json")) as f:
        traffic = json.load(f)
    prog = drv.program_cfg(cfg, traffic, seed=1)
    assert prog.lm == get_config("jamba2-3b").lm
    assert prog.engine_slots == 64 and prog.beam_size == 3
    assert prog.tar_len == 64 and prog.compute_dtype == "bfloat16"
    assert (prog.engine_harvest_every, prog.engine_prefill_depth) == (4, 2)
    assert [prog.lm.bucket_rows(b) for b in prog.lm.prompt_buckets] \
        == [16, 8, 4, 2, 1]
    drv.check_param_tree(prog, cfg, weights_jamba)
    assert [m.__name__ for m in drv.modules_of(cfg)] == [
        "benchmark.weights_jamba", "benchmark.reference_jamba",
        "benchmark.flops_jamba"]
    assert set(check.load_limits(os.path.join(ROOT, "benchmark"), REAL)) \
        == {"prob_gap", "topk_mean", "topk_gap"}


def test_traffic_is_the_issues_table_and_is_dealt_in_rounds():
    from benchmark.drivers.drain_lm import reference_length
    from fira_tpu.data.synthetic import make_prompt_requests

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "drain-small-diffs.json")) as f:
        t = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "drain-long-diffs.json")) as f:
        before = json.load(f)
    table = {"driver": "drain_tokens", "engine_slots": 64,
             "requests": 640, "content_seed": 1, "prompt_min_len": 128,
             "prompt_max_len": 4096, "round_size": 20,
             "max_new_tokens": [16, 32, 48, 63], "check_requests": 16,
             "reference_pad": 256, "trace_seconds": 8.0}
    for k in ("kv_pool_blocks", "feeder_workers", "feeder_depth",
              "warm_turnovers", "trace_start_s"):
        table[k] = before[k]
    assert {k: v for k, v in t.items() if k != "why"} == table
    prompts, max_new = make_prompt_requests(
        640, vocab_size=65536, seed=1, min_len=128, max_len=4096,
        round_size=20, limits=(16, 32, 48, 63))
    lens = np.asarray([len(p) for p in prompts])
    assert lens.min() >= 128 and lens.max() < 4096
    for r in range(32):
        rl, rm = lens[20 * r:20 * r + 20], max_new[20 * r:20 * r + 20]
        for o in range(5):      # 4 prompts an octave, each limit once
            inside = (rl >= 128 * 2 ** o) & (rl < 256 * 2 ** o)
            assert inside.sum() == 4
            assert sorted(rm[inside].tolist()) == [16, 32, 48, 63]
    assert 1050 < lens.mean() < 1250 and float(np.mean(max_new)) == 39.75
    assert 650 < float(np.median(lens)) < 800
    # the reference compiles one shape a bucket: a pass a beam
    assert {reference_length(p, n, 256) for p in (130, 256)
            for n in (16, 63)} == {320}
    assert reference_length(4095, 63, 256) == 4160
