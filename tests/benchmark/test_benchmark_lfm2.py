"""The benchmark's files for LFM2-8B-A1B: the ``drain_tokens`` driver (its
weights, reference and counts named by the configuration file) through
``run.run`` on the CPU at a tiny manifest of its own (``tiny_lfm2/``: the
``lfm2-tiny`` preset), the float8 control and two faults of this model's
mechanisms (a convolution tail that ignores the beams' parents, a router
that drops its selection bias) against the tiny limits, the operation and
byte counts on hand-made inputs, every per-layer metric of the cell read
from a hand-made trace, and the real configuration file against the
catalog's row."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny_lfm2")
CELL = "lfm2-tiny.drain-small-diffs"
REAL = "lfm2-8b-a1b-l12.drain-small-diffs"
SUFFIX = ".lfm2-l12"

from benchmark import check, flops_lfm2  # noqa: E402


@pytest.fixture(scope="module")
def result():
    """One run of the tiny cell with the float8 control's and one swapped
    token's readings beside it; its run files go to a directory of this
    module's own."""
    import tempfile

    from benchmark import run

    keep, run.OUT_DIR = run.OUT_DIR, tempfile.mkdtemp(prefix="bench_lfm2_")
    try:
        args = run._args(["--workload", CELL, "--seed", "3000000019",
                          "--seconds", "0.5", "--trace", "0", "--allow-cpu"])
        return run.run(args, os.path.join(TINY, "BENCHMARK.json"), TINY,
                       extra=("control", "wrong_token"))
    finally:
        run.OUT_DIR = keep


def test_driver_runs_the_cell_on_the_cpu_and_is_correct(result):
    """The tiny cell end to end under --allow-cpu, with the device's expert
    counts and the arena's tails."""
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    c, info = result["info"]["counters"], result["info"]
    assert c["prompt_tokens"] <= c["prompt_tokens_padded"]
    assert c["flops"] > 0 and c["prefill_flops"] > 0 and c["step_min_bytes"] > 0
    # every expert is held; 3 expert layers of 8, top-2: a decode position
    # of an occupied slot reads between 2 and 8 experts a layer
    assert c["moe_assignments"] == c["moe_assignments_held"] > 0
    assert 0 < c["moe_experts_read"] <= 3 * 8 * c["steps"]
    assert c["expert_bytes_moved"] == (2 * 3 * 64 * 32
                                       * c["moe_experts_read"])
    # 4 conv layers' tails a beam lane, float32 here; the attention layer's
    # prompt leaves shared by the beams
    assert c["kv_bytes_per_slot_state"] == 3 * 4 * 2 * 64 * 4
    assert c["kv_bytes_per_slot"] > c["kv_bytes_per_slot_full"] > 0 \
        == c["kv_bytes_per_slot_window"]
    arena = info["arena"]
    assert arena["conv_tail3"] == [[2, 12, 64], "float32"]
    assert arena["prompt_k_full0"][0] == [4, 32, 64]
    assert "conv_tail4" not in arena and "prompt_k_full1" not in arena
    assert arena["kv_pool"][0][0] == 1 and arena["parent"][0] == [4, 3]
    checked = info["checked_prompt_len"]
    assert len(checked) == 6 and max(checked) == info["prompt_len"]["max"]


def test_the_control_and_one_swapped_token_fail_the_tiny_limits(result):
    """The reference in float8 put in the program's place fails every
    limit a hundred times over; one served token swapped for a random id
    reads far over ``topk_gap``'s limit."""
    limits = check.load_limits(TINY, CELL)
    low = result["info"]["extra_numbers"]["control_fp8"]
    for name, limit in limits.items():
        assert result["check"][name]["value"] <= limit
        assert low[name] > 100 * limit, (name, low[name])
    assert not check.judge(low, limits)["correct"]
    wrong = result["info"]["extra_numbers"]["wrong_token"]
    assert wrong["topk_gap"] > 100 * limits["topk_gap"]


def _served(config, fault=None):
    """Four requests through 2 slots of the tiny preset (mixed buckets,
    limits of 9-15 positions, the arena refilled once), with ``fault``
    planted in the program. -> (samples for ``lm_check``, cfg, params)."""
    import jax.numpy as jnp

    from benchmark import weights_lfm2
    from benchmark.drivers import drain_tokens as drv
    from fira_tpu.data import buckets
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.data.synthetic import make_prompt_requests
    from fira_tpu.decode.engine import SlotEngine
    from fira_tpu.model import lfm2

    traffic = {"engine_slots": 2, "feeder_workers": 0, "feeder_depth": 2}
    cfg = drv.program_cfg(config, traffic, seed=1)
    drv.check_param_tree(cfg, config, weights_lfm2)
    params = weights_lfm2.make_params(config, 1, jnp.float32)
    reqs = make_prompt_requests(4, vocab_size=config["vocab_size"], seed=4,
                                min_len=8, max_len=60, limits=(9, 12, 15))
    keep = {}
    if fault == "parent_ignored":
        # the tails left on their old lanes after a selection
        inner = keep["decode_step"] = lfm2.decode_step
        lfm2.decode_step = lambda *a, **k: inner(
            *a[:5], jnp.broadcast_to(jnp.arange(a[5].shape[1]), a[5].shape),
            *a[6:], **k)
    elif fault == "bias_dropped":
        # the router picks by its scores alone
        inner_route = keep["route"] = lfm2.route
        lfm2.route = lambda s, b, lm: inner_route(s, jnp.zeros_like(b), lm)
    try:
        eng = SlotEngine(None, params, cfg)
        eng.prewarm(buckets.prompt_warm_batches(cfg.lm))
        tasks = buckets.prompt_tasks(cfg.lm, ((i, p, int(m)) for i, (p, m)
                                              in enumerate(zip(*reqs))))
        with Feeder(tasks, num_workers=0, depth=2) as feed:
            items = list(eng.run(feed))
    finally:
        for name, fn in keep.items():
            setattr(lfm2, name, fn)
    return [(it.host["tokens"][it.row, :it.host["lengths"][it.row]],
             int(it.host["_limits"][it.row]) - 1, it.tokens.copy(),
             it.probs) for it in items], cfg, params


@pytest.fixture(scope="module")
def tiny_config():
    with open(os.path.join(TINY, "configs", "lfm2-tiny.json")) as f:
        return json.load(f)


def _check(config, samples, cfg, params, **kw):
    from benchmark import reference_lfm2, weights_lfm2
    from benchmark.drivers import drain_tokens as drv

    return drv.lm_check(config, params, samples, cfg.beam_size, 16,
                        weights_lfm2, reference_lfm2, **kw)


@pytest.mark.parametrize("fault", ["parent_ignored", "bias_dropped"])
def test_a_fault_of_this_models_mechanisms_fails_the_tiny_limits(
        tiny_config, fault):
    """Two faults, each planted alone in the program and read by the check
    the cell is judged with: what the engine then serves is not what the
    reference's full forward pass gives those tokens."""
    limits = check.load_limits(TINY, CELL)
    sound = _check(tiny_config, *_served(tiny_config))
    assert check.judge(sound["numbers"], limits)["correct"]
    bad = _check(tiny_config, *_served(tiny_config, fault))
    assert not check.judge(bad["numbers"], limits)["correct"]
    assert bad["numbers"]["prob_gap"] > 100 * limits["prob_gap"]


def test_a_checkout_without_the_model_stops_before_any_weight():
    """A checkout without ``fira_tpu.model.lfm2`` (any tree from before
    this model): the driver's first act imports it, so the cell fails
    there, at once, and never hangs."""
    from benchmark.drivers import drain_tokens as drv

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-l12.json")) as f:
        cfg = json.load(f)
    gone = dict(cfg, modules=dict(cfg["modules"],
                                  model="fira_tpu.model.lfm2_absent"))
    with pytest.raises(ModuleNotFoundError):
        drv.modules_of(gone)
    assert [m.__name__ for m in drv.modules_of(cfg)] == [
        "benchmark.weights_lfm2", "benchmark.reference_lfm2",
        "benchmark.flops_lfm2"]


def _real():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-l12.json")) as f:
        return json.load(f)


def test_operation_and_byte_counts_on_hand_made_inputs():
    cfg = _real()
    from benchmark import weights_lfm2

    # the count from the sizes alone: conv mixer 16.78 M,
    # attention mixer 10.49 M, dense 44.04 M, 32 experts 352.3 M, router
    # and bias 65,568, embedding once: 3,928,728,256
    assert flops_lfm2.conv_params(cfg) == 16_783_360
    assert flops_lfm2.attn_proj_params(cfg) + 2 * 64 == 10_485_888
    assert flops_lfm2.dense_params(cfg) == 44_040_192
    assert 32 * flops_lfm2.expert_params(cfg) == 352_321_536
    assert flops_lfm2.layer_counts(cfg) == (9, 3, 2, 10)
    assert flops_lfm2.param_count(cfg) == weights_lfm2.param_count(cfg) \
        == 3_928_728_256
    assert 2 * flops_lfm2.param_count(cfg) == pytest.approx(7.86e9, rel=1e-3)
    # 1.42 GFLOP a prompt token, 62 % of it in the routed experts
    per_token = 2 * flops_lfm2.fixed_params(cfg) + flops_lfm2.routed_flops(
        cfg)
    assert per_token == pytest.approx(1.42e9, rel=3e-3)
    assert flops_lfm2.routed_flops(cfg) / per_token == pytest.approx(
        0.62, abs=0.01)
    # a dispatch of 16,384 real tokens: 23.3 TFLOP + the causal half of
    # three attention layers; padding is not counted
    assert 16384 * per_token == pytest.approx(23.3e12, rel=2e-3)
    # ... less what of the last layer (a conv layer) no cache keeps, which
    # the compiled prefill leaves out: its experts and router, C and W_out
    p = 4096
    unread = 2 * (2048 * 32 + 4 * 3 * 2048 * 1792 + 2 * 2048 * 2048)
    assert flops_lfm2.unread_in_prefill(cfg) == unread
    assert flops_lfm2.prefill_flops(cfg, p) == (
        (per_token - unread) * p + 3 * 4 * 32 * 64 * p * (p + 1) / 2)
    d = flops_lfm2.decode_row_flops(cfg, 1001) \
        - flops_lfm2.decode_row_flops(cfg, 1000)
    assert d == 3 * 4 * 32 * 64
    assert flops_lfm2.request_flops(cfg, 100, 2, 3) == (
        flops_lfm2.prefill_flops(cfg, 100)
        + 3 * flops_lfm2.decode_row_flops(cfg, 101)
        + 3 * flops_lfm2.decode_row_flops(cfg, 102))
    assert flops_lfm2.counted_flops(cfg, {}) == 0.0
    # bytes: at 192 rows every expert is read, 7.05 GB of the 7.86; at 1
    # row 4 a layer, each missed with probability (31/32)^4 by the count
    assert flops_lfm2.step_weight_bytes(cfg, 192) == pytest.approx(
        2 * 3_928_728_256, rel=1e-9)
    assert 10 * 32 * 2 * flops_lfm2.expert_params(cfg) == pytest.approx(
        7.05e9, rel=1e-3)
    one = flops_lfm2.step_weight_bytes(cfg, 1)
    assert 2 * 3_928_728_256 - one == pytest.approx(
        10 * 32 * (31 / 32) ** 4 * 2 * flops_lfm2.expert_params(cfg),
        rel=1e-6)
    # 73,728 B of tails a beam, read AND written; 1,024 values a token for
    # each of three attention layers
    assert flops_lfm2.tail_bytes_per_beam(cfg) == 9 * 2 * 2048 * 2 == 73_728
    assert flops_lfm2.step_slot_bytes(cfg, 1000, 10, 3) == (
        2 * 3 * 73_728 + 2 * 1024 * 3 * (1000 + 30))
    # the experts' share of what a position must move, from the device's
    # count: all 320 read at 64 occupied slots
    moved = flops_lfm2.derived_counters(cfg, {"moe_experts_read": 320})
    assert moved == {"expert_bytes_moved": 320 * 2 * 3 * 2048 * 1792}
    step = flops_lfm2.step_weight_bytes(cfg, 192) + 64 * \
        flops_lfm2.step_slot_bytes(cfg, 1145, 20, 3)
    assert moved["expert_bytes_moved"] / step == pytest.approx(0.85,
                                                               abs=0.02)
    assert flops_lfm2.derived_counters(cfg, {"steps": 4}) == {}


def test_every_layer_metric_of_the_cell_reads_a_number_from_hand_made_inputs(
        monkeypatch):
    """All thirteen, through ``run.read_layer_metrics`` and the real
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
                "moe_held_load_max": 300, "moe_assignments_held": 6400,
                "moe_experts_read": 320, "expert_bytes_moved": 3e9,
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
            "engine_step_hbm_roofline": 50.0, "expert_bytes_share": 75.0,
            "moe_held_load_max_over_mean": 1.5,
            "prompt_padding_share": 25.0, "slot_occupancy": 50.0,
            "device_idle_share": 40.0, "harvest_read_ms": 4.0,
            "prewarm_s": 61.0}
    assert set(got) == {k + SUFFIX for k in want}
    for k, v in want.items():
        assert got[k + SUFFIX]["value"] == pytest.approx(v), k
    # a program without the counter (the parent): the metric is left out,
    # nothing raises
    bare = {k: v for k, v in counters.items()
            if k not in ("moe_experts_read", "expert_bytes_moved")}
    got = run.read_layer_metrics(
        manifest, REAL, {"counters": bare, "records": [], "trace": None,
                         "window_s": 10.0, "peak_flops": 1e14})
    assert "expert_bytes_share" + SUFFIX not in got
    by = {m["name"]: m for m in manifest["per_layer"]}
    for k in want:
        assert by[k + SUFFIX]["workloads"] == [REAL]
        assert by[k + SUFFIX]["moves"] == (
            "setup_s" if k == "prewarm_s" else "decode_commits_per_s")
    for k in ("expert_bytes_share", "moe_held_load_max_over_mean",
              "prefill_mfu", "engine_step_hbm_roofline"):
        assert by[k + SUFFIX]["layer"] \
            == "gated-convolution expert decoder model/lfm2.py"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert REAL in e2e["decode_commits_per_s"]["workloads"]
    for k in ("setup_build_s", "setup_cache_hit_share", "setup_unspanned_s"):
        assert REAL in by[k]["workloads"]


def test_configuration_file_keeps_every_published_key_and_cuts_depth_only():
    """Every key of the catalog's row under the same key, with the same
    value but the depth and its layer types; every width as published;
    (a)-(g) assumed; the deployment one chip of a two-stage pipeline."""
    cfg = _real()
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    for k, v in published.items():
        assert cfg[k] == v and type(cfg[k]) is type(v), k
    C, A = "conv", "full_attention"
    whole = [C, C, A] + [C, C, C, A] * 4 + [C, C, A, C, C]
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["changed_from_source"] == {
        "num_hidden_layers": [24, 12], "layer_types": [whole, whole[:12]]}
    assert cfg["num_hidden_layers"] == 12 and cfg["layer_types"] == whole[:12]
    assert cfg["source"] == ("https://huggingface.co/LiquidAI/"
                             "LFM2-8B-A1B/blob/main/config.json")
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    assert cfg["parameters"]["total"] == 3_928_728_256
    assert cfg["parameters"]["whole_model_24_layers"] == 8_339_930_560
    assert [k[0] for k in sorted(cfg["assumed"]) if k[1] == "_"] \
        == list("abcdefg")
    assert all("other reading" in cfg["assumed"][k] or k[0] in "eg"
               for k in cfg["assumed"] if k[1] == "_")
    # the program's preset is this file, and takes the benchmark's weights
    from benchmark import weights_lfm2
    from benchmark.drivers import drain_tokens as drv
    from fira_tpu.config import get_config

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "drain-small-diffs.json")) as f:
        traffic = json.load(f)
    prog = drv.program_cfg(cfg, traffic, seed=1)
    assert prog.lm == get_config("lfm2-8b-a1b-l12").lm
    assert prog.engine_slots == 64 and prog.beam_size == 3
    assert prog.tar_len == 64 and prog.compute_dtype == "bfloat16"
    assert (prog.engine_harvest_every, prog.engine_prefill_depth) == (4, 2)
    assert [prog.lm.bucket_rows(b) for b in prog.lm.prompt_buckets] \
        == [64, 32, 16, 8, 4]
    drv.check_param_tree(prog, cfg, weights_lfm2)
    assert set(check.load_limits(os.path.join(ROOT, "benchmark"), REAL)) \
        == {"prob_gap", "topk_mean", "topk_gap"}
    # the cell rides Jamba2-3B's traffic file as it is
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[REAL]["traffic"] == cells[
        "jamba2-3b.drain-small-diffs"]["traffic"] == "drain-small-diffs"
    assert cells[REAL]["chips"] == 1
    assert np.isclose(cfg["expert_bias_std"], 0.01)
