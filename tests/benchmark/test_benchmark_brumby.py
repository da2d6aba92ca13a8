"""The benchmark's files for Brumby-14B-Base: the ``drain_tokens`` driver
(its weights, reference and counts named by the configuration file) through
``run.run`` on the CPU at a tiny manifest of its own (``tiny_brumby/``: the
``brumby-tiny`` preset), the float8 control and three faults of the
prompt-state mechanism against the tiny limits, the operation and byte
counts on hand-made inputs, every per-layer metric of the cell read from a
hand-made trace, and the real configuration and traffic files against the
catalog's row and the traffic's specification."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny_brumby")
CELL = "brumby-tiny.drain-diffs-1k16k"
REAL = "brumby-14b-l4.drain-diffs-1k16k"

from benchmark import check, flops_brumby  # noqa: E402


@pytest.fixture(scope="module")
def result():
    """One run of the tiny cell with the float8 control's readings beside
    it; its run files go to a directory of this module's own."""
    import tempfile

    from benchmark import run

    keep, run.OUT_DIR = run.OUT_DIR, tempfile.mkdtemp(prefix="bench_brumby_")
    try:
        args = run._args(["--workload", CELL, "--seed", "3000000019",
                          "--seconds", "0.5", "--trace", "0", "--allow-cpu"])
        return run.run(args, os.path.join(TINY, "BENCHMARK.json"), TINY,
                       extra=("control",))
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
    # the device's own counts: 2 layers' prompt state a position of an
    # occupied slot, and from them the bytes the steps moved
    assert c["state_reads"] == 2 * c["occupied_slot_steps"] > 0
    assert c["own_keys_read"] > 3 * 2 * c["occupied_slot_steps"]
    # ... at the bytes the ARENA's state leaves hold a slot (float32 here:
    # the tiny preset computes in float32)
    slot = sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
               for name, (shape, dtype) in info["arena"].items()
               if name.startswith("ret_")) // 4
    assert c["kv_bytes_per_slot_state"] == slot == 2 * 2 * 136 * (16 + 1) * 4
    assert c["state_bytes_moved"] == slot // 2 * c["state_reads"]
    assert c["kv_bytes_per_slot"] > c["kv_bytes_per_slot_state"] > 0 \
        == c["kv_bytes_per_slot_full"] == c["kv_bytes_per_slot_window"]
    arena = info["arena"]
    assert arena["ret_state1"] == [[4, 2, 136, 16], "float32"]
    assert arena["ret_norm0"] == [[4, 2, 136], "float32"]
    assert arena["gen_gate"][0][-1] == 2 and "parent" not in arena
    checked = info["checked_prompt_len"]
    assert len(checked) == 6 and max(checked) == info["prompt_len"]["max"]


def test_the_float8_control_fails_the_tiny_limits(result):
    """The reference in the precision below the configuration's bfloat16
    products, put in the program's place: every limit, a hundred times
    over."""
    limits = check.load_limits(TINY, CELL)
    low = result["info"]["extra_numbers"]["control_fp8"]
    for name, limit in limits.items():
        assert result["check"][name]["value"] <= limit
        assert low[name] > 100 * limit, (name, low[name])
    assert not check.judge(low, limits)["correct"]


def _served(config, fault=None):
    """Four requests through 2 slots of the tiny preset (mixed buckets,
    limits of 9-15 positions, the arena refilled once), with ``fault``
    planted in the program. -> (samples for ``lm_check``, cfg, params)."""
    import jax.numpy as jnp

    from benchmark import weights_brumby
    from benchmark.drivers import drain_tokens as drv
    from fira_tpu.data import buckets
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.data.synthetic import make_prompt_requests
    from fira_tpu.decode.engine import SlotEngine
    from fira_tpu.model import brumby

    traffic = {"engine_slots": 2, "feeder_workers": 0, "feeder_depth": 2}
    cfg = drv.program_cfg(config, traffic, seed=1)
    drv.check_param_tree(cfg, config, weights_brumby)
    params = weights_brumby.make_params(config, 1, jnp.float32)
    reqs = make_prompt_requests(4, vocab_size=config["vocab_size"], seed=4,
                                min_len=8, max_len=60, limits=(9, 12, 15))
    keep = {}
    if fault == "no_decay":
        # the prompt's state read at full weight however far the beam went
        keep["prompt_weight"] = brumby.prompt_weight
        brumby.prompt_weight = jnp.ones_like
    elif fault == "bucket_end":
        # the state taken at the bucket's end instead of the prompt's
        keep["real_positions"] = brumby.real_positions
        brumby.real_positions = lambda P, lengths: jnp.ones(
            (lengths.shape[0], P), bool)
    elif fault == "no_normaliser":
        # the prompt's part of the normaliser, z, dropped
        inner = keep["decode_step"] = brumby.decode_step
        brumby.decode_step = lambda *a: inner(
            *a[:5], [jnp.zeros_like(z) for z in a[5]], *a[6:])
    try:
        eng = SlotEngine(None, params, cfg)
        eng.prewarm(buckets.prompt_warm_batches(cfg.lm))
        tasks = buckets.prompt_tasks(cfg.lm, ((i, p, int(m)) for i, (p, m)
                                              in enumerate(zip(*reqs))))
        with Feeder(tasks, num_workers=0, depth=2) as feed:
            items = list(eng.run(feed))
    finally:
        for name, fn in keep.items():
            setattr(brumby, name, fn)
    return [(it.host["tokens"][it.row, :it.host["lengths"][it.row]],
             int(it.host["_limits"][it.row]) - 1, it.tokens.copy(),
             it.probs) for it in items], cfg, params


@pytest.fixture(scope="module")
def tiny_config():
    with open(os.path.join(TINY, "configs", "brumby-tiny.json")) as f:
        return json.load(f)


def _check(config, samples, cfg, params, **kw):
    from benchmark import reference_brumby, weights_brumby
    from benchmark.drivers import drain_tokens as drv

    return drv.lm_check(config, params, samples, cfg.beam_size, 16,
                        weights_brumby, reference_brumby, **kw)


@pytest.mark.parametrize("fault", ["no_decay", "bucket_end",
                                   "no_normaliser"])
def test_a_fault_of_the_prompt_state_fails_the_tiny_limits(tiny_config,
                                                           fault):
    """Three faults of THIS mechanism, each planted alone in the program
    and read by the check the cell is judged with: the prompt state's decay
    ``e^{c_t}`` left out of the step, the state taken at the bucket's end,
    the normaliser's prompt part ``z`` dropped."""
    limits = check.load_limits(TINY, CELL)
    sound = _check(tiny_config, *_served(tiny_config))
    assert check.judge(sound["numbers"], limits)["correct"]
    bad = _check(tiny_config, *_served(tiny_config, fault))
    assert not check.judge(bad["numbers"], limits)["correct"]
    assert bad["numbers"]["prob_gap"] > 10 * limits["prob_gap"]


def test_a_token_altered_at_harvest_fails_the_tiny_limits(tiny_config):
    """One served token swapped after the engine produced it, for the id
    the reference ranks LAST there."""
    from benchmark import reference_brumby

    samples, cfg, params = _served(tiny_config)
    limits = check.load_limits(TINY, CELL)
    prompt, _n, tokens, probs = samples[0]
    served = int(np.argmax(probs))
    seq = np.concatenate([prompt, tokens[served, :3]])
    worst = int(np.argmin(np.asarray(reference_brumby.forward(
        tiny_config, params, seq))[-1][4:])) + 4
    tokens[served, 3] = worst
    bad = _check(tiny_config, samples, cfg, params)
    assert not check.judge(bad["numbers"], limits)["correct"]
    assert bad["numbers"]["topk_gap"] > 100 * limits["topk_gap"]
    read = _check(tiny_config, samples, cfg, params, extra=("wrong_token",),
                  seed=11)["wrong_token"]
    assert read["_where"]["requests"] == 4 and read["prob_gap"] > 0


def _real():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "brumby-14b-l4.json")) as f:
        return json.load(f)


def test_operation_and_byte_counts_on_hand_made_inputs():
    cfg = _real()
    from benchmark import weights_brumby
    from fira_tpu.config import BrumbyConfig, get_config
    from fira_tpu.model import brumby

    # the published count, reproduced from the sizes alone: projections
    # 62.91 M, SwiGLU 267.39 M, a layer 330.35 M with its gate (40,968
    # with the bias) and norms, embedding and head 777.9 M each
    assert flops_brumby.proj_params(cfg) == 62_914_560
    assert flops_brumby.mlp_params(cfg) == 3 * 5120 * 17408 == 267_386_880
    assert flops_brumby.layer_params(cfg) == 330_352_904
    assert flops_brumby.state_dim(cfg) == 8256
    # ... equal to the benchmark's tree and the program's, from SHAPES:
    # nothing is allocated; 2,877.2 M at four layers, 14.77 B at forty
    assert flops_brumby.param_count(cfg) == weights_brumby.param_count(cfg) \
        == 2_877_241_376 == cfg["parameters"]["total"]
    import jax

    def tree(lm):
        return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            brumby.param_shapes(lm), is_leaf=lambda s: isinstance(s, tuple)))
    assert flops_brumby.param_count(cfg) == tree(
        get_config("brumby-14b-l4").lm)
    whole = dict(cfg, num_hidden_layers=40)
    assert flops_brumby.param_count(whole) == tree(BrumbyConfig()) \
        == 14_769_945_920
    assert 2 * flops_brumby.param_count(cfg) == pytest.approx(5.75e9,
                                                              rel=1e-3)
    # 2.64 GFLOP a token of products in four layers
    assert 2 * flops_brumby.fixed_params(cfg) == pytest.approx(2.64e9,
                                                               rel=2e-3)
    # retention a layer: the state once, and the lesser form: the
    # attention form's pairs below about D tokens, the reads above
    D, H, KV, hd = 8256, 40, 8, 128
    for p in (1024, 8000, 8300, 16384):
        want = 2 * KV * D * hd * p + min(4 * hd * H * p * (p + 1) / 2,
                                         2 * H * D * hd * p)
        assert flops_brumby.retention_prefill_flops(cfg, p) == want
    assert 4 * hd * H * 8255 / 2 < 2 * H * D * hd < 4 * hd * H * 8257 / 2
    p = 4096
    assert flops_brumby.prefill_flops(cfg, p) == (
        2.0 * flops_brumby.fixed_params(cfg) * p
        + 4 * flops_brumby.retention_prefill_flops(cfg, p))
    assert flops_brumby.prefill_flops(cfg, 1000) < flops_brumby.prefill_flops(
        cfg, 1024)
    # a decode row: the state's read through 40 query heads' features and
    # the beam's own positions, every layer; the head
    d = flops_brumby.decode_row_flops(cfg, 11) \
        - flops_brumby.decode_row_flops(cfg, 10)
    assert d == 4 * 4 * H * hd
    assert flops_brumby.decode_row_flops(cfg, 0) == (
        2.0 * flops_brumby.fixed_params(cfg) + 4 * 2 * H * D * hd
        + 2 * 5120 * 151936)
    assert flops_brumby.request_flops(cfg, 100, 2, 3) == (
        flops_brumby.prefill_flops(cfg, 100)
        + 3 * flops_brumby.decode_row_flops(cfg, 1)
        + 3 * flops_brumby.decode_row_flops(cfg, 2))
    assert flops_brumby.counted_flops(cfg, {}) == 0.0
    # bytes: 68.7 MB of state a slot whatever the prompt, read ONCE for its
    # beams; 2,080 B a beam, position and layer in the pool
    assert flops_brumby.state_bytes_per_slot(cfg) == 4 * 8 * D * (
        128 * 2 + 4) == 68_689_920
    assert flops_brumby.step_weight_bytes(cfg, 0) == 2 * (
        2_877_241_376 - 777_912_320)
    assert flops_brumby.step_weight_bytes(cfg, 0) == pytest.approx(4.20e9,
                                                                   rel=1e-3)
    assert flops_brumby.step_weight_bytes(cfg, 10) \
        - flops_brumby.step_weight_bytes(cfg, 0) == 2 * 10 * 5120
    assert flops_brumby.step_slot_bytes(cfg, 1000, 10, 3) == (
        68_689_920 + 3 * 10 * 4 * 8 * (256 * 2 + 4))
    assert flops_brumby.step_slot_bytes(cfg, 16000, 10, 3) \
        == flops_brumby.step_slot_bytes(cfg, 1000, 10, 3)
    # the state's share of what a position must move: a quarter at 21
    # seated slots, counted at what the arena's leaves hold a slot
    moved = 21 * 68_689_920
    assert flops_brumby.derived_counters(
        cfg, {"state_reads": 4 * 21, "kv_bytes_per_slot_state": 68_689_920}
    ) == {"state_bytes_moved": moved}
    assert moved / (4.20e9 + moved) == pytest.approx(0.255, abs=0.01)
    assert flops_brumby.derived_counters(cfg, {"state_reads": 84}) == {}


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
                "step_dispatches": 1, "flops": 1e13, "slots": 32,
                "steps": 4, "occupied_slot_steps": 64,
                "state_reads": 256, "state_bytes_moved": 1e9,
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
    sfx = ".drain-diffs-1k16k"
    assert {k for k in got if k.endswith(sfx)} == {k + sfx for k in want}
    for k, v in want.items():
        assert got[k + sfx]["value"] == pytest.approx(v), k
    # a program without the counter (the parent): the metric is left out,
    # nothing raises
    bare = {k: v for k, v in counters.items() if not k.startswith("state_")}
    got = run.read_layer_metrics(
        manifest, REAL, {"counters": bare, "records": [], "trace": None,
                         "window_s": 10.0, "peak_flops": 1e14})
    assert "state_bytes_share" + sfx not in got
    layer = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    for k in ("state_bytes_share", "prefill_mfu", "engine_step_hbm_roofline"):
        assert layer[k + sfx] == "power-retention decoder model/brumby.py"
    cells = {m["name"]: m.get("workloads", []) for m in
             manifest["end_to_end"] + manifest["per_layer"]}
    for k in ("decode_commits_per_s", "setup_build_s",
              "setup_cache_hit_share", "setup_unspanned_s"):
        assert cells[k][-1] == REAL


def test_configuration_file_keeps_every_published_key_but_the_depth():
    """Every key of the catalog's row under the same key with the same
    value but ``num_hidden_layers`` (40 -> 4, in ``reduced``); (a)-(g)
    assumed, each with its other reading; the deployment."""
    cfg = _real()
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg["changed_from_source"][k] == [v, cfg[k]]
            continue
        assert cfg[k] == v and type(cfg[k]) is type(v), k
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 4
    assert cfg["source"] == ("https://huggingface.co/manifestai/"
                             "Brumby-14B-Base/blob/main/config.json")
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    assert "Ten pipeline stages" in cfg["deployment"]["whole"]
    assert cfg["parameters"]["bytes_bfloat16"] == 2 * 2_877_241_376
    assert [k[0] for k in sorted(cfg["assumed"]) if k[1] == "_"] \
        == list("abcdefg")
    assert all("other reading" in cfg["assumed"][k] or k[0] in "fg"
               for k in cfg["assumed"] if k[1] == "_")
    assert cfg["prompt_buckets"] == [2048, 4096, 8192, 16384]
    # the program's preset is this file, and takes the benchmark's weights
    from benchmark import weights_brumby
    from benchmark.drivers import drain_tokens as drv
    from fira_tpu.config import get_config

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "drain-diffs-1k16k.json")) as f:
        traffic = json.load(f)
    prog = drv.program_cfg(cfg, traffic, seed=1)
    assert prog.lm == get_config("brumby-14b-l4").lm
    assert prog.engine_slots == 32 and prog.beam_size == 3
    assert prog.tar_len == 64 and prog.compute_dtype == "bfloat16"
    assert (prog.engine_harvest_every, prog.engine_prefill_depth) == (4, 2)
    assert [prog.lm.bucket_rows(b) for b in prog.lm.prompt_buckets] \
        == [8, 4, 2, 1]
    drv.check_param_tree(prog, cfg, weights_brumby)
    assert [m.__name__ for m in drv.modules_of(cfg)] == [
        "benchmark.weights_brumby", "benchmark.reference_brumby",
        "benchmark.flops_brumby"]
    assert set(check.load_limits(os.path.join(ROOT, "benchmark"), REAL)) \
        == {"prob_gap", "topk_mean", "topk_gap"}


def test_traffic_is_as_specified_and_is_dealt_in_rounds():
    from benchmark.drivers.drain_lm import reference_length
    from fira_tpu.data.synthetic import make_prompt_requests

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "drain-diffs-1k16k.json")) as f:
        t = json.load(f)
    table = {"driver": "drain_tokens", "engine_slots": 32,
             "kv_pool_blocks": 0, "requests": 640, "content_seed": 1,
             "prompt_min_len": 1024, "prompt_max_len": 16384,
             "round_size": 16, "max_new_tokens": [16, 32, 48, 63],
             "feeder_workers": 2, "feeder_depth": 4, "warm_turnovers": 1,
             "check_requests": 16, "reference_pad": 2048,
             "trace_seconds": 8.0, "trace_start_s": 2.0}
    assert {k: v for k, v in t.items() if k != "why"} == table
    prompts, max_new = make_prompt_requests(
        640, vocab_size=151936, seed=1, min_len=1024, max_len=16384,
        round_size=16, limits=(16, 32, 48, 63))
    lens = np.asarray([len(p) for p in prompts])
    assert lens.min() >= 1024 and lens.max() < 16384
    for r in range(40):
        rl, rm = lens[16 * r:16 * r + 16], max_new[16 * r:16 * r + 16]
        for o in range(4):      # 4 prompts an octave, each limit once
            inside = (rl >= 1024 * 2 ** o) & (rl < 2048 * 2 ** o)
            assert inside.sum() == 4
            assert sorted(rm[inside].tolist()) == [16, 32, 48, 63]
    assert 5000 < lens.mean() < 6100 and float(np.mean(max_new)) == 39.75
    assert 3500 < float(np.median(lens)) < 4700
    # the reference compiles one shape a bucket: a pass a beam
    assert {reference_length(p, n, 2048) for p in (1030, 2048)
            for n in (16, 63)} == {2560}
    assert reference_length(16383, 63, 2048) == 16896
