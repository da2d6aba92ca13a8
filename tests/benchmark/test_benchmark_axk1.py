"""The benchmark's files for A.X-K1: the ``drain_lm`` driver through
``run.run`` on the CPU at a tiny manifest of its own (``tiny_axk1/``: the
``axk1-tiny`` preset holding experts 4..7 of 16, so the share is a real
cut), the float8 control and a fault against the tiny limits, the operation
and byte counts and the new readers on hand-made inputs."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny_axk1")
CELL = "axk1-tiny.drain-diffs"

from axk1_util import small_query_blocks  # noqa: E402,F401
from benchmark import check, flops_axk1  # noqa: E402
from benchmark.readers import counter_over_module, module_share  # noqa: E402


@pytest.fixture(scope="module")
def result():
    """One run of the tiny cell with the control's readings beside it; its
    run files go to a directory of this module's own (two tests' runs in
    one ``.bench_out/`` race)."""
    import tempfile

    from benchmark import run

    keep, run.OUT_DIR = run.OUT_DIR, tempfile.mkdtemp(prefix="bench_axk1_")
    try:
        args = run._args(["--workload", CELL, "--seed", "3000000019",
                          "--seconds", "0.5", "--trace", "0", "--allow-cpu"])
        return run.run(args, os.path.join(TINY, "BENCHMARK.json"), TINY,
                       extra=("control",))
    finally:
        run.OUT_DIR = keep


def test_driver_runs_the_cell_on_the_cpu_and_is_correct(result):
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    c = result["info"]["counters"]
    # a request runs exactly its limit: no beam ends early over random
    # weights, and nothing stands in for an EOS bias
    assert result["info"]["positions_run"] == result["info"]["positions_limit"]
    assert c["prompt_tokens"] <= c["prompt_tokens_padded"]
    assert c["prompt_pad_tokens"] == c["prompt_tokens_padded"] - c["prompt_tokens"]
    # 4 of 16 experts held: about a quarter of the choices land here
    assert 0.15 < c["moe_assignments_held"] / c["moe_assignments"] < 0.35
    assert c["moe_held_load_max"] * 4 >= c["moe_assignments_held"]
    assert c["flops"] > 0 and c["prefill_flops"] > 0 and c["step_min_bytes"] > 0
    arena = result["info"]["arena"] if "arena" in result["info"] else None
    assert arena is None or arena["prompt_lat"][0][1] == 4    # slots


def test_float8_control_fails_the_tiny_limits(result):
    limits = check.load_limits(TINY, CELL)
    low = result["info"]["extra_numbers"]["control_fp8"]
    for name, limit in limits.items():
        assert result["check"][name]["value"] <= limit
        assert low[name] > 100 * limit, (name, low[name])
    assert not check.judge(low, limits)["correct"]


def test_a_token_altered_at_harvest_fails_the_tiny_limits():
    """The fault: one served token swapped for another id after the engine
    produced it. The served beam's probability no longer matches and the
    token ranks outside the reference's beam."""
    import jax.numpy as jnp

    from benchmark import weights_axk1
    from benchmark.drivers import drain_lm
    from fira_tpu.data.synthetic import make_prompt_requests

    with open(os.path.join(TINY, "configs", "axk1-tiny.json")) as f:
        config = json.load(f)
    traffic = {"engine_slots": 2, "feeder_workers": 0, "feeder_depth": 2}
    cfg = drain_lm.program_cfg(config, traffic, seed=1)
    drain_lm.check_param_tree(cfg, config)
    params = weights_axk1.make_params(config, 1, jnp.float32)
    reqs = make_prompt_requests(2, vocab_size=config["vocab_size"], seed=4,
                                min_len=8, max_len=32, limits=(6, 9))
    from fira_tpu.data import buckets
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.decode.engine import SlotEngine

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
    sound = drain_lm.lm_check(config, params, samples, cfg.beam_size, 16)
    assert check.judge(sound["numbers"], limits)["correct"]
    served = int(np.argmax(samples[0][3]))
    samples[0][2][served, 3] = (samples[0][2][served, 3] + 97) % 500 + 4
    bad = drain_lm.lm_check(config, params, samples, cfg.beam_size, 16)
    verdict = check.judge(bad["numbers"], limits)
    assert not verdict["correct"]
    assert bad["numbers"]["prob_gap"] > 100 * limits["prob_gap"]
    # the single wrong pick is what ``topk_gap`` is limited for: the token
    # lies outside the reference's beam, whatever the probabilities say
    assert bad["numbers"]["topk_gap"] > 100 * limits["topk_gap"]
    # the reading the real cell's ``topk_gap`` limit is set under: one token
    # of each request swapped in turn, the least any of them shows
    read = drain_lm.lm_check(config, params, samples, cfg.beam_size, 16,
                             extra=("wrong_token",), seed=11)["wrong_token"]
    assert read["_where"]["requests"] == 2
    assert read["topk_gap"] > 100 * limits["topk_gap"]


def _real():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "axk1-ep16.json")) as f:
        return json.load(f)


def test_operation_and_byte_counts_on_hand_made_inputs():
    cfg = _real()
    # the parameter count is the configuration file's own arithmetic
    from benchmark import weights_axk1

    # 4,841,209,856 in matrices (configs/axk1-ep16.json: deployment.weights)
    # and the RMSNorm gains
    assert weights_axk1.param_count(cfg) == 4841209856 + 7 * (
        2 * 7168 + 1536 + 512) + 7168
    assert 2 * weights_axk1.param_count(cfg) == pytest.approx(9.68e9,
                                                              rel=1e-3)
    # an absent expert costs nothing: no assignment to a held expert, no
    # routed operations; each assignment costs one expert's three products
    base = flops_axk1.prefill_flops(cfg, 1000, 0.0)
    one = flops_axk1.prefill_flops(cfg, 1000, 1.0)
    assert one - base == 2 * 3 * 7168 * 2048
    assert flops_axk1.routed_flops(cfg, 0) == 0.0
    # padding is not counted: the count takes real tokens, and a prompt of
    # 300 tokens costs the same in a bucket of 512 as anywhere
    assert flops_axk1.prefill_flops(cfg, 300, 0.0) < \
        flops_axk1.prefill_flops(cfg, 512, 0.0)
    # a prompt token's matmuls over the 7 layers held: about 3.0 GFLOP with
    # the even router's share of the experts (ISSUE's plan from the peaks)
    per_token = (flops_axk1.prefill_flops(
        cfg, 1, flops_axk1.expected_held_assignments(cfg, 1))
        - 7 * 2 * 64 * 320)
    assert per_token == pytest.approx(3.0e9, rel=0.05)
    assert flops_axk1.expected_held_assignments(cfg, 8192) \
        == 6 * 8192 * 8 * 12 / 192
    # attention grows with the square of the prompt
    a = flops_axk1.prefill_flops(cfg, 4096, 0) \
        - 4 * flops_axk1.prefill_flops(cfg, 1024, 0)
    assert a > 0
    # a decode row reads the latents: its cost grows by 2 H (576 + 512) a
    # cached token a layer
    d = flops_axk1.decode_row_flops(cfg, 1001, 0) \
        - flops_axk1.decode_row_flops(cfg, 1000, 0)
    assert d == 7 * 2 * 64 * (576 + 512)
    # bytes: the weights outside the experts once, the experts that got an
    # assignment, the latents of the slots occupied
    none = flops_axk1.step_weight_bytes(cfg, 0)
    full = flops_axk1.step_weight_bytes(cfg, 192)
    assert full - none == pytest.approx(
        2 * 6 * 12 * 3 * 7168 * 2048 * (1 - (1 - 1 / 192) ** 1536))
    assert full == pytest.approx(9.4e9, rel=0.02)
    assert flops_axk1.step_latent_bytes(cfg, 1000, 10, 3) \
        == 2 * 7 * 576 * 1030
    assert 2 * 7 * 576 == 8064      # bytes of latent cache a token


def test_new_readers_on_a_hand_made_event_list(monkeypatch):
    from benchmark import trace_reduce

    ms = 1_000_000
    # (a run that touches either end of the device's own events is recorded
    # cut off and left out, so the list starts and ends on other work)
    ops = [("%first", 1 * ms, 1 * ms), ("%fusion.1", 5 * ms, 40 * ms),
           ("%fusion.2", 50 * ms, 10 * ms), ("%fusion.3", 70 * ms, 8 * ms),
           ("%last", 98 * ms, 1 * ms)]
    modules = [("jit__prefill_fn(11)", 5 * ms, 40 * ms),
               ("jit__step_fn(9)", 50 * ms, 10 * ms),
               ("jit__prefill_fn(12)", 70 * ms, 8 * ms)]
    host = [("bench.window", 0, 100 * ms)]
    r = trace_reduce.reduce_events(ops, modules, host)
    ctx = {"trace": r, "counters": {"prefill_flops": 2e12,
                                    "prefills": 2,
                                    "step_min_bytes": 4e9,
                                    "step_dispatches": 1}}
    # the two prefill programs (two buckets) read as one: 48 of 60 busy ms
    assert module_share.read(ctx, module="_prefill_fn") \
        == pytest.approx(100 * 48 / 60)
    assert module_share.read(ctx, module="nowhere") is None
    assert module_share.read({"trace": None}, module="_step_fn") is None
    monkeypatch.setattr(counter_over_module, "_peak",
                        lambda name: {"bf16_flops_per_s": 1e14,
                                      "hbm_bytes_per_s": 8e11}[name])
    # 1e12 operations a dispatch over 24 ms a dispatch over 1e14
    assert counter_over_module.read(
        ctx, num="prefill_flops", per="prefills",
        module="_prefill_fn", peak="bf16_flops_per_s"
    ) == pytest.approx(100 * 1e12 / 0.024 / 1e14)
    # 4e9 bytes over 10 ms over 8e11: 50 %
    assert counter_over_module.read(
        ctx, num="step_min_bytes", per="step_dispatches",
        module="_step_fn", peak="hbm_bytes_per_s") == pytest.approx(50.0)
    assert counter_over_module.read(
        {"trace": r, "counters": {}}, num="prefill_flops",
        per="prefills", module="_prefill_fn",
        peak="bf16_flops_per_s") is None
    # a device that peaks.json does not know gives nothing, not a default
    monkeypatch.undo()
    assert counter_over_module._peak("hbm_bytes_per_s") is None   # the CPU


def test_configuration_file_keeps_the_published_widths():
    """Every number of the catalog's row under the same key, but the three
    cuts, which ``reduced`` names with their published values beside."""
    cfg = _real()
    published = {
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "num_attention_heads": 64,
        "num_key_value_heads": 64, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
        "n_shared_experts": 1, "first_k_dense_replace": 1,
        "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-06,
        "rope_theta": 10000, "max_position_embeddings": 131072,
        "moe_layer_freq": 1, "ep_size": 1}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 192,
                                "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (7, 12, 20480)
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    assert "topk_method" in cfg["assumed"]
    # the program's preset is this file
    from benchmark.drivers import drain_lm
    from fira_tpu.config import get_config

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "drain-diffs.json")) as f:
        traffic = json.load(f)
    prog = drain_lm.program_cfg(cfg, traffic, seed=1)
    assert prog.lm == get_config("axk1-ep16").lm
    assert prog.engine_slots == 64 and prog.beam_size == 3
    assert (prog.engine_harvest_every, prog.engine_prefill_depth) == (4, 2)
    drain_lm.check_param_tree(prog, cfg)
    # the cell's limits: the control's two and the single wrong pick's
    assert set(check.load_limits(os.path.join(ROOT, "benchmark"),
                                 "axk1-ep16.drain-diffs")) \
        == {"prob_gap", "topk_mean", "topk_gap"}


def test_traffic_is_dealt_in_rounds_that_hold_the_same_work():
    from fira_tpu.data.synthetic import make_prompt_requests

    prompts, max_new = make_prompt_requests(
        512, vocab_size=20480, seed=1, min_len=256, max_len=4096,
        round_size=16, limits=(16, 32, 48, 63))
    lens = np.asarray([len(p) for p in prompts])
    assert lens.min() >= 256 and lens.max() < 4096
    assert min(int(p.min()) for p in prompts) >= 4
    for r in range(32):
        rl, rm = lens[16 * r:16 * r + 16], max_new[16 * r:16 * r + 16]
        for o in range(4):      # 4 prompts an octave, each limit once
            inside = (rl >= 256 * 2 ** o) & (rl < 512 * 2 ** o)
            assert inside.sum() == 4
            assert sorted(rm[inside].tolist()) == [16, 32, 48, 63]
    assert 1250 < lens.mean() < 1500 and float(np.mean(max_new)) == 39.75
    from benchmark.drivers.drain_lm import reference_length, request_stream

    stream = request_stream(prompts, max_new, 16, seed=3000000019)
    got = [next(stream) for _ in range(1024)]
    assert [g[0] for g in got] == list(range(1024))
    # two epochs: every request twice, rounds kept whole (each stretch of 16
    # holds 4 prompts an octave with each limit once), and the seed permutes
    # the rounds AND the inside of each round
    sizes = sorted(len(g[1]) for g in got)
    assert sizes == sorted(list(lens) * 2)
    orders = set()
    for r in range(0, 1024, 16):
        this = [(int(np.log2(len(g[1]) / 256)), g[2]) for g in got[r:r + 16]]
        assert sorted(this) == [(o, m) for o in range(4)
                                for m in (16, 32, 48, 63)]
        orders.add(tuple(this))
    assert len(orders) > 32              # no two rounds in one order
    first = [len(g[1]) for g in got[:16]]
    assert sorted(first) in [sorted(lens[16 * r:16 * r + 16].tolist())
                             for r in range(32)]
    other = request_stream(prompts, max_new, 16, seed=7)
    again = [next(other) for _ in range(512)]
    assert [len(g[1]) for g in again] != [len(g[1]) for g in got[:512]]
    same = request_stream(prompts, max_new, 16, seed=3000000019)
    assert [len(next(same)[1]) for _ in range(64)] \
        == [len(g[1]) for g in got[:64]]
    # the reference compiles one shape a bucket
    assert {reference_length(p, 2 * n, 512) for p in (257, 400, 512)
            for n in (16, 63)} == {640}
    assert reference_length(4095, 126, 512) == 4224
