"""Drain window for Trinity-Mini (``model_type: afmoe``): ``drain_lm``'s
window — the SAME ``decode/engine.SlotEngine.run`` over token-id prompts
dealt in rounds, ``--seed`` permuting them and drawing the sample checked —
with this architecture's weights, reference and counts. What ``drain_lm``
and ``drain`` export is imported, not copied (``request_stream``,
``reference_length``, ``Window``); the check is written over again here
only because ``drain_lm.lm_check`` names A.X-K1's reference in its body.

The check is the decode cells': a sample of the requests finished in the
window — **at least half of them longer than the window, the longest among
them**, so what is compared went through the rings — their served beam and
their last beam teacher-forced through the plain reference
(``reference_afmoe.py``) in one pass over [prompt | beam | beam]: prefill,
the ring and the whole arena written at insert, then decoding through both
and the paged pool must agree with the reference's full forward pass, on
what the timed path produced at the timed sizes."""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

# the system under test has to have the architecture: a checkout without it
# stops here, before any weight is made
from fira_tpu.model import afmoe  # noqa: F401

from .. import check, common, flops_afmoe, reference_afmoe, weights_afmoe
from . import decode_common as dc
from .drain import Window
from .drain_lm import _gaps, _predictions, reference_length, request_stream

# the configuration file's keys the program's key block takes as they are
LM_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
           "num_hidden_layers", "num_dense_layers", "num_attention_heads",
           "num_key_value_heads", "head_dim", "sliding_window",
           "num_experts", "num_shared_experts", "num_experts_per_tok",
           "route_norm", "route_scale", "rms_norm_eps", "mup_enabled",
           "vocab_size", "experts_held", "expert_offset",
           "prefill_token_budget")


def lm_overrides(config: Dict) -> Dict:
    out = {k: config[k] for k in LM_KEYS}
    out.update(rope_theta=float(config["rope_theta"]),
               layer_types=tuple(config["layer_types"]),
               prompt_buckets=tuple(config["prompt_buckets"]))
    return out


def program_cfg(config: Dict, traffic: Dict, seed: int):
    from fira_tpu.config import get_config

    return get_config(
        config["preset"], lm=lm_overrides(config),
        compute_dtype=config["compute_dtype"],
        beam_size=config["beam_size"], tar_len=config["tar_len"],
        engine_slots=int(traffic["engine_slots"]),
        kv_pool_blocks=int(traffic.get("kv_pool_blocks", 0)),
        feeder_workers=int(traffic["feeder_workers"]),
        feeder_depth=int(traffic["feeder_depth"]),
        seed=common.seed31(seed), **config.get("decode_knobs", {}))


def check_param_tree(cfg, config: Dict) -> None:
    """The program has to accept the benchmark's weights as they are."""
    if afmoe.param_shapes(cfg.lm) != weights_afmoe.param_shapes(config):
        raise ValueError("the program's parameter tree is not the one "
                         "benchmark/weights_afmoe.py builds")


def pick(items: List, n: int, seed: int, length_of, window: int) -> List:
    """Up to ``n`` finished requests drawn from the seed: the longest among
    them, and at least half longer than ``window`` where the run finished
    that many."""
    if not items:
        return []
    order = [int(i) for i in np.random.default_rng(
        common.seed31(seed)).permutation(len(items))]
    longest = max(range(len(items)), key=lambda i: length_of(items[i]))
    chosen = [longest]
    beyond = [i for i in order if i != longest
              and length_of(items[i]) > window]
    chosen += beyond[:max(0, n // 2 - (length_of(items[longest]) > window))]
    chosen += [i for i in order if i not in chosen][:n - len(chosen)]
    return [items[i] for i in chosen[:n]]


def lm_check(config: Dict, params, samples: List, beam: int, pad: int,
             extra=(), seed: int = 0) -> Dict:
    """``samples``: (prompt ids, max_new, tokens (K, T), probs (K,)) of
    served requests, probs sums of logs. The numbers, the control and the
    one wrong pick are ``drain_lm.lm_check``'s, read through this
    architecture's reference: of each request the served (most probable)
    beam, whose probability is compared too, and the last beam go through
    the reference in one pass with their prompt. ``control``: the reference
    in float8 put in the program's place. ``wrong_token``: one token of
    each request's served beam swapped for an id drawn from ``seed``, a
    request at a time — the LEAST ``topk_gap`` any one such request
    reads."""
    if not samples:
        return {"numbers": {"_where": {"requests": 0, "positions": 0}}}
    control = "control" in extra
    rng = np.random.default_rng(common.seed31(seed))
    eos = weights_afmoe.EOS_ID
    runs = {"numbers": [], "control_fp8": [], "wrong_token": []}

    def score(prompt, beams, mode, pad_to, probe=None):
        return reference_afmoe.score_request(
            config, params, prompt, beams, beam, mode, probe_ids=probe,
            pad_to=pad_to)
    for prompt, n, tokens, probs in samples:
        served = int(np.argmax(probs))
        rows = [served, tokens.shape[0] - 1 if served != tokens.shape[0] - 1
                else 0]
        beams = tokens[rows][:, :n + 1].astype(np.int32)
        logp_served = np.asarray([float(probs[served]), np.nan])
        pad_to = reference_length(len(prompt), len(rows) * n, pad)
        low = score(prompt, beams, "fp8", pad_to) if control else None
        ref = score(prompt, beams, "f32", pad_to,
                    low["top_ids"] if control else None)
        below = ref["logp_kth"] - ref["logp_token"]
        runs["numbers"].append(dict(check.beam_numbers(
            beams, logp_served, ref["logp_token"], below, eos),
            _gaps=_gaps(beams, below)))
        if control:
            kept = np.take_along_axis(ref["logp_probe"],
                                      ref["rank"][..., None], -1)[..., 0]
            low_served = np.asarray(
                [np.sum(low["logp_token"][0, :_predictions(beams[0], n)]),
                 np.nan])
            below = ref["logp_kth"] - kept
            runs["control_fp8"].append(dict(check.beam_numbers(
                beams, low_served, ref["logp_token"], below, eos),
                _gaps=_gaps(beams, below)))
        if "wrong_token" in extra:
            bad = beams.copy()
            at = 1 + int(rng.integers(_predictions(beams[0], n)))
            new = int(rng.integers(weights_afmoe.FIRST_ID,
                                   config["vocab_size"] - 1))
            bad[0, at] = new + (new >= bad[0, at])     # any id but its own
            ref = score(prompt, bad, "f32", pad_to)
            runs["wrong_token"].append(check.beam_numbers(
                bad, logp_served, ref["logp_token"],
                ref["logp_kth"] - ref["logp_token"], eos))

    def merged(parts: List[Dict]) -> Dict:
        positions = sum(p["_where"]["positions"] for p in parts)
        gaps = np.sort(np.concatenate([p["_gaps"] for p in parts]))[::-1]
        return {"prob_gap": max(p["prob_gap"] for p in parts),
                "topk_gap": max(p["topk_gap"] for p in parts),
                "topk_mean": sum(p["topk_mean"] * p["_where"]["positions"]
                                 for p in parts) / max(positions, 1),
                "_top_gaps": [round(float(g), 4) for g in gaps[:24]],
                "_where": {"requests": len(parts), "positions": positions}}
    out = {"numbers": merged(runs["numbers"])}
    if control:
        out["control_fp8"] = merged(runs["control_fp8"])
    if runs["wrong_token"]:
        out["wrong_token"] = {
            "topk_gap": min(p["topk_gap"] for p in runs["wrong_token"]),
            "prob_gap": min(p["prob_gap"] for p in runs["wrong_token"]),
            "_where": {"requests": len(runs["wrong_token"])}}
    return out


def window_counters(config: Dict, cfg, win, admits: List, stats, since
                    ) -> Dict:
    """The window's share of the engine's counts and the operations and
    bytes they stand for (``flops_afmoe.py``)."""
    def grown(field: str) -> int:
        return getattr(stats, field) - getattr(since, field)
    K = cfg.beam_size
    out = {"commits": len(win.items), "window_s": win.t_end - win.t0,
           "slots": win.eng.slots}
    for field in ("steps", "step_dispatches", "occupied_slot_steps",
                  "prefills", "harvest_row_reads",
                  "prompt_tokens", "prompt_tokens_padded",
                  "moe_assignments", "moe_assignments_held",
                  "moe_held_load_max", "attn_keys_read",
                  "attn_keys_context"):
        out[field] = grown(field)
    out["prompt_pad_tokens"] = (out["prompt_tokens_padded"]
                                - out["prompt_tokens"])
    for field in ("kv_bytes_per_slot", "kv_bytes_per_slot_full",
                  "kv_bytes_per_slot_window"):
        out[field] = getattr(stats, field)
    # what the window FINISHED: each harvested request's prefill and the
    # positions it ran, plus the routed products the device counted
    done = [(int(it.host["lengths"][it.row]),
             int(it.host["_limits"][it.row]) - 1) for it in win.items]
    out["flops"] = (sum(flops_afmoe.request_flops(config, p, n, K)
                        for p, n in done)
                    + flops_afmoe.routed_flops(config,
                                               out["moe_assignments_held"]))
    # prefill dispatches inside the window, by the lengths they held; the
    # routed part by the even router's expectation (the device's count does
    # not tell prefill's assignments from decode's)
    inside = [ls for t, ls in admits if win.t0 <= t <= win.t_end]
    out["prefill_flops"] = sum(
        flops_afmoe.prefill_flops(
            config, int(p), flops_afmoe.expected_held_assignments(config, p))
        for ls in inside for p in ls)
    # a step dispatch: R positions, each reading the weights once and the
    # cache inside window or context of the slots occupied, at the
    # requests' mean depth
    R = max(1, int(cfg.engine_harvest_every))
    slot_steps = sum(n for _p, n in done)
    if slot_steps and out["steps"]:
        occupied = out["occupied_slot_steps"] / out["steps"]
        cache = sum(flops_afmoe.step_kv_bytes(config, p, t + 1, K)
                    for p, n in done for t in range(n)) / slot_steps
        out["step_min_bytes"] = R * out["step_dispatches"] * (
            flops_afmoe.step_weight_bytes(config, occupied * K)
            + occupied * cache)
    return out


def run(ctx: Dict) -> Dict:
    from fira_tpu.data import buckets
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.data.synthetic import make_prompt_requests
    from fira_tpu.decode.engine import SlotEngine

    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    content_seed = int(traffic.get("content_seed", seed))
    cfg = program_cfg(config, traffic, seed)
    check_param_tree(cfg, config)
    params = weights_afmoe.make_params(config, content_seed)
    prompts, max_new = make_prompt_requests(
        int(traffic["requests"]), vocab_size=config["vocab_size"],
        seed=common.seed31(content_seed),
        min_len=int(traffic["prompt_min_len"]),
        max_len=int(traffic["prompt_max_len"]),
        round_size=int(traffic["round_size"]),
        limits=tuple(traffic["max_new_tokens"]),
        first_id=weights_afmoe.FIRST_ID)
    eng = SlotEngine(None, params, cfg, slots=cfg.engine_slots)
    eng.prewarm(buckets.prompt_warm_batches(cfg.lm))

    tracer = common.tracer_for(ctx, traffic)
    win = Window(eng, int(traffic["warm_turnovers"]) * eng.slots,
                 ctx["seconds"], tracer)
    admits: List = []          # (host time, real prompt lengths) a dispatch
    inner_admit = eng.admit

    def admit(host, index, device_batch=None):
        admits.append((time.perf_counter(),
                       host["lengths"][host["valid"]].tolist()))
        return inner_admit(host, index, device_batch)
    eng.admit = admit
    harvests: List = []        # (host time, requests settled) a harvest

    def on_return(meth: str, out) -> None:
        if meth == "harvest":
            harvests.append((time.perf_counter(), len(out)))
        win.on_return(meth, out)
    common.wrap_spans(eng, dc.ENGINE_SPANS, on_return)
    tasks = buckets.prompt_tasks(
        cfg.lm, request_stream(prompts, max_new, int(traffic["round_size"]),
                               seed), flush=False)
    with Feeder(tasks, num_workers=cfg.feeder_workers,
                depth=cfg.feeder_depth) as feed:
        gen = eng.run(feed)
        try:
            for _item in gen:
                if win.t_end is not None:
                    break
        finally:
            gen.close()
            tracer.close()
    counters = window_counters(config, cfg, win, admits, win.stats1,
                               win.stats0)
    peak, memory = common.memory_peak_bytes(), common.memory_stats()
    arena = {k: [list(v.shape), str(v.dtype)]
             for k, v in (eng._state or {}).items()}
    eng._state = None                      # free the arena before the check

    def prompt_len(it) -> int:
        return int(it.host["lengths"][it.row])
    t_ref = time.perf_counter()
    sample = pick(win.items, int(traffic["check_requests"]), seed,
                  prompt_len, int(config["sliding_window"]))
    checked = lm_check(
        config, params,
        [(it.host["tokens"][it.row, :prompt_len(it)],
          int(it.host["_limits"][it.row]) - 1, it.tokens, it.probs)
         for it in sample], cfg.beam_size, int(traffic["reference_pad"]),
        extra=ctx["extra"], seed=seed)
    top_gaps = checked["numbers"].pop("_top_gaps", [])
    positions = [int(it.host["_limits"][it.row]) - 1 for it in win.items]
    ran = [int(np.count_nonzero(it.tokens, axis=-1).max()) - 1
           for it in win.items]
    return {
        "setup_end": win.t0, "window_s": counters["window_s"],
        "attempted": len(win.items), "failed": 0,
        "end_to_end": {"decode_commits_per_s":
                       len(win.items) / counters["window_s"]},
        "counters": counters, "records": [], "tracer": tracer,
        "memory_peak_bytes": peak, "numbers": checked.pop("numbers"),
        "extra_numbers": checked,
        "info": {"positions_per_commit":
                 counters["occupied_slot_steps"] / max(len(win.items), 1),
                 "positions_limit": dc.length_stats(positions),
                 "positions_run": dc.length_stats(ran),
                 "prompt_len": dc.length_stats(
                     [prompt_len(it) for it in win.items]),
                 "checked_prompt_len": sorted(prompt_len(it)
                                              for it in sample),
                 "reference_s": time.perf_counter() - t_ref,
                 # the widest gaps of the sample, largest first
                 "top_gaps": top_gaps,
                 "memory": memory, "arena": arena,
                 "warm_commits": win.warmed,
                 # every harvest and every prefill dispatch of the run:
                 # (seconds from the window's opening, requests)
                 "harvests": [(round(t - win.t0, 4), n)
                              for t, n in harvests],
                 "prefills": [(round(t - win.t0, 4), len(ls))
                              for t, ls in admits],
                 "weights_bytes": 2 * weights_afmoe.param_count(config)},
    }
