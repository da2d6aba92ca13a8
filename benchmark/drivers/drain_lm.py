"""Drain window for a decoder-only configuration (A.X-K1): the SAME
``decode/engine.SlotEngine.run`` as ``drain``, over token-id prompts of
mixed length instead of a packed graph split. Requests are fixed by the
mix's ``content_seed`` and dealt in rounds that each hold every octave of
prompt length and every position limit equally often
(``data/synthetic.make_prompt_requests``); ``--seed`` permutes the rounds and
the requests inside each, and draws the sample that is checked. The window
opens after the arena has turned over once and opens and closes at a
harvest (``drain.Window``).

The check is the decode cells': a sample of the requests finished in the
window, their served beam and their last beam teacher-forced through the
plain reference (``reference_axk1.py``) over prompt AND message, so prefill
and then decoding through the latent cache must agree with the reference's
full forward pass, on what the timed path produced at the timed sizes."""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

# the system under test has to have the architecture: a checkout without it
# stops here, before any weight is made
from fira_tpu.model import axk1  # noqa: F401

from .. import check, common, flops_axk1, reference_axk1, weights_axk1
from . import decode_common as dc
from .drain import Window


def lm_overrides(config: Dict) -> Dict:
    """The configuration file's keys as the program's ``lm`` block takes
    them: the file counts the experts HELD under ``n_routed_experts`` (the
    guide's rule for a chip's share) and the router's width under
    ``published``."""
    rs = config["rope_scaling"]
    take = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_shared_experts", "num_experts_per_tok", "n_group",
            "topk_group", "norm_topk_prob", "routed_scaling_factor",
            "rms_norm_eps", "vocab_size", "expert_offset",
            "prefill_token_budget")
    out = {k: config[k] for k in take}
    out.update(
        n_routed_experts=config["published"]["n_routed_experts"],
        experts_held=config["n_routed_experts"],
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(rs["factor"]), rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]), rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        rope_original_max_position_embeddings=int(
            rs["original_max_position_embeddings"]),
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
    if axk1.param_shapes(cfg.lm) != weights_axk1.param_shapes(config):
        raise ValueError("the program's parameter tree is not the one "
                         "benchmark/weights_axk1.py builds")


def request_stream(prompts, max_new, round_size: int, seed: int):
    """(position, prompt, max_new) for ever: every epoch all the rounds, the
    seed permuting the rounds and the requests inside each round. Every
    round holds the same octaves and limits, so any stretch of a few rounds
    holds the same work whatever the seed; WHICH requests share a prefill
    dispatch, and which limits sit in which slots, is the seed's."""
    n_rounds = len(prompts) // round_size
    rng = np.random.default_rng(common.seed31(seed))
    pos = 0
    while True:
        for r in rng.permutation(n_rounds):
            for j in rng.permutation(round_size):
                i = int(r) * round_size + int(j)
                yield pos, prompts[i], int(max_new[i])
                pos += 1


def reference_length(prompt_len: int, message_tokens: int, pad: int) -> int:
    """Tokens the reference's pass over one request is padded to: the
    prompt's length rounded up to a power of two times ``pad`` (its prefill
    bucket, at the cell's sizes) plus the beams' tokens rounded up to
    ``pad``/4 — so the reference compiles four shapes, not one a length."""
    bucket = pad
    while bucket < prompt_len:
        bucket *= 2
    step = max(1, pad // 4)
    return bucket + -(-message_tokens // step) * step


def _predictions(row: np.ndarray, n: int) -> int:
    """Predictions a beam made: through <eos>, or its request's limit."""
    hit = np.nonzero(row[1:n + 1] == weights_axk1.EOS_ID)[0]
    return int(hit[0]) + 1 if len(hit) else n


def _gaps(beams: np.ndarray, below_kth: np.ndarray) -> np.ndarray:
    """Every predicted position's distance below the reference's
    ``beam``-th best (0 where the served token is inside its beam)."""
    return np.concatenate(
        [np.maximum(0.0, np.asarray(below_kth)[i, :n]) for i, n in
         enumerate(check.beam_predictions(beams, weights_axk1.EOS_ID))])


def lm_check(config: Dict, params, samples: List, beam: int, pad: int,
             extra=(), seed: int = 0) -> Dict:
    """``samples``: (prompt ids, max_new, tokens (K, T), probs (K,)) of
    served requests, probs sums of logs. Of each request two beams go
    through the reference in one pass with their prompt: the one served
    (the most probable), whose probability is compared too, and the last,
    which any reorder of the cache that lost a beam's history shows in.
    ``extra`` (readings.py) asks for the upper readings, each read as the
    program's numbers are. ``control``: the reference in float8 put in the
    program's place — its probability of each served beam, and at each
    position its own pick of the rank the served token holds in the
    reference. ``wrong_token``: one token of each request's served beam
    swapped for an id drawn from ``seed``, a request at a time — the LEAST
    ``topk_gap`` any one such request reads (what a single wrong pick shows
    at the least)."""
    if not samples:
        return {"numbers": {"_where": {"requests": 0, "positions": 0}}}
    control = "control" in extra
    rng = np.random.default_rng(common.seed31(seed))
    runs = {"numbers": [], "control_fp8": [], "wrong_token": []}
    for prompt, n, tokens, probs in samples:
        served = int(np.argmax(probs))
        rows = [served, tokens.shape[0] - 1 if served != tokens.shape[0] - 1
                else 0]
        beams = tokens[rows][:, :n + 1].astype(np.int32)
        logp_served = np.asarray([float(probs[served]), np.nan])
        pad_to = reference_length(len(prompt), len(rows) * n, pad)
        probe = None
        if control:
            low = reference_axk1.score_request(
                config, params, prompt, beams, beam, "fp8", pad_to=pad_to)
            probe = low["top_ids"]
        ref = reference_axk1.score_request(
            config, params, prompt, beams, beam, "f32", probe_ids=probe,
            pad_to=pad_to)
        below = ref["logp_kth"] - ref["logp_token"]
        runs["numbers"].append(dict(check.beam_numbers(
            beams, logp_served, ref["logp_token"], below,
            weights_axk1.EOS_ID), _gaps=_gaps(beams, below)))
        if control:
            kept = np.take_along_axis(ref["logp_probe"],
                                      ref["rank"][..., None], -1)[..., 0]
            low_served = np.asarray(
                [np.sum(low["logp_token"][0, :_predictions(beams[0], n)]),
                 np.nan])
            below = ref["logp_kth"] - kept
            runs["control_fp8"].append(dict(check.beam_numbers(
                beams, low_served, ref["logp_token"], below,
                weights_axk1.EOS_ID), _gaps=_gaps(beams, below)))
        if "wrong_token" in extra:
            bad = beams.copy()
            at = 1 + int(rng.integers(_predictions(beams[0], n)))
            new = int(rng.integers(weights_axk1.FIRST_ID,
                                   config["vocab_size"] - 1))
            bad[0, at] = new + (new >= bad[0, at])     # any id but its own
            ref = reference_axk1.score_request(
                config, params, prompt, bad, beam, "f32", pad_to=pad_to)
            runs["wrong_token"].append(check.beam_numbers(
                bad, logp_served, ref["logp_token"],
                ref["logp_kth"] - ref["logp_token"], weights_axk1.EOS_ID))

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
    bytes they stand for (``flops_axk1.py``)."""
    def grown(field: str) -> int:
        return getattr(stats, field) - getattr(since, field)
    K = cfg.beam_size
    out = {"commits": len(win.items), "window_s": win.t_end - win.t0,
           "slots": win.eng.slots}
    for field in ("steps", "step_dispatches", "occupied_slot_steps",
                  "prefills", "harvest_row_reads",
                  "prompt_tokens", "prompt_tokens_padded",
                  "moe_assignments", "moe_assignments_held",
                  "moe_held_load_max"):
        out[field] = grown(field)
    out["prompt_pad_tokens"] = (out["prompt_tokens_padded"]
                                - out["prompt_tokens"])
    out["kv_bytes_per_slot"] = stats.kv_bytes_per_slot
    # what the window FINISHED: each harvested request's prefill and the
    # positions it ran, plus the routed products the device counted
    done = [(int(it.host["lengths"][it.row]),
             int(it.host["_limits"][it.row]) - 1) for it in win.items]
    out["flops"] = (sum(flops_axk1.request_flops(config, p, n, K)
                        for p, n in done)
                    + flops_axk1.routed_flops(config,
                                              out["moe_assignments_held"]))
    # prefill dispatches inside the window, by the lengths they held; the
    # routed part by the even router's expectation (the device's count does
    # not tell prefill's assignments from decode's)
    inside = [ls for t, ls in admits if win.t0 <= t <= win.t_end]
    out["prefill_flops"] = sum(
        flops_axk1.prefill_flops(
            config, int(p), flops_axk1.expected_held_assignments(config, p))
        for ls in inside for p in ls)
    # a step dispatch: R positions, each reading the weights once and the
    # latents of the slots occupied, at the requests' mean depth
    R = max(1, int(cfg.engine_harvest_every))
    slot_steps = sum(n for _p, n in done)
    if slot_steps and out["steps"]:
        occupied = out["occupied_slot_steps"] / out["steps"]
        latents = sum(flops_axk1.step_latent_bytes(config, p, t + 1, K)
                      for p, n in done for t in range(n)) / slot_steps
        out["step_min_bytes"] = R * out["step_dispatches"] * (
            flops_axk1.step_weight_bytes(config, occupied * K)
            + occupied * latents)
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
    params = weights_axk1.make_params(config, content_seed)
    prompts, max_new = make_prompt_requests(
        int(traffic["requests"]), vocab_size=config["vocab_size"],
        seed=common.seed31(content_seed),
        min_len=int(traffic["prompt_min_len"]),
        max_len=int(traffic["prompt_max_len"]),
        round_size=int(traffic["round_size"]),
        limits=tuple(traffic["max_new_tokens"]),
        first_id=weights_axk1.FIRST_ID)
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

    t_ref = time.perf_counter()
    sample = dc.pick(win.items, int(traffic["check_requests"]), seed,
                     lambda it: int(it.host["lengths"][it.row]))
    checked = lm_check(
        config, params,
        [(it.host["tokens"][it.row, :it.host["lengths"][it.row]],
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
                     [int(it.host["lengths"][it.row]) for it in win.items]),
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
                 "weights_bytes": 2 * weights_axk1.param_count(config)},
    }
