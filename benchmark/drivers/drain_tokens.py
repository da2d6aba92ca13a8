"""Drain window for a decoder-only configuration whose architecture the
CONFIGURATION FILE names: ``drain_lm``'s window — the SAME
``decode/engine.SlotEngine.run`` over token-id prompts dealt in rounds,
``--seed`` permuting them and drawing the sample checked — with the weights,
reference and counts of the modules the file lists under ``modules``::

    "modules": {"model": "fira_tpu.model.jamba", "weights": "weights_jamba",
                "reference": "reference_jamba", "flops": "flops_jamba"}

so that a further architecture brings those three files and no driver
(``drain_lm_afmoe`` is ``drain_lm`` over again with another import: PERF.md
section 7 (n)). What the three have to offer:

- ``weights``: ``param_shapes(config)``, ``param_count(config)``,
  ``make_params(config, seed)``, ``EOS_ID``, ``FIRST_ID``.
- ``reference``: ``score_request(config, params, prompt, beams, beam, mode,
  probe_ids=, pad_to=)`` as ``reference_afmoe`` has it.
- ``flops``: ``request_flops(config, prompt_len, positions, beam)``,
  ``counted_flops(config, counters)`` (operations only a device count
  gives), ``prefill_flops(config, prompt_len)``, ``step_weight_bytes(config,
  rows)``, ``step_slot_bytes(config, prompt_len, gen_len, beam)`` (what an
  occupied slot's position moves besides the weights),
  ``derived_counters(config, counters)``.

The program's key block takes every key of the file that it has a field
for, as the file gives it. The window's counters are the engine's own plus
whatever the architecture's slot model counts on the device
(``arena_counters``).

The check is the decode cells', judged as ``drain_lm`` and
``drain_lm_afmoe`` judge: a sample of the requests finished in the window,
the longest prompt among them, their served beam and their last beam
teacher-forced through the plain reference over prompt AND message."""

from __future__ import annotations

import dataclasses
import importlib
import time
from typing import Dict, List

import numpy as np

from .. import check, common
from . import decode_common as dc
from .drain import Window
from .drain_lm import reference_length, request_stream


def modules_of(config: Dict):
    """(weights, reference, flops) the file names; the system under test
    has to have the architecture — a checkout without its model module
    stops here, before any weight is made."""
    names = config["modules"]
    importlib.import_module(names["model"])
    return tuple(importlib.import_module(f"benchmark.{names[k]}")
                 for k in ("weights", "reference", "flops"))


def program_cfg(config: Dict, traffic: Dict, seed: int):
    from fira_tpu.config import get_config

    block = type(get_config(config["preset"]).lm)
    lm = {f.name: (tuple(config[f.name])
                   if isinstance(config[f.name], list) else config[f.name])
          for f in dataclasses.fields(block) if f.name in config}
    return get_config(
        config["preset"], lm=lm, compute_dtype=config["compute_dtype"],
        beam_size=config["beam_size"], tar_len=config["tar_len"],
        engine_slots=int(traffic["engine_slots"]),
        kv_pool_blocks=int(traffic.get("kv_pool_blocks", 0)),
        feeder_workers=int(traffic["feeder_workers"]),
        feeder_depth=int(traffic["feeder_depth"]),
        seed=common.seed31(seed), **config.get("decode_knobs", {}))


def check_param_tree(cfg, config: Dict, weights) -> None:
    """The program has to accept the benchmark's weights as they are."""
    model = importlib.import_module(config["modules"]["model"])
    if model.param_shapes(cfg.lm) != weights.param_shapes(config):
        raise ValueError(f"the program's parameter tree is not the one "
                         f"benchmark/{config['modules']['weights']}.py "
                         f"builds")


def _predictions(row: np.ndarray, n: int, eos: int) -> int:
    """Predictions a beam made: through <eos>, or its request's limit."""
    hit = np.nonzero(row[1:n + 1] == eos)[0]
    return int(hit[0]) + 1 if len(hit) else n


def _gaps(beams: np.ndarray, below_kth: np.ndarray, eos: int) -> np.ndarray:
    """Every predicted position's distance below the reference's
    ``beam``-th best (0 where the served token is inside its beam)."""
    return np.concatenate(
        [np.maximum(0.0, np.asarray(below_kth)[i, :n]) for i, n in
         enumerate(check.beam_predictions(beams, eos))])


# ``readings.py --extra`` name -> the mode of the reference that stands in
# for the program: each the nearest precision below one the configuration
# states (bfloat16 products; where it has one, a float32 recurrent state)
CONTROLS = {"control": "fp8", "control_state": "state_bf16"}


def lm_check(config: Dict, params, samples: List, beam: int, pad: int,
             weights, reference, extra=(), seed: int = 0) -> Dict:
    """``samples``: (prompt ids, max_new, tokens (K, T), probs (K,)) of
    served requests, probs sums of logs. The numbers, the control and the
    one wrong pick are ``drain_lm.lm_check``'s, read through the reference
    the file names: of each request the served (most probable) beam, whose
    probability is compared too, and the last beam. ``control``: the
    reference in float8 put in the program's place; any other name of
    ``CONTROLS`` likewise, where the reference has that mode (a recurrent
    state in bfloat16). ``wrong_token``: one token of each request's served
    beam swapped for an id drawn from ``seed``, a request at a time — the
    LEAST ``topk_gap`` any one such request reads."""
    if not samples:
        return {"numbers": {"_where": {"requests": 0, "positions": 0}}}
    controls = [CONTROLS[x] for x in extra if x in CONTROLS]
    rng = np.random.default_rng(common.seed31(seed))
    eos = weights.EOS_ID
    runs = {"numbers": [], "wrong_token": [],
            **{f"control_{mode}": [] for mode in controls}}

    def score(prompt, beams, mode, pad_to, probe=None):
        return reference.score_request(config, params, prompt, beams, beam,
                                       mode, probe_ids=probe, pad_to=pad_to)
    for prompt, n, tokens, probs in samples:
        served = int(np.argmax(probs))
        rows = [served, tokens.shape[0] - 1 if served != tokens.shape[0] - 1
                else 0]
        beams = tokens[rows][:, :n + 1].astype(np.int32)
        logp_served = np.asarray([float(probs[served]), np.nan])
        pad_to = reference_length(len(prompt), n, pad)
        lows = [score(prompt, beams, mode, pad_to) for mode in controls]
        # the reference is asked for each control's own picks in one pass
        ref = score(prompt, beams, "f32", pad_to,
                    np.concatenate([low["top_ids"] for low in lows], -1)
                    if lows else None)
        below = ref["logp_kth"] - ref["logp_token"]
        runs["numbers"].append(dict(check.beam_numbers(
            beams, logp_served, ref["logp_token"], below, eos),
            _gaps=_gaps(beams, below, eos)))
        for i, (mode, low) in enumerate(zip(controls, lows)):
            kept = np.take_along_axis(
                ref["logp_probe"], i * beam + ref["rank"][..., None],
                -1)[..., 0]
            low_served = np.asarray([np.sum(low["logp_token"][
                0, :_predictions(beams[0], n, eos)]), np.nan])
            below = ref["logp_kth"] - kept
            runs[f"control_{mode}"].append(dict(check.beam_numbers(
                beams, low_served, ref["logp_token"], below, eos),
                _gaps=_gaps(beams, below, eos)))
        if "wrong_token" in extra:
            bad = beams.copy()
            at = 1 + int(rng.integers(_predictions(beams[0], n, eos)))
            new = int(rng.integers(weights.FIRST_ID,
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
    for mode in controls:
        out[f"control_{mode}"] = merged(runs[f"control_{mode}"])
    if runs["wrong_token"]:
        out["wrong_token"] = {
            "topk_gap": min(p["topk_gap"] for p in runs["wrong_token"]),
            "prob_gap": min(p["prob_gap"] for p in runs["wrong_token"]),
            "_where": {"requests": len(runs["wrong_token"])}}
    return out


ENGINE_COUNTS = ("steps", "step_dispatches", "occupied_slot_steps",
                 "prefills", "harvest_row_reads", "prompt_tokens",
                 "prompt_tokens_padded")


def window_counters(config: Dict, cfg, win, admits: List, stats, since,
                    flops) -> Dict:
    """The window's share of the engine's counts and the operations and
    bytes they stand for (the file's ``flops`` module)."""
    K = cfg.beam_size
    out = {"commits": len(win.items), "window_s": win.t_end - win.t0,
           "slots": win.eng.slots}
    for field in ENGINE_COUNTS + tuple(win.eng.smodel.arena_counters):
        out[field] = getattr(stats, field) - getattr(since, field)
    out["prompt_pad_tokens"] = (out["prompt_tokens_padded"]
                                - out["prompt_tokens"])
    for field in ("kv_bytes_per_slot", "kv_bytes_per_slot_full",
                  "kv_bytes_per_slot_window", "kv_bytes_per_slot_state"):
        out[field] = getattr(stats, field)
    out.update(flops.derived_counters(config, out))
    # what the window FINISHED: each harvested request's prefill and the
    # positions it ran
    done = [(int(it.host["lengths"][it.row]),
             int(it.host["_limits"][it.row]) - 1) for it in win.items]
    out["flops"] = (sum(flops.request_flops(config, p, n, K)
                        for p, n in done)
                    + flops.counted_flops(config, out))
    # prefill dispatches inside the window, by the lengths they held
    inside = [ls for t, ls in admits if win.t0 <= t <= win.t_end]
    out["prefill_flops"] = sum(flops.prefill_flops(config, int(p))
                               for ls in inside for p in ls)
    # a step dispatch: R positions, each reading the weights once and
    # moving what the occupied slots need, at the requests' mean depth
    R = max(1, int(cfg.engine_harvest_every))
    slot_steps = sum(n for _p, n in done)
    if slot_steps and out["steps"]:
        occupied = out["occupied_slot_steps"] / out["steps"]
        a_slot = sum(flops.step_slot_bytes(config, p, t + 1, K)
                     for p, n in done for t in range(n)) / slot_steps
        out["step_min_bytes"] = R * out["step_dispatches"] * (
            flops.step_weight_bytes(config, occupied * K)
            + occupied * a_slot)
    return out


def run(ctx: Dict) -> Dict:
    from fira_tpu.data import buckets
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.data.synthetic import make_prompt_requests
    from fira_tpu.decode.engine import SlotEngine

    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    weights, reference, flops = modules_of(config)
    content_seed = int(traffic.get("content_seed", seed))
    cfg = program_cfg(config, traffic, seed)
    check_param_tree(cfg, config, weights)
    params = weights.make_params(config, content_seed)
    prompts, max_new = make_prompt_requests(
        int(traffic["requests"]), vocab_size=config["vocab_size"],
        seed=common.seed31(content_seed),
        min_len=int(traffic["prompt_min_len"]),
        max_len=int(traffic["prompt_max_len"]),
        round_size=int(traffic["round_size"]),
        limits=tuple(traffic["max_new_tokens"]),
        first_id=weights.FIRST_ID)
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
                               win.stats0, flops)
    peak, memory = common.memory_peak_bytes(), common.memory_stats()
    arena = {k: [list(v.shape), str(v.dtype)]
             for k, v in (eng._state or {}).items()}
    eng._state = None                      # free the arena before the check

    def prompt_len(it) -> int:
        return int(it.host["lengths"][it.row])
    t_ref = time.perf_counter()
    sample = dc.pick(win.items, int(traffic["check_requests"]), seed,
                     prompt_len)
    checked = lm_check(
        config, params,
        [(it.host["tokens"][it.row, :prompt_len(it)],
          int(it.host["_limits"][it.row]) - 1, it.tokens, it.probs)
         for it in sample], cfg.beam_size, int(traffic["reference_pad"]),
        weights, reference, extra=ctx["extra"], seed=seed)
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
                 "weights_bytes": 2 * weights.param_count(config)},
    }
