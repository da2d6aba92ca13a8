"""Serve window: ``serve/server.serve_split`` on a pre-warmed engine, wall
clock, open-loop Poisson arrivals from the seed at the rate the traffic file
fixes, requests cycling the split, FIFO refill, no deadline shedding.

Set-up serves a short untimed burst through every program first. The window
then offers every arrival due in ``--seconds`` and keeps serving until each
is done, so every due request has a latency; shed, errored or unfinished
ones are ``failed``. Latencies run from the SCHEDULED arrival.
"""

from __future__ import annotations

import os
import time
import types
from typing import Dict

import numpy as np

from .. import arrivals, common
from . import decode_common as dc


def _record_served(server, store: Dict):
    """Keep what each request was served — (host batch, row, beams, their
    probabilities) — as the serve loop emits it."""
    inner = server.sample_emitter

    def factory(writer, **kw):
        emit = inner(writer, **kw)

        def recording(pos, host, row, tokens, probs):
            store[pos] = (host, row, np.array(tokens), np.array(probs))
            return emit(pos, host, row, tokens, probs)
        return recording
    return inner, factory


def run(ctx: Dict) -> Dict:
    from fira_tpu.decode.engine import EngineStats
    from fira_tpu.serve import server

    traffic, seed = ctx["traffic"], ctx["seed"]
    mcfg = common.model_cfg_dict(ctx["config"])
    cfg, split, vocab, model, params, eng = dc.build_engine(
        ctx, int(traffic["corpus_commits"]),
        prefix_cache=bool(traffic.get("prefix_cache", False)),
        serve_prefill_budget=int(traffic.get("serve_prefill_budget", 1)))
    common.wrap_spans(eng, dc.ENGINE_SPANS)
    n = len(split)
    dataset = types.SimpleNamespace(
        splits={"test": split}, word_vocab=vocab,
        split_indices={"test": list(range(n))}, cfg=cfg)
    out_dir = os.path.join(ctx["out_dir"], "serve")
    rate = float(traffic["rate_rps"])
    rng = np.random.default_rng(common.seed31(seed))

    def serve(times):
        mix = rng.permutation(max(n, len(times)))[:len(times)] % n
        return server.serve_split(
            model, params, dataset, cfg, arrival_times=times,
            out_dir=out_dir, engine=eng, request_mix=mix, clock="wall")

    served: Dict[int, tuple] = {}
    original, factory = _record_served(server, served)
    server.sample_emitter = factory
    tracer = common.tracer_for(ctx, traffic)
    try:
        # warm-up: a burst through prefill, insert, step and harvest
        serve(np.zeros(int(traffic["warm_requests"])))
        served.clear()
        eng.stats = EngineStats(slots=eng.slots)
        times = arrivals.arrivals_for_window(
            rate, ctx["seconds"], common.seed31(seed),
            base_seed=int(traffic.get("arrival_gaps_seed", 0)))
        t_setup = time.perf_counter()
        tracer.open()
        with common.span("serve.loop"):
            result = serve(times)
        wall_s = time.perf_counter() - t_setup
        tracer.close()
    finally:
        server.sample_emitter = original

    records = result["request_records"]
    done = [r for r in records if r["status"] == "done"
            and r["done_t"] == r["done_t"]]
    e2e = [r["done_t"] - r["arrival_t"] for r in done]
    st = eng.stats
    counters = dc.engine_counters(mcfg, len(done), wall_s, eng.slots, st)
    # seats held during the offered window: a request holds its slot from
    # ``seat_t`` to the harvest that ends it (``done_t``), whole dispatches,
    # where ``occupied_slot_steps`` counts only the positions it ran and is
    # averaged over the ramp and the tail after the last arrival as well
    T = float(ctx["seconds"])
    counters.update(
        seat_slot_seconds=sum(min(r["done_t"], T) - min(r["seat_t"], T)
                              for r in done if r["seat_t"] == r["seat_t"]),
        offered_window_s=T,
        offered=len(times), rate_rps=rate,
        peak_queue_depth=result["serve"].get("peak_queue_depth"),
        last_done_s=max((r["done_t"] for r in done), default=0.0))
    peak, memory = common.memory_peak_bytes(), common.memory_stats()
    eng._state = None                      # free the arena before the check

    t_ref = time.perf_counter()
    finished = [served[r["position"]] for r in done
                if r["position"] in served]
    sample = dc.pick(finished, int(traffic["check_requests"]), seed,
                     lambda s: dc.beam_lengths(s[2]))
    checked = dc.beam_check(mcfg, params, sample, cfg.beam_size,
                            log_space=not cfg.beam_compat_prob_space,
                            control="control" in ctx["extra"])
    return {
        "setup_end": t_setup, "window_s": wall_s,
        "attempted": len(times), "failed": len(times) - len(done),
        "end_to_end": {
            "serve_e2e_p50_s": common.percentile(e2e, 50),
            "serve_e2e_p95_s": common.percentile(e2e, 95),
            "decode_commits_per_s": len(done) / wall_s if wall_s else None},
        "counters": counters, "records": records, "tracer": tracer,
        "memory_peak_bytes": peak, "numbers": checked.pop("numbers"),
        "extra_numbers": checked,
        "info": {**dc.length_info(st.occupied_slot_steps,
                                  [(s[2], s[3]) for s in finished]),
                 "reference_s": time.perf_counter() - t_ref,
                 "memory": memory,
                 "serve_summary": {k: v for k, v in result["serve"].items()
                                   if isinstance(v, (int, float, str))}},
    }
