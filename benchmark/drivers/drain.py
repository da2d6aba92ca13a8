"""Drain window: ``decode/engine.SlotEngine.run`` over a cycled in-memory
split, as ``decode/runner.run_test`` drives it under ``decode_engine``. The
window opens once warm-up has turned the whole arena over, so it sees the
steady refill regime and not the ramp; it opens and closes at the end of a
harvest, the only place the host sees the device's progress."""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Dict, List

from .. import common
from . import decode_common as dc


def _chunks(n: int, size: int, seed: int):
    from fira_tpu.data.batching import epoch_order

    for epoch in itertools.count():
        order = epoch_order(n, shuffle=True, seed=seed, epoch=epoch)
        for start in range(0, n - size + 1, size):
            yield order[start:start + size]


class Window:
    """Called at the end of every harvest: counts warm-up commits, opens the
    window, keeps what it harvests, closes it after ``seconds``."""

    def __init__(self, eng, warm_commits: int, seconds: float, tracer):
        self.eng, self.tracer = eng, tracer
        self.warm_commits, self.seconds = warm_commits, seconds
        self.warmed = 0
        self.t0 = self.t_end = self.stats0 = None
        self.items: List = []

    def on_return(self, meth: str, out) -> None:
        if meth != "harvest" or self.t_end is not None:
            return
        now = time.perf_counter()
        if self.t0 is None:
            self.warmed += len(out)
            if self.warmed >= self.warm_commits:
                self.stats0 = dataclasses.replace(self.eng.stats)
                self.tracer.open()
                self.t0 = time.perf_counter()
            return
        self.items.extend(out)
        if now - self.t0 >= self.seconds:
            self.t_end = now
            self.stats1 = dataclasses.replace(self.eng.stats)


def run(ctx: Dict) -> Dict:
    from fira_tpu.data.feeder import Feeder, assembly_tasks

    traffic, seed = ctx["traffic"], ctx["seed"]
    mcfg = common.model_cfg_dict(ctx["config"])
    cfg, split, _vocab, _model, params, eng = dc.build_engine(
        ctx, int(traffic["corpus_commits"]))
    tracer = common.tracer_for(ctx, traffic)
    win = Window(eng, int(traffic["warm_turnovers"]) * eng.slots,
                 ctx["seconds"], tracer)
    common.wrap_spans(eng, dc.ENGINE_SPANS, win.on_return)
    tasks = assembly_tasks(
        split, _chunks(len(split), cfg.test_batch_size, common.seed31(seed)),
        cfg, batch_size=cfg.test_batch_size)

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
    window_s = win.t_end - win.t0
    commits = len(win.items)
    counters = dc.engine_counters(mcfg, commits, window_s, eng.slots,
                                  win.stats1, since=win.stats0)
    peak, memory = common.memory_peak_bytes(), common.memory_stats()
    arena = {k: [list(v.shape), str(v.dtype)]
             for k, v in (eng._state or {}).items()}
    eng._state = None                      # free the arena before the check

    t_ref = time.perf_counter()
    sample = dc.pick(win.items, int(traffic["check_requests"]), seed,
                     lambda it: dc.beam_lengths(it.tokens))
    checked = dc.beam_check(
        mcfg, params, [(it.host, it.row, it.tokens, it.probs)
                       for it in sample], cfg.beam_size,
        log_space=not cfg.beam_compat_prob_space,
        control="control" in ctx["extra"])
    return {
        "setup_end": win.t0, "window_s": window_s,
        "attempted": commits, "failed": 0,
        "end_to_end": {"decode_commits_per_s": commits / window_s},
        "counters": counters, "records": [], "tracer": tracer,
        "memory_peak_bytes": peak, "numbers": checked.pop("numbers"),
        "extra_numbers": checked,
        "info": {**dc.length_info(counters["occupied_slot_steps"],
                                  [(it.tokens, it.probs)
                                   for it in win.items]),
                 "reference_s": time.perf_counter() - t_ref,
                 "memory": memory, "arena": arena,
                 "warm_commits": win.warmed},
    }
