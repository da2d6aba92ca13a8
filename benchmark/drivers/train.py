"""Training window: ``train/step.py:jit_multi_step`` (one dispatch = K fused
optimizer steps) fed by a running ``data.feeder.Feeder``, dispatched and
synchronised the way ``train/loop.py:train`` does it, with no checkpoint and
no dev gate.

Set-up builds ONE compiled step with its state, drives it through its first
dispatch (K different batches) and hands the same object to the window. What
that first dispatch produced — K losses, Adam's first moment, the parameters'
change — is what ``correct`` compares with the reference once the window has
closed (``check.py``).
"""

from __future__ import annotations

import itertools
import math
import time
from typing import Dict

import numpy as np

from .. import check, common, reference, weights


def _tasks(split, cfg, batch_size: int, group: int, seed: int):
    """Assembly tasks for epoch 0, 1, 2, ... of the corpus, each epoch a
    fresh seeded permutation cut into K-groups (data/grouping.py)."""
    from fira_tpu.data import grouping

    for epoch in itertools.count():
        plan = grouping.grouped_plan(
            split, cfg, batch_size=batch_size, group_size=group,
            accum=False, shuffle=True, seed=seed, epoch=epoch)
        plan = [e for e in plan if e.pad_to == group]  # whole groups only
        yield from grouping.grouped_assembly_tasks(
            split, plan, cfg, batch_size=batch_size)


def _adam_mu(opt_state):
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise ValueError("no Adam first moment in the optimizer state")


def run(ctx: Dict) -> Dict:
    import jax
    import jax.numpy as jnp

    from fira_tpu.data.feeder import Feeder
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train import step as step_lib
    from fira_tpu.train.state import (TrainState, make_optimizer,
                                      prng_impl_name)

    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    mcfg = common.model_cfg_dict(config)
    B = int(traffic["batch_size"])
    cfg = common.program_cfg(
        config, "train_knobs", batch_size=B,
        feeder_workers=int(traffic["feeder_workers"]),
        feeder_depth=int(traffic["feeder_depth"]), seed=common.seed31(seed))
    K = int(cfg.fused_steps)
    n_commits = int(traffic["corpus_groups"]) * K * B
    cfg, split, _vocab = common.make_corpus(cfg, config, n_commits, seed)

    model = FiraModel(cfg, dtype=jnp.dtype(cfg.compute_dtype))
    params = weights.make_params(mcfg, seed)
    params0 = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))(params)
    rng = jax.random.key_data(jax.random.key(
        common.seed31(seed), impl=prng_impl_name(cfg.rng_impl)))
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=make_optimizer(cfg).init(params), rng=rng)
    del params

    feed = Feeder(_tasks(split, cfg, B, K, common.seed31(seed)),
                  num_workers=cfg.feeder_workers, depth=cfg.feeder_depth)
    try:
        first = next(feed)
        wire_row = {k: v[0, :1] for k, v in first.host.items()
                    if not k.startswith("_")}
        common.check_param_tree(model, cfg, wire_row, config)
        step = step_lib.jit_multi_step(model, cfg, None, state,
                                       first.device)

        # --- the first dispatch: compiles (or hits the cache), and is what
        # the reference follows step for step
        state, metrics = step(state, first.device)
        losses0 = np.asarray(jax.device_get(metrics["loss"]), np.float64)
        got = {"losses": losses0,
               "mu": check.leaf_norms(_adam_mu(state.opt_state)),
               "change": check.leaf_norms(state.params, params0)}
        first_host = {k: v for k, v in first.host.items()
                      if not k.startswith("_") and k != "valid"}
        del first

        # --- the window
        tracer = common.tracer_for(ctx, traffic)
        stall0 = feed.stats()["feed_stall_s"]
        commits = dispatches = failed = 0
        t_setup = time.perf_counter()
        tracer.open()
        t0 = time.perf_counter()
        while True:
            with common.span("feed.next"):
                item = next(feed)
            with common.span("dispatch"):
                state, metrics = step(state, item.device)
            with common.span("sync"):
                loss = np.asarray(jax.device_get(metrics["loss"]))
            dispatches += 1
            commits += int(item.n_valid)
            if not np.all(np.isfinite(loss)):
                failed += 1
            if time.perf_counter() - t0 >= ctx["seconds"]:
                break
        window_s = time.perf_counter() - t0
        tracer.close()
        stall_s = feed.stats()["feed_stall_s"] - stall0
    finally:
        feed.close()

    peak, memory = common.memory_peak_bytes(), common.memory_stats()
    del state, metrics, item

    # --- the reference, once the program's state is freed
    t_ref = time.perf_counter()
    block = int(traffic["ref_block"])
    stacked = {k: jnp.asarray(v) for k, v in first_host.items()}
    ref = reference.make_train_steps(mcfg, "f32", block, cfg.lr)(
        params0, stacked)
    ref_n = {"losses": np.asarray(ref["losses"], np.float64),
             "mu": check.leaf_norms(ref["mu"]),
             "change": check.leaf_norms(ref["params"], params0),
             "grad1": check.named_scalars(ref["grad_norms"], 0)}
    numbers = check.train_numbers(got, ref_n)
    ref_s = time.perf_counter() - t_ref

    # readings for setting the limits (readings.py), never in a timed run:
    # the control — the reference in float8 put in the program's place —
    # and the fault "half of the batch left out, the mean over the rest"
    extra = {}
    stand_ins = {"control": ("fp8", stacked),
                 "half_batch": ("f32", {k: v[:, :B // 2]
                                        for k, v in stacked.items()})}
    for name, (mode, data) in stand_ins.items():
        if name not in ctx["extra"]:
            continue
        blk = block if data["diff"].shape[1] % block == 0 else B // 2
        alt = reference.make_train_steps(mcfg, mode, blk, cfg.lr)(
            params0, data)
        extra[name] = check.train_numbers(
            {"losses": np.asarray(alt["losses"], np.float64),
             "mu": check.leaf_norms(alt["mu"]),
             "change": check.leaf_norms(alt["params"], params0)}, ref_n)

    steps = dispatches * K
    return {
        "setup_end": t_setup,
        "window_s": window_s,
        "attempted": dispatches,
        "failed": failed,
        "end_to_end": {"train_commits_per_s": commits / window_s},
        "counters": {"commits": commits, "dispatches": dispatches,
                     "steps": steps, "steps_per_dispatch": K,
                     "batch_size": B, "feed_stall_s": stall_s,
                     "window_s": window_s,
                     "flops": steps * _step_flops(mcfg, cfg, B)},
        "records": [],
        "tracer": tracer,
        "memory_peak_bytes": peak,
        "numbers": numbers,
        "extra_numbers": extra,
        "info": {"first_losses": [float(x) for x in losses0],
                 "ref_losses": [float(x) for x in ref_n["losses"]],
                 "reference_s": ref_s, "corpus_commits": n_commits,
                 "memory": memory,
                 "loss_last": float(np.ravel(loss)[-1])
                 if math.isfinite(float(np.ravel(loss)[-1])) else None},
    }


def _step_flops(mcfg: Dict, cfg, batch_size: int) -> float:
    from .. import flops

    return flops.train_step_flops(
        {**mcfg, "adjacency_impl": cfg.adjacency_impl}, batch_size)
