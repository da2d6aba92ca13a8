"""Second ablation round (honest D2H sync): the optimization levers.

  base        current code — in round 4 this includes the lever set shipped
              as default code paths (closed-form sigmoid combination gate,
              gather-then-log loss, single-buffer encoder, direct
              compute-dtype adjacency scatter); the delta vs the 106.87
              ms/step round-3 base IS their combined measurement
  rbg         cfg.rng_impl="rbg" hardware dropout PRNG
  sorted_scatter  host-sorted COO so scatters run indices_are_sorted
  fused8      cfg.fused_steps=8 device loop (one dispatch per 8 steps)
  rbg_fused8  both
  det         dropout rates zeroed — what's left of the RNG cost
  batch340    2x batch (per-sample cost check at the bigger tile)
  bf16_residual  stable_residual=False: inter-layer activations stored bf16
  no_remat    copy_head_remat=False: store the tanh intermediate instead of
              recomputing it in backward
  stacked     all cheap knobs together (the candidate production config)

Baseline to compare against: 106.87 ms/step (pre-optimization base,
builders' 2026-07-31 window, docs/PERF.md).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from fira_tpu.config import fira_full
from fira_tpu.data.batching import make_batch
from fira_tpu.data.synthetic import make_memory_split
from fira_tpu.model.model import FiraModel
from fira_tpu.train import step as step_lib
from fira_tpu.train.state import init_state

from fira_tpu.utils.startup import configure_compile_cache  # noqa: E402

configure_compile_cache()

N = 16


def measure(tag, rng_impl="threefry", fused=1, sort_edges=False,
            batch=170, **cfg_over):
    cfg = fira_full(batch_size=batch, compute_dtype="bfloat16",
                    rng_impl=rng_impl, fused_steps=fused,
                    sort_edges=sort_edges, **cfg_over)
    cfg, split, _ = make_memory_split(cfg, 256, seed=0,
                                      pad_vocab_to=24650, pad_ast_vocab_to=71)
    rng = np.random.RandomState(0)
    host = [make_batch(split, rng.choice(256, batch, replace=True), cfg)
            for _ in range(4)]
    model = FiraModel(cfg, dtype=jnp.bfloat16)
    state = init_state(model, cfg, host[0])

    if fused > 1:
        stacked = step_lib.stack_batches(
            [host[i % len(host)] for i in range(fused)])
        run = jax.jit(step_lib.make_multi_step(model, cfg),
                      donate_argnums=(0,))
        dev = jax.device_put(stacked)
        steps_per_call, calls = fused, max(1, N // fused)
    else:
        run = jax.jit(step_lib.make_train_step(model, cfg),
                      donate_argnums=(0,))
        dev = jax.device_put(host)
        steps_per_call, calls = 1, N
    jax.block_until_ready(dev)

    def one_round():
        nonlocal state
        for i in range(calls):
            b = dev if steps_per_call > 1 else dev[i % len(dev)]
            state, m = run(state, b)
        return float(np.asarray(jax.device_get(m["loss"])).ravel()[-1])

    t0 = time.perf_counter()
    loss = one_round()
    compile_s = time.perf_counter() - t0
    one_round()  # saturation throwaway
    times = []
    for _w in range(3):
        t0 = time.perf_counter()
        loss = one_round()
        times.append(time.perf_counter() - t0)
    n_steps = steps_per_call * calls
    dt = sorted(times)[1] / n_steps
    print(json.dumps({"tag": tag, "step_ms": round(dt * 1e3, 2),
                      "commits_per_sec": round(batch / dt, 1),
                      "loss_finite": bool(np.isfinite(loss)),
                      "compile_s": round(compile_s, 1)}), flush=True)


# FIRA_ABLATE2_ONLY=tag,tag runs a subset (e.g. "base,stacked" to re-pin
# the endpoints after a code change without the 25-min full sweep)
_only = os.environ.get("FIRA_ABLATE2_ONLY", "")
_only = {t.strip() for t in _only.split(",") if t.strip()} if _only else None
_ran: set = set()


def maybe(tag, **kw):
    if _only is None or tag in _only:
        _ran.add(tag)
        measure(tag, **kw)


maybe("base")
maybe("rbg", rng_impl="rbg")
maybe("sorted_scatter", sort_edges=True)
maybe("fused8", fused=8)
maybe("rbg_fused8", rng_impl="rbg", fused=8)
maybe("det", dropout_rate=0.0, gcn_dropout_rate=0.0)
maybe("batch340", batch=340)
maybe("bf16_residual", stable_residual=False)
maybe("no_remat", copy_head_remat=False)
# every cheap knob at once: the candidate production configuration
maybe("stacked", rng_impl="rbg", fused=8, sort_edges=True,
      stable_residual=False, copy_head_remat=False)
maybe("stacked_b340", rng_impl="rbg", fused=4, sort_edges=True,
      stable_residual=False, copy_head_remat=False, batch=340)
# round-4 second wave: split encoder buffer (no per-round update-slice),
# flat 1-D adjacency scatter (fully-ascending stream under sort_edges)
maybe("split_buffer", encoder_buffer="split")
maybe("stacked_split", rng_impl="rbg", fused=8, sort_edges=True,
      stable_residual=False, copy_head_remat=False, encoder_buffer="split")
maybe("stacked_flat", rng_impl="rbg", fused=8, sort_edges=True,
      stable_residual=False, copy_head_remat=False, flat_scatter=True)
maybe("stacked_split_flat", rng_impl="rbg", fused=8, sort_edges=True,
      stable_residual=False, copy_head_remat=False, encoder_buffer="split",
      flat_scatter=True)

if _only is not None and _only - _ran:
    # a typo'd tag silently measuring nothing would waste a TPU window
    print(json.dumps({"error": f"unknown tags: {sorted(_only - _ran)}"}),
          flush=True)
    sys.exit(2)
