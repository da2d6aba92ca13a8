"""Honest (D2H-synced) per-variant step timing on the live chip.

Variants toggle the two TPU-layout knobs (adjacency_impl, copy_head_impl)
plus diagnostic geometry cuts that localize the cost (not shippable configs,
just attribution). One throwaway window first absorbs executable load and
pipeline fill.
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

N = 10


def measure(tag: str, pad_vocab=24650, **cfg_kw) -> None:
    cfg = fira_full(batch_size=170, compute_dtype="bfloat16", **cfg_kw)
    cfg, split, _ = make_memory_split(cfg, 256, seed=0,
                                      pad_vocab_to=pad_vocab,
                                      pad_ast_vocab_to=71)
    rng = np.random.RandomState(0)
    host = [make_batch(split, rng.choice(256, 170, replace=True), cfg)
            for _ in range(4)]
    model = FiraModel(cfg, dtype=jnp.bfloat16)
    state = init_state(model, cfg, host[0])
    step = jax.jit(step_lib.make_train_step(model, cfg), donate_argnums=(0,))
    dev = jax.device_put(host)
    jax.block_until_ready(dev)

    t0 = time.perf_counter()
    state, m = step(state, dev[0])
    _ = float(m["loss"])
    compile_s = time.perf_counter() - t0

    # throwaway window: executable load + pipeline fill
    for i in range(N):
        state, m = step(state, dev[i % 4])
    _ = float(m["loss"])

    times = []
    for _w in range(3):
        t0 = time.perf_counter()
        for i in range(N):
            state, m = step(state, dev[i % 4])
        _ = float(m["loss"])  # D2H materialization - honest sync
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[1] / N
    print(json.dumps({"tag": tag, "step_ms": round(dt * 1e3, 2),
                      "commits_per_sec": round(170 / dt, 1),
                      "compile_s": round(compile_s, 1)}), flush=True)


measure("base_dense_xla")
measure("segment_adj", adjacency_impl="segment")
measure("pallas_copy", copy_head_impl="pallas")
measure("segment_pallas", adjacency_impl="segment", copy_head_impl="pallas")
# diagnostics: where does the time live?
measure("diag_vocab1k", pad_vocab=1000)          # output-head share
measure("diag_layers1", num_layers=1)            # enc+dec stack share
