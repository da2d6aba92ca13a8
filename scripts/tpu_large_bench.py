"""fira-large numbers, each window ended by materializing its result on
the host: train-step throughput + MFU and KV-beam decode rate at the
8-layer d=512 beam-8 geometry (BASELINE.json's v4-32 config). Prints one
JSON line per measurement.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from fira_tpu.config import fira_large
from fira_tpu.data.batching import make_batch
from fira_tpu.data.synthetic import make_memory_split
from fira_tpu.decode.beam import make_beam_search
from fira_tpu.model.model import FiraModel
from fira_tpu.train import step as step_lib
from fira_tpu.train.state import init_state

from fira_tpu.utils.startup import configure_compile_cache  # noqa: E402

configure_compile_cache()

BATCH = int(os.environ.get("FIRA_LARGE_BATCH", "64"))
N_STEPS = int(os.environ.get("FIRA_LARGE_STEPS", "12"))


def main() -> None:
    cfg = fira_large(batch_size=BATCH, compute_dtype="bfloat16",
                     test_batch_size=16)
    cfg, split, _ = make_memory_split(cfg, 128, seed=0,
                                      pad_vocab_to=24650, pad_ast_vocab_to=71)
    rng = np.random.RandomState(0)
    host = [make_batch(split, rng.choice(128, BATCH, replace=True), cfg)
            for _ in range(4)]
    model = FiraModel(cfg, dtype=jnp.bfloat16)
    state = init_state(model, cfg, host[0])
    train = jax.jit(step_lib.make_train_step(model, cfg), donate_argnums=(0,))
    dev = jax.device_put(host)
    jax.block_until_ready(dev)

    def window():
        nonlocal state
        for i in range(N_STEPS):
            state, m = train(state, dev[i % len(dev)])
        return float(np.asarray(jax.device_get(m["loss"])).ravel()[-1])

    t0 = time.perf_counter()
    loss = window()
    compile_s = time.perf_counter() - t0
    window()  # queue-fill throwaway
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        loss = window()
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[1] / N_STEPS

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from bench import _analytic_flops, _peak_flops  # noqa: E402

    flops = _analytic_flops(cfg, BATCH)
    peak = _peak_flops(jax.devices()[0].device_kind, "bfloat16")
    print(json.dumps({
        "tag": "fira-large-train", "batch": BATCH,
        "step_ms": round(dt * 1e3, 2),
        "commits_per_sec_per_chip": round(BATCH / dt, 1),
        "mfu": round(flops / dt / peak, 4),
        "flops_per_step": flops,
        "loss_finite": bool(np.isfinite(loss)),
        "compile_s": round(compile_s, 1),
    }), flush=True)

    # KV-cached beam decode at test batch
    tb = make_batch(split, np.arange(cfg.test_batch_size), cfg)
    beam = make_beam_search(model, cfg)
    t0 = time.perf_counter()
    tokens, probs = beam(state.params, tb)
    _ = np.asarray(jax.device_get(probs))
    beam_compile_s = time.perf_counter() - t0
    times = []
    for _ in range(4):
        t0 = time.perf_counter()
        tokens, probs = beam(state.params, tb)
        _ = np.asarray(jax.device_get(probs))  # D2H sync
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[1]
    print(json.dumps({
        "tag": "fira-large-decode-kv", "batch": cfg.test_batch_size,
        "beam": cfg.beam_size,
        "batch_secs": round(dt, 3),
        "commits_per_sec_per_chip": round(cfg.test_batch_size / dt, 2),
        "compile_s": round(beam_compile_s, 1),
    }), flush=True)


if __name__ == "__main__":
    main()
