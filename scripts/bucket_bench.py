"""Bucket-vs-single-geometry measurement: padding_frac, assembly
throughput, and train/decode steps-per-second at EQUAL batch stream.

The win must be measured, not asserted: this script runs the SAME sample
stream through the single-geometry path and the bucketed path
(data/buckets.py) and reports, as JSON lines:

  assembly    make_batch host cost per epoch both ways (the bucketed path
              pads less, so it also copies less), plus the corpus
              padding_frac accounting (data.buckets.padding_report).
  train       wall clock for one epoch of jitted train steps over the
              identical (seed, epoch) sample stream, single vs bucketed
              (bucketed = pre-warmed program family, packed batches).
              Reported as steps/sec and commits/sec.
  decode      wall clock to beam-decode the split, single vs bucketed
              (sort-by-length packing; tar stays full on decode buckets).

Runs on CPU at the fira-tiny geometry by default — the RELATIVE number is
the point (pad FLOPs removed per FLOP kept); the flagship absolute numbers
belong to the TPU campaign scripts. Usage:

    JAX_PLATFORMS=cpu python scripts/bucket_bench.py [n_samples]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fira_tpu.utils.startup import force_cpu_backend  # noqa: E402

force_cpu_backend()

import numpy as np  # noqa: E402


def bench(n_data: int = 256) -> int:
    import jax

    from fira_tpu.config import fira_tiny
    from fira_tpu.data import buckets as B
    from fira_tpu.data.batching import epoch_index_chunks, make_batch
    from fira_tpu.data.synthetic import make_memory_split
    from fira_tpu.decode.beam import make_beam_search
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train import step as step_lib
    from fira_tpu.train.state import init_state

    cfg0, split, _ = make_memory_split(fira_tiny(), n_data, seed=0)
    table_spec = B.choose_buckets(split, cfg0)
    cfg = cfg0.replace(buckets=table_spec)
    table = B.bucket_table(cfg)
    dec_table = B.decode_table(cfg)
    bs = cfg.batch_size

    report = B.padding_report(split, cfg, table)
    print(json.dumps({"leg": "padding", **report,
                      "buckets_declared": [B.geom_tag(g) for g in table]}))

    # --- assembly: one epoch of host-side make_batch, same stream ---
    chunks = epoch_index_chunks(len(split), cfg, shuffle=True, seed=1,
                                epoch=0)
    plan = B.packed_plan(split, cfg, batch_size=bs, shuffle=True, seed=1,
                         epoch=0)

    def time_best(fn, reps: int = 3) -> float:
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_single = time_best(lambda: [make_batch(split, c, cfg, batch_size=bs)
                                  for c in chunks])
    t_bucket = time_best(lambda: [make_batch(split, c, cfg, batch_size=bs,
                                             geom=g) for c, g in plan])
    print(json.dumps({
        "leg": "assembly",
        "batches_single": len(chunks), "batches_bucketed": len(plan),
        "assembly_ms_single": round(1e3 * t_single, 2),
        "assembly_ms_bucketed": round(1e3 * t_bucket, 2),
        "assembly_speedup": round(t_single / t_bucket, 3),
    }))

    # --- train: one epoch of jitted steps over the identical stream ---
    model = FiraModel(cfg)
    sample = make_batch(split, np.arange(bs), cfg, batch_size=bs)
    state0 = init_state(model, cfg, sample)
    step = jax.jit(step_lib.make_train_step(model, cfg))

    def run_epoch(batches, state):
        t0 = time.perf_counter()
        for b in batches:
            state, m = step(state, b)
        float(np.asarray(jax.device_get(m["loss"])).ravel()[-1])  # sync
        return time.perf_counter() - t0, state

    single_batches = [make_batch(split, c, cfg, batch_size=bs)
                      for c in chunks]
    bucket_batches = [make_batch(split, c, cfg, batch_size=bs, geom=g)
                      for c, g in plan]
    # warm every program of both paths out of the timed window
    warm = jax.device_put(jax.device_get(state0))
    warm, _ = step(warm, single_batches[0])
    for g in table:
        warm, _ = step(warm, B.warmup_batch(split, cfg, g, bs))
    dt_single, _ = run_epoch(single_batches,
                             jax.device_put(jax.device_get(state0)))
    dt_bucket, _ = run_epoch(bucket_batches,
                             jax.device_put(jax.device_get(state0)))
    print(json.dumps({
        "leg": "train",
        "steps_single": len(single_batches),
        "steps_bucketed": len(bucket_batches),
        "steps_per_sec_single": round(len(single_batches) / dt_single, 2),
        "steps_per_sec_bucketed": round(len(bucket_batches) / dt_bucket, 2),
        "commits_per_sec_single": round(n_data / dt_single, 2),
        "commits_per_sec_bucketed": round(n_data / dt_bucket, 2),
        "train_speedup": round(dt_single / dt_bucket, 3),
    }))

    # --- decode: beam the split, sequential vs sort-by-length packed ---
    dcfg = cfg.replace(test_batch_size=cfg.test_batch_size)
    beam = make_beam_search(model, dcfg)
    params = state0.params
    dchunks = epoch_index_chunks(len(split), dcfg,
                                 batch_size=dcfg.test_batch_size)
    dplan = B.packed_plan(split, dcfg, batch_size=dcfg.test_batch_size,
                          table=dec_table, use_msg=False)
    d_single = [make_batch(split, c, dcfg, batch_size=dcfg.test_batch_size)
                for c in dchunks]
    d_bucket = [make_batch(split, c, dcfg, batch_size=dcfg.test_batch_size,
                           geom=g) for c, g in dplan]
    beam(params, d_single[0])  # warm
    for g in dec_table:
        beam(params, B.warmup_batch(split, dcfg, g, dcfg.test_batch_size))

    def run_decode(batches):
        t0 = time.perf_counter()
        for b in batches:
            tokens, probs = beam(params, b)
        np.asarray(jax.device_get(tokens))
        return time.perf_counter() - t0

    dt_dec_single = run_decode(d_single)
    dt_dec_bucket = run_decode(d_bucket)
    print(json.dumps({
        "leg": "decode",
        "batches_single": len(d_single), "batches_bucketed": len(d_bucket),
        "commits_per_sec_single": round(n_data / dt_dec_single, 2),
        "commits_per_sec_bucketed": round(n_data / dt_dec_bucket, 2),
        "decode_speedup": round(dt_dec_single / dt_dec_bucket, 3),
    }))
    return 0


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    raise SystemExit(bench(n))
