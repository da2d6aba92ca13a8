"""Honest (D2H-synced) TPU decode benchmark: KV-cached beam vs full
re-decode at the flagship geometry. Prints one JSON line per mode."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from fira_tpu.config import get_config
from fira_tpu.data.batching import make_batch
from fira_tpu.data.synthetic import make_memory_split
from fira_tpu.decode.beam import make_beam_search
from fira_tpu.model.model import FiraModel
from fira_tpu.train.state import init_state

from fira_tpu.utils.startup import configure_compile_cache  # noqa: E402

configure_compile_cache()

N = int(os.environ.get("DECODE_N", "5"))
BATCH = int(os.environ.get("DECODE_BATCH", "170"))
DTYPE = os.environ.get("DECODE_DTYPE", "bfloat16")
# DECODE_CONFIG=fira-tiny: CPU smoke of the harness itself (compiling the
# flagship beam on CPU takes tens of minutes; the tiny geometry compiles in
# seconds). The official rows are fira-full.
CONFIG = os.environ.get("DECODE_CONFIG", "fira-full")
# DECODE_TAR_LEN: override the message-position budget — the CPU engine
# smoke runs fira-tiny geometry at the flagship tar 30 so the
# length-mix rows exercise the real 29-step budget.
TAR_LEN = os.environ.get("DECODE_TAR_LEN")

cfg0 = get_config(CONFIG).replace(batch_size=BATCH, test_batch_size=BATCH,
                                  compute_dtype=DTYPE,
                                  **({"tar_len": int(TAR_LEN)}
                                     if TAR_LEN else {}))
pad_v = 24650 if CONFIG == "fira-full" else 0
cfg0, split, _ = make_memory_split(cfg0, max(256, BATCH), seed=0,
                                   pad_vocab_to=pad_v,
                                   pad_ast_vocab_to=71 if pad_v else 0)
rng = np.random.RandomState(0)
host = make_batch(split, rng.choice(len(split), BATCH, replace=True), cfg0)
model0 = FiraModel(cfg0, dtype=jnp.dtype(DTYPE))
params = init_state(model0, cfg0, host).params
dev = jax.device_put(host)
jax.block_until_ready(dev)

results = {}
VARIANTS = [
    ("kv_cached", dict(beam_kv_cache=True)),
    ("full_redecode", dict(beam_kv_cache=False)),
    # per-side top-k selection instead of the assembled 25,020-way fused
    # tensor (token-exact, pinned by tests)
    ("kv_factored_topk", dict(beam_kv_cache=True, beam_factored_topk=True)),
    # while_loop exit one settling step after all beams emit EOS
    # (bit-exact, tests/test_beam_early_exit.py). NOTE the synthetic bench
    # messages are 2-7 tokens vs tar_len 30, so this row's win is an upper
    # bound; real-corpus means are ~8-10 tokens and the win is set by the
    # batch's LONGEST message.
    ("kv_early_exit", dict(beam_kv_cache=True, beam_early_exit=True)),
    ("kv_factored_early_exit", dict(beam_kv_cache=True,
                                    beam_factored_topk=True,
                                    beam_early_exit=True)),
]
# Random-init params essentially never emit EOS, which makes the early-exit
# rows their own WORST case (steps_run == tar_len-1: pure while_loop
# overhead vs scan). The eos-biased paramset saturates beams almost
# immediately — the BEST case. Together the two rows bracket the lever;
# real corpora land in between, set by the batch's longest message.
from fira_tpu.decode.beam import eos_biased_params

params_eos = eos_biased_params(params)

for tag, over in VARIANTS:
    cfg = cfg0.replace(**over)
    model = FiraModel(cfg, dtype=jnp.dtype(DTYPE))
    early = cfg.beam_early_exit
    beam = make_beam_search(model, cfg, with_steps=early)
    paramsets = [("", params)] + ([("_saturated", params_eos)] if early else [])

    for suffix, ps in paramsets:
        # first call per paramset: compile on the first, executable-cache
        # hit on later ones — timed per row so compile_s is never stale
        t0 = time.perf_counter()
        out = beam(ps, dev)
        first = np.asarray(out[0])  # D2H materialization - honest sync
        compile_s = time.perf_counter() - t0
        steps_run = int(out[2]) if early else cfg.tar_len - 1

        for _ in range(N):  # saturation throwaway
            out = beam(ps, dev)
        _ = np.asarray(out[1])
        times = []
        for _w in range(3):
            t0 = time.perf_counter()
            for _ in range(N):
                out = beam(ps, dev)
            _ = np.asarray(out[1])  # scores depend on the full scan
            times.append(time.perf_counter() - t0)
        dt = sorted(times)[1] / N
        results[tag + suffix] = dt
        print(json.dumps({
            "tag": tag + suffix, "batch_ms": round(dt * 1e3, 2),
            "commits_per_sec": round(BATCH / dt, 1),
            "beam": cfg.beam_size, "tar_len": cfg.tar_len,
            "steps_run": steps_run,
            "compile_s": round(compile_s, 1),
        }), flush=True)

print(json.dumps({
    "tag": "speedup_kv_over_full",
    "value": round(results["full_redecode"] / results["kv_cached"], 2),
}), flush=True)


# --------------------------------------------------------------------------
# Slot-refill engine rows (decode/engine.py): continuous batching over a
# STREAM of batches, vs the batched early-exit path at equal geometry.
# Three paramset brackets:
#   engine            random params — no beam ever emits EOS, every slot
#                     runs the full budget: the engine's own worst case
#                     (pure per-step dispatch overhead vs the fused scan);
#   engine_saturated  hard EOS bias — slots settle almost immediately:
#                     refill-bound best case;
#   engine_mixed      moderate EOS bias (DECODE_ENGINE_EOS_DELTA) — per-
#                     sample settle depths SPREAD like a real corpus
#                     (mean ~8-10 tokens against the tar-1 budget at the
#                     default delta), the regime where the batch path pays
#                     the per-batch max and the engine pays the mean. Its
#                     twin row kv_early_exit_mixed runs the batched
#                     early-exit beam on the SAME stream and paramset;
#                     speedup_engine_over_early_exit_mixed is the ratio.
# Measurement protocol is mirrored by bench.py's FIRA_BENCH_DECODE_ENGINE
# leg — change the warm/reset/sync boundaries in BOTH places or the two
# reported speedups silently diverge.
# --------------------------------------------------------------------------
from fira_tpu.data.feeder import Feeder
from fira_tpu.decode import engine as engine_lib

# 6 batches (192 commits at batch 32) is the shortest stream where the
# end-of-stream drain (no refills left for the last slots standing) stops
# dominating engine slot_occupancy; a 4-batch stream understates the
# steady-state engine by ~15% on CPU.
ENGINE_BATCHES = int(os.environ.get("DECODE_ENGINE_BATCHES", "6"))
ENGINE_MIX_DELTA = float(os.environ.get("DECODE_ENGINE_EOS_DELTA", "4.75"))

cfg_eng = cfg0.replace(beam_kv_cache=True, beam_factored_topk=False)
model_eng = FiraModel(cfg_eng, dtype=jnp.dtype(DTYPE))
params_mixed = eos_biased_params(params, delta=ENGINE_MIX_DELTA)

rng_eng = np.random.RandomState(1)
stream_chunks = [rng_eng.choice(len(split), BATCH, replace=True)
                 for _ in range(ENGINE_BATCHES)]


def stream_tasks():
    for ix in stream_chunks:
        yield (lambda ix=ix: make_batch(split, ix, cfg_eng))


def drive_engine(eng):
    with Feeder(stream_tasks(), num_workers=2, depth=2) as feed:
        for _ in eng.run(feed):
            pass


def engine_row(tag, ps, *, model=None, cfg=None, driver=None, slots=None,
               pool_blocks=None):
    """One timed engine drain. ``driver``/``cfg``/``model`` default to the
    flat mixed stream above; the paged rows pass their own bucketed
    stream. kv_* / pool_* fields are the machine-recorded HBM accounting
    (decode/paging.py) every equal-memory claim rides on."""
    cfg = cfg or cfg_eng
    eng = engine_lib.SlotEngine(model or model_eng, ps, cfg, slots=slots,
                                pool_blocks=pool_blocks)
    drive = driver or drive_engine
    t0 = time.perf_counter()
    drive(eng)                             # compiles prefill/step/insert
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(2):
        eng.stats = engine_lib.EngineStats(slots=eng.slots)
        t0 = time.perf_counter()
        drive(eng)
        times.append(time.perf_counter() - t0)
    dt = min(times)
    st = eng.stats.summary()
    cps = st["commits"] / dt
    print(json.dumps({
        "tag": tag, "commits_per_sec": round(cps, 1),
        "batch": BATCH, "slots": st["slots"], "beam": cfg.beam_size,
        "tar_len": cfg.tar_len, "n_commits": st["commits"],
        "slot_occupancy": st["slot_occupancy"],
        "steps_run": st["steps_run"], "refills": st["refills"],
        "steps_per_commit": st["steps_per_commit"],
        "dispatches": st["dispatches"],
        "pool_blocks": st["pool_blocks"],
        "kv_block_size": st["kv_block_size"],
        "kv_bytes_per_slot": st["kv_bytes_per_slot"],
        "peak_blocks": st["peak_blocks"],
        "pool_utilization": st["pool_utilization"],
        "kv_dtype": st["kv_dtype"],
        "serve_precision": st["serve_precision"],
        "compile_s": round(compile_s, 1),
    }), flush=True)
    return cps, st


def batch_early_exit_row(tag, ps):
    # SAME input pipeline as the engine row (assembly + H2D through the
    # async Feeder, inside the timed window) — the speedup ratio must
    # compare decode strategies, not who pre-staged their batches
    cfgb = cfg_eng.replace(beam_early_exit=True)
    model_b = FiraModel(cfgb, dtype=jnp.dtype(DTYPE))
    beam_b = make_beam_search(model_b, cfgb, with_steps=True)
    warm = jax.device_put(make_batch(split, stream_chunks[0], cfgb))
    jax.block_until_ready(warm)
    out = beam_b(ps, warm)
    _ = np.asarray(out[0])                 # compile + honest sync
    times = []
    steps_total = 0
    for _w in range(2):
        steps_total = 0
        t0 = time.perf_counter()
        with Feeder(stream_tasks(), num_workers=2, depth=2) as feed:
            for item in feed:
                out = beam_b(ps, item.device)
                # per-batch D2H, the same harvest boundary run_test pays
                steps_total += int(out[2])
                _ = np.asarray(out[0])
        times.append(time.perf_counter() - t0)
    dt = min(times)
    n_commits = BATCH * len(stream_chunks)
    cps = n_commits / dt
    print(json.dumps({
        "tag": tag, "commits_per_sec": round(cps, 1),
        "batch": BATCH, "beam": cfgb.beam_size, "tar_len": cfgb.tar_len,
        "n_commits": n_commits, "steps_run": steps_total,
        "steps_per_commit": round(steps_total / n_commits, 3),
        "dispatches": len(stream_chunks),
    }), flush=True)
    return cps


v_batch_mixed = batch_early_exit_row("kv_early_exit_mixed", params_mixed)
v_engine_mixed, _ = engine_row("engine_mixed", params_mixed)
engine_row("engine", params)
engine_row("engine_saturated", params_eos)
print(json.dumps({
    "tag": "speedup_engine_over_early_exit_mixed",
    "value": round(v_engine_mixed / v_batch_mixed, 2),
}), flush=True)


# --------------------------------------------------------------------------
# Speculative draft-and-verify rows (decode/spec.py + docs/DECODE_ENGINE.md
# "Speculative drafting"): spec-on vs the plain engine twin at EQUAL
# geometry and harvest cadence 1 (so the comparison isolates speculation
# from cadence batching; the engine_mixed row above is the cadence-R plain
# context). Every spec row's tokens are asserted identical to its plain
# twin's INSIDE the bench — a speedup that costs output bytes is a bug,
# not a result. Rows:
#
#   spec_plain_r1            the cadence-1 plain twin (the denominator);
#   spec_draft_k{2,4,8}      greedy full-step drafter at k — the k sweep
#                            pins byte-invariance while steps_per_commit
#                            moves with acceptance;
#   spec_copy_k4             copy-head-only drafter on the SAME paramset —
#                            acceptance is machine-recorded, whatever the
#                            (random-init) copy head really achieves;
#   spec_copy_plain_twin /   the copy-biased target-blind regime
#   spec_copy_k4_saturated   (spec.copy_biased_params): drafter proxy
#                            scores == real step scores, acceptance
#                            saturates — the copy tier's deterministic
#                            best case. On a TRAINED model the copy tier
#                            rides FIRA's measured copy fraction instead.
#
# The spec_verdict row names the CPU caveat explicitly: CPU executes the
# verify's while-loop frames serially, so commits/s parity is expected
# here; the machine-recorded steps_per_commit / dispatch reduction is the
# claim, and wall-clock is not measured on the chip. DECODE_SPEC=0 skips
# the leg. Mirrored by
# bench.py's FIRA_BENCH_SPEC leg — keep the protocols in lockstep.
# --------------------------------------------------------------------------
if os.environ.get("DECODE_SPEC", "1") == "1":
    from fira_tpu.decode import spec as spec_lib

    cfg_spec0 = cfg_eng.replace(decode_engine=True, engine_harvest_every=1)

    def spec_row(tag, ps, cfg_leg, ref=None):
        model_leg = FiraModel(cfg_leg, dtype=jnp.dtype(DTYPE))
        eng = engine_lib.SlotEngine(model_leg, ps, cfg_leg)

        def drive(collect):
            out = {}
            with Feeder(stream_tasks(), num_workers=2, depth=2) as feed:
                for it in eng.run(feed):
                    if collect:
                        out[it.position] = np.asarray(it.tokens)
            return out

        t0 = time.perf_counter()
        toks = drive(True)                 # warm pass; tokens for the check
        compile_s = time.perf_counter() - t0
        if ref is not None:
            assert set(toks) == set(ref), tag
            for p in ref:
                np.testing.assert_array_equal(toks[p], ref[p], err_msg=tag)
        times = []
        for _ in range(2):
            eng.stats = engine_lib.EngineStats(slots=eng.slots)
            t0 = time.perf_counter()
            drive(False)
            times.append(time.perf_counter() - t0)
        dt = min(times)
        st = eng.stats.summary()
        cps = st["commits"] / dt
        print(json.dumps({
            "tag": tag, "commits_per_sec": round(cps, 1),
            "batch": BATCH, "slots": st["slots"], "beam": cfg_leg.beam_size,
            "tar_len": cfg_leg.tar_len, "n_commits": st["commits"],
            "spec_decode": cfg_leg.spec_decode,
            "spec_k": cfg_leg.engine_spec_k,
            "tokens_identical": ref is not None,
            "steps_run": st["steps_run"],
            "steps_per_commit": st["steps_per_commit"],
            "dispatches": st["dispatches"],
            "acceptance_rate": st["acceptance_rate"],
            "drafted": st["drafted"], "accepted": st["accepted"],
            "verify_dispatches": st["verify_dispatches"],
            "steps_saved": st["steps_saved"],
            "spec_frames": st["spec_frames"],
            "compile_s": round(compile_s, 1),
        }), flush=True)
        return cps, st, toks

    cps_plain, st_plain, ref_toks = spec_row("spec_plain_r1", params_mixed,
                                             cfg_spec0)
    spc_k = {}
    for k in (2, 4, 8):
        _, st_k, _ = spec_row(
            f"spec_draft_k{k}", params_mixed,
            cfg_spec0.replace(spec_decode="draft", engine_spec_k=k),
            ref=ref_toks)
        spc_k[k] = st_k["steps_per_commit"]
    spec_row("spec_copy_k4", params_mixed,
             cfg_spec0.replace(spec_decode="copy", engine_spec_k=4),
             ref=ref_toks)
    params_copy = spec_lib.copy_biased_params(params_mixed, delta=6.0,
                                              target_blind=True)
    _, st_ct, ref_copy = spec_row("spec_copy_plain_twin", params_copy,
                                  cfg_spec0)
    _, st_cs, _ = spec_row(
        "spec_copy_k4_saturated", params_copy,
        cfg_spec0.replace(spec_decode="copy", engine_spec_k=4),
        ref=ref_copy)
    print(json.dumps({
        "tag": "spec_verdict",
        "tokens_identical_all_rows": True,
        "steps_per_commit_plain_r1": st_plain["steps_per_commit"],
        "steps_per_commit_draft": {str(k): v for k, v in spc_k.items()},
        "steps_per_commit_copy_saturated": st_cs["steps_per_commit"],
        "steps_per_commit_copy_twin": st_ct["steps_per_commit"],
        "platform": jax.devices()[0].platform,
        "caveat": (
            "CPU executes the verify while-loop frames SERIALLY, so "
            "commits/s parity (not speedup) is expected on this backend; "
            "the machine-recorded steps_per_commit / dispatch reduction "
            "at recorded acceptance is the claim here; wall-clock is not "
            "measured on the chip"
            if jax.devices()[0].platform == "cpu" else ""),
    }), flush=True)


# --------------------------------------------------------------------------
# Paged KV arena rows (decode/paging.py +
# docs/DECODE_ENGINE.md "Paged KV arena"): the longer-target-geometry
# door. Raise tar_len to DECODE_PAGED_TAR (the PR-description budget the
# 30-position arena could never host) and declare the common case —
# DECODE_PAGED_TAR_SHORT — as a decode tar bucket: short messages reserve
# ceil(short/block) pool blocks, long ones the full budget, ONE step
# program serves both. Two rows make the HBM claim machine-recorded:
#
#   paged_tar<T>              full-residency pool: every slot can hold the
#                             full T-position budget (kv_bytes_per_slot
#                             is what a whole-sequence stripe would cost);
#                             pool_utilization shows the share mixed
#                             reservations actually map;
#   paged_tar<T>_2xslots      TWICE the slots against the SAME pool bytes
#                             (kv_bytes_per_slot
#                             halves) — the equal-memory slot-count gain,
#                             servable because the short bucket dominates
#                             real streams.
#
# DECODE_PAGED=0 skips the leg (it pays its own model init + compiles).
# --------------------------------------------------------------------------
if os.environ.get("DECODE_PAGED", "1") == "1":
    from fira_tpu.data import buckets as buckets_lib
    from fira_tpu.decode import paging

    PAGED_TAR = int(os.environ.get("DECODE_PAGED_TAR", "64"))
    PAGED_TAR_SHORT = int(os.environ.get("DECODE_PAGED_TAR_SHORT",
                                         str(PAGED_TAR // 2)))
    cfg_p0 = get_config(CONFIG).replace(
        batch_size=BATCH, test_batch_size=BATCH, compute_dtype=DTYPE,
        tar_len=PAGED_TAR, decode_tar_buckets=True,
        beam_kv_cache=True, beam_factored_topk=False)
    cfg_p0 = cfg_p0.replace(buckets=(
        (cfg_p0.ast_change_len, cfg_p0.max_edges, PAGED_TAR_SHORT),))
    cfg_p, split_p, _ = make_memory_split(cfg_p0, max(256, BATCH), seed=0,
                                          pad_vocab_to=pad_v,
                                          pad_ast_vocab_to=71 if pad_v else 0)
    model_p = FiraModel(cfg_p, dtype=jnp.dtype(DTYPE))
    host_p = make_batch(split_p, np.arange(min(BATCH, len(split_p))), cfg_p,
                        batch_size=BATCH)
    params_p = eos_biased_params(init_state(model_p, cfg_p, host_p).params,
                                 delta=ENGINE_MIX_DELTA)

    table_p = buckets_lib.decode_table(cfg_p)
    plan_p = buckets_lib.packed_plan(split_p, cfg_p, batch_size=BATCH,
                                     table=table_p, use_msg=True)

    def drive_paged(eng):
        tasks = buckets_lib.bucketed_assembly_tasks(split_p, plan_p, cfg_p,
                                                    batch_size=BATCH)
        with Feeder(tasks, num_workers=2, depth=2) as feed:
            for _ in eng.run(feed):
                pass

    bs_p = paging.resolve_block_size(cfg_p)
    w_long = paging.blocks_per_seq(PAGED_TAR, bs_p)
    n_short = sum(len(ix) for ix, g in plan_p if g.tar_len == PAGED_TAR_SHORT)
    print(json.dumps({
        "tag": "paged_stream", "tar": PAGED_TAR,
        "tar_short": PAGED_TAR_SHORT, "block_size": bs_p,
        "n_commits": len(split_p), "n_short_bucket": n_short,
        "n_batches": len(plan_p),
    }), flush=True)
    _, st_full = engine_row(f"paged_tar{PAGED_TAR}", params_p,
                            model=model_p, cfg=cfg_p, driver=drive_paged)
    # SAME pool bytes as the full-residency row (BATCH x W_long blocks),
    # twice the slots: kv_bytes_per_slot halves at equal total HBM
    _, st_2x = engine_row(
        f"paged_tar{PAGED_TAR}_2xslots", params_p, model=model_p, cfg=cfg_p,
        driver=drive_paged, slots=2 * BATCH, pool_blocks=BATCH * w_long)
    print(json.dumps({
        "tag": "paged_equal_hbm_slot_gain",
        "slots": f"{st_full['slots']} -> {st_2x['slots']}",
        "kv_bytes_per_slot": f"{st_full['kv_bytes_per_slot']} -> "
                             f"{st_2x['kv_bytes_per_slot']}",
        "value": round(st_2x["slots"] / st_full["slots"], 2),
    }), flush=True)

    # ----------------------------------------------------------------------
    # Low-precision serving tiers (cfg.kv_dtype / cfg.serve_precision;
    # decode/quant.py + docs/DECODE_ENGINE.md "Low-precision tiers"),
    # riding the paged stream above. The bf16 arena halves the per-
    # position KV bytes, so the equal-HBM slot-count gain DOUBLES: the
    # 4xslots row serves four times the full-residency f32 slots against
    # the SAME pool bytes (2 x BATCH x W_long bf16 blocks == BATCH x W_long
    # f32 blocks). The int8w row keeps the f32 arena and swaps the
    # decode weight tier — throughput at unchanged KV accounting.
    # Quality vs f32 is measured by serve_bench.py --quant
    # (docs/QUANT_BENCH_r01.jsonl), not here. DECODE_QUANT=0 skips.
    # ----------------------------------------------------------------------
    if os.environ.get("DECODE_QUANT", "1") == "1":
        cfg_bf = cfg_p.replace(kv_dtype="bf16")
        engine_row(f"bf16kv_tar{PAGED_TAR}", params_p, model=model_p,
                   cfg=cfg_bf, driver=drive_paged)
        _, st_bf4x = engine_row(
            f"bf16kv_tar{PAGED_TAR}_4xslots", params_p, model=model_p,
            cfg=cfg_bf, driver=drive_paged, slots=4 * BATCH,
            pool_blocks=2 * BATCH * w_long)
        print(json.dumps({
            "tag": "paged_equal_hbm_slot_gain",
            "kv_dtype": "bf16",
            "slots": f"{st_full['slots']} -> {st_bf4x['slots']}",
            "kv_bytes_per_slot": f"{st_full['kv_bytes_per_slot']} -> "
                                 f"{st_bf4x['kv_bytes_per_slot']}",
            "value": round(st_bf4x["slots"] / st_full["slots"], 2),
        }), flush=True)
        engine_row(f"int8w_tar{PAGED_TAR}", params_p, model=model_p,
                   cfg=cfg_p.replace(serve_precision="int8w"),
                   driver=drive_paged)
