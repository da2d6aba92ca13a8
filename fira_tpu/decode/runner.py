"""Test-split decoding driver (the reference's `test()`,
/root/reference/run_model.py:187-380): decode every sample, pick the
argmax-probability beam, cook text, score in-loop sentence BLEU, and write
one prediction per line to OUTPUT/output_fira (ablations write their own
suffixed files, matching OUTPUT/output_fira_{no_edit,no_subtoken,nothing}).

Two decode paths, selected by ``cfg.decode_engine`` (CLI ``--engine``;
token-exact per sample — docs/DECODE_ENGINE.md):

- **batched beam** (default): one beam program dispatch per packed batch,
  in the form ``beam_kv_cache`` / ``beam_factored_topk`` choose; with
  ``beam_early_exit`` the dispatch still runs until the batch's LONGEST
  message settles.
- **slot-refill engine** (decode/engine.py): S static slots advanced one
  token per step, settled slots harvested and refilled mid-flight from
  the same packer stream — wall clock scales with total tokens emitted.
  ONE form (cached, paged, selecting from the factors), whatever the
  batched beam's knobs say.
  With ``cfg.engine_replicas > 1`` the engine becomes a replicated FLEET
  (parallel/fleet.py): N engines on N devices pull from one shared
  admission queue; decoded file bytes are invariant to the replica count.

Both paths stream through the ordered writer (decode/stream.py): the
contiguous split-order prefix is on disk the moment it completes, a crash
leaves a parseable prefix, and completion atomically renames
``.partial`` to the final file.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

import jax
import numpy as np

from fira_tpu.analysis.sanitizer import program_label
from fira_tpu.config import FiraConfig
from fira_tpu.data import buckets as buckets_lib
from fira_tpu.data.batching import epoch_index_chunks
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.feeder import Feeder, assembly_tasks
from fira_tpu.decode import engine as engine_lib
from fira_tpu.decode.beam import make_beam_search
from fira_tpu.decode.stream import OrderedStreamWriter
from fira_tpu.decode.text import cook_prediction, deanonymize, reference_words
from fira_tpu.eval.dev_bleu import nltk_sentence_bleu
from fira_tpu.model.model import FiraModel


def output_name(ablation: Optional[str]) -> str:
    """OUTPUT file naming per paper ablation (BASELINE.md rows)."""
    if ablation in (None, "", "none", "full"):
        return "output_fira"
    return f"output_fira_{ablation}"


def sample_emitter(writer, *, vocab, cfg: FiraConfig, bleu_by_pos: Dict,
                   n_total: int, var_maps=None, indices=None):
    """The per-sample tail every decode driver shares (batched beam, slot
    engine, fleet, and the serving loop — serve/server.py): pick the
    argmax beam, cook text, score BLEU, de-anonymize, write at the
    sample's split position."""

    def emit(pos, host, row, tokens, probs):
        best = int(np.argmax(probs))             # run_model.py:351
        ids = tokens[best].tolist()
        # beam output ids are already copy-resolved at extension time
        hyp = cook_prediction(ids[1:], host["diff"][row],
                              host["sub_token"][row], vocab, cfg,
                              resolve=False)
        ref = reference_words(host["msg"][row], vocab)
        # keyed by position, summed in split order at the end: samples
        # settle in scheduler order (engine/fleet/serve), and float
        # addition in settle order would make the aggregate depend on
        # replica count / refill interleaving in the last ulp
        bleu_by_pos[pos] = nltk_sentence_bleu([ref], hyp)
        n = len(bleu_by_pos)
        var_map = (var_maps[indices[pos]]
                   if var_maps is not None else None)
        writer.add(pos, " ".join(deanonymize(hyp, var_map)) + "\n")
        if n % 1000 == 0:
            writer.flush()
            print(f"decode: {n}/{n_total}", flush=True)

    return emit


def _decode_tasks(data, cfg: FiraConfig):
    """The packed decode stream: (tasks, decode bucket table or None).
    Shared by both decode paths — the engine prefills EXACTLY the batches
    the batched beam would dispatch."""
    stamp = None
    if cfg.prefix_cache:
        # content digests computed worker-side with the rest of assembly
        # (bucketed and unbucketed streams alike — the engine's on-demand
        # fallback exists only for streams that bypass these task
        # builders); the digest carries the serving tier's namespace so a
        # cached f32 artifact never seats a bf16 slot (decode/quant.py)
        import functools

        from fira_tpu.decode import quant
        from fira_tpu.decode.prefix_cache import stamp_digests
        stamp = functools.partial(stamp_digests,
                                  namespace=quant.tier_namespace(cfg))
    if cfg.buckets:
        table = buckets_lib.decode_table(cfg)
        # tar-bucketed decode assigns by reference-message extent (the
        # bucket's tar is a generation budget, so a sample must FIT its
        # bucket); the tar-pinned default ignores msg, as before
        plan = buckets_lib.packed_plan(data, cfg,
                                       batch_size=cfg.test_batch_size,
                                       table=table,
                                       use_msg=cfg.decode_tar_buckets)
        tasks = buckets_lib.bucketed_assembly_tasks(
            data, plan, cfg, batch_size=cfg.test_batch_size, stamp=stamp)
        return tasks, table
    chunks = epoch_index_chunks(len(data), cfg,
                                batch_size=cfg.test_batch_size)
    return assembly_tasks(data, chunks, cfg,
                          batch_size=cfg.test_batch_size,
                          stamp=stamp), None


def run_test(model: FiraModel, params, dataset: FiraDataset,
             cfg: Optional[FiraConfig] = None, *,
             out_dir: str = "OUTPUT",
             ablation: Optional[str] = None,
             var_maps: Optional[List[Dict[str, str]]] = None,
             split: str = "test",
             guard=None,
             engine_slots: Optional[int] = None,
             refill_order: str = "fifo",
             faults=None) -> Dict[str, float]:
    """``guard``: an armed analysis.sanitizer.CompileGuard — each decode
    program must compile exactly once (warmup), then never again. The CLI
    arms it via ``--sanitize``; library callers use the
    sanitizer.sanitize() context manager so global config is restored.
    ``engine_slots``/``refill_order`` apply to the engine path only (the
    latter exists so the determinism tests can pin refill-order
    independence).

    ``faults``: an armed robust.faults.FaultInjector (None resolves from
    ``cfg.inject_faults``; "" keeps it off at zero overhead). Drain mode
    degrades like a batch job should: transient assembly faults are
    absorbed by the feeder's ``cfg.robust_retries`` retry budget, a fleet
    replica whose dispatch raises or blows ``cfg.dispatch_watchdog_s``
    retires with its requests requeued onto survivors (parallel/fleet.py)
    — and a fault nothing can absorb fails LOUDLY with the sample named
    in the traceback, never silently truncating the output file."""
    cfg = cfg or dataset.cfg
    if faults is None:
        from fira_tpu.robust import faults as faults_lib

        faults = faults_lib.injector_from(cfg)
    data = dataset.splits[split]
    vocab = dataset.word_vocab
    indices = dataset.split_indices[split]
    tasks, table = _decode_tasks(data, cfg)

    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, output_name(ablation))
    bleu_by_pos: Dict[int, float] = {}
    n_total = len(data)
    engine_stats = None

    def make_emit(writer):
        return sample_emitter(writer, vocab=vocab, cfg=cfg,
                              bleu_by_pos=bleu_by_pos, n_total=n_total,
                              var_maps=var_maps, indices=indices)

    if cfg.decode_engine:
        n_rep = max(1, int(cfg.engine_replicas))
        if n_rep > 1:
            from fira_tpu.parallel import fleet as fleet_lib

            eng = fleet_lib.EngineFleet(model, params, cfg, replicas=n_rep,
                                        slots=engine_slots, guard=guard,
                                        faults=faults)
        else:
            eng = engine_lib.SlotEngine(model, params, cfg,
                                        slots=engine_slots, guard=guard,
                                        faults=faults)
        if table is not None:
            if guard is not None:
                # single engine: the classic (geometry x {prefill, step,
                # insert}) family; fleet: the union over replicas, each
                # label suffixed r<i> (per-device executables are real
                # per-replica compiles)
                guard.declare(eng.labels(table))
            eng.prewarm(
                (buckets_lib.warmup_batch(data, cfg, g, cfg.test_batch_size),
                 buckets_lib.geom_tag(g)) for g in table)
            print(f"decode buckets: {len(table)} engine prefill programs "
                  f"pre-warmed"
                  f"{f' x {n_rep} replicas' if n_rep > 1 else ''} "
                  f"({', '.join(buckets_lib.geom_tag(g) for g in table)})",
                  flush=True)
        else:
            # unbucketed: pre-warm the single-geometry engine family
            # (prefill + no-op insert/step) so the
            # dispatch watchdog never reads a first-use XLA compile as a
            # hung replica (docs/FAULTS.md)
            from fira_tpu.data.batching import make_batch

            warm = make_batch(data, np.arange(0), cfg,
                              batch_size=cfg.test_batch_size)
            eng.prewarm([(warm, None)])
        # the Feeder is constructed INSIDE the with (after the writer's
        # open succeeds): a failing open must not leak worker threads.
        # The fleet's feeder skips the device_put (put=False): which
        # replica a chunk lands on is a scheduling decision, so the
        # transfer happens at admission, onto the claiming replica's chip.
        with OrderedStreamWriter(out_path, expected=n_total) as writer, \
                Feeder(tasks, num_workers=cfg.feeder_workers,
                       depth=cfg.feeder_depth, put=n_rep == 1,
                       retries=max(0, cfg.robust_retries),
                       faults=faults) as feed:
            emit = make_emit(writer)
            for item in eng.run(feed, refill_order=refill_order):
                emit(item.position, item.host, item.row, item.tokens,
                     item.probs)
        engine_stats = eng.stats.summary()
    else:
        beam = make_beam_search(model, cfg)
        # Bucketed decode (data/buckets.py): each bucket's beam program is
        # pre-warmed with an all-pad batch, then the guard learns the
        # closed family.
        if table is not None:
            if guard is not None:
                guard.declare(program_label("beam_search",
                                            buckets_lib.geom_tag(g))
                              for g in table)
            for g in table:
                beam(params, buckets_lib.warmup_batch(data, cfg, g,
                                                      cfg.test_batch_size))
                if guard is not None:
                    guard.step(program_label("beam_search",
                                             buckets_lib.geom_tag(g)))
            print(f"decode buckets: {len(table)} beam programs pre-warmed "
                  f"({', '.join(buckets_lib.geom_tag(g) for g in table)})",
                  flush=True)
        cursor = 0
        with OrderedStreamWriter(out_path, expected=n_total) as writer, \
                Feeder(tasks, num_workers=cfg.feeder_workers,
                       depth=cfg.feeder_depth) as feed:
            emit = make_emit(writer)
            for item in feed:
                batch = item.host  # numpy fields for host-side text cooking
                tokens, probs = beam(params, item.device)
                # firacheck: allow[HOST-SYNC] per-batch output collection IS the decode boundary: beams must reach the host to be cooked into text
                tokens = np.asarray(jax.device_get(tokens))
                probs = np.asarray(jax.device_get(probs))  # firacheck: allow[HOST-SYNC] same decode output boundary as the line above
                positions = batch.get("_positions")  # bucketed stream only
                if guard is not None:
                    guard.step(program_label("beam_search",
                                             batch.get("_tag")))
                valid = batch["valid"]  # host-side numpy field, no sync
                for i in range(tokens.shape[0]):
                    if not valid[i]:
                        continue
                    pos = cursor if positions is None else int(positions[i])  # firacheck: allow[HOST-SYNC] _positions is a host-only numpy field (feeder strips it from the wire); no device value exists here
                    emit(pos, batch, i, tokens[i], probs[i])
                    cursor += 1
    n = len(bleu_by_pos)
    total_bleu = sum(bleu_by_pos[p] for p in sorted(bleu_by_pos))
    out: Dict[str, float] = {
        "sentence_bleu": total_bleu / max(n, 1), "n": float(n),
        "output_path": out_path}  # type: ignore[assignment]
    if engine_stats is not None:
        out["engine"] = engine_stats  # type: ignore[assignment]
    return out  # type: ignore[return-value]


def run_lm_test(cfg: FiraConfig, *, out_dir: str = "OUTPUT",
                n_requests: int = 64, guard=None, params=None,
                requests=None) -> Dict:
    """``cli test --engine`` for a decoder-only architecture (any ``arch``
    of config.ARCH_TABLE with a key block): the SAME slot engine, over
    token-id prompts from data/synthetic.py (no tokenizer ships, so the output file holds
    ids: one line a request, its most probable beam after <start>) and
    weights drawn from ``cfg.seed`` unless ``params`` are given.
    ``requests``: (prompts, max_new) to use instead of the synthetic draw."""
    import importlib

    import jax.numpy as jnp

    from fira_tpu.config import ARCH_TABLE
    from fira_tpu.data.synthetic import make_prompt_requests

    lm = cfg.lm
    if params is None:
        model = importlib.import_module(ARCH_TABLE[cfg.arch].model)
        params = model.init_params(lm, cfg.seed,
                                   jnp.dtype(cfg.compute_dtype))
    if requests is None:
        T = cfg.tar_len
        requests = make_prompt_requests(
            n_requests, vocab_size=lm.vocab_size, seed=cfg.seed,
            min_len=max(1, lm.prompt_buckets[0] // 2),
            max_len=lm.prompt_len_max,
            limits=tuple(max(1, (T - 1) * q // 4) for q in (1, 2, 3, 4)))
    prompts, max_new = requests[0], [int(m) for m in requests[1]]
    eng = engine_lib.SlotEngine(None, params, cfg, guard=guard)
    warm = buckets_lib.prompt_warm_batches(lm)
    if guard is not None:
        guard.declare(eng.labels_for_tags([t for _b, t in warm]))
    eng.prewarm(warm)
    print(f"prompt buckets: {len(warm)} engine prefill programs pre-warmed "
          f"({', '.join(t for _b, t in warm)})", flush=True)
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, "output_" + cfg.arch)
    tasks = buckets_lib.prompt_tasks(
        lm, ((i, p, m) for i, (p, m) in enumerate(zip(prompts, max_new))))
    with OrderedStreamWriter(out_path, expected=len(prompts)) as writer, \
            Feeder(tasks, num_workers=cfg.feeder_workers,
                   depth=cfg.feeder_depth) as feed:
        for item in eng.run(feed):
            best = item.tokens[int(np.argmax(item.probs))]
            n = max_new[item.position]
            writer.add(item.position,
                       " ".join(map(str, best[1:n + 1].tolist())) + "\n")
    return {"n": float(len(prompts)), "output_path": out_path,
            "engine": eng.stats.summary()}
