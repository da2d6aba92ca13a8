"""Online per-request ingest pipeline: raw diff -> wire payload -> served
message (docs/INGEST.md).

Each request runs the WHOLE preprocessing stack the corpus went through
offline, per request, inside the existing async Feeder worker pool:

    raw diff text
      -> difftext.parse_request          (lex:      file/hunk structure +
                                          Java lexing, mark streams)
      -> fsm.split_hunks + extract_commit (parse:    hunk FSM, AST parse/
                                          diff, graph extraction — native
                                          astdiff, loaded once per process)
      -> process_record + make_batch     (assemble: frozen-vocab encode,
                                          copy labels, COO adjacency, the
                                          exact single-row wire payload the
                                          corpus path ships)

EQUIVALENCE CONTRACT: a corpus commit's reconstructed diff
(difftext.reconstruct_request) pushed through :func:`ingest_request`
yields a wire payload BYTE-IDENTICAL to ``make_batch`` over the frozen
corpus row — and therefore byte-identical served output — provided the
corpus' graph streams came from the same extraction
(data.synthetic.write_extracted_corpus_dir builds exactly such corpora;
tests/test_ingest.py and the check.sh ingest smoke pin it end to end).

DEGRADATION CONTRACT, in order of severity:
- unknown word tokens encode to <unkm> and unknown AST/change labels to
  <pad> (counted per request, never a crash — the corpus path's frozen
  vocabs cover the corpus by construction; arbitrary diffs don't);
- an extraction failure degrades the request to a code-tokens-only graph
  (the pipeline's per-commit degradation, recorded per request);
- an over-budget diff is deterministically TRUNCATED to the config
  geometry (``cfg.ingest_truncate = "clip"``, recorded per request) or
  rejected with a recorded error (``"shed"``) — never a mid-loop
  admissibility backstop in ``make_batch``;
- malformed diff text (difftext.DiffParseError) rides the feeder's
  per-task error channel into the serving loop's poison-request
  quarantine: recorded shed + empty output line, never a dead loop. The
  ``ingest.parse`` fault site (robust/faults.py) injects exactly this
  class of failure deterministically.

Payloads are digest-stamped WORKER-side (decode/prefix_cache.py) when
``cfg.prefix_cache`` is armed, so byte-identical repeated diffs hit the
cross-request prefix cache and in-flight dedup unchanged.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from fira_tpu.config import FiraConfig
from fira_tpu.data.schema import CommitRecord
from fira_tpu.data.vocab import PAD_ID, UNK_TOKEN, Vocab, normalize_token
from fira_tpu.ingest.cache import (EXEC_MODES, HunkMemo, IngestCache,
                                   IngestExecutor, LexMemo, text_digest)
from fira_tpu.ingest.difftext import DiffRequest, parse_request
from fira_tpu.preprocess.fsm import NB, NL, split_hunks
from fira_tpu.preprocess.pipeline import split_sub_tokens

TRUNCATE_MODES = ("clip", "shed")


class IngestError(ValueError):
    """A request the ingest pipeline rejects by POLICY (over-budget under
    ``ingest_truncate = "shed"``, empty after truncation): quarantined
    like a parse failure — recorded shed, never a crash."""


# --------------------------------------------------------------------------
# parse-time knob validation (CLI exit 2 — the ingest twin of
# serve.server.serve_errors / decode.paging.paging_errors)
# --------------------------------------------------------------------------

def ingest_errors(cfg: FiraConfig, *, input_mode: str = "graphs",
                  diff_trace: Optional[str] = None,
                  command: str = "serve") -> List[str]:
    """Named-knob ingest admission check (docs/INGEST.md knob table)."""
    errs: List[str] = []
    if cfg.ingest_workers < 0:
        errs.append(
            f"ingest_workers {cfg.ingest_workers} must be >= 0 assembly "
            f"workers (0 = reuse feeder_workers for ingest request tasks)")
    if cfg.ingest_truncate not in TRUNCATE_MODES:
        errs.append(
            f"ingest_truncate {cfg.ingest_truncate!r} must be one of "
            f"{'/'.join(TRUNCATE_MODES)}: 'clip' deterministically "
            f"truncates an over-budget diff to the config geometry "
            f"(recorded per request), 'shed' rejects it with a recorded "
            f"error")
    if cfg.ingest_cache_entries < 0:
        errs.append(
            f"ingest_cache_entries {cfg.ingest_cache_entries} must be "
            f">= 0 cached whole-diff payloads (0 = unbounded entry "
            f"count; the LRU of the ingest result cache)")
    if cfg.ingest_cache_bytes < 0:
        errs.append(
            f"ingest_cache_bytes {cfg.ingest_cache_bytes} must be >= 0 "
            f"(0 = unbounded; otherwise the whole-diff result cache "
            f"evicts LRU-first until its payload bytes fit)")
    if cfg.ingest_exec not in EXEC_MODES:
        errs.append(
            f"ingest_exec {cfg.ingest_exec!r} must be one of "
            f"{'/'.join(EXEC_MODES)}: 'thread' runs the AST parse stage "
            f"inline on the feeder workers, 'process' ships it to a "
            f"spawned process pool (the GIL-bound stage's scaling mode)")
    if command != "serve":
        return errs
    if input_mode not in ("graphs", "diffs"):
        errs.append(f"--input {input_mode!r} must be 'graphs' (corpus "
                    f"split requests) or 'diffs' (raw-diff requests)")
    if input_mode == "diffs":
        if not diff_trace:
            errs.append(
                "--input diffs needs --diff-trace PATH: a file of "
                "'#! request'-separated unified diffs, or a directory of "
                ".diff files (docs/INGEST.md)")
        elif not os.path.exists(diff_trace):
            errs.append(f"--diff-trace {diff_trace}: path does not exist")
        else:
            # actually load the trace at parse time: an empty file, an
            # unreadable one, or a directory with no .diff files must be
            # exit 2 here — not a raw traceback after the checkpoint
            # loads (request texts are small; reading twice is cheap)
            from fira_tpu.ingest.difftext import read_diff_trace

            try:
                read_diff_trace(diff_trace)
            except (OSError, ValueError) as e:
                errs.append(f"--diff-trace {diff_trace}: {e}")
    elif diff_trace:
        errs.append("--diff-trace only applies with --input diffs "
                    "(--input graphs serves the corpus test split)")
    return errs


# --------------------------------------------------------------------------
# lenient frozen-vocab encoding (OOV -> UNK / PAD, never a crash)
# --------------------------------------------------------------------------

class _LenientVocab(Vocab):
    """View over a frozen vocab whose conversion NEVER raises: unknown
    tokens fall back to <unkm> when the vocab has one (the word vocab),
    else to <pad> (the ast/change vocab, which the corpus covers by
    construction but an arbitrary diff's AST need not). Fallbacks are
    counted — the per-request OOV record. Identical ids to the strict
    vocab whenever every token is known, which is what keeps the
    round-trip contract byte-exact."""

    def __init__(self, base: Vocab):
        self.token_to_id = base.token_to_id
        self.id_to_token = base.id_to_token
        self.unk_fallbacks = 0   # unknown -> <unkm> (the word vocab)
        self.pad_fallbacks = 0   # unknown -> <pad>  (the ast/change vocab)

    def convert_tokens_to_ids(self, tokens) -> List[int]:
        out = []
        for t in tokens:
            t = normalize_token(t)
            if t in self.token_to_id:
                out.append(self.token_to_id[t])
            elif UNK_TOKEN in self.token_to_id:
                self.unk_fallbacks += 1
                out.append(self.token_to_id[UNK_TOKEN])
            else:
                self.pad_fallbacks += 1
                out.append(PAD_ID)
        return out


# --------------------------------------------------------------------------
# per-request record construction (FSM + extraction + truncation policy)
# --------------------------------------------------------------------------

def _truncate_tokens(tokens: List[str], marks: List[int], budget: int
                     ) -> Tuple[List[str], List[int], int]:
    """Clip the streams to ``budget`` tokens at a chunk-safe boundary: a
    cut landing inside an open ``<nb>`` block backs off to before the
    ``<nb>`` (a half-open header block would fail the FSM)."""
    cut = budget
    for j in range(cut - 1, -1, -1):
        if tokens[j] == NL:
            break
        if tokens[j] == NB:
            cut = j
            break
    return tokens[:cut], marks[:cut], len(tokens) - cut


def _clip_sub_tokens(tokens: List[str], atts: List[List[str]],
                     budget: int) -> Tuple[List[List[str]], int]:
    """Drop whole tokens' sub-token lists (every occurrence — the dedup
    invariant requires a repeated token to keep ONE att list) so the
    deduplicated sub-token node count fits ``budget``."""
    kept: set = set()
    used = 0
    dropped: Dict[str, int] = {}   # unique token -> its sub-token count
    for tok, att in zip(tokens, atts):
        if not att or tok in kept or tok in dropped:
            continue
        if used + len(att) > budget:
            dropped[tok] = len(att)
        else:
            kept.add(tok)
            used += len(att)
    if not dropped:
        return atts, 0
    out = [[] if (tok in dropped and att) else att
           for tok, att in zip(tokens, atts)]
    # count dropped NODES (the dedup'd unit the budget is in), not
    # occurrences — a token repeated k times still owned one node set
    return out, sum(dropped.values())


def ingest_record(req: DiffRequest, cfg: FiraConfig, *,
                  truncate: Optional[str] = None,
                  commit_index: Optional[int] = None,
                  memo: Optional[HunkMemo] = None
                  ) -> Tuple[CommitRecord, Dict]:
    """Parsed request -> :class:`CommitRecord` + per-request info dict
    (``truncated``: what the deterministic clip dropped, or None;
    ``degraded``: the extraction error the request degraded on, or
    None). Mirrors the offline pipeline exactly for requests that FIT
    the config geometry — the round-trip contract's precondition.

    ``memo``: optional hunk-level AST memo (``ingest.cache.HunkMemo``)
    — per-chunk extraction reuses cached results across near-identical
    requests, bit-exact by purity (the rebase/merge still runs here)."""
    from fira_tpu.preprocess import extract

    truncate = truncate or cfg.ingest_truncate
    if truncate not in TRUNCATE_MODES:
        raise ValueError(f"truncate {truncate!r} not in {TRUNCATE_MODES}")
    info: Dict = {"truncated": None, "degraded": None}

    def record_trunc(key: str, n: int) -> None:
        if n:
            info["truncated"] = dict(info["truncated"] or {}, **{key: n})

    tokens, marks = list(req.tokens), list(req.marks)
    budget = cfg.sou_len - 2  # <start>/<eos> take two positions
    if len(tokens) > budget:
        if truncate == "shed":
            raise IngestError(
                f"diff has {len(tokens)} tokens > sou budget {budget} "
                f"(ingest_truncate=shed)")
        tokens, marks, dropped = _truncate_tokens(tokens, marks, budget)
        if not tokens:
            raise IngestError(
                "diff empty after truncation to the sou budget (a single "
                "header block larger than sou_len)")
        record_trunc("diff_tokens_dropped", dropped)

    atts = [split_sub_tokens(t) for t in tokens]
    atts, sub_dropped = _clip_sub_tokens(tokens, atts, cfg.sub_token_len)
    if sub_dropped:
        if truncate == "shed":
            raise IngestError(
                f"diff needs {sub_dropped} sub-token nodes beyond "
                f"sub_token_len {cfg.sub_token_len} (ingest_truncate=shed)")
        record_trunc("sub_tokens_dropped", sub_dropped)

    try:
        chunks, types = split_hunks(tokens, marks)
        g = extract.extract_commit(chunks, types, tokens,
                                   commit_index=commit_index, memo=memo)
        ast, change = list(g.ast), list(g.change)
        edge_ast = list(g.edge_ast)
        edge_ast_code = list(g.edge_ast_code)
        edge_change_ast = list(g.edge_change_ast)
        edge_change_code = list(g.edge_change_code)
    except Exception as exc:
        # the pipeline's per-commit degradation (preprocess/pipeline.py):
        # the request keeps its code tokens, the graph goes empty
        info["degraded"] = f"{type(exc).__name__}: {exc}"
        ast, change = [], []
        edge_ast, edge_ast_code = [], []
        edge_change_ast, edge_change_code = [], []

    node_budget = cfg.ast_change_len
    if len(ast) + len(change) > node_budget:
        if truncate == "shed":
            raise IngestError(
                f"diff has {len(ast)} AST + {len(change)} change nodes > "
                f"ast_change_len {node_budget} (ingest_truncate=shed)")
        keep_ast = min(len(ast), node_budget)
        keep_change = node_budget - keep_ast
        record_trunc("ast_nodes_dropped", len(ast) - keep_ast)
        record_trunc("change_nodes_dropped", len(change) - keep_change)
        ast, change = ast[:keep_ast], change[:keep_change]
        edge_ast = [(a, b) for a, b in edge_ast
                    if a < keep_ast and b < keep_ast]
        edge_ast_code = [(a, j) for a, j in edge_ast_code if a < keep_ast]
        edge_change_ast = [(c, a) for c, a in edge_change_ast
                           if c < keep_change and a < keep_ast]
        edge_change_code = [(c, j) for c, j in edge_change_code
                            if c < keep_change]

    record = CommitRecord(
        diff_tokens=tokens, diff_marks=marks, diff_atts=atts,
        msg_tokens=list(req.msg_tokens), var_map=dict(req.var_map),
        ast_labels=ast, change_labels=change,
        edge_ast=edge_ast, edge_ast_code=edge_ast_code,
        edge_change_ast=edge_change_ast,
        edge_change_code=edge_change_code)
    return record, info


# --------------------------------------------------------------------------
# record -> wire payload
# --------------------------------------------------------------------------

def _clip_edges(ex, cfg: FiraConfig) -> Tuple[object, int]:
    """Fit an example's ragged COO under ``cfg.max_edges``: drop TRAILING
    family edges (self-loops — the last ``graph_len`` entries, which the
    bucketed ``make_batch`` drop logic depends on — stay whole)."""
    n = int(ex.senders.shape[0])  # firacheck: allow[HOST-SYNC] Example arrays are host numpy (data/dataset.process_record output); shape arithmetic is pure host planning
    if n <= cfg.max_edges:
        return ex, 0
    fam = n - cfg.graph_len
    keep_fam = cfg.max_edges - cfg.graph_len
    sel = np.r_[0:keep_fam, fam:n]
    return dataclasses.replace(
        ex, senders=ex.senders[sel], receivers=ex.receivers[sel],
        values=ex.values[sel], kinds=ex.kinds[sel]), fam - keep_fam


def ingest_request(text: str, word_vocab: Vocab, ast_change_vocab: Vocab,
                   cfg: FiraConfig, *, table=None,
                   truncate: Optional[str] = None,
                   batch_size: int = 1,
                   lex=None,
                   executor: Optional[IngestExecutor] = None) -> Dict:
    """One raw request -> its single-row wire payload (the exact
    ``make_batch(batch_size=1)`` dict the corpus serve path assembles),
    plus the host-only metadata the serving loop reads:

    - ``_bucket``   smallest admissible decode bucket by the request's
                    MEASURED extents (0 when unbucketed);
    - ``_var``      the request's anonymization map (output
                    de-anonymization), one entry per row;
    - ``_ingest``   lifecycle stamps: per-stage seconds
                    (``lex_s``/``parse_s``/``assemble_s``), token count,
                    the truncation record, the degradation reason, and
                    the OOV fallback counts (``oov_words``: diff/msg
                    tokens encoded to <unkm>; ``oov_ast``: AST/change
                    labels encoded to <pad>).

    ``batch_size``: rows of the assembled batch (request row 0, the rest
    pad) — 1 for the serving loop's single-row payloads, the beam batch
    width for the one-shot ``cli message`` path.

    ``lex``/``executor``: the ingest fast-path hooks (ingest/cache.py,
    docs/INGEST.md "Fast path") — the persistent lexer memo for the lex
    stage and the parse-stage executor (thread-inline with the hunk
    memo, or the spawned process pool). None (default) runs the
    pristine pipeline; outputs are bit-exact either way.
    """
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.dataset import ProcessedSplit, process_record

    t0 = time.perf_counter()
    req = parse_request(text, lex=lex)
    t1 = time.perf_counter()
    memo_hits = memo_misses = 0
    if executor is not None:
        record, info, memo_hits, memo_misses = executor.parse(
            req, cfg, truncate or cfg.ingest_truncate)
    else:
        record, info = ingest_record(req, cfg, truncate=truncate)
    t2 = time.perf_counter()

    words = _LenientVocab(word_vocab)
    asts = _LenientVocab(ast_change_vocab)
    ex = process_record(record, words, asts, cfg)
    ex, edges_dropped = _clip_edges(ex, cfg)
    if edges_dropped:
        if (truncate or cfg.ingest_truncate) == "shed":
            raise IngestError(
                f"diff has {edges_dropped} edges beyond max_edges "
                f"{cfg.max_edges} (ingest_truncate=shed)")
        info["truncated"] = dict(info["truncated"] or {},
                                 edges_dropped=edges_dropped)
    split1 = ProcessedSplit.from_examples([ex])
    if table is not None:
        from fira_tpu.data import buckets as buckets_lib

        ext = buckets_lib.sample_extents(split1, cfg)
        if cfg.decode_tar_buckets and not record.msg_tokens:
            # tar-bucketed assignment goes by reference-message extent,
            # which is the generation BUDGET cap on the engine — a
            # referenceless real-traffic diff has no such proxy, so it
            # must reserve the FULL tar budget or its generated message
            # would be silently clipped at a small bucket's tar
            ext = dataclasses.replace(
                ext, msg=np.full_like(ext.msg, cfg.tar_len))
        bucket = int(buckets_lib.assign_buckets(
            ext, table, use_msg=cfg.decode_tar_buckets)[0])
        geom = table[bucket]
    else:
        bucket, geom = 0, None
    host = make_batch(split1, np.asarray([0]), cfg,  # firacheck: allow[HOST-SYNC] np.asarray of a host int list builds the make_batch index chunk; no device value exists here
                      batch_size=batch_size, geom=geom)
    t3 = time.perf_counter()

    host["_bucket"] = bucket
    host["_var"] = [req.var_map or None] + [None] * (batch_size - 1)
    host["_ingest"] = {
        "lex_s": round(t1 - t0, 9),
        "parse_s": round(t2 - t1, 9),
        "assemble_s": round(t3 - t2, 9),
        "n_tokens": len(record.diff_tokens),
        "truncated": info["truncated"],
        "degraded": info["degraded"],
        "oov_words": words.unk_fallbacks,
        "oov_ast": asts.pad_fallbacks,
    }
    if executor is not None:
        # the PARTIAL-hit meter (docs/INGEST.md "Fast path"): hunk-memo
        # reuse inside a whole-diff MISS — accounted separately from the
        # whole-diff `cached` flag the result cache replays
        host["_ingest"]["memo_hits"] = memo_hits
        host["_ingest"]["memo_misses"] = memo_misses
    return host


def build_fast_path(cfg: FiraConfig, *, faults=None, context=None):
    """The ingest fast-path objects for one serve run, per the knobs:
    ``(cache, lex, executor)`` — the whole-diff result cache + lexer
    memo (None with ``ingest_cache`` off), and the execution mode (the
    spawned process pool under ``ingest_exec=process``; the
    thread-inline executor carrying the hunk memo when the fast path is
    armed; None when everything is off — the pristine legacy path).

    ``context``: ``(word_vocab, ast_change_vocab, cfg, table)`` — when
    given, the process pool does WHOLE-request offload: each cache miss
    ships raw text out and an assembled payload back, so the parent's
    per-request GIL time is pickling only and ``ingest_workers`` scales
    across cores (the serve path always passes it). The caller owns
    ``executor.close()`` (serve_diffs wraps it in a finally)."""
    cache = lex = memo = None
    if cfg.ingest_cache:
        cache = IngestCache(cfg.ingest_cache_entries,
                            max_bytes=cfg.ingest_cache_bytes,
                            faults=faults)
        memo = HunkMemo()
        lex = LexMemo()
    if cfg.ingest_exec == "process":
        executor = IngestExecutor(
            "process", workers=cfg.ingest_workers or cfg.feeder_workers,
            context=context)
    elif memo is not None:
        executor = IngestExecutor("thread", memo=memo)
    else:
        executor = None
    return cache, lex, executor


def ingest_request_tasks(requests: Sequence[str], cfg: FiraConfig,
                         word_vocab: Vocab, ast_change_vocab: Vocab,
                         table=None, faults=None, cache=None, lex=None,
                         executor: Optional[IngestExecutor] = None):
    """One ingest task per request, request order — the Feeder runs them
    on its worker pool exactly like serve._request_tasks runs corpus
    assembly: payloads are ready ahead of their arrivals, a failing
    request rides the per-task error channel into the quarantine, and
    digests are stamped worker-side when the prefix cache is armed. The
    ``ingest.parse`` fault site fires here (raise/hang before the parse,
    corrupt on the assembled payload — each retry a fresh keyed draw).

    ``cache``/``lex``/``executor``: the fast-path hooks from
    :func:`build_fast_path`. With the cache armed the raw text is
    content-addressed BEFORE any lexing: a byte-identical repeat skips
    the whole pipeline and replays the stored payload (``_ingest``
    stamps with ``cached: True``); the ``ingest.cache`` fault site fires
    inside the lookup (raise => miss, corrupt => checksum-detected drop
    => re-ingest). The cache stores the CLEAN computation — the
    ``ingest.parse`` corrupt scramble and the prefix-cache digest stamp
    are applied per emission, after the lookup, so fault blast radii and
    dedup identities are byte-for-byte what the cache-off path
    produces."""
    from fira_tpu.data.feeder import task_note

    stamp = None
    if cfg.prefix_cache:
        # tier-namespaced like every other stamping site: the digest
        # commits to the serving precision so artifacts cached under one
        # tier can never seat a slot under another (decode/quant.py)
        import functools

        from fira_tpu.decode import quant
        from fira_tpu.decode.prefix_cache import stamp_digests
        stamp = functools.partial(stamp_digests,
                                  namespace=quant.tier_namespace(cfg))

    for i, text in enumerate(requests):
        def task(text=text, i=i, attempts={"n": 0}):
            if faults is not None:
                # advance the attempt BEFORE the check so a fired raise
                # still moves the key forward — every retry is a fresh
                # deterministic draw (the feeder.assemble contract)
                key = (i, attempts["n"])
                attempts["n"] += 1
                faults.check("ingest.parse", key=key)
            host = None
            digest = None
            if cache is not None:
                digest = text_digest(text)
                host, _outcome = cache.take(digest, fault_key=i)
            if host is None:
                # a miss makes this task the digest's in-flight leader:
                # concurrent duplicates are parked inside cache.take
                # until put (success) or abandon (the quarantine path —
                # a failing request must not wedge its duplicates)
                try:
                    if executor is not None and executor.offloads_requests:
                        # whole-request process offload: text out,
                        # assembled payload back — the parent thread
                        # parks GIL-free
                        host = executor.ingest(text)
                    else:
                        host = ingest_request(text, word_vocab,
                                              ast_change_vocab, cfg,
                                              table=table, lex=lex,
                                              executor=executor)
                except BaseException:
                    if cache is not None:
                        cache.abandon(digest)
                    raise
                if cache is not None:
                    cache.put(digest, host)
            if faults is not None:
                host = faults.corrupt("ingest.parse", i, host)
            return stamp(host) if stamp is not None else host
        task.note = task_note([i], site="ingest request")
        yield task


def _template_split(word_vocab: Vocab, ast_change_vocab: Vocab,
                    cfg: FiraConfig):
    """A one-row ProcessedSplit at the config geometry (an empty commit)
    — the shape/dtype source for all-pad warmup/template batches when no
    corpus split backs the request stream."""
    from fira_tpu.data.dataset import ProcessedSplit, process_record

    rec = CommitRecord([], [], [], [], {}, [], [], [], [], [], [])
    ex = process_record(rec, _LenientVocab(word_vocab),
                        _LenientVocab(ast_change_vocab), cfg)
    return ProcessedSplit.from_examples([ex])


# --------------------------------------------------------------------------
# the diff-serving driver (the raw-diff twin of serve.server.serve_split)
# --------------------------------------------------------------------------

def serve_diffs(model, params, word_vocab: Vocab, ast_change_vocab: Vocab,
                cfg: FiraConfig, *,
                requests: Sequence[str],
                arrival_times,
                out_dir: str = "OUTPUT",
                ablation: Optional[str] = None,
                guard=None,
                engine_slots: Optional[int] = None,
                refill_order: str = "fifo",
                clock: str = "wall",
                step_cost_s: float = 1.0,
                prefill_cost_s: float = 1.0,
                engine=None,
                faults=None,
                metrics_path: Optional[str] = None,
                fast_path=None) -> Dict:
    """Serve raw-diff ``requests`` (request ``i`` arrives at
    ``arrival_times[i]``) end to end through the ServeLoop: same
    admission/deadline/shed/retirement/dedup machinery, same
    position-keyed ordered writer, same metrics artifact — the request
    payloads just come from :func:`ingest_request` on the feeder workers
    instead of corpus ``make_batch``. Requests that fail to parse (or
    are rejected by the truncation policy) are recorded-shed with an
    empty output line; every completed request's lifecycle record
    carries its ingest stamps."""
    from fira_tpu.data import buckets as buckets_lib
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.decode.runner import output_name
    from fira_tpu.decode.stream import OrderedStreamWriter
    from fira_tpu.decode.text import (cook_prediction, deanonymize,
                                      reference_words)
    from fira_tpu.eval.dev_bleu import nltk_sentence_bleu
    from fira_tpu.robust import faults as faults_lib
    from fira_tpu.serve.server import (ServeLoop, build_engines,
                                       finalize_serve_result, make_clock,
                                       metrics_snapshotter,
                                       prepare_templates,
                                       run_loop_guarded, serve_errors)

    if faults is None:
        faults = faults_lib.injector_from(cfg)
    times = np.asarray(arrival_times, dtype=np.float64)
    n_req = len(times)
    if n_req != len(requests):
        raise ValueError(f"{len(requests)} requests for {n_req} arrivals")
    errs = serve_errors(cfg, trace=True) + ingest_errors(cfg)
    if errs:
        raise ValueError("; ".join(errs))
    clk = make_clock(clock, step_cost_s=step_cost_s,
                     prefill_cost_s=prefill_cost_s)

    table = buckets_lib.decode_table(cfg) if cfg.buckets else None
    tmpl_split = _template_split(word_vocab, ast_change_vocab, cfg)
    owner, engines, built = build_engines(model, params, cfg,
                                          engine=engine,
                                          engine_slots=engine_slots,
                                          guard=guard, faults=faults)
    templates = prepare_templates(owner, tmpl_split, cfg, table,
                                  guard=guard, prewarm=built)

    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, output_name(ablation))
    bleu_by_pos: Dict[int, float] = {}
    snapshot = metrics_snapshotter(metrics_path, owner, faults)

    def emit(pos, host, row, tokens, probs):
        # the sample_emitter tail with the request's OWN anonymization
        # map (the packed batch's _var column) instead of a corpus-
        # indexed var_maps table — identical cooking, so reconstructed
        # corpus requests serve byte-identical output
        best = int(np.argmax(probs))
        ids = tokens[best].tolist()
        hyp = cook_prediction(ids[1:], host["diff"][row],
                              host["sub_token"][row], word_vocab, cfg,
                              resolve=False)
        ref = reference_words(host["msg"][row], word_vocab)
        bleu_by_pos[pos] = nltk_sentence_bleu([ref], hyp)
        vm = host.get("_var")
        var_map = vm[row] if vm is not None else None
        writer.add(pos, " ".join(deanonymize(hyp, var_map)) + "\n")

    # the ingest fast path (docs/INGEST.md "Fast path"): whole-diff
    # result cache + lexer memo + execution mode, one set per serve
    # run; the executor owns pool processes, so its close rides the
    # same finally as the feeder threads. The pipeline DEPTH scales
    # with the worker count: single-row ingest payloads are tens of KB,
    # and a depth that caps in-flight tasks at feeder_depth=4 would
    # idle a wide pool the moment four payloads are ready — the workers
    # must be able to run AHEAD of arrivals (that is the whole point of
    # pre-assembly) for fan-out to show up as stall reduction.
    workers = cfg.ingest_workers or cfg.feeder_workers
    depth = max(cfg.feeder_depth, 4 * max(1, workers))
    if fast_path is not None:
        # caller-owned reuse across runs (the engine= discipline): the
        # caller keeps the pool warm and decides cache clearing/close
        cache, lex, executor = fast_path
        own_executor = None
    else:
        cache, lex, executor = build_fast_path(
            cfg, faults=faults,
            context=(word_vocab, ast_change_vocab, cfg, table))
        own_executor = executor
    try:
        with OrderedStreamWriter(out_path, expected=n_req) as writer, \
                Feeder(ingest_request_tasks(requests, cfg, word_vocab,
                                            ast_change_vocab, table,
                                            faults=faults, cache=cache,
                                            lex=lex, executor=executor),
                       num_workers=workers,
                       depth=depth, put=False,
                       on_error="record",
                       retries=max(0, cfg.robust_retries),
                       # one task is one REQUEST (its stamps are the
                       # record's `ingest` block): no span each
                       faults=faults, per_request=True) as feed:
            loop = ServeLoop(
                engines, cfg, arrival_times=times, feed=feed, table=table,
                assignment=None, templates=templates, clock=clk, emit=emit,
                shed=lambda rec: writer.add(rec.position, "\n"),
                refill_order=refill_order, faults=faults, snapshot=snapshot)
            loop.stats.ingest_pipeline = (workers, depth)
            if cache is not None:
                # the run-level cache meter lands in the serve summary's
                # ingest block (entries/bytes/hits/evictions/integrity)
                loop.stats.ingest_cache = cache.summary
            stats = run_loop_guarded(loop, snapshot)
    finally:
        if own_executor is not None:
            own_executor.close()
    # same teardown oracle as serve.server.serve_split: armed, a leaked
    # block/thread/pool raises here naming its acquire site (success
    # path only — a serve error must not be masked by its leak fallout)
    from fira_tpu.analysis.sanitizer import leak_guard

    lg = leak_guard()
    if lg is not None:
        lg.assert_clean("serve_diffs teardown")
    return finalize_serve_result(stats, owner, faults, out_path=out_path,
                                 bleu_by_pos=bleu_by_pos,
                                 metrics_path=metrics_path)


# --------------------------------------------------------------------------
# one-shot: cli message <diff-file>
# --------------------------------------------------------------------------

def one_shot_message(model, params, word_vocab: Vocab,
                     ast_change_vocab: Vocab, cfg: FiraConfig,
                     text: str) -> str:
    """One diff in, one commit message out (``cli message``): ingest the
    request through the SAME pipeline the serving loop uses (truncation
    policy included — a diff `cli serve --input diffs` would shed under
    ``ingest_truncate=shed`` is rejected here too), run the batched beam
    on the payload, cook and de-anonymize the argmax beam. No engine, no
    serving loop — the smallest possible diff->message path."""
    from fira_tpu.decode.beam import make_beam_search
    from fira_tpu.decode.text import cook_prediction, deanonymize

    host = ingest_request(text, word_vocab, ast_change_vocab, cfg,
                          batch_size=cfg.test_batch_size)
    beam = make_beam_search(model, cfg)
    wire = {k: v for k, v in host.items() if not k.startswith("_")}
    tokens, probs = beam(params, wire)
    tokens = np.asarray(tokens)
    probs = np.asarray(probs)
    best = int(np.argmax(probs[0]))
    hyp = cook_prediction(tokens[0][best].tolist()[1:], host["diff"][0],
                          host["sub_token"][0], word_vocab, cfg,
                          resolve=False)
    return " ".join(deanonymize(hyp, host["_var"][0]))
