"""What the two decode drivers share: the engine under test, the spans around
its scheduler pieces, and the comparison of served beams with the reference."""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from .. import check, common, reference, weights

REF_BLOCK = 32   # rows the reference scores at a time

PROMPT_FIELDS = ("diff", "diff_mark", "ast_change", "sub_token", "senders",
                 "receivers", "values")


def build_engine(ctx: Dict, corpus_commits: int, **cfg_overrides):
    """-> (cfg, split, vocab, model, params, engine), engine pre-warmed
    through its whole program family on an all-pad batch."""
    import jax.numpy as jnp

    from fira_tpu.data.batching import make_batch
    from fira_tpu.decode.engine import SlotEngine
    from fira_tpu.model.model import FiraModel

    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    # How many positions a request runs follows from the weights and its
    # prompt together. A mix that fixes ``content_seed`` gives every run
    # the same requests (so the same set of lengths) and leaves to
    # ``--seed`` their order, the arrivals and the sample that is checked.
    content_seed = int(traffic.get("content_seed", seed))
    cfg = common.program_cfg(
        config, "decode_knobs",
        engine_slots=int(traffic["engine_slots"]),
        kv_pool_blocks=int(traffic.get("kv_pool_blocks", 0)),
        test_batch_size=int(traffic["prefill_batch"]),
        feeder_workers=int(traffic["feeder_workers"]),
        feeder_depth=int(traffic["feeder_depth"]),
        seed=common.seed31(seed), **cfg_overrides)
    cfg, split, vocab = common.make_corpus(cfg, config, corpus_commits,
                                           content_seed)
    model = FiraModel(cfg, dtype=jnp.dtype(cfg.compute_dtype))
    warm = make_batch(split, np.arange(0), cfg,
                      batch_size=cfg.test_batch_size)
    common.check_param_tree(
        model, cfg, {k: v[:1] for k, v in warm.items()}, config)
    params = weights.make_params(common.model_cfg_dict(config), content_seed,
                                 eos_bias=float(traffic["eos_bias"]))
    eng = SlotEngine(model, params, cfg, slots=cfg.engine_slots)
    eng.prewarm([(warm, None)])
    return cfg, split, vocab, model, params, eng


ENGINE_SPANS = {"admit": "admit", "refill": "refill",
                "step_dispatch": "dispatch", "harvest": "harvest"}


def pick(items: List, n: int, seed: int, length_of) -> List:
    """A sample of up to ``n`` finished requests drawn from the seed, the
    longest among them."""
    if not items:
        return []
    order = np.random.default_rng(common.seed31(seed)).permutation(len(items))
    longest = max(range(len(items)), key=lambda i: length_of(items[i]))
    chosen = [longest] + [int(i) for i in order if int(i) != longest][:n - 1]
    return [items[i] for i in chosen]


def served_length(tokens: np.ndarray, probs: np.ndarray) -> int:
    """Tokens of the served (most probable) beam, <start> and <eos> in."""
    return int(np.count_nonzero(tokens[int(np.argmax(probs))]))


def beam_lengths(tokens: np.ndarray) -> int:
    """Positions a request ran: its longest beam, <start> not counted (the
    engine steps a slot until every beam has ended)."""
    return int(np.count_nonzero(tokens, axis=-1).max()) - 1


def length_stats(lengths: List[int]) -> Dict:
    """Spread of a length over all the requests a window finished."""
    if not lengths:
        return {}
    v = np.sort(np.asarray(lengths))
    return {"mean": float(v.mean()), "min": int(v[0]), "max": int(v[-1]),
            "p10": int(v[len(v) // 10]), "p50": int(v[len(v) // 2]),
            "p90": int(v[(9 * len(v)) // 10])}


def beam_check(mcfg: Dict, params, samples: List[Tuple[Dict, int, np.ndarray,
                                                       np.ndarray]],
               beam: int, log_space: bool, control: bool = False) -> Dict:
    """``samples``: (host batch, row, tokens (K, T), probs (K,)) of served
    requests; ``log_space`` says whether ``probs`` are sums of logs
    (``beam_compat_prob_space`` off) or products. Of each request two beams
    go through the reference, once, with their tokens: the one served (the
    most probable), whose probability is compared too, and the longest,
    which is the one that went through every position the request ran.
    With ``control`` also the control's numbers, read as the program's are:
    the reference in float8 put in the program's place, over the same
    prompts and tokens — its probability of each served beam, and at each
    position the token it would have kept where the program kept the served
    one: its own pick of the rank the served token holds in the reference."""
    import jax.numpy as jnp

    if not samples:
        return {"numbers": {"_where": {"requests": 0, "positions": 0}}}
    rows = []                    # (host, row, tokens (T,), log prob or None)
    for host, r, tokens, probs in samples:
        b = int(np.argmax(probs))
        p = float(probs[b])
        rows.append((host, r, tokens[b],
                     p if log_space else float(np.log(max(p, 1e-300)))))
        longest = int(np.argmax(np.count_nonzero(tokens, axis=-1)))
        rows.append((host, r, tokens[longest], None))
    tokens = np.stack([t for _h, _r, t, _p in rows]).astype(np.int32)
    served = np.asarray([np.nan if p is None else p
                         for _h, _r, _t, p in rows])
    score = reference.make_beam_scorer(mcfg, "f32", beam)
    score_low = reference.make_beam_scorer(mcfg, "fp8", beam) \
        if control else None
    ref, low = [], []
    for at in range(0, len(rows), REF_BLOCK):      # in blocks, so it fits
        idx = [min(i, len(rows) - 1) for i in range(at, at + REF_BLOCK)]
        prompts = {f: jnp.asarray(np.stack([rows[i][0][f][rows[i][1]]
                                            for i in idx]))
                   for f in PROMPT_FIELDS}
        toks = jnp.asarray(tokens[idx])
        probe = jnp.zeros(toks.shape + (1,), jnp.int32)
        if control:
            low.append(score_low(params, prompts, toks, probe))
            probe = low[-1]["top_ids"]
        ref.append(score(params, prompts, toks, probe))

    def whole(parts, key):
        return np.concatenate([np.asarray(p[key]) for p in parts])[:len(rows)]
    logp_token, logp_kth = whole(ref, "logp_token"), whole(ref, "logp_kth")
    out = {"numbers": check.beam_numbers(
        tokens, served, logp_token, logp_kth - logp_token, weights.EOS_ID)}
    out["numbers"]["_where"]["requests"] = len(samples)
    if control:
        kept = np.take_along_axis(whole(ref, "logp_probe"),
                                  whole(ref, "rank")[..., None], -1)[..., 0]
        lengths = check.beam_predictions(tokens, weights.EOS_ID)
        low_served = np.asarray(
            [np.sum(whole(low, "logp_token")[i, :n])
             if np.isfinite(served[i]) else np.nan
             for i, n in enumerate(lengths)])
        out["control_fp8"] = check.beam_numbers(
            tokens, low_served, logp_token, logp_kth - kept, weights.EOS_ID)
    return out


def engine_counters(mcfg: Dict, commits: int, window_s: float, slots: int,
                    stats, since=None) -> Dict:
    """The window's share of the engine's own counts (``EngineStats`` now,
    less the copy taken when the window opened) and the operations they
    stand for."""
    def grown(field: str) -> int:
        return getattr(stats, field) - (getattr(since, field) if since else 0)
    out = {"commits": commits, "window_s": window_s, "slots": slots}
    for field in ("steps", "step_dispatches", "occupied_slot_steps",
                  "prefills", "harvest_row_reads"):
        out[field] = grown(field)
    out["kv_bytes_per_slot"] = stats.kv_bytes_per_slot
    out["flops"] = decode_flops(mcfg, commits, out["occupied_slot_steps"])
    return out


def length_info(occupied_slot_steps: int, beams: List[Tuple[np.ndarray,
                                                            np.ndarray]]) -> Dict:
    """What the finished requests looked like: ``beams`` holds (tokens,
    probs) of each."""
    return {"positions_per_commit": occupied_slot_steps / max(len(beams), 1),
            "served_len": length_stats([served_length(t, p)
                                        for t, p in beams]),
            "positions_run": length_stats([beam_lengths(t)
                                           for t, _p in beams])}


def decode_flops(mcfg: Dict, commits: int, occupied_slot_steps: int) -> float:
    """Prefill of every harvested commit plus every position really run."""
    from .. import flops

    if not commits:
        return 0.0
    mean_positions = occupied_slot_steps / commits
    return (commits * flops.prefill_flops(mcfg)
            + occupied_slot_steps * flops.decode_position_flops(
                mcfg, (mean_positions + 1) / 2))
