"""The comparison that decides ``correct``: what the timed path produced,
against the plain reference, each number beside a limit of its own
(``limits/<workload>.json``; how each was set is in PERF.md section 2).

Training — one fused dispatch of K optimizer steps, K different batches,
followed by the reference step for step:

- ``loss_gap``: widest relative gap between a step's loss and the
  reference's;
- ``grad_gap``: worst leaf of | ||m|| - ||m_ref|| | over
  max(||m_ref|| of that leaf, of the median leaf), m being Adam's first
  moment after the K steps — the gradients as the optimizer got them;
- ``change_gap``: the same measure on the parameters' change over the K
  steps, leaving out leaves whose reference gradient is nought to rounding
  (under a thousandth of the median leaf's): under Adam those move by
  round-off alone.

Decoding — a sample of the requests finished in the window, teacher-forced
through the reference:

- ``prob_gap``: widest | log p(served beam) - log p_ref(served beam) |,
  over each request's served (most probable) beam;
- ``topk_gap``: widest gap by which a token's reference probability lies
  below the reference's ``beam``-th best at that position (a token that
  survived beam selection ranks within the beam of its own prefix), over
  each request's served beam and its longest one; ``topk_mean`` is the mean
  of the same gaps over all those positions.

A number is compared where ``limits/<workload>.json`` gives it a limit; the
others are printed beside them under the run's ``info``.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence

import numpy as np


def named_scalars(tree, index=None) -> Dict[str, float]:
    """{path: value} of a tree of scalars (or of vectors, at ``index``)."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return {jax.tree_util.keystr(p): float(x if index is None else x[index])
            for p, x in flat}


def leaf_norms(tree, base=None) -> Dict[str, float]:
    """{path: L2 norm} of every leaf (of ``tree - base`` where given)."""
    import jax
    import jax.numpy as jnp

    def norms(t, b):
        if b is not None:
            t = jax.tree_util.tree_map(jnp.subtract, t, b)
        return jax.tree_util.tree_map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t)
    return named_scalars(jax.jit(norms)(tree, base))


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float],
                   keep: Optional[Sequence[str]] = None):
    """(gap, leaf): | got - ref | over max(ref, median ref), worst leaf."""
    names = list(ref) if keep is None else list(keep)
    med = float(np.median([ref[n] for n in names]))
    worst, where = 0.0, ""
    for n in names:
        gap = abs(got[n] - ref[n]) / max(ref[n], med, 1e-30)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap > worst:
            worst, where = gap, n
    return worst, where


def train_numbers(got: Dict, ref: Dict) -> Dict:
    """``got``/``ref``: {"losses": (K,), "mu": {leaf: norm}, "change":
    {leaf: norm}}; ``ref`` also {"grad1": {leaf: norm}} — the reference's
    first-step gradient, which decides the leaves that count."""
    gl, rl = np.asarray(got["losses"], float), np.asarray(ref["losses"], float)
    loss_gap = float(np.max(np.abs(gl - rl) / np.abs(rl))) \
        if gl.shape == rl.shape and np.all(np.isfinite(gl)) else float("inf")
    med_g = float(np.median(list(ref["grad1"].values())))
    moving = [n for n, g in ref["grad1"].items() if g >= 1e-3 * med_g]
    grad_gap, grad_leaf = worst_leaf_gap(got["mu"], ref["mu"], moving)
    change_gap, change_leaf = worst_leaf_gap(got["change"], ref["change"],
                                             moving)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap,
            "_where": {"grad_gap": grad_leaf, "change_gap": change_leaf,
                       "leaves_left_out": len(ref["grad1"]) - len(moving)}}


def beam_predictions(tokens: np.ndarray, eos_id: int) -> List[int]:
    """Predictions made for each served beam: through <eos>, or all."""
    out = []
    for row in tokens:
        hit = np.nonzero(row[1:] == eos_id)[0]
        out.append(int(hit[0]) + 1 if len(hit) else len(row) - 1)
    return out


def beam_numbers(tokens: np.ndarray, logp_served: np.ndarray,
                 logp_token: np.ndarray, below_kth: np.ndarray,
                 eos_id: int) -> Dict:
    """One sample of served beams. ``tokens`` (N, T): beams of the sampled
    requests; ``logp_served`` (N,): log of each one's probability as whoever
    stands in the program's place gave it, NaN for a beam whose probability
    is not compared (only the most probable beam's is: a lesser beam may
    hold a word copied from the lesser of two source positions that carry
    it, which the tokens do not show); ``logp_token`` (N, T): the
    reference's log probability of each served token; ``below_kth`` (N, T):
    how far the token(s) put forward at each position lie below the
    reference's ``beam``-th best there (position t predicts
    tokens[:, t+1])."""
    prob_gap, topk_gap, below, positions = 0.0, 0.0, 0.0, 0
    for i, n in enumerate(beam_predictions(tokens, eos_id)):
        if not np.isnan(logp_served[i]):
            prob_gap = max(prob_gap, abs(float(logp_served[i])
                                         - float(np.sum(logp_token[i, :n]))))
        gaps = np.maximum(0.0, below_kth[i, :n])
        topk_gap = max(topk_gap, float(np.max(gaps)))
        below += float(np.sum(gaps))
        positions += n
    return {"prob_gap": prob_gap, "topk_gap": topk_gap,
            "topk_mean": below / max(positions, 1),
            "_where": {"requests": len(tokens), "positions": positions}}


def load_limits(root: str, workload: str) -> Dict[str, float]:
    with open(os.path.join(root, "limits", workload + ".json")) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def judge(numbers: Dict, limits: Dict[str, float]) -> Dict:
    """-> {"correct": bool, "check": {name: {"value", "limit"}}}; a number
    that is missing, not finite or over its limit fails the run."""
    check, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok = ok and bool(good)
        check[name] = {"value": float(v) if v is not None
                       and np.isfinite(v) else None, "limit": limit}
    return {"correct": ok, "check": check}


def format_check(check: Dict) -> List[str]:
    return [f"check {n}: value {c['value']!r} limit {c['limit']!r}"
            for n, c in check.items()]
