"""Bootstrap the Table-3 ordering statistics on a fullscale2-style artifact
directory (the round-4 bootstrap used 300 resamples and was run ad-hoc;
this is the committed version at 10k).

B-Norm BLEU (the paper's metric of record, /root/reference/Metrics/
Bleu-B-Norm.py) is a mean of per-sentence smoothed BLEU-4 scores, so the
bootstrap resamples test-set indices and recomputes each variant's mean from
its per-sentence score vector. Reported events:
  p_full_strictly_top_bnorm   P(full > every other variant)
  p_paper_strict_order_bnorm  P(full > no_edit > no_subtoken > nothing)
  p_no_edit_below_full_bnorm  P(no_edit < full)

Usage: python scripts/bootstrap_ordering.py [DIR] [RESAMPLES]
Updates DIR/FULLSCALE2.json in place (analysis.bnorm_bootstrap) and prints
the result as one JSON line.
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fira_tpu.eval.bnorm_bleu import _pair_by_index, sentence_bleu_stats

VARIANTS = ["full", "no_edit", "no_subtoken", "nothing"]


def per_sentence_scores(hyp_path: str, ref_path: str) -> np.ndarray:
    with open(hyp_path) as h, open(ref_path) as r:
        pairs = _pair_by_index(h.readlines(), r.readlines())
    return np.array([sentence_bleu_stats(hyp, [ref])[0] * 100.0
                     for hyp, ref in pairs])


def main() -> None:
    root = sys.argv[1] if len(sys.argv) > 1 else "fullscale3_cpu"
    resamples = int(sys.argv[2]) if len(sys.argv) > 2 else 10_000
    ref = os.path.join(root, "ground_truth")
    scores = {}
    for v in VARIANTS:
        hyp = os.path.join(root, f"out_{v}", "output_fira")
        if not os.path.exists(hyp):
            print(json.dumps({"error": f"missing {hyp}"}))
            sys.exit(1)
        scores[v] = per_sentence_scores(hyp, ref)
    n = len(scores["full"])
    assert all(len(s) == n for s in scores.values()), \
        {v: len(s) for v, s in scores.items()}

    rng = np.random.RandomState(0)
    mat = np.stack([scores[v] for v in VARIANTS])  # (4, n)
    idx = rng.randint(0, n, size=(resamples, n))
    means = mat[:, idx].mean(axis=2)               # (4, resamples)
    full, no_edit, no_sub, nothing = means
    result = {
        "bootstrap_resamples": resamples,
        "n_test": n,
        "point_estimates": {v: round(float(scores[v].mean()), 3)
                            for v in VARIANTS},
        "p_full_strictly_top_bnorm": round(float(
            ((full > no_edit) & (full > no_sub) & (full > nothing)).mean()), 4),
        "p_paper_strict_order_bnorm": round(float(
            ((full > no_edit) & (no_edit > no_sub) & (no_sub > nothing)).mean()), 4),
        "p_no_edit_below_full_bnorm": round(float((no_edit < full).mean()), 4),
    }

    fs_path = os.path.join(root, "FULLSCALE2.json")
    if os.path.exists(fs_path):
        with open(fs_path) as f:
            doc = json.load(f)
        doc.setdefault("analysis", {})["bnorm_bootstrap"] = result
        tmp = fs_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, fs_path)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
