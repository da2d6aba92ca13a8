"""Full-SCALE prediction artifact (closes SURVEY C17): decode a test split
of exactly the reference's size — 7,661 commits out of a 90,661-commit
corpus, split 75,000/8,000/7,661 like Dataset.py:10-12 — at the flagship
model geometry, writing OUTPUT/output_fira (7,661 lines) and scoring it
with every in-repo metric.

Training here is deliberately brief (the quality target lives with the real
corpus, not synthetic data); the artifact proves the ENVELOPE: 90k-commit
corpus build, split bookkeeping, full-size KV-cached beam decode, metric
scoring. Env knobs: FULLSCALE_COMMITS (default 90661), FULLSCALE_STEPS
(default 100), FULLSCALE_BATCH (default 16), FULLSCALE_CPU=1,
FULLSCALE_DIR (default fullscale).

Run: python scripts/full_scale_decode.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts.dress_rehearsal import REHEARSAL_VOCAB, pad_vocab_file  # noqa: E402


def main() -> None:
    if os.environ.get("FULLSCALE_CPU") == "1":
        from fira_tpu.utils.startup import force_cpu_backend

        force_cpu_backend()

    import jax
    import numpy as np

    from fira_tpu.config import fira_full
    from fira_tpu.data.batching import epoch_batches
    from fira_tpu.data.dataset import FiraDataset
    from fira_tpu.data.synthetic import write_corpus_dir
    from fira_tpu.decode.runner import run_test
    from fira_tpu.eval.bnorm_bleu import bnorm_bleu_files
    from fira_tpu.eval.meteor import meteor_detail_files
    from fira_tpu.eval.penalty_bleu import penalty_bleu_files
    from fira_tpu.eval.rouge import rouge_l_files
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train import step as step_lib
    from fira_tpu.train.state import init_state

    n = int(os.environ.get("FULLSCALE_COMMITS", "90661"))
    n_steps = int(os.environ.get("FULLSCALE_STEPS", "100"))
    batch = int(os.environ.get("FULLSCALE_BATCH", "16"))
    base = os.path.abspath(os.environ.get("FULLSCALE_DIR", "fullscale"))
    data_dir = os.path.join(base, "DataSet")
    out_dir = os.path.join(base, "OUTPUT")
    report: dict = {"n_commits": n, "train_steps": n_steps,
                    "batch_size": batch}

    t0 = time.time()
    sentinel = os.path.join(data_dir, ".corpus_ready")
    if not os.path.exists(sentinel):
        write_corpus_dir(data_dir, n, seed=23)
        pad_vocab_file(os.path.join(data_dir, "word_vocab.json"),
                       REHEARSAL_VOCAB)
        with open(sentinel, "w") as f:
            f.write("ok\n")
    report["corpus_secs"] = round(time.time() - t0, 1)
    print(f"[fullscale] corpus written: {report['corpus_secs']}s", flush=True)

    t0 = time.time()
    cfg = fira_full(batch_size=batch, test_batch_size=20)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    assert cfg.vocab_size == REHEARSAL_VOCAB
    sizes = {k: len(v) for k, v in dataset.split_indices.items()}
    if n == 90661:  # the reference's exact corpus size => its exact split
        assert sizes == {"train": 75000, "valid": 8000, "test": 7661}, sizes
    report["split"] = sizes
    report["dataset_secs"] = round(time.time() - t0, 1)
    print(f"[fullscale] dataset processed: {report['dataset_secs']}s", flush=True)

    # brief training: enough steps for non-degenerate output, not quality
    t0 = time.time()
    model = FiraModel(cfg)
    first = next(epoch_batches(dataset.splits["train"], cfg))
    state = init_state(model, cfg, first)
    train_step = jax.jit(step_lib.make_train_step(model, cfg),
                         donate_argnums=(0,))
    it = epoch_batches(dataset.splits["train"], cfg, shuffle=True,
                       seed=cfg.seed, drop_remainder=True)
    loss = None
    for i in range(n_steps):
        state, metrics = train_step(state, next(it))
        if i % 20 == 0:
            loss = float(jax.device_get(metrics["loss"]))
            print(f"[fullscale] step {i} loss {loss:.4f}", flush=True)
    report["train"] = {"secs": round(time.time() - t0, 1),
                       "final_loss": round(loss, 4)}

    with open(os.path.join(data_dir, "variable.json")) as f:
        var_maps = json.load(f)

    t0 = time.time()
    metrics = run_test(model, state.params, dataset, out_dir=out_dir,
                       var_maps=var_maps)
    out_path = metrics["output_path"]
    n_pred = len(open(out_path).read().splitlines())
    assert n_pred == sizes["test"], (n_pred, sizes["test"])
    report["decode"] = {"n_predictions": n_pred,
                        "sentence_bleu": round(metrics["sentence_bleu"], 4),
                        "secs": round(time.time() - t0, 1)}
    print(f"[fullscale] decode done: {report['decode']}", flush=True)

    # ground truth + the full metric battery
    from fira_tpu.decode.text import deanonymize, reference_words

    gt_path = os.path.join(out_dir, "ground_truth")
    test_split = dataset.splits["test"]
    test_idx = dataset.split_indices["test"]
    lines = []
    for i in range(len(test_split)):
        words = reference_words(test_split.arrays["msg"][i],
                                dataset.word_vocab)
        lines.append(" ".join(deanonymize(words, var_maps[test_idx[i]])))
    with open(gt_path, "w") as f:
        f.write("\n".join(lines) + "\n")

    md = meteor_detail_files(out_path, gt_path)
    report["metrics"] = {
        "bnorm_bleu": round(bnorm_bleu_files(out_path, gt_path), 3),
        "penalty_bleu": round(penalty_bleu_files(out_path, gt_path), 3),
        "rouge_l": round(rouge_l_files(out_path, gt_path), 3),
        "meteor": round(md["value"], 3),
        "meteor_wordnet": md["wordnet"],
    }
    report["ok"] = True
    with open(os.path.join(base, "FULLSCALE.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report), flush=True)


if __name__ == "__main__":
    main()
