"""Full-geometry dress rehearsal (VERDICT r2 #4): prove the flagship
fira-full program runs end-to-end outside bench.py's 4-batch loop, matching
the reference's operational envelope (/root/reference/run_model.py:382-425):

  1. synthetic corpus on disk at full geometry, word vocab padded to the
     reference's 24,650 entries (fused output = 25,020-way);
  2. train leg A: fit with dev gating, train_process logging, checkpoints;
  3. train leg B: NEW process-equivalent resume from the latest checkpoint
     (optimizer moments + PRNG + epoch restored), more epochs;
  4. beam-decode the test split -> OUTPUT/output_fira;
  5. score the output with the in-repo B-Norm / Penalty BLEU implementations
     against a ground_truth file built from the test split;
  6. write a REHEARSAL.json artifact with every number.

Sizes default to the flagship geometry (batch 170, a few hundred steps) —
right for a TPU chip. The machine this repo is built on has ONE CPU core, so
CPU runs must shrink via env: REHEARSAL_COMMITS, REHEARSAL_BATCH,
REHEARSAL_EPOCHS_A/B, REHEARSAL_CPU=1 (pins the CPU backend),
REHEARSAL_DIR.

Run:  python scripts/dress_rehearsal.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REHEARSAL_VOCAB = 24650  # reference word vocab size (run_model.py:48)


def pad_vocab_file(path: str, target: int) -> int:
    """Inflate word_vocab.json with filler tokens to the reference size so
    the model's fused output distribution costs what the real corpus costs."""
    with open(path) as f:
        vocab = json.load(f)
    n0 = len(vocab)
    for i in range(target - n0):
        vocab[f"fillertok{i}"] = n0 + i
    with open(path, "w") as f:
        json.dump(vocab, f)
    return len(vocab)


def main() -> None:
    if os.environ.get("REHEARSAL_CPU") == "1":
        from fira_tpu.utils.startup import force_cpu_backend

        force_cpu_backend()

    import numpy as np

    from fira_tpu.config import fira_full
    from fira_tpu.data.dataset import FiraDataset
    from fira_tpu.data.synthetic import write_corpus_dir
    from fira_tpu.decode.runner import run_test
    from fira_tpu.decode.text import deanonymize, reference_words
    from fira_tpu.eval.bnorm_bleu import bnorm_bleu_files
    from fira_tpu.eval.penalty_bleu import penalty_bleu_files
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train.loop import train

    n_commits = int(os.environ.get("REHEARSAL_COMMITS", "2048"))
    batch_size = int(os.environ.get("REHEARSAL_BATCH", "170"))
    epochs_a = int(os.environ.get("REHEARSAL_EPOCHS_A", "2"))
    epochs_b = int(os.environ.get("REHEARSAL_EPOCHS_B", "1"))
    base = os.path.abspath(os.environ.get("REHEARSAL_DIR", "rehearsal"))
    data_dir = os.path.join(base, "DataSet")
    out_dir = os.path.join(base, "OUTPUT")
    ckpt_dir = os.path.join(base, "ckpt")
    report: dict = {"n_commits": n_commits, "batch_size": batch_size,
                    "epochs": [epochs_a, epochs_b]}

    t0 = time.time()
    os.makedirs(base, exist_ok=True)
    # each invocation is a FULL rehearsal: stale checkpoints/outputs from a
    # previous run would turn both legs into no-ops and void the resume proof
    for d in (ckpt_dir, out_dir):
        if os.path.exists(d):
            import shutil

            shutil.rmtree(d)
    # corpus readiness is gated on a sentinel written AFTER the vocab
    # padding, so an interrupted first run regenerates instead of limping on
    # a half-built directory
    sentinel = os.path.join(data_dir, ".corpus_ready")
    if not os.path.exists(sentinel):
        write_corpus_dir(data_dir, n_commits, seed=11)
        pad_vocab_file(os.path.join(data_dir, "word_vocab.json"),
                       REHEARSAL_VOCAB)
        with open(sentinel, "w") as f:
            f.write("ok\n")
    # flagship geometry; dev gate made reachable within the short run
    # (reference cadence epoch>=15 %10 is config, run_model.py:89)
    cfg = fira_full(batch_size=batch_size,
                    test_batch_size=min(20, batch_size),
                    dev_start_epoch=0, dev_every_batches=4)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    assert cfg.vocab_size == REHEARSAL_VOCAB, cfg.vocab_size
    with open(os.path.join(data_dir, "variable.json")) as f:
        var_maps = json.load(f)  # deanonymize() reverses each map itself
    report["corpus_secs"] = round(time.time() - t0, 1)
    print(f"[rehearsal] corpus ready: {n_commits} commits, "
          f"vocab {cfg.vocab_size}, {report['corpus_secs']}s", flush=True)

    # ---- leg A: train from scratch ----
    t0 = time.time()
    res_a = train(dataset, out_dir=out_dir, ckpt_dir=ckpt_dir,
                  epochs=epochs_a, var_maps=var_maps, resume=True)
    report["leg_a"] = {
        "epochs_run": res_a.epochs_run,
        "best_dev_bleu": round(res_a.best_bleu, 4),
        "commits_per_sec_per_chip": round(res_a.commits_per_sec_per_chip, 2),
        "secs": round(time.time() - t0, 1),
        "final_step": int(res_a.state.step),
    }
    assert os.path.exists(os.path.join(out_dir, "train_process"))
    print(f"[rehearsal] leg A done: {report['leg_a']}", flush=True)

    # ---- leg B: resume (fresh train() call = process-equivalent restart) ----
    t0 = time.time()
    res_b = train(dataset, out_dir=out_dir, ckpt_dir=ckpt_dir,
                  epochs=epochs_a + epochs_b, var_maps=var_maps, resume=True)
    # resume PROOF: leg B must have executed exactly epochs_b epochs' worth
    # of steps on top of leg A's final step — a silent from-scratch restart
    # would add epochs_a + epochs_b epochs and fail both checks
    steps_per_epoch = -(-len(dataset.splits["train"]) // cfg.batch_size)
    delta = int(res_b.state.step) - int(res_a.state.step)
    assert delta == epochs_b * steps_per_epoch, \
        f"resume leg ran {delta} steps, expected {epochs_b}x{steps_per_epoch}"
    assert res_b.epochs_run == epochs_b, res_b.epochs_run
    report["leg_b"] = {
        "epochs_run": res_b.epochs_run,
        "best_dev_bleu": round(res_b.best_bleu, 4),
        "resumed_from_step": int(res_a.state.step),
        "final_step": int(res_b.state.step),
        "secs": round(time.time() - t0, 1),
    }
    print(f"[rehearsal] leg B (resume) done: {report['leg_b']}", flush=True)

    # ---- decode the test split ----
    t0 = time.time()
    model = FiraModel(cfg)
    metrics = run_test(model, res_b.state.params, dataset, out_dir=out_dir,
                       var_maps=var_maps)
    report["decode"] = {
        "n_predictions": int(metrics["n"]),
        "sentence_bleu": round(metrics["sentence_bleu"], 4),
        "secs": round(time.time() - t0, 1),
    }
    out_path = metrics["output_path"]
    print(f"[rehearsal] decode done: {report['decode']}", flush=True)

    # ---- ground truth + offline metrics (the reference's Metrics/ flow) ----
    gt_path = os.path.join(out_dir, "ground_truth")
    test_split = dataset.splits["test"]
    test_idx = dataset.split_indices["test"]
    lines = []
    for i in range(len(test_split)):
        words = reference_words(test_split.arrays["msg"][i],
                                dataset.word_vocab)
        vm = var_maps[test_idx[i]] if var_maps is not None else None
        lines.append(" ".join(deanonymize(words, vm)))
    with open(gt_path, "w") as f:
        f.write("\n".join(lines) + "\n")

    report["metrics"] = {
        "bnorm_bleu": round(bnorm_bleu_files(out_path, gt_path), 3),
        "penalty_bleu": round(penalty_bleu_files(out_path, gt_path), 3),
    }
    n_pred = len(open(out_path).read().splitlines())
    assert n_pred == len(test_split), (n_pred, len(test_split))
    report["ok"] = True

    with open(os.path.join(base, "REHEARSAL.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report), flush=True)


if __name__ == "__main__":
    main()
