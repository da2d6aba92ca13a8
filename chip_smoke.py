#!/usr/bin/env python3
"""Does the system still start on the chip?  `python3 chip_smoke.py`

Drives the main path once — train -> decode -> serve — through the entry
points a user calls (`python -m fira_tpu.cli ...`), at fira-full's published
width (d 256, 6 + 6 layers, 650-node graphs, batch 170, bf16, the 24,650-word
/ 71-label vocabulary and its 25,020-way fused head) on a corpus generated
from a seed, and checks what comes out by the repo's own means. Exit 0 and a
last stdout line of exactly
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`
(the device as jax reports it) mean every phase ran on a TPU and passed;
anything else is a non-zero exit. The line before it, `chip_smoke: summary
{...}`, is the full record (versions, compile-cache entry counts, per phase
`{ok, seconds, cache_entries_added, detail}`, `engine_bytes_equal`, ending
`"claim": null`); the same object is written to
`chiprun_out/chip_smoke/summary.json` beside the children's logs.

One process per chip: this parent never imports jax. Every phase is a child
that exits before the next starts, with JAX_PLATFORMS=tpu in its environment
so that jax raises where it would otherwise choose the CPU. The first child
only reports `jax.devices()`: a missing chip is named before any phase runs
(and nothing is printed on stdout).

Phases, in order (PHASES below):
  corpus        seeded corpus + vocabularies padded to the published sizes
                (parent-side, no jax), all under the output directory
  train         cli train, production knobs; enough short epochs to reach
                the first dev gate: finite non-increasing loss, a gate line,
                a checkpoint
  test          cli test (batched beam) on that checkpoint
  test_engine   cli test --engine (slot engine); bytes compared with `test`
  serve         cli serve --serve-rate R (wall clock, Poisson, prefix cache
                on): everything offered completes, nothing shed, finite
                percentiles; bytes compared with both decodes
  kernel        the Pallas copy-score kernel compiled (interpret=False),
                forward and backward, at fira-full and fira-large widths,
                against copy_scores_reference
  train_pallas  cli train --copy-head pallas on a one-batch corpus
  sync          one warmed train dispatch timed twice: ended by
                jax.block_until_ready and ended by float(loss)
  adjacency     the encoder's dense adjacency as `--perf production` builds
                it, at batch 170, 340 and 680: the cells it fills against a
                plain float32 scatter of the same triplets on the host
  train_mesh, test_fleet   only with >= 4 devices: cli train --mesh 4x1 and
                cli test --engine --engine-replicas 4, with every device's
                peak memory checked so device 0 is not holding everything

`--perf production` switches the slot engine on (config.DECODE_PERF_KNOBS),
so the batched beam cannot run under it; the three decode phases therefore
share the parity decode knobs, which is also what makes their output bytes
comparable. Training runs the production set bench.py times.

`--rehearse` runs the same phases here, without a chip: fira-tiny,
JAX_PLATFORMS=cpu with four virtual devices, the kernel interpreted, the
summary stamped "rehearsal": true. `--only a,b` runs a subset (plus the
probe and the corpus) while debugging; its summary says "partial": true.

Seconds in the summary are wall times of this smoke. They are not rates and
are never to be quoted as such.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
MARKER = ".chip_smoke"           # an output directory this script may wipe
WALL_LIMIT_S = 1150.0            # the driver allows 1200 s, compiles included
# exit codes (2 and 3 are the chip tool's own): 1 = a phase failed
EXIT_NO_DEVICE, EXIT_WONT_WIPE, EXIT_NO_REPO = 4, 5, 6

PHASES = ("train", "test", "test_engine", "serve", "kernel", "train_pallas",
          "sync", "adjacency", "train_mesh", "test_fleet")
MULTICHIP = ("train_mesh", "test_fleet")

# sizes: the corpus must give the train split one fused K=8 dispatch plus a
# per-step tail (the split is ~83/9/8 %: 1,800 commits -> 1,489 to train on
# = 8 x 170 + 129); the one-batch corpus gives train_pallas one full batch
# and a partial one
FULL = {"config": "fira-full", "batch": 170, "commits": 1800,
        "small_commits": 210, "pad_words": 24650, "pad_ast": 71,
        "serve_rate": 8.0,
        # 170 alone is the one size at which a batched N-D scatter under the
        # sorted-indices promise was right on the chip (PERF.md section 6)
        "adjacency_batches": [170, 340, 680],
        # (name, B, T, S = sou + sub_token, D)
        "kernel_shapes": [("fira-full", 4, 30, 370, 256),
                          ("fira-large", 4, 30, 370, 512)]}
TINY = {"config": "fira-tiny", "batch": 16, "commits": 200,
        "small_commits": 21, "pad_words": 0, "pad_ast": 0,
        "serve_rate": 8.0,
        "adjacency_batches": [16, 32, 64],
        "kernel_shapes": [("fira-tiny", 2, 12, 56, 64),
                          ("fira-tiny-wide", 2, 12, 56, 128)]}


class PhaseFailed(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# --------------------------------------------------------------------------
# children (the only code here that imports jax)
# --------------------------------------------------------------------------

def child_probe(_args) -> None:
    import jax
    import jaxlib
    from importlib import metadata

    from fira_tpu.utils.startup import device_info

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    print(json.dumps({
        **device_info(),  # raises when JAX_PLATFORMS names a missing backend
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
    }))


def child_kernel(args) -> None:
    """Compile (or, rehearsing, interpret) the copy-score kernel forward and
    backward at each width and compare with the XLA oracle in float32."""
    from fira_tpu.utils import startup

    startup.configure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fira_tpu.ops.copy_score import copy_scores, copy_scores_reference

    interpret = bool(args.rehearse)
    platform = jax.devices()[0].platform
    rows = []
    for name, B, T, S, D in json.loads(args.shapes):
        ks = jax.random.split(jax.random.PRNGKey(D), 5)
        src = 0.5 * jax.random.normal(ks[0], (B, S, D), jnp.float32)
        tgt = 0.5 * jax.random.normal(ks[1], (B, T, D), jnp.float32)
        w = jax.random.normal(ks[2], (D, 1), jnp.float32) / math.sqrt(D)
        bias = jnp.full((1,), 0.25, jnp.float32)
        cot = jax.random.normal(ks[3], (B, T, S), jnp.float32)

        def loss(fn, s, t, w_, b_):
            return jnp.sum(fn(s, t, w_, b_) * cot)

        kern = lambda s, t, w_, b_: copy_scores(s, t, w_, b_, interpret)
        # the oracle's matmul at full f32 precision: on a TPU the default
        # is a bf16 pass, which is not what the kernel is held to
        with jax.default_matmul_precision("highest"):
            ref_out = copy_scores_reference(src, tgt, w, bias)
            ref_grads = jax.grad(
                lambda *a: loss(copy_scores_reference, *a),
                argnums=(0, 1, 2, 3))(src, tgt, w, bias)
        out = jax.jit(kern)(src, tgt, w, bias)
        grads = jax.jit(jax.grad(lambda *a: loss(kern, *a),
                                 argnums=(0, 1, 2, 3)))(src, tgt, w, bias)

        def rel(a, b):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))

        rows.append({
            "name": name, "shape": [B, T, S, D], "interpret": interpret,
            "finite": bool(np.isfinite(np.asarray(out)).all()),
            "fwd_rel_err": rel(out, ref_out),
            "bwd_rel_err": {k: rel(g, r) for k, g, r in zip(
                ("dsrc", "dtgt", "dw", "dbias"), grads, ref_grads)},
        })
    print(json.dumps({"platform": platform, "kernels": rows}))


def child_sync(args) -> None:
    """One warmed train dispatch (the program `cli train` runs: same flags,
    same data, so a compile-cache hit), timed ended by block_until_ready and
    ended by float(loss), alternating."""
    from fira_tpu.utils import startup

    startup.configure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fira_tpu import cli
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.dataset import FiraDataset
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train import step as step_lib
    from fira_tpu.train.state import init_state

    cfg = cli._resolve_cfg(cli.build_parser().parse_args(
        ["train", *json.loads(args.cli_flags)]))
    dataset = FiraDataset(args.data_dir, cfg)
    cfg = dataset.cfg
    split = dataset.splits["train"]
    K = max(1, cfg.fused_steps)
    model = FiraModel(cfg, dtype=jnp.dtype(cfg.compute_dtype))
    sample = make_batch(split, np.arange(min(cfg.batch_size, len(split))),
                        cfg, batch_size=cfg.batch_size)
    state = init_state(model, cfg, sample)
    stacked = step_lib.stack_batches([sample] * K)
    step = step_lib.jit_multi_step(model, cfg, None, state, stacked)
    dev = jax.device_put(stacked)
    state, m = step(state, dev)
    first = float(np.asarray(m["loss"])[-1])     # compile + warm
    block_s, float_s, loss = [], [], first
    for _ in range(int(args.reps)):
        t0 = time.perf_counter()
        state, m = step(state, dev)
        jax.block_until_ready(m["loss"])
        block_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        state, m = step(state, dev)
        loss = float(np.asarray(m["loss"])[-1])
        float_s.append(time.perf_counter() - t0)
    print(json.dumps({
        "platform": jax.devices()[0].platform, "steps_per_dispatch": K,
        "batch": cfg.batch_size, "loss_first": first, "loss_last": loss,
        "block_until_ready_s": block_s, "float_loss_s": float_s,
    }))


def child_adjacency(args) -> None:
    """The dense adjacency as FiraModel.encode builds it under the CLI's
    flags (the sorted-indices promise with --perf production, straight into
    the compute dtype), on real batches of each size, against np.add.at of
    the same triplets in float32."""
    from fira_tpu.utils import startup

    startup.configure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fira_tpu import cli
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.dataset import FiraDataset
    from fira_tpu.model.model import dense_adjacency

    cfg = cli._resolve_cfg(cli.build_parser().parse_args(
        ["train", *json.loads(args.cli_flags)]))
    dataset = FiraDataset(args.data_dir, cfg)
    cfg = dataset.cfg
    split = dataset.splits["train"]
    dtype = jnp.dtype(cfg.compute_dtype)
    build = jax.jit(lambda s, r, v: dense_adjacency(
        s, r, v, cfg.graph_len, indices_sorted=cfg.sort_edges,
        out_dtype=dtype))
    rows = []
    for B in json.loads(args.shapes):
        batch = make_batch(split, np.arange(B) % len(split), cfg,
                           batch_size=B)
        s, r, v = (np.asarray(batch[k])
                   for k in ("senders", "receivers", "values"))
        got = np.asarray(build(s, r, v).astype(jnp.float32))
        plain = np.zeros((B, cfg.graph_len, cfg.graph_len), np.float32)
        np.add.at(plain, (np.arange(B)[:, None], s.astype(np.int64),
                          r.astype(np.int64)), v.astype(np.float32))
        # the program scatters in the compute dtype: one value a cell
        # (graph_build's dedup), so rounding the plain scatter is exact
        want = np.asarray(jnp.asarray(plain).astype(dtype)
                          .astype(jnp.float32))
        rows.append({"batch": B, "edges": int(np.count_nonzero(v)),
                     "cells_program": int(np.count_nonzero(got)),
                     "cells_plain": int(np.count_nonzero(plain)),
                     "values_equal": bool(np.array_equal(got, want))})
    print(json.dumps({"platform": jax.devices()[0].platform,
                      "sort_edges": bool(cfg.sort_edges),
                      "dtype": str(dtype), "rows": rows}))


CHILDREN = {"probe": child_probe, "kernel": child_kernel, "sync": child_sync,
            "adjacency": child_adjacency}


# --------------------------------------------------------------------------
# parent
# --------------------------------------------------------------------------

class Smoke:
    def __init__(self, args):
        self.rehearse = args.rehearse
        self.size = TINY if args.rehearse else FULL
        self.out = os.path.abspath(args.out_dir)
        self.logs = os.path.join(self.out, "logs")
        self.data = os.path.join(self.out, "data")
        self.data_small = os.path.join(self.out, "data_small")
        self.t_start = time.monotonic()
        self.phases: dict = {}
        self.outputs: dict = {}     # phase -> its output_fira bytes
        self.env = dict(os.environ)
        self.env["PYTHONUNBUFFERED"] = "1"
        if self.rehearse:
            self.env["JAX_PLATFORMS"] = "cpu"
            self.env["XLA_FLAGS"] = (
                self.env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
        else:
            self.env["JAX_PLATFORMS"] = "tpu"
        self.want = "cpu" if self.rehearse else "tpu"
        from fira_tpu.config import get_config
        from fira_tpu.utils.startup import compile_cache_dir

        self.cache_dir = compile_cache_dir()   # the children's rule
        cfg = get_config(self.size["config"])
        # the dev gate opens at cfg.dev_start_epoch (15 at fira-full, the
        # reference's cadence) and the CLI has no flag for it: train one
        # epoch past it so that the gate, its program and the best
        # checkpoint are part of what is proved
        self.epochs = cfg.dev_start_epoch + 1
        self.model_flags = ["--config", self.size["config"],
                            "--dtype", "bfloat16", "--data-dir", self.data]
        self.train_flags = [*self.model_flags, "--perf", "production"]

    # --- process handling -------------------------------------------------

    def remaining(self) -> float:
        return WALL_LIMIT_S - (time.monotonic() - self.t_start)

    def run(self, name: str, cmd: list) -> str:
        """Run one child to its end inside what is left of the wall limit;
        return its stdout. Non-zero exit or a timeout fails the phase, and
        the child's whole process group is killed either way."""
        budget = self.remaining()
        check(budget > 5, f"{name}: the {WALL_LIMIT_S:.0f}s wall limit is spent")
        log = os.path.join(self.logs, f"{name}.log")
        err = os.path.join(self.logs, f"{name}.err")
        with open(log, "w") as fo, open(err, "w") as fe:
            p = subprocess.Popen(cmd, cwd=REPO, env=self.env, stdout=fo,
                                 stderr=fe, start_new_session=True)
            try:
                rc = p.wait(timeout=budget)
            except subprocess.TimeoutExpired:
                rc = None
            finally:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                p.wait()
        with open(log) as f:
            stdout = f.read()
        if rc != 0:
            with open(err) as f:
                tail = f.read()[-3000:]
            why = (f"timed out after {budget:.0f}s" if rc is None
                   else f"exit code {rc}")
            raise PhaseFailed(f"{name}: {why}\n--- stdout tail\n"
                              f"{stdout[-1500:]}\n--- stderr tail\n{tail}")
        return stdout

    def cli(self, name: str, *argv: str) -> str:
        return self.run(name, [sys.executable, "-m", "fira_tpu.cli", *argv])

    def child(self, name: str, *argv: str) -> dict:
        out = self.run(name, [sys.executable, os.path.abspath(__file__),
                              "--child", name,
                              *(["--rehearse"] if self.rehearse else []),
                              *argv])
        return json.loads(out.strip().splitlines()[-1])

    def run_info(self, out_dir: str) -> dict:
        """What the CLI child said it ran on; anything but the wanted
        platform fails the phase."""
        with open(os.path.join(out_dir, "run_info.json")) as f:
            info = json.load(f)
        check(info["platform"] == self.want,
              f"{out_dir} ran on platform {info['platform']!r}, "
              f"not {self.want!r}")
        return info

    # --- phases -----------------------------------------------------------

    def corpus(self) -> None:
        """Seeded corpus and vocabularies, padded with filler entries to the
        published sizes so the embeddings and the fused head are full width
        (data only: filler ids never occur in the corpus)."""
        from fira_tpu.data.synthetic import write_corpus_dir

        for d, n in ((self.data, self.size["commits"]),
                     (self.data_small, self.size["small_commits"])):
            os.makedirs(d)
            write_corpus_dir(d, n_commits=n, seed=0)
            for fname, size in (("word_vocab.json", self.size["pad_words"]),
                                ("ast_change_vocab.json",
                                 self.size["pad_ast"])):
                path = os.path.join(d, fname)
                with open(path) as f:
                    vocab = json.load(f)
                check(size == 0 or len(vocab) <= size,
                      f"{fname}: {len(vocab)} entries exceed {size}")
                for i in range(len(vocab), size):
                    vocab[f"<filler{i}>"] = i
                with open(path, "w") as f:
                    json.dump(vocab, f)

    def _out_lines(self, out_dir: str, n: int) -> bytes:
        path = os.path.join(out_dir, "output_fira")
        check(os.path.exists(path), f"{path} missing")
        check(not os.path.exists(path + ".partial"),
              f"{path}.partial left behind")
        with open(path, "rb") as f:
            data = f.read()
        check(data.count(b"\n") == n,
              f"{path}: {data.count(b'\n')} lines for {n} test commits")
        return data

    def _n_test(self) -> int:
        with open(os.path.join(self.data, "all_index")) as f:
            return len(json.load(f)["test"])

    @staticmethod
    def _diff_lines(a: bytes, b: bytes) -> int:
        la, lb = a.split(b"\n"), b.split(b"\n")
        return sum(x != y for x, y in zip(la, lb)) + abs(len(la) - len(lb))

    @staticmethod
    def _losses(stdout: str) -> list:
        return [float(ln.rsplit("loss:", 1)[1]) for ln in stdout.splitlines()
                if ln.startswith("epoch:") and "loss:" in ln]

    def _mesh_flags(self) -> list:
        # `train` without --mesh puts every visible device on the data
        # axis; the main path is the one-chip path wherever it runs
        return ["--mesh", "1x1"] if self.n_devices > 1 else []

    def train(self) -> dict:
        out_dir = os.path.join(self.out, "train")
        stdout = self.cli("train", "train", *self.train_flags,
                          *self._mesh_flags(), "--out-dir", out_dir,
                          "--epochs", str(self.epochs))
        info = self.run_info(out_dir)
        losses = self._losses(stdout)
        check(len(losses) >= 2, f"train printed {len(losses)} loss lines")
        check(all(math.isfinite(x) for x in losses),
              f"non-finite loss in {losses}")
        check(losses[-1] <= losses[0] * 1.02,
              f"loss rose: first {losses[0]}, last {losses[-1]}")
        with open(os.path.join(out_dir, "train_process")) as f:
            gates = [ln for ln in f if "dev bleu" in ln]
        check(len(gates) >= 1, "no dev gate ran")
        ckpt = self._ckpt("train")
        return {"epochs": self.epochs, "loss_first": losses[0],
                "loss_last": losses[-1], "loss_lines": len(losses),
                "dev_gates": len(gates),
                "best_checkpoint": os.path.isdir(os.path.join(ckpt, "best")),
                "peak_bytes_in_use": info["peak_bytes_in_use"]}

    def _ckpt(self, phase: str) -> str:
        ckpt = os.path.join(self.out, phase, "ckpt")
        check(os.path.isdir(os.path.join(ckpt, "latest")),
              f"not run: the {phase} phase left no checkpoint")
        return ckpt

    def _decode(self, name: str, command: str, *extra: str) -> dict:
        out_dir = os.path.join(self.out, name)
        self.cli(name, command, *self.model_flags, "--out-dir", out_dir,
                 "--ckpt-dir", self._ckpt("train"), *extra)
        info = self.run_info(out_dir)
        self.outputs[name] = self._out_lines(out_dir, self._n_test())
        return {"lines": self._n_test(),
                "peak_bytes_in_use": info["peak_bytes_in_use"]}

    def test(self) -> dict:
        return self._decode("test", "test")

    def test_engine(self) -> dict:
        d = self._decode("test_engine", "test", "--engine")
        if "test" in self.outputs:
            d["lines_differing_from_test"] = self._diff_lines(
                self.outputs["test_engine"], self.outputs["test"])
        return d

    def serve(self) -> dict:
        d = self._decode("serve", "serve", "--serve-rate",
                         str(self.size["serve_rate"]))
        with open(os.path.join(self.out, "serve",
                               "serve_metrics.json")) as f:
            sv = json.load(f)["serve"]
        check(sv["completed"] == sv["offered"] == self._n_test(),
              f"serve completed {sv['completed']} of {sv['offered']} "
              f"offered ({self._n_test()} test commits)")
        shed = {k: sv[k] for k in ("shed_error", "shed_queue_full",
                                   "shed_deadline")}
        check(not any(shed.values()), f"serve shed requests: {shed}")
        pct = {k: sv[k] for k in ("p50_ttft_s", "p99_ttft_s", "p50_e2e_s",
                                  "p99_e2e_s")}
        check(all(isinstance(v, (int, float)) and math.isfinite(v)
                  for v in pct.values()), f"serve percentiles: {pct}")
        d.update(completed=sv["completed"], offered_rate=self.size["serve_rate"],
                 **pct)
        for other in ("test", "test_engine"):
            if other in self.outputs:
                d[f"lines_differing_from_{other}"] = self._diff_lines(
                    self.outputs["serve"], self.outputs[other])
        return d

    # float32 kernel against a float32 oracle whose matmul runs at full
    # precision: agreement is rounding-level (largest relative error seen
    # on the v5e 5.4e-7, PERF.md "Bring-up"); one bf16 pass anywhere in the
    # kernel is ~4e-3 and fails
    KERNEL_TOL = 1e-5

    def kernel(self) -> dict:
        res = self.child("kernel", "--shapes",
                         json.dumps(self.size["kernel_shapes"]))
        check(res["platform"] == self.want,
              f"kernel ran on {res['platform']!r}")
        for k in res["kernels"]:
            check(k["interpret"] == self.rehearse,
                  f"kernel {k['name']} interpret={k['interpret']}")
            check(k["finite"], f"kernel {k['name']}: non-finite scores")
            worst = max(k["fwd_rel_err"], *k["bwd_rel_err"].values())
            check(worst <= self.KERNEL_TOL,
                  f"kernel {k['name']} disagrees with the reference: {k}")
        return {"kernels": res["kernels"], "tolerance": self.KERNEL_TOL}

    def train_pallas(self) -> dict:
        out_dir = os.path.join(self.out, "train_pallas")
        flags = [f if f != self.data else self.data_small
                 for f in self.train_flags]
        stdout = self.cli("train_pallas", "train", *flags,
                          *self._mesh_flags(), "--out-dir", out_dir,
                          "--copy-head", "pallas", "--fused-steps", "1",
                          "--epochs", "1")
        info = self.run_info(out_dir)
        losses = self._losses(stdout)
        check(losses and all(math.isfinite(x) for x in losses),
              f"train --copy-head pallas losses: {losses}")
        # copy_score._use_interpret: only the cpu backend interprets
        return {"loss": losses[0], "interpret": info["platform"] == "cpu",
                "peak_bytes_in_use": info["peak_bytes_in_use"]}

    def sync(self) -> dict:
        res = self.child("sync", "--data-dir", self.data, "--cli-flags",
                         json.dumps(self.train_flags), "--reps", "5")
        check(res["platform"] == self.want, f"sync ran on {res['platform']!r}")
        check(math.isfinite(res["loss_last"]), f"sync loss {res['loss_last']}")
        b, f = sorted(res["block_until_ready_s"]), sorted(res["float_loss_s"])
        med_b, med_f = b[len(b) // 2], f[len(f) // 2]
        spread = max(b[-1] - b[0], f[-1] - f[0])
        return {"steps_per_dispatch": res["steps_per_dispatch"],
                "batch": res["batch"],
                "block_until_ready_s": {"median": med_b, "min": b[0],
                                        "max": b[-1]},
                "float_loss_s": {"median": med_f, "min": f[0], "max": f[-1]},
                "agree_within_spread": abs(med_b - med_f) <= max(
                    spread, 0.02 * med_f)}

    def adjacency(self) -> dict:
        res = self.child("adjacency", "--data-dir", self.data, "--cli-flags",
                         json.dumps(self.train_flags), "--shapes",
                         json.dumps(self.size["adjacency_batches"]))
        check(res["platform"] == self.want,
              f"adjacency ran on {res['platform']!r}")
        check(res["sort_edges"], "adjacency: --perf production did not set "
                                 "the sorted-indices promise")
        for row in res["rows"]:
            check(row["cells_program"] == row["cells_plain"] > 0
                  and row["values_equal"],
                  f"adjacency at batch {row['batch']}: the program filled "
                  f"{row['cells_program']} cells, a plain float32 scatter "
                  f"of the same triplets {row['cells_plain']}: {row}")
        return {"dtype": res["dtype"], "rows": res["rows"]}

    def _placement(self, info: dict) -> dict:
        """Every device holds its share: with memory stats, no device's peak
        may be under a tenth of the largest (device 0 also holds the
        restored checkpoint, so shares are not equal)."""
        peaks = info["peak_bytes_in_use"]
        if peaks is None:
            return {"placement": "not checked: the backend reports no "
                                 "memory stats"}
        check(len(peaks) >= 4 and min(peaks[:4]) >= 0.1 * max(peaks),
              f"devices do not each hold their share: peaks {peaks}")
        return {"placement": "ok", "peak_bytes_in_use": peaks}

    def train_mesh(self) -> dict:
        out_dir = os.path.join(self.out, "train_mesh")
        stdout = self.cli("train_mesh", "train", *self.train_flags,
                          "--mesh", "4x1", "--batch-size",
                          str(4 * self.size["batch"]), "--fused-steps", "1",
                          "--out-dir", out_dir, "--epochs", "1")
        losses = self._losses(stdout)
        check(losses and all(math.isfinite(x) for x in losses),
              f"train --mesh 4x1 losses: {losses}")
        return {"global_batch": 4 * self.size["batch"], "loss": losses[0],
                **self._placement(self.run_info(out_dir))}

    def test_fleet(self) -> dict:
        out_dir = os.path.join(self.out, "test_fleet")
        # no dev gate ran in train_mesh's one epoch, so this decodes the
        # LATEST state; and it is the one decode here under the production
        # decode set (factored top-k on the engine)
        self.cli("test_fleet", "test", *self.train_flags, "--engine",
                 "--engine-replicas", "4", "--out-dir", out_dir,
                 "--ckpt-dir", self._ckpt("train_mesh"))
        self._out_lines(out_dir, self._n_test())
        return {"lines": self._n_test(),
                **self._placement(self.run_info(out_dir))}

    # --- driver -----------------------------------------------------------

    def cache_entries(self) -> int:
        d = self.cache_dir
        if not os.path.isdir(d):
            return 0
        return sum(1 for _r, _d, files in os.walk(d) for f in files
                   if not f.endswith("-atime"))

    def main(self, only) -> int:
        # nothing is printed on stdout until a device has answered
        if os.path.exists(self.out):
            if os.listdir(self.out) and not os.path.exists(
                    os.path.join(self.out, MARKER)):
                print(f"chip_smoke: {self.out} exists and is not a "
                      f"chip_smoke output directory; refusing to wipe it",
                      file=sys.stderr)
                return EXIT_WONT_WIPE
            shutil.rmtree(self.out)
        os.makedirs(self.logs)
        open(os.path.join(self.out, MARKER), "w").close()
        try:
            dev = self.child("probe")
            check(dev["platform"] == self.want,
                  f"jax found platform {dev['platform']!r} "
                  f"({dev['device_kind']}), not {self.want!r}")
        except PhaseFailed as e:
            print(f"chip_smoke: no {self.want.upper()} to run on — no phase "
                  f"was run.\n{e}", file=sys.stderr)
            return EXIT_NO_DEVICE
        self.n_devices = dev["n_devices"]
        entries_before = self.cache_entries()
        print(f"chip_smoke: {dev['n_devices']} x {dev['device_kind']} "
              f"({dev['platform']}), compile cache {self.cache_dir} "
              f"({entries_before} entries)", flush=True)

        todo = [p for p in PHASES
                if (only is None or p in only)
                and (p not in MULTICHIP or self.n_devices >= 4)]
        # a failed phase fails the run but does not end it (a decode phase
        # whose checkpoint is missing says so): one call names every failure
        failed = []
        for name in ["corpus", *todo]:
            t0, entries0 = time.monotonic(), self.cache_entries()
            try:
                check("corpus" not in failed, "not run: no corpus")
                detail = getattr(self, name)() or {}
            except PhaseFailed as e:
                detail = {"error": str(e)}
                failed.append(name)
                print(f"chip_smoke: {name}: {e}", file=sys.stderr)
            secs = round(time.monotonic() - t0, 1)
            ok = name not in failed
            self.phases[name] = {
                "ok": ok, "seconds": secs,
                "cache_entries_added": self.cache_entries() - entries0,
                "detail": detail}
            print(f"chip_smoke: {name} {'ok' if ok else 'FAILED'} "
                  f"({secs}s)", flush=True)

        diffs = [v for ph in self.phases.values()
                 for k, v in ph["detail"].items()
                 if k.startswith("lines_differing_from_")]
        # one process per chip: had this parent touched jax on a TPU host,
        # it would hold the chip its children need
        parent_clean = "jax" not in sys.modules
        if not parent_clean:
            print("chip_smoke: the parent process imported jax",
                  file=sys.stderr)
        ok = not failed and parent_clean
        summary = {
            "ok": ok,
            "platform": dev["platform"], "device_kind": dev["device_kind"],
            "n_devices": dev["n_devices"], "versions": dev["versions"],
            **({"rehearsal": True} if self.rehearse else {}),
            **({"partial": True} if only is not None else {}),
            "config": {"name": self.size["config"],
                       "batch": self.size["batch"], "dtype": "bfloat16",
                       "word_vocab": self.size["pad_words"] or "corpus",
                       "commits": self.size["commits"]},
            "compile_cache": {"dir": self.cache_dir,
                              "entries_before": entries_before,
                              "entries_after": self.cache_entries()},
            "engine_bytes_equal": (not any(diffs)) if diffs else None,
            "lines_differing": max(diffs) if diffs else None,
            "wall_seconds": round(time.monotonic() - self.t_start, 1),
            "parent_imported_jax": not parent_clean,
            "phases": self.phases,
            "claim": None,
        }
        # the summary and the children's logs go where the chip tool brings
        # files back from; checkpoints and corpora stay in the output dir
        keep = os.path.join(REPO, "chiprun_out", "chip_smoke_rehearsal"
                            if self.rehearse else "chip_smoke")
        os.makedirs(keep, exist_ok=True)
        shutil.copytree(self.logs, os.path.join(keep, "logs"),
                        dirs_exist_ok=True)
        with open(os.path.join(keep, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
        print("chip_smoke: summary " + json.dumps(summary), flush=True)
        # the result line: these keys and no others
        print(json.dumps({"ok": ok, "device": {
            "platform": dev["platform"], "kind": dev["device_kind"],
            "count": dev["n_devices"]}}), flush=True)
        return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="fira-tiny on JAX_PLATFORMS=cpu, kernel interpreted")
    ap.add_argument("--out-dir", default=os.path.join(REPO, "smoke_out"),
                    help="corpus, checkpoints and outputs (wiped first)")
    ap.add_argument("--only", default=None, metavar="PHASE[,PHASE]",
                    help=f"debugging: run only these of {', '.join(PHASES)}")
    ap.add_argument("--child", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    ap.add_argument("--shapes", help=argparse.SUPPRESS)
    ap.add_argument("--data-dir", help=argparse.SUPPRESS)
    ap.add_argument("--cli-flags", help=argparse.SUPPRESS)
    ap.add_argument("--reps", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        CHILDREN[args.child](args)
        return 0
    only = None
    if args.only is not None:
        only = args.only.split(",")
        unknown = sorted(set(only) - set(PHASES))
        if unknown:
            ap.error(f"unknown phase(s) {unknown}; choose from {PHASES}")
    if not os.path.isdir(os.path.join(REPO, "fira_tpu")):
        print(f"chip_smoke: {REPO} holds no fira_tpu package; there is "
              f"nothing to drive — no phase was run.", file=sys.stderr)
        return EXIT_NO_REPO
    return Smoke(args).main(only)


if __name__ == "__main__":
    sys.exit(main())
