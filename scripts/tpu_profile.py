"""Capture a jax.profiler trace of the fira-full train step on the live
chip and print the top ops by self time (parsed offline with
tensorboard_plugin_profile — no TensorBoard UI needed).

The result attributes the train step op by op.
"""

import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from fira_tpu.config import fira_full
from fira_tpu.data.batching import make_batch
from fira_tpu.data.synthetic import make_memory_split
from fira_tpu.model.model import FiraModel
from fira_tpu.train import step as step_lib
from fira_tpu.train.state import init_state

from fira_tpu.utils.startup import configure_compile_cache  # noqa: E402

configure_compile_cache()

TRACE_DIR = os.environ.get("PROFILE_DIR", "/tmp/fira_tpu_trace")
BATCH = int(os.environ.get("PROFILE_BATCH", "170"))
if os.environ.get("PROFILE_CPU") == "1":
    # CPU mode: op-relative attribution only (CPU cost model != TPU), but
    # op NAMES match — a grossly dominant op (e.g. the adjacency scatter)
    # shows up on either backend
    from fira_tpu.utils.startup import force_cpu_backend

    force_cpu_backend()

cfg = fira_full(batch_size=BATCH, compute_dtype="bfloat16")
# PROFILE_OVERRIDES: JSON FiraConfig fields, e.g. the production knob set
# (minus fused_steps — this script profiles the single-step program):
#   PROFILE_OVERRIDES='{"rng_impl":"rbg","sort_edges":true,
#                       "stable_residual":false,"copy_head_remat":false,
#                       "encoder_buffer":"split"}'
_over = json.loads(os.environ.get("PROFILE_OVERRIDES", "{}"))
if _over:
    cfg = cfg.replace(**_over)
cfg, split, _ = make_memory_split(cfg, 256, seed=0,
                                  pad_vocab_to=24650, pad_ast_vocab_to=71)
rng = np.random.RandomState(0)
host = [make_batch(split, rng.choice(256, BATCH, replace=True), cfg)
        for _ in range(2)]
model = FiraModel(cfg, dtype=jnp.bfloat16)
state = init_state(model, cfg, host[0])
step = jax.jit(step_lib.make_train_step(model, cfg), donate_argnums=(0,))
dev = jax.device_put(host)
jax.block_until_ready(dev)

# warmup/compile + queue-fill
state, m = step(state, dev[0])
_ = float(m["loss"])
for i in range(6):
    state, m = step(state, dev[i % 2])
_ = float(m["loss"])

jax.profiler.start_trace(TRACE_DIR)
for i in range(8):
    state, m = step(state, dev[i % 2])
_ = float(m["loss"])  # D2H materialization: all 8 steps really executed
jax.profiler.stop_trace()
print(json.dumps({"trace_dir": TRACE_DIR}), flush=True)

# ---- offline parse: top ops by summed duration per plane ----------------
# tensorboard_plugin_profile's native converter is broken against this TF
# build (no xspace_to_tools_data symbol), so parse the XSpace proto
# directly; the vendored schema lives under tensorflow.tsl.
xplanes = sorted(glob.glob(os.path.join(
    TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")))
if not xplanes:
    print(json.dumps({"error": "no xplane.pb produced"}))
    sys.exit(1)

from tensorflow.tsl.profiler.protobuf import xplane_pb2  # noqa: E402

space = xplane_pb2.XSpace()
with open(xplanes[-1], "rb") as f:
    space.ParseFromString(f.read())

report = []
for plane in space.planes:
    by_name: dict = {}
    for line in plane.lines:
        for ev in line.events:
            md = plane.event_metadata[ev.metadata_id]
            rec = by_name.setdefault(md.name, [0, 0])
            rec[0] += ev.duration_ps
            rec[1] += 1
    if not by_name:
        continue
    top = sorted(by_name.items(), key=lambda kv: -kv[1][0])[:30]
    report.append({
        "plane": plane.name,
        "total_ms": round(sum(v[0] for v in by_name.values()) / 1e9, 2),
        "top_ops": [{"name": n, "ms": round(ps / 1e9, 3), "count": c}
                    for n, (ps, c) in top],
    })

report = {"config_overrides": _over, "batch": BATCH,
          "cpu_backend": os.environ.get("PROFILE_CPU") == "1",
          "planes": report}
out = os.path.join(TRACE_DIR, "op_times.json")
with open(out, "w") as f:
    json.dump(report, f, indent=1)
# The aggregated table is the committable evidence (the raw xplane trace is
# tens of MB of /tmp); land it in docs/ so it can be committed.
# Provenance rules: the parity-default TPU capture owns
# TPU_OP_TIMES.json, an overridden config gets its own file, and a CPU
# capture never overwrites TPU evidence.
if not report["cpu_backend"]:
    slug = "_".join(f"{k}-{_over[k]}" for k in sorted(_over))
    slug = "".join(c if c.isalnum() or c in "-_" else "-" for c in slug)
    name = ("TPU_OP_TIMES.json" if not _over
            else f"TPU_OP_TIMES_{slug}.json")
    repo_out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", name)
    with open(repo_out, "w") as f:
        json.dump(report, f, indent=1)
for plane in report["planes"]:
    print(json.dumps({"plane": plane["plane"], "total_ms": plane["total_ms"],
                      "top5": plane["top_ops"][:5]}), flush=True)
print(json.dumps({"path": out}), flush=True)
