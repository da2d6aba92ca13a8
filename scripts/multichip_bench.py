"""Multi-chip scaling bench on virtual CPU devices -> MULTICHIP_r06.json.

Measures the COMPOSED production stack — grouped bucketed train dispatch
and the replicated slot-engine decode fleet — at 1/2/4/8 logical devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``, one fresh
process per count: the device count is fixed at backend init). Per
device-count row: train steps/s + commits/s + feed_stall_frac, fleet
aggregate commits/s + per-replica slot occupancy.

Scaling mode is WEAK: the per-shard train batch and the per-replica slot
arena are fixed, so the aggregate stream grows with the device count —
the GShard framing, and the honest one on a CPU host whose core count
caps real parallelism: per-device-count rows measure that the sharded
program family compiles, stays retrace-free, keeps every shard fed, and
that aggregate throughput rises as serialized per-dispatch host overhead
amortizes over more shards. Absolute numbers are CPU proxies (the
flagship numbers live in BENCH_r*/docs/PERF.md); the scaling SHAPE — and
its saturation at the host's core count — is the artifact.

Modes:
  (default)      orchestrate: one subprocess per device count, write
                 MULTICHIP_r06.json at the repo root, echo it to stdout.
  --devices N    one measurement row in THIS process (forces the CPU
                 backend with N virtual devices; must be a fresh process).
  --smoke        2-device fast sanity leg for scripts/check.sh: one
                 sharded grouped-train window + a 2-replica fleet drain,
                 both under the compile guard (zero post-warmup compiles).

Env knobs: FIRA_MC_DEVICES (default "1,2,4,8"), FIRA_MC_PER_SHARD_BATCH
(default 8), FIRA_MC_WINDOWS (default 5), FIRA_MC_TRAIN_DATA_FACTOR
(epoch size = factor * global batch, default 6), FIRA_MC_FLEET_CHUNKS
(decode chunks PER replica, default 4), FIRA_MC_CHILD_TIMEOUT (s/child,
default 600).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

RECORD = os.path.join(REPO_ROOT, "MULTICHIP_r06.json")
_COUNT_FLAG = "--xla_force_host_platform_device_count"


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


# --------------------------------------------------------------------------
# worker: one device count, one row
# --------------------------------------------------------------------------

def measure(n_devices: int) -> dict:
    from fira_tpu.utils.startup import force_cpu_backend

    force_cpu_backend(n_virtual_devices=n_devices)

    import jax
    import numpy as np

    from fira_tpu.config import fira_tiny
    from fira_tpu.data import buckets as buckets_lib
    from fira_tpu.data import grouping
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.data.synthetic import make_memory_split
    from fira_tpu.decode.beam import eos_biased_params
    from fira_tpu.model.model import FiraModel
    from fira_tpu.parallel import fleet as fleet_lib
    from fira_tpu.parallel import mesh as pmesh
    from fira_tpu.train import step as step_lib
    from fira_tpu.train.state import init_state

    devices = jax.devices("cpu")[:n_devices]
    if len(devices) < n_devices:
        raise RuntimeError(f"need {n_devices} devices, have {len(devices)}: "
                           f"run in a fresh process")

    pb = int(os.environ.get("FIRA_MC_PER_SHARD_BATCH", "8"))
    windows = int(os.environ.get("FIRA_MC_WINDOWS", "5"))
    K = 2  # fused device loop — the composed production shape
    cfg = fira_tiny(batch_size=pb * n_devices, buckets=((16, 256, 8),),
                    fused_steps=K, test_batch_size=8)
    factor = int(os.environ.get("FIRA_MC_TRAIN_DATA_FACTOR", "6"))
    n_data = factor * cfg.batch_size
    cfg, split, _ = make_memory_split(cfg, n_data, seed=0)

    # --- train leg: grouped bucketed dispatch over the (data, model) mesh
    mesh = pmesh.make_mesh(n_data=n_devices, n_model=1, devices=devices)
    errs = pmesh.divisibility_errors(cfg, n_devices)
    if errs:
        raise ValueError("; ".join(errs))
    table = buckets_lib.bucket_table(cfg)
    ext = buckets_lib.sample_extents(split, cfg)
    assignment = buckets_lib.assign_buckets(ext, table)
    plan = grouping.grouped_plan(split, cfg, batch_size=cfg.batch_size,
                                 group_size=K, accum=False, shuffle=True,
                                 seed=0, epoch=0, table=table,
                                 assignment=assignment)
    acct = grouping.plan_report(split, cfg, plan, batch_size=cfg.batch_size,
                                extents=ext)
    model = FiraModel(cfg)
    sample = make_batch(split, np.arange(cfg.batch_size), cfg,
                        batch_size=cfg.batch_size)
    state = init_state(model, cfg, sample)
    state = state.replace(params=pmesh.shard_params(state.params, mesh))
    train_step = step_lib.jit_train_step(model, cfg, mesh, state, sample)
    stacked = step_lib.stack_batches([sample] * K)
    grouped_step = step_lib.jit_multi_step(model, cfg, mesh, state, stacked)

    def train_pass():
        nonlocal state
        feed = Feeder(grouping.grouped_assembly_tasks(
                          split, plan, cfg, batch_size=cfg.batch_size),
                      num_workers=cfg.feeder_workers,
                      depth=cfg.feeder_depth,
                      sharding=pmesh.feed_shardings(mesh))
        m = None
        with feed:
            for item in feed:
                dispatch = (grouped_step if item.host["valid"].ndim == 2
                            else train_step)
                state, m = dispatch(state, item.device)
        # honest sync: materialize the last loss (train/loop._materialize)
        loss = float(np.asarray(jax.device_get(m["loss"])).ravel()[-1])
        if not np.isfinite(loss):
            raise RuntimeError(f"non-finite loss {loss}")
        return feed.stats()

    train_pass()  # warmup: compiles the (geometry x entrypoint x K) family
    times, stalls = [], []
    for _ in range(windows):
        t0 = time.perf_counter()
        st = train_pass()
        dt = time.perf_counter() - t0
        times.append(dt)
        stalls.append(min(1.0, st["feed_stall_s"] / dt))
    dt_train = _median(times)
    train_row = {
        "per_shard_batch": pb,
        "global_batch": cfg.batch_size,
        "epoch_commits": acct["commits"],
        "dispatches": acct["dispatches"],
        "steps_per_sec": round(acct["steps_dispatched"] / dt_train, 3),
        "commits_per_sec": round(acct["commits"] / dt_train, 2),
        "feed_stall_frac": round(_median(stalls), 4),
        "padding_frac_dispatched": acct["padding_frac_dispatched"],
    }

    # --- fleet leg: N engine replicas over one shared admission queue
    n_chunks = int(os.environ.get("FIRA_MC_FLEET_CHUNKS", "4")) * n_devices
    cfg_dec = cfg.replace(decode_engine=True, engine_replicas=n_devices)
    params_dec = eos_biased_params(jax.device_get(state.params), delta=4.0)
    rng = np.random.RandomState(0)
    chunks = [rng.choice(n_data, cfg_dec.test_batch_size, replace=True)
              for _ in range(n_chunks)]
    model_dec = FiraModel(cfg_dec)
    fleet = fleet_lib.EngineFleet(model_dec, params_dec, cfg_dec,
                                  replicas=n_devices, devices=devices)

    def fleet_pass():
        tasks = ((lambda ix=ix: make_batch(split, ix, cfg_dec,
                                           batch_size=cfg_dec.test_batch_size))
                 for ix in chunks)
        with Feeder(tasks, num_workers=cfg.feeder_workers,
                    depth=cfg.feeder_depth, put=False) as feed:
            n = sum(1 for _ in fleet.run(feed))
        return n

    from fira_tpu.decode.engine import EngineStats

    fleet_pass()  # warmup: compiles each replica's prefill/step/insert
    ftimes = []
    n_dec = 0
    for _ in range(max(2, windows - 2)):
        for eng in fleet.engines:  # occupancy of the timed runs only
            eng.stats = EngineStats(slots=eng.slots)
        t0 = time.perf_counter()
        n_dec = fleet_pass()
        ftimes.append(time.perf_counter() - t0)
    dt_fleet = _median(ftimes)
    fsum = fleet.stats.summary()
    fleet_row = {
        "replicas": n_devices,
        "slots_per_replica": fleet.engines[0].slots,
        "stream_commits": n_dec,
        "commits_per_sec": round(n_dec / dt_fleet, 2),
        "slot_occupancy": fsum["slot_occupancy"],
        "per_replica_occupancy": fsum["per_replica_occupancy"],
        "per_replica_commits": fsum["per_replica_commits"],
    }

    return {"n_devices": n_devices, "host_cores": os.cpu_count(),
            "train": train_row, "fleet": fleet_row}


# --------------------------------------------------------------------------
# smoke: the check.sh 2-device tier-1 leg
# --------------------------------------------------------------------------

def smoke() -> None:
    """Fast 2-device sanity: one sharded grouped-bucketed train window and
    a 2-replica fleet drain, both under the compile guard. Keeps the mesh
    paths green in CI without the full scaling sweep."""
    from fira_tpu.utils.startup import force_cpu_backend

    force_cpu_backend(n_virtual_devices=2)

    import jax
    import numpy as np

    from fira_tpu.analysis import sanitizer
    from fira_tpu.config import fira_tiny
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.data.synthetic import make_memory_split
    from fira_tpu.decode.beam import eos_biased_params
    from fira_tpu.model.model import FiraModel
    from fira_tpu.parallel import fleet as fleet_lib
    from fira_tpu.parallel import mesh as pmesh
    from fira_tpu.train import step as step_lib
    from fira_tpu.train.state import init_state

    devices = jax.devices("cpu")[:2]
    assert len(devices) == 2, f"need 2 virtual devices, have {len(devices)}"
    cfg = fira_tiny(batch_size=8, test_batch_size=6)
    cfg, split, _ = make_memory_split(cfg, 24, seed=0)
    mesh = pmesh.make_mesh(n_data=2, n_model=1, devices=devices)
    assert not pmesh.divisibility_errors(cfg, 2)

    model = FiraModel(cfg)
    sample = make_batch(split, np.arange(8), cfg, batch_size=8)
    state = init_state(model, cfg, sample)
    state = state.replace(params=pmesh.shard_params(state.params, mesh))
    stacked = step_lib.stack_batches([sample] * 2)
    grouped = step_lib.jit_multi_step(model, cfg.replace(fused_steps=2),
                                      mesh, state, stacked)
    tasks = [lambda i=i: make_batch(split, np.arange(i * 8, i * 8 + 8), cfg,
                                    batch_size=8) for i in range(2)]

    def stack_task():
        return step_lib.stack_batches([t() for t in tasks])

    with Feeder([stack_task], num_workers=1, depth=2,
                sharding=pmesh.feed_shardings(mesh)) as feed:
        for item in feed:
            state, m = grouped(state, item.device)
    losses = np.asarray(jax.device_get(m["loss"]))
    assert losses.shape == (2,) and np.isfinite(losses).all(), losses
    print(f"multichip smoke: 2-device sharded fused scan OK, "
          f"losses={losses.round(4).tolist()}", flush=True)

    cfg_dec = cfg.replace(decode_engine=True, engine_replicas=2)
    params = eos_biased_params(jax.device_get(state.params), delta=4.0)
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        fleet = fleet_lib.EngineFleet(FiraModel(cfg_dec), params, cfg_dec,
                                      replicas=2, devices=devices,
                                      guard=guard)
        guard.declare(fleet.labels())
        chunks = [np.arange(0, 6), np.arange(6, 12), np.arange(12, 18)]
        tasks2 = ((lambda ix=ix: make_batch(split, ix, cfg_dec,
                                            batch_size=6)) for ix in chunks)
        with Feeder(tasks2, num_workers=1, depth=2, put=False) as feed:
            positions = sorted(item.position for item in fleet.run(feed))
        assert positions == list(range(18)), positions
        assert guard.compiles_after_warmup() == 0, guard._seen
    fsum = fleet.stats.summary()
    assert fsum["commits"] == 18 and all(
        c > 0 for c in fsum["per_replica_commits"]), fsum
    print(f"multichip smoke: 2-replica fleet drained 18 commits OK "
          f"(per-replica {fsum['per_replica_commits']}, zero post-warmup "
          f"compiles)", flush=True)


# --------------------------------------------------------------------------
# orchestrator: one subprocess per device count -> MULTICHIP_r06.json
# --------------------------------------------------------------------------

def _last_json_line(out: str) -> dict | None:
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None

def orchestrate() -> int:
    counts = [int(x) for x in os.environ.get(
        "FIRA_MC_DEVICES", "1,2,4,8").split(",")]
    timeout = float(os.environ.get("FIRA_MC_CHILD_TIMEOUT", "600"))
    rows, errors = [], []
    for n in counts:
        env = os.environ.copy()
        env["JAX_PLATFORMS"] = "cpu"
        # pin the EXACT virtual device count for the child: the guard only
        # raises a preexisting count, so an inherited 8 would leak into a
        # 2-device child
        import re

        xf = re.sub(_COUNT_FLAG + r"=\d+", "",
                    env.get("XLA_FLAGS", "")).strip()
        env["XLA_FLAGS"] = f"{xf} {_COUNT_FLAG}={n}".strip()
        t0 = time.time()
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--devices", str(n)],
                text=True, timeout=timeout, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE)
            row = _last_json_line(p.stdout) if p.returncode == 0 else None
            if row is None:
                errors.append({"n_devices": n, "rc": p.returncode,
                               "tail": (p.stderr or p.stdout).strip()[-400:]})
                print(f"devices={n}: FAILED rc={p.returncode}",
                      file=sys.stderr)
            else:
                rows.append(row)
                print(f"devices={n}: train {row['train']['commits_per_sec']}"
                      f" c/s, fleet {row['fleet']['commits_per_sec']} c/s "
                      f"({time.time() - t0:.0f}s)", file=sys.stderr)
        except subprocess.TimeoutExpired:
            errors.append({"n_devices": n, "rc": None,
                           "tail": f"timeout after {timeout:.0f}s"})
            print(f"devices={n}: TIMEOUT", file=sys.stderr)

    def monotonic(leg: str) -> bool:
        vals = [r[leg]["commits_per_sec"] for r in rows
                if r["n_devices"] <= 4]
        return len(vals) >= 3 and all(b > a for a, b in zip(vals, vals[1:]))

    record = {
        "metric": "multichip_scaling",
        "unit": "commits/sec aggregate (weak scaling: fixed per-shard "
                "batch / per-replica arena)",
        "host_cores": os.cpu_count(),
        "config": "fira-tiny, buckets (16:256:8)+full, fused K=2, "
                  "engine fleet 1 replica/device",
        "rows": rows,
        "monotonic_train_1_to_4": monotonic("train"),
        "monotonic_fleet_1_to_4": monotonic("fleet"),
        **({"errors": errors} if errors else {}),
    }
    if rows:
        with open(RECORD, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    else:
        # every child failed: keep the previously committed artifact
        # intact instead of clobbering it with an empty record
        print(f"no successful rows; leaving {RECORD} untouched",
              file=sys.stderr)
    print(json.dumps(record))
    return 0 if rows and not errors else 1


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    elif "--devices" in sys.argv:
        n = int(sys.argv[sys.argv.index("--devices") + 1])
        print(json.dumps(measure(n)))
    else:
        raise SystemExit(orchestrate())
