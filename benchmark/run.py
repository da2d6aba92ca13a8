"""The benchmark's one command: one process, one cell, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reaches the chip, builds data and weights from ``--seed``, warms this cell's
programs only, measures for ``--seconds``, decides ``correct`` against the
plain reference, prints the result as the last line of standard output.

Nothing about any cell lives here: the cell's configuration, traffic mix,
limits and per-layer metrics are data files found by the names in
``BENCHMARK.json`` (``configs/``, ``traffic/``, ``limits/``,
``layer_metrics/`` + ``readers/``), and a traffic mix names its driver
(``drivers/``). ``--allow-cpu`` is for the tests: it stamps
``device.platform: cpu`` and reports no time, rate or share.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")

EXIT_NO_DEVICE = 5


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--allow-cpu", action="store_true")
    return p.parse_args(argv)


def load_manifest(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def find_cell(manifest: Dict, name: str):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    return cell, config


def metrics_for(manifest: Dict, group: str, cell: str) -> List[Dict]:
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def read_layer_metrics(manifest: Dict, cell: str, reader_ctx: Dict) -> Dict:
    """Every per-layer metric this cell lists, each through the reader its
    own file names; a reader that finds nothing returns None and the metric
    is left out of the line."""
    out = {}
    for m in metrics_for(manifest, "per_layer", cell):
        with open(os.path.join(HERE, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(reader_ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(args, manifest_path: Optional[str] = None,
        data_root: Optional[str] = None, extra=()) -> Dict:
    """One run; returns the result line's object. ``extra`` (readings.py
    only) asks the driver for the control's and the faults' readings too,
    which come back under ``info.extra_numbers``."""
    manifest_path = manifest_path or os.path.join(ROOT, "BENCHMARK.json")
    data_root = data_root or HERE
    manifest = load_manifest(manifest_path)
    cell, config_entry = find_cell(manifest, args.workload)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    # the system under test: without it there is nothing to run
    from fira_tpu.utils import startup

    from benchmark import check, common, trace_reduce

    if args.allow_cpu:
        startup.force_cpu_backend()
    import jax

    if not args.allow_cpu:
        startup.configure_compile_cache()
        # programs that compile in under a second are redone by every
        # process otherwise (PERF.md, PR 21)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    platform = devs[0].platform
    if not args.allow_cpu and (platform != "tpu" or len(devs) < cell["chips"]):
        print(f"benchmark: needs {cell['chips']} TPU chip(s), found "
              f"{len(devs)} x {platform!r}", file=sys.stderr)
        raise SystemExit(EXIT_NO_DEVICE)

    with open(os.path.join(os.path.dirname(manifest_path),
                           config_entry["file"])) as f:
        config = json.load(f)
    traffic = common.load_json(data_root, "traffic", cell["traffic"])
    peaks = common.load_json(HERE, ".", "peaks")
    kind = devs[0].device_kind
    on_chip = platform == "tpu"
    if on_chip and kind not in peaks:
        raise SystemExit(f"no peaks on record for device_kind {kind!r}; add "
                         f"it to benchmark/peaks.json with its source")

    trace_dir = os.path.join(OUT_DIR, "trace")
    ctx = {"config": config, "traffic": traffic, "seed": args.seed,
           "seconds": float(args.seconds), "trace": bool(args.trace),
           "trace_dir": trace_dir, "out_dir": OUT_DIR, "root": data_root,
           "extra": tuple(extra)}
    driver = importlib.import_module(f"benchmark.drivers.{traffic['driver']}")
    res = driver.run(ctx)

    device = {"platform": platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    result = {"correct": False, "attempted": int(res["attempted"]),
              "failed": int(res["failed"]), "metrics": {}, "device": device}
    trace = None
    if args.trace and res["tracer"].traced:
        trace = trace_reduce.reduce_trace(trace_dir, common.HOST_SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    if on_chip:
        if args.trace:
            result["metrics"] = read_layer_metrics(
                manifest, cell["name"], {
                    "counters": res["counters"], "records": res["records"],
                    "trace": trace, "window_s": res["window_s"],
                    "peak_flops": peaks[kind]["bf16_flops_per_s"]})
        else:
            values = {**res["end_to_end"],
                      "setup_s": res["setup_end"] - T_START}
            for m in metrics_for(manifest, "end_to_end", cell["name"]):
                if values.get(m["name"]) is None:
                    raise SystemExit(f"the {traffic['driver']} driver reports "
                                     f"no {m['name']} for {cell['name']}")
                result["metrics"][m["name"]] = {
                    "value": float(values[m["name"]]), "unit": m["unit"]}
    result["info"] = {**res["info"], "window_s": res["window_s"],
                      "setup_s": res["setup_end"] - T_START,
                      "counters": res["counters"],
                      "numbers": {k: v for k, v in res["numbers"].items()
                                  if not k.startswith("_")},
                      "where": res["numbers"].get("_where")}
    if extra:
        result["info"]["extra_numbers"] = res["extra_numbers"]
    verdict = check.judge(res["numbers"],
                          check.load_limits(data_root, cell["name"]))
    if res["attempted"] <= 0:
        verdict["correct"] = False
    result["correct"] = verdict["correct"]
    result["check"] = verdict["check"]     # last, each number by its limit
    return result


def main(argv=None, manifest_path: Optional[str] = None,
         data_root: Optional[str] = None) -> int:
    args = _args(argv)
    result = run(args, manifest_path, data_root)
    sys.stdout.flush()
    from benchmark import check

    print("\n".join(check.format_check(result["check"])), file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
