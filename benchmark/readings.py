"""Readings that the limits of ``limits/<workload>.json`` and the rates of the
serve mixes are set from, read on the chip at the cell's own size, several
seeds in one process:

    python3 benchmark/readings.py --workload <name> --seeds 1,2,3 --seconds 3 \\
        [--extra control,half_batch] [--set traffic.rate_rps=40 ...]

For each seed one short run of the cell (its own driver, its own timed path)
prints the numbers ``correct`` compares — the LOWER readings — and, with
``--extra``, the same numbers for the control (the reference in float8 put in
the program's place) and for the fault "half of the batch left out": the
UPPER readings. ``--set`` runs a variant of the cell — a key of its traffic
mix (``traffic.<key>``) or of its configuration (``config.<group>.<key>``)
changed for this call alone, written under ``.bench_out/`` — which is how a
sweep of offered rates or of sizes is made. The benchmark's own runs never
come here.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _variant(workload: str, sets) -> tuple:
    """A copy of the cell's manifest entry, configuration, traffic mix and
    limits with ``sets`` applied -> (manifest path, data root)."""
    from benchmark import run

    root = os.path.join(run.OUT_DIR, "variant")
    shutil.rmtree(root, ignore_errors=True)
    manifest = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cell, entry = run.find_cell(manifest, workload)
    files = {"config": (os.path.join(ROOT, entry["file"]),
                        os.path.join(root, entry["file"])),
             "traffic": (os.path.join(run.HERE, "traffic",
                                      cell["traffic"] + ".json"),
                         os.path.join(root, "traffic",
                                      cell["traffic"] + ".json"))}
    docs = {}
    for kind, (src, _dst) in files.items():
        with open(src) as f:
            docs[kind] = json.load(f)
    for item in sets:
        path, value = item.split("=", 1)
        kind, *keys = path.split(".")
        node = docs[kind]
        for k in keys[:-1]:
            node = node[k]
        node[keys[-1]] = json.loads(value)
    for kind, (_src, dst) in files.items():
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        with open(dst, "w") as f:
            json.dump(docs[kind], f)
    os.makedirs(os.path.join(root, "limits"))
    shutil.copy(os.path.join(run.HERE, "limits", workload + ".json"),
                os.path.join(root, "limits"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return os.path.join(root, "BENCHMARK.json"), root


def main(argv=None) -> int:
    from benchmark import run

    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=3.0)
    p.add_argument("--extra", default="")
    p.add_argument("--set", action="append", default=[], dest="sets")
    p.add_argument("--allow-cpu", action="store_true")
    a = p.parse_args(argv)
    extra = tuple(x for x in a.extra.split(",") if x)
    where = _variant(a.workload, a.sets) if a.sets else (None, None)
    lower, upper = {}, {}
    for seed in (int(s) for s in a.seeds.split(",")):
        args = run._args(["--workload", a.workload, "--seed", str(seed),
                          "--seconds", str(a.seconds), "--trace", "0"]
                         + (["--allow-cpu"] if a.allow_cpu else []))
        res = run.run(args, *where, extra=extra)
        row = {"seed": seed, "set": a.sets, "correct": res["correct"],
               "numbers": {k: v["value"] for k, v in res["check"].items()},
               "extra": res["info"].get("extra_numbers", {}),
               "where": res["info"]["where"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "memory_peak_bytes": res["device"]["memory_peak_bytes"],
               "info": {k: v for k, v in res["info"].items()
                        if k not in ("extra_numbers", "arena", "where")}}
        print(json.dumps(row), flush=True)
        for k, v in row["numbers"].items():
            lower[k] = max(lower.get(k, 0.0), v if v is not None else float("inf"))
        for name, nums in row["extra"].items():
            for k, v in nums.items():
                if k.startswith("_"):
                    continue
                key = f"{name}.{k}"
                upper[key] = min(upper.get(key, float("inf")), v)
    print(json.dumps({"largest_lower": lower, "smallest_upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
