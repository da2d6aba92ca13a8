"""Drive bench.py's full orchestrator -> probe -> worker -> JSON contract on
CPU at fira-tiny geometry. This is the driver's artifact generator: its
one-JSON-line-in-every-outcome promise gets a test, not just a docstring."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run_bench(extra_env, timeout=420):
    env = dict(os.environ)
    env.update({
        "FIRA_BENCH_ALLOW_CPU": "1",
        "FIRA_BENCH_CONFIG": "fira-tiny",
        "FIRA_BENCH_DTYPE": "float32",   # bf16 is emulated (slow) on CPU
        "FIRA_BENCH_STEPS": "2",
        "FIRA_BENCH_WINDOWS": "1",
        "FIRA_BENCH_BATCH": "8",
        "FIRA_BENCH_DATA": "16",
        "FIRA_BENCH_PROBE_TIMEOUT": "120",
        "FIRA_BENCH_WORKER_TIMEOUT": "300",
    })
    env.update(extra_env)
    p = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=REPO)
    lines = [ln for ln in p.stdout.strip().splitlines()
             if ln.strip().startswith("{")]
    assert lines, f"no JSON line in stdout:\n{p.stdout}\n{p.stderr}"
    return p.returncode, json.loads(lines[-1])


def test_bench_harness_failure_emits_json():
    # The worker dies deterministically (unknown config field) -> the
    # orchestrator must still print a final structured JSON line with value
    # null and the worker's failure, and exit nonzero.
    rc, result = _run_bench({
        "FIRA_BENCH_OVERRIDES": '{"no_such_field": 1}',
    })
    assert rc != 0
    assert result["metric"] == "train_commits_per_sec_per_chip"
    assert result["value"] is None
    assert result["vs_baseline"] is None
    assert result["error"]
    assert any(a.get("phase") == "worker" for a in result["attempts"]
               if isinstance(a, dict))


def test_bench_failed_leg_exits_nonzero():
    # A leg that was asked for, ran and raised sinks the run: no record
    # with a value beside an {"error": ...} block and exit 0. The decode
    # leg dies on its first line (unparseable EOS delta), after the train
    # legs measured fine.
    rc, result = _run_bench({
        "FIRA_BENCH_COMPOSED": "0",
        "FIRA_BENCH_DECODE_ENGINE": "1",
        "FIRA_BENCH_DECODE_EOS_DELTA": "not-a-number",
    })
    assert rc != 0
    assert result["value"] is None
    assert not result.get("in_progress"), result
    assert any(a.get("phase") == "worker" and a.get("rc") not in (0, None)
               and "not-a-number" in a.get("tail", "")
               for a in result["attempts"]), result


def test_peak_flops_exact_match_or_error():
    import pytest

    sys.path.insert(0, REPO)
    import bench

    assert bench._peak_flops("TPU v5 lite", "bfloat16") == 197e12
    assert bench._peak_flops("TPU v5 lite", "float32") == 197e12 / 2
    with pytest.raises(KeyError, match="TPU v5x"):
        bench._peak_flops("TPU v5x", "bfloat16")


def test_bench_harness_cpu_success():
    rc, result = _run_bench(
        {"FIRA_BENCH_OVERRIDES": '{"sort_edges": true}'})
    assert rc == 0, result
    assert result["metric"] == "train_commits_per_sec_per_chip"
    assert result["value"] is not None and result["value"] > 0
    assert result["platform"] == "cpu"
    # MFU and the peak are device metrics: a CPU harness run carries none
    assert result["mfu"] is None and result["peak_flops"] is None
    assert result["compute_step_time_s"] > 0
    assert result["step_time_s"] > 0
    assert result["flops_per_step"] > 0
    assert result["overrides"] == {"sort_edges": True}
    # the composed production leg (stacked knobs x buckets) rides on every
    # success record with its dispatch-count + padding accounting
    comp = result["composed"]
    assert "error" not in comp, comp
    assert result["value_composed"] == comp["value"] > 0
    assert comp["dispatches"] == (comp["grouped_dispatches"]
                                  + comp["per_step_dispatches"])
    assert comp["commits"] > 0 and comp["steps_dispatched"] > 0
    assert 0.0 <= comp["padding_frac_dispatched"] < 1.0
    assert comp["buckets"]
