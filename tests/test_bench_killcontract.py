"""The driver contract bench.py must honor: the driver wrapping
`python bench.py` parses the LAST JSON line of stdout and may SIGKILL the
process at ANY time. These tests run the real orchestrator against a hanging
probe (the FIRA_BENCH_TEST_HANG_S hook — no backend is touched), SIGKILL it
at varied times, and assert the stdout tail is always a parseable structured
record."""

import json
import os
import signal
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _kill_after(delay_s: float, env_extra: dict) -> str:
    """Launch the orchestrator, SIGKILL it after delay_s, return stdout."""
    env = dict(os.environ)
    env.update({
        # the probe hangs (simulated stuck backend init) well past every
        # kill point below, so the orchestrator is mid-probe when it lands
        "FIRA_BENCH_TEST_HANG_S": "999",
        "FIRA_BENCH_PROBE_TIMEOUT": "60",
    })
    env.update(env_extra)
    with tempfile.TemporaryFile(mode="w+") as out:
        p = subprocess.Popen([sys.executable, BENCH], stdout=out,
                             stderr=subprocess.DEVNULL, env=env, cwd=REPO)
        try:
            p.wait(timeout=delay_s)
        except subprocess.TimeoutExpired:
            p.send_signal(signal.SIGKILL)
            p.wait()
        out.seek(0)
        return out.read()


def _last_json_line(out: str) -> dict:
    lines = [ln for ln in out.strip().splitlines()
             if ln.strip().startswith("{")]
    assert lines, f"no JSON line in stdout:\n{out!r}"
    return json.loads(lines[-1])


def test_sigkill_at_random_times_leaves_parseable_tail():
    # Kill points from just after interpreter boot to well into the probe.
    # The contract: whatever the timing, the last stdout line parses as the
    # structured record with the metric name and a null value.
    for delay in (2.5, 4.0, 6.5):
        out = _kill_after(delay, {})
        rec = _last_json_line(out)
        assert rec["metric"] == "train_commits_per_sec_per_chip", rec
        assert rec["value"] is None
        assert rec["unit"] == "commits/sec/chip"
        assert rec["vs_baseline"] is None
        assert "error" in rec and rec["error"], rec
        assert rec.get("in_progress"), rec
        assert isinstance(rec.get("attempts"), list)


def test_probe_timeout_emits_final_record():
    # No kill: the probe hangs past its timeout, is killed, and is NOT
    # retried — the orchestrator exits nonzero with a FINAL (not
    # in_progress) record whose attempts hold the one probe failure.
    env = dict(os.environ)
    env.update({
        "FIRA_BENCH_TEST_HANG_S": "999",
        "FIRA_BENCH_PROBE_TIMEOUT": "1",
    })
    p = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, timeout=60, env=env, cwd=REPO)
    rec = _last_json_line(p.stdout)
    assert p.returncode != 0
    assert rec["value"] is None
    assert not rec.get("in_progress"), rec
    assert "probe timeout" in rec["error"], rec
    assert [a["phase"] for a in rec["attempts"]] == ["probe"]
    assert rec["attempts"][0]["rc"] is None
