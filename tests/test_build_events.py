"""The recorder's build listener and its two clocks
(fira_tpu/utils/profiling.py): jax's three build stages of a program in the
ring, the persistent cache's loads, the process's start on the ring's clock,
and the ring laid over a profiler trace by its recorded offset. CPU."""

import glob
import json
import os
import statistics
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fira_tpu.utils import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _inside(ev, outer):
    return outer.t_start <= ev.t_start and ev.t_end <= outer.t_end


def _builds(since):
    return [e for e in profiling.events()
            if e.name in profiling.BUILD_EVENTS and e.t_start >= since]


def test_listener_records_every_build_stage_once_a_build():
    profiling.listen()

    @jax.jit
    def _build_probe_inner(x):
        return x * 5

    @jax.jit
    def _build_probe_outer(x):
        return _build_probe_inner(x) - 1

    phases = profiling.collect()
    t0 = time.perf_counter()
    with profiling.span("probe.build") as sp:
        _build_probe_outer(np.arange(5.0)).block_until_ready()
    mine = [e for e in _builds(t0) if "_build_probe" in e.ids["program"]]
    by = {(e.name, e.ids["program"]): e for e in mine}
    outer = by[(profiling.TRACE_EVENT, "_build_probe_outer")]
    inner = by[(profiling.TRACE_EVENT, "_build_probe_inner")]
    assert by[(profiling.LOWER_EVENT, "jit(_build_probe_outer)")]
    assert by[(profiling.COMPILE_EVENT, "jit(_build_probe_outer)")]
    assert all(e.parent_id == sp.span_id and _inside(e, sp) for e in mine)
    # tracing the outer jit traced the inner one inside it: the union of
    # the two is the outer's, and a sum would count the inner twice
    assert _inside(inner, outer)
    # the stages follow each other: trace, lower, backend build
    lower = by[(profiling.LOWER_EVENT, "jit(_build_probe_outer)")]
    build = by[(profiling.COMPILE_EVENT, "jit(_build_probe_outer)")]
    assert outer.t_end <= lower.t_start + 1e-4
    assert lower.t_end <= build.t_start + 1e-4
    s = phases.summary()
    assert s["traces"] >= 2 and s["lowers"] >= 1 and s["compiles"] >= 1
    assert s["trace_s"] >= outer.duration_s + inner.duration_s - 1e-5
    assert not any(name in s for name in profiling.BUILD_EVENTS)
    # a second call is no build: no stage is recorded again
    t1 = time.perf_counter()
    _build_probe_outer(np.arange(5.0)).block_until_ready()
    assert _builds(t1) == []


_CACHE_PROBE = """
import json, time
import jax, jax.numpy as jnp, numpy as np
from fira_tpu.utils import profiling, startup
startup.configure_compile_cache()     # the directory from the environment
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

def _cache_probe_fn(x):
    return jnp.sin(x) * 7 + x

x = np.arange(11.0)
jax.jit(_cache_probe_fn)(x).block_until_ready()
jax.clear_caches()
phases = profiling.collect()
jax.jit(_cache_probe_fn)(x).block_until_ready()
print(json.dumps({
    "builds": [[e.duration_s, e.ids] for e in profiling.events()
               if e.name == profiling.COMPILE_EVENT
               and e.ids["program"] == "jit(_cache_probe_fn)"],
    "rebuild": phases.summary()}))
"""


def test_a_persistent_cache_load_reads_hit_and_its_load_time(tmp_path):
    """A child with the cache where the environment says: the first build
    compiles, and after ``jax.clear_caches()`` the rebuild is a load."""
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu",
           "JAX_ENABLE_COMPILATION_CACHE": "true",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=ROOT,
                         check=True, capture_output=True, text=True,
                         env=env).stdout
    got = json.loads(out.splitlines()[-1])
    (_cold_s, cold), (warm_s, warm) = got["builds"]
    assert cold["cache"] == "miss" and "load_s" not in cold
    assert warm["cache"] == "hit" and 0 < warm["load_s"] <= warm_s
    s = got["rebuild"]
    assert s["cache_hits"] >= 1 and s["cache_misses"] == 0
    assert s["cache_load_s"] >= warm["load_s"] - 1e-6
    # a compile counts every backend build, the loads among them
    assert s["compiles"] >= s["cache_hits"]
    assert os.listdir(tmp_path)


def test_process_start_is_the_interpreters_start_on_the_rings_clock():
    """A child reads the ring's clock at its first line: the OS's start of
    the process lies before it, by the interpreter's start-up at most."""
    code = ("import time; t = time.perf_counter(); import json; "
            "from fira_tpu.utils import profiling; "
            "print(json.dumps([t, profiling.process_start(), "
            "profiling.process_start()]))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                         capture_output=True, text=True,
                         env={**os.environ, "PYTHONPATH": ROOT}).stdout
    first_line, start, again = json.loads(out)
    assert start == again                       # read once
    assert start <= first_line + 0.011          # the OS's clock ticks: 10 ms
    assert first_line - start < 5.0
    assert profiling.process_start() < time.perf_counter()


def test_dump_header_holds_the_two_clocks(tmp_path):
    rec = profiling.Recorder()
    with rec.span("a"):
        pass
    path = rec.dump(str(tmp_path / "spans.jsonl"))
    head = json.loads(open(path).readline())["recorder"]
    assert head["process_start"] == profiling.process_start()
    wall = time.time_ns() - time.perf_counter_ns()
    assert abs(head["profiler_offset_ns"] - wall) < 50_000_000
    assert head["recorded"] == head["events"] == 1
    assert {"traces", "trace_s", "lowers", "lower_s",
            "cache_load_s"} <= set(head)


def test_ring_plus_offset_is_the_profilers_host_clock(tmp_path):
    """Ring starts plus the recorded offset land on the ``/host:CPU``
    events' starts, which the ``.xplane.pb`` gives from the session's start
    (``profile_start_time``): each name's median within 0.2 ms."""
    from jax.profiler import ProfileData

    x = jnp.arange(64.0)
    (x * 2).block_until_ready()
    t0 = time.perf_counter()
    with profiling.trace(str(tmp_path)):
        for i in range(20):
            with profiling.span("clock.outer", i=i):
                with profiling.span("clock.inner"):
                    (x * i).block_until_ready()
                time.sleep(0.001)
    offset = profiling.profiler_offset_ns()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host, start_ns = {}, None
    for plane in ProfileData.from_file(path).planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start_ns = stats["profile_start_time"]
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("clock."):
                        host.setdefault(ev.name, []).append(ev.start_ns)
    assert start_ns is not None
    ring = {}
    for e in profiling.events():
        if e.name.startswith("clock.") and e.t_start >= t0:
            ring.setdefault(e.name, []).append(e.t_start)
    assert set(ring) == set(host) == {"clock.outer", "clock.inner"}
    for name in ring:
        mine = sorted(t * 1e9 + offset - start_ns for t in ring[name])
        traced = sorted(host[name])
        assert len(mine) == len(traced) == 20, name
        gaps = [abs(a - b) for a, b in zip(mine, traced)]
        assert statistics.median(gaps) <= 2e5, (name, statistics.median(gaps))


@pytest.mark.parametrize("stage", profiling.BUILD_EVENTS)
def test_each_build_stage_is_a_counter_not_a_span_name(stage):
    """Build events feed the counters, never ``Phases.spans``: a serve
    round's ``phases`` stays a block of the layers' own spans."""
    rec = profiling.Recorder()
    phases = rec.collect()
    rec._on_build(stage, 0.25, "jit(f)")
    s = phases.summary()
    assert stage not in s
    key = {"jax.trace": "trace_s", "jax.lower": "lower_s",
           "xla.compile": "compile_s"}[stage]
    assert s[key] == 0.25
    (ev,) = rec.events()
    assert ev.name == stage and ev.ids == {"program": "jit(f)"}
    assert rec.dropped() == 0
