"""Process start-up: which backend this interpreter uses, where XLA's
persistent compilation cache lives, and what a run records about its device.

Every entry point (``fira_tpu.cli``, ``bench.py``'s worker, ``chip_smoke.py``'s
children, the ``scripts/tpu_*.py`` that time the chip) calls
:func:`configure_compile_cache` first thing, so one rule decides where
compiled programs are kept; CPU-only entry points (tests, the virtual-mesh
scripts) call :func:`force_cpu_backend` before their first jax use. Either
registers the recorder's build listener (``profiling.listen``), so every
program the process builds is in the ring, those built before its first
span too.

Nothing here falls back: a ``JAX_PLATFORMS`` that names a backend the machine
lacks makes :func:`device_info` raise, and callers that need a TPU compare
``platform`` themselves.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional

from fira_tpu.utils import profiling

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache. The directory is part of the cache key's lookup, so
# it is derived from the package's own location: never a temp dir, a pid or a
# time, which would make every run a cold one.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")
RUN_INFO_FILE = "run_info.json"


def force_cpu_backend(n_virtual_devices: Optional[int] = None) -> None:
    """Pin this interpreter to the CPU backend. Must run before jax creates
    its first backend.

    ``n_virtual_devices``: ensure ``XLA_FLAGS`` requests at least this many
    virtual host devices (a smaller preexisting count is raised, a larger
    one kept) — how the multi-chip paths run without chips."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_virtual_devices is not None:
        xf = os.environ.get("XLA_FLAGS", "")
        m = re.search(_DEVICE_COUNT_FLAG + r"=(\d+)", xf)
        if m is None:
            os.environ["XLA_FLAGS"] = (
                xf + f" {_DEVICE_COUNT_FLAG}={n_virtual_devices}").strip()
        elif int(m.group(1)) < n_virtual_devices:
            os.environ["XLA_FLAGS"] = (
                xf[:m.start()]
                + f"{_DEVICE_COUNT_FLAG}={n_virtual_devices}"
                + xf[m.end():])
    import jax

    jax.config.update("jax_platforms", "cpu")
    profiling.listen()


def compile_cache_dir() -> str:
    """The one compile-cache rule (jax-free, so a parent that must not
    touch jax can ask too): ``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache`` (git-ignored)."""
    return os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR


def configure_compile_cache() -> str:
    """Apply :func:`compile_cache_dir`; returns the directory in effect.
    With the variable set jax reads it by itself and nothing is set in
    code."""
    if not os.environ.get(CACHE_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    profiling.listen()
    return compile_cache_dir()


def device_info() -> Dict:
    """What this process runs on, as jax reports it. Initializes the
    backend: raises here, before any work, when ``JAX_PLATFORMS`` names one
    the machine does not have."""
    import jax

    with profiling.span("startup.backend"):
        devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "n_devices": len(devs)}


def device_line(info: Dict) -> str:
    return (f"device: platform={info['platform']} "
            f"kind={info['device_kind']!r} count={info['n_devices']} "
            f"compile_cache={info['compile_cache_dir']}")


def peak_bytes_per_device() -> Optional[List[int]]:
    """``peak_bytes_in_use`` of every device, or None where the backend
    reports no memory stats (the CPU backend)."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        peaks.append(int(stats["peak_bytes_in_use"]))
    return peaks


def write_run_info(out_dir: str, info: Dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, RUN_INFO_FILE), "w") as f:
        json.dump(info, f, indent=1)
        f.write("\n")
