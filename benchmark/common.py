"""What the three drivers share: reading the benchmark's data files, turning a
configuration file into the program's config object, the traced window, and
the spans the benchmark records around its calls into the program."""

from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Dict, Iterable, Optional

HOST_SPANS = ("feed.next", "dispatch", "sync", "admit", "refill", "harvest",
              "serve.loop")


def load_json(root: str, kind: str, name: str) -> Dict:
    path = os.path.join(root, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def model_cfg_dict(config: Dict) -> Dict:
    """The sizes the reference, the weights and the FLOP counts read."""
    return dict(config["model"])


def program_cfg(config: Dict, knobs_key: str, **overrides):
    """The program's config object for one configuration file: its preset,
    every key the file states, the knob set of the path driven, then the
    traffic's own settings."""
    from fira_tpu.config import get_config

    fields = dict(config["model"])
    fields["compute_dtype"] = config["compute_dtype"]
    fields.update(config.get(knobs_key, {}))
    fields.update(overrides)
    return get_config(config["preset"], **fields)


def seed31(seed: int) -> int:
    """The program's seeded helpers take 31-bit seeds; ``--seed`` may be
    wider, so fold the high bits in instead of dropping them."""
    seed = int(seed)
    return (seed ^ (seed >> 31) * 0x9E3779B1) % (2 ** 31 - 1)


def make_corpus(cfg, config: Dict, n: int, seed: int):
    """In-memory synthetic commits from the seed, vocabularies padded to the
    configuration's sizes; the padded sizes must be the file's."""
    from fira_tpu.data.synthetic import make_memory_split

    m = config["model"]
    cfg, split, vocab = make_memory_split(
        cfg, n, seed=seed31(seed), pad_vocab_to=m["vocab_size"],
        pad_ast_vocab_to=m["ast_change_vocab_size"])
    if (cfg.vocab_size != m["vocab_size"]
            or cfg.ast_change_vocab_size != m["ast_change_vocab_size"]):
        raise ValueError(
            f"corpus vocabularies ({cfg.vocab_size}, "
            f"{cfg.ast_change_vocab_size}) outgrew the configuration's "
            f"({m['vocab_size']}, {m['ast_change_vocab_size']})")
    # filler words for the padded ids, as chip_smoke.py's corpus has them: a
    # beam over random weights may well generate one, and the serve path
    # cooks every served id into text
    from fira_tpu.data.vocab import Vocab

    words = dict(vocab.token_to_id)
    for i in range(len(words), cfg.vocab_size):
        words[f"<filler{i}>"] = i
    return cfg, split, Vocab(words)


def check_param_tree(model, cfg, wire_row: Dict, config: Dict) -> None:
    """The program has to accept the benchmark's weights as they are: same
    names, same shapes."""
    import jax

    from . import weights

    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), wire_row,
                           deterministic=True))["params"]
    got = jax.tree_util.tree_map(lambda x: tuple(x.shape), shapes)
    want = weights.param_shapes(model_cfg_dict(config))
    if got != want:
        raise ValueError("the program's parameter tree is not the one "
                         "benchmark/weights.py builds")


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def wrap_spans(obj, methods: Dict[str, str], on_return=None) -> None:
    """Record a span around ``obj.<method>`` for each (method, span name):
    the benchmark's own instrumentation around its calls into a layer."""
    for meth, name in methods.items():
        inner = getattr(obj, meth)

        def outer(*a, _inner=inner, _name=name, _meth=meth, **kw):
            with span(_name):
                out = _inner(*a, **kw)
            if on_return is not None:
                on_return(_meth, out)
            return out
        setattr(obj, meth, outer)


class Tracer:
    """Profiles ``duration_s`` seconds of the steady window from a thread of
    its own, ``start_after_s`` into it; the traced stretch is marked by a
    ``bench.window`` span on the trace's own clock."""

    def __init__(self, trace_dir: Optional[str], start_after_s: float,
                 duration_s: float):
        self.dir = trace_dir
        self._start_after, self._duration = start_after_s, duration_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None
        self.traced = False

    def _run(self) -> None:
        import jax

        try:
            if self._stop.wait(self._start_after):
                return
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    self._stop.wait(self._duration)
            finally:
                jax.profiler.stop_trace()
            self.traced = True
        except BaseException as e:  # reported by close(); never lost
            self.error = e

    def open(self) -> None:
        if self.dir is None:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self._thread = threading.Thread(target=self._run, name="bench-tracer",
                                        daemon=True)
        self._thread.start()

    def close(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=120)
        if self._thread.is_alive():
            raise RuntimeError("the tracer thread did not stop")
        if self.error is not None:
            raise self.error


def tracer_for(ctx, traffic: Dict) -> Tracer:
    seconds = float(ctx["seconds"])
    dur = min(float(traffic.get("trace_seconds", 4.0)), max(0.5, seconds / 2))
    start = min(float(traffic.get("trace_start_s", 2.0)), seconds / 4)
    return Tracer(ctx["trace_dir"] if ctx["trace"] else None, start, dur)


def memory_peak_bytes() -> Optional[int]:
    """Peak on the fullest chip, or None where the backend reports none.

    The TPU runtime keeps two counts (PERF.md section 4): live arrays
    (``peak_bytes_in_use``) and what loaded programs reserve for their
    temporaries (``peak_bytes_reserved``) — a train step that fills the chip
    ran under 1.6 GB of the first and 15.3 GB of the second. Each is a peak
    that did occur; whether the two fell together the runtime does not say,
    so the chip's peak is reported as the larger of them and never as their
    sum. A run's ``info.memory`` carries both as the runtime gave them."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(max(int(stats["peak_bytes_in_use"]),
                             int(stats.get("peak_bytes_reserved", 0))))
    return max(peaks) if peaks else None


def memory_stats() -> Optional[Dict]:
    """The first device's own counts, as they are, for a run's ``info``."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return dict(stats) if stats else None


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile of all the values (numpy's default),
    in the benchmark's own code."""
    v = sorted(float(x) for x in values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)
