"""Asynchronous host input pipeline: batch assembly + H2D off the hot loop.

The COO rewrite fixed the wire format (data/batching.py) and
``prefetch_to_device`` overlapped the H2D transfer, but batch ASSEMBLY
(gather + pad + narrow + sort) still ran serially on the consumer thread,
inside the step-dispatch interval. As the device step gets faster (fused
K-step scans, bf16 wire) that host work becomes the throughput ceiling —
the reference has the same disease terminally (torch DataLoader densifies
650^2 adjacencies per sample and blocks on .cuda() per batch,
run_model.py:94-101).

``Feeder`` is a bounded worker pool that runs assembly tasks ahead of the
training loop:

- **order**: the task sequence IS the batch order. Workers assemble out of
  order; the consumer side emits strictly in sequence, so the exact
  deterministic ``(seed, epoch)`` stream of ``data.batching.epoch_batches``
  is preserved byte-for-byte (pinned by tests/test_feeder.py).
- **bounding**: at most ``depth`` tasks are in flight (dispatched but not
  yet consumed) — host memory stays O(depth * batch_bytes).
- **transfer**: each worker finishes its task with a (sharded)
  ``jax.device_put``, which is asynchronous — the transfer of batch i+1
  overlaps the compute of batch i, same as the old prefetcher. A grouped
  dispatch item (data/grouping.py: a K-stacked same-geometry batch for the
  fused device loop / gradient accumulation) is assembled AND transferred
  by ONE task on one worker, so the whole K-group ships as a single
  ``device_put`` instead of K round-trips; ``n_valid`` sums the 2-D
  ``valid`` of a stacked group the same way it sums the 1-D one.
- **errors**: every task exception is wrapped in :class:`FeederTaskError`
  carrying the task's sequence number and its ``note`` (split positions,
  bucket geometry — set by the task generators), so a poisoned sample is
  identifiable from the traceback. A failing task is retried up to
  ``retries`` times with linear backoff first (transient faults are
  absorbed in the worker). Then, under the default ``on_error="raise"``,
  the first surviving exception re-raises at the consumer on its next
  ``__next__`` (not deferred until the failing sequence number comes up)
  — the historical fail-stop contract. Under ``on_error="record"`` (the
  serving path's PER-TASK ERROR CHANNEL, docs/FAULTS.md) the failing
  item is emitted in sequence with ``error`` set and ``host``/``device``
  None, and the stream continues: one bad sample no longer poisons the
  feed — the consumer sheds it (serve/server.py) instead of dying.
- **fault injection**: an armed robust.faults.FaultInjector checks the
  ``feeder.assemble`` / ``feeder.device_put`` sites around each task,
  keyed by (task sequence, attempt) so thread scheduling cannot reorder
  the deterministic draws; None (default) costs one is-None branch.
- **shutdown**: ``close()`` (or the context manager / end-of-stream /
  error paths, which call it) stops dispatch, unblocks and joins every
  thread — no live threads remain (pinned by tests/test_feeder.py).
- **observability**: every item carries ``stall_s`` (how long the consumer
  blocked waiting for it — the feed-stall numerator train/loop.py feeds
  into profiling.Meter) and ``queue_depth`` (ready-but-unconsumed batches
  when the consumer arrived — persistently 0 means the feed can't keep
  up); ``stats()`` aggregates them. The clock behind them is the
  program's recorder (utils/profiling.py): ``feeder.assemble`` and
  ``feeder.put`` on the worker threads, ``feeder.next`` on the consumer —
  ``stall_s`` IS that span's duration, one source. A graph batch's
  ``feeder.assemble`` span also carries ``edge_slots`` (rows x COO pad of
  the dispatch it built) and ``edges`` (the real edges among them), and
  ``stats()`` their running sums: ``edges / edge_slots`` is the wire's
  fill share — how often the edge ladder (data/buckets.py) engages, 0.118
  at the admission bound and ~0.945 at the 768 rung on the benchmark's
  corpus. ``per_request=True``
  says one task is ONE REQUEST (the serve paths): the feed keeps the
  timing and drops the record, since a span each would break the
  recorder's never-per-request rule.

``num_workers=0`` is the synchronous mode: same interface, tasks run
inline on the consumer thread (assembly time then IS stall), no threads
created. It is both the debug fallback and the control leg bench.py
measures ``feed_stall_frac`` against.

Sync boundaries: the feeder itself never syncs with the device — workers
only *enqueue* transfers; ``n_valid`` is computed host-side from the numpy
batch BEFORE the transfer (reading it back would force a mid-epoch sync).
See docs/PIPELINE.md.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Dict, Iterable, Iterator, Optional

import numpy as np

from fira_tpu.utils import profiling

Batch = Dict[str, Any]
Task = Callable[[], Batch]


class FeederTaskError(RuntimeError):
    """One assembly task failed (after its retry budget): carries the
    task's sequence number and its generator-set ``note`` — split
    positions, bucket geometry, site — so the poisoned sample is
    identifiable from the traceback instead of an anonymous re-raise."""

    def __init__(self, index: int, note: Optional[str],
                 original: BaseException) -> None:
        where = f" ({note})" if note else ""
        super().__init__(
            f"feeder task {index}{where} failed: "
            f"{type(original).__name__}: {original}")
        self.index = index
        self.note = note
        self.original = original


@dataclasses.dataclass
class FedBatch:
    """One emitted pipeline item."""

    index: int          # position in the deterministic batch order
    host: Optional[Batch]  # the assembled numpy batch (for host-side
                        # fields, incl. "_"-prefixed host-only metadata);
                        # None on an error-carrying item (record mode)
    device: Any         # jax.device_put result, "_" keys stripped
                        # (== host when put=False)
    n_valid: int        # real (non-pad) rows, computed pre-transfer
    stall_s: float      # consumer time blocked waiting for THIS item
    queue_depth: int    # ready-but-unconsumed items when consumer arrived
    error: Optional[BaseException] = None  # FeederTaskError in record mode
    retries: int = 0    # assembly attempts beyond the first this item took
    task_s: float = 0.0  # worker-side wall seconds of the successful
                        # assembly attempt (task + device_put enqueue) —
                        # the per-task cost meter the ingest worker-
                        # scaling rows divide stall against; 0 on
                        # error-carrying items
    edge_slots: int = 0  # COO slots on the wire (rows x pad; 0: no graph)
    edges: int = 0      # real edges among them (nonzero values)


class Feeder:
    """Bounded-queue background batch assembly + H2D pipeline.

    ``tasks``: iterable of zero-arg callables, each returning one host
    batch; the iterable is drained lazily on the dispatcher thread, so a
    generator is fine. ``sharding``: pytree of NamedShardings or a callable
    ``batch -> sharding-or-None`` (mixed-shape streams). ``put=False``
    skips the device transfer (host-only pipelines, e.g. tests).
    """

    def __init__(self, tasks: Iterable[Task], *, num_workers: int = 2,
                 depth: int = 4, sharding=None, put: bool = True,
                 on_error: str = "raise", retries: int = 0,
                 retry_backoff_s: Optional[float] = None, faults=None,
                 per_request: bool = False):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if num_workers < 0:
            raise ValueError(f"num_workers must be >= 0, got {num_workers}")
        if on_error not in ("raise", "record"):
            raise ValueError(f"on_error {on_error!r} not in "
                             f"{{'raise', 'record'}}")
        if retries < 0:
            raise ValueError(f"retries must be >= 0, got {retries}")
        self._sharding = sharding
        self._put = put
        self._num_workers = num_workers
        self._depth = depth
        self._on_error = on_error
        self._retries = retries
        self._retry_backoff_s = retry_backoff_s
        self._faults = faults          # robust.faults.FaultInjector or None
        self._span = (profiling.stopwatch if per_request
                      else profiling.span)
        self._next = 0                 # next sequence number to emit
        self._n_stalls = 0
        self._stall_s = 0.0
        self._stall_max = 0.0
        self._depth_sum = 0
        self._depth_min: Optional[int] = None
        self._n_task_errors = 0
        self._n_task_retries = 0
        self._task_s = 0.0
        self._edge_slots = 0
        self._edges = 0
        self._closed = False
        # resource-lifecycle sanitizer: armed, every pipeline thread is
        # ledgered at start and retired at join, so a close() path that
        # skips a join shows up at teardown with this start site named
        # (analysis.sanitizer.LeakGuard; static twin: RES-LEAK)
        from fira_tpu.analysis.sanitizer import leak_guard

        self._leaks = leak_guard()

        if num_workers == 0:
            self._task_iter: Iterator[Task] = iter(tasks)
            self._threads: list = []
            return

        self._cond = threading.Condition()
        self._ready: Dict[int, FedBatch] = {}
        # lock-discipline sanitizer (--sanitize / tests): the ordered-
        # ready channel is the one structure every worker AND the
        # consumer mutate — armed, a write outside `with self._cond`
        # raises at the line (analysis.sanitizer.ThreadGuard)
        from fira_tpu.analysis.sanitizer import guard_structures

        self._cond, (self._ready,) = guard_structures(
            self, self._cond, [(self._ready, "_ready")],
            lock_label="_cond")
        self._error: Optional[BaseException] = None
        self._total: Optional[int] = None   # set when tasks exhaust
        self._stop = threading.Event()
        self._inflight = threading.Semaphore(depth)
        self._task_q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._threads = [
            threading.Thread(target=self._dispatch, args=(iter(tasks),),
                             name="fira-feeder-dispatch", daemon=True)
        ] + [
            threading.Thread(target=self._work, name=f"fira-feeder-worker-{i}",
                             daemon=True)
            for i in range(num_workers)
        ]
        for t in self._threads:
            t.start()
            if self._leaks is not None:
                self._leaks.track_thread(t)

    # --- pipeline threads ---

    def _dispatch(self, tasks: Iterator[Task]) -> None:
        seq = 0
        try:
            for task in tasks:
                # bound in-flight work; poll so close() can interrupt a
                # dispatcher blocked on a full pipeline
                while not self._stop.is_set():
                    if self._inflight.acquire(timeout=0.05):
                        break
                if self._stop.is_set():
                    return
                self._task_q.put((seq, task))
                seq += 1
        except BaseException as e:  # a raising tasks generator poisons the feed
            self._poison(e)
            return
        finally:
            for _ in range(self._num_workers):
                self._task_q.put(None)
        with self._cond:
            self._total = seq
            self._cond.notify_all()

    def _work(self) -> None:
        while True:
            got = self._task_q.get()
            if got is None or self._stop.is_set():
                return
            seq, task = got
            try:
                item = self._execute(seq, task)
            except BaseException as e:
                self._poison(e)
                return
            with self._cond:
                self._ready[seq] = item
                self._cond.notify_all()

    def _execute(self, seq: int, task: Task) -> FedBatch:
        """Run ONE assembly task under the retry/fault policy. Transient
        failures burn the retry budget with linear backoff; a surviving
        exception is wrapped with the task's identity (FeederTaskError)
        and either raised (``on_error="raise"``, the fail-stop default)
        or returned as an error-carrying item (``"record"`` — the
        per-task error channel the serving path sheds on)."""
        attempt = 0
        while True:
            try:
                with self._span("feeder.assemble") as assemble:
                    if self._faults is not None:
                        self._faults.check("feeder.assemble",
                                           key=(seq, attempt))
                    host = task()
                    if self._faults is not None:
                        host = self._faults.corrupt("feeder.assemble", seq,
                                                    host)
                    # host-side row count BEFORE the transfer — reading it
                    # back from the device array would force a mid-epoch
                    # sync
                    n_valid = int(host["valid"].sum())
                    slots, edges = _edge_fill(host)
                    if slots:
                        assemble.note(edge_slots=slots, edges=edges)
                with self._span("feeder.put") as put:
                    if self._faults is not None:
                        self._faults.check("feeder.device_put",
                                           key=(seq, attempt))
                    device = self._device_put(host)
                return FedBatch(seq, host, device, n_valid, 0.0, 0,
                                retries=attempt,
                                task_s=assemble.duration_s + put.duration_s,
                                edge_slots=slots, edges=edges)
            except Exception as e:
                if attempt < self._retries:
                    attempt += 1
                    if self._retry_backoff_s is not None:
                        time.sleep(self._retry_backoff_s * attempt)  # firacheck: allow[SCHED-BLOCK] worker-side quarantine retry backoff: the WORKER thread is the right place to sleep — siblings keep assembling and the consumer only ever waits on the ordered-ready condition
                    else:
                        # the shared quarantine backoff curve — one
                        # definition for every retry site (docs/FAULTS.md)
                        from fira_tpu.robust.faults import backoff_s

                        time.sleep(backoff_s(attempt))  # firacheck: allow[SCHED-BLOCK] same worker-side retry backoff as above (the shared docs/FAULTS.md curve)
                    continue
                err = FeederTaskError(seq, getattr(task, "note", None), e)
                if self._on_error == "record":
                    return FedBatch(seq, None, None, 0, 0.0, 0, error=err,
                                    retries=attempt)
                raise err from e

    def _device_put(self, host: Batch):
        if not self._put:
            return host
        import jax

        # keys starting with "_" are HOST-ONLY metadata (bucket packer
        # positions/tags, data/buckets.py): they never ship to the device
        # and never reach the sharding callable — the wire pytree keeps the
        # exact structure the jitted programs were traced with
        wire = ({k: v for k, v in host.items() if not k.startswith("_")}
                if isinstance(host, dict) else host)
        sh = self._sharding(wire) if callable(self._sharding) else self._sharding
        return jax.device_put(wire, sh) if sh is not None else jax.device_put(wire)

    def _poison(self, e: BaseException) -> None:
        with self._cond:
            if self._error is None:
                self._error = e
            self._cond.notify_all()
        self._stop.set()

    # --- consumer side ---

    def __iter__(self) -> "Feeder":
        return self

    def __next__(self) -> FedBatch:
        if self._num_workers == 0:
            return self._next_sync()
        with self._span("feeder.next") as waited, self._cond:
            depth_seen = len(self._ready)
            while True:
                if self._error is not None:
                    err = self._error
                    break
                if self._next in self._ready:
                    err = None
                    item = self._ready.pop(self._next)
                    break
                if self._total is not None and self._next >= self._total:
                    err = StopIteration()
                    break
                self._cond.wait()  # firacheck: allow[SCHED-BLOCK] this wait IS the metered feed stall (stall_s): the consumer blocks exactly until the next in-order item, and close()/_poison notify_all so it can never wedge
        if err is not None:
            self.close()
            raise err
        stall = waited.duration_s
        self._next += 1
        self._inflight.release()
        item.stall_s = stall
        item.queue_depth = depth_seen
        self._record(item, stall, depth_seen)
        return item

    def _next_sync(self) -> FedBatch:
        with self._span("feeder.next") as waited:
            try:
                task = next(self._task_iter)
            except StopIteration:
                self._closed = True
                raise
            item = self._execute(self._next, task)
        stall = waited.duration_s
        self._next += 1
        item.stall_s = stall
        self._record(item, stall, 0)
        return item

    def _record(self, item: FedBatch, stall: float, depth_seen: int) -> None:
        self._n_stalls += 1
        self._stall_s += stall
        self._stall_max = max(self._stall_max, stall)
        self._depth_sum += depth_seen
        self._depth_min = (depth_seen if self._depth_min is None
                           else min(self._depth_min, depth_seen))
        self._n_task_retries += item.retries
        self._task_s += item.task_s
        self._edge_slots += item.edge_slots
        self._edges += item.edges
        if item.error is not None:
            self._n_task_errors += 1

    # --- lifecycle ---

    def close(self) -> None:
        """Stop dispatch, unblock and join every pipeline thread. Idempotent;
        called automatically at end-of-stream, on error, and by the context
        manager — callers that break out of iteration early must call it (or
        use ``with``)."""
        if self._closed:
            return
        self._closed = True
        if not self._threads:
            return
        self._stop.set()
        for _ in range(self._num_workers):
            self._task_q.put(None)   # unblock workers parked on get()
        with self._cond:
            self._cond.notify_all()
        for t in self._threads:
            t.join()
            if self._leaks is not None:
                self._leaks.note_joined(t)
        self._threads = []

    def __enter__(self) -> "Feeder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # best-effort: never leave threads parked forever
        try:
            self.close()
        except Exception:
            pass

    # --- observability ---

    def stats(self) -> Dict[str, float]:
        """Aggregate feed-stall / queue-depth stats over the items emitted
        so far. ``feed_stall_s`` is the numerator of ``feed_stall_frac``
        (profiling.Meter owns the interval-time denominator)."""
        n = self._n_stalls
        return {
            "batches": float(n),
            "feed_stall_s": self._stall_s,
            "feed_stall_max_ms": 1e3 * self._stall_max,
            "queue_depth_sum": float(self._depth_sum),
            "queue_depth_mean": (self._depth_sum / n) if n else 0.0,
            "queue_depth_min": float(self._depth_min or 0),
            "num_workers": float(self._num_workers),
            "depth": float(self._depth),
            # per-task error channel accounting (docs/FAULTS.md): items
            # emitted with a recorded error (record mode) and assembly
            # retry attempts absorbed in the workers
            "task_errors": float(self._n_task_errors),
            "task_retries": float(self._n_task_retries),
            # total worker-side assembly seconds over the emitted items:
            # task_s / (workers x wall) is pool utilization — the meter
            # the ingest worker-scaling rows read next to stall_frac
            "task_s": self._task_s,
            # COO slots shipped and the real edges among them (their
            # ratio is the wire's fill share; the rest is pad)
            "edge_slots": float(self._edge_slots),
            "edges": float(self._edges),
        }

    # --- adapters ---

    @classmethod
    def from_batches(cls, batches: Iterable[Batch], *, depth: int = 2,
                     num_workers: int = 1, sharding=None,
                     put: bool = True) -> "Feeder":
        """Wrap an ALREADY-ASSEMBLED batch stream (generator or list): the
        stream is drained on the dispatcher thread and each batch's
        device_put runs on a worker — the contract of the old
        ``prefetch_to_device``, which is now a shim over this."""
        tasks = ((lambda b=b: b) for b in batches)
        return cls(tasks, num_workers=num_workers, depth=depth,
                   sharding=sharding, put=put)


def _edge_fill(host) -> tuple:
    """(COO slots, real edges) of an assembled graph batch: a pad slot is
    (0, 0, value 0.0) and a real edge's normalised value is never zero.
    (0, 0) for a batch without edges (a token model's prompts)."""
    values = host.get("values")
    if values is None:
        return 0, 0
    return values.size, np.count_nonzero(values)   # host numpy: plain ints


def task_note(positions, *, geom_tag: Optional[str] = None,
              site: Optional[str] = None) -> str:
    """Human-readable task identity for FeederTaskError: the split
    positions the task assembles (truncated), plus the bucket geometry
    and call site when known — enough to name the poisoned sample from
    the traceback alone."""
    pos = [int(p) for p in positions]  # firacheck: allow[HOST-SYNC] positions are host-side planning ints (index chunks / request ids); no device value exists here
    shown = ", ".join(str(p) for p in pos[:6])
    if len(pos) > 6:
        shown += f", ... {len(pos) - 6} more"
    parts = [f"split positions [{shown}]"]
    if geom_tag:
        parts.append(f"bucket {geom_tag}")
    if site:
        parts.append(site)
    return "; ".join(parts)


def assembly_tasks(split, chunks, cfg, *, batch_size: Optional[int] = None,
                   stamp: Optional[Callable[[Batch], Batch]] = None
                   ) -> Iterator[Task]:
    """One ``make_batch`` task per index chunk (see
    data.batching.epoch_index_chunks for the order contract). Each task
    carries a ``note`` naming its split positions, so a failing worker's
    FeederTaskError identifies the poisoned chunk.

    ``stamp``: optional post-assembly hook applied WORKER-side (it runs
    inside the task, on the pool thread) — the decode drivers pass
    decode.prefix_cache.stamp_digests here when ``cfg.prefix_cache`` is
    armed, so payload content digests are computed off the scheduler
    thread like the rest of batch assembly."""
    from fira_tpu.data.batching import make_batch

    for chunk in chunks:
        def task(c=chunk):
            b = make_batch(split, c, cfg, batch_size=batch_size)
            return stamp(b) if stamp is not None else b
        task.note = task_note(chunk, site="assembly_tasks")
        yield task
