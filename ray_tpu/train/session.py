"""Per-worker training session: the `ray_tpu.train.report()` plumbing.

Reference capability: python/ray/train/_internal/session.py — _TrainSession (:112),
report (:405), public ray.train.report (:672) and get_context
(python/ray/train/context.py:117). The user's train loop runs on a daemon thread inside
the worker actor; report() enqueues (metrics, checkpoint) for the driver-side executor to
drain. Checkpoints are staged into run storage *before* report() returns (worker-side
persistence, like Train v2's storage upload), so callers may delete their local snapshot
directory immediately after reporting.
"""
from __future__ import annotations

import contextlib
import functools
import logging
import os
import queue
import shutil
import threading
import time
import uuid
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

from ray_tpu.core import worker as core_worker
from ray_tpu.util import telemetry

from .checkpoint import Checkpoint

LOGGER = logging.getLogger(__name__)
_session_lock = threading.Lock()
_session: Optional["_TrainSession"] = None

# The train loop's clock. What the PROGRAM can see of a loop it does not own: the step
# callable is entered and returns (train/step.py), `report()` is entered and returns, a
# batch is asked of a dataset's iterator and comes back (data/iterator.py); whatever lies
# between is the user's own code, which in a loop that syncs on its loss is the wait for
# the device. A lap ends where the next begins, so the four sum to the thread's wall
# time. Each is a span (a profiler annotation on the loop's thread whenever a profile
# records, an entry of the ring under RAY_TPU_TRACING) and, always, a monotonic integer
# of `metrics()`.
LOOP_SPANS = ("train.loop.dispatch", "train.loop.report", telemetry.DATA_LAP,
              "train.loop.user")
DISPATCH, REPORT, DATA, USER = range(len(LOOP_SPANS))
_LOOP_COUNTERS = ("train_loop_dispatch_ns_total", "train_loop_report_ns_total",
                  "train_loop_data_ns_total", "train_loop_user_ns_total")
_STEPS = "train_steps_total"
_SLOW = "train_slow_steps_total"
_STEP_INTERVAL_BOUNDARIES = [0.001 * 2 ** (i / 2) for i in range(34)]  # 1 ms .. 92 s
# A slow step leaves a record (`_slow_step`): one whose entry-to-entry interval is more than
# SLOW_FACTOR x the median of `train_step_interval_seconds`. The threshold is read from the
# histogram's buckets at the loop's FIRST_REFRESH-th step and every REFRESH_EVERY steps after, so
# that a step pays one comparison, and no step before that has one (the first compiles).
SLOW_FACTOR, FIRST_REFRESH, REFRESH_EVERY = 4, 16, 64


class _Loop:
    """One thread's laps, its count of steps and when it last entered a step; for the
    record of a slow step the threshold (SLOW_FACTOR x the median), the step at which it is read
    again, the process's integers at the last entry (`_held`) and the operating system's
    numbers at the last refresh (`_os_numbers`). A session's `train_loop` thread has one;
    outside a session (a bare process, the tests) a thread gets one at its first step."""

    __slots__ = ("clock", "last_step_ns", "thread", "slow_after_ns", "refresh_at", "at_entry", "at_refresh")

    def __init__(self):
        self.clock = telemetry.LapClock(LOOP_SPANS, _LOOP_COUNTERS + (_STEPS, _SLOW), "train")
        self.last_step_ns = 0
        self.thread = threading.current_thread()
        self.slow_after_ns, self.refresh_at = 1 << 62, FIRST_REFRESH
        self.at_entry = self.at_refresh = None


_loops_lock = threading.Lock()
_loops: list = []  # the live threads'
_retired: Dict[str, int] = dict.fromkeys(_LOOP_COUNTERS + (_STEPS, _SLOW), 0)  # the ended threads', summed
_local = threading.local()
_setup_seconds: Dict[str, float] = {}  # phase -> seconds, this process's (record_setup)


def _loop() -> _Loop:
    """The calling thread's laps, begun in `user` at the first call."""
    loop = getattr(_local, "loop", None)
    if loop is None:
        loop = _local.loop = _Loop()
        with _loops_lock:
            _loops.append(loop)
        telemetry.set_thread_clock(loop.clock)
        loop.clock.enter(USER)
        # carried to the head from the processes that run steps only, so that the
        # `train` row of cluster_status() sums the train workers' own
        telemetry.export_counters(
            metrics, _LOOP_COUNTERS + (_STEPS, _SLOW, "compiles_total", "compile_ns_total")
            + tuple(core_worker.process_counters()),
            "the train loop's laps, steps and compiles, its process's tasks and "
            "collector pauses (ray_tpu.train.metrics)")
    return loop


def _end_loop() -> None:
    """The calling thread's loop ends: its open lap closes, its integers stay in the sums."""
    loop = getattr(_local, "loop", None)
    if loop is None:
        return
    loop.clock.enter(None)
    telemetry.set_thread_clock(None)
    _local.loop = None
    with _loops_lock:
        _retire_locked(loop)


def _retire_locked(loop: _Loop) -> None:
    _loops.remove(loop)
    for key, n in loop.clock.totals.items():
        _retired[key] += n


@functools.lru_cache(maxsize=None)
def _step_interval():
    return telemetry.get_histogram(
        "train_step_interval_seconds", "entry to entry of the train step callable",
        boundaries=_STEP_INTERVAL_BOUNDARIES)


def _held(totals: Dict[str, int]) -> tuple:
    """The integers the process already holds that say what a step's interval went to: the
    four laps' totals, the collector's pauses, the programs compiled. Dictionary reads."""
    dispatch, report, data, user = _LOOP_COUNTERS
    return (totals[dispatch], totals[report], totals[data], totals[user],
            core_worker.process_counters()["gc_pause_ns_total"], telemetry.compile_counters()["compiles_total"])


def _os_numbers(steps: int) -> Dict[str, int]:
    """What the operating system says of the process and of the calling thread: CPU time, the
    thread's voluntary and involuntary context switches, its wait on the run queue. SYSTEM
    CALLS, so never on a step's path (PERF.md section 6, PR 35: a burst of them on the loop's
    thread changes which of the machine's two step times a process runs at): read at a
    refresh and after a slow step has happened, beside the loop's count of `steps` and the
    instant. A field the platform has not is left out."""
    out = {"steps": steps, "at_ns": time.perf_counter_ns(), "process_cpu_ns": time.process_time_ns()}
    try:
        import resource

        usage = resource.getrusage(resource.RUSAGE_THREAD)
        out["voluntary_switches"], out["involuntary_switches"] = usage.ru_nvcsw, usage.ru_nivcsw
    except (ImportError, AttributeError, ValueError, OSError):
        pass  # no `resource`, or no RUSAGE_THREAD: not Linux
    try:
        with open("/proc/thread-self/schedstat") as f:
            out["thread_cpu_ns"], out["run_queue_wait_ns"] = (int(n) for n in f.read().split()[:2])
    except (OSError, ValueError):
        pass
    return out


def _refresh(loop: _Loop, steps: int) -> None:
    """The threshold from the histogram's buckets, and the operating system's numbers to
    hold a slow step's against; the next at `steps + REFRESH_EVERY`."""
    from ray_tpu.util.metrics import histogram_quantile

    median = histogram_quantile(_step_interval()._export(), 0.5)
    if median:
        loop.slow_after_ns = int(SLOW_FACTOR * median * 1e9)
    loop.refresh_at = steps + REFRESH_EVERY
    loop.at_refresh = _os_numbers(steps)


def _slow_step(loop: _Loop, step: int, interval_ns: int) -> None:
    """One warning line and one count for the step that took `interval_ns` entry to entry:
    how the interval divides over the loop's laps, the collector's pauses and the compiles
    of the same step, and what the operating system counted since the last refresh (at
    most REFRESH_EVERY steps back, so a stall of seconds dominates it). A step that stood
    still with no CPU used, no collection and no compile is told from one the program
    held up. With the ring off."""
    totals = loop.clock.totals
    totals[_SLOW] += 1
    *laps, gc_ns, compiles = (now - then for now, then in zip(_held(totals), loop.at_entry))
    then, now = loop.at_refresh, _os_numbers(step)
    ms = 1e-6
    since = [f"{label} {(now[key] - then[key]) * scale:.{digits}f}{unit}" for key, label, scale, digits, unit in (
        ("process_cpu_ns", "process CPU", ms, 1, " ms"), ("thread_cpu_ns", "loop thread CPU", ms, 1, " ms"),
        ("run_queue_wait_ns", "run-queue wait", ms, 1, " ms"), ("voluntary_switches", "voluntary switches", 1, 0, ""),
        ("involuntary_switches", "involuntary", 1, 0, "")) if key in now and key in then]
    LOGGER.warning(
        "slow train step: step %d took %.1f ms entry to entry against a median of %.1f ms; laps %s ms; "
        "collector pauses %.1f ms, compiles %d; in the %d steps and %.1f ms since the threshold was read: %s",
        step, interval_ns * ms, loop.slow_after_ns / SLOW_FACTOR * ms,
        " ".join(f"{name.rpartition('.')[2]} {n * ms:.1f}" for name, n in zip(LOOP_SPANS, laps)),
        gc_ns * ms, compiles, now["steps"] - then["steps"], (now["at_ns"] - then["at_ns"]) * ms, ", ".join(since))
    loop.at_refresh = now  # the next slow step's numbers are its own


def enter_step() -> Optional[int]:
    """The step callable is entered (train/step.py): the `dispatch` lap begins, the step
    is counted, the time since the last entry is observed and, past the threshold, left
    on record (`_slow_step`). -> what `leave_step` takes."""
    loop = _loop()
    back = loop.clock.lap
    now = loop.clock.enter(DISPATCH)
    totals = loop.clock.totals
    steps = totals[_STEPS]  # (the count of the step whose interval ends here)
    if loop.last_step_ns:
        interval = now - loop.last_step_ns
        _step_interval().observe(interval * 1e-9)
        if interval > loop.slow_after_ns:
            _slow_step(loop, steps, interval)
    loop.last_step_ns = now
    totals[_STEPS] = steps + 1
    if steps + 1 == loop.refresh_at:
        _refresh(loop, steps + 1)
    loop.at_entry = _held(totals)
    return back


def leave_step(back: Optional[int]) -> None:
    _local.loop.clock.enter(back)


class CountedStep:
    """A step callable (train/step.py's jitted step, train/grad_sync.py's) with the train
    loop's clock around its call: the `dispatch` lap from entry to return (pytree
    flattening, donation, the enqueue; a compile if there is one), `train_steps_total`,
    the interval since the last entry. Tracing on or off that is two clock reads and
    integer additions a call. Everything else is the step's own: `.lower(...)`,
    `._cache_size()`, its name (the profile's module stays `jit_step`, the compile
    cache's key knows nothing of this wrapper)."""

    def __init__(self, jitted):
        self._jitted = jitted

    def __call__(self, *args, **kwargs):
        back = enter_step()
        try:
            return self._jitted(*args, **kwargs)
        finally:
            leave_step(back)

    def __getattr__(self, name):  # only what this class does not define
        return getattr(self._jitted, name)


def record_setup(phase: str, start_wall_ns: int, dur_ns: int, **args: Any) -> None:
    """One part of the way from `fit()` to the first line of the user's loop, measured
    where it happens: a ring event under the phase's name on the wall clock (the ring's
    head offset places it beside other processes'), `train_setup_seconds{phase}`, and
    `metrics()["setup_seconds"]` of this process."""
    _setup_seconds[phase] = dur_ns * 1e-9
    telemetry.get_gauge(
        "train_setup_seconds", "seconds of each part of the way from fit() to the "
        "user's loop", tag_keys=("phase",)).set(dur_ns * 1e-9, tags={"phase": phase})
    telemetry.complete(phase, "train", start_wall_ns, dur_ns, **args)


def metrics() -> Dict[str, Any]:
    """This process's always-on integers of the training path, as `JaxLLMEngine.metrics()`
    gives the engine's: `train_loop_{dispatch,report,data,user}_ns_total` (the laps of
    every thread that ran steps), `train_steps_total`, `train_slow_steps_total` (steps
    of more than SLOW_FACTOR x the median interval, each a warning line of the worker's
    log: `_slow_step`), `compiles_total` /
    `compile_ns_total` (the process's: a step that compiled again shows here), the
    worker's `worker_tasks_total` / `worker_task_ns_total` and the collector's
    `gc_pause_ns_total` / `gc_collections_total` (core/worker.py), and `setup_seconds`
    by phase. Monotonic: read them before and after a window and divide the differences.
    Tracing on or off."""
    with _loops_lock:
        for loop in [l for l in _loops if not l.thread.is_alive()]:
            # a thread that ran steps outside a session and ended without `_end_loop`:
            # its closed laps stay in the sums, its open one (`user`, of a length nobody
            # measured) stops growing
            _retire_locked(loop)
        out: Dict[str, Any] = dict(_retired)
        for loop in _loops:
            for key, n in loop.clock.read().items():
                out[key] += n
    out.update(telemetry.compile_counters())
    out.update(core_worker.process_counters())
    out["setup_seconds"] = dict(_setup_seconds)
    return out


@dataclass
class TrainContext:
    """Reference: ray.train.get_context() — world/rank topology of the worker group."""

    world_size: int
    world_rank: int
    local_rank: int
    local_world_size: int
    node_rank: int
    experiment_name: str = ""
    trial_name: str = ""

    def get_world_size(self) -> int:
        return self.world_size

    def get_world_rank(self) -> int:
        return self.world_rank

    def get_local_rank(self) -> int:
        return self.local_rank

    def get_local_world_size(self) -> int:
        return self.local_world_size

    def get_node_rank(self) -> int:
        return self.node_rank

    def get_experiment_name(self) -> str:
        return self.experiment_name


class _TrainSession:
    def __init__(
        self,
        train_fn: Callable[[Dict[str, Any]], None],
        config: Dict[str, Any],
        context: TrainContext,
        checkpoint: Optional[Checkpoint] = None,
        dataset_shards: Optional[Dict[str, Any]] = None,
        staging_dir: Optional[str] = None,
    ):
        self.train_fn = train_fn
        self.config = config
        self.context = context
        self.starting_checkpoint = checkpoint
        self.dataset_shards = dataset_shards or {}
        self.staging_dir = staging_dir
        self.results: "queue.Queue" = queue.Queue()
        self.error: Optional[BaseException] = None
        self.finished = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        started_wall, started = time.time_ns(), time.perf_counter_ns()
        for phase, (wall_ns, dur_ns) in core_worker.boot_stamps().items():
            record_setup(phase, wall_ns, dur_ns, rank=self.context.world_rank)

        def run():
            try:
                _loop()  # the thread's laps begin, in `user`
                record_setup("train.setup.loop_entered", started_wall,
                             time.perf_counter_ns() - started, rank=self.context.world_rank)
                self.train_fn(self.config)
            except BaseException as e:  # noqa: BLE001 — report worker crash faithfully
                self.error = e
            finally:
                _end_loop()
                self.finished.set()

        self._thread = threading.Thread(target=run, daemon=True, name="train_loop")
        self._thread.start()

    def report(self, metrics: Dict[str, Any], checkpoint: Optional[Checkpoint] = None) -> None:
        with telemetry.lap(LOOP_SPANS[REPORT]):  # checkpoint staging lies here
            self._report(metrics, checkpoint)

    def _report(self, metrics: Dict[str, Any], checkpoint: Optional[Checkpoint]) -> None:
        if checkpoint is not None and self.staging_dir is not None:
            # Stage into run storage now: the caller may delete its snapshot dir the
            # moment report() returns, long before the driver polls.
            from . import storage

            # remote staging UPLOADS from this worker's host (reference
            # _internal/storage.py persist_to_storage on the worker); local
            # staging keeps the zero-copy move
            if not storage.is_remote(self.staging_dir):
                os.makedirs(self.staging_dir, exist_ok=True)
            dest = storage.join_any(self.staging_dir,
                                    f"staged_{uuid.uuid4().hex[:12]}")
            storage.persist_dir(checkpoint.path, dest)
            checkpoint = Checkpoint(dest)
        self._record_report(metrics)
        self.results.put({"metrics": metrics, "checkpoint": checkpoint})

    def _record_report(self, metrics: Dict[str, Any]) -> None:
        """Train load signals: an MFU gauge whenever the loop reports one
        (bench.py's trainer path does), plus a timeline event per report."""
        try:
            tags = {"rank": str(self.context.world_rank)}
            mfu = metrics.get("mfu")
            if isinstance(mfu, (int, float)):
                telemetry.get_gauge(
                    "train_mfu", "model FLOPs utilization reported by the "
                    "training loop", tag_keys=("rank",)).set(float(mfu),
                                                             tags=tags)
            tps = metrics.get("tokens_per_sec")
            if isinstance(tps, (int, float)):
                telemetry.get_gauge(
                    "train_tokens_per_s", "training tokens/s reported by the "
                    "training loop", tag_keys=("rank",)).set(float(tps),
                                                             tags=tags)
            if telemetry.enabled():
                telemetry.event(
                    "train.report", "train", rank=self.context.world_rank,
                    **{k: v for k, v in metrics.items()
                       if isinstance(v, (int, float, str, bool))})
        # graftlint: allow[swallowed-exception] telemetry emission is best-effort; a report must never fail on it
        except Exception:
            pass  # telemetry must never fail a report

    def drain(self, max_items: Optional[int] = None) -> list:
        out = []
        while max_items is None or len(out) < max_items:
            try:
                out.append(self.results.get_nowait())
            except queue.Empty:
                break
        return out


def _set_session(s: Optional[_TrainSession]) -> None:
    global _session
    with _session_lock:
        _session = s


def _get_session() -> Optional[_TrainSession]:
    with _session_lock:
        return _session


# -- public API (mirrors ray.train.*) --------------------------------------------------
def report(metrics: Dict[str, Any], checkpoint: Optional[Checkpoint] = None) -> None:
    """Reference: ray.train.report (session.py:672)."""
    s = _get_session()
    if s is None:
        raise RuntimeError("ray_tpu.train.report() called outside a training worker")
    s.report(metrics, checkpoint)


def get_context() -> TrainContext:
    s = _get_session()
    if s is None:
        raise RuntimeError("ray_tpu.train.get_context() called outside a training worker")
    return s.context


def get_checkpoint() -> Optional[Checkpoint]:
    s = _get_session()
    if s is None:
        raise RuntimeError("ray_tpu.train.get_checkpoint() called outside a training worker")
    return s.starting_checkpoint


@contextlib.contextmanager
def step_phase(name: str):
    """Time a phase of a training step that the loop runs as a block of its own: a span
    `train.phase.<name>` and `train_step_phase_seconds{phase}`, behind `step_phases` in
    the train row of `ray-tpu status`. The stock step (`make_train_step`) is ONE jitted
    program, so it has no `forward_backward` / `optimizer` to time from the host: the
    loop's own laps (`train.loop.*`, `ray_tpu.train.metrics()`) say where its thread's
    time goes, the device's profile where the program's does. What still enters this:
    `grad_sync`'s telemetry mode (`forward_backward` / `bucket_wait` / `optimizer`, three
    programs with a sync after each) and a user's loop around blocks of its own (`data`,
    an evaluation pass).

    Usage inside a train loop:
        with train.step_phase("eval"):
            ...

    Works outside a session too (bench scripts): rank then reports as -1."""
    s = _get_session()
    rank = s.context.world_rank if s is not None else -1
    t0 = time.perf_counter()
    with telemetry.span(f"train.phase.{name}", "train", rank=rank):
        yield
    telemetry.get_histogram(
        "train_step_phase_seconds", "per-phase training step time",
        tag_keys=("phase",)).observe(time.perf_counter() - t0,
                                     tags={"phase": name})


def get_dataset_shard(dataset_name: str = "train"):
    """Reference: ray.train.get_dataset_shard — this worker's split of a Dataset."""
    s = _get_session()
    if s is None:
        raise RuntimeError("get_dataset_shard() called outside a training worker")
    shard = s.dataset_shards.get(dataset_name)
    if shard is None:
        raise KeyError(
            f"no dataset shard named {dataset_name!r}; passed datasets: {list(s.dataset_shards)}"
        )
    return shard
