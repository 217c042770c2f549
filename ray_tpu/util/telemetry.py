"""Hot-path telemetry: a per-process lock-light ring buffer of timeline events.

The metrics registry (util/metrics.py) answers "how much / how fast" with
counters and histograms; this module answers "when and for how long" with
nanosecond-timestamped events that merge into ONE cross-worker chrome-trace
timeline (util/state.telemetry_timeline). Instrumentation points live on the
hottest paths in the system — data-plane pulls, collective phases, serve
request lifecycles, train steps — so the recorder is built around two rules:

  near-zero when disabled   every probe is `if telemetry.enabled():` around a
                            memoized env read (~0.1us) plus nothing. span()
                            returns a shared no-op context manager, never a
                            fresh generator frame.
  bounded when enabled      events land in a deque(maxlen=ring_size): memory
                            is capped, the hot path never blocks on a slow
                            consumer, and overflow silently drops the OLDEST
                            events (the flush thread logs — never print()s —
                            a throttled warning with the drop count so lost
                            history is visible without corrupting worker
                            stdout or tqdm progress bars).

Enablement rides the tracing switch: RAY_TPU_TRACING=1 (or
tracing.enable_tracing() / telemetry.enable()) turns both the span tracer and
this recorder on. Ring capacity: RAY_TPU_TELEMETRY_RING_SIZE.

The profiler's clock: while a `jax.profiler` session records in this process,
span() ALSO enters a `jax.profiler.TraceAnnotation` of the same name, whether
or not the ring is enabled, so the span lies on the host plane of the same
`.xplane.pb` as the device's `XLA Ops`, on one clock by construction. JAX is
never imported for this: a process that has not imported it has no profiler
to write to. A profile's timeline starts at zero when the session starts;
the first span after that also writes one `telemetry.clock_sync` annotation
that carries `time.time_ns()` as `wall_ns`, so that whoever holds the profile
can place the ring's events (complete(): spans whose two ends are on
different threads) on it: profile_origin_ns().

Transport: worker processes flush their ring to the head over the same
control-pipe push the metrics registry uses (core/worker.py push_telemetry ->
core/node.py "telemetry" message), tagged with a clock offset measured against
the head via an NTP-style state_request("head_clock_ns") handshake — so the
merged timeline's timestamps are comparable across processes. The in-process
driver keeps events local; util/state folds them in on read.

Usage:
    from ray_tpu.util import telemetry
    with telemetry.span("transfer.pull", "transfer", bytes=n):
        ...
    telemetry.event("collective.abort", "collective", group=g, epoch=e)
"""
from __future__ import annotations

import logging
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ray_tpu.config import memoized_flag

logger = logging.getLogger("ray_tpu.telemetry")

_tracing_flag = memoized_flag("tracing")
_ring_size_flag = memoized_flag("telemetry_ring_size")

# tri-state override: None = the RAY_TPU_TRACING env decides; True/False from
# enable()/disable() wins (bench toggles between rounds without re-spawning)
_forced: Optional[bool] = None

_lock = threading.Lock()
_ring: deque = deque(maxlen=8192)
_dropped = 0  # events lost to ring overflow since the last flush/drain
# records made where `_lock` may already be held by the same thread (a `gc.callbacks`
# hook runs wherever the interpreter stops, also inside `_append` and `drain`): a plain
# list, whose append is atomic, spliced into the ring by the next `_append` or `drain`
_deferred: List[dict] = []
_flush_thread: Optional[threading.Thread] = None
_clock_offset_ns: Optional[int] = None  # head_clock - local_clock (workers)


def enabled() -> bool:
    """THE hot-path gate: a memoized env read + one comparison."""
    if _forced is not None:
        return _forced
    return bool(_tracing_flag())


def enable() -> None:
    """Force-enable in this process (bench/test toggle; env untouched)."""
    global _forced
    _forced = True


def disable() -> None:
    global _forced
    _forced = False


def reset_forced() -> None:
    """Back to env-driven enablement (RAY_TPU_TRACING)."""
    global _forced
    _forced = None


# --------------------------------------------------------- the profiler's clock

CLOCK_SYNC = "telemetry.clock_sync"  # marker annotation, `wall_ns` in its stats

_annotation_cls = None  # jax.profiler.TraceAnnotation, once this process imported jax
_synced = False  # this profile has its CLOCK_SYNC marker


def _profiling():
    """`jax.profiler.TraceAnnotation` while a profiler session records in this
    process, else None (~0.1us). Never imports JAX: the driver and the head
    must not, and a process without it has no session to write to. That a
    profile ended is seen by the next span: a second profile that starts
    before any span was made in between gets no CLOCK_SYNC marker."""
    global _annotation_cls, _synced
    cls = _annotation_cls
    if cls is None:
        cls = getattr(getattr(sys.modules.get("jax"), "profiler", None),
                      "TraceAnnotation", None)
        if cls is None:
            return None
        _annotation_cls = cls
    if not cls.is_enabled():
        _synced = False
        return None
    if not _synced:
        # first span of this profile: tie its timeline to the ring's clock
        _synced = True
        with cls(CLOCK_SYNC, wall_ns=time.time_ns()):
            pass
    return cls


def profile_origin_ns(xplane_path: str) -> Optional[int]:
    """The `time.time_ns()` of time zero of a profile written while spans were
    recorded (`wall_ns - start` of its CLOCK_SYNC marker), so a ring event
    stamped `ts_ns` lies at `ts_ns - origin` on the profile's timeline. None
    when the profile holds no marker. Imports JAX (no backend): for whoever
    opens a profile, not for the hot path."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(xplane_path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == CLOCK_SYNC:
                    wall_ns = dict(ev.stats).get("wall_ns")
                    if wall_ns is not None:
                        return int(wall_ns) - int(ev.start_ns)
    return None


def _resize_ring_locked() -> None:
    global _ring
    want = max(64, int(_ring_size_flag() or 8192))
    if _ring.maxlen != want:
        _ring = deque(_ring, maxlen=want)


# ------------------------------------------------------------------ recording

def _active_trace_id() -> Optional[str]:
    """The caller's request-scoped trace id (util/tracing.py contextvar), or
    None. Events recorded inside a traced request are tagged with it so
    state.request_trace can attribute data-plane pulls / engine phases to the
    request's critical path. Pure read — never starts a trace."""
    try:
        from ray_tpu.util import tracing

        return tracing.current_trace_id()
    # graftlint: allow[swallowed-exception] degrades to the coded fallback (return None) by design
    except Exception:
        return None


def _tag_trace(args: Dict[str, Any]) -> Dict[str, Any]:
    if "trace_id" not in args:
        tid = _active_trace_id()
        if tid is not None:
            args["trace_id"] = tid
    elif args["trace_id"] is None:
        del args["trace_id"]  # explicit "untraced" from a lifecycle recorder
    return args


def _push_locked(rec: dict) -> None:
    global _dropped
    if len(_ring) == _ring.maxlen:
        _dropped += 1
    _ring.append(rec)


def _splice_deferred_locked() -> None:
    while _deferred:  # a collection may add one while this runs: pop, never swap
        _push_locked(_deferred.pop(0))


def _append(rec: dict) -> None:
    with _lock:
        _resize_ring_locked()
        _splice_deferred_locked()
        _push_locked(rec)
    _ensure_flush_thread()


def event(name: str, cat: str = "app", **args: Any) -> None:
    """Record an instant event (chrome-trace 'i' phase) at now."""
    if not enabled():
        return
    _append({
        "name": name, "cat": cat, "ts_ns": time.time_ns(), "dur_ns": None,
        "tid": threading.current_thread().name, "args": _tag_trace(args or {}),
    })


class _Span:
    """A lightweight timed region. Duration from perf_counter_ns (monotonic,
    ns resolution); the wall anchor from time_ns at entry places it on the
    shared timeline. Extra attributes may be attached mid-span via set().
    `ring`: record into the ring at exit; `note_cls`: the profiler's
    annotation class while a profile records (entered right after the wall
    anchor is read, so both starts are one instant on two timelines)."""

    __slots__ = ("name", "cat", "args", "_t0_wall", "_t0_perf", "_ring",
                 "_note_cls", "_note")

    def __init__(self, name: str, cat: str, args: Dict[str, Any],
                 ring: bool = True, note_cls=None):
        self.name = name
        self.cat = cat
        self.args = args
        self._ring = ring
        self._note_cls = note_cls
        self._note = None

    def set(self, **kw: Any) -> None:
        self.args.update(kw)

    def __enter__(self) -> "_Span":
        if self._ring:
            # the trace tag is captured at ENTRY (the request thread);
            # __exit__ may run after the contextvar was reset
            _tag_trace(self.args)
        self._t0_wall = time.time_ns()
        if self._note_cls is not None:
            self._note = self._note_cls(self.name, **_scalars(self.args))
            self._note.__enter__()
        self._t0_perf = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dur = time.perf_counter_ns() - self._t0_perf
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
            self._note = None
        if not self._ring:
            return
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        _append({
            "name": self.name, "cat": self.cat, "ts_ns": self._t0_wall,
            "dur_ns": dur, "tid": threading.current_thread().name,
            "args": self.args,
        })


def _scalars(args: Dict[str, Any]) -> Dict[str, Any]:
    """What of a span's attributes an annotation can carry as its stats."""
    return {k: v for k, v in args.items() if isinstance(v, (str, int, float, bool))}


def annotate(name: str, **args: Any):
    """An ENTERED profiler annotation while a profile records in this process (its
    holder calls `__exit__(None, None, None)`), else None: for a region whose two ends
    are two calls, as a `gc.callbacks` function sees a collection. No ring entry."""
    note_cls = _profiling()
    if note_cls is None:
        return None
    note = note_cls(name, **_scalars(args))
    note.__enter__()
    return note


class _NoopSpan:
    """Shared disabled-path context manager: no allocation per probe."""

    __slots__ = ()

    def set(self, **kw: Any) -> None:
        pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NOOP = _NoopSpan()


def span(name: str, cat: str = "app", **args: Any):
    """Context manager recording a complete ('X') event around the block:
    into the ring when enabled(), and as a profiler annotation while a
    `jax.profiler` session records in this process (enabled() or not).
    Neither: the shared no-op."""
    ring, note_cls = enabled(), _profiling()
    if not ring and note_cls is None:
        return _NOOP
    return _Span(name, cat, dict(args), ring, note_cls)


def complete(name: str, cat: str, start_wall_ns: int, dur_ns: int,
             **args: Any) -> None:
    """Record a complete event whose timing the caller already measured
    (request lifecycles that start and end on different threads)."""
    if not enabled():
        return
    _append({
        "name": name, "cat": cat, "ts_ns": int(start_wall_ns),
        "dur_ns": int(dur_ns), "tid": threading.current_thread().name,
        "args": _tag_trace(args or {}),
    })


def complete_deferred(name: str, cat: str, start_wall_ns: int, dur_ns: int,
                      **args: Any) -> None:
    """`complete` for a caller that may run while its own thread holds `_lock`
    (the garbage collector's callback): takes no lock, starts no thread and
    reads no contextvar. The record waits in `_deferred` for the next
    `_append` or `drain`."""
    if not enabled():
        return
    _deferred.append({
        "name": name, "cat": cat, "ts_ns": int(start_wall_ns),
        "dur_ns": int(dur_ns), "tid": threading.current_thread().name,
        "args": args,
    })


# ------------------------------------------------------------- laps of a loop

class LapClock:
    """Which phase of its turn ONE thread's loop is in. enter(i) ends the lap
    that was open and begins `names[i]` at the same instant, so every
    nanosecond of the thread lies in exactly one lap (time the thread waits to
    run, say for the interpreter lock after it woke other threads, goes to
    the lap it was in). Each lap is a span (ring + profiler annotation) and,
    tracing on or off, adds its duration to `totals[counters[i]]`: monotonic
    integers that a `metrics()` hands out, to be read before and after a
    window. `totals` may be the owner's own dict of counters (the keys must be
    there). The loop is one thread: no lock."""

    __slots__ = ("names", "counters", "cat", "totals", "lap", "_span", "_t0")

    def __init__(self, names, counters, cat: str,
                 totals: Optional[Dict[str, int]] = None):
        self.names, self.counters, self.cat = tuple(names), tuple(counters), cat
        self.totals = dict.fromkeys(self.counters, 0) if totals is None else totals
        self.lap: Optional[int] = None  # the open lap's index
        self._span, self._t0 = None, 0

    def read(self) -> Dict[str, int]:
        """The totals with the open lap counted up to now. Exact from the loop's own
        thread; another thread's read may be off by the lap that changes under it."""
        out, i, t0 = dict(self.totals), self.lap, self._t0
        if i is not None:
            out[self.counters[i]] += time.perf_counter_ns() - t0
        return out

    def enter(self, i: Optional[int]) -> int:
        """-> the instant of the change, on `perf_counter_ns`' clock."""
        now = time.perf_counter_ns()
        if self.lap is not None:
            self.totals[self.counters[self.lap]] += now - self._t0
            self._span.__exit__(None, None, None)
        self.lap = i
        if i is None:  # the loop ends
            self._span = None
            return now
        self._t0 = now
        self._span = span(self.names[i], self.cat)
        self._span.__enter__()
        return now


# The lap a dataset's iterator (data/iterator.py) opens in the loop of the thread that
# asks it for a batch; the train loop's clock (train/session.py) has a lap of this name.
DATA_LAP = "train.loop.data"
_thread = threading.local()  # .clock: the LapClock of the loop this thread runs


def set_thread_clock(clock: Optional[LapClock]) -> None:
    """Say that the calling thread runs the loop `clock` times, so that code
    the loop calls into (a dataset's iterator) can open a lap of it by name."""
    _thread.clock = clock


class _Lap:
    __slots__ = ("_clock", "_i", "_back")

    def __init__(self, clock: LapClock, i: int):
        self._clock, self._i = clock, i

    def __enter__(self) -> "_Lap":
        self._back = self._clock.lap
        if self._back != self._i:  # the lap inside itself (iter_jax_batches around
            self._clock.enter(self._i)  # iter_batches) is the lap that is open
        return self

    def __exit__(self, *exc) -> None:
        if self._back != self._i:
            self._clock.enter(self._back)


def lap(name: str):
    """Context manager: the lap `name` of the calling thread's loop around the
    block, after which the lap that was open goes on. The shared no-op where
    the thread runs no loop that knows the name."""
    clock = getattr(_thread, "clock", None)
    if clock is None or name not in clock.names:
        return _NOOP
    return _Lap(clock, clock.names.index(name))


# ------------------------------------------------------ compiles of a process

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# programs this PROCESS compiled (or read from the compile cache) and the
# seconds that took: JAX reports them to process-wide listeners that cannot be
# taken off again, so one listener and one count serve every engine and every
# train step here
_COMPILES = {"compiles_total": 0, "compile_ns_total": 0}
_compile_listener_lock = threading.Lock()
_compile_listener_on = False


def compile_counters() -> Dict[str, int]:
    """The process's `compiles_total` / `compile_ns_total`, live (read, do not
    write). Registers the `jax.monitoring` listener at the first call, once a
    process; imports JAX, so call it from code that runs programs."""
    global _compile_listener_on
    with _compile_listener_lock:
        if _compile_listener_on:
            return _COMPILES
        _compile_listener_on = True
    import jax

    def on_duration(event: str, duration_secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with _compile_listener_lock:
                _COMPILES["compiles_total"] += 1
                _COMPILES["compile_ns_total"] += int(duration_secs * 1e9)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return _COMPILES


# ------------------------------------------------------------------- draining

def drain() -> List[dict]:
    """Pop every buffered event (oldest first). Used by the flush thread and
    by util/state for the in-process driver's ring."""
    global _dropped
    with _lock:
        _splice_deferred_locked()
        out = list(_ring)
        _ring.clear()
        n_dropped, _dropped = _dropped, 0
    if n_dropped:
        # logger, NEVER print(): worker stdout/stderr interleaves with tqdm
        # progress bars and the head's log capture — a raw print here would
        # corrupt both. Finalize any in-progress bar line first so the warning
        # starts on its own line.
        try:
            from ray_tpu.experimental.tqdm_ray import ensure_newline

            ensure_newline()
        # graftlint: allow[swallowed-exception] a torn tqdm bar must never block the overflow warning itself
        except Exception:
            pass
        logger.warning(
            "telemetry ring overflowed: %d event(s) dropped (raise "
            "RAY_TPU_TELEMETRY_RING_SIZE or flush more often)", n_dropped)
    return out


def pending() -> int:
    with _lock:
        return len(_ring) + len(_deferred)


# -------------------------------------------------------------------- flushing

def clock_offset_ns() -> int:
    """head_clock - local_clock, measured once per process with an NTP-style
    request/response handshake against the head (midpoint of the round trip
    taken as the simultaneity point). The driver holding the cluster IS the
    head clock: offset 0."""
    global _clock_offset_ns
    if _clock_offset_ns is not None:
        return _clock_offset_ns
    from ray_tpu.core import global_state

    if global_state.try_cluster() is not None:
        _clock_offset_ns = 0
        return 0
    w = global_state.try_worker()
    if w is None or not hasattr(w, "state_request"):
        _clock_offset_ns = 0
        return 0
    try:
        t0 = time.time_ns()
        head_ns = int(w.state_request("head_clock_ns"))
        t1 = time.time_ns()
        _clock_offset_ns = head_ns - (t0 + t1) // 2
    # graftlint: allow[swallowed-exception] degrades to the coded fallback (_clock_offset_ns = 0) by design
    except Exception:
        _clock_offset_ns = 0
    return _clock_offset_ns


def flush() -> None:
    """Push buffered events to the head now (worker / remote client driver);
    the in-process driver keeps its ring local for util/state to fold in."""
    from ray_tpu.core import global_state

    w = global_state.try_worker()
    if (w is None or not hasattr(w, "push_telemetry")
            or global_state.try_cluster() is not None):
        return
    offset = clock_offset_ns()
    events = drain()
    if not events:
        return
    try:
        w.push_telemetry({"clock_offset_ns": offset, "events": events,
                          "pid": os.getpid()})
    # graftlint: allow[swallowed-exception] telemetry flush is best-effort; the ring re-drains next interval
    except Exception:
        pass  # pipe closed: worker exiting


def _flush_interval() -> float:
    """Telemetry rides the metrics push cadence — same helper, not a copy."""
    from ray_tpu.util.metrics import _report_interval

    return _report_interval()


_flush_na = False  # cached "this process never flushes" verdict


def _ensure_flush_thread() -> None:
    """Called per append: after the first resolution this is one global read.
    The in-process driver/head never flushes (util/state reads its ring
    directly) — cache that verdict instead of probing global_state per event.
    A process with NO runtime context yet (telemetry before ray_tpu.init) is
    left unresolved: a remote client driver must still get its flusher once
    init lands."""
    global _flush_thread, _flush_na
    if _flush_thread is not None or _flush_na:
        return
    from ray_tpu.core import global_state

    if global_state.try_cluster() is not None:
        _flush_na = True  # in-process driver/head: the ring is read locally
        return
    w = global_state.try_worker()
    if w is None:
        return  # pre-init: can't decide yet
    if not hasattr(w, "push_telemetry"):
        _flush_na = True
        return

    def loop():
        while True:
            time.sleep(_flush_interval())
            try:
                with span("worker.flush_telemetry", "worker"):
                    flush()
            # graftlint: allow[swallowed-exception] degrades to the coded fallback (return) by design
            except Exception:
                return

    with _lock:
        if _flush_thread is None:
            _flush_thread = threading.Thread(target=loop, daemon=True,
                                             name="telemetry-flush")
            _flush_thread.start()


def align_batch(batch: dict, proc: str) -> List[dict]:
    """Head-side merge step: apply the batch's measured clock offset to every
    event timestamp and tag the producing process, so the cluster ring holds
    ONE timeline whose ts_ns values are directly comparable."""
    off = int(batch.get("clock_offset_ns") or 0)
    out = []
    for ev in batch.get("events", ()):
        ev = dict(ev)
        ev["ts_ns"] = int(ev["ts_ns"]) + off
        ev["proc"] = proc
        out.append(ev)
    return out


# --------------------------------------------------------------- lazy metrics

_metric_cache: Dict[str, Any] = {}
_metric_cache_lock = threading.Lock()


def get_counter(name: str, description: str = "", tag_keys=None):
    """Process-wide metric singletons for instrumentation points: creating a
    Counter/Gauge/Histogram registers it forever, so hot paths must reuse one
    instance per name instead of re-instantiating per call."""
    return _get_metric("counter", name, description, tag_keys)


def get_gauge(name: str, description: str = "", tag_keys=None):
    return _get_metric("gauge", name, description, tag_keys)


def get_histogram(name: str, description: str = "", tag_keys=None,
                  boundaries=None):
    return _get_metric("histogram", name, description, tag_keys, boundaries)


def export_counters(read, names, description: str = "") -> None:
    """Carry monotonic integers that live outside the registry into it: one
    counter a name, read as `read()[name]` whenever the registry exports (a
    worker's push, the driver's state API). Once a name: a second call with a
    name that is already there leaves it."""
    from ray_tpu.util import metrics as rm

    with _metric_cache_lock:
        for name in names:
            if name not in _metric_cache:
                _metric_cache[name] = rm.CounterView(
                    name, lambda name=name: read()[name], description)


def _get_metric(kind: str, name: str, description: str, tag_keys,
                boundaries=None):
    with _metric_cache_lock:
        m = _metric_cache.get(name)
        if m is None:
            from ray_tpu.util import metrics as rm

            if kind == "counter":
                m = rm.Counter(name, description, tag_keys=tag_keys)
            elif kind == "gauge":
                m = rm.Gauge(name, description, tag_keys=tag_keys)
            else:
                m = rm.Histogram(name, description, boundaries=boundaries,
                                 tag_keys=tag_keys)
            _metric_cache[name] = m
        return m
