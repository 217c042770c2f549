"""Worker process: task-execution loop + upcall channel back to the node service.

Capability parity: reference CoreWorker task execution loop
(src/ray/core_worker/core_worker.cc ExecuteTask:3298, _raylet.pyx task_execution_handler:2318)
and python/ray/_private/workers/default_worker.py. One process per worker; a duplex pipe to
the node service carries task dispatch downstream and submissions/gets/puts upstream, so
nested tasks and ray_tpu.get() inside tasks work exactly like the reference.

Accelerator isolation: workers are spawned with an `accel` tag. "cpu" workers set
JAX_PLATFORMS=cpu so they never open the TPU chip; "tpu" workers leave platform
selection alone and are told at spawn which chips are theirs (TPU_VISIBLE_CHIPS,
core/accelerators.py) — a chip belongs to one process at a time.
"""
from __future__ import annotations

import gc
import os
import sys
import threading
import traceback
import queue
import time
from typing import Any, Dict, List, Optional, Tuple

import cloudpickle

from . import global_state, object_store, serialization
from .exceptions import TaskError
from .ids import ActorID, ObjectID, TaskID, WorkerID
from .object_ref import ObjectRef
from .task_spec import TaskSpec, _RefMarker

from ray_tpu.util import telemetry

import contextvars

_ASYNC_TASK_ID: "contextvars.ContextVar[Optional[TaskID]]" = contextvars.ContextVar(
    "rt_async_task_id", default=None)


# What this PROCESS did beside whatever loop runs in it, always on: the tasks and actor
# methods it executed (`worker.task` spans: the executor's `poll_session` at 20 Hz shows
# here) and the garbage collector's pauses. Monotonic integers, read through
# `process_counters()`; `ray_tpu.train.metrics()` hands them on, and a process that runs a
# train loop carries them to the head with its laps (train/session.py).
_COUNTERS = {"worker_tasks_total": 0, "worker_task_ns_total": 0,
             "gc_pause_ns_total": 0, "gc_collections_total": 0}
_counters_lock = threading.Lock()  # tasks end on several threads; collections do not overlap
_GC_EVENT_NS = 1_000_000  # a pause this long is written to the ring as `worker.gc`
_gc = {"t0": 0, "note": None}
# phase -> (start, wall clock ns; duration ns): process creation -> `worker_main` entered
# -> `ready` sent -> the first task received. A train session publishes them
# (train/session.py: `train_setup_seconds{phase}`, the ring).
_BOOT: Dict[str, Tuple[int, int]] = {}
_entered_wall_ns = 0  # when `worker_main` was entered


def _count_task(dur_ns: int) -> None:
    with _counters_lock:
        _COUNTERS["worker_tasks_total"] += 1
        _COUNTERS["worker_task_ns_total"] += dur_ns


def process_counters() -> Dict[str, int]:
    return dict(_COUNTERS)


def boot_stamps() -> Dict[str, Tuple[int, int]]:
    return dict(_BOOT)


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """`gc.callbacks`: add the pause to the counters (two clock reads a collection). A
    pause of a millisecond or more is also a `worker.gc` event of the ring, and while a
    profile records every collection is an annotation on the thread it stopped. It runs
    wherever the interpreter stops, also in a thread that holds the ring's lock, so it
    takes no lock: the event goes through `telemetry.complete_deferred`."""
    if phase == "start":
        _gc["note"] = telemetry.annotate("worker.gc", generation=info["generation"])
        _gc["t0"] = time.perf_counter_ns()
        return
    dur = time.perf_counter_ns() - _gc["t0"]
    note, _gc["note"] = _gc["note"], None
    if note is not None:
        note.__exit__(None, None, None)
    _COUNTERS["gc_pause_ns_total"] += dur
    _COUNTERS["gc_collections_total"] += 1
    if dur >= _GC_EVENT_NS:
        telemetry.complete_deferred("worker.gc", "worker", time.time_ns() - dur, dur,
                                    generation=info["generation"], collected=info["collected"])


def count_collections() -> None:
    """Register the collector's callback, once a process."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def _process_created_wall_ns() -> Optional[int]:
    """When the kernel created this process, on the wall clock: /proc/self/stat's start
    time (ticks since boot) against /proc/uptime, to a hundredth of a second. None where
    there is no /proc."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])  # field 22, `starttime`
        with open("/proc/uptime") as f:
            age_s = float(f.read().split()[0]) - ticks / os.sysconf("SC_CLK_TCK")
        return time.time_ns() - int(age_s * 1e9)
    except (OSError, ValueError, IndexError):
        return None


class _ThreadPerCallExecutor:
    """Unbounded concurrency group (size 0): one daemon thread per call, so
    arbitrarily many parked calls (long-poll listeners) never exhaust a pool."""

    def __init__(self, name: str):
        self._name = name

    def submit(self, fn, *args):
        threading.Thread(target=fn, args=args, daemon=True,
                         name=f"cg-{self._name}").start()


class WorkerContext:
    """The worker-side implementation of the runtime API (get/put/submit/...)."""

    def __init__(self, conn, node_id_hex: str, worker_id_hex: str, accel: str):
        self.conn = conn
        self.node_id_hex = node_id_hex
        self.worker_id_hex = worker_id_hex
        self.accel = accel
        self._req_counter = 0
        self._req_lock = threading.Lock()
        self._reply_slots: Dict[int, list] = {}  # req_id -> [Event, ok, value]
        self._task_queue: "queue.Queue" = queue.Queue()
        self._fn_cache: Dict[bytes, Any] = {}
        self._registered_fns: set = set()
        self._send_lock = threading.Lock()
        self._recv_thread: Optional[threading.Thread] = None
        self.actor_instance: Any = None
        self.actor_id: Optional[ActorID] = None
        self._method_pool = None
        self._group_pools: Dict[str, Any] = {}  # concurrency group -> executor
        self._method_groups: Dict[str, str] = {}  # method name -> default group
        self._async_methods: set = set()  # async def methods (per-actor event loop)
        self._actor_loop = None  # asyncio loop thread, created on demand
        # per-thread: concurrent methods of a threaded actor each track their own task
        self._task_ctx = threading.local()
        self._loop_lock = threading.Lock()  # guards _actor_loop creation
        self._cancelled_streams: set = set()  # TaskIDs whose consumer dropped the stream
        self._exit = False

    @property
    def current_task_id(self) -> Optional[TaskID]:
        # async actor methods interleave on one loop thread, so their identity
        # is context-local (each asyncio.Task owns a contextvars copy); sync
        # paths fall back to the thread-local
        async_id = _ASYNC_TASK_ID.get()
        if async_id is not None:
            return async_id
        return getattr(self._task_ctx, "task_id", None)

    @current_task_id.setter
    def current_task_id(self, value: Optional[TaskID]) -> None:
        self._task_ctx.task_id = value

    # -- transport -----------------------------------------------------------------
    def _send(self, msg) -> None:
        with self._send_lock:
            self.conn.send_bytes(cloudpickle.dumps(msg))

    def _recv(self):
        return cloudpickle.loads(self.conn.recv_bytes())

    def _next_req_id(self) -> int:
        with self._req_lock:
            self._req_counter += 1
            return self._req_counter

    def _ensure_recv_thread(self) -> None:
        """Demux thread: the ONLY reader of the pipe. Replies wake their waiting thread
        via per-request events; tasks queue for the main loop. This makes the runtime
        API safe from any thread in the worker (threaded actors: serve proxy/replicas,
        train session reporter threads, ...)."""
        if self._recv_thread is not None:
            return
        def recv_loop():
            while True:
                try:
                    msg = self._recv()
                except (EOFError, OSError):
                    self._exit = True
                    # Fail every blocked _request() waiter (any thread) — otherwise
                    # a thread inside ray_tpu.get() would hang forever when the
                    # coordinator dies without an orderly shutdown.
                    with self._req_lock:
                        slots = list(self._reply_slots.values())
                        self._reply_slots.clear()
                    err = ConnectionError("lost connection to the node coordinator")
                    for slot in slots:
                        slot[1], slot[2] = False, err
                        slot[0].set()
                    self._task_queue.put(("exit",))
                    return
                kind = msg[0]
                if kind == "reply":
                    with self._req_lock:
                        slot = self._reply_slots.pop(msg[1], None)
                    if slot is not None:
                        slot[1], slot[2] = msg[2], msg[3]
                        slot[0].set()
                    # Unmatched replies (cancelled requests) are dropped.
                elif kind == "free":
                    object_store._segment_cache.drop(msg[1])
                elif kind == "dump_stacks":
                    # one-way reply straight from the recv thread (no _request):
                    # py-spy-style introspection of a possibly-busy worker
                    try:
                        self._send(("stacks", msg[1], self.worker_id_hex,
                                    _format_thread_stacks()))
                    # graftlint: allow[swallowed-exception] best-effort send to a possibly-dead peer; death is handled by heartbeat/reaper, not here
                    except Exception:
                        pass
                elif kind == "profile":
                    # py-spy-style SAMPLING profile: a detached thread samples
                    # this process for duration_s and sends collapsed stacks
                    # back (reference: py-spy record via dashboard reporter)
                    _, token, duration_s, hz = msg

                    def run_profile(token=token, duration_s=duration_s, hz=hz):
                        counts = _sample_collapsed_stacks(duration_s, hz)
                        try:
                            self._send(("stacks", token, self.worker_id_hex, counts))
                        # graftlint: allow[swallowed-exception] best-effort send to a possibly-dead peer; death is handled by heartbeat/reaper, not here
                        except Exception:
                            pass

                    threading.Thread(target=run_profile, daemon=True,
                                     name="rt-profiler").start()
                elif kind == "cancel_stream":
                    # consumer abandoned a streaming generator: the producing
                    # thread checks this set at every yield boundary
                    self._cancelled_streams.add(msg[1])
                elif kind == "head_restarted":
                    # the agent re-registered with a RESTARTED head: replies to
                    # requests sent on the old head are gone forever. Fail the
                    # blocked waiters typed (callers like the serve retry plane
                    # classify HeadUnavailableError and resend) instead of
                    # letting them hang on replies that will never come. The
                    # worker itself stays up — its pipe, actor state, and
                    # data-plane pulls are intact.
                    from ray_tpu.core.exceptions import HeadUnavailableError

                    with self._req_lock:
                        slots = list(self._reply_slots.values())
                        self._reply_slots.clear()
                    err = HeadUnavailableError(
                        msg[1] if len(msg) > 1 else 0.0, 0,
                        "head restarted; the pending reply was lost")
                    for slot in slots:
                        slot[1], slot[2] = False, err
                        slot[0].set()
                elif kind == "exit":
                    self._exit = True
                    self._task_queue.put(("exit",))
                else:  # task and anything main-loop-bound
                    self._task_queue.put(msg)

        self._recv_thread = threading.Thread(target=recv_loop, daemon=True, name="ray-tpu-recv")
        self._recv_thread.start()

    def _request(self, msg_type: str, *payload):
        """Send an upcall and block for its reply (thread-safe)."""
        self._ensure_recv_thread()
        req_id = self._next_req_id()
        slot = [threading.Event(), None, None]
        with self._req_lock:
            self._reply_slots[req_id] = slot
        self._send((msg_type, req_id) + payload)
        slot[0].wait()
        ok, value = slot[1], slot[2]
        if not ok:
            raise value
        return value

    # -- runtime API (mirrors DriverContext) ----------------------------------------
    def submit(self, spec: TaskSpec) -> List[ObjectRef]:
        self._send(("submit", spec))
        return [ObjectRef(oid, owned=True) for oid in spec.return_ids]

    def get(self, refs, timeout: Optional[float] = None):
        single = isinstance(refs, ObjectRef)
        ref_list = [refs] if single else list(refs)
        oids = [r.id for r in ref_list]
        locs = self._request("get", oids, timeout)
        values = [self._resolve_recovering(o, loc) for o, loc in zip(oids, locs)]
        return values[0] if single else values

    def _resolve_recovering(self, oid: ObjectID, loc):
        """resolve with lineage reconstruction on loss (reference ObjectRecoveryManager)."""
        try:
            return object_store.resolve(loc, oid=oid)
        except object_store.ObjectLost:
            new_loc = self._request("recover", oid)
            return object_store.resolve(new_loc, oid=oid)

    def put(self, value) -> ObjectRef:
        oid = ObjectID.generate()
        loc = object_store.materialize(value, oid)
        self._send(("put", oid, loc))
        return ObjectRef(oid, owned=True)

    def wait(self, refs, num_returns=1, timeout=None):
        oids = [r.id for r in refs]
        ready_ids, pending_ids = self._request("wait", oids, num_returns, timeout)
        by_id = {r.id: r for r in refs}
        return [by_id[i] for i in ready_ids], [by_id[i] for i in pending_ids]

    def decref(self, oid: ObjectID) -> None:
        try:
            self._send(("decref", oid))
        # graftlint: allow[swallowed-exception] best-effort send to a possibly-dead peer; death is handled by heartbeat/reaper, not here
        except Exception:
            pass

    def incref(self, oid: ObjectID) -> None:
        """Pin an object on the head node (ObjectRefGenerator.handoff: the pin
        outlives this process's refs and transfers to the adopting consumer).
        NOT best-effort: a failed pin must surface so the caller keeps relaying
        instead of handing off a stream the head may free under the adopter."""
        self._send(("incref", oid))

    def drop_stream(self, task_id: TaskID, start_index: int) -> None:
        try:
            self._send(("drop_stream", task_id, start_index))
        # graftlint: allow[swallowed-exception] best-effort send to a possibly-dead peer; death is handled by heartbeat/reaper, not here
        except Exception:
            pass

    def push_metrics(self, snapshot: list) -> None:
        """One-way metric snapshot to the coordinator (util/metrics.py)."""
        self._send(("metrics", snapshot))

    def collective_notify(self, kind: str, group_name: str, rank: int,
                          epoch: int) -> None:
        """One-way collective-membership note ("collective_join"/"collective_leave"):
        the node service keys death-triggered group aborts on these."""
        self._send((kind, group_name, rank, epoch))

    def state_request(self, fn_name: str, *args, **kwargs):
        """State-API aggregation runs on the coordinator (util/state.py)."""
        return self._request("state", fn_name, args, kwargs)

    def kv_request(self, op: str, *args):
        """Cluster KV access from a worker (reference: GCS KV over the core worker)."""
        return self._request("kv", op, *args)

    def push_spans(self, spans: list) -> None:
        """One-way trace-span batch to the coordinator (util/tracing.py)."""
        self._send(("spans", spans))

    def push_telemetry(self, batch: dict) -> None:
        """One-way telemetry event batch ({clock_offset_ns, events}) to the
        coordinator (util/telemetry.py flush thread)."""
        self._send(("telemetry", batch))

    def push_tqdm(self, state: dict) -> None:
        """One-way progress-bar state to the coordinator (experimental/tqdm_ray.py)."""
        self._send(("tqdm", state))

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True, from_gc: bool = False) -> None:
        self._send(("kill_actor", actor_id, no_restart, from_gc))

    def cancel(self, oid: ObjectID, force: bool = False) -> None:
        self._send(("cancel", oid, force))

    def get_named_actor(self, name: str, namespace: str):
        return self._request("get_named_actor", name, namespace)

    def register_fn(self, fn_id: bytes, fn_bytes: bytes) -> None:
        if fn_id not in self._registered_fns:
            self._send(("register_fn", fn_id, fn_bytes))
            self._registered_fns.add(fn_id)

    def fn_known(self, fn_id: bytes) -> bool:
        return fn_id in self._fn_cache or fn_id in self._registered_fns

    def lookup_placement_group(self, pg_id):
        return self._request("lookup_pg", pg_id)

    def pg_ready_ref(self, pg):
        # Blocks until placed, then returns a trivially-ready ref; callers always
        # ray_tpu.get() the result of pg.ready() so the semantics match.
        self._request("pg_ready_ref", pg.id)
        return self.put(True)

    def create_placement_group(self, bundles, strategy, name):
        return self._request("create_pg", bundles, strategy, name)

    def remove_placement_group(self, pg_id):
        self._send(("remove_pg", pg_id))

    def as_future(self, ref: ObjectRef):
        import concurrent.futures

        fut: concurrent.futures.Future = concurrent.futures.Future()

        def run():
            try:
                fut.set_result(self.get(ref))
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

        threading.Thread(target=run, daemon=True,
                         name="worker-async-get").start()
        return fut

    def runtime_context(self) -> Dict[str, Any]:
        return {
            "node_id": self.node_id_hex,
            "worker_id": self.worker_id_hex,
            "task_id": self.current_task_id.hex() if self.current_task_id else None,
            "actor_id": self.actor_id.hex() if self.actor_id else None,
            "accel": self.accel,
        }

    # -- execution -----------------------------------------------------------------
    def _load_fn(self, spec: TaskSpec):
        fn = self._fn_cache.get(spec.fn_id)
        if fn is None:
            if spec.fn_bytes is None:
                fn_bytes = self._request("fetch_fn", spec.fn_id)
            else:
                fn_bytes = spec.fn_bytes
            fn = cloudpickle.loads(fn_bytes)
            self._fn_cache[spec.fn_id] = fn
        return fn

    def _resolve_args(self, spec: TaskSpec, resolved_locs: List) -> Tuple[list, dict]:
        args, kwargs = cloudpickle.loads(spec.args_meta)
        values = [self._resolve_recovering(o, loc)
                  for o, loc in zip(spec.arg_refs, resolved_locs)]

        def sub(x):
            return values[x.index] if isinstance(x, _RefMarker) else x

        args = [sub(a) for a in args]
        kwargs = {k: sub(v) for k, v in kwargs.items()}
        return args, kwargs

    def execute(self, spec: TaskSpec, resolved_locs: List) -> None:
        # Threaded actors (reference max_concurrency): methods run on a pool so a
        # replica can serve requests concurrently (serve batching/long polls).
        # Named concurrency groups (reference concurrency_group_manager.h) get
        # their own pools so e.g. parked long-poll listeners can never exhaust
        # the default pool and starve control RPCs.
        if spec.kind == "actor_method":
            if (spec.method_name in self._async_methods
                    and spec.num_returns != -1):
                # async actor method: schedule on the per-actor event loop so
                # any number of in-flight calls interleave at awaits
                # (reference actor.py:2352); streaming calls keep the thread
                # path (sync-generator protocol)
                import asyncio

                asyncio.run_coroutine_threadsafe(
                    self._execute_async(spec, resolved_locs), self._ensure_actor_loop())
                return
            group = spec.concurrency_group or self._method_groups.get(
                spec.method_name or "", "")
            if group:
                pool = self._group_pools.get(group)
                if pool is None:
                    # never fall back silently: a typo'd group would land parked
                    # calls on the bounded default pool and reintroduce the
                    # starvation the groups exist to prevent
                    self._send_error(spec, ValueError(
                        f"concurrency group {group!r} was not declared in this "
                        f"actor's concurrency_groups "
                        f"(declared: {sorted(self._group_pools)})"))
                    return
                pool.submit(self._execute_inner, spec, resolved_locs)
                return
            if self._method_pool is not None:
                self._method_pool.submit(self._execute_inner, spec, resolved_locs)
                return
        self._execute_inner(spec, resolved_locs)

    def _execute_inner(self, spec: TaskSpec, resolved_locs: List) -> None:
        """One task or actor method on the calling thread, as a `worker.task` span named
        after it (ring, and the profile's host plane) and in the always-on counters."""
        t0 = time.perf_counter_ns()
        try:
            with telemetry.span("worker.task", "worker",
                                task=spec.method_name or spec.name, kind=spec.kind):
                self._execute_traced(spec, resolved_locs)
        finally:
            _count_task(time.perf_counter_ns() - t0)

    def _execute_traced(self, spec: TaskSpec, resolved_locs: List) -> None:
        self.current_task_id = spec.task_id
        ctx_token = None
        try:
            from ray_tpu.runtime_env import applied as _renv_applied

            import contextlib

            if spec.trace_ctx is not None:
                from ray_tpu.util import tracing

                # a propagated context IS the enable signal for THIS task:
                # is_tracing_enabled honors an active context, so no global
                # flag needs flipping (one traced request must not turn a
                # long-lived worker's tracing on forever)
                ctx_token = tracing.set_trace_context(spec.trace_ctx)
                span_cm = tracing.span(f"task::{spec.name}", {"kind": spec.kind})
            else:
                span_cm = contextlib.nullcontext()
            with span_cm:
                args, kwargs = self._resolve_args(spec, resolved_locs)
                if spec.kind == "task" and spec.runtime_env:
                    with _renv_applied(spec.runtime_env):
                        return self._execute_body(spec, args, kwargs)
                if spec.kind == "actor_creation" and spec.runtime_env:
                    # actors keep their runtime env for their lifetime
                    with _renv_applied(spec.runtime_env, permanent=True):
                        pass
                return self._execute_body(spec, args, kwargs)
        except BaseException as e:  # noqa: BLE001
            self._send_error(spec, e)
        finally:
            if ctx_token is not None:
                # reset the POOLED dispatch/method thread: a leaked context
                # would stitch the next (untraced) request on this thread
                # into this trace and mis-tag its telemetry
                from ray_tpu.util import tracing

                tracing._ctx.reset(ctx_token)
            self.current_task_id = None

    def _send_error(self, spec: TaskSpec, e: BaseException) -> None:
        """Report a task failure (body, arg resolution, or runtime-env application)."""
        tb = traceback.format_exc()
        err = TaskError(e, task_desc=spec.name, tb_str=tb)
        try:
            payload = [
                (oid, object_store.materialize(err, oid, is_error=True))
                for oid in spec.return_ids
            ]
        # graftlint: allow[swallowed-exception] the error object itself failed to pickle: re-report as a plain TaskError with the traceback text
        except Exception:
            # the exception object itself failed to serialize; report a plain failure
            err2 = TaskError(RuntimeError(f"unserializable error: {tb}"), spec.name)
            payload = [
                (oid, object_store.materialize(err2, oid, is_error=True))
                for oid in spec.return_ids
            ]
        self._send(("result", spec.task_id, payload, (spec.name, tb, type(e).__name__)))

    def _execute_body(self, spec: TaskSpec, args, kwargs) -> None:
        try:
            if spec.num_returns == -1 and spec.kind in ("task", "actor_method"):
                # streaming generator task (reference _raylet.pyx:1138): each
                # yielded item becomes its own object under a derived id; the
                # ordinary return carries the final item count
                self._execute_streaming(spec, args, kwargs)
                return
            if spec.kind == "actor_creation":
                cls = self._load_fn(spec)
                self.actor_instance = cls(*args, **kwargs)
                self.actor_id = spec.actor_id
                mc = spec.max_concurrency
                if mc > 1 or spec.concurrency_groups:
                    from concurrent.futures import ThreadPoolExecutor

                    self._method_pool = ThreadPoolExecutor(
                        max_workers=mc, thread_name_prefix="actor-method"
                    )
                for gname, size in (spec.concurrency_groups or {}).items():
                    if size and size > 0:
                        from concurrent.futures import ThreadPoolExecutor

                        self._group_pools[gname] = ThreadPoolExecutor(
                            max_workers=size, thread_name_prefix=f"cg-{gname}")
                    else:
                        self._group_pools[gname] = _ThreadPerCallExecutor(gname)
                self._method_groups = {
                    name: m.get("concurrency_group", "")
                    for name, m in (spec.method_meta or {}).items()
                    if m.get("concurrency_group")
                }
                self._async_methods = {
                    name for name, m in (spec.method_meta or {}).items()
                    if m.get("is_async")
                }
                results = [None]
            elif spec.kind == "actor_method":
                if spec.method_name == "__ray_call__":
                    # Escape hatch (reference ActorHandle.__ray_call__): run an arbitrary
                    # function against the actor instance. Used by dag/ exec loops.
                    fn = args[0]
                    out = fn(self.actor_instance, *args[1:], **kwargs)
                else:
                    method = getattr(self.actor_instance, spec.method_name)
                    out = method(*args, **kwargs)
                results = self._split_returns(out, spec.num_returns)
            else:
                fn = self._load_fn(spec)
                out = fn(*args, **kwargs)
                results = self._split_returns(out, spec.num_returns)
            payload = []
            for oid, value in zip(spec.return_ids, results):
                payload.append((oid, object_store.materialize(value, oid)))
            self._send(("result", spec.task_id, payload, None))
        except BaseException as e:  # noqa: BLE001
            self._send_error(spec, e)
        finally:
            self.current_task_id = None

    def _ensure_actor_loop(self):
        """The actor's asyncio loop, running on its own daemon thread. ONE loop
        per actor: dispatch and method-pool threads may race to create it, and
        asyncio primitives bind to the loop they were created on."""
        with self._loop_lock:
            if self._actor_loop is None:
                import asyncio

                loop = asyncio.new_event_loop()
                threading.Thread(target=loop.run_forever, daemon=True,
                                 name="actor-asyncio").start()
                self._actor_loop = loop
            return self._actor_loop

    async def _execute_async(self, spec: TaskSpec, resolved_locs: List) -> None:
        """Async actor method body: resolve args, await the coroutine, report.
        Runs ON the actor loop; blocking work inside belongs in executors."""
        import contextlib

        _ASYNC_TASK_ID.set(spec.task_id)  # task-scoped (per-asyncio.Task context)
        t0_wall, t0 = time.time_ns(), time.perf_counter_ns()
        try:
            if spec.trace_ctx is not None:
                from ray_tpu.util import tracing

                # per-asyncio.Task context: no reset needed, and the active
                # context is itself the enable signal (see _execute_inner)
                tracing.set_trace_context(spec.trace_ctx)
                span_cm = tracing.span(f"task::{spec.name}", {"kind": spec.kind})
            else:
                span_cm = contextlib.nullcontext()
            with span_cm:
                args, kwargs = self._resolve_args(spec, resolved_locs)
                method = getattr(self.actor_instance, spec.method_name)
                out = await method(*args, **kwargs)
                results = self._split_returns(out, spec.num_returns)
                payload = [(oid, object_store.materialize(value, oid))
                           for oid, value in zip(spec.return_ids, results)]
                self._send(("result", spec.task_id, payload, None))
        except BaseException as e:  # noqa: BLE001
            self._send_error(spec, e)
        finally:
            # calls interleave at their awaits on the loop's one thread: no annotation
            # could hold one, so the ring alone gets it, entry to result
            dur = time.perf_counter_ns() - t0
            _count_task(dur)
            telemetry.complete("worker.task", "worker", t0_wall, dur,
                               task=spec.method_name or spec.name, kind=spec.kind)

    def _execute_streaming(self, spec: TaskSpec, args, kwargs) -> None:
        from .object_ref import stream_item_id

        # a retried / lineage-reconstructed execution reuses the task id: a
        # stale cancel from the previous attempt must not kill it at item 0
        self._cancelled_streams.discard(spec.task_id)
        if spec.kind == "actor_method":
            if spec.method_name == "__ray_call__":
                out = args[0](self.actor_instance, *args[1:], **kwargs)
            else:
                out = getattr(self.actor_instance, spec.method_name)(*args, **kwargs)
        else:
            out = self._load_fn(spec)(*args, **kwargs)
        count = 0
        import inspect as _inspect

        if _inspect.iscoroutine(out):
            # plain async def under a streaming call: await it, then stream the
            # result as one item (mirrors the sync non-iterator case below)
            import asyncio

            out = iter((asyncio.run_coroutine_threadsafe(
                out, self._ensure_actor_loop()).result(),))
        if hasattr(out, "__anext__"):
            # async generator (async def + yield): drive it on the actor loop,
            # itemizing from this thread
            import asyncio

            loop = self._ensure_actor_loop()

            def drain(agen):
                try:
                    while True:
                        try:
                            yield asyncio.run_coroutine_threadsafe(
                                agen.__anext__(), loop).result()
                        except StopAsyncIteration:
                            return
                finally:
                    # close() on this wrapper (stream cancellation) must reach
                    # the async generator's finally blocks too
                    try:
                        asyncio.run_coroutine_threadsafe(
                            agen.aclose(), loop).result(timeout=10)
                    # graftlint: allow[swallowed-exception] async-generator close during cancellation: the loop may already be gone
                    except Exception:
                        pass

            out = drain(out)
        elif not hasattr(out, "__next__"):
            # non-iterator return under a streaming call: a one-item stream
            # (lists/dicts must not be exploded into their elements)
            out = iter((out,))
        try:
            while spec.task_id not in self._cancelled_streams:
                try:
                    item = next(out)
                except StopIteration:
                    break
                oid = stream_item_id(spec.task_id, count)
                loc = object_store.materialize(item, oid)
                self._send(("stream", spec.task_id, count, oid, loc))
                count += 1
        finally:
            # cancelled (or errored) mid-stream: GeneratorExit into the user
            # generator so its finally blocks run (e.g. engine request abort)
            close = getattr(out, "close", None)
            if close is not None:
                try:
                    close()
                # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
                except Exception:
                    pass
            self._cancelled_streams.discard(spec.task_id)
        payload = [(spec.return_ids[0],
                    object_store.materialize(count, spec.return_ids[0]))]
        self._send(("result", spec.task_id, payload, None))

    @staticmethod
    def _split_returns(out, num_returns: int):
        if num_returns == 1:
            return [out]
        out_t = tuple(out)
        if len(out_t) != num_returns:
            raise ValueError(f"expected {num_returns} return values, got {len(out_t)}")
        return list(out_t)

    # -- main loop -------------------------------------------------------------------
    def main_loop(self) -> None:
        self._ensure_recv_thread()
        self._send(("ready", self.worker_id_hex))
        ready = time.time_ns()
        if _entered_wall_ns:
            _BOOT["worker.boot.ready"] = (_entered_wall_ns, ready - _entered_wall_ns)
        while not self._exit:
            msg = self._task_queue.get()
            if "worker.boot.first_task" not in _BOOT:
                _BOOT["worker.boot.first_task"] = (ready, time.time_ns() - ready)
            kind = msg[0]
            if kind == "task":
                _, spec, resolved_locs = msg
                self.execute(spec, resolved_locs)
            elif kind == "exit":
                break


def worker_main(conn, node_id_hex: str, worker_id_hex: str, accel: str, env: Dict[str, str]):
    """Entry point of a spawned worker process."""
    global _entered_wall_ns
    _entered_wall_ns = time.time_ns()
    created = _process_created_wall_ns()
    if created is not None:  # interpreter start and `spawn`'s re-import of the driver's __main__
        _BOOT["worker.boot.spawn"] = (created, _entered_wall_ns - created)
    for k, v in env.items():
        os.environ[k] = v
    log_dir = os.environ.get("RAY_TPU_WORKER_LOG_DIR")
    if log_dir:
        # agent-hosted worker: stdout/stderr go to per-worker files the agent
        # tails back to the head (reference: worker log redirection +
        # log_monitor.py:105 re-printing on the driver). Local workers keep the
        # driver's console (no env set).
        try:
            os.makedirs(log_dir, exist_ok=True)
            for stream, fd in (("out", 1), ("err", 2)):
                f = open(os.path.join(log_dir, f"worker-{worker_id_hex}.{stream}"),
                         "ab", buffering=0)
                os.dup2(f.fileno(), fd)
            sys.stdout = os.fdopen(1, "w", buffering=1, closefd=False)
            sys.stderr = os.fdopen(2, "w", buffering=1, closefd=False)
        # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
        except Exception:
            pass
    if accel == "cpu":
        # a CPU worker must never open the chip: it belongs to one process
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "jax" in sys.modules:
            # spawn re-imports the driver's __main__ before this runs; a jax
            # imported there read its environment then
            sys.modules["jax"].config.update("jax_platforms", "cpu")
    else:
        from .accelerators import ensure_compile_cache_dir, jax_platforms_exclude_tpu

        if not jax_platforms_exclude_tpu():
            ensure_compile_cache_dir()
    ctx = WorkerContext(conn, node_id_hex, worker_id_hex, accel)
    global_state.set_worker(ctx)
    count_collections()
    try:
        ctx.main_loop()
    except KeyboardInterrupt:
        pass
    finally:
        try:
            conn.close()
        # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
        except Exception:
            pass
        sys.exit(0)


def _sample_collapsed_stacks(duration_s: float, hz: float) -> dict:
    """Wall-clock stack sampler: every 1/hz, snapshot sys._current_frames()
    and bump a counter per collapsed stack "thread;func (file:line);..."
    (root-first — flamegraph.pl / speedscope collapsed format). The
    dependency-free analogue of `py-spy record` (reference: dashboard
    reporter module's profiling endpoints)."""
    interval = 1.0 / max(1.0, float(hz))
    deadline = time.monotonic() + float(duration_s)
    me = threading.get_ident()
    counts: dict = {}
    while time.monotonic() < deadline:
        names = {t.ident: t.name for t in threading.enumerate()}
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue  # the sampler observing itself is pure noise
            parts = []
            f = frame
            while f is not None:
                code = f.f_code
                parts.append(f"{code.co_name} ({code.co_filename.rsplit('/', 1)[-1]}"
                             f":{f.f_lineno})")
                f = f.f_back
            key = names.get(ident, "?") + ";" + ";".join(reversed(parts))
            counts[key] = counts.get(key, 0) + 1
        time.sleep(interval)
    return counts


def _format_thread_stacks() -> str:
    """All thread stacks of this process (reference: py-spy dump via the
    dashboard reporter; this is the dependency-free in-process equivalent)."""
    import traceback

    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for ident, frame in frames.items():
        out.append(f"--- thread {names.get(ident, '?')} ({ident}) ---")
        out.extend(l.rstrip() for l in traceback.format_stack(frame))
    return "\n".join(out)


def _main_connect() -> None:
    """Socket-connect worker entry (containerized workers: the in-image process
    cannot inherit the node's mp pipe, so it dials back over an authkey'd
    loopback socket and speaks the identical worker protocol)."""
    import argparse

    from multiprocessing.connection import Client

    p = argparse.ArgumentParser()
    p.add_argument("--connect", required=True)
    p.add_argument("--node-id", required=True)
    p.add_argument("--worker-id", required=True)
    p.add_argument("--accel", default="cpu")
    args = p.parse_args()
    host, _, port = args.connect.rpartition(":")
    key = bytes.fromhex(os.environ["RAY_TPU_WORKER_AUTHKEY"])
    conn = Client((host or "127.0.0.1", int(port)), authkey=key)
    worker_main(conn, args.node_id, args.worker_id, args.accel, {})


if __name__ == "__main__":
    _main_connect()
