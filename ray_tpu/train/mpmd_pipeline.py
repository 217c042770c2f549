"""MPMD cross-process pipeline parallelism: per-stage compiled programs,
1F1B microbatch streaming over the zero-copy data plane.

"Scaling Deep Learning Training with MPMD Pipeline Parallelism" (arXiv
2412.14374): instead of GSPMD-tracing one giant program over a `pp` mesh axis
(`parallel/pipeline.py`), each pipeline stage is a *separate process* that
compiles its OWN three programs — forward, backward, optimizer-update — and
activations / activation-gradients stream stage-to-stage as fixed-shape
microbatch blocks over the collective data plane (PR 4's striped
`pull_into` transport; `resolve_stage_transport` in dag/accelerator_context
picks the device plane when both endpoints have it). Nothing ever moves
through the head: block keys are deterministic functions of
(step, microbatch, direction), so the blocking store read IS the
synchronization and zero control-plane round-trips ride the hot path.

Three layers, separable on purpose:

1. **Schedule core** — pure functions (`build_schedule`, `warmup_len`,
   `validate_schedule`, `bubble_fraction`): the 1F1B event order per stage
   and the timeline analysis, unit-testable with no processes involved.
2. **StageComm / StageRunner** — one process's slice of the pipeline: rides
   an existing collective group (PR 3), so stage death poisons the run and
   every blocked pull observes a typed `CollectiveAbortError` within one
   abort-poll interval instead of hanging. Runs equally inside a Train
   worker session (rank == stage; see `stage_runner_from_train_context`)
   or a standalone actor.
3. **MPMDPipeline** — driver facade: spawns one actor per stage, wires the
   group, streams steps. `parallel/mpmd.py` re-exports it.

Within-stage data parallelism reuses PR 10's bucketed grad sync: a stage
with >1 local device shards its microbatch over a local "dp" mesh and folds
`grad_sync._sync_bucketed` into its update program.

Gradient accumulation folds per-microbatch grads in REVERSE microbatch
order from a zero init — the exact float-addition chain `lax.scan`'s
transpose produces in the in-program pipeline — which is what makes the
cross-process runner bit-exact (f32) against `pipeline_spmd` (see
tests/test_mpmd_pipeline.py).
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait as futures_wait
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.util import telemetry
from ray_tpu.util.hot_path import hot_path

Event = Tuple[str, int]  # ("fwd" | "bwd", microbatch index)

PIPELINE_SPAN = "train.pipeline_stage"
BUBBLE_GAUGE = "train_pipeline_bubble_fraction"


# ---------------------------------------------------------------- schedule core
def warmup_len(stage: int, pp: int, num_microbatches: int) -> int:
    """Forward passes stage `stage` runs before its first backward (1F1B):
    the pipeline-fill depth below it, capped by the microbatch count."""
    return min(pp - 1 - stage, num_microbatches)


def build_1f1b_schedule(stage: int, pp: int, num_microbatches: int) -> List[Event]:
    """One stage's 1F1B event order: warmup fills, steady state alternates
    one-forward-one-backward, cooldown drains the in-flight microbatches."""
    m = num_microbatches
    w = warmup_len(stage, pp, m)
    events: List[Event] = [("fwd", i) for i in range(w)]
    for k in range(m - w):  # steady state: fwd(w+k) then bwd(k)
        events.append(("fwd", w + k))
        events.append(("bwd", k))
    events.extend(("bwd", i) for i in range(m - w, m))  # cooldown
    return events


def build_gpipe_schedule(stage: int, pp: int, num_microbatches: int) -> List[Event]:
    """All forwards, then all backwards — the unoverlapped baseline whose
    measured bubble the 1F1B row is gated against in bench.py --pipeline."""
    m = num_microbatches
    return [("fwd", i) for i in range(m)] + [("bwd", i) for i in range(m)]


def build_schedule(pp: int, num_microbatches: int,
                   schedule: str = "1f1b") -> List[List[Event]]:
    """Per-stage event lists for the whole pipeline. Raises on an invalid
    schedule name or a non-positive shape."""
    if pp < 1 or num_microbatches < 1:
        raise ValueError(f"need pp >= 1 and microbatches >= 1, got {pp}/{num_microbatches}")
    builder = {"1f1b": build_1f1b_schedule, "gpipe": build_gpipe_schedule}.get(schedule)
    if builder is None:
        raise ValueError(f"unknown pipeline schedule {schedule!r} (1f1b|gpipe)")
    out = [builder(s, pp, num_microbatches) for s in range(pp)]
    validate_schedule(out, pp, num_microbatches)
    return out


def validate_schedule(schedules: List[List[Event]], pp: int, m: int) -> None:
    """Prove the per-stage event lists deadlock-free by simulation.

    Dependencies: fwd(s, i) needs fwd(s-1, i); bwd(s, i) needs fwd(s, i) and
    bwd(s+1, i) (the last stage seeds its own cotangent). Greedy round-robin
    execution must retire every event — a cyclic wait or a missing/duplicate
    event fails loudly here rather than hanging live processes."""
    for s, evs in enumerate(schedules):
        fwds = [i for k, i in evs if k == "fwd"]
        bwds = [i for k, i in evs if k == "bwd"]
        if sorted(fwds) != list(range(m)) or sorted(bwds) != list(range(m)):
            raise ValueError(f"stage {s}: schedule must touch each microbatch "
                             f"exactly once per direction, got {evs}")
    done: set = set()
    cursor = [0] * pp
    progressed = True
    while progressed:
        progressed = False
        for s in range(pp):
            while cursor[s] < len(schedules[s]):
                kind, i = schedules[s][cursor[s]]
                if kind == "fwd":
                    ready = s == 0 or ("fwd", s - 1, i) in done
                else:
                    ready = ("fwd", s, i) in done and (
                        s == pp - 1 or ("bwd", s + 1, i) in done)
                if not ready:
                    break
                done.add((kind, s, i))
                cursor[s] += 1
                progressed = True
    stuck = [s for s in range(pp) if cursor[s] < len(schedules[s])]
    if stuck:
        raise ValueError(f"schedule deadlocks at stages {stuck}: "
                         f"{[schedules[s][cursor[s]] for s in stuck]}")


def bubble_fraction(events: List[Dict[str, Any]],
                    span_name: str = PIPELINE_SPAN) -> Dict[str, float]:
    """Per-stage bubble fraction from a (merged) telemetry timeline.

    For each stage, take its `span_name` spans (chrome-trace "X" events with a
    `stage` arg; ts/dur in microseconds), and compute the idle fraction of its
    own busy window [first span start, last span end]: 1 - busy/window.
    Overlapping spans are unioned so nested instrumentation can't push the
    fraction negative. Returns {"stage<i>": frac, ..., "mean": frac}; empty
    dict when no pipeline spans are present."""
    by_stage: Dict[int, List[Tuple[float, float]]] = {}
    for ev in events:
        if ev.get("name") != span_name or ev.get("ph", "X") != "X":
            continue
        args = ev.get("args", {})
        stage = args.get("stage")
        if stage is None:
            continue
        t0 = float(ev.get("ts", 0.0))
        by_stage.setdefault(int(stage), []).append((t0, t0 + float(ev.get("dur", 0.0))))
    out: Dict[str, float] = {}
    fracs = []
    for stage, spans in sorted(by_stage.items()):
        spans.sort()
        window = spans[-1][1] - spans[0][0] if spans else 0.0
        busy = 0.0
        cur_start, cur_end = spans[0]
        for s, e in spans[1:]:
            if s > cur_end:
                busy += cur_end - cur_start
                cur_start, cur_end = s, e
            else:
                cur_end = max(cur_end, e)
        busy += cur_end - cur_start
        frac = max(0.0, 1.0 - busy / window) if window > 0 else 0.0
        out[f"stage{stage}"] = frac
        fracs.append(frac)
    if fracs:
        out["mean"] = sum(fracs) / len(fracs)
    return out


def publish_bubble_gauge(fractions: Dict[str, float]) -> None:
    """Surface measured bubble fractions as the `train_pipeline_bubble_fraction`
    gauge (per stage + mean) — the `cluster_status()["train"]` / `ray-tpu
    status` hook."""
    g = telemetry.get_gauge(
        BUBBLE_GAUGE, "pipeline idle fraction per stage from the merged "
        "telemetry timeline (1 - busy/window over train.pipeline_stage spans)",
        tag_keys=("stage",))
    for stage, frac in fractions.items():
        g.set(float(frac), tags={"stage": stage})


# ---------------------------------------------------------------- configuration
@dataclass(frozen=True)
class MPMDPipelineConfig:
    """Shape of one MPMD pipeline run. Defaults come from the RAY_TPU_PIPELINE_*
    knobs (ray_tpu/knobs.py) via `from_env`."""

    num_microbatches: int = 4
    schedule: str = "1f1b"          # "1f1b" | "gpipe"
    prefetch: int = 2               # pull-ahead depth; 0 = unoverlapped transfers
    transfer_streams: int = 1       # concurrent stripes per block pull
    transport: str = "auto"         # "auto" | "host" | "device"
    group_name: str = "mpmd_pipeline"
    stage_dp: int = 1               # local data-parallel devices per stage
    learning_rate: float = 1e-2     # default SGD update when no update_fn given

    def __post_init__(self):
        if self.schedule not in ("1f1b", "gpipe"):
            raise ValueError(f"unknown schedule {self.schedule!r}")
        if self.transport not in ("auto", "host", "device"):
            raise ValueError(f"unknown transport {self.transport!r}")
        if self.num_microbatches < 1 or self.prefetch < 0 or self.transfer_streams < 1:
            raise ValueError("num_microbatches >= 1, prefetch >= 0, transfer_streams >= 1")

    @classmethod
    def from_env(cls, **overrides) -> "MPMDPipelineConfig":
        from ray_tpu.config import CONFIG

        base = dict(
            num_microbatches=int(CONFIG.pipeline_microbatches),
            schedule=str(CONFIG.pipeline_schedule),
            prefetch=int(CONFIG.pipeline_prefetch),
            transfer_streams=int(CONFIG.pipeline_streams),
            transport=str(CONFIG.pipeline_transport),
        )
        base.update(overrides)
        return cls(**base)


# ---------------------------------------------------------------- stage transport
class StageComm:
    """One stage's block transport: publish/pull fixed-shape microbatch blocks
    on the collective group's striped data plane, with abort-aware waits.

    Keys are deterministic — `mpmd:<dir>:<step>:<mb>` — so consumers need no
    per-block control round-trip: the peer's blocking store read is the
    synchronization, and a bounded-probe `pull_into` (one abort-poll interval
    per probe) keeps every wait interruptible by the PR 3 poison flag. Blocks
    publish with expected_read_bytes=nbytes: exactly one consumer reads each
    block once, after which the store auto-retracts it — a clean step leaves
    zero published buffers behind (the chaos test's leak check).

    transport="device" rides `core/device_plane` export/fetch with the handle
    handed off on the coordinator board (metadata only); "host" is the striped
    byte path; "auto" resolves per `dag.accelerator_context.resolve_stage_transport`.
    """

    def __init__(self, st, stage: int, pp: int, cfg: MPMDPipelineConfig):
        from ray_tpu.util.collective import ring

        self.st = st
        self.stage = stage
        self.pp = pp
        self.cfg = cfg
        self.plane = ring._ensure_plane(st)
        self._abort = ring._AbortCheck(st)
        self._published: set = set()
        self._inflight_pulls = 0
        self._lock = threading.Lock()
        self._pool: Optional[ThreadPoolExecutor] = None
        self._futures: Dict[Tuple[str, int, int], Any] = {}
        from ray_tpu.dag.accelerator_context import resolve_stage_transport

        self.transport = resolve_stage_transport(cfg.transport)
        # Rendezvous: every stage board-exchanges its plane address once per
        # epoch; pulls then dial peers directly (never the head).
        self.addrs = self._exchange_addrs()

    def _exchange_addrs(self) -> List[Tuple[str, int]]:
        from ray_tpu.util.collective import ring

        entries = ring._exchange(
            self.st, f"mpmd_addr:{self.st.epoch}:{self.cfg.schedule}",
            tuple(self.plane.addr))
        return [tuple(e) for e in entries]

    # -- key scheme --------------------------------------------------------------------
    @staticmethod
    def _key(direction: str, step: int, mb: int) -> str:
        return f"mpmd:{direction}:{step}:{mb}"

    # -- publish -----------------------------------------------------------------------
    def publish(self, direction: str, step: int, mb: int, arr: np.ndarray) -> None:
        key = self._key(direction, step, mb)
        if self.transport == "device":
            if self._publish_device(key, arr):
                return
        data = np.ascontiguousarray(arr)
        self.plane.publish(key, data.tobytes(), expected_read_bytes=data.nbytes)
        with self._lock:
            self._published.add(key)

    def _publish_device(self, key: str, arr) -> bool:
        """Device-plane path: export the block, hand the handle off on the
        coordinator board (metadata only). Falls back to the host path when
        the plane rejects the export."""
        from ray_tpu.core import device_plane

        dp = device_plane.plane()
        if not dp.available:
            return False
        try:
            handle = dp.export(arr)
        except device_plane.DevicePlaneError:
            return False
        self.st.coordinator.contribute.remote(
            f"{key}:h", self.st.rank, handle, self.st.epoch)
        return True

    # -- pull --------------------------------------------------------------------------
    def prefetch(self, direction: str, step: int, mb: int, src_stage: int,
                 shape: Tuple[int, ...], dtype) -> None:
        """Initiate an overlapped pull for a block the schedule needs soon."""
        if self.cfg.prefetch <= 0:
            return
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=max(2, self.cfg.prefetch * self.cfg.transfer_streams),
                thread_name_prefix=f"mpmd-s{self.stage}")
        slot = (direction, step, mb)
        if slot not in self._futures:
            self._futures[slot] = self._pool.submit(
                self._pull_block, direction, step, mb, src_stage, shape, dtype)

    def take(self, direction: str, step: int, mb: int, src_stage: int,
             shape: Tuple[int, ...], dtype) -> np.ndarray:
        """The block for (direction, step, mb) — from a prefetched future when
        one is in flight, else pulled inline."""
        fut = self._futures.pop((direction, step, mb), None)
        if fut is not None:
            return fut.result()
        return self._pull_block(direction, step, mb, src_stage, shape, dtype)

    def _pull_block(self, direction: str, step: int, mb: int, src_stage: int,
                    shape: Tuple[int, ...], dtype) -> np.ndarray:
        with self._lock:
            self._inflight_pulls += 1
        try:
            if self.transport == "device":
                out = self._fetch_device(direction, step, mb, src_stage)
                if out is not None:
                    return out
            return self._pull_host(direction, step, mb, src_stage, shape, dtype)
        finally:
            with self._lock:
                self._inflight_pulls -= 1

    def _fetch_device(self, direction: str, step: int, mb: int,
                      src_stage: int) -> Optional[np.ndarray]:
        from ray_tpu.core import device_plane
        from ray_tpu.util.collective.coordinator import wait_poll_one

        dp = device_plane.plane()
        if not dp.available:
            return None
        key = f"{self._key(direction, step, mb)}:h"
        handle = wait_poll_one(self.st, key, src_stage, timeout_s=self._op_timeout())
        return np.asarray(dp.fetch(handle, release=True))

    def _op_timeout(self) -> float:
        from ray_tpu.config import CONFIG

        return CONFIG.collective_op_timeout_s

    def _pull_host(self, direction: str, step: int, mb: int, src_stage: int,
                   shape: Tuple[int, ...], dtype) -> np.ndarray:
        """Striped bounded-probe pull: probe stripe 0 until the block lands
        (checking the poison flag on every miss), then fan the remaining
        stripes out over `transfer_streams` concurrent ranged pulls."""
        addr = self.addrs[src_stage]
        key = self._key(direction, step, mb)
        out = np.empty(shape, dtype)
        mv = memoryview(out).cast("B")
        total = out.nbytes
        probe_s = self._abort.interval
        deadline = time.monotonic() + self._op_timeout()
        streams = min(self.cfg.transfer_streams, max(1, total // (64 << 10)) or 1)
        stripe = -(-total // streams)
        first = min(stripe, total)
        while True:  # stripe 0 carries the wait-for-publication probe loop
            try:
                n = self.plane.pull_into(addr, key, 0, first, mv[:first],
                                         timeout=probe_s)
            except (OSError, ConnectionError):
                # peer unreachable (killed or mid-restart): the abort probe
                # below turns this into the typed CollectiveAbortError as soon
                # as the coordinator's poison flag lands (one poll interval)
                n = None
                time.sleep(probe_s)
            if n is not None:
                break
            self._abort.check(force=True)
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"stage {self.stage}: block {key} from stage {src_stage} "
                    f"not published within {self._op_timeout()}s")
        try:
            if streams > 1 and total > first:
                def pull_stripe(k: int) -> None:
                    off = k * stripe
                    ln = min(stripe, total - off)
                    self.plane.pull_into(addr, key, off, ln, mv[off:off + ln])

                with ThreadPoolExecutor(max_workers=streams - 1,
                                        thread_name_prefix="mpmd-stripe") as ex:
                    list(ex.map(pull_stripe, range(1, streams)))
            elif total > first:
                self.plane.pull_into(addr, key, first, total - first, mv[first:])
        except (OSError, ConnectionError):
            # producer died between stripe 0 and the fan-out: prefer the typed
            # abort when the group is poisoned, else surface the IO error
            self._abort.check(force=True)
            raise
        return out

    # -- accounting / teardown ---------------------------------------------------------
    def admission_counters(self) -> Dict[str, int]:
        """In-flight accounting for the leak gate: published-but-unconsumed
        mpmd blocks in this stage's store, plus pulls currently in flight.
        Both must read zero after a completed step AND after abort cleanup."""
        with self._lock:
            inflight = self._inflight_pulls
        with self.plane.store._cond:
            published = sum(1 for k in self.plane.store._bufs if k.startswith("mpmd:"))
        return {"published": published, "inflight_pulls": inflight}

    def abort_cleanup(self) -> None:
        """Retract every mpmd block this stage still serves and drop pending
        prefetch futures: survivors of a poisoned run must not pin activation
        buffers until the TTL sweep."""
        with self.plane.store._cond:
            stale = [k for k in self.plane.store._bufs if k.startswith("mpmd:")]
        for k in stale:
            self.plane.retract(k)
        # A prefetch already pulling cannot be cancelled: it meets the same
        # verdict at its next probe, one abort-poll interval away (or its own
        # deadline, after a timeout). Counted as in flight until then.
        pulling = [f for f in self._futures.values() if not f.cancel()]
        self._futures.clear()
        futures_wait(pulling, timeout=self._op_timeout())
        with self._lock:
            self._published.clear()

    def close(self) -> None:
        self.abort_cleanup()
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None


# ---------------------------------------------------------------- stage runner
def _as_spec(spec) -> Tuple[Tuple[int, ...], Any]:
    """Normalize a jax.ShapeDtypeStruct / (shape, dtype) pair to (shape, dtype)."""
    if hasattr(spec, "shape") and hasattr(spec, "dtype"):
        return tuple(spec.shape), spec.dtype
    shape, dtype = spec
    return tuple(shape), np.dtype(dtype)


class StageRunner:
    """One pipeline stage's execution engine: compiles this stage's OWN three
    programs (forward, backward, update) and walks its 1F1B/GPipe event list,
    publishing/pulling fixed-shape microbatch blocks through `StageComm`.

    `stage_fn(params, x) -> y` must be batch-parallel along axis 0 of `x`
    (each sample independent) — required for stage_dp > 1 sharding and for
    microbatch semantics in general. `loss_fn(y) -> scalar` (last stage only)
    must be a mean over the microbatch. The update defaults to plain SGD at
    `cfg.learning_rate`; pass `update_fn(params, grads) -> params` to replace
    it.

    Bit-exactness contract (vs `parallel/pipeline.py`'s `pipeline_spmd`, f32):
    per-microbatch gradients are buffered and folded in REVERSE microbatch
    order from a zeros init — the float-addition chain `lax.scan`'s transpose
    emits — and the last stage seeds each microbatch cotangent with the exact
    scalar 1/num_microbatches (exact in f32 for power-of-two counts).
    """

    def __init__(self, st, stage: int, pp: int, stage_fn: Callable,
                 params: Any, cfg: MPMDPipelineConfig, *,
                 loss_fn: Optional[Callable] = None,
                 update_fn: Optional[Callable] = None,
                 in_spec=None, out_spec=None):
        import jax

        self.st = st
        self.stage = stage
        self.pp = pp
        self.cfg = cfg
        self.stage_fn = stage_fn
        self.loss_fn = loss_fn
        self.update_fn = update_fn
        self.is_first = stage == 0
        self.is_last = stage == pp - 1
        if self.is_last and loss_fn is None:
            raise ValueError("last stage needs loss_fn")
        self.in_shape, self.in_dtype = _as_spec(in_spec)
        self.out_shape, self.out_dtype = _as_spec(out_spec)
        self.params = jax.device_put(params)
        self.events = build_schedule(pp, cfg.num_microbatches, cfg.schedule)[stage]
        self.comm = StageComm(st, stage, pp, cfg)
        self.last_grads: Any = None      # folded grads of the latest step (parity hook)
        self.last_losses: List[Any] = []  # per-microbatch losses (last stage)
        self.timeline: List[Dict[str, Any]] = []  # local chrome-trace span records
        self._dp_mesh = None
        if cfg.stage_dp > 1:
            self._dp_mesh = self._build_dp_mesh(cfg.stage_dp)
        self._programs_ready = False

    # -- program compilation ---------------------------------------------------------
    @staticmethod
    def _build_dp_mesh(dp: int):
        import jax
        from jax.sharding import Mesh

        devs = jax.local_devices()
        if len(devs) < dp:
            raise ValueError(f"stage_dp={dp} but only {len(devs)} local devices")
        return Mesh(np.array(devs[:dp]), ("dp",))

    def _build_programs(self) -> None:
        import jax
        import jax.numpy as jnp

        stage_fn, loss_fn = self.stage_fn, self.loss_fn
        m = self.cfg.num_microbatches
        # exact in f32 for power-of-two m: the same cotangent jnp.mean's
        # transpose distributes to each microbatch loss in the reference
        self._ct = jnp.float32(1.0 / m)
        self._stash = self._dp_mesh is None
        if self._dp_mesh is None:
            # Residual stashing: forward returns its vjp pullback (a
            # jax.tree_util.Partial — a pytree, so it crosses the jit
            # boundary with the residual arrays as leaves) and backward
            # applies it. One forward per microbatch total, where a
            # vjp-inside-bwd program would recompute it — that recompute is
            # exactly the edge the single-program scan baseline would keep.
            if self.is_last:
                def head(p_, x_):
                    return loss_fn(stage_fn(p_, x_))

                def fwd_last(p, x):
                    loss, pullback = jax.vjp(head, p, x)
                    return loss, pullback

                self._fwd = jax.jit(fwd_last)
            else:
                self._fwd = jax.jit(lambda p, x: jax.vjp(stage_fn, p, x))
            self._bwd = jax.jit(lambda pullback, ct: pullback(ct))  # (gp, gx)
        else:
            self._build_dp_programs()
        self._acc = jax.jit(
            lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
        self._zeros = jax.jit(
            lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
        upd = self.update_fn
        if upd is None:
            lr = jnp.float32(self.cfg.learning_rate)

            def upd(p, g):
                return jax.tree_util.tree_map(lambda pv, gv: pv - lr * gv, p, g)

        self._update = jax.jit(upd)
        self._programs_ready = True

    def _build_dp_programs(self) -> None:
        """stage_dp > 1: shard the microbatch over a local "dp" mesh and fold
        PR 10's bucketed grad sync into the backward program. Per-shard param
        grads are partial sums, so the group reduce is a SUM — expressed as
        dp * pmean to ride `grad_sync._sync_bucketed` unchanged."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ray_tpu.train import grad_sync

        stage_fn, loss_fn = self.stage_fn, self.loss_fn
        mesh = self._dp_mesh
        dp = jnp.float32(self.cfg.stage_dp)
        sync = grad_sync.GradSyncConfig(mode="bucketed")

        def scale(tree, s):
            return jax.tree_util.tree_map(lambda a: a * s, tree)

        self._fwd = jax.jit(jax.shard_map(
            stage_fn, mesh=mesh, in_specs=(P(), P("dp")), out_specs=P("dp"),
            axis_names={"dp"}))
        if self.is_last:
            def bwd_last(p, x, ct):
                def head(p_, x_):
                    return loss_fn(stage_fn(p_, x_))
                loss, vjp = jax.vjp(head, p, x)
                # loss_fn is a microbatch mean: d(mb mean)/d(shard) is the
                # shard's local cotangent scaled by 1/dp
                gp, gx = vjp(ct / dp)
                gp = scale(grad_sync._sync_bucketed(gp, "dp", sync, None), dp)
                return jax.lax.pmean(loss, "dp"), gp, gx

            self._bwd = jax.jit(jax.shard_map(
                bwd_last, mesh=mesh, in_specs=(P(), P("dp"), P()),
                out_specs=(P(), P(), P("dp")), axis_names={"dp"}))
        else:
            def bwd(p, x, gy):
                _, vjp = jax.vjp(stage_fn, p, x)
                gp, gx = vjp(gy)
                gp = scale(grad_sync._sync_bucketed(gp, "dp", sync, None), dp)
                return gp, gx

            self._bwd = jax.jit(jax.shard_map(
                bwd, mesh=mesh, in_specs=(P(), P("dp"), P("dp")),
                out_specs=(P(), P("dp")), axis_names={"dp"}))

    # -- schedule execution ----------------------------------------------------------
    def _prefetch_ahead(self, step: int, idx: int) -> None:
        """Issue overlapped pulls for the next `prefetch` events' remote blocks."""
        for j in range(idx + 1, min(idx + 1 + self.cfg.prefetch, len(self.events))):
            kind, mb = self.events[j]
            if kind == "fwd" and not self.is_first:
                self.comm.prefetch("fwd", step, mb, self.stage - 1,
                                   self.in_shape, self.in_dtype)
            elif kind == "bwd" and not self.is_last:
                self.comm.prefetch("bwd", step, mb, self.stage + 1,
                                   self.out_shape, self.out_dtype)

    @hot_path(reason="per-microbatch schedule walk: transfers must overlap compute")
    def run_step(self, step: int, batch: Optional[np.ndarray] = None) -> Dict[str, Any]:
        """Walk this stage's event list for one optimizer step: forwards pull
        activations from upstream and publish downstream, backwards pull
        activation-grads from downstream and publish upstream; per-microbatch
        param grads fold (reverse order) into one update at the end.

        Raises `CollectiveAbortError` (typed, within one abort-poll interval)
        when any stage of the run dies; activation buffers are retracted on
        the way out so survivors leak nothing."""
        from ray_tpu.core.exceptions import CollectiveAbortError

        if not self._programs_ready:
            self._build_programs()
        m = self.cfg.num_microbatches
        if self.is_first:
            if batch is None:
                raise ValueError("stage 0 needs the step's batch")
            if batch.shape[0] % m:
                raise ValueError(
                    f"batch dim {batch.shape[0]} not divisible by {m} microbatches")
            batch = np.asarray(batch, self.in_dtype).reshape(  # graftlint: allow[host-sync-in-hot-path] stage-0 step input is already host memory; this is a dtype/shape normalize, not a device fetch
                (m, batch.shape[0] // m) + tuple(batch.shape[1:]))
        xs: Dict[int, Any] = {}       # microbatch -> primal input (dp path only)
        pbs: Dict[int, Any] = {}      # microbatch -> stashed vjp pullback
        grads: Dict[int, Any] = {}    # microbatch -> param-grad tree (device)
        losses: Dict[int, Any] = {}
        try:
            for idx, (kind, mb) in enumerate(self.events):
                self._prefetch_ahead(step, idx)
                if kind == "fwd":
                    x = batch[mb] if self.is_first else self.comm.take(
                        "fwd", step, mb, self.stage - 1, self.in_shape, self.in_dtype)
                    with telemetry.span(PIPELINE_SPAN, "train", stage=self.stage,
                                        kind="fwd", mb=mb, step=step):
                        t0 = time.perf_counter()
                        if self._stash:
                            y, pbs[mb] = self._fwd(self.params, x)
                        else:
                            y = self._fwd(self.params, x)
                            xs[mb] = x
                        if not self.is_last:
                            # designed sync point: the block must be host bytes
                            # before it can publish to the data plane
                            y = np.asarray(y)  # graftlint: allow[host-sync-in-hot-path] publish boundary
                        else:
                            import jax

                            y = jax.block_until_ready(y)  # graftlint: allow[host-sync-in-hot-path] span must cover compute, not async dispatch
                            if self._stash:
                                # stashed last-stage forward already folds
                                # loss_fn, so y IS the microbatch loss
                                losses[mb] = y
                        self._record(t0, "fwd", mb, step)
                    if not self.is_last:
                        self.comm.publish("fwd", step, mb, y)
                else:
                    gy = None if self.is_last else self.comm.take(
                        "bwd", step, mb, self.stage + 1, self.out_shape, self.out_dtype)
                    with telemetry.span(PIPELINE_SPAN, "train", stage=self.stage,
                                        kind="bwd", mb=mb, step=step):
                        t0 = time.perf_counter()
                        if self._stash:
                            gp, gx = self._bwd(
                                pbs.pop(mb), self._ct if self.is_last else gy)
                        elif self.is_last:
                            loss, gp, gx = self._bwd(self.params, xs[mb], self._ct)
                            losses[mb] = loss
                        else:
                            gp, gx = self._bwd(self.params, xs[mb], gy)
                        if not self.is_first:
                            # designed sync point: upstream needs host bytes
                            gx = np.asarray(gx)  # graftlint: allow[host-sync-in-hot-path] publish boundary
                        self._record(t0, "bwd", mb, step)
                    grads[mb] = gp
                    xs.pop(mb, None)
                    if not self.is_first:
                        self.comm.publish("bwd", step, mb, gx)
        except (CollectiveAbortError, TimeoutError):
            self.comm.abort_cleanup()
            raise
        # Fold per-microbatch grads in REVERSE order from zeros — the exact
        # chain lax.scan's transpose produces (float add is commutative but
        # not associative; arrival order would NOT be bit-exact).
        acc = self._zeros(self.params)
        for mb in range(m - 1, -1, -1):
            acc = self._acc(acc, grads[mb])
        self.last_grads = acc
        self.params = self._update(self.params, acc)
        self.last_losses = [losses[i] for i in range(m)] if self.is_last else []
        out: Dict[str, Any] = {"stage": self.stage, "step": step,
                               "admission": self.comm.admission_counters()}
        if self.is_last:
            import jax.numpy as jnp

            total = jnp.mean(jnp.stack(self.last_losses))
            out["loss"] = float(total)  # graftlint: allow[host-sync-in-hot-path] step boundary: metrics leave the device here
        return out

    def _record(self, t0: float, kind: str, mb: int, step: int) -> None:
        """Local chrome-trace record of the compute span: per-stage bubble
        fraction needs only the stage's own clock, so these are merged across
        stages without alignment (and work with telemetry disabled)."""
        t1 = time.perf_counter()
        self.timeline.append({
            "name": PIPELINE_SPAN, "ph": "X", "ts": t0 * 1e6,
            "dur": (t1 - t0) * 1e6,
            "args": {"stage": self.stage, "kind": kind, "mb": mb, "step": step},
        })

    # -- state hooks (checkpoint / parity) -------------------------------------------
    def params_host(self) -> Any:
        import jax

        return jax.tree_util.tree_map(np.asarray, self.params)

    def grads_host(self) -> Any:
        import jax

        if self.last_grads is None:
            return None
        return jax.tree_util.tree_map(np.asarray, self.last_grads)

    def set_params(self, params: Any) -> None:
        import jax

        self.params = jax.device_put(params)

    def close(self) -> None:
        self.comm.close()


def stage_runner_from_train_context(stage_fn: Callable, params: Any,
                                    cfg: MPMDPipelineConfig, *,
                                    loss_fn: Optional[Callable] = None,
                                    update_fn: Optional[Callable] = None,
                                    in_spec=None, out_spec=None) -> StageRunner:
    """Build a StageRunner inside a Train worker session: the worker's rank IS
    its pipeline stage and the backend-created collective group (JaxConfig
    (collective_group=True); RAY_TPU_TRAIN_COLLECTIVE_GROUP) carries the
    blocks — so Train's failure policy (max_failures, salvage, restart from
    the latest checkpoint) applies to pipeline runs with no extra wiring."""
    import os

    from ray_tpu.util.collective import collective

    group = os.environ.get("RAY_TPU_TRAIN_COLLECTIVE_GROUP")
    if not group:
        raise RuntimeError(
            "no Train collective group in this session: construct the trainer "
            "with JaxConfig(collective_group=True)")
    st = collective._state(group)
    return StageRunner(st, st.rank, st.world_size, stage_fn, params, cfg,
                       loss_fn=loss_fn, update_fn=update_fn,
                       in_spec=in_spec, out_spec=out_spec)


# ---------------------------------------------------------------- driver facade
class _StageActor:
    """One pipeline stage as a standalone actor (the non-Train entry point:
    parity tests, bench). Joins the group via CollectiveActorMixin, then hosts
    a StageRunner."""

    def setup(self, stage: int, pp: int, stage_fn: Callable, params: Any,
              cfg: MPMDPipelineConfig, loss_fn, update_fn,
              in_spec, out_spec) -> int:
        self.runner = StageRunner(
            _collective_state(cfg.group_name), stage, pp, stage_fn, params,
            cfg, loss_fn=loss_fn, update_fn=update_fn,
            in_spec=in_spec, out_spec=out_spec)
        return stage

    def run_step(self, step: int, batch=None) -> Dict[str, Any]:
        return self.runner.run_step(step, batch)

    def params_host(self):
        return self.runner.params_host()

    def grads_host(self):
        return self.runner.grads_host()

    def admission(self) -> Dict[str, int]:
        return self.runner.comm.admission_counters()

    def timeline(self) -> List[Dict[str, Any]]:
        return list(self.runner.timeline)

    def reset_timeline(self) -> None:
        self.runner.timeline.clear()

    def close(self) -> None:
        runner = getattr(self, "runner", None)
        if runner is not None:
            runner.close()


def _collective_state(group_name: str):
    from ray_tpu.util.collective import collective

    return collective._state(group_name)


def _chain_specs(stage_fns: List[Callable], params: List[Any],
                 microbatch_spec) -> List[Tuple[Any, Any]]:
    """(in_spec, out_spec) per stage via an eval_shape chain from the
    microbatch input spec — no stage runs any real compute here."""
    import jax

    shape, dtype = _as_spec(microbatch_spec)
    spec = jax.ShapeDtypeStruct(shape, dtype)
    out = []
    for fn, p in zip(stage_fns, params):
        y = jax.eval_shape(fn, jax.eval_shape(lambda t: t, p), spec)
        out.append((spec, y))
        spec = y
    return out


class MPMDPipeline:
    """Driver facade: one actor per stage, a collective group underneath, and
    a step loop that streams microbatches through the 1F1B schedule. See the
    module docstring; `parallel/mpmd.py` re-exports this.

        pipe = MPMDPipeline(stage_fns, stage_params, loss_fn=loss,
                            microbatch_spec=((mb, d), jnp.float32),
                            cfg=MPMDPipelineConfig.from_env())
        for step, batch in enumerate(batches):
            metrics = pipe.step(step, batch)   # {"loss": ..., "admission": ...}
        fractions = pipe.bubble_fractions()    # also publishes the gauge
        pipe.shutdown()
    """

    def __init__(self, stage_fns: List[Callable], stage_params: List[Any],
                 *, loss_fn: Callable, microbatch_spec,
                 cfg: Optional[MPMDPipelineConfig] = None,
                 update_fn: Optional[Callable] = None):
        import ray_tpu
        from ray_tpu.util.collective.collective import (CollectiveActorMixin,
                                                        create_collective_group)

        self.cfg = cfg or MPMDPipelineConfig.from_env()
        self.pp = len(stage_fns)
        if self.pp < 2:
            raise ValueError("MPMD pipeline needs pp >= 2 stages")
        if len(stage_params) != self.pp:
            raise ValueError("one params tree per stage")
        specs = _chain_specs(stage_fns, stage_params, microbatch_spec)

        class _Actor(_StageActor, CollectiveActorMixin):
            pass

        actor_cls = ray_tpu.remote(_Actor)
        self.actors = [actor_cls.options(num_cpus=0).remote()
                       for _ in range(self.pp)]
        create_collective_group(self.actors, self.pp, list(range(self.pp)),
                                backend="shm", group_name=self.cfg.group_name)
        ray_tpu.get([
            a.setup.remote(s, self.pp, stage_fns[s], stage_params[s], self.cfg,
                           loss_fn if s == self.pp - 1 else None, update_fn,
                           specs[s][0], specs[s][1])
            for s, a in enumerate(self.actors)])

    def step(self, step: int, batch: np.ndarray) -> Dict[str, Any]:
        """Run one optimizer step; returns the last stage's metrics (loss,
        admission counters). A stage death surfaces as the survivors' typed
        `CollectiveAbortError`."""
        import ray_tpu

        refs = [a.run_step.remote(step, batch if s == 0 else None)
                for s, a in enumerate(self.actors)]
        results = ray_tpu.get(refs)
        return results[-1]

    def params_host(self) -> List[Any]:
        import ray_tpu

        return ray_tpu.get([a.params_host.remote() for a in self.actors])

    def grads_host(self) -> List[Any]:
        import ray_tpu

        return ray_tpu.get([a.grads_host.remote() for a in self.actors])

    def admission(self) -> List[Dict[str, int]]:
        import ray_tpu

        return ray_tpu.get([a.admission.remote() for a in self.actors])

    def merged_timeline(self) -> List[Dict[str, Any]]:
        import ray_tpu

        events: List[Dict[str, Any]] = []
        for tl in ray_tpu.get([a.timeline.remote() for a in self.actors]):
            events.extend(tl)
        return events

    def reset_timelines(self) -> None:
        """Drop span records so far (e.g. compile-step warmup) so
        `bubble_fractions()` reflects only steady-state steps."""
        import ray_tpu

        ray_tpu.get([a.reset_timeline.remote() for a in self.actors])

    def bubble_fractions(self) -> Dict[str, float]:
        """Per-stage bubble fractions from the merged stage timelines; also
        publishes the `train_pipeline_bubble_fraction` gauge so
        `cluster_status()["train"]` / `ray-tpu status` pick it up."""
        fractions = bubble_fraction(self.merged_timeline())
        if fractions:
            publish_bubble_gauge(fractions)
        return fractions

    def shutdown(self) -> None:
        import ray_tpu
        from ray_tpu.util.collective.collective import kill_coordinator

        for a in self.actors:
            try:
                ray_tpu.get(a.close.remote(), timeout=10)
            # graftlint: allow[swallowed-exception] teardown best-effort: a dead stage actor must not block shutdown
            except Exception:
                pass
        kill_coordinator(self.cfg.group_name)
        for a in self.actors:
            ray_tpu.kill(a)
        self.actors = []
