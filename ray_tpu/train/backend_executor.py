"""BackendExecutor: drives the worker group through a training run.

Reference capability: python/ray/train/_internal/backend_executor.py — BackendExecutor
(:73), start (:146), start_training (:460) — plus the v2 controller's failure handling
(v2/_internal/execution/controller/controller.py:94): on worker failure the whole group is
torn down and restarted from the latest checkpoint, up to FailureConfig.max_failures.
"""
from __future__ import annotations

import contextlib
import logging
import time
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.core.exceptions import ActorError, RayTpuError

from ..air.config import FailureConfig, ScalingConfig
from .backend import BackendConfig
from .checkpoint import Checkpoint
from .checkpoint_manager import CheckpointManager
from .result import Result
from . import session
from .session import TrainContext
from .worker_group import WorkerGroup

logger = logging.getLogger(__name__)


class TrainingFailedError(RuntimeError):
    """A training worker (or the whole group) failed.

    worker_rank / error_type carry the first failed rank and its exception's
    type name (e.g. "CollectiveAbortError" when a peer rank died mid-op) so
    failure policies can classify without parsing tracebacks."""

    worker_rank: Optional[int] = None
    error_type: Optional[str] = None


def restart_backoff_s(failure_count: int) -> float:
    """Bounded exponential backoff before worker-group restart N: a crash loop
    (bad checkpoint, flapping node) must not hot-spin group construction."""
    from ray_tpu.config import CONFIG

    base = CONFIG.train_restart_backoff_s
    if base <= 0:
        return 0.0
    return min(CONFIG.train_restart_backoff_max_s,
               base * (2 ** max(0, failure_count - 1)))


@contextlib.contextmanager
def _setup_phase(phase: str):
    start_wall, start = time.time_ns(), time.perf_counter_ns()
    try:
        yield
    finally:
        session.record_setup(phase, start_wall, time.perf_counter_ns() - start)


class BackendExecutor:
    def __init__(
        self,
        backend_config: BackendConfig,
        scaling_config: ScalingConfig,
        checkpoint_manager: Optional[CheckpointManager] = None,
        failure_config: Optional[FailureConfig] = None,
        experiment_name: str = "",
        poll_interval_s: float = 0.05,
    ):
        self.backend_config = backend_config
        self.backend = backend_config.backend_cls()
        self.scaling_config = scaling_config
        self.checkpoint_manager = checkpoint_manager
        self.failure_config = failure_config or FailureConfig()
        self.experiment_name = experiment_name
        self.poll_interval_s = poll_interval_s
        self.worker_group: Optional[WorkerGroup] = None
        self._latest_metrics: Dict[str, Any] = {}
        self._history: List[Dict[str, Any]] = []
        self._per_worker: Dict[int, Dict[str, Any]] = {}  # rank -> last metrics + node

    # -- lifecycle ---------------------------------------------------------------------
    def start(self) -> None:
        # the way from fit() to the user's loop, stamped where it happens (session.record_setup)
        with _setup_phase("train.setup.worker_group"):  # actors asked for -> every get_metadata back
            self.worker_group = WorkerGroup(
                num_workers=self.scaling_config.num_workers,
                resources_per_worker=self.scaling_config.worker_resources(),
                placement_strategy=self.scaling_config.placement_strategy,
            )
        with _setup_phase("train.setup.backend"):
            self.backend.on_start(self.worker_group, self.backend_config)

    def start_training(
        self,
        train_fn: Callable[[Dict[str, Any]], None],
        train_loop_config: Dict[str, Any],
        datasets: Optional[Dict[str, Any]] = None,
        checkpoint: Optional[Checkpoint] = None,
    ) -> None:
        assert self.worker_group is not None, "call start() first"
        with _setup_phase("train.setup.session"):  # start_session sent -> returned
            self._start_sessions(train_fn, train_loop_config, datasets, checkpoint)

    def _start_sessions(self, train_fn, train_loop_config, datasets, checkpoint) -> None:
        self.backend.on_training_start(self.worker_group, self.backend_config)
        if self.checkpoint_manager is not None:
            self.checkpoint_manager.resume_point = checkpoint
        node_ranks = self.worker_group.node_ranks()
        local_counts: Dict[int, int] = {}
        refs = []
        for rank, w in enumerate(self.worker_group.workers):
            nr = node_ranks[rank]
            local_rank = local_counts.get(nr, 0)
            local_counts[nr] = local_rank + 1
            ctx = TrainContext(
                world_size=len(self.worker_group),
                world_rank=rank,
                local_rank=local_rank,
                local_world_size=node_ranks.count(nr),
                node_rank=nr,
                experiment_name=self.experiment_name,
            )
            shards = _split_datasets(datasets, rank, len(self.worker_group))
            staging = (
                self.checkpoint_manager.staging_dir if self.checkpoint_manager else None
            )
            refs.append(
                w.start_session.remote(
                    train_fn, dict(train_loop_config), ctx, checkpoint, shards, staging
                )
            )
        ray_tpu.get(refs)

    def poll(self) -> Dict[str, Any]:
        """One poll cycle. Returns {"finished": bool}; raises on worker failure."""
        assert self.worker_group is not None
        polls = ray_tpu.get([w.poll_session.remote() for w in self.worker_group.workers])
        # Drain reports BEFORE surfacing errors: checkpoints reported ahead of a crash are
        # exactly what the restart resumes from. Metrics: rank 0 is canonical.
        self._register_rank0_reports(polls[0]["reports"])
        metas = self.worker_group.metadata
        for rank, p in enumerate(polls):
            if p["reports"]:
                # per-worker visibility (reference: per-worker metrics in
                # train result) — lets callers assert placement, e.g. one
                # worker per host under STRICT_SPREAD
                self._per_worker[rank] = {
                    **p["reports"][-1]["metrics"],
                    "rank": rank, "node": metas[rank].node_id}
        for rank, p in enumerate(polls):
            if p["error"]:
                e = TrainingFailedError(f"worker rank {rank} failed:\n{p['error']}")
                e.worker_rank = rank
                e.error_type = p.get("error_type")
                raise e
        return {"finished": all(p["finished"] for p in polls)}

    def all_metrics(self) -> List[Dict[str, Any]]:
        """Last reported metrics of every worker rank, each tagged with its
        node id."""
        return [self._per_worker[r] for r in sorted(self._per_worker)]

    def _register_rank0_reports(self, reports: List[Dict[str, Any]]) -> None:
        """Record rank 0's canonical reports (metrics history + durable
        checkpoints) — shared by poll() and the post-failure salvage drain so
        what a restart resumes from never diverges from what polling records."""
        for rep in reports:
            metrics = rep["metrics"]
            self._latest_metrics = metrics
            self._history.append(metrics)
            ckpt = rep["checkpoint"]
            if ckpt is not None and self.checkpoint_manager is not None:
                self.checkpoint_manager.register(ckpt, metrics)

    def drain_after_failure(self, grace_s: float = 2.0) -> None:
        """Salvage surviving ranks' last reports before tearing the group down.

        A worker failure races the other ranks' reporting: rank 0's checkpoint
        for step N may be staged (durable) but not yet polled when another
        rank's error surfaces — and losing it restarts the run from a much
        older step, or from nothing. Give surviving sessions a bounded grace
        period to settle (the backend's abort hook has already unblocked any
        rank stuck in a collective), drain their queues, and register what was
        reported. Best-effort: dead actors and still-hung sessions are skipped.
        """
        if self.worker_group is None:
            return
        deadline = time.monotonic() + grace_s
        while True:
            settled = True
            for rank, w in enumerate(self.worker_group.workers):
                try:
                    p = ray_tpu.get(w.poll_session.remote(),
                                    timeout=max(0.1, deadline - time.monotonic()))
                # graftlint: allow[swallowed-exception] dead/unreachable worker: nothing to salvage there, survivors carry on
                except Exception:
                    continue  # dead/unreachable: nothing to salvage there
                if rank == 0:
                    self._register_rank0_reports(p["reports"])
                if not p["finished"]:
                    settled = False
            if settled or time.monotonic() >= deadline:
                return
            time.sleep(self.poll_interval_s)

    def salvage_after_failure(self, error: BaseException) -> None:
        """The one failure-salvage sequence both the v1 run loop and the v2
        TrainController use: unblock survivors stuck in a collective (the
        backend's abort hook beats the op timeout), then drain their
        already-reported checkpoints before a non-graceful teardown discards
        them. Best-effort — the group is about to be torn down regardless."""
        try:
            if self.worker_group is not None:
                self.backend.on_failure(self.worker_group, self.backend_config, error)
            self.drain_after_failure()
        except Exception as e:
            logger.warning("failure-handling hook raised (%r): worker "
                           "checkpoint salvage may be incomplete for this "
                           "restart", e)

    def run_until_complete(
        self,
        train_fn: Callable[[Dict[str, Any]], None],
        train_loop_config: Dict[str, Any],
        datasets: Optional[Dict[str, Any]] = None,
        resume_checkpoint: Optional[Checkpoint] = None,
    ) -> Result:
        """Full run with group-restart failure policy."""
        failures_allowed = self.failure_config.max_failures
        checkpoint = resume_checkpoint
        if checkpoint is None and self.checkpoint_manager is not None:
            checkpoint = self.checkpoint_manager.latest_checkpoint
        error: Optional[str] = None
        failure_count = 0
        while True:
            try:
                if self.worker_group is None:
                    self.start()
                self.start_training(train_fn, train_loop_config, datasets, checkpoint)
                while True:
                    state = self.poll()
                    if state["finished"]:
                        break
                    time.sleep(self.poll_interval_s)
                break  # success
            except (TrainingFailedError, ActorError, RayTpuError) as e:
                logger.warning("training worker group failed: %s", e)
                failure_count += 1
                self.salvage_after_failure(e)
                self.shutdown(graceful=False)
                if failures_allowed == 0:
                    error = str(e)
                    break
                if failures_allowed > 0:
                    failures_allowed -= 1
                # Restart from the most recent durable checkpoint.
                if self.checkpoint_manager is not None:
                    checkpoint = self.checkpoint_manager.latest_checkpoint or resume_checkpoint
                time.sleep(restart_backoff_s(failure_count))
        latest_ckpt = (
            self.checkpoint_manager.latest_checkpoint if self.checkpoint_manager else None
        )
        best_ckpt = self.checkpoint_manager.best_checkpoint if self.checkpoint_manager else None
        return Result(
            metrics=self._latest_metrics,
            checkpoint=latest_ckpt,
            best_checkpoint=best_ckpt,
            error=error,
            metrics_dataframe=list(self._history),
            all_metrics=self.all_metrics(),
        )

    def shutdown(self, graceful: bool = True) -> None:
        if self.worker_group is None:
            return
        if graceful:
            try:
                self.backend.on_shutdown(self.worker_group, self.backend_config)
                ray_tpu.get([w.end_session.remote() for w in self.worker_group.workers])
            # graftlint: allow[swallowed-exception] shutdown teardown: workers may already be gone
            except Exception:
                pass
        self.worker_group.shutdown()
        self.worker_group = None


def _split_datasets(datasets: Optional[Dict[str, Any]], rank: int, world: int):
    """Per-worker dataset shards (reference _internal/data_config.py). Datasets exposing
    split_at_indices/streaming_split get sharded; plain iterables pass through whole."""
    if not datasets:
        return {}
    out = {}
    for name, ds in datasets.items():
        if hasattr(ds, "split_for_workers"):
            out[name] = ds.split_for_workers(world)[rank]
        else:
            out[name] = ds
    return out
