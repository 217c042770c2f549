"""JaxConfig / JaxBackend: the jax.distributed process-group bootstrap.

Reference shape: python/ray/train/torch/config.py — TorchConfig (:36), _TorchBackend
(:153), _setup_torch_process_group (:66). The reference rendezvouses a NCCL process group;
here the worker group forms ONE jax.distributed universe so workers can build a global
device Mesh spanning every chip of the pod slice, and gradient sync happens *inside* pjit
programs as XLA collectives over ICI — there is no NCCL analogue to configure.

SURVEY.md §2.4 notes JaxTrainer does not exist in the reference; this follows the Backend
plugin shape it prescribes.
"""
from __future__ import annotations

import logging
import os
import socket
from dataclasses import dataclass
from typing import Dict, Optional, Type

from .backend import Backend, BackendConfig
from .grad_sync import GradSyncConfig
from .worker_group import WorkerGroup

LOGGER = logging.getLogger(__name__)


@dataclass
class JaxConfig(BackendConfig):
    """Backend config for JAX workers.

    distributed: form a jax.distributed universe across workers (multi-host pods). Off by
      default for single-host/CPU test runs where each worker keeps a private runtime.
    platform: value for JAX_PLATFORMS in workers ("" = leave as-is / auto-detect TPU).
    collective_group: also create a host-plane shm collective group named "train" over the
      workers (out-of-jit weight broadcast / metric reduction; reference's gloo group).
    grad_sync: device-plane gradient-sync strategy (train/grad_sync.py: bucketed
      overlapped all-reduce, int8 reduction, cross-replica sharded optimizer update).
      Exported to the workers' env, so user loops that call `make_train_step()` /
      `init_state()` without an explicit `sync=` pick it up — the stock-Trainer-API
      config flag.
    """

    distributed: bool = False
    platform: str = ""
    coordinator_port: int = 0
    collective_group: bool = True
    # Unique per run unless pinned: two concurrent trainers must not share a coordinator.
    collective_group_name: str = ""
    grad_sync: Optional[GradSyncConfig] = None
    env: Optional[Dict[str, str]] = None  # extra env vars set in workers before jax import

    @property
    def backend_cls(self) -> Type["JaxBackend"]:
        return JaxBackend


# Rendezvous bound. jax's default initialization_timeout is 300s; the retry
# path below queues behind first-round tasks still blocked in connect (train
# workers execute serially), so a failed first round must release its workers
# well before the fresh coordinator of the retry gives up waiting for them.
_JAX_INIT_TIMEOUT_S = int(os.environ.get("RAY_TPU_TRAIN_JAX_INIT_TIMEOUT_S", "60"))


def _init_jax_distributed(coordinator_address: str, num_processes: int, process_id: int) -> None:
    import jax

    # Re-entrant for the coordinator-port retry: a worker whose first
    # rendezvous died mid-connect still holds the half-initialized client
    # (jax assigns global_state.client BEFORE connect()), and initialize()
    # refuses to run twice. Tear the remnant down first.
    try:
        from jax._src.distributed import global_state as _gs

        if getattr(_gs, "client", None) is not None:
            jax.distributed.shutdown()
    except Exception as e:  # noqa: BLE001 — half-dead client; proceed to init
        LOGGER.warning("jax.distributed pre-init cleanup failed: %r", e)

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        initialization_timeout=_JAX_INIT_TIMEOUT_S,
    )


def _pick_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _is_bind_failure(err: BaseException) -> bool:
    """Did jax.distributed.initialize lose the _pick_port bind->close->reuse
    race (another process grabbed the port between probe and coordinator
    startup)? Matched narrowly: worker errors arrive as TaskError whose str()
    embeds the WHOLE remote traceback, so a generic token like "bind" would
    match unrelated frames (e.g. a `sock.bind(...)` source line) and send an
    unrelated failure into a doomed retry that buries the real error."""
    import errno

    if isinstance(err, OSError) and err.errno == errno.EADDRINUSE:
        return True  # direct (non-wrapped) bind failure
    msg = str(err).lower()
    return any(tok in msg
               for tok in ("failed to bind", "bind failed",
                           "address already in use", "errno 98"))


class JaxBackend(Backend):
    def on_start(self, worker_group: WorkerGroup, backend_config: JaxConfig) -> None:
        if backend_config.collective_group and not backend_config.collective_group_name:
            import uuid

            backend_config.collective_group_name = f"train_{uuid.uuid4().hex[:8]}"
        group_name = backend_config.collective_group_name
        envs = []
        for rank in range(len(worker_group)):
            env = {
                "RAY_TPU_TRAIN_WORLD_SIZE": str(len(worker_group)),
                "RAY_TPU_TRAIN_RANK": str(rank),
            }
            if backend_config.collective_group:
                env["RAY_TPU_TRAIN_COLLECTIVE_GROUP"] = group_name
            if backend_config.platform:
                env["JAX_PLATFORMS"] = backend_config.platform
            if backend_config.grad_sync is not None:
                env.update(backend_config.grad_sync.to_env())
            if backend_config.env:
                env.update(backend_config.env)
            envs.append(env)
        worker_group.set_env(envs)

        if backend_config.distributed and len(worker_group) > 1:
            host = worker_group.execute_single(0, socket.gethostname)
            import ray_tpu

            def _rendezvous(port: int) -> None:
                addr = f"{host}:{port}"
                refs = [
                    w.run_fn.remote(_init_jax_distributed, addr, len(worker_group), rank)
                    for rank, w in enumerate(worker_group.workers)
                ]
                ray_tpu.get(refs)

            # Pick the port ON worker 0's host — a driver-side free port proves nothing
            # about the machine that will actually bind it.
            port = backend_config.coordinator_port or worker_group.execute_single(0, _pick_port)
            try:
                _rendezvous(port)
            except Exception as e:
                # _pick_port's bind->close->probe leaves a TOCTOU window:
                # another process can claim the port before the coordinator
                # binds it. One retry with a fresh probe (only when the port
                # was OURS to re-pick) beats failing the whole run.
                if backend_config.coordinator_port or not _is_bind_failure(e):
                    raise
                port = worker_group.execute_single(0, _pick_port)
                LOGGER.warning(
                    "jax.distributed coordinator lost the port race (%s); "
                    "retrying once on fresh port %d", e, port)
                _rendezvous(port)

        # after the rendezvous (jax.distributed must come before the backend):
        # a worker that was given chips and came up without them fails here,
        # not by training on the host in silence
        from ray_tpu.core.accelerators import check_worker_platform

        worker_group.execute(check_worker_platform)

        if backend_config.collective_group:
            from ray_tpu.util import collective as col
            from ray_tpu.util import telemetry

            # Clear any stale coordinator (e.g. from a crashed prior generation of this
            # run) so the new generation's sequence numbers start on clean boards.
            with telemetry.span("train.collective_init", "train",
                                group=group_name, world=len(worker_group)):
                col.kill_coordinator(group_name)
                col.create_collective_group(
                    worker_group.workers,
                    len(worker_group),
                    list(range(len(worker_group))),
                    backend="shm",
                    group_name=group_name,
                )

    def on_failure(self, worker_group: WorkerGroup, backend_config: JaxConfig,
                   error: BaseException) -> None:
        """Poison the run's collective group before the non-graceful teardown.

        When one rank's session dies (an exception in the user loop — no
        process death, so core worker-death cleanup never fires), its peers
        may be blocked mid-allreduce with nobody left to arrive. The abort
        converts that wait into a fast CollectiveAbortError, so survivors
        finish their sessions in time for the executor's salvage drain and
        the group restart is not pinned behind collective_op_timeout_s."""
        if backend_config.collective_group and backend_config.collective_group_name:
            from ray_tpu.util import collective as col
            from ray_tpu.util import telemetry

            telemetry.get_counter(
                "train_group_failures_total",
                "training worker-group failures that poisoned the run's "
                "collective group").inc()
            telemetry.event("train.abort", "train",
                            group=backend_config.collective_group_name,
                            reason=str(error)[:200])
            # wait=False: on_failure must not block on the (possibly half-
            # dead) group — a wedged coordinator host would otherwise pin the
            # restart behind the op timeout, the exact stall this hook exists
            # to avoid
            col.abort_collective_group(
                backend_config.collective_group_name,
                reason=f"training worker group failed: {error}",
                wait=False)

    def on_shutdown(self, worker_group: WorkerGroup, backend_config: JaxConfig) -> None:
        def _shutdown():
            import jax

            try:
                if jax.process_count() > 1:
                    jax.distributed.shutdown()
            except Exception as e:  # noqa: BLE001 — best-effort teardown
                _warn_shutdown_failure("jax.distributed.shutdown", e)

        try:
            worker_group.execute(_shutdown)
        except Exception as e:  # noqa: BLE001 — workers may already be dead
            _warn_shutdown_failure("worker group shutdown broadcast", e)
        if backend_config.collective_group and backend_config.collective_group_name:
            from ray_tpu.util import collective as col

            col.kill_coordinator(backend_config.collective_group_name)


_shutdown_warn_interval_s = 30.0
_last_shutdown_warning = [0.0]  # monotonic stamp (same convention as tracing._maybe_flush)


def _warn_shutdown_failure(what: str, err: BaseException) -> None:
    """Teardown is best-effort, but a swallowed error is undiagnosable — log it
    (throttled, the repo convention since PR 8's tracing._maybe_flush fix)."""
    import time

    now = time.monotonic()
    if now - _last_shutdown_warning[0] >= _shutdown_warn_interval_s:
        _last_shutdown_warning[0] = now
        LOGGER.warning("JaxBackend.on_shutdown: %s failed: %r (continuing "
                       "teardown; further failures muted for %.0fs)",
                       what, err, _shutdown_warn_interval_s)
