"""ServeController: the singleton reconciliation actor.

Capability parity: reference python/ray/serve/_private/controller.py:88 +
application_state.py + deployment_state.py — target-state reconciliation loop,
replica health checks, rolling updates on version change, request-rate autoscaling
(autoscaling_state.py). Handles/proxies poll get_routing_table() (long-poll analog).
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.core.actor import method as _actor_method

CONTROLLER_NAME = "SERVE_CONTROLLER"

STARTING, RUNNING, STOPPING = "STARTING", "RUNNING", "STOPPING"
# graceful exit: out of the long-poll view immediately (no new requests),
# in-flight requests get up to drain_timeout_s to finish, then the kill
DRAINING = "DRAINING"


import itertools as _it

logger = logging.getLogger("ray_tpu.serve.controller")

_replica_uid = _it.count(1)


def _is_head_unavailable(err: BaseException) -> bool:
    """Head outage vs replica death: a health probe that failed because the
    CONTROL PLANE went away says nothing about the replica process, which
    keeps running on its agent. The reconciler must not turn a head blip into
    a replica-replacement storm (mirrors handle.is_head_unavailable; kept
    local so the controller has no import edge into the handle module)."""
    from ray_tpu.core.exceptions import HeadUnavailableError, TaskError

    if isinstance(err, TaskError):
        return isinstance(err.cause, HeadUnavailableError)
    return isinstance(err, HeadUnavailableError)


class _ReplicaState:
    def __init__(self, actor, version):
        self.actor = actor
        self.version = version
        self.uid = next(_replica_uid)  # stable identity (id() can be reused by GC)
        self.state = STARTING
        self.started_at = time.time()  # stuck-STARTING detection (autoscaler)
        self.health_ref = None
        self.last_health_ok = time.time()
        self.node_id: Optional[str] = None  # packing assignment (soft affinity)
        self.drain_deadline: Optional[float] = None
        self.drain_ref = None  # outstanding drain()/num_inflight() poll


class _DeploymentState:
    """Reference deployment_state.py:1379 — one deployment's replica set."""

    def __init__(self, name: str, app_name: str, info: Dict[str, Any]):
        self.name = name
        self.app_name = app_name
        self.info = info  # serialized_init, config, route_prefix, is_ingress
        self.replicas: List[_ReplicaState] = []
        self.target_num: int = info["config"].num_replicas or 1
        ac = info["config"].autoscaling_config
        if ac:
            self.target_num = max(ac.min_replicas, 1)
        self.autoscale_metric: float = 0.0
        self._last_scale_change = 0.0
        self.deleting = False  # drain-down in progress; reap when empty
        # replica starts that failed in a row, and why (serve.run gives up on these)
        self.start_failures = 0
        self.start_error: Optional[str] = None

    def running(self) -> List[_ReplicaState]:
        return [r for r in self.replicas if r.state == RUNNING]

    def in_state(self, state: str) -> List[_ReplicaState]:
        return [r for r in self.replicas if r.state == state]

    def drain_timeout_s(self) -> float:
        # pre-upgrade KV checkpoints may lack the field (unpickle skips
        # defaults); 0 is a real value ("no grace, kill immediately")
        v = getattr(self.info["config"], "drain_timeout_s", None)
        return 30.0 if v is None else v


class ServeController:
    def __init__(self):
        self.deployments: Dict[str, _DeploymentState] = {}  # key: app/deployment
        self.apps: Dict[str, Dict[str, Any]] = {}  # app -> {route_prefix, ingress, deployments}
        self._lock = threading.RLock()
        self._shutdown = False
        # reconcile-loop warning throttle (the loop runs several times/s)
        from ray_tpu.util.logutil import LogThrottle

        self._loop_warn = LogThrottle(30.0)
        # long-poll host state (reference _private/long_poll.py LongPollHost):
        # versioned keys; listeners block until a key they watch moves
        self._lp_versions: Dict[str, int] = {}
        self._lp_cond = threading.Condition()
        self._lp_last_running: Dict[str, tuple] = {}
        # recover target state checkpointed in the GCS KV (reference: serve app
        # state persisted in GCS KV; with RAY_TPU_GCS_PERSISTENCE_PATH it even
        # survives full cluster restarts)
        try:
            self._restore_from_kv()
        except Exception as e:
            logger.warning("serve state restore from KV failed (%r): "
                           "starting with no applications", e)
        self._reconcile_thread = threading.Thread(
            target=self._reconcile_loop, daemon=True, name="serve-reconcile")
        self._reconcile_thread.start()

    # -- target-state checkpointing (reference: GCS KV-backed serve state) -------
    _KV_NS = "serve"

    def _checkpoint_app(self, app_name: str, route_prefix: str,
                        deployments: List[Dict[str, Any]]) -> None:
        import cloudpickle

        from ray_tpu.experimental import internal_kv

        blob = cloudpickle.dumps({"route_prefix": route_prefix, "deployments": deployments})
        internal_kv._internal_kv_put(b"app::" + app_name.encode(), blob,
                                     namespace=self._KV_NS)

    def _drop_checkpoint(self, app_name: str) -> None:
        from ray_tpu.experimental import internal_kv

        internal_kv._internal_kv_del(b"app::" + app_name.encode(), namespace=self._KV_NS)

    def _restore_from_kv(self) -> None:
        import cloudpickle

        from ray_tpu.experimental import internal_kv

        for key in internal_kv._internal_kv_list(b"app::", namespace=self._KV_NS):
            blob = internal_kv._internal_kv_get(key, namespace=self._KV_NS)
            if not blob:
                continue
            try:
                spec = cloudpickle.loads(blob)
                self.deploy_application(key[len(b"app::"):].decode(),
                                        spec["route_prefix"], spec["deployments"],
                                        _checkpoint=False)
            except Exception as e:
                # a stale/unloadable app must not block the rest
                logger.warning("could not restore serve app %r from its "
                               "checkpoint (%r); skipping it",
                               key[len(b"app::"):].decode(), e)
                continue

    # -- deploy API ------------------------------------------------------------
    def deploy_application(self, app_name: str, route_prefix: str,
                           deployments: List[Dict[str, Any]], _checkpoint: bool = True) -> None:
        """deployments: [{name, serialized_init, config, is_ingress}]"""
        with self._lock:
            # checkpoint under the lock: a concurrent delete must not interleave
            # between the KV write and the in-memory update (resurrection risk)
            if _checkpoint:
                try:
                    self._checkpoint_app(app_name, route_prefix, deployments)
                # graftlint: allow[swallowed-exception] checkpointing is best-effort; serving must not depend on it
                except Exception:
                    pass  # checkpointing is best-effort; serving must not depend on it
            self.apps[app_name] = {
                "route_prefix": route_prefix,
                "ingress": next(d["name"] for d in deployments if d["is_ingress"]),
                "deployments": [d["name"] for d in deployments],
            }
            for d in deployments:
                key = f"{app_name}/{d['name']}"
                existing = self.deployments.get(key)
                if existing is not None and existing.deleting:
                    # re-deploy racing a drain-down: resurrect as a rolling
                    # update (old draining replicas finish; fresh ones start)
                    existing.deleting = False
                if existing is not None:
                    # a new deploy is a new attempt
                    existing.start_failures, existing.start_error = 0, None
                if existing is not None and existing.info["config"].version != d["config"].version:
                    # version change -> rolling update: old replicas DRAIN
                    # (finish in-flight work) while replacements start
                    existing.info = d
                    for r in existing.replicas:
                        if r.version != d["config"].version and r.state in (STARTING, RUNNING):
                            self._drain_replica(r, existing)
                    existing.target_num = d["config"].num_replicas or existing.target_num
                elif existing is None:
                    self.deployments[key] = _DeploymentState(d["name"], app_name, d)
                else:
                    existing.info = d
                    if d["config"].num_replicas:
                        existing.target_num = d["config"].num_replicas
        # draining replicas must leave the long-poll view NOW, not a reconcile
        # tick later — handles stop picking them before the kill window opens
        self._publish_changes()

    def delete_application(self, app_name: str) -> None:
        """Drain-down, not a massacre: replicas finish in-flight requests (up
        to drain_timeout_s) before the reconcile loop reaps them."""
        with self._lock:
            try:
                self._drop_checkpoint(app_name)
            # graftlint: allow[swallowed-exception] checkpoint drop is best-effort; stale blobs are skipped on restore
            except Exception:
                pass
            app = self.apps.pop(app_name, None)
            if not app:
                return
            for dname in app["deployments"]:
                ds = self.deployments.get(f"{app_name}/{dname}")
                if ds:
                    ds.deleting = True
                    ds.target_num = 0
                    for r in ds.replicas:
                        if r.state in (STARTING, RUNNING):
                            self._drain_replica(r, ds)
        self._publish_changes()

    def shutdown(self) -> None:
        """Graceful stop: every replica drains (bounded by its deployment's
        drain_timeout_s) before the kill. Idle replicas cost one RPC round."""
        import ray_tpu

        for app in list(self.apps):
            self.delete_application(app)
        with self._lock:
            self._shutdown = True  # reconcile loop stops; we finish the drain
        # let any in-progress reconcile pass finish before we touch replica
        # state (drain_ref and the kill below run without the lock held)
        try:
            self._reconcile_thread.join(timeout=10)
        # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
        except Exception:
            pass
        with self._lock:
            pending = [(r, ds) for ds in self.deployments.values()
                       for r in ds.replicas]
        now = time.time()
        # honor drains already in progress (delete_application stamped their
        # deadline): shutdown must not grant a wedged replica a fresh window
        deadline_of = {id(r): (r.drain_deadline if r.drain_deadline is not None
                               else now + ds.drain_timeout_s())
                       for r, ds in pending}
        while pending:
            now = time.time()
            still = []
            polls = []
            for r, ds in pending:
                if now > deadline_of[id(r)]:
                    self._stop_replica(r)  # drain deadline burned: kill anyway
                    continue
                try:
                    polls.append((r, ds, r.drain_ref or r.actor.num_inflight.remote()))
                # graftlint: allow[swallowed-exception] an unusable handle means the replica is gone: it is reaped right here
                except Exception:
                    self._stop_replica(r)  # handle already unusable
            for r, ds, ref in polls:
                r.drain_ref = None
                try:
                    n = ray_tpu.get(ref, timeout=2.0)
                # graftlint: allow[swallowed-exception] degrades to the coded fallback (n = 0) by design
                except Exception:
                    n = 0  # replica already gone: nothing left to drain
                if n == 0:
                    self._stop_replica(r)
                else:
                    still.append((r, ds))
            pending = still
            if pending:
                time.sleep(0.05)
        with self._lock:
            for ds in self.deployments.values():
                ds.replicas.clear()
            self.deployments.clear()
        with self._lp_cond:  # wake parked listeners so they return promptly
            self._lp_cond.notify_all()

    def _drain_replica(self, r: _ReplicaState, ds: _DeploymentState) -> None:
        """RUNNING/STARTING -> DRAINING (caller holds the lock). The drain()
        RPC flips the replica's gate so racing sends bounce to live replicas;
        its reply doubles as the first in-flight poll."""
        r.state = DRAINING
        r.drain_deadline = time.time() + ds.drain_timeout_s()
        r.health_ref = None
        try:
            r.drain_ref = r.actor.drain.remote()
        # graftlint: allow[swallowed-exception] degrades to the coded fallback (r.drain_ref = None) by design
        except Exception:
            r.drain_ref = None  # dead already; reconcile reaps it

    # -- read APIs (handles/proxies poll these; reference LongPollHost) ---------
    def get_routing_table(self) -> Dict[str, Any]:
        with self._lock:
            out = {}
            for app_name, app in self.apps.items():
                key = f"{app_name}/{app['ingress']}"
                ds = self.deployments.get(key)
                out[app["route_prefix"]] = {
                    "app": app_name,
                    "deployment": app["ingress"],
                    "replicas": [r.actor for r in ds.running()] if ds else [],
                }
            return out

    def get_replicas(self, app_name: str, deployment_name: str) -> List[Any]:
        with self._lock:
            ds = self.deployments.get(f"{app_name}/{deployment_name}")
            return [r.actor for r in ds.running()] if ds else []

    def get_deployment_info(self, app_name: str, deployment_name: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            ds = self.deployments.get(f"{app_name}/{deployment_name}")
            if ds is None:
                return None
            return {
                "target_num_replicas": ds.target_num,
                "num_running": len(ds.running()),
                "states": [r.state for r in ds.replicas],
                "start_failures": ds.start_failures,
                "start_error": ds.start_error,
            }

    def get_deployment_limits(self, app_name: str,
                              deployment_name: str) -> Optional[Dict[str, Any]]:
        """Admission/retry knobs the handle enforces client-side (cached there;
        getattr guards cover pre-upgrade KV checkpoints missing new fields)."""
        with self._lock:
            ds = self.deployments.get(f"{app_name}/{deployment_name}")
            if ds is None:
                return None
            cfg = ds.info["config"]
            # target-aware admission: while a scale change is YOUNG the handle
            # sizes capacity on the target (arriving replicas will absorb the
            # queue); once the startup window burns without the fleet reaching
            # it, anticipation expires and shedding resumes on real capacity
            from ray_tpu.config import CONFIG

            young = (time.time() - ds._last_scale_change
                     <= CONFIG.serve_autoscale_startup_timeout_s)
            running = len(ds.running())
            return {
                "max_ongoing_requests": getattr(cfg, "max_ongoing_requests", 8),
                "max_queued_requests": getattr(cfg, "max_queued_requests", -1),
                "retryable": getattr(cfg, "retryable", True),
                "target_num_replicas": ds.target_num,
                "anticipated_replicas": ds.target_num if young else running,
            }

    def status(self) -> Dict[str, Any]:
        with self._lock:
            return {
                app: {
                    "route_prefix": info["route_prefix"],
                    "deployments": {
                        d: self.get_deployment_info(app, d) for d in info["deployments"]
                    },
                }
                for app, info in self.apps.items()
            }

    def ping(self) -> bool:
        return True

    def report_replica_failure(self, app_name: str, deployment_name: str,
                               actor_id) -> bool:
        """Handle-side death push: a client observed an authoritative
        ActorDiedError/WorkerCrashedError on this replica. Mark it STOPPING
        and republish NOW instead of letting it sit in the routing view for
        up to health_check_period_s — the window where a scale-down could
        otherwise drain the healthy replicas and keep the dead one."""
        marked = False
        with self._lock:
            ds = self.deployments.get(f"{app_name}/{deployment_name}")
            if ds is None:
                return False
            for r in ds.replicas:
                if r.actor._actor_id == actor_id and r.state in (STARTING,
                                                                 RUNNING,
                                                                 DRAINING):
                    r.state = STOPPING
                    r.health_ref = None
                    marked = True
        if marked:
            self._publish_changes()  # dead replica leaves the view immediately
        return marked

    # -- autoscaling input (handles push router stats; reference autoscaling_state) --
    def record_handle_metrics(self, app_name: str, deployment_name: str, ongoing: float) -> None:
        with self._lock:
            ds = self.deployments.get(f"{app_name}/{deployment_name}")
            if ds is not None:
                # EWMA smooth so momentary spikes don't flap the replica count
                ds.autoscale_metric = 0.6 * ds.autoscale_metric + 0.4 * ongoing

    # -- SLO-loop autoscaling surface (head-side serve/autoscaler.py) -----------
    @staticmethod
    def _ac_mode(ds: _DeploymentState) -> Optional[str]:
        ac = ds.info["config"].autoscaling_config
        if ac is None:
            return None
        # pre-upgrade KV checkpoints may lack the field (unpickle skips defaults)
        return getattr(ac, "mode", "ongoing")

    def get_autoscale_state(self) -> Dict[str, Dict[str, Any]]:
        """Everything the head-side loop needs to re-derive its decisions,
        keyed "app/deployment" — only deployments opted into mode="slo".
        Served fresh on every tick so a restarted head resumes from the
        KV-restored app configs, not anyone's in-memory state."""
        out: Dict[str, Dict[str, Any]] = {}
        with self._lock:
            for key, ds in self.deployments.items():
                if ds.deleting or self._ac_mode(ds) != "slo":
                    continue
                ac = ds.info["config"].autoscaling_config
                opts = dict(ds.info["config"].ray_actor_options or {})
                shape = {"CPU": float(opts.get("num_cpus", 1))}
                if opts.get("num_tpus"):
                    shape["TPU"] = float(opts["num_tpus"])
                route = ""
                app = self.apps.get(ds.app_name)
                if app:
                    route = app.get("route_prefix", "")
                out[key] = {
                    "app": ds.app_name,
                    "deployment": ds.name,
                    "target": ds.target_num,
                    "running": len(ds.running()),
                    "starting": len(ds.in_state(STARTING)),
                    "draining": len(ds.in_state(DRAINING)),
                    "min_replicas": ac.min_replicas,
                    "max_replicas": ac.max_replicas,
                    "target_queue_depth": getattr(ac, "target_queue_depth",
                                                  None),
                    "slo_names": getattr(ac, "slo_names", None),
                    "resource_shape": shape,
                    "route_prefix": route,
                }
        return out

    def set_autoscale_target(self, app_name: str, deployment_name: str,
                             target: int, reason: str = "") -> Optional[int]:
        """Apply one autoscaler decision. Clamped to the deployment's
        [max(1, min_replicas), max_replicas] — the control loop can never
        order the last healthy replica killed — and executed by the reconcile
        loop through the normal DRAINING choreography. Returns the clamped
        target actually set, or None when the deployment is gone or
        mid-delete (the caller must not record a scale that never happened)."""
        from ray_tpu.util import fault_injection

        fault_injection.fail_point(
            "serve.controller.scale", app=app_name,
            deployment=deployment_name, target=target, reason=reason)
        with self._lock:
            ds = self.deployments.get(f"{app_name}/{deployment_name}")
            if ds is None or ds.deleting:
                return None
            ac = ds.info["config"].autoscaling_config
            lo = max(1, ac.min_replicas) if ac else 1
            hi = ac.max_replicas if ac else max(lo, int(target))
            clamped = max(lo, min(hi, int(target)))
            if clamped != ds.target_num:
                logger.info("autoscale target %s/%s: %d -> %d (%s)",
                            app_name, deployment_name, ds.target_num,
                            clamped, reason or "unspecified")
                ds.target_num = clamped
                ds._last_scale_change = time.time()
            return clamped

    def restart_stuck_replicas(self, app_name: str, deployment_name: str,
                               older_than_s: float = 30.0) -> int:
        """Kill STARTING replicas wedged past `older_than_s` so the reconcile
        loop reschedules them (the soft node-affinity re-picks placement —
        possibly a different, newly launched node). The autoscaler calls this
        when a scale-up never becomes healthy."""
        now = time.time()
        n = 0
        with self._lock:
            ds = self.deployments.get(f"{app_name}/{deployment_name}")
            if ds is None:
                return 0
            for r in ds.replicas:
                if r.state == STARTING and now - r.started_at >= older_than_s:
                    r.state = STOPPING  # reconcile reaps + restarts elsewhere
                    r.health_ref = None
                    n += 1
        if n:
            logger.warning(
                "%s/%s: restarting %d replica(s) stuck in STARTING longer "
                "than %.0fs", app_name, deployment_name, n, older_than_s)
        return n

    # -- chaos hooks (ChaosController.arm_serve_controller) ---------------------
    def _arm_fault(self, site: str, mode: str = "error", prob: float = 1.0,
                   count: Optional[int] = None, delay_s: float = 0.0,
                   seed: Optional[int] = None) -> bool:
        """Arm a fail point in the CONTROLLER process (e.g.
        serve.controller.scale), so chaos runs can kill the scale path
        mid-decision."""
        from ray_tpu.util import fault_injection

        fault_injection.arm(site, mode, prob, count, delay_s, seed)
        return True

    def _disarm_fault(self, site: Optional[str] = None) -> bool:
        from ray_tpu.util import fault_injection

        fault_injection.disarm(site)
        return True

    # -- reconciliation --------------------------------------------------------
    def _choose_replica_node(self, ds: _DeploymentState,
                             num_cpus: float) -> Optional[str]:
        """Replica->node packing (reference _private/deployment_scheduler.py):
        PACK fills the node already hosting the most of this deployment's
        replicas (compact; whole nodes free up for downscaling), SPREAD picks
        the one hosting the fewest. Returns a node id hex, or None to let the
        default scheduler place."""
        try:
            from ray_tpu.util.state import list_nodes

            nodes = [n for n in list_nodes() if n["alive"]]
        # graftlint: allow[swallowed-exception] degrades to the coded fallback (return None) by design
        except Exception:
            return None
        if len(nodes) <= 1:
            return None
        counts = {n["node_id"]: 0 for n in nodes}
        for r in ds.replicas:
            if r.node_id in counts:
                counts[r.node_id] += 1
        fits = [n for n in nodes
                if n["resources_available"].get("CPU", 0.0) >= num_cpus]
        if not fits:
            return None
        # pre-upgrade KV checkpoints may lack the field (unpickle skips defaults)
        spread = getattr(ds.info["config"], "placement_strategy", "PACK") == "SPREAD"
        best = min(fits, key=lambda n: counts[n["node_id"]]) if spread else \
            max(fits, key=lambda n: counts[n["node_id"]])
        return best["node_id"]

    def _start_replica(self, ds: _DeploymentState) -> None:
        import ray_tpu

        opts = dict(ds.info["config"].ray_actor_options or {})
        actor_opts = {"num_cpus": opts.get("num_cpus", 1)}
        if opts.get("num_tpus"):
            actor_opts["num_tpus"] = opts["num_tpus"]
        # replicas serve concurrent requests up to max_ongoing_requests
        # (threaded actor) — the replica-side half of admission control: the
        # runtime caps executing user requests at moq, excess queues in the
        # mailbox. Control RPCs (health/drain/fault-arming) run on their own
        # unbounded group so a saturated replica still answers the controller.
        moq = ds.info["config"].max_ongoing_requests
        actor_opts["max_concurrency"] = max(1, moq or 1)
        actor_opts["concurrency_groups"] = {"control": 0}
        node_id = self._choose_replica_node(ds, actor_opts["num_cpus"])
        if node_id is not None:
            from ray_tpu.core.task_spec import NodeAffinitySchedulingStrategy

            # soft: if the chosen node fills up meanwhile, fall through rather
            # than wedging the deployment
            actor_opts["scheduling_strategy"] = NodeAffinitySchedulingStrategy(
                node_id=node_id, soft=True)
        from .replica import Replica

        cls = ray_tpu.remote(**actor_opts)(Replica)
        actor = cls.remote(ds.name, ds.info["serialized_init"],
                           ds.info["config"].user_config,
                           app_name=ds.app_name,
                           max_ongoing_requests=max(0, moq or 0))
        r = _ReplicaState(actor, ds.info["config"].version)
        r.node_id = node_id
        r.health_ref = actor.check_health.remote()
        ds.replicas.append(r)

    def _stop_replica(self, r: _ReplicaState) -> None:
        import ray_tpu

        try:
            r.actor.prepare_shutdown.remote()
            ray_tpu.kill(r.actor, no_restart=True)
        # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
        except Exception:
            pass

    def _autoscale(self, ds: _DeploymentState, now: float) -> None:
        ac = ds.info["config"].autoscaling_config
        if ac is None:
            return
        if self._ac_mode(ds) == "slo":
            # the head-side SLO loop owns this deployment's target
            # (set_autoscale_target); the request-rate rule stepping on it
            # would thrash the replica count between two masters
            return
        desired = ds.autoscale_metric / max(ac.target_ongoing_requests, 1e-6)
        import math

        desired = int(math.ceil(desired))
        desired = max(ac.min_replicas, min(ac.max_replicas, desired))
        if desired > ds.target_num and now - ds._last_scale_change >= ac.upscale_delay_s:
            ds.target_num = desired
            ds._last_scale_change = now
        elif desired < ds.target_num and now - ds._last_scale_change >= ac.downscale_delay_s:
            ds.target_num = desired
            ds._last_scale_change = now

    def _reconcile_once(self) -> None:
        import ray_tpu

        now = time.time()
        with self._lock:
            states = list(self.deployments.values())
        for ds in states:
            with self._lock:
                self._autoscale(ds, now)
                # promote STARTING replicas whose health check came back
                for r in ds.replicas:
                    if r.state == STARTING and r.health_ref is not None:
                        done, _ = ray_tpu.wait([r.health_ref], num_returns=1, timeout=0)
                        if done:
                            try:
                                ray_tpu.get(r.health_ref)
                                r.state = RUNNING
                                r.last_health_ok = now
                                r.health_ref = None
                                ds.start_failures, ds.start_error = 0, None
                            except Exception as e:
                                if _is_head_unavailable(e):
                                    # control-plane outage, not replica death:
                                    # the reply died with the old head. The
                                    # replica process is untouched — ask again
                                    # instead of replacing a healthy worker.
                                    r.last_health_ok = now
                                    r.health_ref = r.actor.check_health.remote()
                                else:
                                    logger.warning(
                                        "%s replica #%s failed its startup health "
                                        "check (%r); replacing it", ds.name, r.uid, e)
                                    r.state = STOPPING
                                    r.health_ref = None
                                    ds.start_failures += 1
                                    ds.start_error = repr(e)
                # periodic health checks on RUNNING replicas
                period = ds.info["config"].health_check_period_s
                for r in ds.replicas:
                    if r.state == RUNNING and r.health_ref is None and now - r.last_health_ok > period:
                        r.health_ref = r.actor.check_health.remote()
                    elif r.state == RUNNING and r.health_ref is not None:
                        done, _ = ray_tpu.wait([r.health_ref], num_returns=1, timeout=0)
                        if done:
                            try:
                                ray_tpu.get(r.health_ref)
                                r.last_health_ok = now
                            except Exception as e:
                                if _is_head_unavailable(e):
                                    # inconclusive: the head blinked, the
                                    # replica didn't. Grant outage grace and
                                    # re-check a full period from now.
                                    r.last_health_ok = now
                                else:
                                    logger.warning(
                                        "%s replica #%s failed its health check "
                                        "(%r); replacing it", ds.name, r.uid, e)
                                    r.state = STOPPING
                            r.health_ref = None
                        elif now - r.last_health_ok > period + ds.info["config"].health_check_timeout_s:
                            r.state = STOPPING
                            r.health_ref = None
                # DRAINING: poll in-flight; drained (or past deadline) -> STOPPING
                for r in [x for x in ds.replicas if x.state == DRAINING]:
                    if r.drain_ref is None:
                        try:
                            r.drain_ref = r.actor.num_inflight.remote()
                        # graftlint: allow[swallowed-exception] degrades to the coded fallback (r.state = STOPPING) by design
                        except Exception:
                            r.state = STOPPING  # handle unusable: reap now
                            continue
                    done, _ = ray_tpu.wait([r.drain_ref], num_returns=1, timeout=0)
                    if done:
                        try:
                            n = ray_tpu.get(r.drain_ref)
                        # graftlint: allow[swallowed-exception] degrades to the coded fallback (n = 0) by design
                        except Exception:
                            n = 0  # replica died mid-drain: nothing left to wait on
                        r.drain_ref = None
                        if n == 0:
                            r.state = STOPPING
                    if r.state == DRAINING and r.drain_deadline is not None \
                            and now > r.drain_deadline:
                        r.state = STOPPING  # grace burned: kill anyway
                # remove STOPPING
                for r in [x for x in ds.replicas if x.state == STOPPING]:
                    self._stop_replica(r)
                    ds.replicas.remove(r)
                # scale to target: count live (non-stopping, non-draining)
                # replicas of the current version
                live = [r for r in ds.replicas if r.state in (STARTING, RUNNING)]
                if not ds.deleting:
                    for _ in range(ds.target_num - len(live)):
                        self._start_replica(ds)
                extra = len(live) - ds.target_num
                for r in reversed(live):
                    if extra <= 0:
                        break
                    if r.state == RUNNING or r.state == STARTING:
                        self._drain_replica(r, ds)  # graceful scale-down
                        extra -= 1
        # reap deployments whose drain-down finished (app already deleted)
        with self._lock:
            for key in [k for k, ds in self.deployments.items()
                        if ds.deleting and not ds.replicas]:
                del self.deployments[key]

    def _reconcile_loop(self) -> None:
        while not self._shutdown:
            try:
                self._reconcile_once()
            except Exception as e:
                if self._loop_warn.ready("reconcile"):
                    logger.warning("serve reconcile pass failed (suppressed "
                                   "for 30s): %r", e)
            try:
                # never skipped: a throwing reconcile pass (e.g. one poisoned
                # deployment) must not silence membership publishing for the rest
                self._publish_changes()
            except Exception as e:
                if self._loop_warn.ready("publish"):
                    logger.warning("serve long-poll publish failed "
                                   "(suppressed for 30s): %r", e)
            from ray_tpu.config import CONFIG as _CFG

            time.sleep(_CFG.serve_reconcile_interval_s)

    # -- long-poll host (reference LongPollHost) --------------------------------
    def _publish_changes(self) -> None:
        """Bump versions for deployments whose running replica set changed."""
        t0 = time.perf_counter()
        with self._lock:
            snapshots = {
                key: tuple(r.uid for r in ds.running())
                for key, ds in self.deployments.items()
            }
        changed = [k for k, snap in snapshots.items() if self._lp_last_running.get(k) != snap]
        gone = [k for k in self._lp_last_running if k not in snapshots]
        if not changed and not gone:
            return
        with self._lp_cond:
            for k in changed:
                self._lp_last_running[k] = snapshots[k]
                self._lp_versions[f"replicas::{k}"] = self._lp_versions.get(f"replicas::{k}", 0) + 1
            for k in gone:
                self._lp_last_running.pop(k, None)
                self._lp_versions[f"replicas::{k}"] = self._lp_versions.get(f"replicas::{k}", 0) + 1
            self._lp_versions["routes"] = self._lp_versions.get("routes", 0) + 1
            self._lp_cond.notify_all()
        # control-plane self-telemetry: long-poll fan-out cost (snapshot diff
        # + version bumps + waking every parked listener)
        from ray_tpu.util import telemetry as _tel

        _tel.get_histogram(
            "control_decision_seconds",
            "wall time of one control-loop decision pass, by loop",
            tag_keys=("loop",),
        ).observe(time.perf_counter() - t0, tags={"loop": "serve_publish"})

    @_actor_method(concurrency_group="listen")
    def listen_for_change(self, keys_to_versions: Dict[str, int],
                          timeout_s: float = 10.0) -> Dict[str, Any]:
        """Block until any watched key's version differs from the caller's view;
        returns {key: (new_version, snapshot)} ({} on timeout). Runs on the
        unbounded "listen" concurrency group (see serve/api.py) so parked
        listeners never starve deploy/reconcile APIs on the default pool."""
        deadline = time.monotonic() + timeout_s
        with self._lp_cond:
            while not self._shutdown:
                changed = {
                    k: self._lp_versions.get(k, 0)
                    for k, v in keys_to_versions.items()
                    if self._lp_versions.get(k, 0) != v
                }
                if changed:
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return {}
                self._lp_cond.wait(remaining)
            else:
                return {}
        return {k: (ver, self._lp_snapshot(k)) for k, ver in changed.items()}

    def _lp_snapshot(self, key: str) -> Any:
        kind, _, ident = key.partition("::")
        if kind == "replicas":
            app, _, dep = ident.partition("/")
            with self._lock:
                if f"{app}/{dep}" not in self.deployments:
                    return None  # deleted: listeners stop watching this key
            return self.get_replicas(app, dep)
        if key == "routes":
            return self.get_routing_table()
        return None
