"""serve public API: run/delete/status/shutdown/handles.

Capability parity: reference python/ray/serve/api.py (serve.run :691) +
_private/api.py serve_start — get-or-create controller actor, deploy application
graphs, proxy bring-up, handle acquisition.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, Optional

import ray_tpu

from .controller import CONTROLLER_NAME, ServeController
from .deployment import Application, Deployment
from .handle import DeploymentHandle

logger = logging.getLogger(__name__)

_PROXY_NAME = "SERVE_PROXY"


@dataclasses.dataclass
class _HandleMarker:
    app_name: str
    deployment_name: str


def _get_or_create_controller():
    # every serve entry point keeps the head-side autoscaling loop alive (it
    # no-ops off the head process and when already running); the head-restart
    # reattach path restarts it independently (core/node.py)
    from .autoscaler import ensure_serve_autoscaler

    ensure_serve_autoscaler()
    try:
        return ray_tpu.get_actor(CONTROLLER_NAME)
    except ValueError:
        # listen_for_change parks one call per connected handle/proxy process for
        # up to 10s; an unbounded "listen" concurrency group keeps any number of
        # parked listeners from starving deploy/reconcile/health RPCs, which run
        # on the default pool
        cls = ray_tpu.remote(num_cpus=0.1, name=CONTROLLER_NAME, lifetime="detached",
                             max_concurrency=16,
                             concurrency_groups={"listen": 0})(ServeController)
        handle = cls.remote()
        ray_tpu.get(handle.ping.remote())
        return handle


def start(http_options: Optional[Dict[str, Any]] = None,
          grpc_options: Optional[Dict[str, Any]] = None, **_compat) -> Optional[Dict[str, Any]]:
    """Bring up controller + ingress proxies (reference serve.start): HTTP
    always, gRPC when grpc_options is given (reference gRPCProxy). Returns
    {"grpc_port": N} when the gRPC ingress is up (port 0 = ephemeral bind)."""
    _get_or_create_controller()
    http_options = http_options or {}
    try:
        ray_tpu.get_actor(_PROXY_NAME)
    except ValueError:
        from .proxy import ProxyActor

        cls = ray_tpu.remote(num_cpus=0.1, name=_PROXY_NAME, lifetime="detached")(ProxyActor)
        proxy = cls.remote(http_options.get("host", "127.0.0.1"), http_options.get("port", 8000))
        ray_tpu.get(proxy.ready.remote())
    if grpc_options is not None:
        from .grpc_proxy import start_grpc_proxy

        _, port = start_grpc_proxy(
            grpc_options.get("host", "127.0.0.1"),
            grpc_options.get("port", 9000),
            grpc_options.get("grpc_servicer_functions"))
        return {"grpc_port": port}
    return None


def run(
    target: Application,
    *,
    name: str = "default",
    route_prefix: str = "/",
    blocking: bool = False,
    _local_testing_mode: bool = False,
    **_compat,
) -> DeploymentHandle:
    """Deploy an application graph; returns the ingress handle (reference api.py:691).

    _local_testing_mode=True runs the whole graph in-process with no cluster
    (reference _private/local_testing_mode.py)."""
    from ray_tpu.usage import record_library_usage

    record_library_usage("serve")
    if isinstance(target, Deployment):
        target = target.bind()
    if not isinstance(target, Application):
        raise TypeError("serve.run expects an Application (deployment.bind(...))")
    if _local_testing_mode:
        from .local_testing import run_local

        return run_local(target)
    controller = _get_or_create_controller()

    apps: list = []
    target._collect(apps)

    def encode(value):
        if isinstance(value, Application):
            return _HandleMarker(name, value.deployment.name)
        return value

    payload = []
    for bound in apps:
        payload.append({
            "name": bound.deployment.name,
            "serialized_init": {
                "target": bound.deployment._target,
                "args": tuple(encode(a) for a in bound.args),
                "kwargs": {k: encode(v) for k, v in bound.kwargs.items()},
            },
            "config": bound.deployment.config,
            "is_ingress": bound is target,
        })
    ray_tpu.get(controller.deploy_application.remote(name, route_prefix, payload))
    _wait_until_running(controller, name)
    return DeploymentHandle(name, target.deployment.name)


class DeploymentStartError(RuntimeError):
    """A deployment's replicas kept failing their start; `serve.run` gave up."""


# failed replica starts in a row after which serve.run gives a deployment up
_MAX_START_FAILURES = 3


def _wait_until_running(controller, name: str) -> None:
    """Block until every deployment of app `name` has a running replica, so
    that a request sent the moment `run` returns finds one (reference:
    serve.run waits for the application to be RUNNING). A replica that is
    still starting is waited for as long as it takes, as the reference does —
    an LLM replica makes or loads its weights for tens of seconds — and named
    in the log meanwhile; a deployment whose replicas fail their start
    `_MAX_START_FAILURES` times in a row raises."""
    import time

    started = said = time.monotonic()
    while True:
        deployments = ray_tpu.get(controller.status.remote())[name]["deployments"]
        waiting = {d: info for d, info in deployments.items()
                   if info["num_running"] < 1}
        if not waiting:
            return
        for d, info in waiting.items():
            if info["start_failures"] >= _MAX_START_FAILURES:
                raise DeploymentStartError(
                    f"app {name!r}: deployment {d!r} failed to start "
                    f"{info['start_failures']} times in a row; last error: "
                    f"{info['start_error']}")
        now = time.monotonic()
        if now - said >= 30.0:
            said = now
            logger.info("serve.run(%r): %.0f s, still starting: %s", name, now - started,
                        {d: info["states"] for d, info in waiting.items()})
        time.sleep(0.1)


def delete(name: str, _blocking: bool = True) -> None:
    controller = _get_or_create_controller()
    ray_tpu.get(controller.delete_application.remote(name))


def status() -> Dict[str, Any]:
    controller = _get_or_create_controller()
    return ray_tpu.get(controller.status.remote())


def get_app_handle(name: str) -> DeploymentHandle:
    controller = _get_or_create_controller()
    st = ray_tpu.get(controller.status.remote())
    if name not in st:
        raise ValueError(f"no app named {name!r}")
    table = ray_tpu.get(controller.get_routing_table.remote())
    for info in table.values():
        if info["app"] == name:
            return DeploymentHandle(name, info["deployment"])
    raise ValueError(f"app {name!r} has no ingress")


def get_deployment_handle(deployment_name: str, app_name: str = "default") -> DeploymentHandle:
    return DeploymentHandle(app_name, deployment_name)


def shutdown() -> None:
    from .autoscaler import shutdown_serve_autoscaler
    from .handle import _reset_long_poll

    shutdown_serve_autoscaler()  # before the controller: no scale RPCs mid-kill
    try:
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        ray_tpu.get(controller.shutdown.remote())
        ray_tpu.kill(controller)
    # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
    except Exception:
        pass
    try:
        proxy = ray_tpu.get_actor(_PROXY_NAME)
        ray_tpu.kill(proxy)
    # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
    except Exception:
        pass
    try:
        from .grpc_proxy import _GRPC_PROXY_NAME

        gproxy = ray_tpu.get_actor(_GRPC_PROXY_NAME)
        ray_tpu.get(gproxy.stop.remote())
        ray_tpu.kill(gproxy)
    # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
    except Exception:
        pass
    _reset_long_poll()  # watches reference the controller we just killed
