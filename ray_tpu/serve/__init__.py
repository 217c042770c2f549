"""ray_tpu.serve: scalable model serving over the actor runtime.

Capability parity: reference python/ray/serve/ — @serve.deployment / serve.run
(api.py:322,691), ServeController reconciliation (controller.py:88), replica state
machine + rolling updates (deployment_state.py), power-of-two-choices handle router
(request_router/pow_2_router.py:27), aiohttp ingress proxy (proxy.py), @serve.batch
dynamic batching (batching.py), request-rate autoscaling (autoscaling_policy.py).
"""
from .api import (  # noqa: F401
    DeploymentStartError,
    delete,
    get_app_handle,
    get_deployment_handle,
    run,
    shutdown,
    start,
    status,
)
from .batching import batch  # noqa: F401
from .multiplex import get_multiplexed_model_id, multiplexed  # noqa: F401
from .schema import apply_config, apply_config_file  # noqa: F401
from .config import AutoscalingConfig, DeploymentConfig  # noqa: F401
from .deployment import Application, Deployment, deployment  # noqa: F401
from .handle import DeploymentHandle, DeploymentResponse  # noqa: F401
from .asgi import ingress  # noqa: F401

__all__ = [
    "deployment",
    "Deployment",
    "Application",
    "run",
    "start",
    "delete",
    "status",
    "shutdown",
    "get_app_handle",
    "get_deployment_handle",
    "DeploymentHandle",
    "DeploymentResponse",
    "DeploymentStartError",
    "AutoscalingConfig",
    "DeploymentConfig",
    "batch",
    "ingress",
    "multiplexed",
    "get_multiplexed_model_id",
    "apply_config",
    "apply_config_file",
]
