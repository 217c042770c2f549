"""ray_tpu.train: Train-API-shaped distributed training on TPU.

Reference capability: python/ray/train/ (SURVEY.md §2.4). The `JaxTrainer` here is the
north-star API the reference lacks (no JaxTrainer exists upstream — SURVEY.md §2.4 note).

Public surface mirrors ray.train: report/get_context/get_checkpoint/get_dataset_shard
inside the worker loop; JaxTrainer(...).fit() on the driver; ScalingConfig/RunConfig etc.
re-exported from ray_tpu.air.
"""
from ..air.config import (  # noqa: F401
    CheckpointConfig,
    FailureConfig,
    RunConfig,
    ScalingConfig,
)
from .backend import Backend, BackendConfig  # noqa: F401
from .checkpoint import Checkpoint  # noqa: F401
from .data_parallel_trainer import (  # noqa: F401
    DataParallelTrainer,
    JaxTrainer,
    TensorflowTrainer,
    TorchTrainer,
)
from .jax_backend import JaxBackend, JaxConfig  # noqa: F401
from .torch_backend import TorchBackend, TorchConfig  # noqa: F401
from .tensorflow_backend import TensorflowBackend, TensorflowConfig  # noqa: F401
from .gbdt import (  # noqa: F401  (optional-dep GBDT family)
    LightGBMConfig,
    LightGBMTrainer,
    XGBoostConfig,
    XGBoostTrainer,
)
from . import gbdt as xgboost  # noqa: F401
from . import gbdt as lightgbm  # noqa: F401
from . import huggingface  # noqa: F401
from . import lightning  # noqa: F401
from . import torch_backend as torch  # noqa: F401  (ray_tpu.train.torch.prepare_model)

# reference import shapes: `from ray_tpu.train.torch import prepare_model`,
# `from ray_tpu.train.xgboost import get_rabit_args`, ...
import sys as _sys

_sys.modules[__name__ + ".torch"] = torch
_sys.modules[__name__ + ".xgboost"] = xgboost
_sys.modules[__name__ + ".lightgbm"] = lightgbm
from .result import Result  # noqa: F401
from .session import (  # noqa: F401
    TrainContext,
    get_checkpoint,
    get_context,
    get_dataset_shard,
    metrics,
    report,
    step_phase,
)
from .step import TrainState, init_state, make_optimizer, make_train_step  # noqa: F401
from .diffusion import block_diffusion_noise  # noqa: F401
from . import grad_sync  # noqa: F401
from .grad_sync import GradSyncConfig  # noqa: F401
from . import mpmd_pipeline  # noqa: F401
from .mpmd_pipeline import (  # noqa: F401  (cross-process MPMD pipeline runner)
    MPMDPipeline,
    MPMDPipelineConfig,
    StageRunner,
    stage_runner_from_train_context,
)
from .v2 import (  # noqa: F401  (Train v2: controller + policies, SURVEY §2.4)
    DefaultFailurePolicy,
    ElasticScalingPolicy,
    FailureDecision,
    FailurePolicy,
    FixedScalingPolicy,
    ResizeDecision,
    ScalingPolicy,
    TrainController,
    TrainControllerState,
)
