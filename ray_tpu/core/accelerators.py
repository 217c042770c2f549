"""Accelerator managers: TPU topology detection + visibility control.

Capability parity: reference python/ray/_private/accelerators/ — the
`AcceleratorManager` ABC (accelerator.py) and `TPUAcceleratorManager` (tpu.py:110):
chip detection, `TPU_VISIBLE_CHIPS` (tpu.py:118-122), pod-type resources like
"TPU-v5e-8-head" (tpu.py:376) so slice-spanning placement groups can reserve a
whole pod slice atomically. GPU managers are intentionally absent: no GPU
anywhere in the loop (BASELINE.md).

Detection sources, in order: explicit env overrides (TPU_VISIBLE_CHIPS /
TPU_CHIPS_PER_HOST), the TPU runtime's env (set on GCE TPU-VMs), and finally the
device files the TPU driver exposes (what the reference's manager counts). JAX is
never asked: a chip belongs to one process at a time, so a driver that queried
its own JAX would hold the chip its workers need.
"""
from __future__ import annotations

import glob
import os
import sys
from dataclasses import dataclass
from typing import Dict, Optional, Sequence

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
# Where the TPU driver exposes one file per chip: /dev/accel<N> (accel driver)
# or a numbered IOMMU group under /dev/vfio (vfio driver; /dev/vfio/vfio is the
# container node, not a chip). Module-level so tests can point them elsewhere.
ACCEL_DEVICE_GLOB = "/dev/accel*"
VFIO_DEVICE_DIR = "/dev/vfio"


# chips held -> TPU_CHIPS_PER_HOST_BOUNDS of a process that holds part of a host
_SUB_HOST_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


class TPUPlatformError(RuntimeError):
    """A worker that was given TPU chips came up on another JAX platform."""


def _count_device_file_chips() -> int:
    accel = [f for f in glob.glob(ACCEL_DEVICE_GLOB) if f[-1].isdigit()]
    if accel:
        return len(accel)
    try:
        return len([e for e in os.listdir(VFIO_DEVICE_DIR) if e.isdigit()])
    except OSError:
        return 0


@dataclass
class TPUInfo:
    chips_per_host: int
    accelerator_type: str  # e.g. "v5e-8" (slice), "" if unknown
    worker_id: int  # host index within the slice
    num_hosts: int

    @property
    def pod_head_resource(self) -> Optional[str]:
        """The reference's `TPU-{pod}-head` trick: worker 0 of a slice carries one
        unit so a slice-wide placement group anchors atomically (tpu.py:376)."""
        if self.accelerator_type and self.worker_id == 0:
            return f"TPU-{self.accelerator_type}-head"
        return None


class TPUAcceleratorManager:
    """TPU detection + resource shaping (reference tpu.py:110)."""

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        visible = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
        if visible is not None:
            return len([c for c in visible.split(",") if c.strip() != ""])
        env_chips = os.environ.get("TPU_CHIPS_PER_HOST")
        if env_chips:
            return int(env_chips)
        chips = _count_device_file_chips()
        if chips:
            return chips
        # TPU-VM runtime convention: bounds like "2,2,1" = 4 chips on this host.
        # Read after the device files: the bounds name the host TYPE, and a
        # machine that exposes fewer chips than its type has only those.
        bounds = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS")
        if bounds:
            n = 1
            for part in bounds.split(","):
                n *= int(part)
            return n
        return 0

    @staticmethod
    def get_current_node_accelerator_type() -> str:
        t = os.environ.get("TPU_ACCELERATOR_TYPE", "")
        return t

    @staticmethod
    def detect() -> Optional[TPUInfo]:
        chips = TPUAcceleratorManager.get_current_node_num_accelerators()
        if chips <= 0:
            return None
        return TPUInfo(
            chips_per_host=chips,
            accelerator_type=TPUAcceleratorManager.get_current_node_accelerator_type(),
            worker_id=int(os.environ.get("TPU_WORKER_ID", "0")),
            num_hosts=int(os.environ.get("TPU_WORKER_HOSTNAMES", "").count(",") + 1
                          if os.environ.get("TPU_WORKER_HOSTNAMES") else 1),
        )

    @staticmethod
    def visible_chips_env(chip_ids: Sequence[int], chips_on_host: int) -> Dict[str, str]:
        """Environment that restricts a process to `chip_ids` (reference
        TPU_VISIBLE_CHIPS plus the bounds the runtime needs to form a
        sub-host topology). It has to be in place before the process imports
        JAX. A worker that holds the whole host gets none: the runtime's own
        environment already describes it."""
        if len(chip_ids) >= chips_on_host:
            return {}
        env = {TPU_VISIBLE_CHIPS_ENV: ",".join(str(c) for c in chip_ids)}
        bounds = _SUB_HOST_BOUNDS.get(len(chip_ids))
        if bounds:
            env["TPU_CHIPS_PER_HOST_BOUNDS"] = bounds
            env["TPU_HOST_BOUNDS"] = "1,1,1"
        return env

    @staticmethod
    def node_resources() -> Dict[str, float]:
        """Resources this node should advertise for its TPUs."""
        info = TPUAcceleratorManager.detect()
        if info is None:
            return {}
        out: Dict[str, float] = {"TPU": float(info.chips_per_host)}
        head = info.pod_head_resource
        if head:
            out[head] = 1.0
        if info.accelerator_type:
            out[f"accelerator_type:TPU-{info.accelerator_type.split('-')[0].upper()}"] = 1.0
        return out


COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def ensure_compile_cache_dir() -> str:
    """Place JAX's persistent compilation cache for this process and every
    worker it spawns (they inherit the environment). A directory given from
    outside through JAX_COMPILATION_CACHE_DIR is left alone; otherwise it is
    `.jax_cache` next to the package — the path is part of the cache key, so
    it never depends on a pid, a time or a temporary name."""
    path = os.environ.get(COMPILE_CACHE_ENV)
    if not path:
        root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        path = os.path.join(root, ".jax_cache")
        os.environ[COMPILE_CACHE_ENV] = path
    jax = sys.modules.get("jax")
    if jax is not None and jax.config.jax_compilation_cache_dir != path:
        # jax read its environment when it was imported (a spawned worker
        # re-imports the driver's __main__ before worker_main runs)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def jax_backend_untouched() -> bool:
    """True while this process has initialised no JAX backend (importing jax
    does not). A driver must be in this state when it starts the workers that
    need the chip: a parent that has touched JAX holds it."""
    if "jax" not in sys.modules:
        return True
    from jax._src import xla_bridge

    return not xla_bridge.backends_are_initialized()


def jax_platforms_exclude_tpu() -> bool:
    """True when JAX_PLATFORMS names platforms and "tpu" is not among them
    (CPU test clusters schedule fake TPU resources that way, on purpose)."""
    asked = [p.strip().lower() for p in os.environ.get("JAX_PLATFORMS", "").split(",")
             if p.strip()]
    return bool(asked) and "tpu" not in asked


def check_worker_platform() -> Optional[str]:
    """Called where a worker first needs its device. A worker that was given
    TPU chips but whose JAX came up elsewhere, although JAX_PLATFORMS did not
    ask for that, would compute on the host in silence: raise instead.
    Returns the platform, or None in a process that holds no chips (JAX is not
    touched there)."""
    from . import global_state

    ctx = global_state.try_worker()
    if getattr(ctx, "accel", "cpu") != "tpu" or jax_platforms_exclude_tpu():
        return None
    import jax

    platform = jax.default_backend()
    if platform != "tpu":
        raise TPUPlatformError(
            f"this worker holds TPU chips but JAX came up on {platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}): the "
            "chip is missing, or another process owns it")
    return platform
