"""Device-mesh construction.

A `MeshSpec` is the single declarative knob for every parallelism strategy the framework
supports — data (dp), fully-sharded data (fsdp), tensor (tp), sequence/context (sp),
pipeline (pp), expert (ep). The reference framework reaches the same goals with NCCL
process groups per strategy (reference: python/ray/util/collective/collective.py:150,
python/ray/train/torch/config.py:66); on TPU a single mesh + NamedSharding per array is
the idiomatic equivalent, and XLA chooses the collectives.

Axis order matters on TPU: later (minor) axes map to physically-adjacent devices, so put
the most bandwidth-hungry axis (tp, then sp) last so its collectives ride ICI.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical axis order: most-major (cross-slice / DCN friendly) → most-minor (ICI).
AXIS_ORDER: Tuple[str, ...] = ("pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape. Size -1 on at most one axis means "use all remaining".

    Examples:
        MeshSpec(dp=-1)                      # pure data parallel
        MeshSpec(fsdp=-1, tp=4)              # FSDP with 4-way tensor parallel
        MeshSpec(dp=2, sp=2, tp=2)           # 8-chip mixed
    """

    pp: int = 1
    dp: int = 1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def sizes(self) -> Tuple[int, ...]:
        return tuple(getattr(self, a) for a in AXIS_ORDER)

    def resolve(self, n_devices: int) -> "MeshSpec":
        """Fill in a single -1 axis so the product equals n_devices."""
        sizes = list(self.sizes())
        wild = [i for i, s in enumerate(sizes) if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {self}")
        fixed = math.prod(s for s in sizes if s != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(f"mesh {self} needs {fixed} devices, have {n_devices}")
        return MeshSpec(**dict(zip(AXIS_ORDER, sizes)))

    @property
    def n_devices(self) -> int:
        return math.prod(self.sizes())


def build_mesh(
    spec: MeshSpec,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a `jax.sharding.Mesh` from a spec over the given (or all) devices.

    Keeps every axis in the mesh even if size 1 — downstream PartitionSpecs can then
    name any axis unconditionally, and XLA elides the trivial collectives.
    """
    if devices is None:
        devices = jax.devices()
    spec = spec.resolve(len(devices))
    arr = np.asarray(devices).reshape(spec.sizes())
    return Mesh(arr, AXIS_ORDER)


def local_mesh(**axes: int) -> Mesh:
    """Convenience: build_mesh(MeshSpec(**axes)) over all visible devices."""
    return build_mesh(MeshSpec(**axes))


# this package's name for installing `mesh` as the ambient mesh
use_mesh = jax.sharding.set_mesh
