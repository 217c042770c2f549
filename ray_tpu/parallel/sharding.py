"""Logical-axis sharding rules.

Model code annotates arrays with *logical* axis names ("batch", "embed", "heads", …);
a single `AxisRules` table maps logical names to mesh axes. Changing the parallelism
strategy = changing the table, not the model. This is the GSPMD idiom the reference
delegates to external libraries (FSDP/DeepSpeed — SURVEY.md §2.3) but is native here.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LogicalAxis = Optional[str]
MeshAxes = Union[None, str, Tuple[str, ...]]


class AxisRules:
    """Mapping logical axis name → mesh axis (or tuple of mesh axes, or None)."""

    def __init__(self, rules: Dict[str, MeshAxes]):
        self.rules = dict(rules)

    def __getitem__(self, logical: LogicalAxis) -> MeshAxes:
        if logical is None:
            return None
        return self.rules.get(logical)

    def spec(self, logical_axes: Sequence[LogicalAxis]) -> P:
        return P(*(self[a] for a in logical_axes))


# Default rules for transformer training. Parameter axes ("embed"/"mlp"/"heads"/"vocab")
# and activation axes ("act_*") are distinct logical names because a PartitionSpec may not
# reuse a mesh axis: batch → (dp, fsdp) shards activations ZeRO-style while embed → fsdp
# shards parameters, and the two never appear on the same array.
TRAIN_RULES = AxisRules(
    {
        # parameters
        "embed": "fsdp",
        "heads": "tp",
        "kv_heads": "tp",
        "head_dim": None,
        "mlp": "tp",
        "vocab": "tp",
        "expert": "ep",
        "stage": "pp",
        # activations
        "batch": ("dp", "fsdp"),
        "seq": "sp",
        "act_embed": None,
        "act_heads": "tp",
        "act_kv_heads": "tp",
        "act_mlp": "tp",
        "act_vocab": "tp",
        "act_expert": "ep",
    }
)

# Inference: params replicated across dp, sharded over tp; KV cache sharded over heads
# (tp) and batch (dp).
INFER_RULES = AxisRules(
    {
        "embed": None,
        "heads": "tp",
        "kv_heads": "tp",
        "head_dim": None,
        "mlp": "tp",
        "vocab": "tp",
        "expert": "ep",
        "stage": "pp",
        "batch": "dp",
        "seq": "sp",
        "act_embed": None,
        "act_heads": "tp",
        "act_kv_heads": "tp",
        "act_mlp": "tp",
        "act_vocab": "tp",
        "act_expert": "ep",
    }
)


def logical_to_mesh_axes(
    logical_axes: Sequence[LogicalAxis], rules: AxisRules = TRAIN_RULES
) -> P:
    return rules.spec(logical_axes)


def named_sharding(
    mesh: Mesh, *logical_axes: LogicalAxis, rules: AxisRules = TRAIN_RULES
) -> NamedSharding:
    return NamedSharding(mesh, rules.spec(logical_axes))


def shard_pytree(tree, axes_tree, mesh: Mesh, rules: AxisRules = TRAIN_RULES):
    """device_put a pytree according to a parallel tree of logical-axes tuples.

    `axes_tree` mirrors `tree`; each leaf is a tuple of logical axis names (or None)
    matching the array rank.
    """

    def _put(x, axes):
        return jax.device_put(x, _as_jit_returns(named_sharding(mesh, *axes, rules=rules)))

    return jax.tree.map(_put, tree, axes_tree, is_leaf=lambda x: x is None)


def _as_jit_returns(sharding: NamedSharding) -> NamedSharding:
    """The same sharding, spelled as jit spells the shardings of a program's
    outputs: no mesh axis of size one, no trailing None. jit keys its compiled
    programs by the spelling, so a state placed any other way compiles its
    step twice: once for the state as placed, once for the state the first
    step returned."""
    sizes = sharding.mesh.shape

    def keep(entry):
        if entry is None or isinstance(entry, str):
            return entry if entry is not None and sizes[entry] > 1 else None
        kept = tuple(a for a in entry if sizes[a] > 1)
        return kept or None

    spec = [keep(e) for e in sharding.spec]
    while spec and spec[-1] is None:
        spec.pop()
    return NamedSharding(sharding.mesh, P(*spec), memory_kind=sharding.memory_kind)


_MANUAL_AXES: "contextvars.ContextVar[frozenset]" = None  # initialized below


def ambient_mesh():
    """The abstract mesh installed by `use_mesh`; None when no mesh is active."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def auto_spec(*logical_axes: LogicalAxis, rules: AxisRules = TRAIN_RULES) -> Optional[P]:
    """`logical_axes` as a PartitionSpec over the ambient mesh, keeping only
    the mesh axes it has that are still automatic; None without a mesh.

    Mesh axes currently bound manually (inside a shard_map region entered via
    `manual_axes()`) are dropped — GSPMD may only constrain auto axes, and a
    nested shard_map may only split over them."""
    mesh = ambient_mesh()
    if mesh is None:
        return None
    manual = active_manual_axes() | frozenset(mesh.manual_axes)

    def _filt(entry):
        names = entry if isinstance(entry, tuple) else (entry,) if entry else ()
        kept = tuple(a for a in names if a in mesh.shape and a not in manual)
        return None if not kept else kept if isinstance(entry, tuple) else kept[0]

    return P(*(_filt(e) for e in rules.spec(logical_axes)))


def with_sharding_constraint(x, *logical_axes: LogicalAxis, rules: AxisRules = TRAIN_RULES):
    """In-jit sharding hint using logical names. No-op outside jit or without a mesh."""
    spec = auto_spec(*logical_axes, rules=rules)
    return x if spec is None else jax.lax.with_sharding_constraint(x, spec)


# -- manual-axes context ---------------------------------------------------------------
# shard_map callees (pipeline stages, ring attention) trace model code while some mesh
# axes are manual; with_sharding_constraint must not reference those. Code entering a
# manual region wraps the trace in `with manual_axes("pp", "sp"): ...`.
import contextlib as _contextlib
import contextvars as _contextvars

_MANUAL_AXES = _contextvars.ContextVar("ray_tpu_manual_axes", default=frozenset())


def active_manual_axes() -> frozenset:
    return _MANUAL_AXES.get()


def partitioned_by_gspmd() -> bool:
    """Whether an ambient mesh leaves an axis of more than one device to GSPMD: a Pallas
    call there needs a `shard_map` around it (ops/attention.py) or gives way to the plain
    form (ops/kda.py, ops/short_conv.py)."""
    mesh = jax.sharding.get_abstract_mesh()
    return any(mesh.shape[a] > 1 for a in set(mesh.axis_names) - set(mesh.manual_axes))


@_contextlib.contextmanager
def manual_axes(*names: str):
    token = _MANUAL_AXES.set(_MANUAL_AXES.get() | frozenset(names))
    try:
        yield
    finally:
        _MANUAL_AXES.reset(token)


def vary_like(z, ref=None, *, extra: Sequence[str] = ()):
    """Cast `z` to vary over the manual axes `ref` varies over, plus `extra`.

    The shard_map vma type system requires loop carries/inits to match the body's
    varying-axes set; this is the one shared implementation of the
    pcast-to-varying idiom. ref=None means "just `extra`".
    """
    want = set(extra)
    if ref is not None:
        want |= set(jax.typeof(ref).vma)
    need = tuple(want - set(jax.typeof(z).vma))
    if not need:
        return z
    return jax.lax.pcast(z, need, to="varying")
