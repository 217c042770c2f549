"""Device-plane gradient synchronization for data-parallel training.

The train step's remaining MFU lever (ROADMAP "Device-plane training
collectives"): the stock step expresses gradient sync implicitly — GSPMD
inserts one combined all-reduce after the whole backward — and the optimizer
state replicates across data-parallel replicas. This module makes the sync an
explicit, tunable stage with three composable pieces:

1. **Bucketed all-reduce** (`mode="bucketed"`): the grad pytree is partitioned
   into size-bounded buckets (`RAY_TPU_TRAIN_BUCKET_BYTES`) and each bucket is
   reduced by its own `jax.lax.pmean` over the `dp` mesh axis inside a
   `shard_map` manual region. Each bucket is an independent collective in the
   compiled HLO (`overlap_report` verifies reductions are not all sunk to the
   end), so XLA's scheduler can overlap bucket k's reduction with bucket k-1's
   optimizer math and with backward compute instead of serializing one
   monolithic all-reduce after the last gradient.

2. **On-device int8 block-quantized reduction** (`compression="int8"`): each
   rank quantizes its local bucket contribution with the block-scale scheme of
   `ops/quant.py` (device-side `quantize_blockwise`, EQuARX-style — arxiv
   2506.17615), all-gathers the int8 payload + f32 block scales over `dp`, and
   dequant-sums locally. Wire bytes per contribution drop from 4n (f32) to
   n + 4*ceil(n/block) (~3.9x at the default block of 1024). Optional
   stochastic rounding keeps the quantizer unbiased across steps.

   Accuracy contract (mirrors the host-plane int8 wire path from PR 1): per
   element, each rank's contribution carries absolute error <= amax_block/254
   (round-nearest) or <= amax_block/127 (stochastic), where amax_block is the
   max |grad| within that contribution's scale block; the reduced value's
   error is bounded by the mean of the per-rank bounds. f32 mode is bit-exact
   with the monolithic path; int8 is NOT bit-exact and is gated by loss-curve
   parity in `bench.py --grad-sync`. Leaves smaller than `min_quant_elems`
   skip quantization (scales would dominate the payload).

3. **Cross-replica sharded optimizer update** (`sharded_update=True`): the
   ZeRO-style weight-update sharding of arxiv 2004.13336. Grads are constrained
   to a per-leaf spec that extends the parameter sharding with the `(dp, fsdp)`
   axes (GSPMD lowers all-reduce + consumer slice to reduce-scatter), Adam
   state lives and updates shard-local (`optax.tree_map_params` walks the
   param-shaped moment leaves), and only the updated params are all-gathered
   back to their compute sharding. Per-chip optimizer HBM drops by the added
   sharding factor — the knob that lets dp x fsdp mixed meshes fit v5e HBM
   (see `__graft_entry__.hbm_budget_sharded_opt`).

Semantics notes:
- The explicit (bucketed) path computes grads per-dp-shard and averages them
  with `pmean`, which equals the monolithic global-mean gradient when every dp
  shard sees the same number of loss tokens (true for the repo's training
  paths; with a ragged `loss_mask` the shards are weighted equally instead of
  per-token).
- The explicit path owns ONLY the `dp` axis; fsdp/tp sharding stays in GSPMD
  "auto" mode inside the manual region, so it composes with the fsdp param
  sharding. It does not compose with model code that opens its own shard_map
  (pipeline_stages > 1, ring/ulysses attention) — `make_step` rejects those.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.util.hot_path import hot_path

from . import session

DEFAULT_BUCKET_BYTES = 4 << 20  # 4 MiB: ~8 buckets on a 500M-param f32 tree

_TRUE = ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


@dataclasses.dataclass(frozen=True)
class GradSyncConfig:
    """Gradient-sync strategy for `make_train_step` (env-overridable so the
    JaxTrainer backend can hand it to worker loops — see `JaxConfig.grad_sync`).

    mode: "gspmd" (default; the implicit monolithic sync — alias "monolithic")
        or "bucketed" (explicit per-bucket collectives, overlap-friendly).
    bucket_bytes: max payload per bucket (RAY_TPU_TRAIN_BUCKET_BYTES).
    compression: None (f32, bit-exact) or "int8" (block-quantized, see module
        docstring for the tolerance contract).
    stochastic_rounding: unbiased quantizer (int8 only).
    quant_block_elems: elements per int8 scale block.
    min_quant_elems: leaves smaller than this stay f32 even under int8.
    sharded_update: ZeRO-style cross-replica sharded optimizer update.
    update_axes: mesh axes the update shards over (on top of each param's own
        sharding); axes absent from the mesh or sized 1 are ignored.
    telemetry: time grad-sync phases (`train.step_phase` spans +
        `train_grad_sync_seconds{phase}`) by splitting the step into a grads
        stage and an update stage with per-bucket waits in between. Costs the
        grads/update fusion — leave off for headline MFU runs.
    """

    mode: str = "gspmd"
    bucket_bytes: int = DEFAULT_BUCKET_BYTES
    compression: Optional[str] = None
    stochastic_rounding: bool = False
    quant_block_elems: int = 1024
    min_quant_elems: int = 256
    sharded_update: bool = False
    update_axes: Tuple[str, ...] = ("dp", "fsdp")
    axis: str = "dp"
    telemetry: bool = False

    def __post_init__(self):
        mode = {"monolithic": "gspmd"}.get(self.mode, self.mode)
        if mode not in ("gspmd", "bucketed"):
            raise ValueError(f"unknown grad-sync mode {self.mode!r}")
        object.__setattr__(self, "mode", mode)
        if self.compression not in (None, "", "int8"):
            raise ValueError(f"unknown grad compression {self.compression!r}")
        if not self.compression:
            object.__setattr__(self, "compression", None)
        if self.compression and mode != "bucketed":
            # silently running the stock uncompressed step while the user
            # believes int8 is on would be the worst failure mode
            raise ValueError(
                "compression requires mode='bucketed' (the gspmd/monolithic "
                "sync is implicit — there is no stage to compress)")
        if isinstance(self.update_axes, list):
            object.__setattr__(self, "update_axes", tuple(self.update_axes))

    @property
    def is_default(self) -> bool:
        """True when the config changes nothing vs the stock fused step."""
        return (self.mode == "gspmd" and not self.sharded_update
                and not self.telemetry)

    @staticmethod
    def from_env() -> "GradSyncConfig":
        axes = os.environ.get("RAY_TPU_TRAIN_UPDATE_AXES", "") or "dp,fsdp"
        return GradSyncConfig(
            mode=os.environ.get("RAY_TPU_TRAIN_GRAD_SYNC_MODE", "gspmd") or "gspmd",
            bucket_bytes=_env_int("RAY_TPU_TRAIN_BUCKET_BYTES", DEFAULT_BUCKET_BYTES),
            compression=os.environ.get("RAY_TPU_TRAIN_GRAD_COMPRESSION", "") or None,
            stochastic_rounding=os.environ.get(
                "RAY_TPU_TRAIN_GRAD_STOCHASTIC_ROUNDING", "").lower() in _TRUE,
            quant_block_elems=_env_int("RAY_TPU_TRAIN_QUANT_BLOCK_ELEMS", 1024),
            min_quant_elems=_env_int("RAY_TPU_TRAIN_MIN_QUANT_ELEMS", 256),
            sharded_update=os.environ.get(
                "RAY_TPU_TRAIN_SHARDED_UPDATE", "").lower() in _TRUE,
            update_axes=tuple(a for a in axes.split(",") if a),
            axis=os.environ.get("RAY_TPU_TRAIN_GRAD_SYNC_AXIS", "") or "dp",
            telemetry=os.environ.get(
                "RAY_TPU_TRAIN_GRAD_SYNC_TELEMETRY", "").lower() in _TRUE,
        )

    def to_env(self) -> Dict[str, str]:
        """Env representation (inverse of from_env) for worker propagation."""
        return {
            "RAY_TPU_TRAIN_GRAD_SYNC_MODE": self.mode,
            "RAY_TPU_TRAIN_BUCKET_BYTES": str(self.bucket_bytes),
            "RAY_TPU_TRAIN_GRAD_COMPRESSION": self.compression or "",
            "RAY_TPU_TRAIN_GRAD_STOCHASTIC_ROUNDING":
                "1" if self.stochastic_rounding else "",
            "RAY_TPU_TRAIN_QUANT_BLOCK_ELEMS": str(self.quant_block_elems),
            "RAY_TPU_TRAIN_MIN_QUANT_ELEMS": str(self.min_quant_elems),
            "RAY_TPU_TRAIN_SHARDED_UPDATE": "1" if self.sharded_update else "",
            "RAY_TPU_TRAIN_UPDATE_AXES": ",".join(self.update_axes),
            "RAY_TPU_TRAIN_GRAD_SYNC_AXIS": self.axis,
            "RAY_TPU_TRAIN_GRAD_SYNC_TELEMETRY": "1" if self.telemetry else "",
        }


# ---------------------------------------------------------------- bucketing

def partition_buckets(tree: Any, bucket_bytes: int) -> List[List[int]]:
    """Partition a pytree's leaves into size-bounded buckets.

    Returns a list of buckets, each a list of flat-leaf indices (tree_flatten
    order, so the grouping is deterministic for a given tree structure). A
    leaf larger than `bucket_bytes` gets its own bucket; every leaf lands in
    exactly one bucket. Works on concrete arrays and ShapeDtypeStructs.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    for i, leaf in enumerate(leaves):
        nbytes = int(np.prod(leaf.shape or (1,))) * jnp.dtype(leaf.dtype).itemsize
        if cur and cur_bytes + nbytes > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
        if cur_bytes >= bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def sync_payload_bytes(tree: Any, sync: GradSyncConfig) -> Dict[str, int]:
    """Analytic per-rank payload bytes one sync moves, f32 vs the configured
    compression — the `reduced_bytes` accounting behind TRAIN_SYNC_BENCH."""
    f32 = 0
    compressed = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        n = int(np.prod(leaf.shape or (1,)))
        f32 += 4 * n
        if sync.compression == "int8" and n >= sync.min_quant_elems:
            compressed += n + 4 * (-(-n // sync.quant_block_elems))
        else:
            compressed += 4 * n
    return {"f32_bytes": f32, "compressed_bytes": compressed}


# ------------------------------------------------------------- mesh compat

def _mesh_of(tree: Any) -> Optional[Mesh]:
    """Concrete mesh from any NamedSharding-carrying leaf, else the ambient."""
    for leaf in jax.tree_util.tree_leaves(tree):
        s = getattr(leaf, "sharding", None)
        if isinstance(s, NamedSharding):
            return s.mesh
    from ray_tpu.parallel.sharding import ambient_mesh

    return ambient_mesh()


# ----------------------------------------------------- in-jit sync kernels

def _quantized_pmean(leaf: jax.Array, axis: str, sync: GradSyncConfig,
                     key: Optional[jax.Array]) -> jax.Array:
    """int8 block-quantized mean-reduce over `axis` (inside a manual region):
    quantize local contribution -> all-gather int8+scales -> dequant-sum."""
    # the gathered blocks are the same on every member, and shard_map's
    # replication check has to know it: the public all_gather types its
    # result as varying over `axis`
    from jax._src.lax.parallel import all_gather_invariant

    from ray_tpu.ops.quant import quantize_blockwise

    n = int(np.prod(leaf.shape or (1,)))
    q, scales = quantize_blockwise(leaf, sync.quant_block_elems, key=key)
    qg = all_gather_invariant(q, axis)        # [W, nblocks, block] int8
    sg = all_gather_invariant(scales, axis)   # [W, nblocks, 1] f32
    w = jax.lax.psum(1, axis)
    total = jnp.sum(qg.astype(jnp.float32) * sg, axis=0)
    return (total.reshape(-1)[:n] / w).reshape(leaf.shape).astype(leaf.dtype)


def _sync_bucketed(grads: Any, axis: str, sync: GradSyncConfig,
                   key: Optional[jax.Array]) -> Any:
    """Reduce a grad pytree over `axis`, one collective (pmean) per bucket —
    call inside a shard_map region with `axis` manual."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    buckets = partition_buckets(grads, sync.bucket_bytes)
    out: List[Optional[jax.Array]] = [None] * len(leaves)
    for b, idxs in enumerate(buckets):
        plain = [i for i in idxs
                 if sync.compression != "int8"
                 or int(np.prod(leaves[i].shape or (1,))) < sync.min_quant_elems]
        quant = [i for i in idxs if i not in plain]
        if plain:
            reduced = jax.lax.pmean([leaves[i] for i in plain], axis)
            for i, r in zip(plain, reduced):
                out[i] = r
        for i in quant:
            k = None
            if key is not None:
                k = jax.random.fold_in(jax.random.fold_in(key, i),
                                       jax.lax.axis_index(axis))
            out[i] = _quantized_pmean(leaves[i], axis, sync, k)
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------- sharded optimizer update

def _spec_axes(spec: P) -> set:
    used = set()
    for e in spec:
        if isinstance(e, tuple):
            used |= set(e)
        elif e is not None:
            used.add(e)
    return used


def build_update_specs(params: Any, mesh: Mesh,
                       axes: Sequence[str] = ("dp", "fsdp")) -> Any:
    """Per-leaf PartitionSpec tree for the cross-replica sharded update: each
    param's own sharding extended with the (non-trivial, not-already-used)
    `axes` on the dimension with the largest evenly-divisible shard extent.
    Leaves with no eligible dimension keep their original spec (replicated
    update for that leaf). Works on arrays and sharded ShapeDtypeStructs."""

    def leaf_spec(x):
        s = getattr(x, "sharding", None)
        base = s.spec if isinstance(s, NamedSharding) else P()
        add = tuple(a for a in axes
                    if a not in _spec_axes(base) and mesh.shape.get(a, 1) > 1)
        if not add or not getattr(x, "shape", ()):
            return base
        entries = list(base) + [None] * (len(x.shape) - len(base))

        def factor(e):
            if e is None:
                return 1
            names = e if isinstance(e, tuple) else (e,)
            return int(np.prod([mesh.shape[a] for a in names]))

        addf = int(np.prod([mesh.shape[a] for a in add]))
        best, best_local = None, 0
        for i, dim in enumerate(x.shape):
            local = dim // factor(entries[i])
            if local % addf == 0 and local >= addf and local > best_local:
                best, best_local = i, local
        if best is None:
            return base
        cur = entries[best]
        cur = () if cur is None else (cur if isinstance(cur, tuple) else (cur,))
        entries[best] = tuple(cur) + add
        return P(*entries)

    return jax.tree_util.tree_map(leaf_spec, params)


def param_specs(params: Any) -> Any:
    """The params' own PartitionSpec tree (the compute sharding updated params
    are all-gathered back to)."""
    return jax.tree_util.tree_map(
        lambda x: x.sharding.spec
        if isinstance(getattr(x, "sharding", None), NamedSharding) else P(),
        params)


def _constrain(tree: Any, specs: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s)),
        tree, specs)


def constrain_opt_state(tx: optax.GradientTransformation, opt_state: Any,
                        specs: Any, mesh: Mesh) -> Any:
    """Constrain the param-shaped leaves of an optax state (Adam moments) to
    the update shardings; non-param leaves (step counts) pass through."""
    return optax.tree_map_params(
        tx,
        lambda leaf, s: jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, s)),
        opt_state, specs,
        transform_non_params=lambda leaf: leaf)


def shard_opt_state(tx: optax.GradientTransformation, params: Any,
                    opt_state: Any, sync: "GradSyncConfig",
                    mesh: Optional[Mesh] = None) -> Any:
    """Re-layout a fresh optimizer state for the sharded update (used by
    `init_state`): moments land sharded over `sync.update_axes` so they never
    materialize replicated."""
    mesh = mesh or _mesh_of(params)
    if mesh is None or not sync.sharded_update:
        return opt_state
    specs = build_update_specs(params, mesh, sync.update_axes)
    return jax.jit(lambda o: constrain_opt_state(tx, o, specs, mesh))(opt_state)


def abstract_sharded_opt_state(tx: optax.GradientTransformation,
                               params_structs: Any, mesh: Mesh,
                               axes: Sequence[str] = ("dp", "fsdp")) -> Any:
    """ShapeDtypeStructs of tx.init(params) with the sharded-update shardings
    attached — AOT-lowering input for HBM-budget dryruns (nothing
    materializes)."""
    opt_shapes = jax.eval_shape(tx.init, params_structs)
    specs = build_update_specs(params_structs, mesh, axes)
    return optax.tree_map_params(
        tx,
        lambda leaf, s: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, s)),
        opt_shapes, specs,
        transform_non_params=lambda leaf: leaf)


def opt_state_bytes_per_shard(opt_state_structs: Any) -> int:
    """Per-device bytes of an (abstract or concrete) optimizer state, honoring
    each leaf's sharding — the HBM-budget number the dryrun asserts on."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(opt_state_structs):
        shape = getattr(leaf, "shape", None)
        if shape is None:
            continue
        s = getattr(leaf, "sharding", None)
        if isinstance(s, NamedSharding):
            shape = s.shard_shape(shape)
        total += int(np.prod(shape or (1,))) * jnp.dtype(leaf.dtype).itemsize
    return total


# ------------------------------------------------------------ step factory

def _check_model_compat(cfg) -> None:
    if getattr(cfg, "pipeline_stages", 1) > 1:
        raise ValueError(
            "bucketed grad sync opens its own dp-manual shard_map and does "
            "not compose with pipeline_stages > 1 (nested shard_map)")
    if getattr(cfg, "attention_impl", "auto") in ("ring", "ulysses"):
        raise ValueError(
            "bucketed grad sync does not compose with ring/ulysses attention "
            "(nested shard_map); use mode='gspmd'")


class GradSyncStep:
    """A train step with explicit grad sync. Callable like the stock jitted
    step (`state, batch -> state, metrics`) and `.lower()`-able for AOT
    compiles; builds its jitted program lazily on first use because the
    bucket layout and update specs depend on the state's actual shardings."""

    def __init__(self, cfg, tx, loss_fn, sync: GradSyncConfig, donate: bool):
        self.cfg = cfg
        self.tx = tx
        self.loss_fn = loss_fn
        self.sync = sync
        self.donate = donate
        self.buckets: Optional[List[List[int]]] = None
        self.mesh: Optional[Mesh] = None
        self._fn = None
        self._batch_treedef = None

    # -- lazy build
    def _setup(self, state, batch) -> Optional[dict]:
        """Shared first-call analysis: mesh/spec discovery, model-compat
        checks, and the traced sub-functions both step flavors compose.
        Returns None when the program is already built (after guarding
        against a changed batch schema)."""
        treedef = jax.tree_util.tree_structure(batch)
        if self._fn is not None:
            if treedef != self._batch_treedef:
                raise ValueError(
                    f"batch structure changed after the step was built "
                    f"({self._batch_treedef} -> {treedef}); create a new "
                    "train step per batch schema")
            return None
        self._batch_treedef = treedef
        sync = self.sync
        mesh = _mesh_of(state.params)
        self.mesh = mesh
        # explicit sync needs a mesh carrying the sync axis; otherwise
        # (single device / unsharded state) there is nothing to reduce over
        # and the implicit GSPMD path is the same program minus the wrapper
        explicit = sync.mode == "bucketed" and mesh is not None \
            and sync.axis in mesh.axis_names
        if explicit:
            _check_model_compat(self.cfg)
        sharded = sync.sharded_update and mesh is not None
        return {
            "mesh": mesh,
            "explicit": explicit,
            "sharded": sharded,
            "uspecs": build_update_specs(state.params, mesh, sync.update_axes)
                      if sharded else None,
            "pspecs": param_specs(state.params) if sharded else None,
            "grads_of": self._make_grads_fn(mesh, state, batch)
                        if explicit else None,
        }

    def _grads_stage(self, ctx, params, step, batch):
        """(loss, aux, synced grads) — explicit bucketed sync or the stock
        implicit GSPMD gradient. Traced inside the jitted step."""
        if ctx["explicit"]:
            key = None
            sync = self.sync
            if sync.compression == "int8" and sync.stochastic_rounding:
                key = jax.random.fold_in(jax.random.PRNGKey(0xE0A), step)
            return ctx["grads_of"](params, batch, key)
        (loss, aux), grads = jax.value_and_grad(
            self.loss_fn, has_aux=True)(params, batch, self.cfg)
        return loss, aux, grads

    def _update_stage(self, ctx, state, grads, aux):
        """(new TrainState, metrics) — replicated or cross-replica-sharded
        optimizer update. Traced inside the jitted step."""
        from .step import TrainState

        tx, mesh = self.tx, ctx["mesh"]
        metrics = dict(aux)
        if ctx["explicit"] and "tokens" in metrics:
            metrics["tokens"] = metrics["tokens"] * mesh.shape[self.sync.axis]
        metrics["grad_norm"] = optax.global_norm(grads)
        if ctx["sharded"]:
            uspecs, pspecs = ctx["uspecs"], ctx["pspecs"]
            g = _constrain(grads, uspecs, mesh)
            p = _constrain(state.params, uspecs, mesh)
            opt = constrain_opt_state(tx, state.opt_state, uspecs, mesh)
            updates, new_opt = tx.update(g, opt, p)
            new_opt = constrain_opt_state(tx, new_opt, uspecs, mesh)
            new_params = optax.apply_updates(p, updates)
            new_params = _constrain(new_params, pspecs, mesh)
        else:
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        return TrainState(state.step + 1, new_params, new_opt), metrics

    def _ensure(self, state, batch) -> None:
        ctx = self._setup(state, batch)
        if ctx is None:
            return

        def impl(state, batch):
            loss, aux, grads = self._grads_stage(ctx, state.params, state.step,
                                                 batch)
            return self._update_stage(ctx, state, grads, aux)

        self._fn = jax.jit(impl, donate_argnums=(0,) if self.donate else ())

    def _make_grads_fn(self, mesh, state, batch):
        """(params, batch, key) -> (loss, aux, synced grads): the dp-manual
        shard_map region with per-bucket collectives."""
        sync, cfg, loss_fn = self.sync, self.cfg, self.loss_fn
        from ray_tpu.parallel.sharding import manual_axes

        grads_shape = jax.eval_shape(
            lambda p, b: jax.grad(lambda q: loss_fn(q, b, cfg)[0])(p),
            state.params, batch)
        self.buckets = partition_buckets(grads_shape, sync.bucket_bytes)
        aux_shape = jax.eval_shape(
            lambda p, b: loss_fn(p, b, cfg)[1], state.params, batch)

        def body(params, batch, key):
            with manual_axes(sync.axis):
                (loss, aux), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch, cfg)
                grads = _sync_bucketed(grads, sync.axis, sync, key)
                loss = jax.lax.pmean(loss, sync.axis)
                aux = jax.tree_util.tree_map(
                    lambda x: jax.lax.pmean(x, sync.axis), aux)
            return loss, aux, grads

        pspec = jax.tree_util.tree_map(lambda _: P(), state.params)
        bspec = jax.tree_util.tree_map(lambda _: P(sync.axis), batch)
        aux_spec = jax.tree_util.tree_map(lambda _: P(), aux_shape)
        gspec = jax.tree_util.tree_map(lambda _: P(), grads_shape)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, bspec, P()),
            out_specs=(P(), aux_spec, gspec),
            axis_names={sync.axis})

    # -- public surface
    @hot_path
    def __call__(self, state, batch):
        self._ensure(state, batch)
        return self._fn(state, batch)

    def lower(self, state, batch):
        self._ensure(state, batch)
        return self._fn.lower(state, batch)


class InstrumentedGradSyncStep(GradSyncStep):
    """Two-stage variant for `GradSyncConfig(telemetry=True)`: a grads program
    and an update program, so the host observes per-bucket readiness and
    reports grad-sync phases (`train.step_phase` spans around bucket waits +
    `train_grad_sync_seconds{phase}`). Trades the grads/update fusion for
    observability — a diagnostics mode, not the headline-MFU path."""

    def _ensure(self, state, batch) -> None:
        ctx = self._setup(state, batch)
        if ctx is None:
            return
        self._grads_fn = jax.jit(
            lambda params, step, batch: self._grads_stage(ctx, params, step,
                                                          batch))
        self._update_fn = jax.jit(
            lambda state, grads, aux: self._update_stage(ctx, state, grads,
                                                         aux),
            donate_argnums=(0, 1) if self.donate else ())
        self._fn = self._run

    def _phase(self, name: str):
        from ray_tpu.util import telemetry

        class _Ctx:
            def __enter__(_s):
                _s.t0 = time.perf_counter()
                _s.inner = session.step_phase(name)
                _s.inner.__enter__()
                return _s

            def __exit__(_s, *exc):
                _s.inner.__exit__(*exc)
                telemetry.get_histogram(
                    "train_grad_sync_seconds",
                    "per-phase gradient-sync time (grad_sync telemetry mode)",
                    tag_keys=("phase",)).observe(
                        time.perf_counter() - _s.t0, tags={"phase": name})
                return False

        return _Ctx()

    def _run(self, state, batch):
        with self._phase("grad_sync.forward_backward"):
            loss, aux, grads = self._grads_fn(state.params, state.step, batch)
            # jit dispatch is async: without a sync point this phase would
            # time only the enqueue and the fwd/bwd compute would be
            # misattributed to the first bucket wait. Blocking on the loss
            # bounds the phase at loss production; bucket waits then measure
            # each bucket's readiness tail beyond that point.
            jax.block_until_ready(loss)
        leaves = jax.tree_util.tree_leaves(grads)
        for b, idxs in enumerate(self.buckets or [list(range(len(leaves)))]):
            with self._phase("grad_sync.bucket_wait"):
                jax.block_until_ready([leaves[i] for i in idxs])
        with self._phase("grad_sync.optimizer"):
            new_state, metrics = self._update_fn(state, grads, aux)
            jax.block_until_ready(new_state.params)
        return new_state, metrics

    def lower(self, state, batch):  # pragma: no cover - diagnostics mode
        raise NotImplementedError(
            "InstrumentedGradSyncStep is a two-program step; AOT-lower the "
            "fused step (telemetry=False) instead")


def make_step(cfg, tx, loss_fn, sync: GradSyncConfig, donate: bool = True):
    """Factory `train.step.make_train_step` delegates to for non-default
    sync configs. The step is counted as the stock one is (the loop's `dispatch` lap
    around its call, train/session.py); `.lower` and its other attributes come through."""
    cls = InstrumentedGradSyncStep if sync.telemetry else GradSyncStep
    return session.CountedStep(cls(cfg, tx, loss_fn, sync, donate))


# -------------------------------------------------------- HLO inspection

_RED_RE = r"=\s*\S+\s+(all-reduce|reduce-scatter|all-gather)"
_COMPUTE_RE = r"=\s*\S+\s+(fusion|dot|while|convolution|custom-call)"


def overlap_report(compiled_or_text) -> Dict[str, Any]:
    """Inspect a compiled step's HLO for reduction placement — the check that
    bucketed reductions are NOT all sunk to the end of the program.

    Returns op counts and positions within the entry computation:
    `n_reductions` (distinct collective ops), `first_reduction_pos` /
    `last_compute_pos` (instruction indices), and `all_sunk_to_end` (True when
    every collective sits after the last compute op — the monolithic
    pathology the bucketed mode exists to break up).
    """
    import re

    txt = compiled_or_text if isinstance(compiled_or_text, str) \
        else compiled_or_text.as_text()
    entry: List[str] = []
    in_entry = False
    for line in txt.splitlines():
        s = line.strip()
        if s.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry:
            if s.startswith("}"):
                break
            entry.append(s)
    red = [i for i, l in enumerate(entry) if re.search(_RED_RE, l)]
    compute = [i for i, l in enumerate(entry) if re.search(_COMPUTE_RE, l)]
    return {
        "n_instructions": len(entry),
        "n_reductions": len(red),
        "first_reduction_pos": red[0] if red else None,
        "last_reduction_pos": red[-1] if red else None,
        "last_compute_pos": compute[-1] if compute else None,
        "n_compute_after_first_reduction":
            sum(1 for i in compute if i > red[0]) if red else 0,
        "all_sunk_to_end":
            bool(red) and bool(compute) and red[0] > compute[-1],
    }
