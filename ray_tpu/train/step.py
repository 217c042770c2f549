"""Canonical jitted train step: loss -> grads -> optax update, GSPMD-sharded.

This is the compute core `JaxTrainer` drives, and what the benchmark's two train cells
measure (`benchmarks/drivers/train.py`). One function builds the whole step so XLA fuses
grad + update and the optimizer state inherits the parameter shardings (ZeRO-for-free
under fsdp).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models import ModelConfig, llama
from ray_tpu.parallel import build_mesh, MeshSpec, use_mesh
from ray_tpu.parallel.sharding import AxisRules, TRAIN_RULES, named_sharding, shard_pytree

from . import grad_sync, session


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


def make_optimizer(
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    warmup_steps: int = 100,
    total_steps: int = 10000,
    mu_dtype=None,
) -> optax.GradientTransformation:
    """mu_dtype: dtype of Adam's first moment (e.g. jnp.bfloat16 halves that
    third of optimizer HBM; the second moment stays f32 — its dynamic range is
    the one that cannot survive bf16). Used with the sharded optimizer update
    on HBM-tight pod budgets (__graft_entry__.hbm_budget_sharded_opt)."""
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1)
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay,
                    mu_dtype=mu_dtype),
    )


def init_state(
    rng: jax.Array,
    cfg: ModelConfig,
    tx: optax.GradientTransformation,
    mesh=None,
    rules: AxisRules = TRAIN_RULES,
    checkpoint_dir: Optional[str] = None,
    param_dtype=None,
    sync: Optional["grad_sync.GradSyncConfig"] = None,
) -> TrainState:
    """Fresh (or checkpoint-warm-started) sharded TrainState.

    checkpoint_dir: HF-layout safetensors dir (models/checkpoint.py) — streams
    real weights into the sharded pytree instead of random init, so fine-tuning
    starts from a released model (reference: model loading is the engine/trainer
    contract, vllm_engine.py:180).

    sync: with `sharded_update=True` the optimizer state materializes sharded
    over the update axes from the start (train/grad_sync.py) instead of being
    re-laid-out on the first step."""
    if checkpoint_dir is not None:
        from ray_tpu.models import checkpoint as ckpt_io

        params = ckpt_io.load_llama_params(
            checkpoint_dir, cfg, mesh, rules=rules,
            param_dtype=param_dtype or jnp.float32)
    else:
        params = llama.init(rng, cfg)
        if mesh is not None:
            params = shard_pytree(params, llama.param_axes(cfg), mesh, rules)
    sync = sync or grad_sync.GradSyncConfig.from_env()
    if mesh is not None:
        # Every optimizer leaf that mirrors a parameter takes that parameter's
        # sharding, said out loud: the moments are zeros with no data
        # dependence on the parameters, and left to itself XLA replicates them
        # (f32 Adam state stacked whole on every device; PR 22, four chips).
        opt_shardings = optax.tree_map_params(
            tx, lambda _, p: p.sharding, jax.eval_shape(tx.init, params), params,
            transform_non_params=lambda _: named_sharding(mesh))
        with use_mesh(mesh):
            opt_state = jax.jit(tx.init, out_shardings=opt_shardings)(params)
            if sync.sharded_update:
                opt_state = grad_sync.shard_opt_state(
                    tx, params, opt_state, sync, mesh)
        step = jax.device_put(jnp.zeros((), jnp.int32), named_sharding(mesh))
        return TrainState(step=step, params=params, opt_state=opt_state)
    # Committed to the device it is on, as a state that comes back from a checkpoint or
    # from the host is: `jit` keys its programs on that, and a step first called on an
    # uncommitted state was compiled a second time at its first call after a restore
    # (the same program: 3 s from a warm compile cache, 31-39 s from a cold one in the
    # GLM cell, PERF.md section 7).
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params))
    return jax.tree.map(  # (a caller that only asks for shapes traces this: nothing to commit)
        lambda a: a if isinstance(a, jax.core.Tracer) else jax.device_put(a, a.sharding), state)


def make_train_step(
    cfg: ModelConfig,
    tx: optax.GradientTransformation,
    loss_fn: Optional[Callable] = None,
    donate: bool = True,
    sync: Optional["grad_sync.GradSyncConfig"] = None,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict[str, jax.Array]]]:
    """sync=None reads GradSyncConfig.from_env() — how a JaxTrainer backend
    config (`JaxConfig(grad_sync=...)`) reaches user train loops that build
    their own step. The default (env unset) is the stock fused jit below,
    byte-identical to the historical behavior; non-default configs delegate to
    train/grad_sync.py (bucketed overlapped all-reduce, int8 reduction,
    cross-replica sharded optimizer update)."""
    loss_fn = loss_fn or llama.loss_fn
    sync = sync or grad_sync.GradSyncConfig.from_env()
    if not sync.is_default:
        if cfg.moe_dropless and cfg.moe_select_bias:
            raise NotImplementedError(
                "the selection bias's balance rule runs in the stock step only; "
                "train/grad_sync.py's steps would leave it to the optimizer")
        return grad_sync.make_step(cfg, tx, loss_fn, sync, donate)

    def model(params, batch, cfg):
        # JAX wraps the FIRST name inside a transformation in the transformation's own
        # (`jvp(embed)`, `transpose(jvp(lm_head))`), which no reader of plain names finds;
        # this scope takes that wrapper, so the model's scopes below it (`embed`,
        # `lm_head`, `loss`, a layer's parts) stand plain in every instruction's `op_name`
        with jax.named_scope("model"):
            return loss_fn(params, batch, cfg)

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        (loss, aux), grads = jax.value_and_grad(model, has_aux=True)(
            state.params, batch, cfg
        )
        with jax.named_scope("optimizer"):  # a name in the profile; no operation
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        if cfg.moe_dropless and cfg.moe_select_bias:
            # outside the gradient and the optimizer (whose weight decay would pull it to
            # zero): the selection bias moves by the balance rule, on the step's own counts
            new_params = llama.balance_router_bias(state.params, new_params, aux["expert_load"], cfg)
        metrics = dict(aux)
        metrics["grad_norm"] = optax.global_norm(grads)
        return TrainState(state.step + 1, new_params, new_opt), metrics

    return session.CountedStep(jax.jit(step, donate_argnums=(0,) if donate else ()))
