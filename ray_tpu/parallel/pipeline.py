"""Pipeline parallelism: GPipe-style microbatch schedule over the `pp` mesh axis.

Reference capability: the reference only *places* TP×PP workers for vLLM
(SURVEY.md §2.3 TP/PP row); the actual pipeline engine is external. Here it is native:
stages are mesh shards, activations hop stage→stage via `lax.ppermute` over ICI/DCN, and
the whole schedule compiles into the train step (bubbles and all), so autodiff gives the
1F1B-equivalent gradient accumulation for free.

Layout: stage-stacked params (leading axis = pp, sharded over "pp"); inputs split into M
microbatches. The schedule runs M + pp - 1 ticks; each tick every stage runs its layer on
its current microbatch and ppermutes the result forward. Other mesh axes (dp/fsdp/tp/sp)
stay in GSPMD "auto" mode inside the stage function — pipeline composes with them.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_spmd(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x_mb: jax.Array,
    *,
    axis_name: str = "pp",
    with_aux: bool = False,
    side_mb: Any = None,
):
    """Collective pipeline schedule; call inside shard_map manual over `axis_name`.

    stage_fn(params, x) -> y with y.shape == x.shape (a transformer block stack).
    stage_params: THIS stage's params. x_mb: [M, ...] microbatches (same array on every
    stage; only stage 0 consumes it). Returns [M, ...] outputs on every stage.

    with_aux=True: stage_fn returns (y, aux_scalar) — e.g. a MoE load-balancing
    loss. Bubble ticks run on zero inputs, so each stage's aux only counts ticks
    where it holds a real microbatch (its valid window is t - stage in [0, M));
    the return is then (y, psum-over-stages of the per-microbatch MEAN aux) —
    matching the non-pipelined sum-over-layers of a full-batch mean, since
    microbatches are equal-sized.

    side_mb: optional pytree of [M, ...] per-microbatch side inputs that do NOT
    flow stage-to-stage (segment_ids, token masks). Unlike x_mb, every stage
    reads the side slice of the microbatch it is CURRENTLY processing (t - stage),
    and stage_fn is called as stage_fn(params, x, side).
    """
    pp = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    m = x_mb.shape[0]
    ticks = m + pp - 1
    # pcast-to-varying: the carry is device-varying from tick 1 on; the init must match
    # the full varying set (pp plus any other manual axes x_mb carries, e.g. sp) —
    # adding only the axes the value doesn't already vary over.
    from .sharding import vary_like

    def _vary(z):
        return vary_like(z, x_mb, extra=(axis_name,))

    y0 = _vary(jnp.zeros_like(x_mb))
    buf0 = _vary(jnp.zeros_like(x_mb[0]))
    aux0 = _vary(jnp.zeros((), jnp.float32))
    fwd = [(i, i + 1) for i in range(pp - 1)]  # non-circular: stage 0 receives zeros

    def body(carry, t):
        buf, y, aux_acc = carry
        inp = jnp.where(stage == 0, x_mb[jnp.clip(t, 0, m - 1)], buf)
        args = (stage_params, inp)
        if side_mb is not None:
            mb_now = jnp.clip(t - stage, 0, m - 1)
            args += (jax.tree_util.tree_map(lambda a: a[mb_now], side_mb),)
        if with_aux:
            out, aux = stage_fn(*args)
            valid = (t >= stage) & (t - stage < m)
            aux_acc = aux_acc + jnp.where(valid, aux.astype(jnp.float32), 0.0)
        else:
            out = stage_fn(*args)
        mb = t - (pp - 1)
        done = lax.dynamic_update_index_in_dim(y, out, jnp.clip(mb, 0, m - 1), 0)
        y = jnp.where((stage == pp - 1) & (mb >= 0), done, y)
        buf_next = lax.ppermute(out, axis_name, fwd) if pp > 1 else buf
        return (buf_next, y, aux_acc), None

    (_, y, aux_acc), _ = lax.scan(body, (buf0, y0, aux0), jnp.arange(ticks))
    # Hand the last stage's outputs to every stage (loss is then computed redundantly —
    # the SPMD idiom; XLA keeps one copy per pp group member).
    y = lax.psum(jnp.where(stage == pp - 1, y, jnp.zeros_like(y)), axis_name)
    if with_aux:
        return y, lax.psum(aux_acc, axis_name) / m
    return y


def pipeline(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    *,
    num_microbatches: int,
    mesh=None,
    axis_name: str = "pp",
    x_spec: P = None,
    extra_manual: tuple = (),
    with_aux: bool = False,
    side: Any = None,
    side_spec: Any = None,
):
    """Driver-level wrapper: global [B, ...] input, stage-stacked params.

    stacked_params: pytree whose leaves have leading dim pp, sharded P("pp", ...).
    Splits x into `num_microbatches`, runs the schedule, returns [B, ...] outputs
    (or (outputs, aux scalar) when with_aux — see pipeline_spmd; aux is pmean'd
    over `extra_manual` axes, since e.g. sp shards hold disjoint token chunks
    whose shard-mean auxes average to the global mean).
    Jit-friendly: trace under use_mesh(mesh) or pass mesh explicitly.

    `extra_manual` names additional mesh axes the stage itself handles collectively
    (e.g. "sp" when the stage runs ring attention); `x_spec` is the PartitionSpec of one
    microbatch [B/M, ...] over those axes. Nested shard_map is not composable (sdy
    rejects re-bound axes), so pp and sp share ONE manual region here.

    `side`: optional pytree of [B, ...] per-example side inputs (segment_ids,
    token masks) split into microbatches alongside x; stage_fn then receives a
    third argument holding its current microbatch's slice (see pipeline_spmd).
    `side_spec`: matching pytree of per-microbatch PartitionSpecs over the
    manual axes (default: replicated).
    """
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible by num_microbatches {num_microbatches}")
    for leaf in jax.tree_util.tree_leaves(side):
        if leaf.shape[0] != b:
            raise ValueError(
                f"side input leading dim {leaf.shape[0]} != batch {b}")
    from .sharding import ambient_mesh

    env_mesh = mesh if mesh is not None else ambient_mesh()
    pp_size = env_mesh.shape.get(axis_name) if getattr(env_mesh, "shape", None) else None
    leading = {leaf.shape[0] for leaf in jax.tree_util.tree_leaves(stacked_params)}
    if pp_size is not None and leading and leading != {pp_size}:
        raise ValueError(
            f"stacked_params leading dims {sorted(leading)} must equal mesh '{axis_name}' "
            f"size {pp_size}; a mismatch would silently drop pipeline stages"
        )
    x_mb = x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])
    side_mb = jax.tree_util.tree_map(
        lambda a: a.reshape(num_microbatches, b // num_microbatches, *a.shape[1:]),
        side)
    manual = {axis_name, *extra_manual}
    mb_spec = P(None, *(x_spec or P())) if (x_spec or extra_manual) else P()
    side_specs = (jax.tree_util.tree_map(
        lambda s: P(None, *s), side_spec, is_leaf=lambda s: isinstance(s, P))
        if side_spec is not None
        else jax.tree_util.tree_map(lambda _: P(), side))

    def inner(params, x_mb, side_mb):
        from .sharding import manual_axes

        local = jax.tree_util.tree_map(lambda p: p[0], params)  # drop stage axis (len 1)
        with manual_axes(*manual):
            out = pipeline_spmd(stage_fn, local, x_mb, axis_name=axis_name,
                                with_aux=with_aux, side_mb=side_mb)
            if with_aux:
                y, aux = out
                for ax in extra_manual:
                    aux = lax.pmean(aux, ax)
                return y, aux
            return out

    param_specs = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    mapped = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(param_specs, mb_spec, side_specs),
        out_specs=(mb_spec, P()) if with_aux else mb_spec,
        axis_names=set(manual),
    )
    if with_aux:
        y_mb, aux = mapped(stacked_params, x_mb, side_mb)
        return y_mb.reshape(b, *x.shape[1:]), aux
    y_mb = mapped(stacked_params, x_mb, side_mb)
    return y_mb.reshape(b, *x.shape[1:])
