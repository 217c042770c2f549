"""Driver `train_diffusion`: `train_family`'s loop, window, trace and result keys for a
configuration whose training objective is not next-token prediction but block diffusion
(cfg.diffusion_block; benchmarks/DIFFUSION.md). JaxTrainer.fit() around make_train_step
and llama.loss_fn, one worker holding the cell's chips.

What differs from `drivers/train_family.py`, which feeds `{"tokens": [B, S + 1]}` and holds
next-token losses:
  the batch      `{"tokens" [B, L], "masked" [B, L], "p_mask" [B]}`: ids drawn below the
                 configuration's mask token, the noise by the loader-side function a user's
                 collate calls (`ray_tpu.train.block_diffusion_noise`) on the run's seeded
                 generator; the parity batch draws its `t` as any batch does;
  the reference  `loss(params, batch, model, dtype, selection, parts)` over that batch; its
                 `position_losses` are the noised half's cross entropy at EVERY position,
                 masked or not, and its `routings` cover all 2L rows of the doubled row, as
                 the step's `experts_chosen [layers, B * 2L, k]` does;
  the checks     `train_family`'s (the losses the step reports, every gradient row read back
                 from Adam's first moment in multiples of the bfloat16 yardstick, the update
                 from its moments, the selection beyond its margin, the forward pass alone a
                 position at a time), without the selection bias's rule (the family has no
                 bias), plus `masked_tokens` equal to the batch's own count. The losses are
                 sums over the masked positions alone, and every masked position carries the
                 same embedding, so their rounding errors are common to them and do not
                 average out: `step_parity_limit.loss_rel_err` guards the loss's FORM (the
                 1 / p weight, the divisor, the count), not its precision (PERF.md, PR 50);
  the counters   `held_assignments`, `fullest_held_expert_rows`, `masked_tokens` as series;
  tokens         `tokens_per_step` = batch x seq counts TRAINING tokens: 2 x as many rows
                 cross the layers;
  the placement  every masked position of the noised half carries ONE embedding, the mask
                 token's, and with seeded weights (unit-scale embedding rows, depth-scaled
                 layer outputs) that row decides where a masked position is routed in every
                 layer: a quarter of a step's rows choose the same 8 experts a layer. How many
                 of those 8 this chip's share holds is a lottery of the seed, Binomial(8, 1/8) a
                 layer, and a step's time followed it (440.6 to 450.6 ms over 8 seeds, ordered
                 by that count: PERF.md, PR 50). An expert-parallel deployment does not leave
                 its hot experts where they fall; it places them evenly over the group. So after
                 the seeded init and before the first step, `place_hot_experts` relabels each
                 layer's router columns (which expert a column scores is a label; the held
                 experts' seeded weights are alike) so that the 8 experts the mask token's row
                 scores highest lie one in each of the group's shares: this chip holds one of
                 them a layer, the group's mean. The reference runs on the same parameters.
The helpers that are `train_family`'s are imported from it, not copied; so is its module
docstring's account of why the selection is handed over and what `parity_s` is.
"""
import math
import os
import shutil
import tempfile
import time

from benchmarks.drivers.train_family import STEP_TEXT, first_update_errors, gradient_summary, row_errors

# what a step's metrics carry beyond the loss (models/llama.py:block_diffusion_loss), kept as series
COUNTERS = ("held_assignments", "fullest_held_expert_rows", "masked_tokens")
LOSSES = ("loss", "ce_loss")


def place_hot_experts(params, cfg):
    """`params` with every layer's router columns relabelled so that the `moe_top_k` experts
    the mask token's embedding scores highest (its normed row times the router, as the layer
    scores it when the stream is the row alone) are dealt round the shares of the expert group
    (`cfg.experts_held[1]` shares of contiguous labels: one in each at 8 over 8); the other
    columns keep their order.
    Returns (params, how many of those experts each layer's held share held before)."""
    import jax.numpy as jnp
    import numpy as np

    index, shares = cfg.experts_held
    per_share = cfg.n_experts // shares
    row = np.asarray(params["embed"][cfg.diffusion_mask_token], np.float32)
    row = row / np.sqrt(np.mean(np.square(row)) + cfg.norm_eps)
    routers = np.asarray(params["layers"]["router"], np.float32)  # [layers, D, E]
    norms = np.asarray(params["layers"]["mlp_norm"], np.float32)
    placed, before = [], []
    for router, norm in zip(routers, norms):
        hot = np.argsort(-((row * norm) @ router), kind="stable")[:cfg.moe_top_k]
        before.append(int(((hot >= index * per_share) & (hot < (index + 1) * per_share)).sum()))
        seats = [j % shares * per_share + j // shares for j in range(cfg.moe_top_k)]  # dealt round the shares, each share's first labels
        rest = [e for e in range(cfg.n_experts) if e not in set(hot.tolist())]
        free = [label for label in range(cfg.n_experts) if label not in seats]
        column = np.empty(cfg.n_experts, np.int64)  # column[label] = the seeded column that gets it
        column[seats], column[free] = hot, rest
        placed.append(router[:, column])
    layers = dict(params["layers"], router=jnp.asarray(np.stack(placed), params["layers"]["router"].dtype))
    return dict(params, layers=layers), before


def _loop(config: dict) -> None:
    """JaxTrainer body. `config`: model keys, trainer settings, seed, seconds,
    trace directory (or None)."""
    import importlib
    import inspect

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import ray_tpu.train as train
    from benchmarks.lib import compile_events, modelcfg, settle
    from ray_tpu.models import llama
    from ray_tpu.train import block_diffusion_noise, init_state, make_optimizer, make_train_step

    # the package re-exports the function under the module's name
    attention_ops = importlib.import_module("ray_tpu.ops.attention")
    stamps = {"loop_entered": time.time()}
    compiles = compile_events.listen()

    tr = config["trainer"]
    reference = importlib.import_module(f"benchmarks.lib.{tr['reference']}")
    cfg = modelcfg.model_config(config["model"])
    seed = config["seed"]
    devices = jax.devices()
    tx = make_optimizer(**tr["optimizer"])
    # weights on the device, from the seed, in one jitted call (init_state)
    state = init_state(jax.random.PRNGKey(seed & 0x7FFFFFFF), cfg, tx)
    # the deployment's placement of the mask token's experts, one a share (the module's docstring)
    placed, held_before = place_hot_experts(state.params, cfg)
    state = state._replace(params=jax.device_put(placed, jax.tree.map(lambda a: a.sharding, state.params)))
    jax.block_until_ready(state)
    stamps["weights_ready"] = time.time()
    step = make_train_step(cfg, tx)
    rng = np.random.default_rng([seed, 1])
    shape = (tr["batch"], tr["seq"])

    def host_batch(rng=rng):
        """Fresh sequences of ids below the mask token, noised as a loader noises them."""
        return block_diffusion_noise(rng, rng.integers(0, cfg.diffusion_mask_token, shape, dtype=np.int32))

    def one_step(state, n):
        # the annotations cost microseconds and show only in a traced run
        with jax.profiler.StepTraceAnnotation("train_step", step_num=n):
            with jax.profiler.TraceAnnotation("host_batch"):
                batch = host_batch()
            with jax.profiler.TraceAnnotation("dispatch_and_wait"):
                state, metrics = step(state, batch)
                loss = float(metrics["loss"])  # the sync a user's loop makes to log its loss
                jax.block_until_ready(state)
            for name in COUNTERS:  # a few dozen numbers a step, left on the device: a fetch
                counters.setdefault(name, []).append(metrics[name])  # here would hold the next step back
        return state, loss

    counters = {}
    model = config["model"]
    rms = lambda d: float(np.sqrt(np.mean(np.square(d))))  # noqa: E731

    def free(*trees):  # now, whoever else still names them
        for a in jax.tree.leaves(trees):
            a.delete()

    def by_row(chosen):  # [B * 2L, k] a layer -> [B, 2L, k]
        return [np.asarray(c).reshape(shape[0], 2 * shape[1], -1) for c in chosen]

    def system_losses(p, batch):
        """The forward pass alone: (the noised half's cross entropy against x0 at every
        position [B, L], what each expert layer chose over the doubled row)"""
        row, positions = llama.block_diffusion_rows(batch["tokens"], batch["masked"], cfg)
        logits, _, aux = llama.forward(p, row, cfg, positions=positions, return_aux=True, head_rows=shape[1])
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, batch["tokens"][..., None], axis=-1)[..., 0], aux["chosen"]

    alone = jax.jit(system_losses)

    def reference_of(dtype):
        """The reference's loss, its parts and its gradient, on a given selection."""
        def fn(p, batch, chosen):
            (total, parts), grads = jax.value_and_grad(reference.loss, has_aux=True)(
                p, batch, model, dtype, chosen, True)
            routings = [{"own": r["own"], "margin": r["margin"]} for r in parts.pop("routings")]
            return dict(parts, loss=total, routings=routings), grads
        return jax.jit(fn)

    def selection_against(chosen, routings):
        """Where the system chose other experts than the reference would have, and how
        clear the reference's own choice was there."""
        margins, differs = [], []
        for mine, r in zip(chosen, routings):
            same = np.sort(np.asarray(mine), -1) == np.sort(np.asarray(r["own"]), -1)
            margins.append(np.asarray(r["margin"], np.float64).ravel())
            differs.append(~same.all(-1).ravel())
        margins, differs = np.concatenate(margins), np.concatenate(differs)
        return {"tokens": int(differs.size), "differ": int(differs.sum()),
                "largest_margin_where_differs": float(margins[differs].max()) if differs.any() else 0.0,
                "margin_percentiles_1_10_50": [float(x) for x in np.percentile(margins, (1, 10, 50))]}

    def positions_against(system, exact, coarse):
        system, exact, coarse = (np.asarray(x, np.float64) for x in (system, exact, coarse))
        return {"positions": int(exact.size), "loss_reference": float(exact.mean()),
                "loss_system": float(system.mean()),
                "system_rms": rms(system - exact), "system_max": float(np.abs(system - exact).max()),
                "yardstick_rms": rms(coarse - exact),
                "yardstick_max": float(np.abs(coarse - exact).max())}

    def step_parity(state0, params0, reported, batch):
        """The step's first run against the reference. state0: the state that run left,
        reported: its metrics, params0: the parameters it started from (both on the
        host). Returns what it found and the state, put back on the device."""
        opt = {k: v.default for k, v in inspect.signature(make_optimizer).parameters.items()}
        opt.update(tr["optimizer"])
        b1, b2 = opt["b1"], opt["b2"]
        lr = float(optax.warmup_cosine_decay_schedule(
            0.0, opt["learning_rate"], opt["warmup_steps"],
            max(opt["total_steps"], opt["warmup_steps"] + 1))(0))
        lap, laps = time.perf_counter(), {}

        def done(what):  # seconds since the last call, under `what` (every lap ends on the host)
            nonlocal lap
            laps[what], lap = laps.get(what, 0.0) + time.perf_counter() - lap, time.perf_counter()

        shardings = jax.tree.map(lambda a: a.sharding, state0)
        kept = jax.device_get(state0)  # the chip has no room for the state beside the reference's gradient
        mu = optax.tree_utils.tree_get(state0.opt_state, "mu")
        nu = optax.tree_utils.tree_get(state0.opt_state, "nu")
        p0 = jax.device_put(params0, shardings.params)
        out = {"update": dict(zip(("moments_rel", "moved_max_abs_err"), (float(x) for x in jax.jit(
            first_update_errors, static_argnums=(4, 5, 6, 7))(
                p0, state0.params, mu, nu, lr, opt["weight_decay"], b1, b2))), lr=lr)}
        free(state0)  # all of it: the reference's gradient program needs the room
        done("state_to_host_and_update_check")

        chosen = by_row(reported["experts_chosen"])
        out["masked_tokens"] = {"system": float(reported["masked_tokens"]),
                                "batch": int(np.asarray(batch["masked"]).sum()),
                                "p_mask": [float(p) for p in np.asarray(batch["p_mask"])]}
        # (as numpy, like the step's: the reference's compiled program is then the same one)
        system, chosen_alone = jax.device_get(alone(p0, batch))
        chosen_alone = by_row(chosen_alone)
        done("forward_alone")
        alone_differs = int(sum(  # as sets: the order weighs nothing
            (np.sort(a, -1) != np.sort(b, -1)).any(-1).sum() for a, b in zip(chosen, chosen_alone)))
        exact_fn, coarse_fn = reference_of(jnp.float32), reference_of(jnp.dtype(cfg.dtype))
        exact, grads = exact_fn(p0, batch, chosen)
        jax.block_until_ready(grads)
        done("reference_float32")
        # the step clipped its gradient to the norm it reports, and Adam kept (1 - b1) of it
        scale = max(1.0, float(reported["grad_norm"]) / opt["grad_clip"]) / (1 - b1)
        errors = jax.jit(row_errors)
        mu = jax.device_put(optax.tree_utils.tree_get(kept.opt_state, "mu"), shardings.params)
        system_rows = jax.device_get(errors(mu, grads, scale))
        free(mu)
        done("gradient_rows")
        coarse, coarse_grads = coarse_fn(p0, batch, chosen)
        coarse_rows = jax.device_get(errors(coarse_grads, grads))
        done("reference_coarse")
        norm = math.sqrt(sum(float(np.sum(ref)) for _, ref in system_rows.values()))
        free(grads, coarse_grads)
        out["gradient"] = dict(gradient_summary(system_rows, coarse_rows),
                               norm_system=float(reported["grad_norm"]), norm_reference=norm)
        out["losses"] = {}
        for name in LOSSES:
            ref = float(exact[name])
            scale = max(abs(ref), 1e-30)  # (a batch whose noise hides no position has no loss)
            out["losses"][name] = {
                "system": float(reported[name]), "reference": ref,
                "rel_err": abs(float(reported[name]) - ref) / scale,
                "yardstick_rel_err": abs(float(coarse[name]) - ref) / scale}
        out["selection"] = selection_against(chosen, exact["routings"])
        # the forward pass alone, a position at a time; where it chose as the step did
        # (it is the same arithmetic) the reference's numbers are already there
        exact_p, coarse_p = exact["position_losses"], coarse["position_losses"]
        if alone_differs:
            exact_p = exact_fn(p0, batch, chosen_alone)[0]["position_losses"]
            coarse_p = coarse_fn(p0, batch, chosen_alone)[0]["position_losses"]
        out.update(positions_against(system, exact_p, coarse_p),
                   forward_alone_chose_otherwise=alone_differs)
        free(p0)
        done("reference_again_for_the_forward_alone")
        state0 = jax.block_until_ready(jax.device_put(kept, shardings))
        done("state_back")
        return dict(out, seconds=laps), state0

    # parity, outside the window, on a seeded batch of the step's own shape
    parity_rng = np.random.default_rng([seed, 2])
    parity_batch = host_batch(parity_rng)
    while not parity_batch["masked"].any():  # (one sequence in ~8,000 at t near 0: no loss, nothing to compare)
        parity_batch = host_batch(parity_rng)
    parity_s = time.perf_counter()
    params0 = jax.device_get(state.params)  # the step donates its state
    parity_s = time.perf_counter() - parity_s

    t0 = time.perf_counter()
    state, reported = step(state, parity_batch)  # the one compiled step's first run is the parity batch's
    first_loss = float(reported["loss"])
    jax.block_until_ready(state)
    first_step_s = time.perf_counter() - t0  # compiles, or reads the cache
    stamps["compiled"] = time.time()

    t0 = time.perf_counter()
    parity_out, state = step_parity(state, params0, jax.device_get(reported), parity_batch)
    del params0, reported
    jax.block_until_ready(state)
    parity_s += time.perf_counter() - t0
    for _ in range(tr["warmup_steps_run"] - 1):
        state, _ = one_step(state, -1)

    settle.settle_host()  # on this thread, last before the window: every run measures the fast class
    compiles_before = len(compiles)
    step_s, losses = [], [first_loss]
    # a traced run profiles `traced_steps` steps from the fourth of the window
    trace_dir = config["trace_dir"]
    trace_at = (3, 3 + tr["traced_steps"]) if trace_dir else (-1, -1)
    traced_t0 = traced_t1 = None
    stamps["window_start"] = time.time()
    w0 = time.perf_counter()
    while True:
        n = len(step_s)
        if n == trace_at[0]:
            jax.profiler.start_trace(trace_dir)
            traced_t0 = time.perf_counter()
        t0 = time.perf_counter()
        state, loss = one_step(state, n)
        t1 = time.perf_counter()
        step_s.append(t1 - t0)
        losses.append(loss)
        if n + 1 == trace_at[1]:
            traced_t1 = time.perf_counter()
            jax.profiler.stop_trace()
        if t1 - w0 >= config["seconds"] and n + 1 >= trace_at[1]:
            break
    window_s = time.perf_counter() - w0
    compiles_in_window = len(compiles) - compiles_before
    if trace_dir:
        # the text of the program the trace is of, for its scopes: the same lowering
        # compiles to the same program (from the compile cache, where there is one)
        with open(os.path.join(trace_dir, STEP_TEXT), "w") as f:
            f.write(step.lower(state, host_batch()).compile().as_text())

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:config["chips"]])
    train.report({
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
        "stamps": stamps, "first_step_s": first_step_s, "parity_s": parity_s,
        "step_s": step_s, "losses": losses, "window_s": window_s,
        "tokens_per_step": tr["batch"] * tr["seq"],
        "compiles_in_window": compiles_in_window,
        "xla_attention_fallbacks": attention_ops.xla_fallback_count,
        "parity": parity_out, "hot_experts_held_before_placement": held_before,
        "counters": {name: np.asarray(jax.device_get(rows)).tolist() for name, rows in counters.items()},
        "traced_window_s": None if traced_t0 is None else traced_t1 - traced_t0,
        "traced_steps": tr["traced_steps"] if trace_dir else None,
        "cache_dir": jax.config.jax_compilation_cache_dir,
    })


def run(ctx: dict) -> dict:
    import ray_tpu

    work = tempfile.mkdtemp(prefix="bench-train-")
    t0 = time.time()
    ray_tpu.init()
    try:
        out = _fit(ctx, work, t0)
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(work, ignore_errors=True)
    return out


def _fit(ctx: dict, work: str, t0: float) -> dict:
    """On a running cluster: JaxTrainer.fit() around `_loop`, then the worker's
    report shaped into the driver's result (`train_family`'s keys)."""
    import ray_tpu
    from benchmarks.lib import scope_seconds, trace_reduce
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train import JaxConfig, JaxTrainer

    cell, config, log = ctx["cell"], ctx["config"], ctx["log"]
    chips = cell["chips"]
    trace_dir = os.path.join(work, "trace") if ctx["trace"] else None
    found = ray_tpu.cluster_resources().get("TPU", 0)
    if found < chips:
        raise SystemExit(f"the cell needs {chips} TPU chip(s); this host has {found}")
    stamps = {"cluster_up": time.time()}
    result = JaxTrainer(
        _loop,
        train_loop_config={"model": ctx["model"], "trainer": config["trainer"],
                           "seed": ctx["seed"], "seconds": ctx["seconds"],
                           "chips": chips, "trace_dir": trace_dir},
        backend_config=JaxConfig(collective_group=False),
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True, chips_per_worker=chips),
        run_config=RunConfig(name=ctx["cell_name"], storage_path=os.path.join(work, "runs")),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"JaxTrainer.fit() failed: {result.error}")
    m = dict(result.metrics)

    stamps.update(m["stamps"])
    start = ctx["t_process_start"]
    log({"phase": "setup_split_s",
         "process_to_cluster_up": stamps["cluster_up"] - start,
         "cluster_start": stamps["cluster_up"] - t0,
         "worker_start": stamps["loop_entered"] - stamps["cluster_up"],
         "weights": stamps["weights_ready"] - stamps["loop_entered"],
         "compile_and_first_step": stamps["compiled"] - stamps["weights_ready"],
         "warmup_and_parity": stamps["window_start"] - stamps["compiled"],
         "of_which_parity": m["parity_s"],
         "first_step_s": m["first_step_s"], "cache_dir": m["cache_dir"]})
    tokens = len(m["step_s"]) * m["tokens_per_step"]
    parity = dict(m["parity"])
    # the system's error against the float32 reference, as a multiple of what
    # the plain reference itself loses when it computes in the system's type
    parity["ratio_rms"] = parity["system_rms"] / max(parity["yardstick_rms"], 1e-12)
    parity["ratio_max"] = parity["system_max"] / max(parity["yardstick_max"], 1e-12)
    trainer = config["trainer"]
    limit, step_limit = trainer["parity_ratio_limit"], trainer["step_parity_limit"]
    gradient, update = parity["gradient"], parity["update"]
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "no_compile_in_window": m["compiles_in_window"] == 0,
        "no_xla_attention_fallback": m["xla_attention_fallbacks"] == 0,
        "parity_with_reference": parity["ratio_rms"] <= limit["rms"]
        and parity["ratio_max"] <= limit["max"],
        "selection_agrees_beyond_margin": (
            parity["selection"]["largest_margin_where_differs"] <= trainer["selection_margin"]),
        # the step itself, against the reference (train_family's docstring)
        "step_losses_match_reference": all(
            x["rel_err"] <= step_limit["loss_rel_err"] for x in parity["losses"].values()),
        "step_gradients_match_reference": (
            gradient["ratio_worst"] <= step_limit["gradient_ratio_worst"]
            and gradient["ratio_all"] <= step_limit["gradient_ratio_all"]
            and gradient["unreached_rows_are_zero"]),
        "step_update_follows_its_moments": (
            update["moments_rel"] <= 1e-5
            and update["moved_max_abs_err"] <= 1e-6 + 1e-3 * update["lr"]),  # a float32 rounding
        "masked_tokens_are_the_batchs": parity["masked_tokens"]["system"] == parity["masked_tokens"]["batch"],
    }
    ordered = sorted(m["step_s"])
    log({"phase": "window", "steps": len(m["step_s"]), "window_s": m["window_s"],
         "tokens": tokens, "loss_first": m["losses"][0], "loss_last": m["losses"][-1],
         # where a run's seconds went by step: its first ten, and the spread of all
         "step_ms": {"first_ten_mean": 1e3 * sum(m["step_s"][:10]) / len(m["step_s"][:10]),
                     **{name: 1e3 * ordered[int(q * (len(ordered) - 1))] for name, q in
                        (("min", 0.0), ("p10", 0.1), ("p50", 0.5), ("p90", 0.9), ("max", 1.0))}},
         "hot_experts_held_before_placement": m["hot_experts_held_before_placement"],
         "held_rows_a_layer_mean": [sum(col) / len(col) for col in zip(*m["counters"]["held_assignments"])],
         "parity": parity, "parity_ratio_limit": limit,
         "step_parity_limit": step_limit, "checks": checks,
         "compiles_in_window": m["compiles_in_window"]})

    out = {
        "end_to_end": {
            "train_tokens_per_s": {"value": tokens / m["window_s"], "unit": "tokens/s"},
            # process start to the window, less the seconds of the comparison with the
            # reference, which are the yardstick's and not the system's
            "setup_s": {"value": stamps["window_start"] - start - m["parity_s"], "unit": "s"},
        },
        "device": {"platform": m["platform"], "kind": m["kind"], "count": m["count"],
                   "memory_peak_bytes": m["memory_peak_bytes"]},
        "correct": all(checks.values()), "attempted": len(m["step_s"]), "failed": 0,
        # the counters' first entries are the warm-up's steps: keep the window's
        "series": {"step_s": m["step_s"], **{
            name: rows[-len(m["step_s"]):] for name, rows in m["counters"].items()}},
        "tokens_per_step": m["tokens_per_step"], "seq": trainer["seq"],
        "chips": chips, "traced_steps": m["traced_steps"],
    }
    if trace_dir:
        reduced = trace_reduce.reduce_dir(trace_dir, n_devices=chips)
        with open(os.path.join(trace_dir, STEP_TEXT)) as f:
            reduced["op_scopes"] = scope_seconds.op_scopes(reduced["op_seconds"], f.read())
        by_scope = scope_seconds.seconds(reduced["op_seconds"], reduced["op_scopes"])
        trace_reduce.keep(trace_dir, ctx["keep_trace"])
        log({"phase": "trace", "traced_steps": m["traced_steps"],
             "traced_window_s": m["traced_window_s"],
             **{k: reduced[k] for k in ("window_s", "busy_s", "modules", "planes", "lines")},
             "seconds_by_scope": dict(sorted(by_scope.items(), key=lambda x: -x[1])[:32])})
        trace_reduce.into_result(out, reduced)
    return out
