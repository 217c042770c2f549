"""Driver `train_family`: `train`'s loop, checks and result keys for a configuration of
any architecture family models/llama.py runs. JaxTrainer.fit() around make_train_step,
one worker holding the cell's chips.

What differs from `drivers/train.py` (which names `lib/reference.py` and Llama's
leaves): the configuration's `trainer` group names its plain reference
(`"reference": "<module of lib/>"`; default `reference`) and, for the readers, its flops
file (`"flops"`).

`correct` is decided on the timed program. A reference that has `loss` is held against
the ONE compiled step the window runs: the step's first run, from the seeded state, is on
the parity batch, and what it reports and leaves behind is compared with the float32
reference at the highest precision, evaluated on the experts the STEP chose (its metric
`experts_chosen`):
  the losses     `loss`, `ce_loss`, `mtp_loss` as the step reports them, relative error;
  the gradient   of every leaf (a row a layer of a stacked leaf). Adam's first moment
                 after one step from zero moments is (1 - b1) x the clipped gradient, so
                 the step's own gradient is read back from the state it returns. Its
                 error a row, |g - g_ref| / |g_ref|, is stated in multiples of the error
                 the same plain reference makes in the configuration's type: the worst
                 row's multiple, and all rows' together; a leaf no gradient reaches (the
                 selection bias) has none on either side;
  the bias       after the step equals the balance rule on the counts of that selection;
  the update     Adam's second moment is the first's square, and every other leaf moved
                 by AdamW's first update of those moments at the schedule's rate.
Beside it the system's forward pass alone, a loss a position and HEAD (the next-token
head and every MTP module) against the same reference, as `drivers/train.py` has it: a
mean over thousands of positions would average an error at a few of them out.
Why the selection is handed over: a near tie between the k-th and the next router score
is decided by rounding, and a token that goes to another expert moves its loss by more
than any yardstick allows. What is lost by that is checked on its own
(`selection_agrees_beyond_margin`): wherever the reference's own k-th score lies more
than `trainer.selection_margin` above the next, the step must have chosen the
reference's experts. A computation that is coarser or leaves part out still fails: the
reference's arithmetic is its own on every token.
The seconds of all this (`parity_s`: the reference's programs, the copies of the state
to the host and back) are not the system's and are left out of `setup_s`.
The step's counters (`held_assignments`, `fullest_held_expert_rows`, `mtp_loss`, where
the step's metrics have them) come back as series; a traced run also joins the trace's
operations with the compiled step's `jax.named_scope`s (`lib/scope_seconds.py`).

The loop runs in the worker, which is the only process that holds the chip: it times,
checks and (in a traced run) profiles itself, and reports one dict. The driver process
turns that into the cell's end-to-end metrics and leaves the rest for the readers.
"""
import math
import os
import shutil
import tempfile
import time

# what a step's metrics may carry beyond the loss (train/step.py), kept as series
COUNTERS = ("held_assignments", "fullest_held_expert_rows", "mtp_loss")
# leaves that no gradient reaches and the optimizer leaves alone: a rule of their own moves them
RULED_LEAVES = ("router_bias",)


def row_errors(a, b, scale=1.0):
    """Trees of like leaves -> {leaf: (|a * scale - b|^2, |b|^2), a row a layer of a leaf
    that lies in a stack (`layers/w_gate` is [layers, ...]), else one row}."""
    import jax
    import jax.numpy as jnp

    def squares(x, stacked):
        x = jnp.square(x.astype(jnp.float32))
        return x.reshape(x.shape[0], -1).sum(-1) if stacked else x.sum()[None]

    out = {}
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0], jax.tree.leaves(b)):
        stacked = len(path) > 1
        out[jax.tree_util.keystr(path)] = (squares(x * scale - y, stacked), squares(y, stacked))
    return out


def gradient_summary(system_rows: dict, coarse_rows: dict) -> dict:
    """Row errors of the system's and of the coarse reference's gradient against the
    reference's -> the worst row's relative error on each side, the worst multiple of a
    row, all rows' multiple, and whether the rows the reference leaves at zero are zero."""
    import numpy as np

    names, system, coarse, sums, zeros_agree = [], [], [], [0.0, 0.0], True
    for name, (err, ref) in system_rows.items():
        err, ref = np.asarray(err, np.float64), np.asarray(ref, np.float64)
        err_c = np.asarray(coarse_rows[name][0], np.float64)
        for i in range(ref.size):
            if ref[i] == 0:
                zeros_agree = zeros_agree and err[i] == 0
                continue
            names.append(f"{name}[{i}]")
            system.append(math.sqrt(err[i] / ref[i]))
            coarse.append(math.sqrt(err_c[i] / ref[i]))
            sums[0] += err[i]
            sums[1] += err_c[i]
    multiples = [s / max(c, 1e-12) for s, c in zip(system, coarse)]
    by_multiple = sorted(range(len(names)), key=multiples.__getitem__, reverse=True)
    worst = by_multiple[0]
    return {"rows": len(names), "system_worst_rel": max(system), "yardstick_worst_rel": max(coarse),
            "ratio_worst": multiples[worst], "ratio_worst_at": names[worst],
            "ratio_all": math.sqrt(sums[0] / max(sums[1], 1e-300)),
            "unreached_rows_are_zero": bool(zeros_agree),
            # (row, its multiple, the system's relative error, the yardstick's), for the log
            "worst_rows": [[names[i], multiples[i], system[i], coarse[i]] for i in by_multiple[:6]]}


def first_update_errors(p0, p1, mu, nu, lr, weight_decay, b1, b2, eps=1e-8):
    """One AdamW step from zero moments: (largest |nu - (1 - b2) (mu / (1 - b1))^2| over the
    largest nu, largest |p1 - p0 - update| over the leaves the optimizer moves), where
    update = -lr (m / (sqrt(v) + eps) + weight_decay p0), m and v the moments with their
    bias taken out."""
    import jax
    import jax.numpy as jnp

    moments, moved = [], []
    for (path, a), b, m, v in zip(jax.tree_util.tree_flatten_with_path(p0)[0], jax.tree.leaves(p1),
                                  jax.tree.leaves(mu), jax.tree.leaves(nu)):
        g = m / (1 - b1)
        moments.append(jnp.abs(v - (1 - b2) * jnp.square(g)).max() / jnp.maximum(v.max(), 1e-30))
        if not any(name in jax.tree_util.keystr(path) for name in RULED_LEAVES):
            update = -lr * (g / (jnp.sqrt(v / (1 - b2)) + eps) + weight_decay * a)
            moved.append(jnp.abs(b - a - update).max())
    return jnp.stack(moments).max(), jnp.stack(moved).max()


def balance_rule(bias0, chosen, n_experts: int, rate: float):
    """The selection bias after a step (DeepSeek-V3 section 2.1.2), in numpy: an expert
    that got fewer than the mean of the assignments rises by `rate`, one that got more
    falls. bias0 [layers, E]; chosen: [tokens.., k] a layer."""
    import numpy as np

    load = np.stack([np.bincount(np.asarray(c).ravel(), minlength=n_experts) for c in chosen])
    return bias0 + rate * np.sign(load.mean(-1, keepdims=True) - load), load


def _loop(config: dict) -> None:
    """JaxTrainer body. `config`: model keys, trainer settings, seed, seconds,
    trace directory (or None)."""
    import contextlib
    import importlib
    import inspect

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import ray_tpu.train as train
    from benchmarks.lib import compile_events, modelcfg, settle
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh
    from ray_tpu.parallel.sharding import named_sharding
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    # the package re-exports the function under the module's name
    attention_ops = importlib.import_module("ray_tpu.ops.attention")
    stamps = {"loop_entered": time.time()}
    compiles = compile_events.listen()

    tr = config["trainer"]
    reference = importlib.import_module(f"benchmarks.lib.{tr.get('reference', 'reference')}")
    cfg = modelcfg.model_config(config["model"])
    seed = config["seed"]
    devices = jax.devices()
    mesh = None
    if tr.get("mesh"):
        mesh = build_mesh(MeshSpec(**tr["mesh"]), devices[:config["chips"]])
    tx = make_optimizer(**tr["optimizer"])
    # weights on the device, from the seed, in one jitted call (init_state)
    state = init_state(jax.random.PRNGKey(seed & 0x7FFFFFFF), cfg, tx, mesh=mesh)
    jax.block_until_ready(state)
    stamps["weights_ready"] = time.time()
    step = make_train_step(cfg, tx)
    rng = np.random.default_rng([seed, 1])
    batch_shape = (tr["batch"], tr["seq"] + 1)
    batch_sharding = named_sharding(mesh, "batch", None) if mesh is not None else None

    def on_device(tokens):
        return tokens if batch_sharding is None else jax.device_put(tokens, batch_sharding)

    def host_batch():
        return {"tokens": on_device(rng.integers(0, cfg.vocab_size, batch_shape, dtype=np.int32))}

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
                if name in metrics:  # here would hold the next step back by its round trip
                    counters.setdefault(name, []).append(metrics[name])
        return state, loss

    counters = {}
    model = config["model"]
    by_step = hasattr(reference, "loss")  # else next-token losses of the forward pass alone
    rms = lambda d: float(np.sqrt(np.mean(np.square(d))))  # noqa: E731

    def free(*trees):  # now, whoever else still names them
        for a in jax.tree.leaves(trees):
            a.delete()

    def head_losses(logits, targets):
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]

    def system_losses(p, t):
        """The forward pass alone: (losses a position and head, joined along the
        positions; what each expert layer chose, the MTP modules' last, or None)"""
        logits, _, aux = llama.forward(p, t[:, :-1], cfg, return_aux=True)
        losses, chosen = [head_losses(logits, t[:, 1:])], None
        if cfg.moe_dropless:
            chosen = list(aux["chosen"])
        if cfg.mtp_depth:
            for m, (lg, a) in enumerate(llama.mtp_logits(p, aux["hidden"], t, cfg), 1):
                losses.append(head_losses(lg, t[:, m + 1:]))
                chosen.append(a["chosen"])
        return jnp.concatenate(losses, axis=1), by_position(chosen, t)

    def by_position(chosen, t):  # [tokens, k] a layer -> [B, S, k]
        return None if chosen is None else [c.reshape(*t[:, :-1].shape, -1) for c in chosen]

    alone = jax.jit(system_losses)

    def reference_of(dtype):
        """The reference's loss, its parts and its gradient, on a given selection."""
        def fn(p, t, chosen):
            if not by_step:
                return reference.next_token_losses(p, t, model, dtype)
            (total, parts), grads = jax.value_and_grad(reference.loss, has_aux=True)(
                p, t, model, dtype, chosen, True)
            routings = [{"own": r["own"], "margin": r["margin"]} for r in parts.pop("routings")]
            return dict(parts, loss=total, routings=routings), grads
        return jax.jit(fn)

    def selection_against(chosen, routings):
        """Where the system chose other experts than the reference would have, and how
        clear the reference's own choice was there."""
        margins, differs = [], []
        for mine, r in zip(chosen, routings):
            own = np.asarray(r["own"])  # an MTP module of the reference is a position shorter
            same = np.sort(np.asarray(mine)[:, :own.shape[1]], -1) == np.sort(own, -1)
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

    def step_parity(state0, params0, reported, tokens):
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

        chosen = by_position(list(reported["experts_chosen"]), tokens) if cfg.moe_dropless else None
        if chosen is not None:
            names = [n for n in ("layers", "mtp") if n in kept.params]
            bias0 = np.concatenate([np.asarray(params0[n]["router_bias"]) for n in names])
            bias1 = np.concatenate([np.asarray(kept.params[n]["router_bias"]) for n in names])
            ruled, load = balance_rule(bias0, chosen, cfg.n_experts, cfg.moe_bias_update_rate)
            out["router_bias"] = {
                "entries": int(bias1.size), "wrong": int((np.abs(bias1 - ruled) > 1e-7).sum()),
                "counts_equal_the_steps": bool((load == np.asarray(reported["expert_load"])).all())}

        # (as numpy, like the step's: the reference's compiled program is then the same one)
        system, chosen_alone = jax.device_get(alone(p0, tokens))
        done("forward_alone")
        alone_differs = 0 if chosen is None else int(sum(  # as sets: the order weighs nothing
            (np.sort(np.asarray(a), -1) != np.sort(np.asarray(b), -1)).any(-1).sum()
            for a, b in zip(chosen, chosen_alone)))
        exact_fn, coarse_fn = reference_of(jnp.float32), reference_of(jnp.dtype(cfg.dtype))
        exact, grads = exact_fn(p0, tokens, chosen)
        jax.block_until_ready(grads)
        done("reference_float32")
        # the step clipped its gradient to the norm it reports, and Adam kept (1 - b1) of it
        scale = max(1.0, float(reported["grad_norm"]) / opt["grad_clip"]) / (1 - b1)
        errors = jax.jit(row_errors)
        mu = jax.device_put(optax.tree_utils.tree_get(kept.opt_state, "mu"), shardings.params)
        system_rows = jax.device_get(errors(mu, grads, scale))
        free(mu)
        done("gradient_rows")
        coarse, coarse_grads = coarse_fn(p0, tokens, chosen)
        coarse_rows = jax.device_get(errors(coarse_grads, grads))
        done("reference_coarse")
        norm = math.sqrt(sum(float(np.sum(ref)) for _, ref in system_rows.values()))
        free(grads, coarse_grads)
        out["gradient"] = dict(gradient_summary(system_rows, coarse_rows),
                               norm_system=float(reported["grad_norm"]), norm_reference=norm)
        out["losses"] = {}
        for name in ("loss", "ce_loss", "mtp_loss"):
            if name in reported:
                ref = float(exact[name])
                out["losses"][name] = {
                    "system": float(reported[name]), "reference": ref,
                    "rel_err": abs(float(reported[name]) - ref) / abs(ref),
                    "yardstick_rel_err": abs(float(coarse[name]) - ref) / abs(ref)}
        if chosen is not None:
            out["selection"] = selection_against(chosen, exact["routings"])
        # the forward pass alone, a position at a time; where it chose as the step did
        # (it is the same arithmetic) the reference's numbers are already there
        exact_p, coarse_p = exact["position_losses"], coarse["position_losses"]
        if alone_differs:
            exact_p = exact_fn(p0, tokens, chosen_alone)[0]["position_losses"]
            coarse_p = coarse_fn(p0, tokens, chosen_alone)[0]["position_losses"]
        out.update(positions_against(system, exact_p, coarse_p),
                   forward_alone_chose_otherwise=alone_differs)
        free(p0)
        done("reference_again_for_the_forward_alone")
        state0 = jax.block_until_ready(jax.device_put(kept, shardings))
        done("state_back")
        return dict(out, seconds=laps), state0

    with (use_mesh(mesh) if mesh is not None else contextlib.nullcontext()):
        # parity, outside the window, on seeded sequences of the step's own shape
        parity_tokens = on_device(np.random.default_rng([seed, 2]).integers(
            0, cfg.vocab_size, batch_shape if by_step else (tr["parity_sequences"], tr["seq"] + 1),
            dtype=np.int32))
        parity_s = time.perf_counter()
        params0 = jax.device_get(state.params) if by_step else None  # the step donates its state
        parity_s = time.perf_counter() - parity_s

        t0 = time.perf_counter()
        if by_step:  # the one compiled step's first run is the parity batch's
            state, reported = step(state, {"tokens": parity_tokens})
            first_loss = float(reported["loss"])
            jax.block_until_ready(state)
        else:
            state, first_loss = one_step(state, -1)
        first_step_s = time.perf_counter() - t0  # compiles, or reads the cache
        stamps["compiled"] = time.time()

        t0 = time.perf_counter()
        if by_step:
            parity_out, state = step_parity(state, params0, jax.device_get(reported), parity_tokens)
            del params0, reported
        else:
            system = alone(state.params, parity_tokens)[0]
            exact = reference_of(jnp.float32)(state.params, parity_tokens, None)
            coarse = reference_of(jnp.dtype(cfg.dtype))(state.params, parity_tokens, None)
            parity_out = positions_against(system, exact, coarse)
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
        "parity": parity_out,
        "counters": {name: np.asarray(jax.device_get(rows)).tolist() for name, rows in counters.items()},
        "traced_window_s": None if traced_t0 is None else traced_t1 - traced_t0,
        "traced_steps": tr["traced_steps"] if trace_dir else None,
        "cache_dir": jax.config.jax_compilation_cache_dir,
    })


STEP_TEXT = "step_program.txt"  # beside the trace, in the run's scratch directory


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
    report shaped into the driver's result."""
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
    limit = trainer["parity_ratio_limit"]
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "no_compile_in_window": m["compiles_in_window"] == 0,
        "no_xla_attention_fallback": m["xla_attention_fallbacks"] == 0,
        "parity_with_reference": parity["ratio_rms"] <= limit["rms"]
        and parity["ratio_max"] <= limit["max"],
    }
    if "selection" in parity:
        checks["selection_agrees_beyond_margin"] = (
            parity["selection"]["largest_margin_where_differs"] <= trainer["selection_margin"])
    if "gradient" in parity:  # the step itself, against the reference (the module's docstring)
        step_limit = trainer["step_parity_limit"]
        gradient, update = parity["gradient"], parity["update"]
        checks["step_losses_match_reference"] = all(
            x["rel_err"] <= step_limit["loss_rel_err"] for x in parity["losses"].values())
        checks["step_gradients_match_reference"] = (
            gradient["ratio_worst"] <= step_limit["gradient_ratio_worst"]
            and gradient["ratio_all"] <= step_limit["gradient_ratio_all"]
            and gradient["unreached_rows_are_zero"])
        checks["step_update_follows_its_moments"] = (
            update["moments_rel"] <= 1e-5
            and update["moved_max_abs_err"] <= 1e-6 + 1e-3 * update["lr"])  # a float32 rounding
    if "router_bias" in parity:
        checks["router_bias_moved_by_the_rule"] = (
            parity["router_bias"]["wrong"] == 0 and parity["router_bias"]["counts_equal_the_steps"])
    ordered = sorted(m["step_s"])
    log({"phase": "window", "steps": len(m["step_s"]), "window_s": m["window_s"],
         "tokens": tokens, "loss_first": m["losses"][0], "loss_last": m["losses"][-1],
         # where a run's seconds went by step: its first ten, and the spread of all
         "step_ms": {"first_ten_mean": 1e3 * sum(m["step_s"][:10]) / len(m["step_s"][:10]),
                     **{name: 1e3 * ordered[int(q * (len(ordered) - 1))] for name, q in
                        (("min", 0.0), ("p10", 0.1), ("p50", 0.5), ("p90", 0.9), ("max", 1.0))}},
         "parity": parity, "parity_ratio_limit": limit,
         "step_parity_limit": trainer.get("step_parity_limit"), "checks": checks,
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
