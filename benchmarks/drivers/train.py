"""Driver `train`: JaxTrainer.fit() around make_train_step, one worker holding
the cell's chips.

The loop below is the benchmark's own and runs in the worker, which is the
only process that holds the chip: it times, checks and (in a traced run)
profiles itself, and reports one dict. The driver process turns that into the
cell's end-to-end metrics and leaves the rest for the readers.
"""
import math
import os
import shutil
import tempfile
import time


def _loop(config: dict) -> None:
    """JaxTrainer body. `config`: model keys, trainer settings, seed, seconds,
    trace directory (or None)."""
    import contextlib
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu.train as train
    from benchmarks.lib import compile_events, modelcfg, reference, settle
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh
    from ray_tpu.parallel.sharding import named_sharding
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    # the package re-exports the function under the module's name
    attention_ops = importlib.import_module("ray_tpu.ops.attention")
    stamps = {"loop_entered": time.time()}
    compiles = compile_events.listen()

    tr = config["trainer"]
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

    def host_batch():
        tokens = rng.integers(0, cfg.vocab_size, batch_shape, dtype=np.int32)
        if batch_sharding is not None:
            tokens = jax.device_put(tokens, batch_sharding)
        return {"tokens": tokens}

    def one_step(state, n):
        # the annotations cost microseconds and show only in a traced run
        with jax.profiler.StepTraceAnnotation("train_step", step_num=n):
            with jax.profiler.TraceAnnotation("host_batch"):
                batch = host_batch()
            with jax.profiler.TraceAnnotation("dispatch_and_wait"):
                state, metrics = step(state, batch)
                loss = float(metrics["loss"])  # the sync a user's loop makes to log its loss
                jax.block_until_ready(state)
        return state, loss

    with (use_mesh(mesh) if mesh is not None else contextlib.nullcontext()):
        t0 = time.perf_counter()
        state, first_loss = one_step(state, -1)  # compiles, or reads the cache
        first_step_s = time.perf_counter() - t0
        stamps["compiled"] = time.time()
        for _ in range(tr["warmup_steps_run"] - 1):
            state, _ = one_step(state, -1)

        # parity, outside the window: the system's forward pass against the
        # plain reference, same parameters, same seeded sequences, position by
        # position (a mean over thousands of positions would average errors out)
        parity_tokens = jnp.asarray(np.random.default_rng([seed, 2]).integers(
            0, cfg.vocab_size, (tr["parity_sequences"], tr["seq"] + 1), dtype=np.int32))
        if batch_sharding is not None:  # the kernel runs per shard of the batch axis
            parity_tokens = jax.device_put(parity_tokens, batch_sharding)

        def system_losses(p, t):
            logits, _, _ = llama.forward(p, t[:, :-1], cfg, return_aux=True)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            return lse - jnp.take_along_axis(logits, t[:, 1:, None], axis=-1)[..., 0]

        # three programs one after the other, so that only one holds its logits
        exact, coarse, system = (np.asarray(jax.jit(f)(state.params, parity_tokens), np.float64)
                                 for f in (
            lambda p, t: reference.next_token_losses(p, t, config["model"], jnp.float32),
            lambda p, t: reference.next_token_losses(p, t, config["model"], jnp.dtype(cfg.dtype)),
            system_losses))
        rms = lambda d: float(np.sqrt(np.mean(np.square(d))))  # noqa: E731
        parity_out = {
            "positions": int(exact.size), "loss_reference": float(exact.mean()),
            "loss_system": float(system.mean()),
            "system_rms": rms(system - exact), "system_max": float(np.abs(system - exact).max()),
            "yardstick_rms": rms(coarse - exact),
            "yardstick_max": float(np.abs(coarse - exact).max())}

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

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:config["chips"]])
    train.report({
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
        "stamps": stamps, "first_step_s": first_step_s,
        "step_s": step_s, "losses": losses, "window_s": window_s,
        "tokens_per_step": tr["batch"] * tr["seq"],
        "compiles_in_window": compiles_in_window,
        "xla_attention_fallbacks": attention_ops.xla_fallback_count,
        "parity": parity_out,
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
    report shaped into the driver's result."""
    import ray_tpu
    from benchmarks.lib import trace_reduce
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
         "first_step_s": m["first_step_s"], "cache_dir": m["cache_dir"]})
    tokens = len(m["step_s"]) * m["tokens_per_step"]
    parity = dict(m["parity"])
    # the system's error against the float32 reference, as a multiple of what
    # the plain reference itself loses when it computes in the system's type
    parity["ratio_rms"] = parity["system_rms"] / max(parity["yardstick_rms"], 1e-12)
    parity["ratio_max"] = parity["system_max"] / max(parity["yardstick_max"], 1e-12)
    limit = config["trainer"]["parity_ratio_limit"]
    checks = {
        "losses_finite": all(math.isfinite(x) for x in m["losses"]),
        "no_compile_in_window": m["compiles_in_window"] == 0,
        "no_xla_attention_fallback": m["xla_attention_fallbacks"] == 0,
        "parity_with_reference": parity["ratio_rms"] <= limit["rms"]
        and parity["ratio_max"] <= limit["max"],
    }
    log({"phase": "window", "steps": len(m["step_s"]), "window_s": m["window_s"],
         "tokens": tokens, "loss_first": m["losses"][0], "loss_last": m["losses"][-1],
         "parity": parity, "parity_ratio_limit": limit, "checks": checks,
         "compiles_in_window": m["compiles_in_window"]})

    out = {
        "end_to_end": {
            "train_tokens_per_s": {"value": tokens / m["window_s"], "unit": "tokens/s"},
            "setup_s": {"value": stamps["window_start"] - start, "unit": "s"},
        },
        "device": {"platform": m["platform"], "kind": m["kind"], "count": m["count"],
                   "memory_peak_bytes": m["memory_peak_bytes"]},
        "correct": all(checks.values()), "attempted": len(m["step_s"]), "failed": 0,
        "series": {"step_s": m["step_s"]},
        "tokens_per_step": m["tokens_per_step"], "seq": config["trainer"]["seq"],
        "chips": chips,
    }
    if trace_dir:
        reduced = trace_reduce.reduce_dir(trace_dir, n_devices=chips)
        trace_reduce.keep(trace_dir, ctx["keep_trace"])
        log({"phase": "trace", "traced_steps": m["traced_steps"],
             "traced_window_s": m["traced_window_s"],
             **{k: reduced[k] for k in ("window_s", "busy_s", "modules", "planes", "lines")}})
        trace_reduce.into_result(out, reduced)
    return out
