"""Benchmark: Llama train-step MFU on the available accelerator.

Prints ONE JSON line: {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}.
Baseline: the north-star target of 40% MFU via the stock Trainer API (BASELINE.json),
scored here as achieved-MFU / 0.40 on the single-chip flagship-family model.

All diagnostics go to stderr; stdout carries only the JSON line.
"""
import json
import os
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# TPU bf16 peak FLOP/s per chip by device-kind substring (Google Cloud TPU
# documentation, per-generation system architecture pages). A device that is
# not in the table is an error, not a default.
PEAK_FLOPS = {
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def peak_flops_for(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    for key, val in PEAK_FLOPS.items():
        if key in kind:
            return val
    raise ValueError(f"no peak FLOP/s on record for device kind {kind!r}: "
                     "MFU needs the chip's published peak (PEAK_FLOPS)")


def run_one(model_name: str, batch: int, seq: int, steps: int,
            remat_policy: str) -> tuple:
    import jax

    from ray_tpu.models import get_config
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    dev = jax.devices()[0]
    cfg = get_config(model_name)
    if remat_policy != cfg.remat_policy:
        import dataclasses

        # save matmul outputs, recompute only elementwise: a few pp MFU over
        # full remat whenever the saved activations still fit HBM
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy)
    log(f"model={model_name} n_params={cfg.n_params/1e9:.3f}B batch={batch} seq={seq} "
        f"remat={remat_policy}")

    tx = make_optimizer(total_steps=1000)
    state = init_state(jax.random.PRNGKey(0), cfg, tx)
    step = make_train_step(cfg, tx)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)
    batch_dict = {"tokens": tokens}

    t0 = time.perf_counter()
    state, metrics = step(state, batch_dict)
    jax.block_until_ready(state)
    log(f"compile+first step: {time.perf_counter() - t0:.1f}s "
        f"loss={float(metrics['loss']):.3f}")

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch_dict)
    jax.block_until_ready(state)
    dt = (time.perf_counter() - t0) / steps
    final_loss = float(metrics["loss"])

    tokens_per_sec = batch * seq / dt
    flops_per_token = 6 * cfg.n_params  # standard fwd+bwd transformer estimate
    mfu = tokens_per_sec * flops_per_token / peak_flops_for(dev)
    log(f"step={dt*1e3:.1f}ms tokens/s={tokens_per_sec:,.0f} "
        f"mfu={mfu:.3f} loss={final_loss:.3f}")
    return mfu, tokens_per_sec


def _trainer_loop(config) -> None:
    """The stock-Trainer-path measurement body: identical model/step/config as
    run_one, but driven inside a JaxTrainer.fit() worker session (BASELINE.md:25
    words the north star as MFU 'via a stock Trainer API' — this measures exactly
    that, not the bare step function)."""
    import dataclasses
    import time

    import jax

    import ray_tpu.train as train
    from ray_tpu.models import get_config
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    cfg = get_config(config["model"])
    if config["remat"] != cfg.remat_policy:
        cfg = dataclasses.replace(cfg, remat_policy=config["remat"])
    batch, seq, steps = config["batch"], config["seq"], config["steps"]
    tx = make_optimizer(total_steps=1000)
    state = init_state(jax.random.PRNGKey(0), cfg, tx)
    step = make_train_step(cfg, tx)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)
    batch_dict = {"tokens": tokens}
    state, metrics = step(state, batch_dict)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch_dict)
    jax.block_until_ready(state)
    dt = (time.perf_counter() - t0) / steps
    final_loss = float(metrics["loss"])
    tokens_per_sec = batch * seq / dt
    mfu = tokens_per_sec * 6 * cfg.n_params / peak_flops_for(jax.devices()[0])
    train.report({"mfu": mfu, "tokens_per_sec": tokens_per_sec, "loss": final_loss})


def run_trainer_path(model_name: str, batch: int, seq: int, steps: int,
                     remat_policy: str, grad_sync=None) -> tuple:
    """Same measurement as run_one but through JaxTrainer.fit() (1 worker owning the
    chip). Returns (mfu, tokens_per_sec) reported from inside the session. The
    calling process must not have initialised a JAX backend: the chip belongs
    to one process, and here that is the worker.

    grad_sync: GradSyncConfig handed to the workers via JaxConfig — how the
    winning `--grad-sync` row reaches the default trainer-path MFU run (the
    worker's make_train_step picks it up from env)."""
    import tempfile

    import ray_tpu
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train import JaxConfig, JaxTrainer

    log(f"trainer-path: model={model_name} batch={batch} seq={seq} steps={steps} "
        f"grad_sync={grad_sync}")
    from ray_tpu.core.accelerators import (ensure_compile_cache_dir,
                                           jax_backend_untouched)

    if not jax_backend_untouched():
        raise RuntimeError("this process initialised a JAX backend before "
                           "starting the worker that needs the chip")
    ensure_compile_cache_dir()
    ray_tpu.init(num_cpus=2)
    try:
        # use_tpu: the worker must be spawned with the "tpu" accel tag — plain
        # CPU workers force JAX_PLATFORMS=cpu and would run the model on host
        scaling = ScalingConfig(num_workers=1, use_tpu=True, chips_per_worker=1)
        trainer = JaxTrainer(
            _trainer_loop,
            train_loop_config={"model": model_name, "batch": batch, "seq": seq,
                               "steps": steps, "remat": remat_policy},
            backend_config=JaxConfig(collective_group=False, grad_sync=grad_sync),
            scaling_config=scaling,
            run_config=RunConfig(name="bench_trainer_path",
                                 storage_path=tempfile.mkdtemp(prefix="bench_tp_")),
        )
        result = trainer.fit()
        if result.error is not None:
            raise RuntimeError(f"trainer-path bench failed: {result.error}")
        m = result.metrics
        log(f"trainer-path: mfu={m['mfu']:.3f} tokens/s={m['tokens_per_sec']:,.0f} "
            f"loss={m['loss']:.3f}")
        return m["mfu"], m["tokens_per_sec"]
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------- grad-sync bench
# `bench.py --grad-sync`: paired device-plane gradient-sync rows (monolithic vs
# bucketed vs bucketed+int8 vs +sharded-update) -> TRAIN_SYNC_BENCH.json, the
# evidence behind train/grad_sync.py. The device-mesh section runs in a child
# with its own 8-device CPU platform (the multichip dryrun mesh); the
# loss-parity section runs on the native backend (llama-500m when a TPU is
# attached); the sharded-HBM section is analytic at llama3-8b fsdp-pod
# geometry. The winning mesh-section config is wired into the default
# trainer-path MFU row via JaxConfig(grad_sync=...).

GRAD_SYNC_MODES = {
    "monolithic": {},
    "bucketed": {"mode": "bucketed"},
    "bucketed_int8": {"mode": "bucketed", "compression": "int8"},
    "bucketed_int8_sharded": {"mode": "bucketed", "compression": "int8",
                              "sharded_update": True},
    "sharded_update": {"sharded_update": True},
}


def _grad_sync_child() -> None:
    """Child body for the device-mesh section: dp=8 virtual-CPU mesh, every
    mode stepped in interleaved rounds (drift-fair), one JSON line out."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import get_config
    from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh
    from ray_tpu.parallel.sharding import named_sharding
    from ray_tpu.train import (GradSyncConfig, grad_sync, init_state,
                               make_optimizer, make_train_step)

    model = os.environ.get("BENCH_SYNC_MODEL", "test-tiny")
    batch = int(os.environ.get("BENCH_SYNC_BATCH", "16"))
    seq = int(os.environ.get("BENCH_SYNC_SEQ", "64"))
    steps = int(os.environ.get("BENCH_SYNC_STEPS", "8"))
    rounds = int(os.environ.get("BENCH_SYNC_ROUNDS", "3"))
    ndev = len(jax.devices())
    cfg = get_config(model)
    mesh = build_mesh(MeshSpec(dp=-1).resolve(ndev), jax.devices())
    tx = make_optimizer(total_steps=10_000)
    tokens_host = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)

    runs = {}
    with use_mesh(mesh):
        tokens = jax.device_put(tokens_host, named_sharding(mesh, "batch", None))
        batch_dict = {"tokens": tokens}
        for name, kw in GRAD_SYNC_MODES.items():
            sync = GradSyncConfig(**kw)
            state = init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh, sync=sync)
            step = make_train_step(cfg, tx, donate=False, sync=sync)
            overlap = None
            if not sync.is_default:
                overlap = grad_sync.overlap_report(
                    step.lower(state, batch_dict).compile())
            state, metrics = step(state, batch_dict)  # compile + step 1
            losses = [float(metrics["loss"])]
            runs[name] = {"sync": sync, "step": step, "state": state,
                          "losses": losses, "overlap": overlap, "best_dt": None}
        for _ in range(rounds):
            for name, run in runs.items():
                step, state = run["step"], run["state"]
                t0 = _time.perf_counter()
                for _ in range(steps):
                    state, metrics = step(state, batch_dict)
                loss = float(metrics["loss"])  # fetch = sync point
                dt = (_time.perf_counter() - t0) / steps
                run["state"] = state
                run["losses"].append(loss)
                if run["best_dt"] is None or dt < run["best_dt"]:
                    run["best_dt"] = dt

    out = {}
    for name, run in runs.items():
        payload = grad_sync.sync_payload_bytes(run["state"].params, run["sync"])
        out[name] = {
            "tokens_per_sec": round(batch * seq / run["best_dt"], 1),
            "step_ms": round(run["best_dt"] * 1e3, 2),
            "losses": [round(v, 6) for v in run["losses"]],
            "payload_f32_bytes": payload["f32_bytes"],
            "payload_bytes": payload["compressed_bytes"],
            "overlap": run["overlap"],
        }
    print("GRAD_SYNC_RESULT " + json.dumps(
        {"model": model, "batch": batch, "seq": seq, "steps": steps,
         "world": ndev, "modes": out}))


def _grad_sync_hbm_child() -> None:
    """Analytic sharded-optimizer HBM rows at llama3-8b pod geometry (needs a
    64-device platform; nothing compiles or materializes)."""
    import jax

    from ray_tpu.models import get_config
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import grad_sync, make_optimizer

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import __graft_entry__ as ge

    gib = 1024**3
    cfg = get_config("llama3-8b", dtype="bfloat16", remat_policy="full")
    tx = make_optimizer(total_steps=10)
    mesh = build_mesh(MeshSpec(dp=8, fsdp=8).resolve(64), jax.devices()[:64])
    state = ge._abstract_train_state(cfg, mesh, tx)
    base = grad_sync.opt_state_bytes_per_shard(
        grad_sync.abstract_sharded_opt_state(tx, state.params, mesh, axes=()))
    sharded = grad_sync.opt_state_bytes_per_shard(
        grad_sync.abstract_sharded_opt_state(
            tx, state.params, mesh, axes=("dp", "fsdp")))
    print("GRAD_SYNC_HBM " + json.dumps({
        "mesh": "dp8xfsdp8", "model": "llama3-8b",
        "opt_state_gib_inherited": round(base / gib, 3),
        "opt_state_gib_sharded_update": round(sharded / gib, 3),
        "cut_factor": round(base / max(sharded, 1), 2),
    }))


def _run_child(target: str, n_devices: int, timeout: int = 1200) -> dict:
    """Run a child bench body on a fresh virtual-CPU platform, parse its
    marker line."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    marker = {"mesh": "GRAD_SYNC_RESULT ", "hbm": "GRAD_SYNC_HBM ",
              "pipeline": "PIPELINE_RESULT ", "rl": "RL_RESULT "}[target]
    fn = {"mesh": "_grad_sync_child", "hbm": "_grad_sync_hbm_child",
          "pipeline": "_pipeline_child", "rl": "_rl_child"}[target]
    proc = subprocess.run(
        [sys.executable, "-c", f"import bench; bench.{fn}()"],
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        log(proc.stderr[-4000:])
        raise RuntimeError(f"bench child {target} failed rc={proc.returncode}")
    line = next((ln for ln in proc.stdout.splitlines()
                 if ln.startswith(marker)), None)
    if line is None:
        log(proc.stderr[-4000:])
        raise RuntimeError(f"bench child {target} printed no {marker!r}")
    return json.loads(line[len(marker):])


def _loss_parity_section() -> dict:
    """f32 vs int8 grad-sync loss curves on the native backend — llama-500m on
    an accelerator, test-tiny on CPU — plus the analytic payload-bytes cut."""
    import time as _time

    import jax

    from ray_tpu.models import get_config
    from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh
    from ray_tpu.parallel.sharding import named_sharding
    from ray_tpu.train import (GradSyncConfig, grad_sync, init_state,
                               make_optimizer, make_train_step)

    on_cpu = jax.default_backend() == "cpu"
    model = os.environ.get("BENCH_SYNC_PARITY_MODEL",
                           "test-tiny" if on_cpu else "llama-500m")
    batch = int(os.environ.get("BENCH_SYNC_PARITY_BATCH", "8" if on_cpu else "4"))
    seq = int(os.environ.get("BENCH_SYNC_PARITY_SEQ", "64" if on_cpu else "512"))
    steps = int(os.environ.get("BENCH_SYNC_PARITY_STEPS", "10"))
    cfg = get_config(model)
    mesh = build_mesh(MeshSpec(dp=-1).resolve(len(jax.devices())), jax.devices())
    tx = make_optimizer(total_steps=10_000)
    tokens_host = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)

    curves = {}
    payload = {}
    with use_mesh(mesh):
        tokens = jax.device_put(tokens_host, named_sharding(mesh, "batch", None))
        batch_dict = {"tokens": tokens}
        for name, kw in (("f32", {"mode": "bucketed"}),
                         ("int8", {"mode": "bucketed", "compression": "int8",
                                   "stochastic_rounding": True})):
            sync = GradSyncConfig(**kw)
            state = init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh, sync=sync)
            step = make_train_step(cfg, tx, donate=False, sync=sync)
            losses = []
            for _ in range(steps):
                state, metrics = step(state, batch_dict)
                losses.append(float(metrics["loss"]))
            curves[name] = losses
            payload[name] = grad_sync.sync_payload_bytes(state.params, sync)
    max_rel = max(abs(a - b) / max(abs(a), 1e-9)
                  for a, b in zip(curves["f32"], curves["int8"]))
    return {
        "model": model, "batch": batch, "seq": seq, "steps": steps,
        "world": len(jax.devices()),
        "loss_f32": [round(v, 5) for v in curves["f32"]],
        "loss_int8": [round(v, 5) for v in curves["int8"]],
        "max_rel_divergence": round(max_rel, 6),
        "payload_f32_bytes": payload["int8"]["f32_bytes"],
        "payload_int8_bytes": payload["int8"]["compressed_bytes"],
        "bytes_cut_factor": round(
            payload["int8"]["f32_bytes"]
            / max(payload["int8"]["compressed_bytes"], 1), 2),
    }


def run_grad_sync_bench() -> None:
    log("grad-sync bench: device-mesh section (8-device virtual-CPU child)")
    mesh_rows = _run_child("mesh", 8)
    log("grad-sync bench: loss-parity section (native backend)")
    parity = _loss_parity_section()
    log("grad-sync bench: sharded-optimizer HBM section (analytic, 64 devices)")
    hbm = _run_child("hbm", 64, timeout=900)

    modes = mesh_rows["modes"]
    mono = modes["monolithic"]
    # f32 modes must track the monolithic loss curve bit-for-bit-ish; int8
    # modes within the documented tolerance
    checks = {
        "bucketed_matches_monolithic": max(
            abs(a - b) for a, b in zip(modes["bucketed"]["losses"],
                                       mono["losses"])) < 1e-5,
        "bucketed_ge_monolithic_tokens_per_sec":
            modes["bucketed"]["tokens_per_sec"]
            >= mono["tokens_per_sec"] * 0.999,
        "int8_halves_payload_bytes":
            modes["bucketed_int8"]["payload_bytes"] * 2
            <= modes["bucketed_int8"]["payload_f32_bytes"],
        "int8_loss_parity": parity["max_rel_divergence"] < 0.02,
        "sharded_update_cuts_opt_hbm_2x": hbm["cut_factor"] >= 2.0,
        "bucketed_reductions_not_sunk":
            not modes["bucketed"]["overlap"]["all_sunk_to_end"],
    }
    ranked = sorted(
        (name for name in modes
         if name in ("monolithic", "bucketed")),  # f32-exact candidates only
        key=lambda n: modes[n]["tokens_per_sec"], reverse=True)
    winning = ranked[0]
    result = {
        "device_mesh": mesh_rows,
        "loss_parity": parity,
        "sharded_hbm": hbm,
        "checks": checks,
        "winning": {"name": winning,
                    "config": GRAD_SYNC_MODES[winning]},
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "TRAIN_SYNC_BENCH.json"), "w") as f:
        json.dump(result, f, indent=2)
    for name, ok in checks.items():
        log(f"grad-sync check {name}: {'PASS' if ok else 'FAIL'}")
    print(json.dumps({
        "metric": "grad_sync_bucketed_tokens_per_sec_dp8",
        "value": modes["bucketed"]["tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": round(modes["bucketed"]["tokens_per_sec"]
                             / max(mono["tokens_per_sec"], 1e-9), 4),
        "secondary": {
            "monolithic_tokens_per_sec": mono["tokens_per_sec"],
            "int8_payload_cut_factor": parity["bytes_cut_factor"],
            "int8_max_rel_loss_divergence": parity["max_rel_divergence"],
            "sharded_opt_hbm_cut_factor": hbm["cut_factor"],
            "checks_passed": sum(checks.values()),
            "checks_total": len(checks),
        },
    }))


def _pipeline_child() -> None:
    """Child body for --pipeline: pp=2 multichip dryrun (2 virtual CPU devices).

    Three measurements on the same 2-stage residual-MLP model and microbatch
    decomposition:
      spmd_baseline       one jitted program — value_and_grad through
                          `pipeline_spmd` on a pure-pp mesh, plus SGD
      mpmd_1f1b           cross-process MPMD runner, 1F1B + prefetch overlap
      mpmd_gpipe_noprefetch  unoverlapped control (all-fwd-then-all-bwd order,
                          prefetch off) — the bubble-fraction gate's baseline
    Tokens/s counts microbatch rows per optimizer step. Prints one
    PIPELINE_RESULT line to stdout."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    d = int(os.environ.get("BENCH_PIPE_D", "512"))
    mb = int(os.environ.get("BENCH_PIPE_MB", "16"))
    m = int(os.environ.get("BENCH_PIPE_M", "4"))
    steps = int(os.environ.get("BENCH_PIPE_STEPS", "5"))
    pp, lr = 2, 1e-2
    rows = m * mb
    log(f"pipeline child: pp={pp} d={d} mb={mb} m={m} steps={steps}")

    def stage_fn(p, x):
        return x + jnp.tanh(x @ p["w"]) @ p["w2"]

    def mb_loss(y):
        return jnp.mean(y ** 2)

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    stacked = {"w": jax.random.normal(k1, (pp, d, 2 * d)) * 0.1,
               "w2": jax.random.normal(k2, (pp, 2 * d, d)) * 0.1}
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (rows, d)),
                   np.float32)

    # -- (a) single-program pipeline_spmd baseline ------------------------------
    from jax.sharding import Mesh

    from ray_tpu.parallel import use_mesh
    from ray_tpu.parallel.pipeline import pipeline

    mesh = Mesh(np.array(jax.devices()[:pp]), ("pp",))

    def full_loss(params, xx):
        with use_mesh(mesh):
            y = pipeline(stage_fn, params, xx, num_microbatches=m, mesh=mesh)
        y_mb = y.reshape(m, mb, d)
        return jnp.mean(jnp.stack([mb_loss(y_mb[i]) for i in range(m)]))

    @jax.jit
    def spmd_step(params, xx):
        loss, g = jax.value_and_grad(full_loss)(params, xx)
        new = jax.tree_util.tree_map(
            lambda pv, gv: pv - jnp.float32(lr) * gv, params, g)
        return new, loss

    params = stacked
    params, loss = spmd_step(params, x)
    log(f"spmd baseline compile+first step done loss={float(loss):.5f}")
    t0 = time.perf_counter()
    for _ in range(steps):
        params, loss = spmd_step(params, x)
    final = float(loss)  # fetch = sync point for the whole dependent chain
    dt = (time.perf_counter() - t0) / steps
    spmd_row = {"tokens_per_sec": round(rows / dt, 1),
                "step_ms": round(dt * 1e3, 2), "loss": final}
    log(f"spmd baseline: {spmd_row}")

    # -- (b)/(c) cross-process MPMD runner --------------------------------------
    import ray_tpu
    from ray_tpu.train.mpmd_pipeline import MPMDPipeline, MPMDPipelineConfig

    ray_tpu.init(num_cpus=4, worker_env={"JAX_PLATFORMS": "cpu"})
    stage_params = [jax.tree_util.tree_map(lambda p: np.asarray(p[s]), stacked)
                    for s in range(pp)]

    def measure(schedule: str, prefetch: int, group: str) -> dict:
        cfg = MPMDPipelineConfig(num_microbatches=m, schedule=schedule,
                                 prefetch=prefetch, learning_rate=lr,
                                 group_name=group)
        pipe = MPMDPipeline([stage_fn] * pp, stage_params, loss_fn=mb_loss,
                            microbatch_spec=((mb, d), np.float32), cfg=cfg)
        try:
            pipe.step(0, x)           # compile step
            pipe.reset_timelines()    # bubble gate wants steady state only
            t0 = time.perf_counter()
            out = {}
            for i in range(steps):
                out = pipe.step(i + 1, x)
            dt = (time.perf_counter() - t0) / steps
            fractions = pipe.bubble_fractions()
            admission = pipe.admission()
        finally:
            pipe.shutdown()
        row = {"schedule": schedule, "prefetch": prefetch,
               "tokens_per_sec": round(rows / dt, 1),
               "step_ms": round(dt * 1e3, 2), "loss": out.get("loss"),
               "bubble_mean": round(fractions["mean"], 4),
               "bubble_per_stage": {k: round(v, 4) for k, v in
                                    fractions.items() if k != "mean"},
               "admission": admission}
        log(f"mpmd {schedule}/prefetch={prefetch}: {row}")
        return row

    try:
        r_1f1b = measure("1f1b", 2, "mpmd_bench_1f1b")
        r_gpipe = measure("gpipe", 0, "mpmd_bench_gpipe")
    finally:
        ray_tpu.shutdown()

    print("PIPELINE_RESULT " + json.dumps({
        "pp": pp, "d": d, "microbatch": mb, "num_microbatches": m,
        "steps": steps, "rows_per_step": rows,
        "spmd_baseline": spmd_row,
        "mpmd_1f1b": r_1f1b,
        "mpmd_gpipe_noprefetch": r_gpipe,
    }))


def run_pipeline_bench() -> None:
    """--pipeline: MPMD cross-process pipeline vs the single-program
    pipeline_spmd baseline, multichip-dryrun pp=2 row. Gates (non-zero exit on
    failure): MPMD tokens/s >= the baseline's, and the overlapped schedule's
    measured bubble fraction below the unoverlapped control's."""
    log("pipeline bench: pp=2 multichip-dryrun child (2 virtual CPU devices)")
    row = _run_child("pipeline", 2, timeout=1500)
    checks = {
        "mpmd_tokens_per_sec_ge_spmd_baseline":
            row["mpmd_1f1b"]["tokens_per_sec"]
            >= row["spmd_baseline"]["tokens_per_sec"],
        "overlapped_bubble_below_unoverlapped":
            row["mpmd_1f1b"]["bubble_mean"]
            < row["mpmd_gpipe_noprefetch"]["bubble_mean"],
        "no_leaked_activation_blocks": all(
            c == {"published": 0, "inflight_pulls": 0}
            for r in (row["mpmd_1f1b"], row["mpmd_gpipe_noprefetch"])
            for c in r["admission"]),
    }
    result = {"rows": [dict(row, mesh="pp2_multichip_dryrun")],
              "checks": checks}
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "PIPELINE_BENCH.json"), "w") as f:
        json.dump(result, f, indent=2)
    for name, ok in checks.items():
        log(f"pipeline check {name}: {'PASS' if ok else 'FAIL'}")
    mpmd = row["mpmd_1f1b"]["tokens_per_sec"]
    base = row["spmd_baseline"]["tokens_per_sec"]
    print(json.dumps({
        "metric": "mpmd_pipeline_tokens_per_sec_pp2",
        "value": mpmd,
        "unit": "tokens/s",
        "vs_baseline": round(mpmd / max(base, 1e-9), 4),
        "secondary": {
            "spmd_baseline_tokens_per_sec": base,
            "bubble_1f1b_prefetch": row["mpmd_1f1b"]["bubble_mean"],
            "bubble_gpipe_noprefetch":
                row["mpmd_gpipe_noprefetch"]["bubble_mean"],
            "checks_passed": sum(checks.values()),
            "checks_total": len(checks),
        },
    }))
    if not all(checks.values()):
        sys.exit(1)


# ----------------------------------------------------------- decoupled RL

# One geometry for every --rl row (a lean policy head keeps the bench
# transport-bound — the regime the rollout plane optimizes; both serialized
# rows and the decoupled row train the exact same model and SGD schedule).
_RL_TRAIN = dict(lr=3e-4, gamma=0.99, lambda_=0.95, clip_param=0.3,
                 entropy_coeff=0.01, train_batch_size=512,
                 minibatch_size=128, num_epochs=2)
_RL_MODEL = {"fcnet_hiddens": [8]}


def _rl_ppo_config(env):
    from ray_tpu.rllib.algorithms.ppo import PPOConfig

    return (PPOConfig().environment(env)
            .training(**_RL_TRAIN)
            .rl_module(model_config=dict(_RL_MODEL))
            .debugging(seed=0))


def _rl_serialized(host_slicing: bool, iters: int) -> dict:
    """One serialized PPO cycle: classic sample -> pickle episodes -> GAE ->
    update loop. host_slicing=True is the seed baseline (host re-slice +
    re-upload per minibatch); False is the device-resident gather path
    (`serialized_opt` row)."""
    import ray_tpu
    from bench_rllib import SyntheticAtariEnv

    if host_slicing:
        os.environ["RAY_TPU_RL_HOST_SLICING"] = "1"
    else:
        os.environ.pop("RAY_TPU_RL_HOST_SLICING", None)
    ray_tpu.init(num_cpus=4, worker_env={"JAX_PLATFORMS": "cpu"})
    try:
        cfg = (_rl_ppo_config(SyntheticAtariEnv)
               .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                            rollout_fragment_length=64))
        algo = cfg.build_algo()
        try:
            algo.train()  # warmup: compiles sampler + learner
            t0 = time.perf_counter()
            rets = []
            for _ in range(iters):
                r = algo.train()
                rets.append(r.get("episode_return_mean") or 0.0)
            dt = time.perf_counter() - t0
            batch = _RL_TRAIN["train_batch_size"]
            mb_per_iter = _RL_TRAIN["num_epochs"] * (
                batch // _RL_TRAIN["minibatch_size"])
            return {
                "env_steps_per_s": round(iters * batch / dt, 1),
                "updates_per_s": round(iters * mb_per_iter / dt, 1),
                "episode_return": round(sum(rets[-2:]) / 2, 2),
            }
        finally:
            algo.cleanup()
    finally:
        ray_tpu.shutdown()


def _rl_decoupled(iters: int) -> dict:
    """Decoupled cycle: 2 vectorized rollout workers (48 envs each) stream
    trajectory blocks over the data plane into the device-resident learner;
    weights broadcast back every 3 updates. Rates are measured at drained
    steady state (post-warmup backlog consumed before the clock starts)."""
    import ray_tpu
    from bench_rllib import SyntheticAtariEnv

    os.environ.pop("RAY_TPU_RL_HOST_SLICING", None)
    ray_tpu.init(num_cpus=4, worker_env={"JAX_PLATFORMS": "cpu"})
    try:
        B = 48
        cfg = (_rl_ppo_config(SyntheticAtariEnv)
               .env_runners(num_env_runners=2, num_envs_per_env_runner=B,
                            rollout_fragment_length=64)
               .decoupled_rollout(enabled=True, blocks_per_update=1,
                                  queue_depth=8, max_block_lag=4,
                                  weight_sync_interval=3))
        algo = cfg.build_algo()
        try:
            algo.train()  # warmup: compiles both sides
            for _ in range(3):  # drain the block backlog built during compile
                algo.train()
            sampled = lambda: sum(  # noqa: E731
                m.get("num_env_steps_sampled") or 0
                for m in algo.rollout_plane.worker_metrics())
            base = sampled()
            t0 = time.perf_counter()
            n_upd = 0
            for _ in range(iters):
                if algo.train().get("num_env_steps_trained"):
                    n_upd += 1
            dt = time.perf_counter() - t0
            steps = sampled() - base
            mb_per_round = _RL_TRAIN["num_epochs"] * (
                (64 * B) // _RL_TRAIN["minibatch_size"])
            rets = [m["episode_return_mean"]
                    for m in algo.rollout_plane.worker_metrics()
                    if m.get("episode_return_mean") is not None]
            out = {
                "env_steps_per_s": round(steps / dt, 1),
                "updates_per_s": round(n_upd * mb_per_round / dt, 1),
                "episode_return": round(sum(rets) / max(len(rets), 1), 2),
                "update_rounds": n_upd,
            }
        finally:
            algo.cleanup()
        out["plane"] = algo.final_plane_stats
        return out
    finally:
        ray_tpu.shutdown()


def _rl_dry() -> dict:
    """--dry-run body: tiny CartPole serialized + decoupled cycles. Proves
    the full path (block transport, staleness filter, weight broadcast,
    release accounting) end-to-end in seconds; rate/return gates are
    meaningless at this size and are skipped by the parent."""
    import ray_tpu
    from ray_tpu.rllib.algorithms.ppo import PPOConfig

    def tiny(decoupled):
        ray_tpu.init(num_cpus=3, worker_env={"JAX_PLATFORMS": "cpu"})
        try:
            cfg = (PPOConfig().environment("CartPole-v1")
                   .env_runners(num_env_runners=1, num_envs_per_env_runner=2,
                                rollout_fragment_length=32)
                   .training(lr=3e-4, train_batch_size=64, minibatch_size=32,
                             num_epochs=1, gamma=0.99, lambda_=0.95)
                   .rl_module(model_config={"fcnet_hiddens": [16]})
                   .debugging(seed=0))
            if decoupled:
                cfg = cfg.decoupled_rollout(
                    enabled=True, blocks_per_update=1, queue_depth=4,
                    max_block_lag=4, weight_sync_interval=1)
            algo = cfg.build_algo()
            n_upd = 0
            try:
                for _ in range(3):
                    if algo.train().get(
                            "num_env_steps_trained" if decoupled
                            else "num_env_steps_sampled"):
                        n_upd += 1
            finally:
                algo.cleanup()
            out = {"update_rounds": n_upd}
            if decoupled:
                out["plane"] = algo.final_plane_stats
            return out
        finally:
            ray_tpu.shutdown()

    ser = tiny(decoupled=False)
    dec = tiny(decoupled=True)
    return {"dry_run": True,
            "serialized": dict(ser, env_steps_per_s=0.0, updates_per_s=0.0,
                               episode_return=0.0),
            "serialized_opt": None,
            "decoupled": dict(dec, env_steps_per_s=0.0, updates_per_s=0.0,
                              episode_return=0.0)}


def _rl_child() -> None:
    """Child body for --rl: three init/shutdown cycles on one process
    (serialized baseline, serialized_opt, decoupled) so every row sees an
    identical platform."""
    if os.environ.get("BENCH_RL_DRY") == "1":
        print("RL_RESULT " + json.dumps(_rl_dry()), flush=True)
        return
    row = {
        "dry_run": False,
        "serialized": _rl_serialized(host_slicing=True, iters=4),
        "serialized_opt": _rl_serialized(host_slicing=False, iters=4),
        "decoupled": _rl_decoupled(iters=10),
    }
    print("RL_RESULT " + json.dumps(row), flush=True)


def run_rl_bench() -> None:
    """--rl: decoupled actor-learner PPO vs the serialized baseline on the
    synthetic-Atari transport workload. Gates (non-zero exit on failure):
    decoupled env-steps/s AND learner-updates/s >= 3x the serialized
    baseline at matched final return, trained-block staleness p99 within
    the configured bound, and zero leaked block admissions after clean
    shutdown. --dry-run swaps in a tiny CartPole config and keeps only the
    structural gates (leaks, staleness, liveness)."""
    dry = "--dry-run" in sys.argv[1:]
    if dry:
        os.environ["BENCH_RL_DRY"] = "1"
    log("rl bench: decoupled rollout/learn plane vs serialized PPO"
        + (" [dry-run]" if dry else ""))
    row = _run_child("rl", 1, timeout=2400)
    ser, dec = row["serialized"], row["decoupled"]
    plane = dec["plane"]
    checks = {
        "learner_made_progress": dec.get("update_rounds", 0) > 0,
        "block_lag_p99_within_bound":
            (plane.get("lag_p99_taken") or 0) <= plane["max_lag"],
        "zero_leaked_block_admissions":
            plane["outstanding"] == 0 and plane["unreleased"] == 0
            and plane.get("worker_outstanding", 0) == 0,
    }
    if not dry:
        checks["env_steps_ge_3x_serialized"] = (
            dec["env_steps_per_s"] >= 3.0 * ser["env_steps_per_s"])
        checks["learner_updates_ge_3x_serialized"] = (
            dec["updates_per_s"] >= 3.0 * ser["updates_per_s"])
        # decoupled trains on ~3x the data in the window; "matched" means
        # it must never come out BELOW the serialized run's return
        checks["matched_final_return"] = (
            dec["episode_return"] >= ser["episode_return"] - 1.0)
        _rl_rewrite_bench_json(row)
    for name, ok in checks.items():
        log(f"rl check {name}: {'PASS' if ok else 'FAIL'}")
    print(json.dumps({
        "metric": "rl_decoupled_env_steps_per_s_atari_synth",
        "value": dec["env_steps_per_s"],
        "unit": "env_steps/s",
        "vs_baseline": round(
            dec["env_steps_per_s"] / max(ser["env_steps_per_s"], 1e-9), 4)
            if not dry else 0.0,
        "secondary": {
            "decoupled_updates_per_s": dec["updates_per_s"],
            "serialized_env_steps_per_s": ser["env_steps_per_s"],
            "serialized_updates_per_s": ser["updates_per_s"],
            "updates_vs_baseline": round(
                dec["updates_per_s"] / max(ser["updates_per_s"], 1e-9), 4)
                if not dry else 0.0,
            "block_lag_p99_taken": plane.get("lag_p99_taken"),
            "checks_passed": sum(checks.values()),
            "checks_total": len(checks),
        },
    }))
    if not all(checks.values()):
        sys.exit(1)


def _rl_rewrite_bench_json(row: dict) -> None:
    """Rewrite RL_BENCH.json in place: refresh the atari-synth PPO rows,
    preserve every other row (data pipeline, shuffle, cartpole, tpu_learner,
    notes) verbatim."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "RL_BENCH.json")
    try:
        with open(path) as f:
            out = json.load(f)
    except (OSError, ValueError):
        out = {}
    out = {k: v for k, v in out.items()
           if not k.startswith("ppo_atari_synth")
           and not k.startswith("rl_decoupled")}
    for name, r in (("ppo_atari_synth_serialized", row["serialized"]),
                    ("ppo_atari_synth_serialized_opt", row["serialized_opt"]),
                    ("ppo_atari_synth_decoupled", row["decoupled"])):
        out[f"{name}_env_steps_per_s"] = r["env_steps_per_s"]
        out[f"{name}_updates_per_s"] = r["updates_per_s"]
        out[f"{name}_episode_return"] = r["episode_return"]
    out["rl_decoupled_plane_stats"] = row["decoupled"]["plane"]
    out["rl_decoupled_note"] = (
        "atari-synth rows share one geometry: fcnet [8], batch 512, "
        "minibatch 128, 2 epochs. serialized = seed host-slicing loop "
        "(2x4 envs); serialized_opt = device-resident gather SGD; "
        "decoupled = 2x48-env vectorized rollout plane streaming blocks "
        "over the zero-copy data plane, weights broadcast every 3 updates.")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    log(f"wrote {path}")


def _winning_grad_sync():
    """The winning --grad-sync config (TRAIN_SYNC_BENCH.json), as a
    GradSyncConfig for the trainer-path MFU row; None when the bench has not
    run or the stock config won."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "TRAIN_SYNC_BENCH.json")
    try:
        with open(path) as f:
            kw = json.load(f)["winning"]["config"]
        if not kw:
            return None
        from ray_tpu.train import GradSyncConfig

        return GradSyncConfig(**kw)
    except Exception:
        return None


def main() -> None:
    """Train-step MFU on one chip. There is no CPU variant of this number: with
    no chip the command fails and prints no metric line."""
    from ray_tpu.core.accelerators import (TPUAcceleratorManager,
                                           jax_platforms_exclude_tpu)

    if (jax_platforms_exclude_tpu()
            or TPUAcceleratorManager.get_current_node_num_accelerators() < 1):
        log("bench.py measures on a TPU and found none "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}); "
            "the host-plane gates (--rl, --pipeline, --grad-sync) run without one")
        sys.exit(1)

    env_model = os.environ.get("BENCH_MODEL")
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    seq = int(os.environ.get("BENCH_SEQ", "2048"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    remat = os.environ.get("BENCH_REMAT", "dots_no_batch")

    if env_model:
        mfu, _ = run_one(env_model, batch, seq, steps, remat)
        print(json.dumps({
            "metric": f"train_mfu_{env_model}_b{batch}_s{seq}",
            "value": round(mfu, 4),
            "unit": "mfu_fraction",
            "vs_baseline": round(mfu / 0.40, 4),
        }))
        return

    # Headline: llama3-8b LAYER GEOMETRY at single-chip depth — the realistic
    # arithmetic-intensity regime (d_model 4096, GQA 32/8, d_ff 14336); b6 with
    # remat "dots" is the largest batch that held in 16 GB when last swept.
    # The historical llama-500m number rides along.
    # Order matters: the trainer path runs FIRST, in a worker that owns the
    # chip, while this process has not touched JAX. Only after that cluster is
    # shut down (its tpu worker gone) do the bare-step runs take the chip in
    # this process.
    mfu_fit, _ = run_trainer_path("llama8b-geom2", 6, 2048, steps, "dots",
                                  grad_sync=_winning_grad_sync())
    mfu_8b, _ = run_one("llama8b-geom2", 6, 2048, steps, "dots")
    mfu_500m, _ = run_one("llama-500m", 8, 2048, steps, "dots_no_batch")
    # Headline = the STOCK TRAINER API number — exactly how BASELINE.md:25 words
    # the 40%-MFU north star. The bare step function rides along as secondary.
    result = {
        "metric": "train_mfu_llama8b_geometry_trainer_fit_b6_s2048",
        "value": round(mfu_fit, 4),
        "unit": "mfu_fraction",
        "vs_baseline": round(mfu_fit / 0.40, 4),
        "secondary": {
            "train_mfu_llama8b_geometry_bare_step_b6_s2048": round(mfu_8b, 4),
            "train_mfu_llama-500m_b8_s2048": round(mfu_500m, 4),
        },
    }
    print(json.dumps(result))


if __name__ == "__main__":
    if "--grad-sync" in sys.argv[1:]:
        run_grad_sync_bench()
    elif "--pipeline" in sys.argv[1:]:
        run_pipeline_bench()
    elif "--rl" in sys.argv[1:]:
        run_rl_bench()
    else:
        main()
