"""chip_smoke.py — the trainer and the LLM server on a TPU, through their normal
entry points.

    python chip_smoke.py            # one chip: kernel, train, train again (cache), serve
    python chip_smoke.py --chips 4  # one host of four: sharded train, tensor-parallel
                                    # serve, two one-chip workers; nothing else

This process never initialises a JAX backend: a chip belongs to one process at
a time, so everything that touches the device runs in a ray_tpu worker or serve
replica, and the device named in the last line is what those workers saw. Each
phase prints one JSON object; a phase that fails raises, and the exit code is
then non-zero. The last line is `{"ok": ..., "device": {...}}` and nothing
else. There is no mode that passes without a TPU: sizes may be given as
arguments to rehearse the script tiny under JAX_PLATFORMS=cpu (with
RAY_TPU_NUM_TPUS standing in for detection), and then the last line says
`"ok": false`, as it does for any size that differs from the defaults.
"""
import argparse
import json
import os
import socket
import sys
import time

# what `memory_stats()["bytes_limit"]` reports on a v5e chip (first chip run, PR 22)
V5E_BYTES_LIMIT = 16_909_336_064
# kept free next to weights and KV pool: the runtime's own 258 MB, activations, logits
SERVE_RESERVE_BYTES = 3 << 29  # 1.5 GiB
# the fused paged decode burst holds up to two more copies of the pool as
# temporaries (XLA's memory analysis of the program compiled for a v5e,
# tests/test_tpu_compile.py): the pool is counted three times
SERVE_POOL_COPIES = 3

KERNEL_SHAPE = dict(batch=2, seq=2048, heads=32, kv_heads=8, head_dim=128)
# bf16 in and out, f32 accumulation: the forward output and the three
# gradients against attention_reference on the same inputs, as the largest
# absolute error over the largest reference magnitude (a bf16 ulp is 2^-8 of it)
KERNEL_TOL = 2e-2
CACHE_HIT = "/jax/compilation_cache/cache_hits"
LOSS_TOL_4CHIP = 2e-2  # |loss(dp2 x fsdp2) - loss(one device)|, every step
# tensor_parallel_size=4 against 1, the same bf16 weights and the same token
# history: rms of the difference of the two [vocab] logit vectors over the rms
# of the tp=1 vector, at every position. tp=4 rounds four partial sums to bf16
# (2^-8) where tp=1 rounds one, twice a layer; a wrong sharding gives ~1.4.
LOGIT_RMS_TOL = 2.0 ** -5
# greedy tokens may part only at a near-tie: where tp=1's best logit leads its
# second by no more than this many times the rms logit difference measured at
# that position (two logits each moved by ~1 rms, with room for the tail)
NEAR_TIE_RMS = 6.0


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def parent_backend_untouched() -> bool:
    from ray_tpu.core.accelerators import jax_backend_untouched

    return jax_backend_untouched()


def serve_depth(max_num_seqs: int, max_model_len: int, model: str) -> dict:
    """Whole layers of `model` whose bf16 weights plus the KV pool for
    max_num_seqs x max_model_len tokens fit one v5e chip (shape arithmetic
    only: no JAX backend is touched)."""
    from ray_tpu.models import get_config

    cfg = get_config(model)
    fixed = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2) + cfg.d_model
    per_layer_w = (cfg.n_params - fixed) // cfg.n_layers * 2
    per_layer_kv = 2 * max_num_seqs * max_model_len * cfg.n_kv_heads * cfg.head_dim * 2
    budget = V5E_BYTES_LIMIT - SERVE_RESERVE_BYTES - fixed * 2
    depth = max(1, min(cfg.n_layers,
                       budget // (per_layer_w + SERVE_POOL_COPIES * per_layer_kv)))
    return {"depth": int(depth), "published_depth": cfg.n_layers,
            "weights_bytes": int(fixed * 2 + depth * per_layer_w),
            "kv_pool_bytes": int(depth * per_layer_kv)}


# ------------------------------------------------------------------ worker bodies
# Module-level: workers are spawned and re-import this file as __mp_main__.

def _device_report():
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _kernel_phase(shape: dict, seed: int) -> dict:
    """Compiled (not interpreted) flash attention, forward and backward,
    against attention_reference, with and without segment ids; and the rotate
    kernel in front of it against models/llama.py:rope."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.core.accelerators import check_worker_platform
    from ray_tpu.models.llama import rope
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops.attention import attention_reference

    check_worker_platform()
    b, s, h, kv, d = (shape[k] for k in ("batch", "seq", "heads", "kv_heads", "head_dim"))
    kq, kk, kv_, kg = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, kv, d), jnp.bfloat16)
    v = jax.random.normal(kv_, (b, s, kv, d), jnp.bfloat16)
    g = jax.random.normal(kg, (b, s, h, d), jnp.bfloat16)
    # three packed documents per row
    seg = (jnp.arange(s)[None, :] // (-(-s // 3))).astype(jnp.int32).repeat(b, 0)
    out = dict(_device_report(), interpreted=bool(fa._interpret()), cases={})

    def err(a, ref):
        a, ref = a.astype(jnp.float32), ref.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - ref))), float(jnp.max(jnp.abs(ref)))

    def case(hlo, errs):
        return {
            "kernel_in_program": "tpu_custom_call" in hlo,
            "max_abs_err": {n: e for n, (e, _) in errs.items()},
            "ref_max_abs": {n: m for n, (_, m) in errs.items()},
            "worst_relative": max(e / m for e, m in errs.values()),
        }

    # the rotate kernel: every row of positions starts somewhere else
    pos = jnp.arange(s, dtype=jnp.int32)[None, :] + 1000 * jnp.arange(b, dtype=jnp.int32)[:, None]
    cts = (g.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

    def rotated(fn):
        def both(q, k):
            rot, vjp = jax.vjp(fn, q, k)
            return (*rot, *vjp(cts))
        return jax.jit(both)

    kernel = rotated(lambda q, k: fa.rope_to_heads(q, k, pos, 1e6))
    plain = rotated(lambda q, k: tuple(rope(x, pos, 1e6).transpose(0, 2, 1, 3) for x in (q, k)))
    out["cases"]["rope"] = case(
        kernel.lower(q, k).compile().as_text(),
        {n: err(a, ref) for n, a, ref in zip(("q", "k", "dq", "dk"), kernel(q, k), plain(q, k))})
    # the reference a few kv heads at a time (heads are independent), so that its
    # [heads, S, S] float32 scores fit at any sequence length: 2 GB of them a call
    group = max(1, min(kv, (1 << 29) // (s * s * (h // kv))))
    for name, segment_ids in (("causal", None), ("segment_ids", seg)):
        def run(fn, g):
            def loss(q, k, v):
                o = fn(q, k, v, causal=True, segment_ids=segment_ids)
                return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32)), o
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))

        flash = run(fa.flash_attention, g)
        hlo = flash.lower(q, k, v).compile().as_text()
        (_, o1), g1 = flash(q, k, v)
        errs = {}
        for j in range(0, kv, group):
            heads, kvs = slice(j * (h // kv), (j + group) * (h // kv)), slice(j, j + group)
            sl = lambda x, hs: x[:, :, hs].astype(jnp.float32)  # noqa: E731
            with jax.default_matmul_precision("highest"):  # float32 products as such
                (_, o2), g2 = run(attention_reference, g[:, :, heads])(
                    sl(q, heads), sl(k, kvs), sl(v, kvs))
            for n, a, ref, hs in zip(("fwd", "dq", "dk", "dv"), (o1, *g1), (o2, *g2),
                                     (heads, heads, kvs, kvs)):
                e, m = err(a[:, :, hs], ref)
                errs[n] = (max(e, errs.get(n, (0, 0))[0]), max(m, errs.get(n, (0, 0))[1]))
        out["cases"][name] = case(hlo, errs)
    return out


def _train_loop(config: dict) -> None:
    """JaxTrainer body: a few steps of make_train_step on a repeated batch."""
    import dataclasses
    import importlib

    import jax
    import numpy as np

    import ray_tpu.train as train
    from ray_tpu.models import get_config
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    # the package re-exports the function under the module's name
    attention_ops = importlib.import_module("ray_tpu.ops.attention")

    events = []
    jax.monitoring.register_event_listener(lambda name, **kw: events.append(name))
    cfg = dataclasses.replace(get_config(config["model"]), remat_policy=config["remat"])
    tx = make_optimizer(warmup_steps=1, total_steps=1000)
    state = init_state(jax.random.PRNGKey(config["seed"]), cfg, tx)
    step = make_train_step(cfg, tx)
    tokens = jax.random.randint(jax.random.PRNGKey(config["seed"] + 1),
                                (config["batch"], config["seq"] + 1), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    t0, hits0 = time.perf_counter(), events.count(CACHE_HIT)
    hlo = step.lower(state, batch).compile().as_text()
    compile_s = time.perf_counter() - t0
    step_from_cache = events.count(CACHE_HIT) > hits0
    kernel_in_step = "tpu_custom_call" in hlo
    losses, step_s = [], []
    for _ in range(config["steps"]):
        # called as a user calls it: the first call traces again and takes
        # the program compiled above out of the cache
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        jax.block_until_ready(state)
        step_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    dev = jax.devices()[0]
    train.report(dict(
        _device_report(), model=cfg.name, n_params=cfg.n_params,
        batch=config["batch"], seq=config["seq"], remat=config["remat"],
        losses=losses, compile_s=compile_s, step_s=step_s,
        step_s_median=float(np.median(step_s[1:] or step_s)),
        kernel_in_step=kernel_in_step,
        xla_attention_fallbacks=attention_ops.xla_fallback_count,
        cache_dir=jax.config.jax_compilation_cache_dir,
        step_from_cache=step_from_cache,
        peak_bytes_in_use=int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)),
    ))


def _train_loop_4chip(config: dict) -> None:
    """One worker, four chips: dp=2 x fsdp=2 against a one-device mesh of
    devices[:1], same seeds and batch, in this one process."""
    import dataclasses
    import gc

    import jax

    import ray_tpu.train as train
    from ray_tpu.models import get_config
    from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh
    from ray_tpu.parallel.sharding import named_sharding
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    cfg = dataclasses.replace(get_config(config["model"]), remat_policy=config["remat"])
    tokens = jax.random.randint(jax.random.PRNGKey(config["seed"] + 1),
                                (config["batch"], config["seq"] + 1), 0, cfg.vocab_size)
    tokens = jax.device_get(tokens)

    def run(mesh):
        tx = make_optimizer(warmup_steps=1, total_steps=1000)
        state = init_state(jax.random.PRNGKey(config["seed"]), cfg, tx, mesh=mesh)
        jax.block_until_ready(state)
        in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                  for d in jax.devices()]
        step = make_train_step(cfg, tx)
        with use_mesh(mesh):
            batch = {"tokens": jax.device_put(
                tokens, named_sharding(mesh, "batch", None))}
            hlo = step.lower(state, batch).compile().as_text()
            losses, step_s = [], []
            for _ in range(config["steps"]):
                t0 = time.perf_counter()
                state, metrics = step(state, batch)
                jax.block_until_ready(state)
                step_s.append(time.perf_counter() - t0)
                losses.append(float(metrics["loss"]))
        return {"losses": losses, "step_s": step_s, "state_bytes_in_use": in_use,
                "kernel_in_step": "tpu_custom_call" in hlo,
                "collectives": sorted({op for op in (
                    "all-reduce", "all-gather", "reduce-scatter") if op in hlo})}

    devices = jax.devices()
    sharded = run(build_mesh(MeshSpec(dp=2, fsdp=2), devices[:4]))
    gc.collect()
    single = run(build_mesh(MeshSpec(), devices[:1]))
    train.report(dict(_device_report(), model=cfg.name, batch=config["batch"],
                      seq=config["seq"], sharded=sharded, single=single))


def _engine_tokens(model: str, overrides: dict, tp: int, max_num_seqs: int,
                   max_model_len: int, prompts, max_tokens: int, history=None) -> dict:
    """Greedy tokens from JaxLLMEngine at the given tensor-parallel degree
    (its paged prefill and fused decode, as a request takes them), and the
    logits of its prefill program at every position of `history` (this run's
    own tokens when None), so that two degrees are compared on one history."""
    import jax
    import numpy as np

    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.config import SamplingParams
    from ray_tpu.llm.engine import JaxLLMEngine

    eng = JaxLLMEngine(LLMConfig(
        model_id=f"tp{tp}", model_source=model, engine_kwargs=overrides,
        dtype="bfloat16", max_num_seqs=max_num_seqs, max_model_len=max_model_len,
        kv_layout="paged", enable_prefix_caching=False, tensor_parallel_size=tp))
    eng.start()
    try:
        toks = [eng.generate_sync(p, SamplingParams(
            max_tokens=max_tokens, temperature=0.0, stop_token_ids=[-1])).token_ids
            for p in prompts]
        logits = []
        for p, forced in zip(prompts, history or toks):
            ids = eng.tokenizer.encode(p)
            logits.append(np.stack([
                np.asarray(eng._prefill_kv_tensors(ids + list(forced[:i]))[2], np.float32)
                for i in range(len(forced))]))
        m = eng.device_report()
    finally:
        eng.shutdown()
    return dict(_device_report(), tp=tp, tokens=toks, logits=logits,
                bytes_in_use=[int((d.memory_stats() or {}).get("bytes_in_use", 0))
                              for d in jax.devices()],
                param_dtype=m["param_dtype"])


def _one_chip_worker(tag: int, hold_s: float) -> dict:
    """What a worker holding TPU: 1 sees, while a sibling holds another chip."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    y = float(jnp.sum((x @ x).astype(jnp.float32)))
    time.sleep(hold_s)  # stay alive while the sibling starts
    return {"tag": tag, "pid": os.getpid(), "matmul_sum": y,
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "devices": [(d.id, getattr(d, "coords", None)) for d in jax.devices()],
            **_device_report()}


# ------------------------------------------------------------------------- phases

def _init_cluster(ray_tpu):
    """ray_tpu.init() with no arguments, from a parent that holds no chip."""
    assert parent_backend_untouched(), "the parent initialised a JAX backend"
    ray_tpu.init()
    from ray_tpu.core import object_store

    res = ray_tpu.cluster_resources()
    if res.get("TPU", 0) < 1:
        ray_tpu.shutdown()
        print(json.dumps({"ok": False, "device": None,
                          "error": "no TPU chip was found on this host"}), flush=True)
        sys.exit(1)
    if object_store._arena_disabled:
        raise RuntimeError("the native shared-memory arena did not come up")
    return res


def _kernel_shape(args) -> dict:
    return {**KERNEL_SHAPE, "seq": args.kernel_seq, **json.loads(args.kernel_shape)}


def phase_kernel(args) -> dict:
    import ray_tpu

    res = _init_cluster(ray_tpu)
    try:
        out = ray_tpu.get(ray_tpu.remote(num_tpus=1)(_kernel_phase).remote(
            _kernel_shape(args), args.seed))
    finally:
        ray_tpu.shutdown()
    out = dict(phase="kernel", shape=_kernel_shape(args),
               dtype="bfloat16", tolerance=KERNEL_TOL, cluster_tpus=res.get("TPU", 0), **out)
    emit(out)
    for name, c in out["cases"].items():
        if not c["worst_relative"] <= KERNEL_TOL:
            raise RuntimeError(f"kernel phase: {name} beyond tolerance: {c}")
    return out


def _fit(loop, config: dict, chips: int, name: str) -> dict:
    import tempfile

    import ray_tpu
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train import JaxConfig, JaxTrainer

    _init_cluster(ray_tpu)
    try:
        result = JaxTrainer(
            loop, train_loop_config=config,
            backend_config=JaxConfig(collective_group=False),
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=chips),
            run_config=RunConfig(name=name, storage_path=tempfile.mkdtemp(prefix=name)),
        ).fit()
        if result.error is not None:
            raise RuntimeError(f"{name}: JaxTrainer.fit() failed: {result.error}")
        return dict(result.metrics)
    finally:
        ray_tpu.shutdown()


def phase_train(args, steps: int, label: str) -> dict:
    m = _fit(_train_loop, {"model": args.train_model, "batch": args.train_batch,
                           "seq": args.train_seq, "steps": steps, "remat": "dots",
                           "seed": args.seed}, 1, "chip_smoke_train")
    out = {k: m[k] for k in (
        "platform", "kind", "count", "model", "n_params", "batch", "seq", "remat",
        "losses", "compile_s", "step_s", "step_s_median", "kernel_in_step",
        "xla_attention_fallbacks", "cache_dir", "step_from_cache",
        "peak_bytes_in_use")}
    reduced = {"n_layers": "2 of llama3-8b's 32", "vocab_size": "32000 of 128256",
               "why": "f32 params + Adam state of the full depth and vocabulary "
                      "do not fit 16 GB"} if args.train_model == "llama8b-geom2" else None
    out = dict(phase=label, reduced=reduced, **out)
    emit(out)
    losses = out["losses"]
    if not all(x == x and abs(x) != float("inf") for x in losses):
        raise RuntimeError(f"{label}: non-finite loss {losses}")
    if len(losses) > 1 and not losses[-1] < losses[0]:
        raise RuntimeError(f"{label}: loss did not fall on a repeated batch: {losses}")
    if out["xla_attention_fallbacks"]:
        raise RuntimeError(f"{label}: attention(impl='auto') left the kernel for XLA")
    return out


def phase_serve(args) -> dict:
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig, build_openai_app

    res = _init_cluster(ray_tpu)
    try:
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        serve.start(http_options={"port": port})
        cfg = LLMConfig(
            model_id="smoke", model_source=args.serve_model,
            engine_kwargs=dict(args.serve_overrides, n_layers=args.serve_layers),
            dtype="bfloat16",
            max_num_seqs=args.max_num_seqs, max_model_len=args.max_model_len,
            # paged pool; the repeat-determinism check below wants one program
            # for both passes, and a warm prefix hit is a different program
            kv_layout="paged", enable_prefix_caching=False)
        t0 = time.perf_counter()
        serve.run(build_openai_app([cfg]), name="smoke", route_prefix="/v1")
        replica_ready_s = time.perf_counter() - t0
        handle = serve.get_app_handle("smoke")

        def chat(content: str, max_tokens: int = 16):
            return {"model": "smoke", "max_tokens": max_tokens, "temperature": 0.0,
                    "messages": [{"role": "user", "content": content}]}

        first = handle.options(method_name="chat").remote(
            chat("Say hello to the chip.")).result(timeout_s=900)
        first_s = time.perf_counter() - t0
        again = handle.options(method_name="chat").remote(
            chat("Say hello to the chip.")).result(timeout_s=300)
        t0 = time.perf_counter()
        pending = [handle.options(method_name="chat").remote(chat(f"Count to {i}.", 32))
                   for i in range(3, 7)]
        batch = [p.result(timeout_s=600) for p in pending]
        batch_s = time.perf_counter() - t0
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/chat/completions",
            data=json.dumps(chat("Say hello to the chip.")).encode(),
            headers={"Content-Type": "application/json"})
        http = json.loads(urllib.request.urlopen(req, timeout=300).read())
        server = serve.get_deployment_handle("llm:smoke", "smoke")
        replica = dict(
            server.options(method_name="device_report").remote().result(timeout_s=60),
            **server.options(method_name="metrics").remote().result(timeout_s=60))
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    text = lambda r: r["choices"][0]["message"]["content"]  # noqa: E731
    out = dict(
        phase="serve", cluster_tpus=res.get("TPU", 0), model=args.serve_model,
        depth_kept=args.serve_layers, vocab_size=replica["vocab_size"],
        max_num_seqs=args.max_num_seqs, max_model_len=args.max_model_len,
        platform=replica["platform"], kind=replica["device_kind"],
        count=replica["device_count"], param_dtype=replica["param_dtype"],
        peak_bytes_in_use=max(replica["peak_bytes_in_use"]),
        tokens_generated=replica["total_generated"],
        replica_ready_s=replica_ready_s, first_answer_s=first_s, batch_of_4_s=batch_s,
        batch_tokens=sum(r["usage"]["completion_tokens"] for r in batch),
        same_prompt_same_tokens=text(first) == text(again) == text(http),
        completion_tokens=[r["usage"]["completion_tokens"] for r in (first, again, http, *batch)])
    emit(out)
    if not out["same_prompt_same_tokens"]:
        raise RuntimeError("serve: the same greedy prompt gave different answers: "
                           f"{text(first)!r} / {text(again)!r} / {text(http)!r}")
    if out["param_dtype"] != "bfloat16":
        raise RuntimeError(f"serve: weights are {out['param_dtype']}, not LLMConfig.dtype")
    if min(out["completion_tokens"]) < 1:
        raise RuntimeError("serve: a chat request generated no token")
    return out


def phase_train_4chip(args) -> dict:
    m = _fit(_train_loop_4chip, {"model": args.train_model, "batch": args.train_batch,
                                 "seq": args.train_seq, "steps": args.train_steps,
                                 "remat": "dots", "seed": args.seed}, 4,
             "chip_smoke_train4")
    sh, one = m["sharded"], m["single"]
    diff = max(abs(a - b) for a, b in zip(sh["losses"], one["losses"]))
    spread = sh["state_bytes_in_use"]
    out = dict(phase="train_4chip", platform=m["platform"], kind=m["kind"],
               count=m["count"], model=m["model"], batch=m["batch"], seq=m["seq"],
               mesh="dp=2 x fsdp=2", loss_max_abs_diff=diff, tolerance=LOSS_TOL_4CHIP,
               sharded=sh, single=one)
    emit(out)
    if diff > LOSS_TOL_4CHIP:
        raise RuntimeError(f"train_4chip: losses differ by {diff}")
    alone = one["state_bytes_in_use"][0]
    if alone:  # the CPU backend of a rehearsal reports no memory
        # fsdp=2 halves the state on each device, dp=2 repeats it: every device
        # holds about half of what one device holds alone, none holds it all
        if not (min(spread[:4]) > 0.3 * alone and max(spread[:4]) < 0.75 * alone):
            raise RuntimeError(f"train_4chip: state not spread over four devices: "
                               f"{spread} against {alone} on one")
    return out


def phase_serve_4chip(args) -> dict:
    import numpy as np

    import ray_tpu

    prompts = ["user: tell me about chip number 0\nassistant:",
               "The quick brown fox jumps over the lazy dog. Then",
               "def fib(n):\n    return n if n < 2 else",
               "1, 1, 2, 3, 5, 8, 13,"]
    runs = {}
    for tp in (1, 4):  # tp=1 first: its tokens are the history both are held to
        _init_cluster(ray_tpu)
        try:
            runs[tp] = ray_tpu.get(ray_tpu.remote(num_tpus=4)(_engine_tokens).remote(
                args.serve_model, dict(args.serve_overrides, n_layers=args.serve_layers),
                tp, args.max_num_seqs, args.max_model_len, prompts, 8,
                runs[1]["tokens"] if tp == 4 else None))
        finally:
            ray_tpu.shutdown()

    def rms(x) -> float:
        return float(np.sqrt(np.mean(np.square(x, dtype=np.float64))))

    per_prompt, worst_rel, partings = [], 0.0, []
    for n, (t1, t4, l1, l4) in enumerate(zip(
            runs[1]["tokens"], runs[4]["tokens"], runs[1]["logits"], runs[4]["logits"])):
        rel = [rms(b - a) / rms(a) for a, b in zip(l1, l4)]
        top2 = np.sort(np.partition(l1, -2, axis=-1)[:, -2:], axis=-1)
        margin = (top2[:, 1] - top2[:, 0]).tolist()
        common = next((i for i, (a, b) in enumerate(zip(t1, t4)) if a != b), len(t1))
        worst_rel = max(worst_rel, *rel)
        per_prompt.append({"common_prefix": common, "logit_rel_rms": rel,
                           "tp1_top2_margin": margin,
                           "tp1_tokens_are_prefill_argmax":
                               [int(a) for a in l1.argmax(-1)] == list(t1)})
        if common < len(t1):
            # the one position where the two engines chose differently on the
            # same history: how far tp=4's choice trails in tp=1's own logits
            i, d = common, rms(l4[common] - l1[common])
            partings.append({
                "prompt": n, "position": i, "tp1_token": t1[i], "tp4_token": t4[i],
                "tp1_top2_margin": margin[i], "logit_diff_rms": d,
                "tp4_token_trails_by": float(l1[i].max() - l1[i][t4[i]]),
                "near_tie_bound": NEAR_TIE_RMS * d})
    out = dict(phase="serve_4chip", platform=runs[4]["platform"], kind=runs[4]["kind"],
               count=runs[4]["count"], model=args.serve_model, depth_kept=args.serve_layers,
               param_dtype=runs[4]["param_dtype"],
               tp4_tokens=runs[4]["tokens"], tp1_tokens=runs[1]["tokens"],
               identical=runs[4]["tokens"] == runs[1]["tokens"],
               logit_rel_rms_max=worst_rel, logit_rel_rms_tolerance=LOGIT_RMS_TOL,
               near_tie_rms=NEAR_TIE_RMS, partings=partings, prompts=per_prompt,
               tp4_bytes_in_use=runs[4]["bytes_in_use"],
               tp1_bytes_in_use=runs[1]["bytes_in_use"])
    emit(out)
    if not worst_rel <= LOGIT_RMS_TOL:
        raise RuntimeError(f"serve_4chip: tensor_parallel_size=4 logits are off "
                           f"tp=1's by {worst_rel} rms (tolerance {LOGIT_RMS_TOL})")
    for part in partings:
        if part["tp4_token_trails_by"] > part["near_tie_bound"]:
            raise RuntimeError("serve_4chip: tensor_parallel_size=4 chose another "
                               f"token than tp=1 where there was no near-tie: {part}")
    return out


def phase_two_one_chip_workers(args) -> dict:
    """Two workers holding TPU: 1 each on one host, and one holding TPU: 2,
    alive at the same time."""
    import ray_tpu

    res = _init_cluster(ray_tpu)
    try:
        fn = ray_tpu.remote(num_tpus=1)(_one_chip_worker)
        # and a third process beside them that holds the other two chips
        pair = ray_tpu.remote(num_tpus=2)(_one_chip_worker)
        *seen, two = ray_tpu.get([fn.remote(0, 20.0), fn.remote(1, 20.0),
                                  pair.remote(2, 20.0)], timeout=300)
    finally:
        ray_tpu.shutdown()
    out = dict(phase="two_one_chip_workers", cluster_tpus=res.get("TPU", 0), workers=seen,
               two_chip_worker=two)
    emit(out)
    if two["count"] != 2 or two["pid"] in {w["pid"] for w in seen}:
        raise RuntimeError(f"two_one_chip_workers: the TPU: 2 worker saw {two}")
    if len({w["pid"] for w in seen}) != 2:
        raise RuntimeError("two_one_chip_workers: both tasks ran in one process")
    if any(w["count"] != 1 for w in seen):
        raise RuntimeError("two_one_chip_workers: a TPU: 1 worker saw more than its chip")
    if len({w["visible_chips"] for w in seen}) != 2:
        raise RuntimeError("two_one_chip_workers: both workers were given the same chip")
    return out


def main() -> int:
    from ray_tpu.core.accelerators import ensure_compile_cache_dir

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--kernel-seq", type=int, default=KERNEL_SHAPE["seq"])
    p.add_argument("--kernel-shape", default="{}",
                   help="JSON over KERNEL_SHAPE's keys, e.g. the latent-attention cell's "
                        '{"batch": 1, "seq": 8192, "heads": 20, "kv_heads": 20, "head_dim": 256}')
    p.add_argument("--phases", default="all", choices=("all", "kernel"),
                   help="kernel: the kernel phase alone (one chip)")
    p.add_argument("--train-model", default="llama8b-geom2")
    p.add_argument("--train-batch", type=int, default=None)
    p.add_argument("--train-seq", type=int, default=2048)
    p.add_argument("--train-steps", type=int, default=5)
    p.add_argument("--serve-model", default="llama3-8b")
    p.add_argument("--serve-layers", type=int, default=None)
    p.add_argument("--serve-overrides", type=json.loads, default={},
                   help="JSON of ModelConfig fields for a tiny rehearsal")
    p.add_argument("--max-num-seqs", type=int, default=8)
    p.add_argument("--max-model-len", type=int, default=2048)
    args = p.parse_args()
    defaults = vars(p.parse_args([] if args.chips == 1 else ["--chips", "4"]))
    at_defaults = vars(args) == defaults
    if args.train_batch is None:
        # b6 fills one chip; b4 is the global batch divisible by four that one
        # device also holds, for the four-chip comparison
        args.train_batch = 6 if args.chips == 1 else 4
    depth = serve_depth(args.max_num_seqs, args.max_model_len, args.serve_model)
    if args.serve_layers is None:
        args.serve_layers = depth["depth"]

    # the native object store builds from what git commits; on this path a
    # failed build is an error, not a fallback
    from ray_tpu._native.build import load_library

    load_library("shm_store")
    cache_dir = ensure_compile_cache_dir()
    emit({"phase": "setup", "chips": args.chips, "compile_cache_dir": cache_dir,
          "cache_dir_from_env": cache_dir != os.path.join(
              os.path.dirname(os.path.abspath(__file__)), ".jax_cache"),
          "serve_depth": depth, "at_defaults": at_defaults,
          "parent_backend_untouched": parent_backend_untouched()})

    if args.phases == "kernel":
        phases = [phase_kernel(args)]
    elif args.chips == 1:
        phases = [phase_kernel(args),
                  phase_train(args, args.train_steps, "train"),
                  phase_train(args, 3, "train_again")]
        if not phases[2]["step_from_cache"]:
            raise RuntimeError("train_again: the step did not come from the compile "
                               f"cache at {cache_dir}: {phases[2]}")
        phases.append(phase_serve(args))
    else:
        phases = [phase_train_4chip(args), phase_serve_4chip(args),
                  phase_two_one_chip_workers(args)]

    assert parent_backend_untouched(), "the parent initialised a JAX backend"
    devices = [{k: ph[k] for k in ("platform", "kind", "count")}
               for ph in phases if "platform" in ph]
    device = devices[0]
    kernel_ok = all(ph.get("kernel_in_step", True) for ph in phases) and all(
        c["kernel_in_program"] for ph in phases for c in ph.get("cases", {}).values())
    ok = (at_defaults and kernel_ok
          and all(d["platform"] == "tpu" for d in devices)
          and all(d["count"] == args.chips for d in devices))
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
