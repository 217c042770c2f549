"""What the serving drivers share: serve.run(build_openai_app([cfg])) on one
replica, a client over HTTP, set-up, warm-up, the checks, and (in a traced
run) a replica that can profile itself.

Only the process that holds the chip can trace it, and nothing in the program
calls jax.profiler, so a traced run deploys `TracedLLMServer`, a subclass of
the program's LLMServer with two more methods, bound exactly as
build_openai_app binds LLMServer. An untraced run uses build_openai_app itself.
"""
import os
import shutil
import socket
import tempfile
import threading
import time

import numpy as np

from ray_tpu.llm.server import LLMServer

APP = "bench"


class TracedLLMServer(LLMServer):
    """LLMServer that can start and stop a profiler trace in its own process
    and counts the programs compiled there."""

    def __init__(self, llm_config):
        from benchmarks.lib import compile_events

        self._compiles = compile_events.listen()
        super().__init__(llm_config)

    def trace_start(self, directory: str) -> float:
        import jax

        jax.profiler.start_trace(directory)
        return time.time()

    def trace_stop(self) -> float:
        import jax

        jax.profiler.stop_trace()
        return time.time()

    def compile_count(self) -> int:
        return len(self._compiles)

    def reference_greedy(self, prompt_ids: list, max_tokens: int, model: dict) -> dict:
        """The engine's greedy continuation of `prompt_ids`, held to the plain
        reference (lib/reference.py: float32, no cache, no kernel, no capacity)
        run on the replica's own parameters over the same tokens: at each
        generated position, by how much the reference's best logit leads the
        logit of the token the engine chose, over the spread of the logits."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.lib import reference
        from ray_tpu.llm.config import SamplingParams

        out = self.engine.generate_sync(
            list(prompt_ids), SamplingParams(max_tokens=max_tokens, temperature=0.0))
        chosen = list(out.token_ids)
        history = jnp.asarray([list(prompt_ids) + chosen[:-1]], jnp.int32)
        logits = np.asarray(jax.jit(lambda p, t: reference.forward(p, t, model))(
            self.engine.params, history))[0, len(prompt_ids) - 1:]
        lead = logits.max(axis=-1) - logits[np.arange(len(chosen)), chosen]
        return {"tokens": chosen, "reference_tokens": logits.argmax(axis=-1).tolist(),
                "lead_over_logit_std": (lead / logits.std(axis=-1)).tolist()}


def _build_traced_app(cfg):
    """build_openai_app (llm/server.py), with the subclass in LLMServer's place."""
    from ray_tpu import serve
    from ray_tpu.llm.server import OpenAIRouter, _replica_actor_options

    d = serve.deployment(TracedLLMServer).options(
        name=f"llm:{cfg.model_id}",
        num_replicas=cfg.deployment_config.get("num_replicas", 1),
        max_ongoing_requests=cfg.deployment_config.get("max_ongoing_requests", 64),
        ray_actor_options=_replica_actor_options(cfg))
    router = serve.deployment(OpenAIRouter).options(name="llm-router")
    return router.bind(**{cfg.model_id: d.bind(cfg)})


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Session:
    """One deployed model and a client for it. `with Session(ctx) as s:`"""

    def __init__(self, ctx: dict):
        self.ctx, self.log = ctx, ctx["log"]
        self.stamps = {}
        self.work = tempfile.mkdtemp(prefix="bench-serve-")
        self.polled = {}
        self._poll_stop = threading.Event()
        self._poll_thread = None

    def __enter__(self):
        import ray_tpu
        from benchmarks.lib import loadgen, modelcfg, wordtok
        from ray_tpu import serve
        from ray_tpu.llm import LLMConfig, build_openai_app

        ctx, config = self.ctx, self.ctx["config"]
        model = modelcfg.model_config(ctx["model"])
        t0 = time.time()
        tok_dir = wordtok.write(os.path.join(self.work, "tokenizer"), model.vocab_size)
        self.stamps["tokenizer_written"] = time.time()
        ray_tpu.init()
        self._up = True
        found = ray_tpu.cluster_resources().get("TPU", 0)
        if found < ctx["cell"]["chips"]:
            raise SystemExit(f"the cell needs {ctx['cell']['chips']} TPU chip(s); "
                             f"this host has {found}")
        port = _free_port()
        serve.start(http_options={"port": port})
        self.stamps["cluster_up"] = time.time()
        self.model_id = config["name"]
        cfg = LLMConfig(model_id=self.model_id, model_source=model,
                        tokenizer=f"hf:{tok_dir}", **config["engine"])
        app = _build_traced_app(cfg) if ctx["trace"] else build_openai_app([cfg])
        serve.run(app, name=APP, route_prefix="/v1")
        self.stamps["replica_ready"] = time.time()
        self.replica = serve.get_deployment_handle(f"llm:{self.model_id}", APP)
        self.vocab_size = model.vocab_size
        rng = np.random.default_rng([ctx["seed"], 11])
        self.client = loadgen.Client(
            "127.0.0.1", port, "/v1/chat/completions", self.model_id,
            lambda n: wordtok.prompt_words(rng, n, model.vocab_size))
        self.log({"phase": "serve_up", "tokenizer_s": self.stamps["tokenizer_written"] - t0,
                  "cluster_s": self.stamps["cluster_up"] - self.stamps["tokenizer_written"],
                  "serve_run_s": self.stamps["replica_ready"] - self.stamps["cluster_up"]})
        return self

    def __exit__(self, *exc):
        from ray_tpu import serve
        import ray_tpu

        self.stop_polling()
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
            shutil.rmtree(self.work, ignore_errors=True)
        return False

    def call(self, method: str, *args, timeout_s: float = 120.0):
        return self.replica.options(method_name=method).remote(*args).result(timeout_s=timeout_s)

    # ------------------------------------------------------------- set-up
    def warm_up(self, prompt_lens: list, max_tokens: int, concurrent: int) -> None:
        """One request for each prefill bucket the cell's traffic reaches,
        then `concurrent` at once so that decode has run at a full batch."""
        for n in prompt_lens:
            rec = self.client.request(self.client.body(n, max_tokens), timeout=1200.0)
            if rec["error"] or not rec["frames"]:
                raise RuntimeError(f"warm-up request of {n} words failed: {rec}")
        self.stamps["first_answers"] = time.time()
        threads = [threading.Thread(target=self.client.request, args=(
            self.client.body(prompt_lens[i % len(prompt_lens)], max_tokens),), daemon=True)
            for i in range(concurrent)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600.0)
        self.stamps["warm"] = time.time()
        self.client.records.clear()

    def canary(self) -> dict:
        """The same greedy prompt, streamed and not streamed: fewer words than
        a KV block, so no prefix-cache hit changes its program between calls."""
        body = {"model": self.model_id, "stream": True, "max_tokens": 12, "temperature": 0.0,
                "messages": [{"role": "user", "content": "w7 w11 w13 w17 w19 w23 w29 w31"}]}
        streamed = self.client.request(dict(body))
        self.client.records.remove(streamed)
        unary = self.client.unary(body)
        text = unary["choices"][0]["message"]["content"]
        return {"streamed_words": streamed["words"], "error": streamed["error"],
                "unary_text": text, "unary_words": len(text.split()),
                "unary_completion_tokens": unary["usage"]["completion_tokens"],
                "unary_finish": unary["choices"][0]["finish_reason"],
                "prompt_tokens": unary["usage"]["prompt_tokens"]}

    def wait_idle(self, timeout_s: float = 60.0) -> None:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            m = self.call("metrics")
            if m["num_active"] == 0 and m["num_pending"] == 0:
                return
            time.sleep(0.2)
        raise RuntimeError("the engine did not go idle after the window")

    # ---------------------------------------------------- around the window
    def start_polling(self, names: list, every_s: float = 1.0) -> None:
        """Poll engine gauges through the deployment handle (traced runs only)."""
        self.polled = {n: [] for n in names}

        def loop():
            while not self._poll_stop.wait(every_s):
                try:
                    m = self.call("metrics", timeout_s=10.0)
                except Exception:  # noqa: BLE001 - a missed poll is a missing sample
                    continue
                for n in names:
                    if n in m:
                        self.polled[n].append(m[n])

        self._poll_thread = threading.Thread(target=loop, daemon=True)
        self._poll_thread.start()

    def stop_polling(self) -> None:
        self._poll_stop.set()
        if self._poll_thread is not None:
            self._poll_thread.join(10.0)

    def trace_in_window(self, at_s: float, trace_s: float) -> dict:
        """Start a thread that traces `trace_s` seconds from `at_s` seconds
        into a window that starts now."""
        out = {}
        trace_dir = os.path.join(self.work, "trace")

        def run():
            time.sleep(at_s)
            out["t0"] = self.call("trace_start", trace_dir)
            time.sleep(trace_s)
            out["t1"] = self.call("trace_stop", timeout_s=300.0)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        out.update(thread=t, dir=trace_dir)
        return out


def measure(ctx: dict, prompt_lens: list, warm_tokens: int, run_window) -> dict:
    """Set up, warm up, run `run_window(session) -> (run, extra_log)`, check,
    and shape the driver's result. The two serving drivers differ only in
    `run_window` and in the shapes they warm up."""
    from benchmarks.lib import loadgen, trace_reduce

    cell, config, log = ctx["cell"], ctx["config"], ctx["log"]
    engine = config["engine"]
    with Session(ctx) as s:
        s.warm_up(prompt_lens, warm_tokens, concurrent=engine["max_num_seqs"])
        before = s.canary()
        counters0 = s.call("metrics")
        # only the subclass of a traced run can reach the replica's parameters
        against_reference = s.call(
            "reference_greedy", [7, 11, 13, 17, 19, 23, 29, 31], 12, ctx["model"],
            timeout_s=600.0) if ctx["trace"] else None
        compiles0 = s.call("compile_count") if ctx["trace"] else None
        trace = None
        if ctx["trace"]:
            s.start_polling(["kv_pool_occupancy", "num_active", "num_pending"])
            # in mid-window, unless the configuration says when its device has work
            trace = s.trace_in_window(
                config.get("trace_at_s", max(0.0, (ctx["seconds"] - config["trace_seconds"]) / 2)),
                config["trace_seconds"])
        s.stamps["window_start"] = time.time()
        run = run_window(s)
        if trace is not None:
            trace["thread"].join(300.0)
        s.stop_polling()
        compiles1 = s.call("compile_count") if ctx["trace"] else None
        counters1 = s.call("metrics")
        s.wait_idle()
        after = s.canary()
        report = s.call("device_report")
        reduced = None
        if trace is not None:
            reduced = trace_reduce.reduce_dir(trace["dir"], n_devices=cell["chips"],
                                              min_window_s=config["trace_seconds"])
            trace_reduce.keep(trace["dir"], ctx["keep_trace"])
        records, stamps, polled = list(s.client.records), s.stamps, s.polled

    summary = loadgen.summarize(records, run)
    start = ctx["t_process_start"]
    log({"phase": "setup_split_s",
         "process_to_cluster_up": stamps["cluster_up"] - start,
         "weights_and_replica_start": stamps["replica_ready"] - stamps["cluster_up"],
         "compile_and_first_answers": stamps["first_answers"] - stamps["replica_ready"],
         "warmup_at_full_batch": stamps["warm"] - stamps["first_answers"],
         "canary_and_counters": stamps["window_start"] - stamps["warm"]})
    log(dict(summary, phase="window"))
    checks = {
        "same_prompt_same_tokens": before["unary_text"] == after["unary_text"]
        and bool(before["unary_text"]),
        # every generated token reaches the client as a word in a content frame
        "streamed_equals_usage": before["streamed_words"] == before["unary_words"]
        and (before["unary_completion_tokens"] == before["unary_words"]
             or before["unary_finish"] == "stop"),
        "every_request_full_length": summary["wrong_length"] == 0,
        "no_request_failed": summary["failed"] == 0,
        "replica_on_tpu": report["platform"] == "tpu",
        "weights_in_config_dtype": report["param_dtype"] == engine["dtype"],
    }
    if compiles0 is not None:
        checks["no_compile_in_window"] = compiles1 == compiles0
        # the engine may part from the reference's choice only where the reference
        # itself all but ties: the limit is the configuration's, with its reason
        checks["greedy_tokens_within_reference_lead"] = max(
            against_reference["lead_over_logit_std"]) <= config["reference_lead_limit"]
    log({"phase": "checks", "checks": checks, "canary_before": before, "canary_after": after,
         "against_reference": against_reference,
         "compiles_in_window": None if compiles0 is None else compiles1 - compiles0,
         "decode_fused_steps": counters1.get("decode_fused_steps")})
    device = {"platform": report["platform"], "kind": report["device_kind"],
              "count": report["device_count"],
              "memory_peak_bytes": max(report["peak_bytes_in_use"])}
    out = {
        "end_to_end": {
            "serve_tokens_per_s": {"value": summary["tokens_per_s"], "unit": "tokens/s"},
            "ttft_p90_ms": {"value": summary["ttft_p90_ms"], "unit": "ms"},
            "tpot_p90_ms": {"value": summary["tpot_p90_ms"], "unit": "ms"},
            "setup_s": {"value": stamps["window_start"] - start, "unit": "s"},
        },
        "device": device, "correct": all(checks.values()),
        "attempted": summary["attempted"], "failed": summary["failed"],
        "counters": {"before": counters0, "after": counters1},
        "series": dict(polled), "summary": summary,
    }
    if reduced is not None:
        log({"phase": "trace", **{k: reduced[k] for k in (
            "window_s", "busy_s", "modules", "planes", "lines")}})
        trace_reduce.into_result(out, reduced)
    return out
