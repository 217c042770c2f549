"""The engine measured from inside (PR 25): always-on counters in metrics(),
the scheduler loop's phases as spans on the profiler's clock, the request's
way in, and names in the compiled program. CPU, tiny model."""
import contextlib
import glob
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from ray_tpu.llm import JaxLLMEngine, LLMConfig, SamplingParams
from ray_tpu.llm import engine as engine_mod
from ray_tpu.util import telemetry

LOOP_COUNTERS = list(engine_mod._LOOP_COUNTERS)
NEW_COUNTERS = LOOP_COUNTERS + [
    "prefill_ns_total", "prefill_tokens_total", "prefill_calls_total",
    "decode_steps_total", "decode_slot_steps_total",
    "queue_wait_ns_total", "admitted_total",
    "ingress_ns_total", "ingress_requests_total",
    "compiles_total", "compile_ns_total"]
# what every generate_sync() run moves: the ingress pair moves only for a
# request that came through the HTTP proxy (tested below), a second run
# compiles nothing, and a busy loop may never idle
MOVED_BY_EVERY_RUN = [n for n in NEW_COUNTERS if not n.startswith(
    ("ingress_", "compile", "loop_idle_"))]
GREEDY = dict(temperature=0.0, stop_token_ids=[-1])


def _engine(model_id, **kw):
    cfg = LLMConfig(model_id=model_id, model_source="test-tiny", max_num_seqs=4,
                    max_model_len=64, tokenizer="byte", kv_layout="paged",
                    kv_block_size=16, **kw)
    eng = JaxLLMEngine(cfg)
    eng.start()
    return eng


def _run_batch(eng, prompts, max_tokens):
    """The prompts at once, so that they decode as one batch."""
    outs = [None] * len(prompts)

    def run(i):
        outs[i] = eng.generate_sync(prompts[i], SamplingParams(max_tokens=max_tokens, **GREEDY))

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert all(o is not None for o in outs)
    return outs


@pytest.fixture(scope="module")
def k1_engine():
    eng = _engine("obs-k1", num_decode_steps=1)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def fused_engine():
    eng = _engine("obs-fused", num_decode_steps=4)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def snapshots(k1_engine):
    """metrics() before a run, after it, and after a second one, tracing off."""
    assert not telemetry.enabled()
    snaps = [k1_engine.metrics()]
    for _ in range(2):
        _run_batch(k1_engine, [[1, 10, 11], [1, 20, 21, 22]], max_tokens=5)
        snaps.append(k1_engine.metrics())
    return snaps


@pytest.mark.parametrize("name", NEW_COUNTERS)
def test_counter_present_monotonic_and_integer_with_telemetry_off(snapshots, name):
    values = [s[name] for s in snapshots]
    assert all(isinstance(v, int) for v in values), values
    assert values == sorted(values), values
    if name in MOVED_BY_EVERY_RUN:
        assert values[0] < values[1] < values[2], values


def test_prefill_time_lies_inside_the_admit_phase(snapshots):
    # serve_engine_host_ms_per_step takes prefill_ns_total out of loop_admit_ns_total
    for before, after in zip(snapshots, snapshots[1:]):
        prefill = after["prefill_ns_total"] - before["prefill_ns_total"]
        assert 0 < prefill <= after["loop_admit_ns_total"] - before["loop_admit_ns_total"]


def test_estimates_stay_and_say_they_are_estimates(k1_engine):
    m = k1_engine.metrics()
    for name in ("decode_device_step_ms", "decode_host_rt_ms", "decode_host_sync_fraction"):
        assert name in m
    assert "ESTIMATES from the host clock" in JaxLLMEngine.metrics.__doc__


def test_loop_phases_account_for_the_loop_threads_wall_time(k1_engine):
    _run_batch(k1_engine, [[1, 2, 3]], max_tokens=2)  # the loop runs, shapes compiled
    t0, before = time.perf_counter_ns(), k1_engine.metrics()
    for _ in range(3):
        _run_batch(k1_engine, [[1, 10, 11], [1, 20, 21], [1, 30, 31]], max_tokens=12)
        time.sleep(0.4)  # the idle wait is a phase too
    t1, after = time.perf_counter_ns(), k1_engine.metrics()
    in_phases = sum(after[n] - before[n] for n in LOOP_COUNTERS)
    # a phase ends where the next begins, so the sum is the loop's time but for the
    # phase in flight at either snapshot (at most one idle wait of 50 ms)
    assert 0.95 <= in_phases / (t1 - t0) <= 1.05, (in_phases, t1 - t0)


@pytest.mark.parametrize("which,max_tokens", [("k1", 6), ("fused", 9)])
def test_slot_steps_equal_the_tokens_generated_in_decode(request, which, max_tokens):
    eng = request.getfixturevalue(f"{which}_engine")
    prompts = [[1, 10, 11], [1, 20, 21], [1, 30, 31]]
    before = eng.metrics()
    outs = _run_batch(eng, prompts, max_tokens)
    after = eng.metrics()
    generated = sum(o.num_generated_tokens for o in outs)
    assert generated == len(prompts) * max_tokens
    # prefill samples each request's first token; decode the rest, one a slot-step
    assert after["decode_slot_steps_total"] - before["decode_slot_steps_total"] == (
        generated - len(prompts))
    steps = after["decode_steps_total"] - before["decode_steps_total"]
    k = eng.decode_steps_target()
    assert steps % k == 0 and steps >= max_tokens - 1
    assert after["prefill_calls_total"] - before["prefill_calls_total"] == len(prompts)
    assert after["prefill_tokens_total"] - before["prefill_tokens_total"] == sum(
        len(p) for p in prompts)


def test_queue_wait_counted_once_for_a_preempted_and_readmitted_request():
    # two slots need six 8-token blocks for 16 tokens each; the pool has four (test_llm.py)
    eng = JaxLLMEngine(LLMConfig(
        model_id="obs-preempt", model_source="test-tiny", max_num_seqs=2, max_model_len=64,
        tokenizer="byte", kv_layout="paged", kv_block_size=8, num_kv_blocks=4,
        num_decode_steps=4, enable_prefix_caching=False))
    eng.start()
    try:
        _run_batch(eng, [[1, 10, 11], [1, 20, 21], [1, 30, 31]], max_tokens=16)
        m = eng.metrics()
        assert m["num_preemptions"] >= 1, "the pool was sized to force a preemption"
        assert m["admitted_total"] == 3  # once a request, readmissions not counted
        assert m["prefill_calls_total"] == 3 + m["num_preemptions"]
        assert m["queue_wait_ns_total"] > 0
    finally:
        eng.shutdown()


def test_compiles_total_moves_on_a_new_shape_and_not_on_a_repeat(k1_engine):
    f = jax.jit(lambda x: x * 3 + 1)
    a, b = np.ones((3, 7), np.float32), np.ones((5, 7), np.float32)
    f(a).block_until_ready()
    n0 = k1_engine.metrics()
    f(a).block_until_ready()
    n1 = k1_engine.metrics()
    f(b).block_until_ready()
    n2 = k1_engine.metrics()
    assert n1["compiles_total"] == n0["compiles_total"]
    assert n2["compiles_total"] == n1["compiles_total"] + 1
    assert n2["compile_ns_total"] > n1["compile_ns_total"]


def _host_events(trace_dir):
    """{name: [(start_ns, duration_ns)]} of the profile's host planes, and its path."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append((ev.start_ns, ev.duration_ns))
    return events, path


def test_profile_of_an_engine_run_holds_every_loop_span_with_tracing_unset(
        k1_engine, tmp_path, monkeypatch):
    monkeypatch.delenv("RAY_TPU_TRACING", raising=False)
    telemetry.reset_forced()
    assert not telemetry.enabled()
    _run_batch(k1_engine, [[1, 2, 3]], max_tokens=2)
    ring_before = telemetry.pending()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run_batch(k1_engine, [[1, 10, 11], [1, 20, 21]], max_tokens=6)
        time.sleep(0.2)  # the loop goes idle
    finally:
        jax.profiler.stop_trace()
    events, path = _host_events(str(tmp_path))
    for name in engine_mod.LOOP_SPANS:
        assert name in events, (name, sorted(n for n in events if n.startswith("llm.")))
    assert telemetry.CLOCK_SYNC in events
    assert telemetry.profile_origin_ns(path) is not None
    assert telemetry.pending() == ring_before  # the ring stayed off
    # outside a profile, and off, span() is the shared no-op again
    assert telemetry.span("llm.loop.admit", "llm") is telemetry._NOOP


def test_ring_and_annotation_of_one_span_start_within_a_millisecond(tmp_path):
    telemetry.enable()
    try:
        with telemetry.span("obs.test.before_the_profile", "test"):
            pass  # a profile gets its clock marker from the first span in it
        telemetry.drain()
        jax.profiler.start_trace(str(tmp_path))
        try:
            for _ in range(3):
                with telemetry.span("obs.test.both_ways", "test"):
                    time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        ring = [e for e in telemetry.drain() if e["name"] == "obs.test.both_ways"]
    finally:
        telemetry.reset_forced()
    events, path = _host_events(str(tmp_path))
    origin = telemetry.profile_origin_ns(path)
    noted = sorted(events["obs.test.both_ways"])
    assert len(ring) == len(noted) == 3
    for rec, (start_ns, dur_ns) in zip(sorted(ring, key=lambda e: e["ts_ns"]), noted):
        assert abs((rec["ts_ns"] - origin) - start_ns) < 1_000_000
        assert abs(rec["dur_ns"] - dur_ns) < 1_000_000


def test_span_never_imports_jax_in_a_process_that_has_not():
    code = ("import sys\n"
            "from ray_tpu.util import telemetry\n"
            "assert telemetry.span('a') is telemetry._NOOP\n"
            "telemetry.enable()\n"
            "with telemetry.span('b'):\n    pass\n"
            "assert telemetry.pending() == 1\n"
            "assert 'jax' not in sys.modules, 'telemetry imported jax'\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "RAY_TPU_TRACING"}
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    done = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_named_scopes_change_no_operation_of_the_train_step(monkeypatch):
    import jax.numpy as jnp

    from ray_tpu.models.config import get_config
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    cfg = get_config("test-tiny")
    tx = make_optimizer()
    state = jax.eval_shape(lambda: init_state(jax.random.PRNGKey(0), cfg, tx))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}

    def lowered():
        jax.clear_caches()  # or the second trace is the first one's, names and all
        return make_train_step(cfg, tx, donate=False).lower(state, batch)

    named = lowered()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = lowered()
    with_names, without = named.as_text(debug_info=True), bare.as_text(debug_info=True)
    for scope in ("embed", "attn", "mlp", "lm_head", "loss", "optimizer"):
        in_name_stack = re.compile(rf'[/("]{scope}[/)]')  # .../attn/mul, jvp(loss)/...
        assert in_name_stack.search(with_names), scope
        assert not in_name_stack.search(without), scope
    assert named.as_text() == bare.as_text()  # the operations, without their locations


def test_ingress_counters_move_for_an_http_request_and_not_for_a_handle_call(rt):
    from ray_tpu import serve
    from ray_tpu.llm import build_openai_app

    cfg = LLMConfig(model_id="obs-m", model_source="byte-tiny", max_num_seqs=2,
                    max_model_len=64)
    body = {"model": "obs-m", "messages": [{"role": "user", "content": "yo"}],
            "max_tokens": 3, "temperature": 0.0}
    port = 18331
    try:
        serve.start(http_options={"port": port})
        serve.run(build_openai_app([cfg]), name="obs-app", route_prefix="/v1")
        replica = serve.get_deployment_handle("llm:obs-m", "obs-app")

        def metrics():
            return replica.options(method_name="metrics").remote().result(timeout_s=60)

        def post(stream):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/chat/completions",
                data=json.dumps(dict(body, stream=stream)).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                return resp.read()

        m0 = metrics()
        assert m0["ingress_requests_total"] == 0
        serve.get_app_handle("obs-app").options(method_name="chat").remote(body).result(
            timeout_s=120)
        m1 = metrics()
        assert m1["admitted_total"] == m0["admitted_total"] + 1
        assert m1["ingress_requests_total"] == 0 and m1["ingress_ns_total"] == 0
        assert b"chat.completion" in post(stream=False)
        assert b"[DONE]" in post(stream=True)
        m2 = metrics()
        assert m2["ingress_requests_total"] == 2
        # proxy -> router -> replica -> engine on one host: more than nothing, under a minute
        assert 0 < m2["ingress_ns_total"] < 60e9
    finally:
        serve.shutdown()
