"""Paged P/D KV handoff tests (per-page overlapped streaming over the striped
data plane — core/device_plane.py PagedKVHandle/PagedKVFetch + llm/engine.py
admission overlap).

Tier-1 budget: every test shares ONE module-scoped set of compiled paged
engines (`pd_engines`) — the paged burst program compiles once. Load-shaped
scenarios live in bench_serve.py --pd, not here.
"""
import time

import pytest

from ray_tpu.llm import JaxLLMEngine, LLMConfig, SamplingParams
from ray_tpu.test_utils import wait_for_condition

PROMPT = [1, 7, 42, 99, 5]


def _params(max_tokens=6):
    return SamplingParams(max_tokens=max_tokens, temperature=0.0,
                          stop_token_ids=[-1])


def _cfg():
    return LLMConfig(model_id="pd-paged", model_source="test-tiny",
                     max_num_seqs=2, max_model_len=64)


@pytest.fixture(scope="module")
def pd_engines(rt):
    """(prefill, decode, colocated-reference) — compiled once for the module.

    Needs the session cluster (`rt`): the device plane's control channel
    authenticates against the cluster authkey, and paged handoff requires it.
    """
    prefill = JaxLLMEngine(_cfg())
    decode = JaxLLMEngine(_cfg())
    colo = JaxLLMEngine(_cfg())
    yield prefill, decode, colo
    for e in (prefill, decode, colo):
        e.shutdown()


def _decode_all(decode, pre, params):
    ids = []
    for chunk in decode.generate_from_prefill(pre, params):
        ids.extend(chunk.token_ids)
    return ids


def test_paged_handoff_matches_colocated(pd_engines):
    """The paged per-page pull path reproduces the colocated greedy output,
    and the consumer's release ack — not the TTL backstop — drains the
    prefill engine's export bookkeeping."""
    from ray_tpu.core.device_plane import PagedKVHandle, plane

    prefill, decode, colo = pd_engines
    params = _params()
    want = colo.generate_sync(PROMPT, params).token_ids

    # the plane is one a process: whatever an earlier file of this xdist
    # worker left exported is not this test's to drain
    others = plane().stats()["exports_live"]
    pre = prefill.prefill_only(PROMPT, params)
    assert isinstance(pre["kv_handle"], PagedKVHandle)
    assert pre["kv_handle"].n_pages >= 1
    assert _decode_all(decode, pre, params) == want

    # release-ack propagation is async (arm channel + listener); the TTL
    # backstop is minutes out, so draining within seconds proves the ack path
    wait_for_condition(
        lambda: (prefill.metrics()["pd_exports_live"] == 0
                 and plane().stats()["exports_live"] == others),
        timeout=10,
        message="the decode side's release ack never drained this test's export")


def test_first_token_streams_before_pages_land(pd_engines):
    """Overlap contract: the prefill-sampled first token rides the ~1 KB
    handle and is emitted immediately, while the page pulls (here delayed by
    an armed fail point) are still in flight."""
    from ray_tpu.util import fault_injection as fi

    prefill, decode, _ = pd_engines
    params = _params(max_tokens=4)
    pre = prefill.prefill_only(PROMPT, params)
    n_pages = pre["kv_handle"].n_pages
    fi.arm("llm.pd.handoff", "delay", delay_s=2.0, count=1)
    try:
        t0 = time.monotonic()
        gen = decode.generate_from_prefill(pre, params)
        first = next(iter(gen))
        ttft = time.monotonic() - t0
        ids = list(first.token_ids)
        for chunk in gen:
            ids.extend(chunk.token_ids)
    finally:
        fi.disarm("llm.pd.handoff")
    assert first.token_ids, "first chunk must carry the prefill-sampled token"
    assert ttft < 1.5, (
        f"first token took {ttft:.2f}s — it must not wait on the armed "
        f"2s page delay ({n_pages} pages)")
    assert len(ids) == 4  # transfer completed and decode finished the request


def test_midtransfer_fault_is_typed_and_host_fallback_recovers(pd_engines):
    """An injected pull failure surfaces as DevicePlaneError (the class the
    router's fallback matches on), and the host-path retry — release the
    orphaned export, re-prefill with force_host — still matches colocated."""
    from ray_tpu.core.device_plane import DevicePlaneError
    from ray_tpu.util import fault_injection as fi

    prefill, decode, colo = pd_engines
    params = _params()
    want = colo.generate_sync(PROMPT, params).token_ids

    pre = prefill.prefill_only(PROMPT, params)
    fi.arm("llm.pd.handoff", "error", count=1)
    try:
        with pytest.raises(DevicePlaneError):
            _decode_all(decode, pre, params)
    finally:
        fi.disarm("llm.pd.handoff")
    # router fallback choreography at engine level
    prefill.release_prefill_export(pre["kv_key"])
    assert prefill.metrics()["pd_exports_live"] == 0
    pre2 = prefill.prefill_only(PROMPT, params, force_host=True)
    assert "kv_handle" not in pre2
    assert _decode_all(decode, pre2, params) == want


def test_released_export_raises_eagerly(pd_engines):
    """A dead export (producer released/pruned it) must fail the decode-side
    fetch at the liveness probe — a typed error in milliseconds, not a
    timeout burn."""
    from ray_tpu.core.device_plane import DevicePlaneError

    prefill, decode, _ = pd_engines
    params = _params(max_tokens=4)
    pre = prefill.prefill_only(PROMPT, params)
    prefill.release_prefill_export(pre["kv_key"])
    t0 = time.monotonic()
    with pytest.raises(DevicePlaneError, match="released"):
        _decode_all(decode, pre, params)
    assert time.monotonic() - t0 < 5.0  # eager stat probe, no timeout burn


def test_build_pd_app_pool_autoscaling_configs():
    """build_pd_openai_app wires independent slo-mode autoscaling per pool:
    prefill pinned to the TTFT SLO, decode driven by queue depth."""
    from ray_tpu.llm.server import build_pd_openai_app

    cfg = LLMConfig(model_id="pd-as", model_source="byte-tiny",
                    max_num_seqs=2, max_model_len=64)
    app = build_pd_openai_app(
        cfg, num_prefill=1, max_prefill=3, num_decode=2, max_decode=5,
        ttft_slo_name="llm-ttft")
    prefill_app, decode_app = app.args[0], app.args[1]
    pa = prefill_app.deployment.config.autoscaling_config
    da = decode_app.deployment.config.autoscaling_config
    assert pa.mode == "slo" and pa.slo_names == ["llm-ttft"]
    assert (pa.min_replicas, pa.max_replicas) == (1, 3)
    assert da.mode == "slo" and da.slo_names is None
    assert (da.min_replicas, da.max_replicas) == (2, 5)
    # without caps the pools stay pinned — no autoscaling config
    pinned = build_pd_openai_app(cfg)
    assert pinned.args[0].deployment.config.autoscaling_config is None
    assert pinned.args[1].deployment.config.autoscaling_config is None


@pytest.mark.slow
def test_chaos_prefill_killed_mid_handoff(rt):
    """SIGKILL the prefill replica while the decode side is mid-pull (a fail
    point holds the transfer open): the stream fails with a typed error well
    inside the stall bound, the router's host fallback completes the request
    against the replacement replica, and no KV export is left pinned."""
    from ray_tpu import serve
    from ray_tpu.llm import build_pd_openai_app
    from ray_tpu.util.fault_injection import ChaosController

    cfg = LLMConfig(model_id="pd-chaos", model_source="byte-tiny",
                    max_num_seqs=2, max_model_len=64)
    body = {"messages": [{"role": "user", "content": "hi"}], "max_tokens": 6,
            "temperature": 0.0}
    try:
        serve.run(build_pd_openai_app(cfg), name="pd-chaos",
                  route_prefix="/pd-chaos")
        h = serve.get_app_handle("pd-chaos")
        want = h.options(method_name="chat").remote(dict(body)).result()
        chaos = ChaosController()
        # hold every page pull open 3s so the kill lands mid-handoff
        assert chaos.arm_replica("pd-chaos", "llm-pd:decode",
                                 "llm.pd.handoff", mode="delay",
                                 delay_s=3.0) >= 1

        import threading

        got, err = {}, {}

        def run():
            try:
                got["resp"] = h.options(method_name="chat").remote(
                    dict(body)).result()
            except Exception as e:  # surfaced to the main thread's asserts
                err["e"] = e

        t = threading.Thread(target=run, daemon=True)
        t0 = time.monotonic()
        t.start()
        time.sleep(1.0)  # prefill done, decode stuck inside the armed delay
        assert chaos.kill_replica("pd-chaos", "llm-pd:prefill", index=0)
        t.join(timeout=120)
        assert not t.is_alive(), "request did not complete after the kill"
        assert "e" not in err, f"request lost: {err.get('e')!r}"
        assert time.monotonic() - t0 < 90
        resp = got["resp"]
        assert resp["choices"][0]["message"]["content"] == \
            want["choices"][0]["message"]["content"]
        # replacement prefill replica must pin nothing: the fallback path
        # released the orphan and host-path prefills never export
        chaos.disarm_replica("pd-chaos", "llm-pd:decode")
        pre_h = serve.get_deployment_handle("llm-pd:prefill", "pd-chaos")
        deadline = time.monotonic() + 15
        live = None
        while time.monotonic() < deadline:
            live = pre_h.options(method_name="metrics").remote().result()[
                "pd_exports_live"]
            if live == 0:
                break
            time.sleep(0.25)
        assert live == 0, f"leaked {live} prefill KV exports past recovery"
    finally:
        serve.delete("pd-chaos")
