"""Device-native tensor transfer plane (core/device_plane.py).

Reference parity: python/ray/experimental/gpu_object_manager/gpu_object_manager.py:54
(device-resident objects, transfer on demand) and experimental/channel/
torch_tensor_nccl_channel.py (device channels). These tests prove a jax.Array
crosses actor PROCESS boundaries with zero host-pickle of the payload: the plane's
own byte counters account for every payload byte, and the producer-side export is
observed armed + released.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(autouse=True)
def plane_ok(rt):
    """Plane availability is probed AFTER cluster init so the lazily-started
    transfer endpoint shares the session authkey with the workers."""
    from ray_tpu.core.device_plane import plane

    if not plane().available:
        pytest.skip(f"device plane unavailable: {plane().disabled_reason}")


def test_export_fetch_roundtrip_sharded(rt):
    """A mesh-sharded array crosses to an actor process device-to-device, arriving
    with the producer's sharding rebuilt; payload bytes move only via the plane."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.core.device_plane import plane

    mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("x",))
    x = jax.device_put(jnp.arange(4096.0).reshape(8, 512), NamedSharding(mesh, P("x")))
    before = plane().stats()
    handle = plane().export({"kv": x})

    @rt.remote
    def consume(h):
        import numpy as _np

        from ray_tpu.core.device_plane import plane as _plane

        tree = _plane().fetch(h)
        arr = tree["kv"]
        st = _plane().stats()
        return {
            "sum": float(_np.asarray(arr).sum()),
            "spec": str(arr.sharding.spec),
            "pulls": st["pulls"],
            "bytes_pulled": st["bytes_pulled"],
        }

    out = rt.get(consume.remote(handle))
    assert out["sum"] == float(np.arange(4096.0).sum())
    assert out["spec"] == "PartitionSpec('x',)"
    assert out["pulls"] == 1
    # every payload byte is accounted for by the plane, none by pickle
    assert out["bytes_pulled"] == x.nbytes
    after = plane().stats()
    assert after["arms"] == before["arms"] + 1
    plane().release(handle.key)


def test_fetch_release_drops_producer_export(rt):
    from ray_tpu.core.device_plane import plane

    h = plane().export(jnp.ones((1024,)))
    assert plane().stats()["exports_live"] >= 1

    @rt.remote
    def pull_and_ack(h):
        from ray_tpu.core.device_plane import plane as _plane

        arr = _plane().fetch(h, release=True)
        return float(np.asarray(arr).sum())

    assert rt.get(pull_and_ack.remote(h)) == 1024.0
    # the consumer's ack released the export (poll briefly: ack is best-effort async)
    import time

    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if not any(k == h.key for k in plane()._exports):
            break
        time.sleep(0.05)
    assert h.key not in plane()._exports


def test_fetch_after_release_raises_and_falls_back(rt):
    from ray_tpu.core.device_plane import DevicePlaneError, plane

    h = plane().export(jnp.ones((2048,)))
    plane().release(h.key)

    @rt.remote
    def try_fetch(h):
        from ray_tpu.core.device_plane import DevicePlaneError as E, plane as _plane

        try:
            _plane().fetch(h)
            return "fetched"
        except E:
            return "error"

    assert rt.get(try_fetch.remote(h)) == "error"


def test_object_store_get_uses_device_plane(rt):
    """ray_tpu.put(jax.Array) + cross-process get: the consumer pulls the payload
    device-to-device (its plane counters show the bytes), host copy untouched."""
    x = jnp.full((131072,), 3.0, jnp.float32)  # 512 KiB < 1 MiB min -> host path
    big = jnp.full((524288,), 2.0, jnp.float32)  # 2 MiB >= min -> device path
    ref_small = rt.put(x)
    ref_big = rt.put(big)

    @rt.remote
    def consume(refs):  # refs nested in a list resolve inside, so counter deltas
        import numpy as _np  # bracket each get (workers are reused across tests)

        import ray_tpu
        from ray_tpu.core.device_plane import plane as _plane

        st0 = _plane().stats()
        a = ray_tpu.get(refs[0])
        st1 = _plane().stats()
        b = ray_tpu.get(refs[1])
        st2 = _plane().stats()
        return {
            "sum_small": float(_np.asarray(a).sum()),
            "sum_big": float(_np.asarray(b).sum()),
            "small_bytes": st1["bytes_pulled"] - st0["bytes_pulled"],
            "big_pulls": st2["pulls"] - st1["pulls"],
            "big_bytes": st2["bytes_pulled"] - st1["bytes_pulled"],
        }

    out = rt.get(consume.remote([ref_small, ref_big]))
    assert out["sum_small"] == 3.0 * 131072
    assert out["sum_big"] == 2.0 * 524288
    assert out["small_bytes"] == 0  # below min size: host path
    assert out["big_pulls"] == 1  # the big array rode the plane
    assert out["big_bytes"] == big.nbytes
    del ref_small, ref_big


def test_device_native_mode_stores_stub_only(rt, monkeypatch):
    """'native' mode: no host copy in the store — the inline frame is tiny and the
    consumer still receives the full array via the plane."""
    from ray_tpu.core import object_store

    monkeypatch.setenv("RAY_TPU_DEVICE_OBJECTS", "native")
    big = jnp.full((524288,), 2.5, jnp.float32)  # 2 MiB
    loc = object_store.materialize(big, _oid())
    # the durable form is a tiny inline stub, not a 2 MiB arena/shm object
    assert loc[0] == "inline", loc[0]
    assert len(loc[1]) < 4096

    ref = rt.put(big)

    @rt.remote
    def consume(a):
        import numpy as _np

        return float(_np.asarray(a).sum())

    assert rt.get(consume.remote(ref)) == 2.5 * 524288
    del ref


def _oid():
    from ray_tpu.core.ids import ObjectID

    return ObjectID.generate()


def test_pd_disagg_kv_rides_device_plane(rt):
    """Prefill -> decode handoff: the prefill result carries a handle (no host
    KV arrays), decode pulls device-to-device and matches the non-disagg output."""
    from ray_tpu.llm import JaxLLMEngine, LLMConfig, SamplingParams

    cfg = LLMConfig(model_id="pd-dev", model_source="test-tiny", max_num_seqs=2,
                    max_model_len=64, tokenizer="byte")
    eng = JaxLLMEngine(cfg)
    eng.start()
    try:
        params = SamplingParams(max_tokens=6, temperature=0.0, stop_token_ids=[-1])
        want = eng.generate_sync([1, 7, 42, 9], params).token_ids

        pre = eng.prefill_only([1, 7, 42, 9], params)
        assert "kv_handle" in pre and "k" not in pre, (
            "device plane up: prefill result must carry a handle, not host arrays")
        ids = []
        for chunk in eng.generate_from_prefill(pre, params):
            ids.extend(chunk.token_ids)
        assert [pre["first_token"]] + ids[1:] == ids  # first token came from prefill
        assert ids == want
    finally:
        eng.shutdown()


def test_pd_force_host_and_dead_handle_fallback(rt):
    """force_host pins the host path even with the plane up; a dead handle makes
    decode raise DevicePlaneError, which the PD router recognizes for fallback."""
    from ray_tpu.core.device_plane import DevicePlaneError, plane
    from ray_tpu.llm import JaxLLMEngine, LLMConfig, SamplingParams
    from ray_tpu.llm.server import _is_device_plane_error

    cfg = LLMConfig(model_id="pd-fb", model_source="test-tiny", max_num_seqs=2,
                    max_model_len=64, tokenizer="byte")
    eng = JaxLLMEngine(cfg)
    eng.start()
    try:
        params = SamplingParams(max_tokens=4, temperature=0.0, stop_token_ids=[-1])
        pre = eng.prefill_only([1, 5, 9], params, force_host=True)
        assert "k" in pre and "kv_handle" not in pre

        pre2 = eng.prefill_only([1, 5, 9], params)
        assert "kv_handle" in pre2
        plane().release(pre2["kv_handle"].key)  # simulate prefill replica loss
        try:
            eng.generate_from_prefill(pre2, params)
            raised = None
        except DevicePlaneError as e:
            raised = e
        assert raised is not None and _is_device_plane_error(raised)
    finally:
        eng.shutdown()


def test_device_channel_cross_process_pull(rt):
    """aDAG device channel: a jax.Array written on one side arrives on the other
    via the plane (device frame has no embedded host copy)."""
    import os

    from ray_tpu.dag.accelerator_context import DeviceChannel
    from ray_tpu.core.device_plane import plane

    name = "devch_" + os.urandom(4).hex()
    ch = DeviceChannel(name, 1 << 20, create=True)
    try:
        arr = jnp.ones((524288,)) * 4.0  # 2 MiB: above the device-native gate
        before = plane().stats()
        ch.write(("ok", arr))

        @rt.remote
        def read_side(chan):
            import numpy as _np

            from ray_tpu.core.device_plane import plane as _plane

            status, got = chan.read(timeout=10)
            st = _plane().stats()
            return status, float(_np.asarray(got).sum()), st["pulls"]

        status, total, pulls = rt.get(read_side.remote(ch))
        assert status == "ok"
        assert total == 4.0 * 524288
        assert pulls >= 1
        assert plane().stats()["arms"] >= before["arms"] + 1
    finally:
        ch.destroy()


def test_same_process_channel_still_zero_copy():
    """Same-process read returns the literal original array (no pull, no copy)."""
    import os

    from ray_tpu.dag.accelerator_context import DeviceChannel

    name = "devch_" + os.urandom(4).hex()
    ch = DeviceChannel(name, 1 << 20, create=True)
    try:
        arr = jnp.ones((128, 128))
        ch.write(arr)
        got = ch.read(timeout=5)
        assert got is arr
    finally:
        ch.destroy()


def test_reshard_fetch_across_unequal_meshes(rt):
    """Producer mesh (4,) -> consumer process with only TWO devices: fetch
    still rides the device plane (per-shard pull + one compiled reassembly
    under a consumer-sized mesh) with zero host pickle of the payload — the
    unequal-size P/D deployment shape (big prefill TP, small decode TP).
    Reference analogue: resharding NCCL channels,
    experimental/channel/torch_tensor_nccl_channel.py."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.core.device_plane import plane

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("x",))
    x = jax.device_put(jnp.arange(4096.0).reshape(8, 512),
                       NamedSharding(mesh, P("x")))
    handle = plane().export({"kv": x})

    @rt.remote(runtime_env={"env_vars": {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}})
    def consume(h):
        import jax as _jax
        import numpy as _np

        from ray_tpu.core.device_plane import plane as _plane

        assert len(_jax.devices()) == 2, len(_jax.devices())
        tree = _plane().fetch(h, release=True)
        arr = tree["kv"]
        st = _plane().stats()
        return {
            "sum": float(_np.asarray(arr).sum()),
            "ndev": len(arr.sharding.device_set),
            "spec": str(arr.sharding.spec),
            "reshard_pulls": st.get("reshard_pulls", 0),
            "bytes_pulled": st["bytes_pulled"],
        }

    out = rt.get(consume.remote(handle))
    assert out["sum"] == float(np.arange(4096.0).sum())
    assert out["reshard_pulls"] == 1
    # every payload byte is accounted for by the plane, none by pickle
    assert out["bytes_pulled"] == x.nbytes
    # arrived sharded over the consumer's OWN 2-device mesh, same logical spec
    assert out["ndev"] == 2 and out["spec"] == "PartitionSpec('x',)"
    # the producer-side export was released by the ack (other tests' exports
    # may still be live in this process — check only OURS is gone)
    deadline = __import__("time").time() + 10
    while plane()._exports.get(handle.key) is not None:
        assert __import__("time").time() < deadline, "export never released"


def test_pd_disagg_unequal_pools_device_path(rt):
    """P/D disaggregation with UNEQUAL pool sizes in separate processes (the
    common deployment: big prefill TP, small decode pool): prefill runs tp=2
    inside a 4-device actor, decode inside a 1-device actor. The KV handoff
    STILL rides the device plane — the decode side takes the reshard-fetch
    path — and the output matches colocated greedy decoding exactly. This is
    the monolithic export: the paged handoff, which is the default, moves
    pages over the striped data plane and has no mesh to reshard
    (tests/test_pd_paged.py), so both engines run with it off."""
    from ray_tpu.llm import JaxLLMEngine, LLMConfig, SamplingParams

    prompt = [1, 7, 42, 9]
    n_tokens = 6

    ref_eng = JaxLLMEngine(LLMConfig(
        model_id="pd-ref", model_source="test-tiny", max_num_seqs=2,
        max_model_len=64, tokenizer="byte"))
    ref_eng.start()
    try:
        want = ref_eng.generate_sync(prompt, SamplingParams(
            max_tokens=n_tokens, temperature=0.0, stop_token_ids=[-1])).token_ids
    finally:
        ref_eng.shutdown()

    @rt.remote(runtime_env={"env_vars": {
        "JAX_PLATFORMS": "cpu", "RAY_TPU_PD_PAGED": "0",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}})
    class Prefill:
        def __init__(self):
            from ray_tpu.llm import JaxLLMEngine as Eng, LLMConfig as Cfg

            self.eng = Eng(Cfg(model_id="pd-up", model_source="test-tiny",
                               max_num_seqs=2, max_model_len=64,
                               tokenizer="byte", tensor_parallel_size=2))
            self.eng.start()

        def prefill(self, p, mt):
            from ray_tpu.llm import SamplingParams as SP

            out = self.eng.prefill_only(p, SP(max_tokens=mt, temperature=0.0,
                                              stop_token_ids=[-1]))
            assert "kv_handle" in out and "k" not in out
            return out

    @rt.remote(runtime_env={"env_vars": {
        "JAX_PLATFORMS": "cpu", "RAY_TPU_PD_PAGED": "0",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}})
    class Decode:
        def __init__(self):
            from ray_tpu.llm import JaxLLMEngine as Eng, LLMConfig as Cfg

            self.eng = Eng(Cfg(model_id="pd-down", model_source="test-tiny",
                               max_num_seqs=2, max_model_len=64,
                               tokenizer="byte"))
            self.eng.start()

        def decode(self, pre, mt):
            import jax as _jax

            from ray_tpu.core.device_plane import plane as _plane
            from ray_tpu.llm import SamplingParams as SP

            assert len(_jax.devices()) == 1
            ids = []
            for chunk in self.eng.generate_from_prefill(
                    pre, SP(max_tokens=mt, temperature=0.0,
                            stop_token_ids=[-1])):
                ids.extend(chunk.token_ids)
            return ids, _plane().stats().get("reshard_pulls", 0)

    pre_actor = Prefill.remote()
    dec_actor = Decode.remote()
    pre = rt.get(pre_actor.prefill.remote(prompt, n_tokens), timeout=180)
    ids, reshards = rt.get(dec_actor.decode.remote(pre, n_tokens), timeout=180)
    assert ids == want
    assert reshards == 1  # the pull really took the reshard path


def test_a_plane_asked_for_before_any_session_is_off_for_now_not_for_the_process(monkeypatch):
    """A first touch before any cluster session wrote its authkey (an engine's host-bytes handoff in a test that
    starts no cluster) leaves the control channel off and asks again at the next call: latched, it took paged
    handoff from every later file of an xdist worker (PR 62: tests/test_pd_paged.py, four cases, once)."""
    from ray_tpu.core import device_plane
    from ray_tpu.util.client import server

    key = []
    monkeypatch.setattr(server, "load_authkey", lambda: key[0] if key else None)
    fresh = device_plane.DevicePlane()
    assert not fresh.paged_available and fresh._control_disabled_reason is None and not fresh.available
    key.append(b"k" * 32)
    assert fresh.paged_available and fresh._control_disabled_reason is None
