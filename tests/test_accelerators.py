"""TPU accelerator manager tests (reference _private/accelerators/tpu.py)."""
import pytest

from ray_tpu.core import accelerators
from ray_tpu.core.accelerators import TPUAcceleratorManager, TPUInfo

_TPU_ENV = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST",
            "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_ACCELERATOR_TYPE")


@pytest.fixture
def no_tpu_env(monkeypatch, tmp_path):
    """No override in the environment and no device file on the host."""
    for var in _TPU_ENV:
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(accelerators, "ACCEL_DEVICE_GLOB", str(tmp_path / "accel*"))
    monkeypatch.setattr(accelerators, "VFIO_DEVICE_DIR", str(tmp_path / "vfio"))
    return tmp_path


def test_detect_none_without_env(no_tpu_env):
    assert TPUAcceleratorManager.detect() is None
    assert TPUAcceleratorManager.node_resources() == {}


@pytest.mark.parametrize("files,chips", [
    (["vfio/vfio", "vfio/1"], 1),                       # the one-chip v5e machine
    (["vfio/vfio", "vfio/0", "vfio/1", "vfio/2", "vfio/3"], 4),
    (["accel0", "accel1", "accel2", "accel3"], 4),      # accel driver
    (["vfio/vfio"], 0),                                 # the container node alone
])
def test_detect_from_device_files_without_jax(no_tpu_env, monkeypatch, files, chips):
    """A driver that has not imported JAX learns the chip count from the
    device files, and JAX is never consulted (an imported one would hold the
    chip its workers need)."""
    import sys

    for f in files:
        path = no_tpu_env / f
        path.parent.mkdir(exist_ok=True)
        path.touch()

    class _NoJax:
        def __getattr__(self, name):
            raise AssertionError("chip detection must not ask JAX")

    monkeypatch.setitem(sys.modules, "jax", _NoJax())
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == chips
    assert TPUAcceleratorManager.node_resources().get("TPU", 0.0) == float(chips)


def test_device_files_beat_host_type_bounds(no_tpu_env, monkeypatch):
    """The runtime's bounds name the host TYPE (2x2 on a v5litepod-4 image); a
    machine of that type that exposes one chip has one."""
    (no_tpu_env / "vfio").mkdir()
    (no_tpu_env / "vfio" / "1").touch()
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 1
    monkeypatch.setenv("TPU_CHIPS_PER_HOST", "4")  # the explicit override still wins
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 4


def test_detect_from_env(no_tpu_env, monkeypatch):
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5e-8")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    info = TPUAcceleratorManager.detect()
    assert info.chips_per_host == 4
    assert info.accelerator_type == "v5e-8"
    assert info.pod_head_resource == "TPU-v5e-8-head"
    res = TPUAcceleratorManager.node_resources()
    assert res["TPU"] == 4.0
    assert res["TPU-v5e-8-head"] == 1.0


def test_non_head_worker_has_no_head_resource(monkeypatch):
    monkeypatch.setenv("TPU_CHIPS_PER_HOST", "4")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5e-16")
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    res = TPUAcceleratorManager.node_resources()
    assert res["TPU"] == 4.0
    assert "TPU-v5e-16-head" not in res  # only worker 0 anchors the slice


def test_visible_chips_override(monkeypatch):
    monkeypatch.setenv("TPU_CHIPS_PER_HOST", "8")
    env = TPUAcceleratorManager.visible_chips_env([0, 1], chips_on_host=8)
    assert env == {"TPU_VISIBLE_CHIPS": "0,1", "TPU_CHIPS_PER_HOST_BOUNDS": "1,2,1",
                   "TPU_HOST_BOUNDS": "1,1,1"}
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 2
    # a worker that holds the whole host needs no restriction
    assert TPUAcceleratorManager.visible_chips_env(range(8), chips_on_host=8) == {}


def test_tpu_workers_are_told_their_chips(rt):
    """Two tasks holding TPU: 1 each on one (fake, CPU-backed) four-chip node
    run in two processes, each told a chip of its own at spawn; a task that
    holds the whole host retires them and sees no restriction."""
    import os

    from ray_tpu.core import global_state
    from ray_tpu.core.task_spec import NodeAffinitySchedulingStrategy

    cluster = global_state.try_cluster()
    node = cluster.add_node({"CPU": 4.0, "TPU": 4.0})
    on_node = NodeAffinitySchedulingStrategy(node_id=node.node_id.hex(), soft=False)

    def chips(hold_s):
        import time

        time.sleep(hold_s)
        return os.getpid(), os.environ.get("TPU_VISIBLE_CHIPS"), \
            os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS")

    try:
        one = rt.remote(num_tpus=1, num_cpus=0.1, scheduling_strategy=on_node)(chips)
        seen = rt.get([one.remote(1.0), one.remote(1.0)], timeout=60)
        assert len({pid for pid, _, _ in seen}) == 2
        assert sorted(v for _, v, _ in seen) == ["0", "1"]
        assert {b for _, _, b in seen} == {"1,1,1"}
        # three chips: two are free, so ONE idle one-chip worker is retired
        # for the third and the other stays, to be used again
        three = rt.remote(num_tpus=3, num_cpus=0.1, scheduling_strategy=on_node)(chips)
        pid3, visible3, _ = rt.get(three.remote(0.0), timeout=60)
        assert len(visible3.split(",")) == 3
        kept = rt.get(one.remote(0.0), timeout=60)
        assert kept[0] in {p for p, _, _ in seen} and kept[1] not in visible3.split(",")
        whole = rt.remote(num_tpus=4, num_cpus=0.1, scheduling_strategy=on_node)(chips)
        pid, visible, _ = rt.get(whole.remote(0.0), timeout=60)
        assert visible is None and pid not in {pid3, *(p for p, _, _ in seen)}
        assert node.free_chips == []  # the idle whole-host worker still owns them
        assert node.chips_leaving == 0
    finally:
        cluster.remove_node(node.node_id)


def test_failed_spawn_gives_its_chips_back(rt, monkeypatch):
    from ray_tpu.core import global_state
    from ray_tpu.core.container import ContainerRuntimeError
    from ray_tpu.core.task_spec import NodeAffinitySchedulingStrategy

    cluster = global_state.try_cluster()
    node = cluster.add_node({"CPU": 1.0, "TPU": 2.0})

    def no_runtime(*args, **kwargs):
        raise ContainerRuntimeError("no container runtime on this host")

    monkeypatch.setattr(node, "spawn_worker", no_runtime)
    try:
        fn = rt.remote(num_tpus=1, num_cpus=0.1, scheduling_strategy=NodeAffinitySchedulingStrategy(
            node_id=node.node_id.hex(), soft=False))(lambda: 1)
        with pytest.raises(Exception, match="no container runtime"):
            rt.get(fn.remote(), timeout=60)
        assert node.free_chips == [0, 1]
    finally:
        cluster.remove_node(node.node_id)


@pytest.mark.parametrize("cluster_tpus,tp,expected", [
    (0.0, 1, None),                    # CPU cluster: no request, suites keep scheduling
    (4.0, 1, {"num_tpus": 1}),
    (4.0, 4, {"num_tpus": 4}),
])
def test_llm_app_asks_for_chips_when_the_cluster_has_them(monkeypatch, cluster_tpus,
                                                          tp, expected):
    import ray_tpu
    from ray_tpu.llm import LLMConfig, build_openai_app, build_pd_openai_app

    monkeypatch.setattr(ray_tpu, "is_initialized", lambda: True)
    monkeypatch.setattr(ray_tpu, "cluster_resources",
                        lambda: {"CPU": 4.0, "TPU": cluster_tpus})
    cfg = LLMConfig(model_id="m", model_source="byte-tiny", tensor_parallel_size=tp)

    def replica_options(app):
        found = []
        app._collect(found)
        return [b.deployment.config.ray_actor_options or None for b in found
                if b.deployment.name.startswith(("llm:", "llm-pd:"))]

    assert replica_options(build_openai_app([cfg])) == [expected]
    assert replica_options(build_pd_openai_app(cfg)) == [expected, expected]
    # the caller's own ray_actor_options still win
    cfg.deployment_config = {"ray_actor_options": {"num_cpus": 2}}
    assert replica_options(build_openai_app([cfg])) == [{"num_cpus": 2}]


def test_tpu_worker_on_the_wrong_platform_is_an_error(monkeypatch):
    from ray_tpu.core import global_state
    from ray_tpu.core.accelerators import TPUPlatformError, check_worker_platform

    class _Ctx:
        accel = "tpu"

    monkeypatch.setattr(global_state, "try_worker", lambda: _Ctx())
    # the CPU suite's fake TPU resources: JAX_PLATFORMS=cpu asked for the CPU
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert check_worker_platform() is None
    # nobody asked for the CPU, and JAX (here, the test's) is on it all the same
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(TPUPlatformError):
        check_worker_platform()
    _Ctx.accel = "cpu"
    assert check_worker_platform() is None


def test_slice_spanning_placement_group(rt):
    """The TPU-{pod}-head trick: a PG anchored on the head resource reserves the
    slice atomically (reference tpu.py:376 + SURVEY.md §7 phase 2)."""
    from ray_tpu.core import global_state
    from ray_tpu.util import placement_group_api as pg_api

    cluster = global_state.try_cluster()
    node = cluster.add_node({"CPU": 4.0, "TPU": 8.0, "TPU-v5e-8-head": 1.0})
    try:
        pg = pg_api.placement_group(
            [{"TPU-v5e-8-head": 1.0, "TPU": 4.0}, {"TPU": 4.0}], strategy="STRICT_PACK")
        assert pg.wait(timeout_seconds=30)
        bundles = cluster.pg_manager.bundles(pg.id)
        assert all(b.node_id == node.node_id for b in bundles)  # whole slice, one host
        pg_api.remove_placement_group(pg)
    finally:
        cluster.remove_node(node.node_id)