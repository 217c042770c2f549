"""Object spilling, memory-monitor OOM killing, lineage reconstruction
(reference: local_object_manager.h:43, memory_monitor.h:52 +
worker_killing_policy_retriable_fifo.h, object_recovery_manager.h:43)."""
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core import global_state, object_store


@pytest.fixture()
def small_store_cluster():
    """Own cluster with a tiny arena so spilling kicks in fast. Restores the
    session-wide cluster afterwards (conftest rt) so later rt tests keep working."""
    was_up = global_state.is_initialized()
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, object_store_memory=8 * 1024 * 1024,
                 worker_env={"JAX_PLATFORMS": "cpu"})
    yield global_state.worker().cluster
    ray_tpu.shutdown()
    if was_up:
        ray_tpu.init(num_cpus=4, worker_env={"JAX_PLATFORMS": "cpu"},
                     max_workers_per_node=8)


def test_spill_location_roundtrip(tmp_path):
    """spill_location moves bytes to disk; resolve reads them back zero-copy."""
    from ray_tpu.core.ids import ObjectID

    oid = ObjectID.generate()
    arr = np.arange(100_000, dtype=np.float64)  # ~800KB > inline threshold
    loc = object_store.materialize(arr, oid)
    assert loc[0] in ("arena", "shm")
    new_loc = object_store.spill_location(loc, str(tmp_path / "spill"))
    assert new_loc is not None and new_loc[0] == "disk"
    out = object_store.resolve(new_loc)
    np.testing.assert_array_equal(out, arr)
    # original storage is gone: resolving the old location raises ObjectLost
    with pytest.raises(object_store.ObjectLost):
        object_store.resolve(loc)


def test_pressure_spills_lru_and_gets_still_work(small_store_cluster):
    cluster = small_store_cluster
    # fill ~3x the 8MB arena with 1MB objects; the maintenance loop must spill
    refs = [ray_tpu.put(np.full(128 * 1024, i, np.float64)) for i in range(24)]
    # the maintenance loop must spill LRU objects until under the high watermark
    deadline = time.time() + 20
    while time.time() < deadline:
        if cluster.store.memory_bytes() <= 0.9 * cluster._object_store_capacity:
            break
        time.sleep(0.2)
    else:
        pytest.fail(f"memory not relieved: {cluster.store.memory_bytes()} bytes resident")
    with cluster.store._lock:
        kinds = {k[0] for k in cluster.store._locations.values()}
    assert "disk" in kinds
    # every object is still readable (most from disk now)
    for i, r in enumerate(refs):
        v = ray_tpu.get(r)
        assert v[0] == i and len(v) == 128 * 1024


def test_spill_frees_memory_after_the_directory_names_the_file(small_store_cluster,
                                                              monkeypatch):
    """A get that resolved an object's arena location just before the spill took
    the memory asks the directory again: by then it must name the disk copy (a
    put object has no lineage to fall back on). So spill_lru frees an object's
    memory only after it swapped the entry."""
    store = small_store_cluster.store
    stale = []
    real = object_store.free_local

    def checking(loc):
        with store._lock:
            stale.append(loc in store._locations.values())
        real(loc)

    monkeypatch.setattr(object_store, "free_local", checking)
    refs = [ray_tpu.put(np.full(128 * 1024, i, np.float64)) for i in range(4)]
    assert store.spill_lru(2 << 20, small_store_cluster.spill_dir) >= 2 << 20
    assert stale and not any(stale)
    for i, r in enumerate(refs):
        assert ray_tpu.get(r)[0] == i


def test_lineage_reconstruction_after_loss(small_store_cluster):
    cluster = small_store_cluster

    @ray_tpu.remote(max_retries=2)
    def produce(seed):
        rng = np.random.default_rng(seed)
        return rng.standard_normal(64 * 1024)  # ~512KB -> arena

    ref = produce.remote(7)
    first = ray_tpu.get(ref)
    # simulate loss: destroy the object's storage behind the directory's back
    loc = cluster.store.try_location(ref.id)
    assert loc[0] in ("arena", "shm")
    if loc[0] == "arena":
        object_store._open_arena(loc[1]).delete(loc[2])
    else:
        from multiprocessing import shared_memory

        seg = shared_memory.SharedMemory(name=loc[1])
        seg.close()
        seg.unlink()
    # driver get triggers reconstruction via lineage resubmit
    again = ray_tpu.get(ref)
    np.testing.assert_array_equal(first, again)


def test_lineage_reconstruction_for_task_args(small_store_cluster):
    cluster = small_store_cluster

    @ray_tpu.remote(max_retries=1)
    def produce():
        return np.ones(64 * 1024)

    @ray_tpu.remote
    def consume(x):
        return float(x.sum())

    ref = produce.remote()
    ray_tpu.get(ref)
    loc = cluster.store.try_location(ref.id)
    if loc[0] == "arena":
        object_store._open_arena(loc[1]).delete(loc[2])
    # worker-side arg resolution must recover through the coordinator
    assert ray_tpu.get(consume.remote(ref)) == 64 * 1024


def test_unreconstructable_object_raises(small_store_cluster):
    cluster = small_store_cluster
    ref = ray_tpu.put(np.zeros(64 * 1024))  # put objects have no lineage
    loc = cluster.store.try_location(ref.id)
    if loc[0] == "arena":
        object_store._open_arena(loc[1]).delete(loc[2])
        with pytest.raises(ray_tpu.ObjectLostError):
            ray_tpu.get(ref)


def test_memory_monitor_kills_newest_retriable_task(small_store_cluster):
    cluster = small_store_cluster
    fired = {"n": 0}

    def fake_sampler():
        # report pressure exactly once; recover afterwards
        fired["n"] += 1
        return 0.99 if fired["n"] < 3 else 0.10

    @ray_tpu.remote(max_retries=3)
    def slow():
        import time as t

        t.sleep(1.5)
        return os.getpid()

    cluster.memory_usage_threshold = 0.9
    cluster._memory_sampler = fake_sampler
    ref = slow.remote()
    time.sleep(0.3)  # let it dispatch, then the monitor kills it
    pid = ray_tpu.get(ref, timeout=60)
    assert isinstance(pid, int)
    assert cluster.num_oom_kills >= 1


def test_oom_error_when_not_retriable(small_store_cluster):
    cluster = small_store_cluster
    always_high = lambda: 0.99  # noqa: E731

    @ray_tpu.remote(max_retries=0)
    def hog():
        import time as t

        t.sleep(5)
        return 1

    ref = hog.remote()
    time.sleep(0.3)
    cluster.memory_usage_threshold = 0.9
    cluster._memory_sampler = always_high
    with pytest.raises((ray_tpu.OutOfMemoryError, ray_tpu.WorkerCrashedError)):
        ray_tpu.get(ref, timeout=30)
    cluster.memory_usage_threshold = 2.0  # stop the killer for teardown
