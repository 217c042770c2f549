"""Head fault tolerance: SIGKILL the head process, restart it on the same
ports, and keep using the cluster.

Reference: GCS server restart backed by Redis while raylets buffer and re-sync
(src/ray/gcs/gcs_server/gcs_redis_failure_detector.h, NotifyGCSRestart in
src/ray/protobuf/node_manager.proto:316). Here: the head journals detached/
named actor placements into the GCS KV journal; the surviving node agent
redials with backoff and re-registers its node id, its still-running worker
processes, and its arena contents; the restarted head rebinds the actors and
rebuilds the object directory.
"""
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from _head_main import spawn_head


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.fixture()
def restart_env(rt, tmp_path):
    """Shared session dir + GCS journal for all processes in the test; the
    session cluster is parked for the duration."""
    import ray_tpu

    ray_tpu.shutdown()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
           "RAY_TPU_SESSION_DIR": str(tmp_path / "session"),
           "RAY_TPU_GCS_PERSISTENCE_PATH": str(tmp_path / "gcs.journal"),
           "RAY_TPU_AGENT_RECONNECT_TIMEOUT_S": "60"}
    saved = {k: os.environ.get(k) for k in
             ("RAY_TPU_SESSION_DIR", "RAY_TPU_GCS_PERSISTENCE_PATH")}
    os.environ.update({k: env[k] for k in saved})
    procs = []
    try:
        yield env, procs
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        ray_tpu.shutdown()
        ray_tpu.init(num_cpus=4, worker_env={"JAX_PLATFORMS": "cpu"},
                     max_workers_per_node=8)


def test_head_restart_actor_and_object_survive(restart_env):
    import ray_tpu
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.object_ref import ObjectRef
    from ray_tpu.core.task_spec import NodeAffinitySchedulingStrategy

    env, procs = restart_env
    node_port, client_port = _free_port(), _free_port()
    head = spawn_head(env, node_port, client_port)
    procs.append(head)
    agent = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.core.node_agent",
         "--address", f"127.0.0.1:{node_port}", "--num-cpus", "2"], env=env)
    procs.append(agent)

    # -- before: a detached named actor + a big object, both on the agent -------
    ray_tpu.init(address=f"ray-tpu://127.0.0.1:{client_port}")
    deadline = time.time() + 30
    while len([n for n in ray_tpu.nodes() if n["Alive"]]) < 2:
        assert time.time() < deadline, "agent never joined"
        time.sleep(0.2)
    remote_id = next(n["NodeID"] for n in ray_tpu.nodes()
                     if n["Alive"] and n["Labels"].get("agent") == "remote")
    sched = NodeAffinitySchedulingStrategy(node_id=remote_id)

    @ray_tpu.remote(scheduling_strategy=sched, lifetime="detached",
                    name="survivor", max_restarts=0)
    class Survivor:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

        def make_data(self, k):
            import numpy as np

            import ray_tpu as rt

            # the actor OWNS the object (instance-held ref): it stays pinned in
            # this host's arena across client disconnects and head restarts
            self.keep = rt.put(np.full(100_000, float(k)))
            return self.keep.id.hex()

    s = Survivor.remote()
    assert ray_tpu.get(s.bump.remote(), timeout=60) == 1
    oid_hex = ray_tpu.get(s.make_data.remote(7.0), timeout=60)
    ref0 = ObjectRef(ObjectID.from_hex(oid_hex))
    assert float(ray_tpu.get(ref0, timeout=60)[0]) == 7.0
    del ref0
    ray_tpu.shutdown()  # drop the client cleanly; detached actor must survive

    # -- kill the head, restart it on the same ports ---------------------------
    os.kill(head.pid, signal.SIGKILL)
    head.wait(timeout=10)
    time.sleep(1.0)
    head2 = spawn_head(env, node_port, client_port)
    procs.append(head2)

    # -- after: agent re-attached; actor state + object survived ----------------
    ray_tpu.init(address=f"ray-tpu://127.0.0.1:{client_port}")
    deadline = time.time() + 60
    while True:
        alive = [n for n in ray_tpu.nodes()
                 if n["Alive"] and n["Labels"].get("agent") == "remote"]
        if alive:
            assert alive[0]["NodeID"] == remote_id  # SAME node id re-registered
            break
        assert time.time() < deadline, "agent never re-attached to the new head"
        time.sleep(0.3)
    h = ray_tpu.get_actor("survivor")
    # in-memory actor state (n=1) survived: the WORKER PROCESS was never
    # restarted, only rebound to the new head
    assert ray_tpu.get(h.bump.remote(), timeout=60) == 2
    # the pre-restart object is still addressable through the rebuilt directory
    ref = ObjectRef(ObjectID.from_hex(oid_hex))
    arr = ray_tpu.get(ref, timeout=60)
    assert float(arr[0]) == 7.0 and arr.shape == (100_000,)
    ray_tpu.shutdown()


def test_head_restart_new_address_external_journal(restart_env, tmp_path):
    """HA variant (reference: GCS behind EXTERNAL Redis, restartable anywhere,
    gcs_redis_failure_detector.h): the journal lives in a URI store (mock://
    — reachable only through the fs abstraction), the replacement head starts
    on a DIFFERENT node+client port, and the agent finds it via its candidate
    address list."""
    import ray_tpu

    env, procs = restart_env
    env = dict(env)
    mock_root = str(tmp_path / "bucket")
    env["RAY_TPU_MOCK_FS_ROOT"] = mock_root
    env["RAY_TPU_GCS_PERSISTENCE_PATH"] = "mock://gcs-ha/journal"
    os.environ["RAY_TPU_MOCK_FS_ROOT"] = mock_root
    try:
        port_a, client_a = _free_port(), _free_port()
        port_b, client_b = _free_port(), _free_port()
        head = spawn_head(env, port_a, client_a)
        procs.append(head)
        agent = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.core.node_agent",
             "--address", f"127.0.0.1:{port_a},127.0.0.1:{port_b}",
             "--num-cpus", "2"], env=env)
        procs.append(agent)

        ray_tpu.init(address=f"ray-tpu://127.0.0.1:{client_a}")
        deadline = time.time() + 30
        while len([n for n in ray_tpu.nodes() if n["Alive"]]) < 2:
            assert time.time() < deadline, "agent never joined"
            time.sleep(0.2)
        remote_id = next(n["NodeID"] for n in ray_tpu.nodes()
                         if n["Alive"] and n["Labels"].get("agent") == "remote")
        from ray_tpu.core.task_spec import NodeAffinitySchedulingStrategy

        sched = NodeAffinitySchedulingStrategy(node_id=remote_id)

        @ray_tpu.remote(scheduling_strategy=sched, lifetime="detached",
                        name="ha-survivor", max_restarts=0)
        class Survivor:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        s = Survivor.remote()
        assert ray_tpu.get(s.bump.remote(), timeout=60) == 1
        ray_tpu.shutdown()

        # journal segments exist ONLY behind the mock:// scheme
        assert os.path.isdir(os.path.join(mock_root, "gcs-ha", "journal"))

        # -- kill head A; replacement comes up at a DIFFERENT address ------------
        os.kill(head.pid, signal.SIGKILL)
        head.wait(timeout=10)
        time.sleep(1.0)
        head2 = spawn_head(env, port_b, client_b)
        procs.append(head2)

        ray_tpu.init(address=f"ray-tpu://127.0.0.1:{client_b}")
        deadline = time.time() + 60
        while True:
            alive = [n for n in ray_tpu.nodes()
                     if n["Alive"] and n["Labels"].get("agent") == "remote"]
            if alive:
                assert alive[0]["NodeID"] == remote_id
                break
            assert time.time() < deadline, "agent never found the new head"
            time.sleep(0.3)
        h = ray_tpu.get_actor("ha-survivor")
        assert ray_tpu.get(h.bump.remote(), timeout=60) == 2  # state survived
        ray_tpu.shutdown()
    finally:
        os.environ.pop("RAY_TPU_MOCK_FS_ROOT", None)


def test_uri_journal_split_brain_fence():
    """Two heads pointed at one journal URI (split-brain during failover):
    segment names embed writer tokens so appends can never overwrite each
    other, and the OLD writer is fenced out loudly (JournalFencedError) once a
    newer head claims the owner marker (ADVICE r4: no silent corruption)."""
    import uuid

    from ray_tpu.core.gcs import JournalFencedError, _UriJournal

    uri = f"mock://fence-{uuid.uuid4().hex[:8]}"
    j1 = _UriJournal(uri)
    j1.append(b"from-j1")
    j2 = _UriJournal(uri)  # replacement head: newest-writer-wins claim
    j2.append(b"from-j2")
    # j1 hits the fence at its next periodic owner check, not silently
    with pytest.raises(JournalFencedError):
        for _ in range(j1.owner_check_every + 1):
            j1.append(b"stale")
    # nothing was overwritten: every append from BOTH writers is a distinct
    # segment object (names carry the writer token)
    names = j2._segments()
    assert len(names) == len(set(names))
    assert any(j1.token in n for n in names)
    assert any(j2.token in n for n in names)
    # the surviving writer's compaction (destructive) also re-checks ownership
    j2.compact([b"snapshot"])
    assert len(j2._segments()) == 1
    # ...and a fenced writer may NOT compact
    with pytest.raises(JournalFencedError):
        j1.compact([b"bad"])
