"""Head-death survivability (PR 18): typed HeadUnavailableError, degraded-mode
serving state, replayable control channels, and reattach idempotency.

The fast tier splits in two. Pure-logic tests drive the serve retry plane and
the long-poll pinning with monkeypatched controller calls; reattach
idempotency drives the head's `_reattach_agent` directly with a fake agent
stream (no subprocesses, deterministic double delivery). The two subprocess
tests bound the wall-clock cost: one spawns a standalone head and kills it to
prove every client entry point surfaces the typed error after a BOUNDED
reconnect window, the other arms the `head.control.recv` fail point in a real
node agent so the reconnect + reregister machinery runs against the LIVE head
— a simulated outage with no process ever dying, which is what keeps it out
of the slow tier. The real SIGKILL end-to-end lives in test_head_restart.py
and the head-chaos bench gate (core_bench.py --head-chaos).
"""
import os
import pickle
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
def outage_env(rt, tmp_path):
    """Standalone-head sandbox: shared session dir + journal, session cluster
    parked for the duration (the test_head_restart.py idiom)."""
    import ray_tpu

    ray_tpu.shutdown()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
           "RAY_TPU_SESSION_DIR": str(tmp_path / "session"),
           "RAY_TPU_GCS_PERSISTENCE_PATH": str(tmp_path / "gcs.journal")}
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


# ---------------------------------------------------------------- typed error

class TestHeadUnavailableError:
    def test_pickle_round_trip_keeps_fields(self):
        from ray_tpu.core.exceptions import HeadUnavailableError

        t0 = time.time() - 5.0
        err = HeadUnavailableError(t0, 7, "reconnect window expired",
                                   cause=ConnectionError("boom"))
        back = pickle.loads(pickle.dumps(err))
        assert back.outage_started_at == t0
        assert back.attempts == 7
        assert back.reason == "reconnect window expired"
        assert isinstance(back.cause, ConnectionError)
        assert back.outage_age_s >= 5.0
        assert "7 reconnect attempt" in str(back)

    def test_classified_retryable_but_not_replica_blame(self):
        from ray_tpu.core.exceptions import HeadUnavailableError, TaskError
        from ray_tpu.serve.handle import is_head_unavailable, is_replica_failure

        err = HeadUnavailableError(time.time(), 1, "x")
        assert is_replica_failure(err)
        assert is_head_unavailable(err)
        wrapped = TaskError(err)
        assert is_replica_failure(wrapped)
        assert is_head_unavailable(wrapped)
        assert not is_head_unavailable(ConnectionError("plain socket death"))


def test_retry_session_head_outage_spares_replica_budget(monkeypatch):
    """A head outage must not consume the replica retry budget or suspect the
    replica, but must still be BOUNDED by its own deadline."""
    from ray_tpu.core.exceptions import HeadUnavailableError
    from ray_tpu.serve.handle import _RetrySession

    monkeypatch.setenv("RAY_TPU_SERVE_RETRY_BACKOFF_S", "0.01")
    monkeypatch.setenv("RAY_TPU_SERVE_RETRY_BACKOFF_MAX_S", "0.02")
    monkeypatch.setenv("RAY_TPU_HEAD_RECONNECT_TIMEOUT_S", "30")

    class _Router:
        def suspect(self, *a):  # must never be called for a head failure
            raise AssertionError("head outage suspected a replica")

    class _Handle:
        app_name, deployment_name = "app", "dep"
        _router = _Router()

    sess = _RetrySession(_Handle(), (), {}, retryable=False, trace_id=None)
    assert sess.attempts_left == 0  # retryable=False: no replica budget at all
    sess.replica = object()  # a suspect() call would blow up via _Router
    err = HeadUnavailableError(time.time(), 1, "blip")
    sess.prepare_retry(err)  # retries despite the empty replica budget
    assert sess.attempts_left == 0 and sess.attempt == 1
    assert sess.head_deadline is not None
    # past the head deadline the SAME error surfaces instead of looping
    sess.head_deadline = time.monotonic() - 1.0
    with pytest.raises(HeadUnavailableError):
        sess.prepare_retry(err)


def test_long_poll_pins_view_through_outage(monkeypatch):
    """Controller gone: the long-poll loop PINS the last replica view (stamped
    stale) instead of dropping it, and clears the stamp on recovery."""
    import ray_tpu
    from ray_tpu.serve.handle import _LongPollClient

    from ray_tpu.serve.handle import _LongPollEntry

    lp = _LongPollClient()
    entry = _LongPollEntry()
    entry.replicas = ["r1", "r2"]
    lp.entries[("app", "dep")] = entry
    lp.versions["replicas::app/dep"] = 3

    state = {"mode": "down"}

    class _Ref:
        pass

    class _Controller:
        class listen_for_change:  # noqa: N801 — mimics .remote() shape
            @staticmethod
            def remote(watched, timeout):
                return _Ref()

    def fake_get_actor(name, *a, **k):
        if state["mode"] == "down":
            raise ConnectionError("head gone")
        return _Controller()

    def fake_get(ref, *a, **k):
        return {"replicas::app/dep": (4, ["r1", "r2", "r3"])}

    monkeypatch.setattr(ray_tpu, "get_actor", fake_get_actor)
    monkeypatch.setattr(ray_tpu, "get", fake_get)

    import threading
    t = threading.Thread(target=lp._loop, daemon=True)
    lp._thread = t
    t.start()
    deadline = time.time() + 5
    while entry.stale_since is None:
        assert time.time() < deadline, "outage never stamped the entry stale"
        time.sleep(0.02)
    assert entry.replicas == ["r1", "r2"]  # PINNED, not dropped
    assert entry.staleness_s() is not None and entry.staleness_s() >= 0.0
    state["mode"] = "up"  # head restarts: next poll refreshes and unpins
    deadline = time.time() + 5
    while entry.stale_since is not None:
        assert time.time() < deadline, "recovery never cleared the stale stamp"
        time.sleep(0.02)
    assert entry.replicas == ["r1", "r2", "r3"]
    with lp.lock:
        lp.entries.clear()  # lets the loop retire


def test_handle_refresh_keeps_last_known_view(monkeypatch):
    """Controller RPC failing must not strand a handle that already has a
    replica view — degraded mode serves from the last-known set."""
    from ray_tpu.serve.handle import DeploymentHandle

    h = DeploymentHandle("app", "dep")
    h._replicas = ["r1"]

    def boom():
        raise ConnectionError("head gone")

    monkeypatch.setattr(h, "_controller", boom)
    h._refresh(force=True)  # must NOT raise
    assert h._replicas == ["r1"]
    # a handle with NO view has nothing to serve from: the error surfaces
    h._replicas = []
    with pytest.raises(ConnectionError):
        h._refresh(force=True)


# ------------------------------------------------------- reattach idempotency

class _FakeAgentStream:
    """Just enough of the agent-side stream for _reattach_agent: reattach
    assigns the callbacks, sends welcome-back, and uses the object as the
    conn-table key."""

    def __init__(self):
        self.peer_ip = None
        self.on_message = None
        self.on_disconnect = None
        self.welcomed = []

    def send_welcome_back(self, payload):
        self.welcomed.append(payload)


def _reattach(cluster, node_hex, extras):
    stream = _FakeAgentStream()
    ok = cluster._reattach_agent(
        stream, ("reregister", node_hex, {"CPU": 2.0}, {}, 4, extras))
    return ok, stream


def test_reattach_double_replay_is_a_noop(rt):
    """The journal replay must be idempotent: a doubly-delivered reregister
    (reconnect racing the death detection) rebinds the same actor once, holds
    ONE arena pin, and leaves the journal record in place for a third replay."""
    import cloudpickle

    from dataclasses import replace

    from ray_tpu.core import global_state
    from ray_tpu.core.ids import ActorID, NodeID, ObjectID, WorkerID

    c = global_state.try_cluster()
    assert c is not None

    @rt.remote(name="journal-donor", lifetime="detached", max_restarts=0)
    class Donor:
        def ping(self):
            return "pong"

    d = Donor.remote()
    assert rt.get(d.ping.remote(), timeout=30) == "pong"
    donor_st = next(st for st in c.actors.values() if st.name == "journal-donor")

    node_hex = NodeID.generate().hex()
    wid_hex = WorkerID.generate().hex()
    spec = replace(donor_st.creation_spec, actor_id=ActorID.generate(),
                   actor_name="fake-survivor", node_id=None)
    rec = cloudpickle.dumps({
        "name": "fake-survivor", "namespace": "", "detached": True,
        "host": node_hex, "wid": wid_hex,
        "method_meta": donor_st.method_meta, "creation_spec": spec})
    c.gcs.kv.put(spec.actor_id.binary(), rec, namespace="@actors")

    oid = ObjectID(os.urandom(ObjectID.SIZE))
    extras = {"workers": ((wid_hex, None),), "data_port": None,
              "arena": "fake-arena", "objects": ((oid.binary(), 128, 0),)}

    ok, s1 = _reattach(c, node_hex, extras)
    assert ok and s1.welcomed[0]["keep_workers"] == [wid_hex]
    st = c.actors[spec.actor_id]
    assert st.state == "alive" and st.worker is not None
    refs_after_first = c.store._refcounts.get(oid, 0) \
        if hasattr(c.store, "_refcounts") else None

    # live mutation between deliveries: unrelated actors keep working
    assert rt.get(d.ping.remote(), timeout=30) == "pong"

    ok, s2 = _reattach(c, node_hex, extras)  # the double delivery
    assert ok and s2.welcomed[0]["keep_workers"] == [wid_hex]
    st = c.actors[spec.actor_id]
    assert st.state == "alive" and st.worker is not None
    # exactly one node entry for the host, bound to the NEWEST stream
    assert c._agents_by_key[node_hex].conn is s2
    alive = [n for n in rt.nodes()
             if n["Alive"] and n["NodeID"] == node_hex]
    assert len(alive) == 1
    # the journal record survived the replay (a third restart can rebind)
    assert c.gcs.kv.get(spec.actor_id.binary(), namespace="@actors") is not None
    # the arena pin was taken ONCE, not once per delivery
    if refs_after_first is not None:
        assert c.store._refcounts.get(oid, 0) == refs_after_first
    # the interleaved live actor still works after the second replay
    assert rt.get(d.ping.remote(), timeout=30) == "pong"

    # teardown: detach the fake node so later tests see a clean view
    agent = c._agents_by_key.get(node_hex)
    if agent is not None:
        c._on_agent_death(agent)
    c.gcs.kv.delete(spec.actor_id.binary(), namespace="@actors")
    rt.kill(d, no_restart=True)


def test_reattach_skips_corrupt_journal_records(rt):
    """A corrupt/unpicklable record in the @actors journal must be skipped —
    the reattach still lands and rebinds nothing from it."""
    from ray_tpu.core import global_state
    from ray_tpu.core.ids import NodeID

    c = global_state.try_cluster()
    c.gcs.kv.put(b"corrupt-record", b"\x00this is not a pickle",
                 namespace="@actors")
    try:
        node_hex = NodeID.generate().hex()
        ok, stream = _reattach(c, node_hex, {"workers": (), "data_port": None})
        assert ok and stream.welcomed[0]["keep_workers"] == []
        agent = c._agents_by_key.get(node_hex)
        assert agent is not None
        c._on_agent_death(agent)
    finally:
        c.gcs.kv.delete(b"corrupt-record", namespace="@actors")


def test_fn_registration_lands_in_the_gcs_journal(rt):
    """Function/class bytes must reach the @fns KV namespace when registered:
    workers and clients dedup register_fn per head lifetime, so a restarted
    head can only serve fetch_fn (actor restarts, replica replacements) from
    what the journal kept."""
    from ray_tpu.core import global_state

    c = global_state.try_cluster()
    fn_id, fn_bytes = b"\xabtest-fn-rec\x01\x02\x03\x04", b"not-really-a-pickle"
    try:
        c._register_fn(fn_id, fn_bytes)
        assert c.fn_table[fn_id] == fn_bytes
        assert c.gcs.kv.get(fn_id, namespace="@fns") == fn_bytes
        # idempotent under double delivery (a reconnecting worker may replay
        # its register_fn): second call is a no-op, not a journal rewrite
        c._register_fn(fn_id, b"different-bytes-must-not-win")
        assert c.fn_table[fn_id] == fn_bytes
        assert c.gcs.kv.get(fn_id, namespace="@fns") == fn_bytes
    finally:
        c.fn_table.pop(fn_id, None)
        c.gcs.kv.delete(fn_id, namespace="@fns")


# ----------------------------------------------------- client bounded typed raise

def test_client_entry_points_raise_typed_after_bounded_reconnect(
        outage_env, monkeypatch):
    """Kill the head with NO restart: get / wait / actor creation must each
    surface HeadUnavailableError once the (tiny) reconnect window expires —
    never a hang, never a raw socket error."""
    import ray_tpu
    from ray_tpu.core.exceptions import HeadUnavailableError

    monkeypatch.setenv("RAY_TPU_HEAD_RECONNECT_TIMEOUT_S", "1.5")
    monkeypatch.setenv("RAY_TPU_HEAD_RECONNECT_BACKOFF_S", "0.1")
    env, procs = outage_env
    node_port, client_port = _free_port(), _free_port()
    head = spawn_head(env, node_port, client_port)
    procs.append(head)

    ray_tpu.init(address=f"ray-tpu://127.0.0.1:{client_port}")
    try:
        @ray_tpu.remote
        def echo(x):
            return x

        ref = echo.remote(41)
        assert ray_tpu.get(ref, timeout=30) == 41

        os.kill(head.pid, signal.SIGKILL)
        head.wait(timeout=10)

        t0 = time.monotonic()
        with pytest.raises(HeadUnavailableError) as ei:
            ray_tpu.get(echo.remote(1), timeout=30)
        assert time.monotonic() - t0 < 15, "reconnect window was not bounded"
        assert ei.value.outage_started_at > 0

        with pytest.raises(HeadUnavailableError):
            ray_tpu.wait([ref], timeout=5)

        @ray_tpu.remote
        class A:
            def f(self):
                return 1

        with pytest.raises(HeadUnavailableError):
            A.remote()  # actor creation is a head-requiring op
    finally:
        ray_tpu.shutdown()


# --------------------------------------------- fail-point simulated outage

def test_agent_failpoint_outage_reattaches_to_live_head(outage_env):
    """Deterministic outage with no process death: the agent's
    head.control.recv fail point errors its recv loop twice, forcing two full
    reconnect + reregister cycles against the LIVE head. The node must keep
    its identity (one alive entry, same NodeID) and serve actors afterwards."""
    import ray_tpu

    env, procs = outage_env
    node_port, client_port = _free_port(), _free_port()
    head = spawn_head(env, node_port, client_port)
    procs.append(head)
    agent_env = {**env,
                 "RAY_TPU_FAULT_INJECTION": "head.control.recv=error@n=2",
                 "RAY_TPU_AGENT_RECONNECT_TIMEOUT_S": "30"}
    agent = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.core.node_agent",
         "--address", f"127.0.0.1:{node_port}", "--num-cpus", "2"],
        env=agent_env)
    procs.append(agent)

    ray_tpu.init(address=f"ray-tpu://127.0.0.1:{client_port}")
    try:
        deadline = time.time() + 40
        remote_nodes = []
        while time.time() < deadline:
            remote_nodes = [n for n in ray_tpu.nodes()
                            if n["Alive"] and n["Labels"].get("agent") == "remote"]
            if remote_nodes:
                break
            time.sleep(0.2)
        assert remote_nodes, "agent never (re)joined through the fail point"
        node_id = remote_nodes[0]["NodeID"]

        from ray_tpu.core.task_spec import NodeAffinitySchedulingStrategy

        @ray_tpu.remote(scheduling_strategy=NodeAffinitySchedulingStrategy(
            node_id=node_id), max_restarts=0)
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        # actor creation may race the second injected outage: retry briefly
        deadline = time.time() + 40
        got = None
        while time.time() < deadline:
            try:
                a = Counter.remote()
                got = ray_tpu.get(a.bump.remote(), timeout=20)
                break
            except Exception:
                time.sleep(0.5)
        assert got == 1, "actor never served after the simulated outages"
        # the storm did not duplicate the node or change its identity
        alive = [n for n in ray_tpu.nodes()
                 if n["Alive"] and n["Labels"].get("agent") == "remote"]
        assert len(alive) == 1 and alive[0]["NodeID"] == node_id
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------------ lint regression

def test_unbounded_reconnect_check_fires_and_clears(tmp_path):
    from ray_tpu.tools.analysis.base import Project, SourceFile
    from ray_tpu.tools.analysis.checks.blocking_control import UnboundedReconnect

    bad = ("import time\n"
           "def loop(self):\n"
           "    while True:\n"
           "        try:\n"
           "            return transport.dial(self.addr)\n"
           "        except Exception:\n"
           "            time.sleep(1)\n")
    good = ("import time\n"
            "def loop(self):\n"
            "    deadline = time.monotonic() + 30\n"
            "    while True:\n"
            "        if time.monotonic() >= deadline:\n"
            "            raise RuntimeError('head gone')\n"
            "        try:\n"
            "            return transport.dial(self.addr)\n"
            "        except Exception:\n"
            "            time.sleep(1)\n")
    check = UnboundedReconnect()
    out = {}
    for label, src in (("bad", bad), ("good", good)):
        p = tmp_path / f"{label}.py"
        p.write_text(src)
        f = SourceFile(str(tmp_path), f"{label}.py")
        out[label] = list(check.run(f, Project(str(tmp_path), [f])))
    assert len(out["bad"]) == 1 and "no deadline/attempt bound" in out["bad"][0].message
    assert out["good"] == []


def test_unbounded_reconnect_check_is_registered():
    """The tree-wide zero-violation gate lives in test_lint.py
    (test_ray_tpu_tree_is_lint_clean); a second full-tree walk here would
    double-pay ~5s of tier-1 budget. What that gate can't prove is that the
    new check participates at all — assert registration so the gate's
    'no failures' includes 'no unbounded reconnect loops'."""
    from ray_tpu.tools.analysis.checks import ALL_CHECKS
    from ray_tpu.tools.analysis.checks.blocking_control import UnboundedReconnect

    assert any(isinstance(c, UnboundedReconnect) for c in ALL_CHECKS)


# ------------------------------------------------------------ bench harness

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_head_chaos_bench_dry_run(tmp_path):
    """HEAD_CHAOS_BENCH smoke inside the tier-1 budget: the mode is wired and
    the gate file lands where pointed — no processes spawned, nothing killed."""
    import json

    out = tmp_path / "HEAD_CHAOS_BENCH.json"
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "core_bench.py"),
         "--head-chaos", "--dry-run", "--out", str(out)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    doc = json.loads(out.read_text())
    assert doc["dry_run"] is True
    assert set(doc["gates"]) == {
        "outage_within_10s", "zero_failed_unary", "streaming_never_hangs",
        "zero_healthy_nodes_reaped", "train_completed",
        "autoscaler_resumed_within_5_ticks", "passed"}


def test_head_chaos_checked_in_gates_pass():
    """The committed HEAD_CHAOS_BENCH.json evidence must show passing gates."""
    import json

    doc = json.loads(open(os.path.join(_REPO, "HEAD_CHAOS_BENCH.json")).read())
    g = doc["gates"]
    assert g["passed"] is True
    assert g["zero_failed_unary"] and g["streaming_never_hangs"]
    assert g["zero_healthy_nodes_reaped"] and g["train_completed"]
    assert doc["unary"]["failed"] == 0 and doc["unary"]["hung"] == 0
    assert doc["measured_outage_s"] <= 10.0
