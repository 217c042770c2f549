"""Actor tests: creation, methods, state, named actors, restart, kill, actor-to-actor."""
import time

import pytest


def test_actor_basics(rt):
    @rt.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def inc(self, k=1):
            self.n += k
            return self.n

        def value(self):
            return self.n

    c = Counter.remote(10)
    assert rt.get(c.inc.remote()) == 11
    assert rt.get(c.inc.remote(5)) == 16
    assert rt.get(c.value.remote()) == 16


def test_actor_method_ordering(rt):
    @rt.remote
    class Appender:
        def __init__(self):
            self.items = []

        def add(self, x):
            self.items.append(x)
            return len(self.items)

        def items_list(self):
            return self.items

    a = Appender.remote()
    for i in range(20):
        a.add.remote(i)
    assert rt.get(a.items_list.remote()) == list(range(20))


def test_actor_error(rt):
    @rt.remote
    class Cranky:
        def fail(self):
            raise RuntimeError("nope")

        def ok(self):
            return "fine"

    c = Cranky.remote()
    with pytest.raises(rt.TaskError):
        rt.get(c.fail.remote())
    # Actor survives method errors.
    assert rt.get(c.ok.remote()) == "fine"


def test_named_actor(rt):
    @rt.remote
    class Registry:
        def ping(self):
            return "pong"

    original = Registry.options(name="reg1").remote()
    h = rt.get_actor("reg1")
    assert rt.get(h.ping.remote()) == "pong"
    with pytest.raises(ValueError):
        rt.get_actor("does-not-exist")


def test_actor_handle_passing(rt):
    @rt.remote
    class Store:
        def __init__(self):
            self.v = None

        def set(self, v):
            self.v = v

        def get(self):
            return self.v

    @rt.remote
    def writer(store):
        import ray_tpu

        ray_tpu.get(store.set.remote(123))
        return "done"

    s = Store.remote()
    assert rt.get(writer.remote(s)) == "done"
    assert rt.get(s.get.remote()) == 123


def test_actor_to_actor(rt):
    @rt.remote
    class Leaf:
        def compute(self, x):
            return x * 10

    @rt.remote
    class Root:
        def __init__(self, leaf):
            self.leaf = leaf

        def go(self, x):
            import ray_tpu

            return ray_tpu.get(self.leaf.compute.remote(x)) + 1

    leaf = Leaf.remote()
    root = Root.remote(leaf)
    assert rt.get(root.go.remote(5)) == 51


def test_kill_actor(rt):
    @rt.remote
    class Victim:
        def ping(self):
            return "alive"

    v = Victim.remote()
    assert rt.get(v.ping.remote()) == "alive"
    rt.kill(v)
    time.sleep(0.3)
    with pytest.raises((rt.ActorError, rt.ActorDiedError, rt.WorkerCrashedError)):
        rt.get(v.ping.remote(), timeout=10)


def test_kill_returns_with_the_actor_dead_and_its_name_free(rt, monkeypatch):
    """ray_tpu.kill() is synchronous: when it returns the name is free and a
    call fails with the kill as its cause, however fast the router thread is to
    meet the dead worker's pipe (here it is given half a second's start)."""
    from ray_tpu.core import global_state

    @rt.remote
    class Named:
        def ping(self):
            return "alive"

    a = Named.options(name="kill-me").remote()
    assert rt.get(a.ping.remote()) == "alive"
    process = global_state.try_cluster().actors[a._actor_id].worker.process
    terminate = process.terminate
    monkeypatch.setattr(process, "terminate", lambda: (terminate(), time.sleep(0.5)))
    rt.kill(a)
    with pytest.raises(ValueError):
        rt.get_actor("kill-me")
    with pytest.raises(rt.ActorDiedError, match="killed via ray_tpu.kill"):
        rt.get(a.ping.remote(), timeout=10)


def test_actor_restart(rt):
    @rt.remote(max_restarts=2)
    class Phoenix:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

        def die(self):
            import os

            os._exit(1)

    p = Phoenix.remote()
    assert rt.get(p.inc.remote()) == 1
    p.die.remote()
    time.sleep(1.0)
    # State resets after restart (no checkpoint), but the actor is alive again.
    deadline = time.time() + 30
    while True:
        try:
            assert rt.get(p.inc.remote(), timeout=10) == 1
            break
        except (rt.ActorError, rt.ActorDiedError, rt.WorkerCrashedError, rt.TaskError):
            if time.time() > deadline:
                raise
            time.sleep(0.2)


def test_worker_crash_retry(rt):
    @rt.remote(max_retries=2)
    def crash_once(key):
        import os
        import tempfile

        marker = os.path.join(tempfile.gettempdir(), f"crash_{key}")
        if not os.path.exists(marker):
            with open(marker, "w") as f:
                f.write("1")
            os._exit(1)
        return "survived"

    key = str(time.time()).replace(".", "")
    assert rt.get(crash_once.remote(key), timeout=60) == "survived"


def test_concurrency_groups(rt):
    """Named concurrency groups (reference concurrency_group_manager.h): parked
    calls in one group must not starve methods on the default pool."""
    import threading

    @rt.remote(max_concurrency=2, concurrency_groups={"listen": 0})
    class Host:
        def __init__(self):
            self.ev = threading.Event()

        @rt.method(concurrency_group="listen")
        def park(self):
            self.ev.wait(30)
            return "woke"

        def ping(self):
            return "pong"

        def wake(self):
            self.ev.set()
            return True

    h = Host.remote()
    # park more listeners than max_concurrency: default-pool RPCs must still run
    parked = [h.park.remote() for _ in range(6)]
    assert rt.get(h.ping.remote(), timeout=10) == "pong"
    assert rt.get(h.wake.remote(), timeout=10) is True
    assert rt.get(parked, timeout=30) == ["woke"] * 6


def test_concurrency_group_call_time_override(rt):
    import threading

    @rt.remote(max_concurrency=1, concurrency_groups={"io": 1})
    class A:
        def __init__(self):
            self.ev = threading.Event()

        def block(self):
            self.ev.wait(30)
            return 1

        def unblock(self):
            self.ev.set()
            return 2

    a = A.remote()
    blocked = a.block.remote()  # occupies the single default thread
    # route around it via the io group at call time
    assert rt.get(a.unblock.options(concurrency_group="io").remote(), timeout=10) == 2
    assert rt.get(blocked, timeout=10) == 1
