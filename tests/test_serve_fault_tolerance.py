"""Self-healing serve plane: request retries, graceful draining, load
shedding, and fail-point-driven chaos (tier-1: deterministic, no load
generators — bench_serve.py --chaos carries the open-loop SLO-burn runs).

Reference analogs: serve request retries on RayActorError
(_private/router.py), replica draining (deployment_state.py graceful stop),
proxy backpressure (503 + Retry-After)."""
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core.exceptions import (BackPressureError, FaultInjectedError,
                                     ReplicaUnavailableError, TaskError)
from ray_tpu.test_utils import wait_for_condition
from ray_tpu.util import fault_injection as fi
from ray_tpu.util import state as rs
from ray_tpu.util.fault_injection import ChaosController


@pytest.fixture(autouse=True)
def _cleanup(rt):
    fi.disarm()
    yield
    fi.disarm()
    serve.shutdown()


@serve.deployment
class Echo:
    def __call__(self, x):
        import os

        return (os.getpid(), x)


def test_retry_on_send_failure(rt):
    """A handle-side send failure (injected at the serve.handle.send fail
    point) is retried transparently: the caller sees the result, not the
    fault."""
    h = serve.run(Echo.options(num_replicas=2).bind(), name="ft-send")
    assert h.remote(1).result()[1] == 1  # warm path, replicas discovered
    fi.arm("serve.handle.send", mode="error", count=1)
    assert h.remote(2).result()[1] == 2  # first send fails, retry succeeds
    assert fi.fired("serve.handle.send") == 1


def test_retry_on_replica_failure_feeds_suspects(rt):
    """An injected replica-side failure (serve.replica.request) is classified
    retryable; the request is resent to a different replica and the failed one
    lands on the router's suspect list."""
    h = serve.run(Echo.options(num_replicas=2).bind(), name="ft-rep")
    assert h.remote(0).result()[1] == 0
    chaos = ChaosController()
    # serve.run returns once ONE replica runs: arm when both are there
    wait_for_condition(lambda: len(chaos._replica_actors("ft-rep", "Echo")) == 2, timeout=30)
    # every replica fails exactly once: whichever gets the request bounces it,
    # the retry lands elsewhere (or re-picks after the budget of exclusions)
    assert chaos.arm_replica("ft-rep", "Echo", "serve.replica.request",
                             mode="error", count=1) == 2
    assert h.remote(5).result()[1] == 5
    assert len(h._router.suspects) >= 1  # failure fed the suspect list
    # subsequent requests keep working (suspects only bias routing)
    assert h.remote(6).result()[1] == 6


def test_retryable_false_surfaces_failure(rt):
    @serve.deployment(num_replicas=2, retryable=False)
    class NoRetry:
        def __call__(self, x):
            return x

    h = serve.run(NoRetry.bind(), name="ft-noretry")
    assert h.remote(1).result() == 1
    ChaosController().arm_replica("ft-noretry", "NoRetry",
                                  "serve.replica.request", mode="error",
                                  count=1)
    with pytest.raises(TaskError) as ei:
        h.remote(2).result()
    assert isinstance(ei.value.cause, FaultInjectedError)


def test_streaming_retries_only_before_first_chunk(rt):
    @serve.deployment(num_replicas=2)
    class Streamer:
        def __call__(self, n):
            for i in range(n):
                yield i

    h = serve.run(Streamer.bind(), name="ft-stream")
    assert list(h.options(stream=True).remote(3)) == [0, 1, 2]
    # failure at request start (no chunk yielded): retried transparently
    chaos = ChaosController()
    chaos.arm_replica("ft-stream", "Streamer", "serve.replica.request",
                      mode="error", count=1)
    assert list(h.options(stream=True).remote(4)) == [0, 1, 2, 3]

    @serve.deployment(num_replicas=2)
    class MidStreamFail:
        def __call__(self, n):
            yield 0
            raise ReplicaUnavailableError("ft-mid", "MidStreamFail",
                                          reason="injected mid-stream")

    h2 = serve.run(MidStreamFail.bind(), name="ft-mid")
    gen = h2.options(stream=True).remote(3)
    assert next(gen) == 0  # first chunk delivered...
    with pytest.raises(Exception):  # ...so a retryable-class failure SURFACES
        next(gen)


def test_replica_process_death_absorbed(rt):
    """SIGKILL one of two replicas' worker processes: in-flight and subsequent
    requests retry against the survivor — zero caller-visible failures."""
    @serve.deployment(num_replicas=2, max_ongoing_requests=4)
    class Sturdy:
        def __call__(self, x):
            time.sleep(0.05)
            return x * 2

    h = serve.run(Sturdy.bind(), name="ft-kill")
    # warm both replicas so the router knows them
    assert {h.remote(i).result() for i in range(4)} == {0, 2, 4, 6}
    resps = [h.remote(i) for i in range(6)]  # in-flight during the kill
    assert ChaosController().kill_replica("ft-kill", "Sturdy", index=0)
    assert sorted(r.result(timeout_s=30) for r in resps) == [0, 2, 4, 6, 8, 10]
    assert h.remote(7).result() == 14  # steady state after the kill


def test_death_push_heals_view_before_health_check(rt):
    """Regression: a replica SIGKILLed right before a scale-down sits
    undetected in the routing view for up to health_check_period_s — long
    enough for the scale-down to drain the HEALTHY replicas and keep the
    corpse. The handle's authoritative death push (report_replica_failure)
    must remove it immediately so traffic keeps flowing."""
    @serve.deployment(num_replicas=3, max_ongoing_requests=4,
                      health_check_period_s=10)  # explicit blind window
    class W:
        def __call__(self, x):
            time.sleep(0.02)
            return x + 1

    h = serve.run(W.bind(), name="ft-deathpush")
    assert {h.remote(i).result() for i in range(6)} == {i + 1 for i in range(6)}
    assert ChaosController().kill_replica("ft-deathpush", "W", index=0)
    # scale down BEFORE any health check can notice the corpse: the drain
    # keeps the first replica — the dead one
    serve.run(W.options(num_replicas=1).bind(), name="ft-deathpush")
    for i in range(20):
        assert h.remote(i).result(timeout_s=30) == i + 1


def test_graceful_drain_scale_down_zero_failures(rt):
    """Acceptance: scale-down 3 -> 1 under concurrent traffic completes with
    ZERO request failures (draining replicas finish their in-flight work and
    leave the routing view before the kill)."""
    @serve.deployment(num_replicas=3, max_ongoing_requests=4)
    class Work:
        def __call__(self, x):
            time.sleep(0.04)
            return x + 1

    serve.run(Work.bind(), name="ft-drain")
    errors, done = [], [0]
    stop = threading.Event()

    def client():
        h = serve.get_deployment_handle("Work", "ft-drain")
        i = 0
        while not stop.is_set():
            try:
                assert h.remote(i).result(timeout_s=30) == i + 1
                done[0] += 1
            except Exception as e:  # noqa: BLE001 — the assertion under test
                errors.append(e)
                return
            i += 1

    threads = [threading.Thread(target=client) for _ in range(6)]
    [t.start() for t in threads]
    time.sleep(0.7)  # traffic across all 3 replicas
    serve.run(Work.options(num_replicas=1).bind(), name="ft-drain")
    time.sleep(1.5)  # traffic THROUGH the scale-down
    stop.set()
    [t.join(timeout=30) for t in threads]
    assert not errors, errors[:3]
    assert done[0] > 50
    controller = ray_tpu.get_actor("SERVE_CONTROLLER")
    wait_for_condition(
        lambda: ray_tpu.get(controller.get_deployment_info.remote(
            "ft-drain", "Work"))["num_running"] == 1,
        timeout=30, message="scale-down never converged to 1 replica")


def test_rolling_update_drains_old_version(rt):
    """Version bump under traffic: old replicas drain (zero failures), new
    version takes over."""
    @serve.deployment(num_replicas=2, version="v1")
    class Ver:
        def __call__(self, x):
            time.sleep(0.02)
            return "v1"

    serve.run(Ver.bind(), name="ft-roll")
    errors, seen = [], set()
    stop = threading.Event()

    def client():
        h = serve.get_deployment_handle("Ver", "ft-roll")
        while not stop.is_set():
            try:
                seen.add(h.remote(0).result(timeout_s=30))
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=client) for _ in range(4)]
    [t.start() for t in threads]
    time.sleep(0.4)

    @serve.deployment(num_replicas=2, version="v2")
    class Ver2:
        def __call__(self, x):
            time.sleep(0.02)
            return "v2"

    serve.run(Ver2.options(name="Ver").bind(), name="ft-roll")
    deadline = time.time() + 20
    while "v2" not in seen and time.time() < deadline and not errors:
        time.sleep(0.1)
    stop.set()
    [t.join(timeout=30) for t in threads]
    assert not errors, errors[:3]
    assert "v2" in seen  # new version serving; old drained without failures


def test_handle_sheds_beyond_queue_limit(rt):
    @serve.deployment(num_replicas=1, max_ongoing_requests=1,
                      max_queued_requests=1)
    class Slow:
        def __call__(self, x):
            time.sleep(0.5)
            return x

    h = serve.run(Slow.bind(), name="ft-shed")
    admitted = [h.remote(0), h.remote(1)]  # capacity 1 + queue 1
    with pytest.raises(BackPressureError) as ei:
        for i in range(4):  # depth accounting is monotone while Slow sleeps
            admitted.append(h.remote(2 + i))
    assert ei.value.retry_after_s > 0
    assert ei.value.queue_depth >= ei.value.limit == 2
    # admitted requests still complete — shedding protects them
    assert [r.result(timeout_s=30) for r in admitted[:2]] == [0, 1]
    shed = rs.get_metrics().get("serve_requests_shed_total", {}).get("values", {})
    assert any(dict(k).get("app") == "ft-shed" and v >= 1
               for k, v in shed.items())


def test_http_proxy_sheds_503_with_retry_after(rt):
    import urllib.error
    import urllib.request

    @serve.deployment(num_replicas=1, max_ongoing_requests=1,
                      max_queued_requests=0)
    class Slow:
        def __call__(self, x):
            time.sleep(0.6)
            return {"ok": True}

    serve.start(http_options={"port": 18431})
    serve.run(Slow.bind(), name="ft-http", route_prefix="/shed")
    statuses, retry_after = [], []

    def hit():
        try:
            resp = urllib.request.urlopen(
                "http://127.0.0.1:18431/shed?x=1", timeout=30)
            statuses.append(resp.status)
        except urllib.error.HTTPError as e:
            statuses.append(e.code)
            if e.code == 503:
                retry_after.append(e.headers.get("Retry-After"))

    threads = [threading.Thread(target=hit) for _ in range(5)]
    [t.start() for t in threads]
    [t.join(timeout=40) for t in threads]
    assert statuses.count(200) >= 1  # admitted work completed
    assert statuses.count(503) >= 1  # overload shed fast
    assert retry_after and all(int(ra) >= 1 for ra in retry_after)


def test_unhealthy_replica_replaced_and_view_converges(rt):
    """Satellite: failed health check -> kill -> reconcile replaces the
    replica and the long-poll view converges (injection-driven, no real
    crash)."""
    @serve.deployment(num_replicas=1, health_check_period_s=0.3)
    class Healthy:
        def __call__(self, x):
            import os

            return os.getpid()

    h = serve.run(Healthy.bind(), name="ft-heal")
    pid0 = h.remote(None).result()
    old_ids = {r._actor_id for r in h._replicas}
    # the replica now fails every health check; its REPLACEMENT starts clean
    # (arming is per-process state, not config)
    assert ChaosController().arm_replica("ft-heal", "Healthy",
                                         "serve.replica.health") == 1
    wait_for_condition(
        lambda: h.remote(None).result(timeout_s=30) != pid0,
        timeout=30, message="unhealthy replica never replaced")
    # long-poll view converged on the replacement
    from ray_tpu.serve.handle import _lp_registry

    entry = _lp_registry.get(("ft-heal", "Healthy"))
    assert entry is not None and entry.replicas is not None
    assert len(entry.replicas) == 1
    assert {r._actor_id for r in entry.replicas} != old_ids


def test_router_prunes_departed_replicas(rt):
    """Satellite: inflight/model_map/suspect state for replicas that left the
    long-poll view is pruned (no slow leak, no stale p2c counts)."""
    h = serve.run(Echo.options(num_replicas=2).bind(), name="ft-prune")
    pids = set()
    deadline = time.time() + 20
    while len(pids) < 2 and time.time() < deadline:
        pids |= {h.remote(i).result()[0] for i in range(10)}
    assert len(pids) == 2
    router = h._router
    assert len(router.inflight) == 2
    router.model_map["m"] = set(router.inflight)  # simulated affinity state
    serve.run(Echo.options(num_replicas=1).bind(), name="ft-prune")
    wait_for_condition(
        lambda: (h.remote(0).result() is not None
                 and len(router.inflight) == 1),
        timeout=30, message="router state never pruned after scale-down")
    live = {r._actor_id for r in h._replicas}
    assert set(router.inflight) <= live
    assert all(ids <= live for ids in router.model_map.values())


def test_single_shared_completion_waiter(rt):
    """Satellite: one waiter thread per handle batches completion waits (was:
    one daemon thread per request)."""
    @serve.deployment(max_ongoing_requests=8)
    class Pause:
        def __call__(self, x):
            time.sleep(0.3)
            return x

    def nthreads():
        return len(threading.enumerate())

    h = serve.run(Pause.bind(), name="ft-waiter")
    h.remote(0).result()
    before = nthreads()
    resps = [h.remote(i) for i in range(8)]
    assert h._waiter.outstanding() >= 1
    # 8 concurrent in-flight requests share ONE waiter thread for this handle
    # (the old design spawned one daemon thread per request)
    assert nthreads() <= before + 1
    assert sum(t.name == "serve-done-waiter" for t in threading.enumerate()
               if t is h._waiter._thread) == 1
    assert sorted(r.result(timeout_s=30) for r in resps) == list(range(8))
    wait_for_condition(lambda: h._waiter.outstanding() == 0, timeout=10,
                       message="waiter never drained")


@pytest.mark.slow
def test_open_loop_chaos_kill_zero_lost(rt):
    """Load-generating chaos (slow: tier-1 runs the deterministic fail-point
    variants above): open-loop HTTP load, SIGKILL a replica mid-stream — the
    retry plane + reconcile loop must lose ZERO requests and recover p99."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    import bench_serve

    serve.start(http_options={"port": 18445})
    out = bench_serve.run_chaos_kill(
        18445, replicas=3, moq=2, service_s=0.05, rps=35.0,
        warm_s=2.5, post_kill_s=8.0, app="ft-chaos")
    assert out["kill_zero_lost"], out
    assert out["kill_p99_recovery_s"] is not None, out


def test_drain_deadline_kills_stuck_replica(rt):
    """A replica that cannot finish its in-flight work inside drain_timeout_s
    is killed anyway — draining bounds shutdown, never wedges it."""
    # retryable=False: the doomed request must surface promptly instead of
    # burning serve_replica_wait_s retrying against a deleted app
    @serve.deployment(num_replicas=1, drain_timeout_s=0.5, retryable=False)
    class Stuck:
        def __call__(self, x):
            time.sleep(20)
            return x

    h = serve.run(Stuck.bind(), name="ft-stuck")
    resp = h.remote(1)  # pins the replica's in-flight count at 1
    time.sleep(0.2)
    serve.delete("ft-stuck")
    controller = ray_tpu.get_actor("SERVE_CONTROLLER")
    wait_for_condition(
        lambda: ray_tpu.get(controller.get_deployment_info.remote(
            "ft-stuck", "Stuck")) is None,
        timeout=10, message="drain deadline never reaped the stuck replica")
    with pytest.raises(Exception):
        resp.result(timeout_s=30)  # its request died with it (deadline burned)
