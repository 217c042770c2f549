"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (reference analog: ray.cluster_utils.Cluster
single-machine multi-node simulation; SURVEY.md §4). Env vars must be set before anything
imports jax, hence module level here.

Every test has a time limit of its own (`TEST_TIMEOUT_S`): a test that waits for
ever fails by name and its xdist worker goes on with the rest of the file.
"""
import faulthandler
import hashlib
import os
import signal
import subprocess
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

# Belt and braces for a jax that something imported before this file set the
# environment: the config value is what backend selection reads.
jax.config.update("jax_platforms", "cpu")
# The tests' CPU programs run once, on a few dozen tokens, and are held to a reference's arithmetic, not
# to XLA's CPU code generator: its optimiser was most of tier-1's CPU time and none of its point. What
# is compiled for the described TPU is read for its optimised text and memory figure, and a few files hold
# CPU programs to the optimiser's own bits: both turn it back on (tests/compiled_step_text.py: `tpu_side`
# behind the `on_tpu` fixture, `optimised`). The benchmark's rehearsals are processes of their own and keep it.
jax.config.update("jax_disable_most_optimizations", True)

import pytest  # noqa: E402

# the family contract's tests live in a module pytest does not collect by itself: a
# family's file imports them (tests/family_contract.py)
pytest.register_assert_rewrite("family_contract")

# One test's set-up, call and tear-down together. Sized from the slowest test
# of whole runs in the driver's form (six xdist workers, 8 cores, PR 27):
# test_sac.py::test_sac_learns_pendulum, 60.0 and 63.7 s on an idle machine,
# 82 s with two suites on it, 100.2 s held to four cores. Three times the idle
# figure, rounded up; 2.4 times the slowest seen. A test that needs more is
# split or is truly `slow`: there is no per-test override.
TEST_TIMEOUT_S = 240
# What a cut test's finalizers (and the fresh cluster) may take before the
# backstop kills the worker; also the whole allowance of a test stuck in a C
# call that no signal handler can interrupt.
BACKSTOP_MARGIN_S = 60

_real_stderr = None  # fd 2 as it was before pytest's capture took it
_cut = False
_killed_a_worker = False


def _kill_group_when_gone():
    """An xdist worker leads a process group of its own, and a child that
    holds the other end of a pipe kills the group once the worker is gone by
    any exit, the backstop's `_exit` included: what a hung test started must
    not live on to meet the next test on a port or a core."""
    os.setpgid(0, 0)
    r, w = os.pipe()  # w stays open, and uninherited, for this process's life
    subprocess.Popen(
        [sys.executable, "-c",
         "import os, signal, sys\nsys.stdin.buffer.read()\n"
         "try: os.killpg(int(sys.argv[1]), signal.SIGKILL)\n"
         "except ProcessLookupError: pass",
         str(os.getpid())],
        stdin=r, start_new_session=True)
    os.close(r)


def pytest_configure(config):
    global _real_stderr
    _real_stderr = os.dup(2)  # capture is suspended while plugins configure
    if "PYTEST_XDIST_WORKER" in os.environ:
        _kill_group_when_gone()


def _on_limit(signum, frame):
    __tracebackhide__ = True
    global _cut
    _cut = True
    # into the test's captured stderr, so the report and the junit file carry it
    faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    pytest.fail(f"ran past the per-test limit of {TEST_TIMEOUT_S} s; every "
                "thread's stack is in the captured stderr", pytrace=True)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    global _cut, _killed_a_worker
    _cut = False
    # A file that exists while the test runs. Under `--dist loadfile` xdist
    # hands a dead worker's file to another worker with the test that killed
    # it still in it: found here, the test fails at once and is not run again.
    running = None
    if hasattr(item.config, "workerinput"):
        running = os.path.join(os.path.dirname(item.config.option.basetemp),
                               "running-" + hashlib.sha1(item.nodeid.encode()).hexdigest())
        _killed_a_worker = os.path.exists(running)
        open(running, "w").close()
    faulthandler.dump_traceback_later(TEST_TIMEOUT_S + BACKSTOP_MARGIN_S,
                                      exit=True, file=_real_stderr)
    signal.signal(signal.SIGALRM, _on_limit)
    signal.setitimer(signal.ITIMER_REAL, TEST_TIMEOUT_S)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        if _cut:
            _fresh_session_cluster()
        faulthandler.cancel_dump_traceback_later()
        if running is not None:
            os.unlink(running)


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    if _killed_a_worker:
        pytest.fail("killed the worker that ran it before (the 'node down' "
                    "line and the stacks above it name it): not run again",
                    pytrace=False)


def _init_session_cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=4, worker_env={"JAX_PLATFORMS": "cpu"}, max_workers_per_node=8)


def _fresh_session_cluster():
    """A test cut in the middle of a call may leave the cluster it shares with
    every other test of this worker holding a task that never completes."""
    ray_tpu = sys.modules.get("ray_tpu")
    if ray_tpu is not None and ray_tpu.is_initialized():
        ray_tpu.shutdown()
        _init_session_cluster()


@pytest.fixture(scope="session")
def rt():
    """Session-wide ray_tpu cluster. Worker pool recovers from destructive tests."""
    import ray_tpu

    _init_session_cluster()
    yield ray_tpu
    ray_tpu.shutdown()
