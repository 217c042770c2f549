"""Standalone head process for head-restart tests (tests/test_head_restart.py).

Runs the cluster head with a node server (agents join), a client server
(drivers join), and GCS journal persistence — all on fixed ports so a restarted
incarnation is reachable at the same addresses.
"""
import os
import select
import subprocess
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"


def spawn_head(env, node_port, client_port, timeout=60):
    """Start this file as a head process and return it once it has said
    HEAD_READY; a head that says nothing, or exits, fails the caller within
    `timeout` with what it did print."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), str(node_port), str(client_port)],
        env=env, stdout=subprocess.PIPE)
    deadline = time.monotonic() + timeout
    said = b""
    while b"HEAD_READY" not in said:
        ready, _, _ = select.select([proc.stdout], [], [],
                                    max(0.0, deadline - time.monotonic()))
        chunk = os.read(proc.stdout.fileno(), 4096) if ready else b""
        if not chunk:
            proc.kill()
            raise AssertionError(
                f"head on port {node_port} never said HEAD_READY within "
                f"{timeout} s (exit code {proc.poll()}); it printed {said!r}")
        said += chunk
    return proc


if __name__ == "__main__":
    import jax

    jax.config.update("jax_platforms", "cpu")
    import ray_tpu

    node_port, client_port = int(sys.argv[1]), int(sys.argv[2])
    # optional third arg: head-local CPUs (0 = pure control plane; every
    # actor/replica schedules onto agents — the head-chaos bench topology)
    num_cpus = int(sys.argv[3]) if len(sys.argv) > 3 else 1
    ray_tpu.init(num_cpus=num_cpus, node_server_port=node_port,
                 client_server_port=client_port,
                 worker_env={"JAX_PLATFORMS": "cpu"})
    print("HEAD_READY", flush=True)
    while True:
        time.sleep(0.5)
