"""Counting the programs a process compiles (or reads from the compile cache):
JAX reports each as a duration event. Call `listen()` before the first jit."""

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def listen() -> list:
    """A list that grows by one entry for every program compiled from now on."""
    import jax

    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: seen.append(name) if name == COMPILE_EVENT else None)
    return seen
