"""Idle share of the device over the traced window: 1 - busy / window."""


def read(ctx):
    trace = ctx["result"].get("trace")
    if not trace or trace["idle_share"] is None:
        return None
    return 100.0 * trace["idle_share"]
