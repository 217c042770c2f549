"""Share of the traced window in collective operations during which no other
operation runs on that device (the part of communication not hidden)."""


def read(ctx):
    trace = ctx["result"].get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
