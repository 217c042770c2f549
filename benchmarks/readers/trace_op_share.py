"""Share of the device's busy time in operations whose name matches a
pattern (a kernel is found by the name the trace gives it)."""
import re


def read(ctx, pattern):
    trace = ctx["result"].get("trace")
    if not trace or not trace["busy_s"]:
        return None
    rx = re.compile(pattern)
    return 100.0 * sum(s for name, s in trace["op_seconds"].items() if rx.search(name)) / trace["busy_s"]
