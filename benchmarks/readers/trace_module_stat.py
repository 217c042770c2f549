"""A statistic of the runs of the jitted programs whose name matches a pattern
(the `XLA Modules` line of the device trace names each run `jit_<function>`):
`runs`, `total_s` or `median_s`, summed over the programs that match."""
import re


def read(ctx, pattern, stat, scale=1.0):
    trace = ctx["result"].get("trace")
    if not trace:
        return None
    found = [m[stat] for name, m in trace["modules"].items() if re.search(pattern, name)]
    return scale * sum(found) if found else None
