"""Share of the device's busy time in operations that ran under a `jax.named_scope`
whose name matches `pattern`, or whose own name matches `ops` (a kernel the compiler
makes itself, such as `ragged-dot`, keeps its name and loses its scope). The scopes are
those of the compiled program the trace is of (`lib/scope_seconds.py`; the driver makes
the join and leaves it under `trace["op_scopes"]`). Nothing to read where the driver made
no join or nothing matches: the parent of the PR that names the scope."""
import re


def read(ctx, pattern, ops=None):
    trace = ctx["result"].get("trace")
    if not trace or not trace["busy_s"] or not trace.get("op_scopes"):
        return None
    scope, own = re.compile(pattern), re.compile(ops) if ops else None
    under = sum(s for op, s in trace["op_seconds"].items()
                if any(scope.search(name) for name in trace["op_scopes"].get(op, ()))
                or (own is not None and own.search(op)))
    return 100.0 * under / trace["busy_s"] if under else None
