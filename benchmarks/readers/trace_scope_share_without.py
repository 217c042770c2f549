"""Share of the device's busy time in operations that ran under a `jax.named_scope`
whose name matches `pattern` and under none whose name matches `without`.

`trace_scope_share` credits a fusion to every scope one of its parts carries, which is
right for a layer and its parts and wrong for a thin part of the program that XLA fuses
into its neighbours': Adam over the head's weights carries `lm_head` and `optimizer`, the
final norm fused with the last layer's output carries `lm_head` and `mlp`, and a constant
made under `loss` rides in attention's fusions. This reader counts an operation for
`pattern` only where none of its parts belongs to what `without` names, so that the
number stays the named part's own and does not move with its neighbours. Nothing to read
where the driver made no join or nothing matches (`trace_scope_share`'s rule)."""
import re


def read(ctx, pattern, without):
    trace = ctx["result"].get("trace")
    if not trace or not trace["busy_s"] or not trace.get("op_scopes"):
        return None
    scope, other = re.compile(pattern), re.compile(without)
    under = 0.0
    for op, s in trace["op_seconds"].items():
        names = trace["op_scopes"].get(op, ())
        if any(scope.search(n) for n in names) and not any(other.search(n) for n in names):
            under += s
    return 100.0 * under / trace["busy_s"] if under else None
