"""Share of its roofline that the state-space scan reaches: the seconds its operations
NEED at the chip's published peaks (`trainer.flops`'s `scan_step_work`: the larger of
operations over the bf16 peak and bytes over the HBM bandwidth, a step, times the traced
steps) over the seconds of the traced operations that ran under the `scope` named (the
join of `lib/scope_seconds.py`, which `drivers/train_family.py` leaves under
`trace["op_scopes"]`). Only what the recurrence needs is counted (inputs read, outputs
written, four products), so it cannot pass 100: a reading above is a wrong count.
Nothing to read where the program has no such scope (a parent without the layer) or the
flops file no such function."""
import importlib

from benchmarks.lib import flops


def read(ctx, scope):
    r = ctx["result"]
    trace = r.get("trace")
    name = ctx["config"].get("trainer", {}).get("flops")
    if (not trace or not trace.get("op_scopes") or name is None or not r.get("traced_steps")
            or ctx["rehearse"]):
        return None
    family = importlib.import_module(f"benchmarks.lib.{name}")
    if not hasattr(family, "scan_step_work"):
        return None
    seconds = sum(s for op, s in trace["op_seconds"].items()
                  if scope in trace["op_scopes"].get(op, ()))
    if not seconds:
        return None
    work = family.scan_step_work(ctx["model"], r["tokens_per_step"])
    peaks = flops.peaks_for(r["device"]["kind"])
    needed = max(work["flops"] / peaks["bf16_flops_per_s"], work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * r["traced_steps"] * needed / (seconds * r["chips"])
