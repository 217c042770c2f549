"""Share of its roofline that a group of kernels reaches: the seconds their work NEEDS at
the chip's published peaks (`trainer.flops`'s function named by `work`, called with the
model, the step's tokens and the sequence's length: the larger of operations over the bf16
peak and bytes over the HBM bandwidth, a step, times the traced steps) over the traced
seconds of the operations whose name matches `pattern` (a kernel is found by the name the
trace gives it, as `trace_op_share` finds it). Only what the arithmetic needs is counted,
whatever implements it, so it cannot pass 100: a reading above is a wrong count. Nothing to
read where no operation matches (a parent whose program has no such kernel, or falls to
the XLA path) or the flops file has no such function."""
import importlib
import re

from benchmarks.lib import flops


def read(ctx, pattern, work):
    r = ctx["result"]
    trace = r.get("trace")
    name = ctx["config"].get("trainer", {}).get("flops")
    if not trace or name is None or not r.get("traced_steps") or ctx["rehearse"]:
        return None
    family = importlib.import_module(f"benchmarks.lib.{name}")
    if not hasattr(family, work):
        return None
    rx = re.compile(pattern)
    seconds = sum(s for op, s in trace["op_seconds"].items() if rx.search(op))
    if not seconds:
        return None
    need = getattr(family, work)(ctx["model"], r["tokens_per_step"], r["seq"])
    peaks = flops.peaks_for(r["device"]["kind"])
    needed = max(need["flops"] / peaks["bf16_flops_per_s"], need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * r["traced_steps"] * needed / (seconds * r["chips"])
