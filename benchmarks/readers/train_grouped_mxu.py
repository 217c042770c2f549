"""Share of the MXU's peak that the expert layer's grouped products reach on the work
they NEED: the operations of gate, up, down and their transposes over the rows that fell
on held experts (the step's counter `held_assignments`, mean over the window's steps,
times the traced steps; `trainer.flops`'s `grouped_products_flops`), over the seconds of
the operations whose name matches `pattern` in the traced window, over the chip's
published peak. Rows a tile or a buffer pads are not counted, so it cannot pass 100."""
import importlib
import re
import statistics

from benchmarks.lib import flops


def read(ctx, pattern):
    r = ctx["result"]
    trace, rows = r.get("trace"), r.get("series", {}).get("held_assignments")
    name = ctx["config"].get("trainer", {}).get("flops")
    if not trace or not rows or name is None or not r.get("traced_steps") or ctx["rehearse"]:
        return None
    rx = re.compile(pattern)
    seconds = sum(s for op, s in trace["op_seconds"].items() if rx.search(op))
    if not seconds:
        return None
    family = importlib.import_module(f"benchmarks.lib.{name}")
    held = statistics.fmean(sum(step) for step in rows)  # all expert layers of a step
    needed = r["traced_steps"] * family.grouped_products_flops(ctx["model"], held)
    peak = flops.peaks_for(r["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * needed / (seconds * r["chips"] * peak)
