"""Model FLOP/s utilisation of a training cell: the operations the forward
and backward passes need for a token (lib/flops.py; recomputation not
counted) times tokens a second, over the chips used times the published peak
of the chip the worker reported (lib/peaks.json). Tokens a second are a step's
tokens over the median step time: per-layer metrics come from the traced run,
whose window also holds the profiler's start and stop."""
import statistics

from benchmarks.lib import flops


def read(ctx):
    r = ctx["result"]
    if "tokens_per_step" not in r or ctx["rehearse"]:
        return None  # a rehearsal's CPU has no published peak, and no utilisation to report
    peak = flops.peaks_for(r["device"]["kind"])["bf16_flops_per_s"]
    per_token = flops.train_flops_per_token(ctx["model"], r["seq"])
    tokens_per_s = r["tokens_per_step"] / statistics.median(r["series"]["step_s"])
    return 100.0 * per_token * tokens_per_s / (r["chips"] * peak)
