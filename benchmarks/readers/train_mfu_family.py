"""Model FLOP/s utilisation of a training cell whose configuration names its own flops
file (`trainer.flops`: a module of lib/ with `train_flops_per_token(model, seq)`):
`train_mfu`'s arithmetic over that family's count of what a token needs. Nothing where
the configuration names none (`readers/train_mfu.py` counts Llama's shapes)."""
import importlib
import statistics

from benchmarks.lib import flops


def read(ctx):
    r = ctx["result"]
    name = ctx["config"].get("trainer", {}).get("flops")
    if name is None or "tokens_per_step" not in r or ctx["rehearse"]:
        return None  # a rehearsal's CPU has no published peak, and no utilisation to report
    family = importlib.import_module(f"benchmarks.lib.{name}")
    peak = flops.peaks_for(r["device"]["kind"])["bf16_flops_per_s"]
    per_token = family.train_flops_per_token(ctx["model"], r["seq"])
    tokens_per_s = r["tokens_per_step"] / statistics.median(r["series"]["step_s"])
    return 100.0 * per_token * tokens_per_s / (r["chips"] * peak)
