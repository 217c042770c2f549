"""How uneven the held experts' load is: rows of the fullest held expert over the mean
rows a held expert, a layer and a step (the step's counters `fullest_held_expert_rows`
and `held_assignments`), averaged over the window's steps and the expert layers. 1 is
even; the grouped products' time follows the sum, a deployment's step the fullest."""
import statistics


def read(ctx):
    series = ctx["result"].get("series", {})
    fullest, held = series.get("fullest_held_expert_rows"), series.get("held_assignments")
    model = ctx["model"]
    if not fullest or not held or "experts_held" not in model:
        return None
    n_held = model["n_experts"] // model["experts_held"][1]
    return statistics.fmean(f * n_held / h for fs, hs in zip(fullest, held)
                            for f, h in zip(fs, hs) if h)
