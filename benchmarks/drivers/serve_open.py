"""Driver `serve_open`: independent users. Streaming chat requests arrive over
HTTP on a schedule fixed before the window (lib/loadgen.open_schedule: the
cell's rate and lengths, in the order `--seed` draws), whatever the server does."""
from benchmarks.drivers import _serve
from benchmarks.lib import loadgen


def run(ctx: dict) -> dict:
    t = ctx["cell"]["traffic_parameters"]
    schedule = loadgen.open_schedule(ctx["seed"], ctx["seconds"], t["rate_per_s"],
                                     t["prompt_tokens"], t["max_tokens"])
    ctx["log"]({"phase": "schedule", "loop": "open", "rate_per_s": t["rate_per_s"],
                "requests": len(schedule),
                "prompt_tokens_sum": sum(i["prompt_len"] for i in schedule),
                "max_tokens_sum": sum(i["max_tokens"] for i in schedule)})
    return _serve.measure(
        ctx, prompt_lens=t["warm_prompt_tokens"], warm_tokens=t["warm_max_tokens"],
        run_window=lambda s: loadgen.run_open(s.client, schedule, ctx["seconds"], t["drain_s"]))
