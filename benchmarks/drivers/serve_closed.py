"""Driver `serve_closed`: callers that wait for their reply. A stated number of
clients, each sending its next streaming chat request when its last ended."""
from benchmarks.drivers import _serve
from benchmarks.lib import loadgen


def run(ctx: dict) -> dict:
    t = ctx["cell"]["traffic_parameters"]
    ctx["log"]({"phase": "schedule", "loop": "closed", "clients": t["clients"],
                "prompt_tokens": t["prompt_tokens"], "max_tokens": t["max_tokens"]})
    return _serve.measure(
        ctx, prompt_lens=[t["prompt_tokens"]], warm_tokens=t["warm_max_tokens"],
        run_window=lambda s: loadgen.run_closed(
            s.client, t["clients"], ctx["seconds"], t["prompt_tokens"], t["max_tokens"]))
