"""Find the rate a serving cell sustains, once: one set-up, then the cell's
open-loop mix at each of a few rates, one after the other.

    python benchmarks/sweep.py --workload <cell> --rates 2,4,6,8 --seconds 20 [--rehearse]

Not part of a check: the cell's file then carries the rate as a number.
"""
import argparse
import json
import sys
import time

import run as bench  # noqa: E402 - puts the repo's root on sys.path


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    from benchmarks.drivers import _serve
    from benchmarks.lib import loadgen
    from ray_tpu.core.accelerators import ensure_compile_cache_dir

    ensure_compile_cache_dir()
    ctx = bench.make_context(args.workload, args.seed, args.seconds, False, args.rehearse)
    config, t = ctx["config"], ctx["cell"]["traffic_parameters"]
    with _serve.Session(ctx) as s:
        s.warm_up(t["warm_prompt_tokens"], t["warm_max_tokens"],
                  concurrent=config["engine"]["max_num_seqs"])
        for rate in (float(r) for r in args.rates.split(",")):
            schedule = loadgen.open_schedule(args.seed, args.seconds, rate,
                                             t["prompt_tokens"], t["max_tokens"])
            before = s.call("metrics")
            run = loadgen.run_open(s.client, schedule, args.seconds, drain_s=180.0)
            out = loadgen.summarize(list(s.client.records), run)
            s.client.records.clear()
            s.wait_idle(300.0)
            after = s.call("metrics")
            out.update(rate_per_s=rate, offered_tokens_per_s=sum(
                i["max_tokens"] for i in schedule) / args.seconds,
                preemptions=after["num_preemptions"] - before["num_preemptions"],
                decode_fused_steps=after["decode_fused_steps"],
                decode_device_step_ms_engine_estimate=after["decode_device_step_ms"])
            bench.log(out)
            time.sleep(1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
