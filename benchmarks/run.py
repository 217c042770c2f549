"""The benchmark's command: one cell, one run, one JSON line.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one new process. It reads the cell's file and its configuration's
file, hands both to the driver the cell names (`drivers/<driver>.py`), which
starts a ray_tpu cluster, sets up through the program's normal entry point,
warms up the cell's own shapes, measures for `--seconds` and shuts the cluster
down. Which metrics a cell reports is the manifest's to say (BENCHMARK.json: a
metric lists its cells under `workloads`, or names none and holds for all):
with `--trace 0` the last line carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, each computed by the reader that
`metrics/<name>.json` names (`readers/<reader>.py`). `--manifest` names another
file of the same form, for cells that are proposed and not yet listed.

This process never initialises a JAX backend: a chip belongs to one process,
and the device named in the last line is what the worker or replica saw. With
no TPU the run exits non-zero and prints no result line. `--rehearse` swaps the
configuration for the tiny one of the same name under `rehearsal/`, so that the
whole path runs under JAX_PLATFORMS=cpu; its last line says `correct: false`
and carries no metric.

Nothing in this file names a model, a cell or a metric: a later PR adds files
under configs/, workloads/, metrics/, readers/ or drivers/ and entries in
BENCHMARK.json (a new cell's name under the `workloads` of the metrics it
reports, a new metric with the cells it holds for), and edits nothing here.
"""
import argparse
import importlib
import json
import os
import sys
import time

T_PROCESS_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# module level, not under the __main__ check: ray_tpu's workers are spawned,
# re-import this file, and must find `benchmarks.*` and `ray_tpu` as the driver does
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL_KEYS = ("config", "chips", "driver", "traffic", "why")
CONFIG_KEYS = ("source", "reduced", "assumed", "deployment")


def log(obj: dict) -> None:
    """A line before the last: phases of set-up, sample counts, lateness."""
    print(json.dumps(obj), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    cell = load_json("workloads", f"{name}.json")
    missing = [k for k in CELL_KEYS if k not in cell]
    if missing:
        raise SystemExit(f"workloads/{name}.json lacks {missing}")
    if len(cell["why"]) > 200:
        raise SystemExit(f"workloads/{name}.json: why is longer than 200 characters")
    return cell


def load_config(name: str, rehearse: bool) -> dict:
    config = load_json("rehearsal" if rehearse else "configs", f"{name}.json")
    missing = [k for k in CONFIG_KEYS if k not in config]
    if missing:
        raise SystemExit(f"configuration {name}.json lacks {missing}")
    if len(config["source"]) > 200:
        raise SystemExit(f"configuration {name}.json: source is longer than 200 characters")
    return config


def cell_metrics(manifest: dict, cell_name: str, kind: str) -> dict:
    """{name: unit} of the manifest's `kind` metrics that hold for this cell."""
    if cell_name not in [w["name"] for w in manifest["workloads"]]:
        raise SystemExit(f"the manifest lists no cell {cell_name!r}: a proposed cell "
                         "needs --manifest <file of BENCHMARK.json's form>")
    return {m["name"]: m["unit"] for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]}


def read_per_layer(ctx: dict, metrics: dict) -> dict:
    out = {}
    for name, unit in metrics.items():
        m = load_json("metrics", f"{name}.json")
        reader = importlib.import_module(f"benchmarks.readers.{m['reader']}")
        value = reader.read(ctx, **m.get("args", {}))
        if value is not None:  # a reader that finds nothing to read returns nothing
            out[name] = {"value": value, "unit": unit}
    return out


def make_context(workload: str, seed: int, seconds: float, trace: bool, rehearse: bool,
                 keep_trace=None) -> dict:
    """What a driver and the readers are handed."""
    from benchmarks.lib import modelcfg

    cell = load_cell(workload)
    config = load_config(cell["config"], rehearse)
    return {"cell_name": workload, "cell": cell, "config": config,
            "model": modelcfg.model_keys(config), "seed": seed, "seconds": seconds,
            "trace": trace, "rehearse": rehearse, "t_process_start": T_PROCESS_START,
            "log": log, "keep_trace": keep_trace}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny configuration from rehearsal/, for JAX_PLATFORMS=cpu")
    p.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                   help="which metrics each cell reports (default: the repository's manifest)")
    p.add_argument("--keep-trace", default=None, metavar="DIR",
                   help="with --trace 1: copy the profiler's .xplane.pb into DIR")
    args = p.parse_args()

    from ray_tpu.core.accelerators import ensure_compile_cache_dir, jax_backend_untouched

    ctx = make_context(args.workload, args.seed, args.seconds, bool(args.trace),
                       args.rehearse, args.keep_trace)
    cell = ctx["cell"]
    with open(args.manifest) as f:
        wanted = cell_metrics(json.load(f), args.workload,
                              "per_layer" if args.trace else "end_to_end")
    driver = importlib.import_module(f"benchmarks.drivers.{cell['driver']}")
    log({"phase": "start", "cell": args.workload, "config": cell["config"],
         "driver": cell["driver"], "chips": cell["chips"], "seed": args.seed,
         "seconds": args.seconds, "trace": args.trace, "rehearse": args.rehearse,
         "compile_cache_dir": ensure_compile_cache_dir()})

    result = driver.run(ctx)
    if not jax_backend_untouched():
        raise SystemExit("the driver process initialised a JAX backend")
    device = result["device"]
    if not args.rehearse and (device["platform"] != "tpu" or device["count"] != cell["chips"]):
        raise SystemExit(f"the cell asks for {cell['chips']} TPU chip(s); the worker saw {device}")

    ctx["result"] = result
    metrics = read_per_layer(ctx, wanted) if args.trace else {
        name: result["end_to_end"][name] for name in wanted}
    line = {"correct": bool(result["correct"]), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if args.trace and result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    if args.rehearse:
        # a rehearsal proves the path, not the system: its numbers go on an
        # earlier line under another name, never under a device metric's
        log({"phase": "rehearsal_values", "values": metrics,
             "would_be_correct": line["correct"], "breakdown": line.pop("breakdown", None)})
        line.update(correct=False, metrics={})
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
