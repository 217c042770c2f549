"""ray-tpu CLI: start/stop/status/submit/job (reference python/ray/scripts/
scripts.py — `ray start` :676, `ray submit` :1718, `ray stop` :1184, plus the
`ray job` group from dashboard/modules/job/cli.py).

Single-host note: the runtime is in-process (no separate GCS/raylet daemons), so
`start` records the head session + brings up the dashboard for external
observation, and drivers attach by just calling ray_tpu.init() — the reference's
`ray.init(address=...)` flow collapses to session-dir discovery.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from ray_tpu.job.manager import JobManager, default_session_dir


def _session_file() -> str:
    return os.path.join(default_session_dir(), "head.json")


def cmd_start(args) -> int:
    if args.address:
        # join an existing head as this host's node agent (reference:
        # `ray start --address=...` bringing up a worker-node raylet)
        from ray_tpu.core.node_agent import agent_main

        resources = None
        if args.num_cpus is not None:
            from ray_tpu.core.resources import normalize_resources

            resources = normalize_resources(num_cpus=args.num_cpus, num_tpus=0.0,
                                            resources=None)
        print(f"joining head at {args.address} as a node agent (ctrl-c to leave)")
        try:
            agent_main(args.address, resources=resources)
        except KeyboardInterrupt:
            pass
        return 0
    os.makedirs(default_session_dir(), exist_ok=True)
    from ray_tpu.config import CONFIG

    dashboard_port = (args.dashboard_port if args.dashboard_port is not None
                      else CONFIG.dashboard_port)
    info = {
        "started_at": time.time(),
        "pid": os.getpid(),
        "num_cpus": args.num_cpus,
        "dashboard_port": dashboard_port,
    }
    if args.node_server_port is not None:
        info["node_server_port"] = args.node_server_port
    with open(_session_file(), "w") as f:
        json.dump(info, f)
    print(f"ray_tpu head session recorded at {_session_file()}")
    if args.block:
        import ray_tpu
        from ray_tpu.dashboard import Dashboard

        ray_tpu.init(num_cpus=args.num_cpus,
                     node_server_port=args.node_server_port,
                     node_server_host=args.node_server_host)
        if args.node_server_port is not None:
            from ray_tpu.core import global_state

            port = global_state.cluster().node_server_port
            print(f"node server: {args.node_server_host}:{port} "
                  "(join with `ray-tpu start --address=HOST:PORT`)")
        dash = Dashboard(port=dashboard_port)
        scheme = "https" if CONFIG.serve_ingress_tls else "http"
        print(f"dashboard: {scheme}://127.0.0.1:{dashboard_port}/api/summary")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            dash.stop()
            ray_tpu.shutdown()
    return 0


def cmd_tls_init(args) -> int:
    from ray_tpu.core.tls_utils import generate_self_signed_tls

    paths = generate_self_signed_tls(args.dir, extra_sans=tuple(args.san))
    print("wrote:")
    for name, p in paths.items():
        print(f"  {name}: {p}")
    print("enable with:")
    print("  export RAY_TPU_USE_TLS=1")
    print(f"  export RAY_TPU_TLS_CA={paths['ca']}")
    print(f"  export RAY_TPU_TLS_CERT={paths['cert']}")
    print(f"  export RAY_TPU_TLS_KEY={paths['key']}")
    print("WARNING: keep the CA private key OFF cluster nodes — distribute only "
          "ca.crt, cluster.crt and cluster.key; anyone holding "
          f"{paths['ca_key']} can mint certificates this cluster trusts.")
    return 0


def cmd_stop(args) -> int:
    try:
        os.remove(_session_file())
        print("head session cleared")
    except FileNotFoundError:
        print("no head session")
    return 0


def _render_status(s: dict) -> str:
    """Human-facing render of util/state.cluster_status(): one short block per
    subsystem, omitting rows with no signal yet."""
    lines = []
    c = s.get("cluster", {})
    lines.append(f"cluster    nodes={c.get('nodes')} workers={c.get('workers')} "
                 f"actors={c.get('actors')} pending_tasks={c.get('pending_tasks')}")
    tr = s.get("transfer", {})
    for path, row in sorted(tr.items()):
        gbps = f"{row['gbps']:.2f} GB/s" if row.get("gbps") is not None else "-"
        lines.append(f"transfer   [{path}] pulls={row['pulls']} "
                     f"bytes={row['bytes']:,} rate={gbps}")
    col = s.get("collective", {})
    if col.get("ops") or col.get("aborts"):
        ops = " ".join(f"{k}={v}" for k, v in sorted(col.get("ops", {}).items()))
        lines.append(f"collective ops: {ops or '-'}  aborts={col.get('aborts', 0)} "
                     f"observed={col.get('aborts_observed', 0)} "
                     f"epoch_rollovers={col.get('epoch_rollovers', 0)}")
    sv = s.get("serve", {})
    if sv.get("requests") or sv.get("queue_depth"):
        def ms(v):
            return f"{v * 1e3:.1f}ms" if v is not None else "-"

        depth = " ".join(f"{k}:{int(v)}" for k, v in sorted(
            sv.get("queue_depth", {}).items()))
        lines.append(f"serve      requests={sv.get('requests', 0)} "
                     f"ttft_p50={ms(sv.get('ttft_p50_s'))} "
                     f"ttft_p99={ms(sv.get('ttft_p99_s'))} "
                     f"queue_depth[{depth or '-'}]")
    asc = sv.get("autoscale") or {}
    if asc.get("targets") or asc.get("decisions_by_reason"):
        for key, row in sorted(asc.get("targets", {}).items()):
            burn = "burning" if row.get("burning") else "ok"
            lines.append(
                f"autoscale  {key}: target={row.get('target')} "
                f"running={row.get('running')} "
                f"queue={row.get('queue_depth', 0):.0f} {burn} "
                f"({row.get('reason', '-')})")
        last = asc.get("last_decision")
        reasons = " ".join(f"{k}={v}" for k, v in sorted(
            asc.get("decisions_by_reason", {}).items()))
        tail = f"  last={last.get('event')}:{last.get('reason', '')}" \
            if isinstance(last, dict) and last.get("event") != "scale" else ""
        if last and isinstance(last, dict) and last.get("event") == "scale":
            tail = (f"  last={last['key']} {last['from']}->{last['to']} "
                    f"({last['reason']})")
        lines.append(f"autoscale  decisions[{reasons or '-'}]{tail}")
    llm = s.get("llm", {})
    if llm.get("prefix_cache_hits") or llm.get("active") or llm.get("pending"):
        fused = " ".join(f"{k}:{int(v)}" for k, v in sorted(
            (llm.get("fused_steps") or {}).items()))
        burst = llm.get("burst_tokens_per_s_p50")
        burst_txt = f"{burst:.0f}" if burst else "-"
        lines.append(f"llm        pending={llm.get('pending')} "
                     f"active={llm.get('active')} "
                     f"tokens={llm.get('generated_tokens', 0)} "
                     f"burst_tok/s_p50={burst_txt} "
                     f"fused_k[{fused or '-'}] "
                     f"prefix_cache hit/miss/skip="
                     f"{llm.get('prefix_cache_hits', 0)}/"
                     f"{llm.get('prefix_cache_misses', 0)}/"
                     f"{llm.get('prefix_cache_skipped', 0)}")
    cp = s.get("control_plane", {})
    if cp.get("scrape_p99_s") is not None or cp.get("nodes_aggregated"):
        def cms(v):
            return f"{v * 1e3:.1f}ms" if v is not None else "-"

        dec = " ".join(f"{k}:{cms(v)}" for k, v in sorted(
            (cp.get("decision_p99_s") or {}).items()))
        lines.append(f"control    scrape_p99={cms(cp.get('scrape_p99_s'))} "
                     f"decision_p99[{dec or '-'}] "
                     f"agg_nodes={cp.get('nodes_aggregated', 0)} "
                     f"direct_workers={cp.get('workers_direct', 0)}")
        dropped = sum((cp.get("dropped_series") or {}).values())
        if (cp.get("backpressure_level") or cp.get("inlet_shed")
                or cp.get("backpressure_transitions") or dropped):
            lines.append(
                f"control    backpressure level={cp.get('backpressure_level', 0) or 0:.0f} "
                f"transitions={cp.get('backpressure_transitions', 0)} "
                f"inlet_frames={cp.get('inlet_frames') or 0:.0f} "
                f"shed={cp.get('inlet_shed', 0)} dropped_series={dropped}")
    tn = s.get("train", {})
    if tn.get("mfu") or tn.get("step_phases_s"):
        mfu = " ".join(f"{k}:{v:.3f}" for k, v in sorted(tn.get("mfu", {}).items()))
        phases = " ".join(f"{k}:{v * 1e3:.1f}ms"
                          for k, v in sorted(tn.get("step_phases_s", {}).items()))
        lines.append(f"train      mfu[{mfu or '-'}] step_phases[{phases or '-'}]")
    if tn.get("steps") or tn.get("setup_seconds") or tn.get("group_failures"):
        laps = " ".join(f"{k}:{v:.2f}ms" for k, v in (tn.get("loop_ms_per_step") or {}).items())
        setup = " ".join(f"{k.rpartition('.')[2]}:{v:.1f}s"
                         for k, v in sorted((tn.get("setup_seconds") or {}).items()))
        lines.append(
            f"train      steps={tn.get('steps', 0)} slow={tn.get('slow_steps', 0)} loop/step[{laps or '-'}] "
            f"compiles={tn.get('compiles', 0)} gc={tn.get('gc_pause_ms', 0):.1f}ms/"
            f"{tn.get('gc_collections', 0)} group_failures={tn.get('group_failures', 0)} "
            f"setup[{setup or '-'}]")
    bubbles = tn.get("pipeline_bubble_fraction") or {}
    if bubbles:
        frac = " ".join(f"{k}:{v:.2f}" for k, v in sorted(bubbles.items()))
        lines.append(f"train      pipeline_bubble[{frac}]")
    rl = s.get("rl", {})
    if rl.get("env_steps") or rl.get("learner_updates"):
        blocks = " ".join(f"{k}:{v}" for k, v in sorted(
            (rl.get("blocks") or {}).items()))
        lag99 = rl.get("block_lag_p99")
        lines.append(f"rl         env_steps={rl.get('env_steps', 0)} "
                     f"updates={rl.get('learner_updates', 0)} "
                     f"broadcasts={rl.get('weight_broadcasts', 0)} "
                     f"blocks[{blocks or '-'}] "
                     f"queue_depth={rl.get('queue_depth') or 0:.0f} "
                     f"lag_p99={f'{lag99:.1f}' if lag99 is not None else '-'}")
    return "\n".join(lines)


_SPARK_BLOCKS = "▁▂▃▄▅▆▇█"


def _sparkline(values, width: int = 30) -> str:
    """Render a numeric series (None = no data) as unicode block bars.
    Scaled against the RENDERED slice only — an old spike outside the last
    `width` points must not flatten every visible bar."""
    values = values[-width:]
    vals = [v for v in values if v is not None]
    if not vals:
        return "-" * min(width, 8)
    lo, hi = min(vals), max(vals)
    span = (hi - lo) or 1.0
    out = []
    for v in values:
        if v is None:
            out.append(" ")
        else:
            out.append(_SPARK_BLOCKS[min(7, int((v - lo) / span * 7.999))])
    return "".join(out)


def _render_history(hist: dict) -> str:
    """Sparkline block for `ray-tpu status --watch`: one row per history
    series that has any signal, latest value alongside."""
    ts, series = hist.get("ts", []), hist.get("series", {})
    if len(ts) < 2:
        return "history    (warming up: <2 frames scraped yet)"
    lines = []
    for name, vals in series.items():
        live = [v for v in vals if v is not None]
        if not live:
            continue
        latest = live[-1]
        if name.endswith("_per_s"):
            shown = f"{latest:,.1f}/s"
        elif name.endswith("_s"):
            shown = f"{latest * 1e3:.1f}ms"
        else:
            shown = f"{latest:,.1f}"
        lines.append(f"  {name:<24} {_sparkline(vals)} {shown}")
    if not lines:
        return "history    (no series with data yet)"
    span = ts[-1] - ts[0]
    return "\n".join([f"history    last {span:.0f}s, {len(ts)} frames:"] + lines)


def _render_slo(status: dict) -> str:
    if not status:
        return ""
    lines = ["slo"]
    for name, row in sorted(status.items()):
        state = row.get("state", "?")
        mark = {"ok": "·", "burning": "!", "no_data": "?"}.get(state, "?")
        bl, bs = row.get("burn_rate_long"), row.get("burn_rate_short")
        fmt = lambda b: f"{b:.2f}" if b is not None else "-"
        lines.append(f"  [{mark}] {name:<16} {state:<8} "
                     f"burn long/short={fmt(bl)}/{fmt(bs)} "
                     f"(objective {row.get('objective')}, "
                     f"window {row.get('window_s')}s)")
    return "\n".join(lines)


def cmd_status(args) -> int:
    """Head-session info plus — when a cluster is reachable (in-process or via
    --address) — the live telemetry summary: per-path transfer GB/s,
    collective ops/aborts, serve TTFT p50/p99 + queue depths, train MFU.
    --watch re-renders every few seconds with metrics-history sparklines and
    SLO burn state."""
    import ray_tpu

    rc = 0
    try:
        with open(_session_file()) as f:
            info = json.load(f)
        print(json.dumps(info, indent=2))
    except FileNotFoundError:
        print("no head session; run `ray-tpu start`")
        rc = 1
    if getattr(args, "address", None):
        try:
            ray_tpu.init(address=args.address)
        except Exception as e:  # noqa: BLE001 — keep the session-info contract
            print(f"(could not reach {args.address}: {e!r})", file=sys.stderr)
    if ray_tpu.is_initialized():
        from ray_tpu.util import state as rs

        if getattr(args, "watch", False):
            try:
                while True:
                    block = [_render_status(rs.cluster_status()),
                             _render_history(rs.history_series())]
                    slo = _render_slo(rs.slo_status())
                    if slo:
                        block.append(slo)
                    print("\x1b[2J\x1b[H" + "\n".join(block), flush=True)
                    time.sleep(args.interval)
            except KeyboardInterrupt:
                return rc
        print(_render_status(rs.cluster_status()))
        slo = _render_slo(rs.slo_status())
        if slo:
            print(slo)
    else:
        # stderr: standalone `ray-tpu status` must keep stdout pure JSON for
        # scripts that parse the session info
        print("(no live cluster for a load summary: pass --address "
              "ray-tpu://host:port or run inside a driver)", file=sys.stderr)
    # rc reflects the head session (the original `status` contract) — a live
    # in-process cluster adds the load summary but doesn't fake a session
    return rc


def cmd_trace(args) -> int:
    """`ray-tpu trace <trace_id>`: render one request's critical path — the
    cross-process span tree plus wall-time attribution over queue / prefill /
    decode / transfer / other. The trace id comes from the serve ingress's
    `traceparent` response header (or the caller's own traceparent)."""
    import ray_tpu

    if args.address:
        ray_tpu.init(address=args.address)
    elif not ray_tpu.is_initialized():
        print("no cluster: pass --address ray-tpu://host:port (or run inside a driver)")
        return 1
    from ray_tpu.util import state as rs

    doc = rs.request_trace(args.trace_id)
    if args.json:
        print(json.dumps(doc, indent=2, default=str))
        return 0 if doc.get("found") else 1
    if not doc.get("found"):
        print(f"no spans or events for trace {args.trace_id!r} (is tracing "
              "enabled, and did the request finish?)")
        return 1
    total = doc["total_s"]
    print(f"trace {doc['trace_id']}  total={total * 1e3:.1f}ms  "
          f"processes={len(doc['processes'])} ({', '.join(doc['processes'])})")
    print("spans:")
    for s in doc["spans"]:
        bar = "  " * s["depth"]
        print(f"  {bar}{s['name']}  +{s['start_s'] * 1e3:.1f}ms "
              f"{s['dur_s'] * 1e3:.1f}ms  (pid {s['pid']})")
    if doc["events"]:
        print("events:")
        for e in doc["events"]:
            phase = f" [{e['phase']}]" if e.get("phase") else ""
            print(f"  {e['name']}{phase}  +{e['start_s'] * 1e3:.1f}ms "
                  f"{e['dur_s'] * 1e3:.1f}ms  ({e['proc']})")
    print("critical path:")
    for phase, secs in doc["attribution"].items():
        pct = secs / total * 100 if total > 0 else 0.0
        if secs > 0 or phase == "other":
            print(f"  {phase:<9} {secs * 1e3:8.1f}ms  {pct:5.1f}%")
    return 0


def cmd_lint(args) -> int:
    """`ray-tpu lint [paths] [--write-docs]`: graftlint, the project-invariant
    static analyzer (ray_tpu/tools/analysis). Pure AST — no jax, no cluster.
    `--write-docs` regenerates the README knob tables from ray_tpu/knobs.py."""
    from ray_tpu.tools.analysis.runner import main as lint_main

    forwarded = list(args.lint_args)
    if args.write_docs:
        forwarded.append("--write-docs")
    if args.json:
        forwarded.append("--json")
    if args.show_allowed:
        forwarded.append("--show-allowed")
    return lint_main(forwarded)


def cmd_submit(args) -> int:
    mgr = JobManager()
    entry = " ".join([sys.executable, args.script] + args.script_args)
    job_id = mgr.submit_job(entry)
    print(f"submitted {job_id}")
    status = mgr.wait_job(job_id)
    print(mgr.get_job_logs(job_id), end="")
    print(f"job {job_id}: {status}")
    return 0 if status == "SUCCEEDED" else 1


def cmd_serve(args) -> int:
    """serve deploy/status/shutdown (reference serve CLI over ServeDeploySchema).

    Single-host note: the runtime is in-process, so the serving cluster lives in
    THIS process — deploy therefore blocks (apps would vanish on exit otherwise),
    and status/shutdown only see apps deployed by the same process (programmatic
    use: ray_tpu.serve.status()/shutdown() in the driver)."""
    import ray_tpu

    ray_tpu.init()
    from ray_tpu import serve

    if args.serve_cmd == "deploy":
        names = serve.apply_config_file(args.config)
        print(f"deployed: {', '.join(names)}", flush=True)
        if args.no_block:
            print("warning: --no-block exits immediately and tears the apps down "
                  "(in-process runtime)", file=sys.stderr)
            return 0
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            pass
        return 0
    if args.serve_cmd == "status":
        st = serve.status()
        if not st:
            print("no apps in this process (serve runs in the deploying process; "
                  "use ray_tpu.serve.status() in the driver)", file=sys.stderr)
        print(json.dumps(st, indent=2, default=str))
        return 0
    if args.serve_cmd == "shutdown":
        serve.shutdown()
        print("serve shut down (this process's session)")
        return 0
    return 2


def cmd_job(args) -> int:
    mgr = JobManager()
    if args.job_cmd == "submit":
        entry = args.entrypoint
        job_id = mgr.submit_job(entry)
        print(job_id)
        if not args.no_wait:
            status = mgr.wait_job(job_id)
            print(mgr.get_job_logs(job_id), end="")
            return 0 if status == "SUCCEEDED" else 1
        return 0
    if args.job_cmd == "list":
        for info in mgr.list_jobs():
            print(f"{info.job_id}\t{info.status}\t{info.entrypoint}")
        return 0
    if args.job_cmd == "status":
        print(mgr.get_job_status(args.job_id))
        return 0
    if args.job_cmd == "logs":
        print(mgr.get_job_logs(args.job_id), end="")
        return 0
    if args.job_cmd == "stop":
        print("stopped" if mgr.stop_job(args.job_id) else "not running")
        return 0
    return 2


def cmd_list(args) -> int:
    """`ray-tpu list nodes|workers|tasks|actors|objects|placement-groups|config`
    (reference `ray list ...`, python/ray/util/state/state_cli.py). Runs against
    the in-process cluster, or a remote head via --address; `config` prints the
    central flag registry (reference ray_config_def.h) and needs no cluster."""
    import ray_tpu

    if args.resource == "config":
        from ray_tpu.config import CONFIG

        print(CONFIG.describe())
        return 0

    if args.address:
        ray_tpu.init(address=args.address)
    elif not ray_tpu.is_initialized():
        print("no cluster: pass --address ray-tpu://host:port (or run inside a driver)")
        return 1
    from ray_tpu.util import state as rs

    fns = {
        "stacks": rs.get_worker_stacks,
        "nodes": rs.list_nodes,
        "workers": rs.list_workers,
        "tasks": rs.list_tasks,
        "actors": rs.list_actors,
        "objects": rs.list_objects,
        "placement-groups": rs.list_placement_groups,
        "summary": rs.summarize_cluster,
        "logs": rs.list_logs,
    }
    out = fns[args.resource]()
    print(json.dumps(out, indent=2, default=str))
    return 0


def cmd_metrics(args) -> int:
    """`ray-tpu metrics launch-config`: write prometheus.yml + Grafana
    provisioning under the session dir (reference `ray metrics launch-prometheus`
    / dashboard/modules/metrics provisioning)."""
    from ray_tpu.metrics_provision import provision

    root = provision(session_dir=args.session_dir or None)
    print(f"metrics configs written under {root}")
    print(f"  prometheus --config.file={root}/prometheus/prometheus.yml")
    print(f"  grafana-server --config {root}/grafana/grafana.ini")
    return 0


def cmd_profile(args) -> int:
    """`ray-tpu profile --duration 5 -o prof.json`: sampling profile of every
    worker + driver, written as a speedscope document (reference: py-spy via
    the dashboard reporter)."""
    import ray_tpu

    if args.address:
        ray_tpu.init(address=args.address)
    elif not ray_tpu.is_initialized():
        print("no cluster: pass --address ray-tpu://host:port (or run inside a driver)")
        return 1
    from ray_tpu.util import state as rs

    profs = rs.profile_workers(duration_s=args.duration, hz=args.hz)
    doc = rs.profile_to_speedscope(profs)
    with open(args.output, "w") as f:
        json.dump(doc, f)
    n = sum(len(v) for v in profs.values())
    print(f"{len(profs)} processes, {n} unique stacks -> {args.output} "
          f"(open at https://speedscope.app)")
    return 0


def cmd_up(args) -> int:
    """`ray-tpu up cluster.yaml` (reference `ray up`)."""
    import ray_tpu
    from ray_tpu.autoscaler.launcher import ClusterConfig, ClusterLauncher

    config = ClusterConfig.from_yaml(args.config)
    ray_tpu.init()
    launcher = ClusterLauncher(config)
    head = launcher.up(start_autoscaler=not args.no_autoscaler)
    print(f"cluster {config.cluster_name!r} up: head={head.instance_id}, "
          f"{len(launcher.provider.non_terminated_nodes())} node(s)")
    state = {
        "config": args.config,
        "cluster_name": config.cluster_name,
        # instance ids let a later `ray-tpu down` (fresh process) terminate
        # nodes whose provider tracks them only in memory (tpu-pod)
        "instances": [
            {"instance_id": n.instance_id, "node_type": n.node_type}
            for n in launcher.provider.non_terminated_nodes()
        ],
    }
    os.makedirs(default_session_dir(), exist_ok=True)
    with open(os.path.join(default_session_dir(), "cluster.json"), "w") as f:
        json.dump(state, f)
    if args.block:
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            launcher.down()
    return 0


def cmd_down(args) -> int:
    """`ray-tpu down [cluster.yaml]` (reference `ray down`)."""
    from ray_tpu.autoscaler.launcher import ClusterConfig, ClusterLauncher

    path = args.config
    state_file = os.path.join(default_session_dir(), "cluster.json")
    recorded = {}
    if os.path.exists(state_file):
        with open(state_file) as f:
            recorded = json.load(f)
    path = path or recorded.get("config")
    if path is None:
        print("no cluster config given and no recorded cluster")
        return 1
    config = ClusterConfig.from_yaml(path)
    launcher = ClusterLauncher(config)
    # only adopt (and clear) the recorded state if it belongs to THIS cluster —
    # `ray-tpu down other.yaml` must not terminate or forget another cluster's nodes
    same_cluster = recorded.get("cluster_name") == config.cluster_name
    if same_cluster:
        launcher.adopt(recorded.get("instances", []))
    n = launcher.down()
    if same_cluster:
        try:
            os.remove(state_file)
        except OSError:
            pass
    print(f"cluster {config.cluster_name!r} down ({n} node(s) terminated)")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ray-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("up", help="launch a cluster from a YAML config")
    sp.add_argument("config")
    sp.add_argument("--no-autoscaler", action="store_true")
    sp.add_argument("--block", action="store_true")
    sp.set_defaults(fn=cmd_up)

    sp = sub.add_parser("down", help="tear down a launched cluster")
    sp.add_argument("config", nargs="?", default=None)
    sp.set_defaults(fn=cmd_down)

    sp = sub.add_parser("list", help="state API listings (reference `ray list`)")
    sp.add_argument("resource", choices=["nodes", "workers", "tasks", "actors",
                                         "objects", "placement-groups", "summary",
                                         "stacks", "config", "logs"])
    sp.add_argument("--address", default=None,
                    help="connect as a client driver, e.g. ray-tpu://127.0.0.1:10001")
    sp.set_defaults(fn=cmd_list)

    sp = sub.add_parser("start", help="record head session (optionally --block with dashboard), "
                                      "or --address=HOST:PORT to join a head as a node agent")
    sp.add_argument("--address", default=None,
                    help="join an existing head's node server as this host's agent")
    sp.add_argument("--num-cpus", type=float, default=None)
    sp.add_argument("--dashboard-port", type=int, default=None,
                    help="default: CONFIG.dashboard_port (RAY_TPU_DASHBOARD_PORT)")
    sp.add_argument("--node-server-port", type=int, default=None,
                    help="accept node agents on this port (0 = ephemeral; head only)")
    sp.add_argument("--node-server-host", default="127.0.0.1")
    sp.add_argument("--block", action="store_true")
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("stop", help="clear head session")
    sp.set_defaults(fn=cmd_stop)

    sp = sub.add_parser("tls-init", help="mint a self-signed cluster CA + cert "
                        "(then set RAY_TPU_USE_TLS + RAY_TPU_TLS_* and "
                        "distribute the files to every node)")
    sp.add_argument("dir", help="output directory for ca.crt/cluster.crt/cluster.key")
    sp.add_argument("--san", action="append", default=[],
                    help="extra SAN entry (IP or DNS name; repeatable)")
    sp.set_defaults(fn=cmd_tls_init)

    sp = sub.add_parser("metrics", help="metrics plane provisioning")
    sp.add_argument("action", choices=["launch-config"])
    sp.add_argument("--session-dir", default="")
    sp.set_defaults(fn=cmd_metrics)

    sp = sub.add_parser("profile", help="sampling profile -> speedscope json")
    sp.add_argument("--address", default="")
    sp.add_argument("--duration", type=float, default=5.0)
    sp.add_argument("--hz", type=float, default=100.0)
    sp.add_argument("-o", "--output", default="ray_tpu_profile.json")
    sp.set_defaults(fn=cmd_profile)

    sp = sub.add_parser("status", help="show head session + live load summary "
                        "(transfer GB/s, collective ops/aborts, serve TTFT, "
                        "train MFU); --watch adds history sparklines + SLOs")
    sp.add_argument("--address", default=None,
                    help="connect as a client driver for the live summary, "
                         "e.g. ray-tpu://127.0.0.1:10001")
    sp.add_argument("--watch", action="store_true",
                    help="re-render every --interval seconds with "
                         "metrics-history sparklines and SLO burn state")
    sp.add_argument("--interval", type=float, default=3.0)
    sp.set_defaults(fn=cmd_status)

    sp = sub.add_parser("trace", help="render one request's critical path "
                        "(span tree + queue/prefill/decode/transfer/other "
                        "attribution) from its trace id")
    sp.add_argument("trace_id", help="32-hex trace id (from the serve "
                    "ingress's traceparent response header)")
    sp.add_argument("--address", default=None,
                    help="connect as a client driver, e.g. ray-tpu://127.0.0.1:10001")
    sp.add_argument("--json", action="store_true",
                    help="print the raw state.request_trace document")
    sp.set_defaults(fn=cmd_trace)

    sp = sub.add_parser("lint", help="graftlint: AST project-invariant "
                        "analysis (swallowed errors, hot-path host syncs, "
                        "blocking control paths, knob registry, thread "
                        "hygiene, no-print)")
    sp.add_argument("lint_args", nargs="*", metavar="path",
                    help="subdirs/files to lint (default: ray_tpu)")
    sp.add_argument("--write-docs", action="store_true",
                    help="regenerate README knob tables from ray_tpu/knobs.py")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--show-allowed", action="store_true")
    sp.set_defaults(fn=cmd_lint)

    sp = sub.add_parser("submit", help="run a python script as a job")
    sp.add_argument("script")
    sp.add_argument("script_args", nargs="*")
    sp.set_defaults(fn=cmd_submit)

    sp = sub.add_parser("serve", help="serve deploy/status/shutdown")
    ssub = sp.add_subparsers(dest="serve_cmd", required=True)
    s = ssub.add_parser("deploy")
    s.add_argument("config")
    s.add_argument("--no-block", action="store_true")
    ssub.add_parser("status")
    ssub.add_parser("shutdown")
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser("job", help="job management")
    jsub = sp.add_subparsers(dest="job_cmd", required=True)
    j = jsub.add_parser("submit")
    j.add_argument("--no-wait", action="store_true")
    j.add_argument("entrypoint")
    j = jsub.add_parser("list")
    j = jsub.add_parser("status")
    j.add_argument("job_id")
    j = jsub.add_parser("logs")
    j.add_argument("job_id")
    j = jsub.add_parser("stop")
    j.add_argument("job_id")
    sp.set_defaults(fn=cmd_job)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
