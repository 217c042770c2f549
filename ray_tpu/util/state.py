"""State API: list/get cluster entities + timeline export.

Capability parity: reference python/ray/util/state/ (api.py list_tasks/actors/
objects/nodes, state_cli.py `ray list ...`) backed by GcsTaskManager +
state_aggregator.py, and `ray.timeline` (python/ray/_private/state.py:986).
Here the cluster lives in the driver process, so the aggregator reads the
Cluster structures directly; worker metrics arrive via the pipe push
(core/node.py "metrics" message).
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional

from ray_tpu.core import global_state


def _cluster():
    c = global_state.try_cluster()
    if c is None:
        raise RuntimeError("ray_tpu is not initialized")
    return c


# names callable through state_request (client server + worker pipe); populated
# by the decorator so the dispatch gate and the decorated surface stay in lockstep
_REMOTEABLE_FNS: set = set()


def _remoteable(fn):
    """Run on the head when this process is a remote client driver (the state
    aggregator reads Cluster structures, which only exist head-side)."""
    import functools

    _REMOTEABLE_FNS.add(fn.__name__)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if global_state.try_cluster() is None:
            w = global_state.try_worker()
            if w is not None and hasattr(w, "state_request"):
                return w.state_request(fn.__name__, *args, **kwargs)
        return fn(*args, **kwargs)

    return wrapper


def dispatch_state_request(fn_name: str, args=(), kwargs=None):
    """THE gate for remote state calls (client server + coordinator pipe):
    only @_remoteable functions are reachable."""
    if fn_name not in _REMOTEABLE_FNS:
        raise ValueError(f"unknown state function {fn_name!r}")
    import sys

    return getattr(sys.modules[__name__], fn_name)(*args, **(kwargs or {}))


@_remoteable
def gcs_nodes() -> List[Dict[str, Any]]:
    """GCS node-table view backing ray_tpu.nodes() — including for remote
    client drivers (reference: ray.nodes() reading the GCS from any driver)."""
    c = _cluster()
    return [
        {
            "NodeID": info.node_id.hex(),
            "Alive": info.alive,
            "Resources": info.resources,
            "Labels": info.labels,
        }
        for info in c.gcs.nodes(alive_only=False)
    ]


@_remoteable
def list_nodes() -> List[Dict[str, Any]]:
    c = _cluster()
    out = []
    for node in c.nodes():
        out.append({
            "node_id": node.node_id.hex(),
            "alive": node.alive,
            "resources_total": dict(node.ledger.total),
            "resources_available": node.ledger.available(),
            "num_workers": len(node.workers),
        })
    return out


@_remoteable
def list_logs() -> List[Dict[str, Any]]:
    """Remote-worker log rings captured by the head (reference `ray logs` /
    log_monitor.py:105 — agents tail per-worker files to the head)."""
    c = _cluster()
    with c._worker_logs_lock:
        return [{"worker_id": wid, "node_id": ring["node"],
                 "num_lines": len(ring["lines"])}
                for wid, ring in c._worker_logs.items()]


@_remoteable
def get_log(worker_id: str, tail: int = 100) -> List[str]:
    """Last `tail` captured lines of one remote worker ("out|err: line")."""
    c = _cluster()
    if tail <= 0:
        return []
    with c._worker_logs_lock:
        ring = c._worker_logs.get(worker_id)
        lines = list(ring["lines"]) if ring is not None else []
    return [f"{stream}: {line}" for stream, line in lines[-tail:]]


@_remoteable
def list_workers() -> List[Dict[str, Any]]:
    c = _cluster()
    out = []
    with c._lock:
        for node in c._nodes.values():
            for w in node.workers.values():
                out.append({
                    "worker_id": w.worker_id.hex(),
                    "node_id": node.node_id.hex(),
                    "pid": w.process.pid,
                    "state": w.state,
                    "accelerator": w.accel,
                    "num_inflight": len(w.inflight),
                })
    return out


@_remoteable
def list_tasks(filters: Optional[Dict[str, Any]] = None) -> List[Dict[str, Any]]:
    """Pending/running tasks plus recent finished ones (bounded ring)."""
    c = _cluster()
    out = []
    with c._lock:
        for ts in c.tasks.values():
            state = "RUNNING" if ts.dispatched_at else "PENDING"
            out.append({
                "task_id": ts.spec.task_id.hex(),
                "name": ts.spec.name,
                "kind": ts.spec.kind,
                "state": state,
                "submitted_at": ts.submitted_at,
            })
        for ev in c.task_events:
            out.append({
                "task_id": ev["task_id"],
                "name": ev["name"],
                "kind": ev["kind"],
                "state": "FAILED" if ev["error"] else "FINISHED",
                "submitted_at": ev["submitted_at"],
            })
    if filters:
        out = [t for t in out if all(t.get(k) == v for k, v in filters.items())]
    return out


@_remoteable
def list_actors() -> List[Dict[str, Any]]:
    c = _cluster()
    out = []
    with c._lock:
        for st in c.actors.values():
            out.append({
                "actor_id": st.actor_id.hex(),
                "class_name": st.creation_spec.name.replace(".__init__", ""),
                "state": st.state.upper(),
                "name": st.name,
                "namespace": st.namespace,
                "pid": st.worker.process.pid if st.worker else None,
                "node_id": st.worker.node.node_id.hex() if st.worker else None,
                "restarts": st.restarts_used,
            })
    return out


@_remoteable
def list_objects() -> List[Dict[str, Any]]:
    c = _cluster()
    store = c.store
    out = []
    with store._lock:
        for oid, loc in store._locations.items():
            kind = loc[0]
            size = (len(loc[1]) if kind == "inline"
                    else loc[3] if kind == "arena" else loc[2])
            out.append({
                "object_id": oid.hex(),
                "tier": kind,
                "size_bytes": size,
                "refcount": store._refcounts.get(oid, 0),
            })
    return out


@_remoteable
def list_placement_groups() -> List[Dict[str, Any]]:
    c = _cluster()
    out = []
    with c.pg_manager._lock:
        entries = list(c.pg_manager._groups.values())
    for pg, bundles in entries:
        out.append({
            "placement_group_id": pg.id.hex(),
            "ready": pg._ready_event.is_set(),
            "strategy": pg.strategy,
            "name": pg.name,
            "bundles": [dict(b.resources) for b in bundles],
        })
    return out


@_remoteable
def summarize_cluster() -> Dict[str, Any]:
    c = _cluster()
    return {
        "nodes": len(list_nodes()),
        "workers": len(list_workers()),
        "actors": len(list_actors()),
        "pending_tasks": len([t for t in list_tasks() if t["state"] == "PENDING"]),
        "objects": c.store.stats(),
    }


# -------------------------------------------------------------------- metrics

def get_metrics() -> Dict[str, dict]:
    """Aggregated metrics: driver registry + latest worker pushes."""
    from ray_tpu.util import metrics as m

    c = _cluster()
    snaps = [m._registry.snapshot()]
    snaps.extend(c.metrics_by_worker.values())
    return m.merge_snapshots(snaps)


def prometheus_metrics() -> str:
    from ray_tpu.util import metrics as m

    user_metrics = get_metrics()
    text = m.prometheus_text(user_metrics)
    # system series alongside the user registry (reference: ray_nodes /
    # ray_actors / ray_object_store_memory exported by the dashboard agent)
    s = summarize_cluster()
    lines = [text] if text else []
    gauges = {
        "cluster_nodes": s["nodes"],
        "cluster_workers": s["workers"],
        "cluster_actors": s["actors"],
        "cluster_pending_tasks": s["pending_tasks"],
    }
    gauges.update({f"object_store_{k}": v for k, v in s["objects"].items()})
    for name, value in gauges.items():
        if name in user_metrics:
            continue  # a user metric claimed this name; duplicate TYPE lines
                      # would invalidate the whole exposition
        full = f"ray_tpu_{name}"
        lines.append(f"# TYPE {full} gauge")
        lines.append(f"{full} {value}")
    return "\n".join(lines) + "\n"


# -------------------------------------------------------------------- tracing

@_remoteable
def get_trace() -> List[Dict[str, Any]]:
    """All collected spans: worker-pushed + driver-local (util/tracing.py).

    Driver-local spans are folded into the cluster's persistent ring on read so
    repeated calls keep returning them."""
    from ray_tpu.util import tracing

    c = _cluster()
    local = tracing.drain_local_spans()
    with c._lock:
        c.trace_spans.extend(local)
        return list(c.trace_spans)


# ------------------------------------------------------------------- telemetry

@_remoteable
def head_clock_ns() -> int:
    """The head's wall clock, for the NTP-style offset handshake worker
    telemetry flushers run once per process (util/telemetry.clock_offset_ns):
    merged timeline timestamps are comparable because every worker batch is
    shifted onto THIS clock."""
    import time as _time

    return _time.time_ns()


@_remoteable
def get_telemetry() -> List[Dict[str, Any]]:
    """All collected hot-path telemetry events (util/telemetry.py), oldest
    first: worker-pushed batches (already clock-aligned and proc-tagged by the
    head) + the in-process driver's ring, folded in on read like get_trace."""
    from ray_tpu.util import telemetry

    c = _cluster()
    local = telemetry.align_batch(
        {"clock_offset_ns": 0, "events": telemetry.drain()}, "driver")
    with c._lock:
        c.telemetry_events.extend(local)
        return list(c.telemetry_events)


@_remoteable
def telemetry_timeline_events() -> List[Dict[str, Any]]:
    """Telemetry events rendered as chrome-trace events (no file IO — remotely
    callable). Spans become complete ('X') events, instants become 'i'; the
    `pid` lane is the producing process, the `tid` lane its thread."""
    events = []
    for ev in get_telemetry():
        out = {
            "cat": ev.get("cat", "app"),
            "name": ev.get("name", "?"),
            "pid": ev.get("proc", "driver"),
            "tid": ev.get("tid", "main"),
            "ts": ev["ts_ns"] / 1e3,  # chrome-trace microseconds
            "args": ev.get("args", {}),
        }
        if ev.get("dur_ns") is None:
            out["ph"] = "i"
            out["s"] = "p"  # instant scope: process
        else:
            out["ph"] = "X"
            out["dur"] = ev["dur_ns"] / 1e3
        events.append(out)
    return events


def telemetry_timeline(filename: Optional[str] = None) -> List[Dict[str, Any]]:
    """Cross-worker chrome-trace timeline: hot-path telemetry spans (transfers,
    collective phases, serve/llm request lifecycles, train steps) merged with
    the task timeline, clocks aligned via the head handshake. Load the JSON in
    chrome://tracing / Perfetto. The file, if requested, is written by THIS
    process (a remote client's filename never touches the head's filesystem)."""
    events = telemetry_timeline_events() + timeline_events()
    if filename:
        with open(filename, "w") as f:
            json.dump(events, f)
    return events


@_remoteable
def cluster_status() -> Dict[str, Any]:
    """Live load summary for `ray-tpu status` / the dashboard: per-path
    transfer GB/s, collective op/abort counts, serve TTFT p50/p99 + queue
    depths, llm engine gauges, train MFU — all derived from the merged metric
    registry, so it reflects every process that pushed within the report
    interval."""
    from ray_tpu.util import metrics as m

    merged = get_metrics()

    def counter_by_tag(name: str, tag: str) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for key, v in merged.get(name, {}).get("values", {}).items():
            label = dict(key).get(tag, "")
            out[label] = out.get(label, 0.0) + v
        return out

    def counter_total(name: str) -> float:
        return sum(merged.get(name, {}).get("values", {}).values())

    def gauges(name: str) -> Dict[str, float]:
        return {",".join(f"{k}={v}" for k, v in key) or "_": val
                for key, val in merged.get(name, {}).get("values", {}).items()}

    status: Dict[str, Any] = {"cluster": summarize_cluster()}

    # -- transfers: counters accumulate (bytes, busy-seconds) per path
    bytes_by_path = counter_by_tag("transfer_bytes_total", "path")
    secs_by_path = counter_by_tag("transfer_seconds_total", "path")
    pulls_by_path = counter_by_tag("transfer_pulls_total", "path")
    transfer = {}
    for path in sorted(set(bytes_by_path) | set(pulls_by_path)):
        b, s = bytes_by_path.get(path, 0.0), secs_by_path.get(path, 0.0)
        transfer[path] = {
            "pulls": int(pulls_by_path.get(path, 0)),
            "bytes": int(b),
            "gbps": round(b / s / 1e9, 3) if s > 0 else None,
        }
    status["transfer"] = transfer

    # -- collectives
    status["collective"] = {
        "ops": {k: int(v) for k, v in
                counter_by_tag("collective_ops_total", "op").items()},
        "aborts": int(counter_total("collective_aborts_total")),
        "aborts_observed": int(counter_total("collective_aborts_observed_total")),
        "epoch_rollovers": int(counter_total("collective_epoch_rollovers_total")),
    }

    # -- serve (queue depth: each process publishes its own proc-tagged gauge;
    # the cluster-wide depth is their SUM per deployment)
    depth_by_dep: Dict[str, float] = {}
    for key, v in merged.get("serve_queue_depth", {}).get("values", {}).items():
        tags = dict(key)
        label = f"{tags.get('app', '?')}/{tags.get('deployment', '?')}"
        depth_by_dep[label] = depth_by_dep.get(label, 0.0) + v
    ttft = merged.get("serve_ttft_seconds")
    status["serve"] = {
        "ttft_p50_s": m.histogram_quantile(ttft, 0.5) if ttft else None,
        "ttft_p99_s": m.histogram_quantile(ttft, 0.99) if ttft else None,
        "queue_depth": depth_by_dep,
        "requests": int(sum(v["count"] for v in merged.get(
            "serve_request_seconds", {}).get("values", {}).values())),
    }
    # -- serve autoscale loop (head-side): live targets + decision counters
    decisions_by_reason = {k: int(v) for k, v in counter_by_tag(
        "serve_autoscale_decisions_total", "reason").items()}
    autoscale: Dict[str, Any] = {}
    if global_state.try_cluster() is not None:
        from ray_tpu.serve.autoscaler import get_serve_autoscaler

        loop = get_serve_autoscaler()
        if loop is not None:
            st = loop.status()
            autoscale = {
                "alive": st["alive"],
                "ticks": st["ticks"],
                "targets": {k: {kk: v.get(kk) for kk in
                                ("target", "running", "queue_depth",
                                 "burning", "reason")}
                            for k, v in st["deployments"].items()},
                "last_decision": (st["decisions"][-1]
                                  if st["decisions"] else None),
            }
    if autoscale or decisions_by_reason:
        autoscale["decisions_by_reason"] = decisions_by_reason
        status["serve"]["autoscale"] = autoscale

    # -- llm engines
    llm_ttft = merged.get("llm_ttft_seconds")
    tok_rate = merged.get("llm_tokens_per_s")
    burst_rate = merged.get("llm_burst_tokens_per_s")
    kv_handoff = merged.get("llm_kv_handoff_gbps")
    status["llm"] = {
        "ttft_p50_s": m.histogram_quantile(llm_ttft, 0.5) if llm_ttft else None,
        "ttft_p99_s": m.histogram_quantile(llm_ttft, 0.99) if llm_ttft else None,
        "tokens_per_s_p50": m.histogram_quantile(tok_rate, 0.5) if tok_rate else None,
        # per-burst engine throughput (one observation per fused K-step burst
        # — truthful under fused decode, where per-host-step numbers would
        # overcount) + total tokens for windowed rates via metrics_history
        "burst_tokens_per_s_p50": (m.histogram_quantile(burst_rate, 0.5)
                                   if burst_rate else None),
        "generated_tokens": int(counter_total("llm_generated_tokens_total")),
        "fused_steps": gauges("llm_decode_fused_steps"),
        "host_sync_fraction": gauges("llm_decode_host_sync_fraction"),
        "pending": gauges("llm_num_pending"),
        "active": gauges("llm_num_active"),
        "prefix_cache_hits": int(counter_total("llm_prefix_cache_hits_total")),
        "prefix_cache_misses": int(counter_total("llm_prefix_cache_misses_total")),
        "prefix_cache_skipped": int(counter_total("llm_num_prefix_skipped")),
        # P/D disaggregation: per-handoff KV transfer rate (paged pulls and
        # monolithic fetches both observe; tagged by mode in the registry)
        "kv_handoff_gbps_p50": (m.histogram_quantile(kv_handoff, 0.5)
                                if kv_handoff else None),
        "kv_handoff_gbps_p99": (m.histogram_quantile(kv_handoff, 0.99)
                                if kv_handoff else None),
    }

    # -- control plane: the observability pipeline observing itself (PR 17).
    # Scrape/decision latency percentiles, inlet pressure, node-aggregation
    # coverage, cardinality-guard drops — the numbers that say whether the
    # head itself is the bottleneck at fleet scale.
    scrape = merged.get("control_scrape_seconds")
    decision = merged.get("control_decision_seconds")
    cp: Dict[str, Any] = {
        "scrape_p50_s": m.histogram_quantile(scrape, 0.5) if scrape else None,
        "scrape_p99_s": m.histogram_quantile(scrape, 0.99) if scrape else None,
        "decision_p99_s": {
            loop: m.histogram_quantile(decision, 0.99, where={"loop": loop})
            for loop in sorted({dict(key).get("loop", "?")
                                for key in (decision or {}).get("values", {})})
        } if decision else {},
        "inlet_frames": gauges("control_inlet_frames").get("_"),
        "backpressure_level": gauges("control_backpressure_level").get("_"),
        "backpressure_transitions": int(counter_total(
            "control_backpressure_transitions_total")),
        "inlet_shed": int(counter_total("control_inlet_shed_total")),
        "dropped_series": {k: int(v) for k, v in counter_by_tag(
            m.DROPPED_SERIES_METRIC, "metric").items()},
    }
    c = global_state.try_cluster()
    if c is not None:
        cp["nodes_aggregated"] = len(getattr(c, "metrics_by_node", {}) or {})
        cp["workers_direct"] = len(getattr(c, "metrics_by_worker", {}) or {})
    status["control_plane"] = cp

    # -- train
    steps = int(counter_total("train_steps_total"))
    interval = merged.get("train_step_interval_seconds")
    status["train"] = {
        "mfu": gauges("train_mfu"),
        "tokens_per_s": gauges("train_tokens_per_s"),
        # the train loop's own clock (train/session.py), over the processes that run
        # steps (they alone export these, their tasks and collector pauses too): steps, where the loop's thread spent a step (a synced loop waits for its
        # loss in `user`), the tail of entry-to-entry step intervals, programs
        # compiled, the collector's pauses, the way from fit() to the loop by phase
        "steps": steps,
        "loop_ms_per_step": {
            lap: round(counter_total(f"train_loop_{lap}_ns_total") / steps / 1e6, 3)
            for lap in ("dispatch", "report", "data", "user")} if steps else {},
        "step_interval_p50_s": m.histogram_quantile(interval, 0.5) if interval else None,
        "step_interval_p99_s": m.histogram_quantile(interval, 0.99) if interval else None,
        # steps of more than 4 x the median interval: each left a line in its worker's log
        "slow_steps": int(counter_total("train_slow_steps_total")),
        "compiles": int(counter_total("compiles_total")),
        "compile_s": round(counter_total("compile_ns_total") / 1e9, 3),
        "gc_pause_ms": round(counter_total("gc_pause_ns_total") / 1e6, 3),
        "gc_collections": int(counter_total("gc_collections_total")),
        "worker_tasks": int(counter_total("worker_tasks_total")),
        "setup_seconds": {dict(key).get("phase", "?"): round(v, 3) for key, v in
                          merged.get("train_setup_seconds", {}).get("values", {}).items()},
        "group_failures": int(counter_total("train_group_failures_total")),
        "step_phases_s": {
            dict(key).get("phase", "?"): round(v["sum"] / v["count"], 6)
            for key, v in merged.get("train_step_phase_seconds",
                                     {}).get("values", {}).items()
            if v["count"]
        },
        # grad-sync phase breakdown (train/grad_sync.py telemetry mode):
        # mean seconds per phase — forward_backward / bucket_wait / optimizer
        "grad_sync_phases_s": {
            dict(key).get("phase", "?"): round(v["sum"] / v["count"], 6)
            for key, v in merged.get("train_grad_sync_seconds",
                                     {}).get("values", {}).items()
            if v["count"]
        },
        # MPMD pipeline idle fraction per stage (+ mean), published from the
        # merged train.pipeline_stage span timeline (train/mpmd_pipeline.py)
        "pipeline_bubble_fraction": {
            dict(key).get("stage", "?"): round(v, 4)
            for key, v in merged.get("train_pipeline_bubble_fraction",
                                     {}).get("values", {}).items()
        },
    }

    # -- rl: decoupled rollout/learn plane (rllib/rollout_plane.py). Block
    # lifecycle counters, staleness distribution at take time, queue depth —
    # the numbers that say whether the learner or the env pool is the
    # bottleneck and whether stale data is being trained on or dropped.
    block_lag = merged.get("rl_block_lag")
    status["rl"] = {
        "env_steps": int(counter_total("rl_env_steps_total")),
        "learner_updates": int(counter_total("rl_learner_updates_total")),
        "weight_broadcasts": int(counter_total("rl_weight_broadcasts_total")),
        "blocks": {k: int(v) for k, v in
                   counter_by_tag("rl_blocks_total", "event").items()},
        "block_pulls": {k: int(v) for k, v in
                        counter_by_tag("rl_block_pulls_total", "path").items()},
        "queue_depth": gauges("rl_queue_depth").get("_"),
        "block_lag_p50": (m.histogram_quantile(block_lag, 0.5)
                          if block_lag else None),
        "block_lag_p99": (m.histogram_quantile(block_lag, 0.99)
                          if block_lag else None),
    }
    return status


# ------------------------------------------------------------ metrics history

@_remoteable
def metrics_history(window_s: float = 60.0) -> Dict[str, Any]:
    """The head's retained metrics-history frames plus the windowed signals
    derived from them (util/metrics_history.py). Each frame is one merged
    cross-worker snapshot sampled by the background scraper
    (RAY_TPU_METRICS_SCRAPE_INTERVAL_S); `windowed` carries the
    bucket-differenced quantiles/rates over the last `window_s` seconds —
    the recent regime, not the lifetime blur lifetime counters give."""
    from ray_tpu.config import CONFIG

    c = _cluster()
    h = c.metrics_history
    windowed = {
        "serve_ttft_p50_s": h.quantile("serve_ttft_seconds", 0.5, window_s),
        "serve_ttft_p99_s": h.quantile("serve_ttft_seconds", 0.99, window_s),
        "serve_requests_per_s": h.rate("serve_request_seconds", window_s),
        "llm_ttft_p99_s": h.quantile("llm_ttft_seconds", 0.99, window_s),
        "transfer_bytes_per_s": h.rate("transfer_bytes_total", window_s),
        "collective_ops_per_s": h.rate("collective_ops_total", window_s),
    }
    return {
        "frames": h.frames(),
        "scrape_interval_s": CONFIG.metrics_scrape_interval_s,
        "window_s": window_s,
        "windowed": windowed,
    }


@_remoteable
def serve_latency_hint(window_s: float = 60.0) -> Dict[str, Optional[float]]:
    """Tiny windowed latency summary for admission control: the p50/p99 of
    RECENT serve request/TTFT latency from the metrics-history ring, without
    shipping the full frame dump metrics_history() returns. The proxies
    derive Retry-After from this (one recent service time ~= how long until
    a replica slot frees), cached caller-side between sheds."""
    c = _cluster()
    h = c.metrics_history
    return {
        "serve_request_p50_s": h.quantile("serve_request_seconds", 0.5, window_s),
        "serve_request_p99_s": h.quantile("serve_request_seconds", 0.99, window_s),
        "serve_ttft_p50_s": h.quantile("serve_ttft_seconds", 0.5, window_s),
        "serve_ttft_p99_s": h.quantile("serve_ttft_seconds", 0.99, window_s),
    }


@_remoteable
def history_series(window_s: float = 300.0) -> Dict[str, Any]:
    """JSON-safe per-frame time series for dashboards/sparklines
    (`/api/history`, `ray-tpu status --watch`): one timestamp list plus one
    value list per signal (None where a frame has no data). Derived signals
    (rates, windowed quantiles) are computed FRAME-over-frame so the series
    shows load shifts, not lifetime averages. Payloads are BOUNDED: more
    in-window frames than RAY_TPU_CONTROL_HISTORY_MAX_POINTS are stride-
    downsampled (newest kept) and more series than
    RAY_TPU_CONTROL_HISTORY_MAX_SERIES are dropped, with `truncated` set —
    a --watch refresh against a 1k-replica fleet must never ship megabytes."""
    from ray_tpu.config import CONFIG
    from ray_tpu.util import metrics as m

    c = _cluster()
    h = c.metrics_history
    all_frames = h.frames()
    truncated = False
    # frame-over-frame values need each frame's PREDECESSOR, so include ONE
    # frame before the window as a differencing seed (its own output is
    # discarded) — without it the first in-window point would difference
    # against nothing and show a lifetime value (a phantom spike at the
    # window edge); deriving over the ENTIRE ring instead would do
    # history_size/window times the needed bucket-difference work per hit
    if all_frames:
        newest = all_frames[-1]["ts"]
        keep = [i for i, f in enumerate(all_frames)
                if f["ts"] >= newest - window_s]
    else:
        keep = []
    max_points = CONFIG.control_history_max_points
    if max_points > 0 and len(keep) > max_points:
        # stride-downsample anchored at the NEWEST frame: the most recent
        # point is always retained, older points thin out evenly
        stride = -(-len(keep) // max_points)  # ceil
        keep = keep[::-1][::stride][::-1]
        truncated = True
    start = max(0, keep[0] - 1) if keep else 0
    frames = all_frames[start:]
    keep = [i - start for i in keep]
    ts = [round(frames[i]["ts"], 3) for i in keep]

    def sliced(series):
        return [series[i] for i in keep]

    def counter_total(frame, name):
        mm = frame["metrics"].get(name)
        if mm is None:
            return None
        if mm["type"] == "histogram":
            return float(sum(v["count"] for v in mm["values"].values()))
        return float(sum(mm["values"].values()))

    def gauge_sum(frame, name):
        mm = frame["metrics"].get(name)
        if mm is None:
            return None
        return float(sum(mm["values"].values()))

    def per_s(name):
        out, prev = [], None
        for f in frames:
            cur = counter_total(f, name)
            if cur is None or prev is None or f["ts"] <= prev[0]:
                out.append(None)
            else:
                out.append(round(max(0.0, cur - prev[1]) / (f["ts"] - prev[0]), 3))
            if cur is not None:
                prev = (f["ts"], cur)
        return out

    def frame_quantile(name, q):
        """q-quantile of each frame's NEW observations (bucket difference
        against the previous frame that carried the histogram — ONE shared
        implementation: metrics_history.diff_histogram). The very first
        retained frame has no predecessor -> None, never a lifetime value; a
        metric first appearing later differences against the implicit zero
        of "didn't exist yet", which is exact."""
        from ray_tpu.util.metrics_history import diff_histogram

        out, prev = [], None
        for i, f in enumerate(frames):
            mm = f["metrics"].get(name)
            if mm is None or mm.get("type") != "histogram":
                out.append(None)
                continue
            if prev is None and i == 0:
                # the ring may have evicted history: differencing the first
                # retained frame would show a lifetime value
                out.append(None)
                prev = mm
                continue
            q_v = m.histogram_quantile(diff_histogram(mm, prev), q)
            out.append(round(q_v, 6) if q_v is not None else None)
            prev = mm
        return out

    series = {
        "serve_ttft_p99_s": sliced(frame_quantile("serve_ttft_seconds", 0.99)),
        "serve_requests_per_s": sliced(per_s("serve_request_seconds")),
        "llm_ttft_p99_s": sliced(frame_quantile("llm_ttft_seconds", 0.99)),
        "transfer_bytes_per_s": sliced(per_s("transfer_bytes_total")),
        "collective_ops_per_s": sliced(per_s("collective_ops_total")),
        "serve_queue_depth": sliced([gauge_sum(f, "serve_queue_depth")
                                     for f in frames]),
    }
    max_series = CONFIG.control_history_max_series
    if max_series > 0 and len(series) > max_series:
        series = dict(list(series.items())[:max_series])
        truncated = True
    return {"ts": ts, "series": series, "truncated": truncated}


@_remoteable
def slo_status() -> Dict[str, Dict[str, Any]]:
    """Current state of every registered SLO (util/slo.py): burn rates over
    the long/short windows, ok|burning|no_data, the windowed observed value.
    The autoscaler/router closed loop polls this (or subscribes head-side via
    slo.subscribe_slo)."""
    return _cluster().slo_engine.status()


@_remoteable
def serve_autoscaler_status() -> Dict[str, Any]:
    """The serve autoscaling loop's introspection surface: whether the loop
    is alive, the last-seen per-deployment view (target/running/queue-depth/
    burning + the latest decision and reason), and the bounded decision
    journal — `ray-tpu status` and the chaos bench read this to explain WHY
    the fleet resized."""
    _cluster()  # head-side state only
    from ray_tpu.serve.autoscaler import get_serve_autoscaler

    loop = get_serve_autoscaler()
    if loop is None:
        return {"alive": False, "ticks": 0, "deployments": {}, "decisions": []}
    return loop.status()


# -------------------------------------------------------- request-scoped trace

_PHASES = ("queue", "prefill", "decode", "transfer")


def _phase_of(name: str, cat: str = "") -> Optional[str]:
    """Critical-path bucket for a span/event name. Container spans (serve
    ingress, task execution) stay None — they ARE the wall clock being
    attributed, not a phase of it."""
    if name == "llm.queue":
        return "queue"
    if name == "llm.prefill":
        return "prefill"
    if name == "llm.decode":
        return "decode"
    if name.startswith("transfer.") or cat == "transfer":
        return "transfer"
    return None


def _attribute(intervals: List, t0: float, t1: float) -> Dict[str, float]:
    """Sweep [t0, t1]: each elementary segment is charged to the
    highest-priority phase covering it (queue > prefill > decode > transfer),
    remainder to "other" — phases stay disjoint, so the attribution sums to
    the window EXACTLY even when phase spans overlap."""
    marks = {t0, t1}
    clipped = []
    for s, e, phase in intervals:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            clipped.append((s, e, phase))
            marks.add(s)
            marks.add(e)
    pts = sorted(marks)
    out = {p: 0.0 for p in _PHASES}
    out["other"] = 0.0
    prio = {p: i for i, p in enumerate(_PHASES)}
    for a, b in zip(pts, pts[1:]):
        mid = (a + b) / 2
        covering = [phase for s, e, phase in clipped if s <= mid < e]
        phase = min(covering, key=lambda p: prio[p]) if covering else "other"
        out[phase] += b - a
    return {k: round(v, 6) for k, v in out.items()}


@_remoteable
def request_trace(trace_id: str) -> Dict[str, Any]:
    """Reconstruct one request's critical path: every tracing span with this
    trace_id (proxy ingress -> handle -> replica -> engine, across
    processes), every telemetry event tagged with it (data-plane pulls,
    engine queue/prefill/decode phases), the span tree, and a wall-time
    attribution over queue/prefill/decode/transfer/other that sums to the
    root span's duration. `ray-tpu trace <trace_id>` renders this."""
    spans = [s for s in get_trace() if s.get("trace_id") == trace_id]
    events = [e for e in get_telemetry()
              if (e.get("args") or {}).get("trace_id") == trace_id]
    if not spans and not events:
        return {"trace_id": trace_id, "found": False, "spans": [],
                "events": [], "processes": [], "attribution": {},
                "total_s": 0.0}

    by_id = {s["span_id"]: s for s in spans}
    children: Dict[str, List[dict]] = {}
    roots = []
    for s in spans:
        parent = s.get("parent_span_id", "")
        if parent and parent in by_id:
            children.setdefault(parent, []).append(s)
        else:
            roots.append(s)
    for kids in children.values():
        kids.sort(key=lambda s: s["start_time"])
    roots.sort(key=lambda s: s["start_time"])

    # the attribution window: the earliest root span (the ingress) — or the
    # envelope of everything collected when only telemetry events matched
    ev_bounds = [(e["ts_ns"] / 1e9, (e["ts_ns"] + (e["dur_ns"] or 0)) / 1e9)
                 for e in events]
    if roots:
        t0 = roots[0]["start_time"]
        t1 = max(r.get("end_time", t0) for r in roots)
    else:
        t0 = min(b[0] for b in ev_bounds)
        t1 = max(b[1] for b in ev_bounds)

    intervals = []
    for e in events:
        phase = _phase_of(e.get("name", ""), e.get("cat", ""))
        if phase and e.get("dur_ns"):
            s = e["ts_ns"] / 1e9
            intervals.append((s, s + e["dur_ns"] / 1e9, phase))
    for s in spans:
        phase = _phase_of(s.get("name", ""))
        if phase and "end_time" in s:
            intervals.append((s["start_time"], s["end_time"], phase))

    tree = []

    def walk(span, depth):
        tree.append({
            "name": span["name"], "span_id": span["span_id"],
            "parent_span_id": span.get("parent_span_id", ""),
            "depth": depth, "pid": span.get("pid"),
            "start_s": round(span["start_time"] - t0, 6),
            "dur_s": round(span.get("end_time", span["start_time"])
                           - span["start_time"], 6),
            "attributes": span.get("attributes", {}),
        })
        for kid in children.get(span["span_id"], ()):
            walk(kid, depth + 1)

    for r in roots:
        walk(r, 0)

    procs = sorted({f"pid-{s['pid']}" for s in spans if s.get("pid")}
                   | {e["proc"] for e in events if e.get("proc")})
    return {
        "trace_id": trace_id,
        "found": True,
        "total_s": round(t1 - t0, 6),
        "attribution": _attribute(intervals, t0, t1),
        "spans": tree,
        "events": [{"name": e.get("name"), "cat": e.get("cat"),
                    "proc": e.get("proc"), "start_s": round(e["ts_ns"] / 1e9 - t0, 6),
                    "dur_s": round((e.get("dur_ns") or 0) / 1e9, 6),
                    "phase": _phase_of(e.get("name", ""), e.get("cat", ""))}
                   for e in sorted(events, key=lambda e: e["ts_ns"])],
        "processes": procs,
    }


# -------------------------------------------------------------------- timeline

@_remoteable
def timeline_events() -> List[Dict[str, Any]]:
    """Chrome-trace events for finished tasks (no file IO — remotely callable)."""
    c = _cluster()
    events = []
    with c._lock:
        evs = list(c.task_events)
    for ev in evs:
        if ev["dispatched_at"] is None:
            continue
        events.append({
            "cat": "task",
            "ph": "X",  # complete event
            "name": ev["name"],
            "pid": ev["node_id"][:8],
            "tid": ev["worker_id"][:8],
            "ts": ev["dispatched_at"] * 1e6,
            "dur": (ev["finished_at"] - ev["dispatched_at"]) * 1e6,
            "args": {"task_id": ev["task_id"], "error": ev["error"]},
        })
    return events


def timeline(filename: Optional[str] = None) -> List[Dict[str, Any]]:
    """Chrome-trace export (reference ray.timeline, python/ray/_private/
    state.py:986). The file, if requested, is written by THIS process — a remote
    client's filename never touches the head's filesystem."""
    events = timeline_events()
    if filename:
        with open(filename, "w") as f:
            json.dump(events, f)
    return events


@_remoteable
def get_worker_stacks(timeout_s: float = 5.0) -> Dict[str, str]:
    """Per-process thread stack dumps (reference: py-spy via the dashboard
    reporter module, python/ray/dashboard/modules/reporter/) — dependency-free:
    workers introspect sys._current_frames() on their recv thread."""
    return _cluster().dump_worker_stacks(timeout_s)


@_remoteable
def profile_workers(duration_s: float = 2.0, hz: float = 100.0) -> Dict[str, Dict[str, int]]:
    """Sampling profile of every live worker + driver: collapsed stacks
    ("thread;frame;frame" -> sample count, flamegraph.pl format). The
    `py-spy record` analogue of the reference's reporter profiling endpoints."""
    return _cluster().profile_workers(duration_s=duration_s, hz=hz)


def profile_to_speedscope(profiles: Dict[str, Dict[str, int]]) -> Dict[str, Any]:
    """Render profile_workers() output as a speedscope-importable document
    (one 'sampled' profile per process; https://speedscope.app file format)."""
    frames: List[Dict[str, str]] = []
    index: Dict[str, int] = {}

    def fid(name: str) -> int:
        if name not in index:
            index[name] = len(frames)
            frames.append({"name": name})
        return index[name]

    profs = []
    for proc, counts in sorted(profiles.items()):
        samples, weights = [], []
        for collapsed, n in counts.items():
            stack = [fid(part) for part in collapsed.split(";")]
            samples.append(stack)
            weights.append(n)
        profs.append({
            "type": "sampled", "name": proc, "unit": "none",
            "startValue": 0, "endValue": sum(weights) or 1,
            "samples": samples, "weights": weights,
        })
    return {
        "$schema": "https://www.speedscope.app/file-format-schema.json",
        "shared": {"frames": frames},
        "profiles": profs,
    }
