"""Application metrics: Counter / Gauge / Histogram.

Capability parity: reference python/ray/util/metrics.py (Counter :164, Histogram
:217, Gauge :295) + the dashboard-agent scrape path (C++ DEFINE_stats ->
OpenCensus -> Prometheus; SURVEY.md §5). Here each process keeps a local registry;
worker processes push deltas to the node coordinator over their control pipe every
REPORT_INTERVAL_S (the reference's agent scrape, inverted), and the aggregated view
is served by the state API / dashboard exporter (util/state.py, dashboard.py).
"""
from __future__ import annotations

import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

def _report_interval() -> float:
    """Read at use: env changes apply live (config.py contract)."""
    try:
        from ray_tpu.config import CONFIG

        return CONFIG.metrics_report_interval_s
    # graftlint: allow[swallowed-exception] degrades to the coded fallback (return 2.0) by design
    except Exception:
        return 2.0

DEFAULT_HISTOGRAM_BOUNDARIES = [
    0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 50.0, 100.0,
]

DROPPED_SERIES_METRIC = "metrics_dropped_series_total"


def _max_series() -> int:
    """Bounded-cardinality cap: max distinct label sets per metric (read at
    use so env changes apply live). <= 0 disables the guard."""
    try:
        from ray_tpu.config import CONFIG

        return CONFIG.control_max_series
    # graftlint: allow[swallowed-exception] degrades to the coded fallback (return 1024) by design
    except Exception:
        return 1024


_dropped_lock = threading.Lock()
_dropped_series: Dict[str, int] = defaultdict(int)


def _record_dropped(metric_name: str, n: int = 1) -> None:
    with _dropped_lock:
        _dropped_series[metric_name] += n


def dropped_series_snapshot() -> Optional[dict]:
    """Synthetic counter export for the cardinality guard. Kept out of the
    Metric registry on purpose: the guard must never be subject to itself,
    and its own cardinality is bounded by the number of metric NAMES."""
    with _dropped_lock:
        if not _dropped_series:
            return None
        return {
            "name": DROPPED_SERIES_METRIC, "type": "counter",
            "description": "label sets dropped by the bounded-cardinality "
                           "guard (RAY_TPU_CONTROL_MAX_SERIES), by metric",
            "values": {(("metric", k),): float(v)
                       for k, v in _dropped_series.items()},
        }


class _Registry:
    """Per-process metric registry; worker side pushes deltas to the coordinator."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, "Metric"] = {}
        self._push_thread: Optional[threading.Thread] = None

    def register(self, m: "Metric") -> None:
        with self._lock:
            existing = self._metrics.get(m.name)
            if existing is not None and existing.TYPE != m.TYPE:
                raise ValueError(f"metric {m.name!r} already registered as {existing.TYPE}")
            self._metrics[m.name] = m
        self._ensure_push_thread()

    def snapshot(self) -> List[dict]:
        with self._lock:
            out = [m._export() for m in self._metrics.values()]
        dropped = dropped_series_snapshot()
        if dropped is not None:
            out.append(dropped)
        return out

    def _ensure_push_thread(self) -> None:
        """Workers and remote client drivers push snapshots to the head; the
        process HOLDING the cluster (in-process driver/head) must not — its
        registry is read directly by the state API, and a self-push would
        land a periodically-frozen copy in metrics_by_worker["driver"] that
        the merge then counts AGAIN (doubling driver counters) and, for
        gauges, writes over the live value with one up to a report interval
        stale (same keying rule as telemetry._ensure_flush_thread)."""
        if self._push_thread is not None:
            return
        from ray_tpu.core import global_state

        if global_state.try_cluster() is not None:
            return
        w = global_state.try_worker()
        if w is None or not hasattr(w, "push_metrics"):
            return

        def loop():
            from ray_tpu.util import telemetry

            while True:
                time.sleep(_report_interval())
                try:
                    with telemetry.span("worker.push_metrics", "worker"):
                        snap = self.snapshot()
                        if snap:
                            w.push_metrics(snap)
                # graftlint: allow[swallowed-exception] degrades to the coded fallback (return) by design
                except Exception:
                    return  # pipe closed: worker exiting

        self._push_thread = threading.Thread(target=loop, daemon=True, name="metrics-push")
        self._push_thread.start()


_registry = _Registry()


def _tag_key(tags: Optional[Dict[str, str]]) -> Tuple:
    return tuple(sorted((tags or {}).items()))


class Metric:
    TYPE = "base"

    def __init__(self, name: str, description: str = "",
                 tag_keys: Optional[Sequence[str]] = None):
        if not name:
            raise ValueError("metric name is required")
        self.name = name
        self.description = description
        self.tag_keys = tuple(tag_keys or ())
        self._default_tags: Dict[str, str] = {}
        self._lock = threading.Lock()
        _registry.register(self)

    def set_default_tags(self, tags: Dict[str, str]):
        self._default_tags = dict(tags)
        return self

    def _merged(self, tags: Optional[Dict[str, str]]) -> Dict[str, str]:
        out = dict(self._default_tags)
        if tags:
            out.update(tags)
        return out

    def _admit(self, key: Tuple, existing: Dict) -> bool:
        """Cardinality guard, called under self._lock: a key already present
        always updates; a NEW label set past the cap is dropped (and counted)
        so an exploding tag value can never grow memory unboundedly."""
        if key in existing:
            return True
        cap = _max_series()
        if cap <= 0 or len(existing) < cap:
            return True
        _record_dropped(self.name)
        return False

    def _export(self) -> dict:
        raise NotImplementedError


class Counter(Metric):
    """Monotonic counter (reference metrics.py:164)."""

    TYPE = "counter"

    def __init__(self, name, description="", tag_keys=None):
        self._values: Dict[Tuple, float] = defaultdict(float)
        super().__init__(name, description, tag_keys)

    def inc(self, value: float = 1.0, tags: Optional[Dict[str, str]] = None):
        if value < 0:
            raise ValueError("Counter.inc() value must be >= 0")
        key = _tag_key(self._merged(tags))
        with self._lock:
            if self._admit(key, self._values):
                self._values[key] += value

    def _export(self) -> dict:
        with self._lock:
            return {"name": self.name, "type": self.TYPE, "description": self.description,
                    "values": {k: v for k, v in self._values.items()}}


class CounterView(Counter):
    """A counter whose value lives outside the registry: hot paths add to plain
    integers (a loop's laps, a step's dispatch) and `read()` is asked for the
    total only when the registry exports, so that carrying a count to the head
    costs the hot path nothing."""

    def __init__(self, name, read, description=""):
        self._read = read
        super().__init__(name, description)

    def inc(self, value: float = 1.0, tags=None):
        raise TypeError(f"{self.name} is a view: its owner counts")

    def _export(self) -> dict:
        return {"name": self.name, "type": self.TYPE, "description": self.description,
                "values": {(): float(self._read())}}


class Gauge(Metric):
    """Last-value gauge (reference metrics.py:295)."""

    TYPE = "gauge"

    def __init__(self, name, description="", tag_keys=None):
        self._values: Dict[Tuple, float] = {}
        super().__init__(name, description, tag_keys)

    def set(self, value: float, tags: Optional[Dict[str, str]] = None):
        key = _tag_key(self._merged(tags))
        with self._lock:
            if self._admit(key, self._values):
                self._values[key] = float(value)

    def _export(self) -> dict:
        with self._lock:
            return {"name": self.name, "type": self.TYPE, "description": self.description,
                    "values": dict(self._values)}


class Histogram(Metric):
    """Bucketed histogram (reference metrics.py:217)."""

    TYPE = "histogram"

    def __init__(self, name, description="", boundaries=None, tag_keys=None):
        self.boundaries = sorted(boundaries or DEFAULT_HISTOGRAM_BOUNDARIES)
        self._buckets: Dict[Tuple, List[int]] = {}
        self._sums: Dict[Tuple, float] = defaultdict(float)
        self._counts: Dict[Tuple, int] = defaultdict(int)
        super().__init__(name, description, tag_keys)

    def observe(self, value: float, tags: Optional[Dict[str, str]] = None):
        key = _tag_key(self._merged(tags))
        with self._lock:
            if not self._admit(key, self._buckets):
                return
            buckets = self._buckets.setdefault(key, [0] * (len(self.boundaries) + 1))
            i = 0
            while i < len(self.boundaries) and value > self.boundaries[i]:
                i += 1
            buckets[i] += 1
            self._sums[key] += value
            self._counts[key] += 1

    def _export(self) -> dict:
        with self._lock:
            return {
                "name": self.name, "type": self.TYPE, "description": self.description,
                "boundaries": self.boundaries,
                "values": {k: {"buckets": list(v), "sum": self._sums[k],
                               "count": self._counts[k]}
                           for k, v in self._buckets.items()},
            }


# ------------------------------------------------------------------- aggregation

def _rebin(counts: List[int], src_bounds: List[float],
           dst_bounds: List[float]) -> List[int]:
    """Map bucket counts from one boundary set onto another: each source
    bucket's count lands in the destination bucket containing the source
    bucket's upper edge (the overflow bucket stays overflow). Lossy only in
    the sense any re-binning is — counts and sums are preserved exactly."""
    out = [0] * (len(dst_bounds) + 1)
    for i, cnt in enumerate(counts):
        if not cnt:
            continue
        if i < len(src_bounds):
            edge = src_bounds[i]
            j = 0
            while j < len(dst_bounds) and edge > dst_bounds[j]:
                j += 1
        else:
            j = len(dst_bounds)
        out[j] += cnt
    return out


def merge_snapshots(snaps: List[List[dict]]) -> Dict[str, dict]:
    """Merge per-process snapshots (driver registry + worker pushes + node
    deltas) by metric name. Histograms carry their own per-metric
    `boundaries` through the worker->coordinator push; when two processes
    registered the same histogram with DIFFERENT boundaries, the incoming
    buckets are re-binned onto the first-seen set instead of being
    zip-truncated into corruption. The merged view applies the same
    bounded-cardinality guard as live registries (a fleet of pre-guard
    workers must not explode head memory); merge-time drops are folded into
    the dropped-series counter so degradation is visible."""
    cap = _max_series()
    merge_dropped: Dict[str, int] = defaultdict(int)

    def admit(name: str, key: Tuple, existing: Dict) -> bool:
        if key in existing or name == DROPPED_SERIES_METRIC:
            return True
        if cap <= 0 or len(existing) < cap:
            return True
        merge_dropped[name] += 1
        return False

    out: Dict[str, dict] = {}
    for snap in snaps:
        for m in snap:
            cur = out.get(m["name"])
            if cur is None:
                import copy

                cur = copy.deepcopy(m)
                if cap > 0 and m["name"] != DROPPED_SERIES_METRIC \
                        and len(cur["values"]) > cap:
                    keep = list(cur["values"].items())[:cap]
                    merge_dropped[m["name"]] += len(cur["values"]) - cap
                    cur["values"] = dict(keep)
                out[m["name"]] = cur
                continue
            if m["type"] == "counter":
                for k, v in m["values"].items():
                    if admit(m["name"], k, cur["values"]):
                        cur["values"][k] = cur["values"].get(k, 0.0) + v
            elif m["type"] == "gauge":
                for k, v in m["values"].items():
                    if admit(m["name"], k, cur["values"]):
                        cur["values"][k] = v
            elif m["type"] == "histogram":
                src_bounds = list(m.get("boundaries", DEFAULT_HISTOGRAM_BOUNDARIES))
                dst_bounds = list(cur.get("boundaries", DEFAULT_HISTOGRAM_BOUNDARIES))
                same = src_bounds == dst_bounds
                for k, v in m["values"].items():
                    buckets = (list(v["buckets"]) if same
                               else _rebin(v["buckets"], src_bounds, dst_bounds))
                    tgt = cur["values"].get(k)
                    if tgt is None:
                        if admit(m["name"], k, cur["values"]):
                            cur["values"][k] = {"buckets": buckets,
                                                "sum": v["sum"], "count": v["count"]}
                    else:
                        tgt["buckets"] = [a + b for a, b in zip(tgt["buckets"], buckets)]
                        tgt["sum"] += v["sum"]
                        tgt["count"] += v["count"]
    if merge_dropped:
        cur = out.get(DROPPED_SERIES_METRIC)
        if cur is None:
            cur = {"name": DROPPED_SERIES_METRIC, "type": "counter",
                   "description": "label sets dropped by the bounded-"
                                  "cardinality guard "
                                  "(RAY_TPU_CONTROL_MAX_SERIES), by metric",
                   "values": {}}
            out[DROPPED_SERIES_METRIC] = cur
        for name, n in merge_dropped.items():
            k = (("metric", name),)
            cur["values"][k] = cur["values"].get(k, 0.0) + float(n)
    return out


# --------------------------------------------------------------- wire codecs

def snapshot_to_wire(snap: List[dict]) -> List[dict]:
    """JSON-safe form of a snapshot: the tag-tuple dict keys (tuples of
    (k, v) pairs) become explicit `series` lists. Node agents ship their
    merged per-node delta to the head as JSON bytes in this form — the head
    never unpickles agent control traffic (core/agent_rpc.py trust
    posture)."""
    out = []
    for m in snap:
        w = {"name": m["name"], "type": m["type"],
             "description": m.get("description", "")}
        if "boundaries" in m:
            w["boundaries"] = list(m["boundaries"])
        w["series"] = [
            {"tags": [[k, v] for k, v in key], "value": val}
            for key, val in m["values"].items()
        ]
        out.append(w)
    return out


def snapshot_from_wire(wire: List[dict]) -> List[dict]:
    """Inverse of snapshot_to_wire: rebuild the tag-tuple-keyed snapshot
    shape that merge_snapshots consumes. Tolerant of malformed entries
    (skips them) — the input crossed a process boundary."""
    out = []
    for m in wire:
        try:
            d = {"name": m["name"], "type": m["type"],
                 "description": m.get("description", "")}
            if "boundaries" in m:
                d["boundaries"] = list(m["boundaries"])
            values = {}
            for s in m.get("series", []):
                key = tuple((str(k), str(v)) for k, v in s["tags"])
                values[key] = s["value"]
            d["values"] = values
            out.append(d)
        # graftlint: allow[swallowed-exception] degrades to the coded fallback (continue) by design
        except Exception:
            continue
    return out


def _tags_match(key_tuple: Tuple, where: Optional[Dict[str, str]]) -> bool:
    """Does this tag-set key (tuple of (k, v) pairs) satisfy the label filter?"""
    if not where:
        return True
    tags = dict(key_tuple)
    return all(tags.get(k) == v for k, v in where.items())


def aggregate_buckets(merged: dict,
                      where: Optional[Dict[str, str]] = None) -> List[int]:
    """Sum a histogram metric's per-tag-set bucket counts into one vector,
    optionally restricted to tag sets matching the `where` label filter
    (e.g. {"route": "/chat"} to quantile serve_ttft_seconds per-route)."""
    bounds = merged.get("boundaries", [])
    agg = [0] * (len(bounds) + 1)
    for key, v in merged.get("values", {}).items():
        if not _tags_match(key, where):
            continue
        for i, c in enumerate(v["buckets"]):
            agg[i] += c
    return agg


def histogram_counts_below(merged: dict, threshold: float,
                           where: Optional[Dict[str, str]] = None
                           ) -> Tuple[float, int]:
    """(estimated observations <= threshold, total observations) for a merged
    histogram — the good/total split behind latency SLO burn rates. The count
    inside the bucket containing the threshold is linearly interpolated, like
    histogram_quantile's inverse."""
    bounds = merged.get("boundaries", [])
    agg = aggregate_buckets(merged, where)
    total = sum(agg)
    if total <= 0:
        return 0.0, 0
    good = 0.0
    for i, c in enumerate(agg):
        lo = bounds[i - 1] if i > 0 else 0.0
        hi = bounds[i] if i < len(bounds) else float("inf")
        if threshold >= hi:
            good += c
        elif threshold > lo:
            good += c * (threshold - lo) / (hi - lo)
    return good, total


def histogram_quantile(merged: dict, q: float,
                       where: Optional[Dict[str, str]] = None
                       ) -> Optional[float]:
    """Estimate the q-quantile (0..1) of a merged histogram metric,
    Prometheus histogram_quantile-style: find the bucket where the cumulative
    count crosses q and interpolate linearly inside it. The overflow bucket
    answers with its lower edge (no upper bound to lerp to). Aggregates
    across ALL tag sets unless `where` narrows them (label filter, e.g.
    {"route": "/chat"}). Returns None for an empty histogram."""
    bounds = merged.get("boundaries", [])
    agg = aggregate_buckets(merged, where)
    total = sum(agg)
    if total <= 0:
        return None
    target = max(0.0, min(1.0, q)) * total
    cum = 0
    for i, c in enumerate(agg):
        if cum + c >= target and c > 0:
            if i >= len(bounds):
                return float(bounds[-1]) if bounds else None
            lo = bounds[i - 1] if i > 0 else 0.0
            frac = (target - cum) / c
            return float(lo + (bounds[i] - lo) * frac)
        cum += c
    return float(bounds[-1]) if bounds else None


def prometheus_text(merged: Dict[str, dict], prefix: str = "ray_tpu") -> str:
    """Render merged metrics in Prometheus exposition format (reference: the
    dashboard agent's re-export; dashboard/modules/metrics)."""
    lines = []
    for name, m in sorted(merged.items()):
        full = f"{prefix}_{name}"
        lines.append(f"# HELP {full} {m.get('description', '')}")
        lines.append(f"# TYPE {full} {m['type']}")

        def fmt_tags(key_tuple, extra=None):
            items = list(key_tuple) + (list(extra.items()) if extra else [])
            if not items:
                return ""
            inner = ",".join(f'{k}="{v}"' for k, v in items)
            return "{" + inner + "}"

        if m["type"] in ("counter", "gauge"):
            for k, v in m["values"].items():
                lines.append(f"{full}{fmt_tags(k)} {v}")
        else:
            for k, v in m["values"].items():
                cum = 0
                for bound, cnt in zip(m["boundaries"] + [float("inf")], v["buckets"]):
                    cum += cnt
                    lines.append(f'{full}_bucket{fmt_tags(k, {"le": bound})} {cum}')
                lines.append(f"{full}_sum{fmt_tags(k)} {v['sum']}")
                lines.append(f"{full}_count{fmt_tags(k)} {v['count']}")
    return "\n".join(lines) + "\n"
