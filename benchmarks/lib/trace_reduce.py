"""From a JAX profiler trace (`*.xplane.pb`) to numbers.

The profiler writes one plane for each device (`/device:TPU:<n>`) and one for
the host. A device plane has a line `XLA Ops` whose events are the operations
as they ran on that chip, each with a start and a duration in nanoseconds;
the host plane has a line for each thread, with the annotations the
benchmark's own loop writes (`jax.profiler.TraceAnnotation`). Both are on one
clock.

    busy      union of the intervals of a device's `XLA Ops` events
    window    first start to last end of those events, over all devices used
              (training: the traced steps); no shorter than the time the
              caller says it kept the profiler recording (serving: a device
              with nothing more to do is idle, and that is the point)
    idle      1 - busy / window
    op time   sum of durations by operation name
    exposed   the part of the collective operations' intervals during which
              no other operation runs on that device
    modules   for each jitted program (`XLA Modules` line, named after the
              jitted function): runs, seconds in all, median seconds a run

Everything is averaged over the devices used. Read by `readers/trace_*.py`.
"""
import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"  # one event for each run of a jitted program, named jit_<function>(<id>)
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")


def is_collective(name: str) -> bool:
    """By the instruction's own name (`%all-gather.12 = ...`), not by its
    HLO text, which also names its operands."""
    return bool(COLLECTIVE.search(name.partition(" = ")[0]))
# events of the line that wrap others (a while loop wraps its body's operations)
# would count their children twice in a sum by name; the union does not care
UNATTRIBUTED = "host: not attributed"
LAYOUT = re.compile(r"\{[^{}]*\}")


def op_label(name: str, width: int = 110) -> str:
    """A short name for an operation. The trace names an operation by its
    whole HLO text: keep the instruction's name, what kind it is (for a
    custom call, its target) and the start of its result type."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:width]
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    kind = re.search(r"[)\]}] ([a-z][\w\-]*)\(", rest)
    what = target.group(1) if target else (kind.group(1) if kind else "op")
    result = LAYOUT.sub("", rest.split(f" {kind.group(1)}(")[0] if kind else rest)
    return f"{head.lstrip('%')} [{what}] {result}"[:width]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """{plane name: {line name: [(start_ns, dur_ns, name), ...]}}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((float(ev.start_ns), float(ev.duration_ns), ev.name))
    return planes


def union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def total(intervals: list) -> float:
    return sum(end - start for start, end in intervals)


def subtract(a: list, b: list) -> list:
    """The parts of merged intervals `a` not covered by merged intervals `b`."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append([cur, end])
    return out


def _leaf_events(events: list) -> list:
    """Events that contain no other event of the line: a `while` or a fusion
    that the line shows around its body is left out, so a sum by name counts
    each nanosecond once."""
    ordered = sorted(events, key=lambda e: (e[0], -e[1]))
    leaves = []
    for i, (start, dur, name) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[0] < start + dur and nxt[0] + nxt[1] <= start + dur + 1:
            continue  # the next event starts inside this one: this one is a wrapper
        leaves.append((start, dur, name))
    return leaves


def host_annotations(planes: dict, names=None) -> list:
    """(start, end, name) of TraceAnnotation events on the host's threads."""
    out = []
    for pname, lines in planes.items():
        if DEVICE_PLANE.match(pname):
            continue
        for events in lines.values():
            for start, dur, name in events:
                if names is None or name in names:
                    out.append((start, start + dur, name))
    return out


def reduce(planes: dict, n_devices: int, annotations=("host_batch", "dispatch_and_wait"),
           top: int = 10, min_window_s: float = 0.0) -> dict:
    devices = sorted((int(DEVICE_PLANE.match(p).group(1)), p) for p in planes
                     if DEVICE_PLANE.match(p) and planes[p].get(OPS_LINE))
    devices = devices[:n_devices]
    summary = {"planes": sorted(planes), "lines": sorted({
        ln for _, p in devices for ln in planes[p]}), "n_devices": len(devices)}
    if not devices:
        return dict(summary, window_s=0.0, busy_s=0.0, idle_share=None, op_seconds={}, modules={},
                    top_ops=[], idle_gaps=[], collective_s=0.0, collective_exposed_s=0.0)
    all_events = [e for _, p in devices for e in planes[p][OPS_LINE]]
    w0 = min(e[0] for e in all_events)
    w1 = max(e[0] + e[1] for e in all_events)
    # the profile's own start and stop times will not do: the third of a second
    # they add at the end is the profiler collecting, with nothing recorded
    w1 = max(w1, w0 + min_window_s * 1e9)
    notes = host_annotations(planes, set(annotations))
    busy, op_ns, coll, exposed, gaps = 0.0, {}, 0.0, 0.0, []
    for _, p in devices:
        events = planes[p][OPS_LINE]
        covered = union([[s, s + d] for s, d, _ in events])
        busy += total(covered)
        leaves = _leaf_events(events)
        for s, d, name in leaves:
            op_ns[name] = op_ns.get(name, 0.0) + d
        c_iv = union([[s, s + d] for s, d, n in leaves if is_collective(n)])
        o_iv = union([[s, s + d] for s, d, n in leaves if not is_collective(n)])
        coll += total(c_iv)
        exposed += total(subtract(c_iv, o_iv))
        edges = [[w0, w0]] + covered + [[w1, w1]]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                gaps.append((b - a, a, b))
    n = len(devices)
    runs = {}
    for _, p in devices:
        for _, d, name in planes[p].get(MODULES_LINE, []):
            runs.setdefault(name.split("(")[0], []).append(d / 1e9)
    modules = {name: {"runs": len(d) / n, "total_s": sum(d) / n, "median_s": statistics.median(d)}
               for name, d in runs.items()}
    gap_by_label = {}
    for dur, a, b in gaps:
        mid, label = (a + b) / 2, UNATTRIBUTED
        for s, e, name in notes:
            if s <= mid <= e:
                label = f"host: {name}"
                break
        gap_by_label.setdefault(label, []).append(dur)
    # the longest single gap under each label, and how much that label holds in all
    idle_gaps = sorted(([f"{label} (longest of {len(d)}, {sum(d) / n / 1e9:.6f} s in all)",
                         max(d) / 1e9] for label, d in gap_by_label.items()),
                       key=lambda g: -g[1])[:top]
    op_seconds = {name: ns / n / 1e9 for name, ns in op_ns.items()}
    top_ops = [[op_label(name), s] for name, s in
               sorted(op_seconds.items(), key=lambda x: -x[1])[:top]]
    window_s, busy_s = (w1 - w0) / 1e9, busy / n / 1e9
    return dict(summary, window_s=window_s, busy_s=busy_s,
                idle_share=1.0 - busy_s / window_s if window_s > 0 else None,
                op_seconds=op_seconds, top_ops=top_ops, idle_gaps=idle_gaps, modules=modules,
                collective_s=coll / n / 1e9, collective_exposed_s=exposed / n / 1e9)


def reduce_dir(trace_dir: str, n_devices: int, **kw) -> dict:
    return reduce(load(find_xplane(trace_dir)), n_devices, **kw)


def into_result(out: dict, reduced: dict) -> None:
    """Put a reduced trace where run.py and the readers look for it."""
    out["trace"] = reduced
    out["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    out["breakdown"] = {"device_ops": reduced["top_ops"], "idle_gaps": reduced["idle_gaps"]}


def keep(trace_dir: str, dest) -> None:
    """Copy the trace file out of the run's scratch directory (--keep-trace)."""
    if dest:
        import shutil

        os.makedirs(dest, exist_ok=True)
        shutil.copy(find_xplane(trace_dir), dest)
