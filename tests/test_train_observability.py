"""The training path's own clock (ISSUE 35): the train loop's laps and always-on
counters (`ray_tpu.train.metrics()`), the worker's other threads as spans on the
profile's host plane, the stamps from `fit()` to the user's loop. CPU, tiny sizes."""
import gc
import glob
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import ray_tpu.train as train
from ray_tpu.core import worker as core_worker
from ray_tpu.models import llama
from ray_tpu.models.config import get_config
from ray_tpu.train import init_state, make_optimizer, make_train_step, session
from ray_tpu.util import telemetry

LAPS = list(session._LOOP_COUNTERS)
COUNTERS = LAPS + ["train_steps_total", "train_slow_steps_total", "compiles_total", "compile_ns_total",
                   "worker_tasks_total", "worker_task_ns_total",
                   "gc_pause_ns_total", "gc_collections_total"]


def _tiny():
    cfg = get_config("test-tiny")
    tx = make_optimizer()
    return cfg, tx, init_state(jax.random.PRNGKey(0), cfg, tx)


def _batch(cfg, rows=2, seq=33, seed=0):
    tokens = np.random.default_rng(seed).integers(0, cfg.vocab_size, (rows, seq), dtype=np.int32)
    return {"tokens": jnp.asarray(tokens)}  # no program of its own: a transfer


def _fit_loop(config):
    """Runs in the train worker, on its `train_loop` thread. Tracing is OFF there."""
    first_line = time.time()
    import jax.profiler

    cfg, tx, state = _tiny()
    step = make_train_step(cfg, tx)
    state, m = step(state, _batch(cfg))  # compiles
    float(m["loss"])
    reads = [train.metrics()]
    t0 = time.perf_counter_ns()
    for i in range(20):
        state, m = step(state, _batch(cfg, seed=i))
        float(m["loss"])  # the sync a user's loop makes
    reads.append(train.metrics())
    wall_ns = time.perf_counter_ns() - t0
    shard = train.get_dataset_shard("train")
    rows = sum(len(b["id"]) for b in shard.iter_batches(batch_size=8))
    gc.collect()
    train.report({"rows": rows})
    reads.append(train.metrics())
    # a profile of the worker, long enough for the executor's 20 Hz poll to land in it
    jax.profiler.start_trace(config["trace_dir"])
    try:
        until = time.monotonic() + 0.5
        while time.monotonic() < until:
            state, m = step(state, _batch(cfg))
            float(m["loss"])
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    # the batch's shape changes: one program more; the same shape again: none
    compiles = [train.metrics()["compiles_total"]]
    for rows_ in (4, 4):
        state, m = step(state, _batch(cfg, rows=rows_))
        float(m["loss"])
        compiles.append(train.metrics()["compiles_total"])
    train.report({
        "first_line": first_line, "reads": reads, "wall_ns": wall_ns, "rows": rows,
        "compiles": compiles,
        "boot": {k: list(v) for k, v in core_worker.boot_stamps().items()},
        "gc_callbacks": gc.callbacks.count(core_worker._on_gc),
        "tracing": telemetry.enabled()})


@pytest.fixture(scope="module")
def fit_run(rt, tmp_path_factory):
    """One tiny `JaxTrainer.fit()`; what its loop and its driver saw."""
    from ray_tpu import data
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train import JaxConfig, JaxTrainer

    work = tmp_path_factory.mktemp("fit")
    trace_dir = str(work / "trace")
    telemetry.enable()  # the driver's ring: the `train.setup.*` events with their starts
    telemetry.drain()
    try:
        before = time.time()
        result = JaxTrainer(
            _fit_loop, train_loop_config={"trace_dir": trace_dir},
            datasets={"train": data.range(32)},
            backend_config=JaxConfig(collective_group=False),
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="obs", storage_path=str(work / "runs"))).fit()
        ring = telemetry.drain()
    finally:
        telemetry.reset_forced()
    assert result.error is None, result.error
    return {"m": result.metrics, "before_fit": before, "trace_dir": trace_dir,
            "driver_setup": dict(train.metrics()["setup_seconds"]),
            "ring": [e for e in ring if e["name"].startswith("train.setup.")]}


def test_the_four_laps_sum_to_the_loop_threads_wall_time(fit_run):
    m = fit_run["m"]
    assert not m["tracing"]  # always-on means with tracing off
    a, b = m["reads"][0], m["reads"][1]
    laps = sum(b[k] - a[k] for k in LAPS)
    assert abs(laps - m["wall_ns"]) < 1_000_000, (laps, m["wall_ns"])
    assert b["train_steps_total"] - a["train_steps_total"] == 20
    # a synced loop waits for its loss in the user's own lap; every step was dispatched
    assert b["train_loop_dispatch_ns_total"] > a["train_loop_dispatch_ns_total"]
    assert b["train_loop_user_ns_total"] > a["train_loop_user_ns_total"]


def test_every_counter_is_present_monotonic_and_moves_with_tracing_off(fit_run):
    reads = fit_run["m"]["reads"]
    for read in reads:
        assert set(COUNTERS) <= set(read), sorted(set(COUNTERS) - set(read))
    for a, b in zip(reads, reads[1:]):
        for k in COUNTERS:
            assert b[k] >= a[k], (k, a[k], b[k])
    first, last = reads[0], reads[-1]
    moved = {k for k in COUNTERS if last[k] > first[k]}
    # between the reads: 20 steps, a dataset read through, a collection, a report, and
    # the executor's polls served on the worker's main thread
    assert moved >= set(LAPS) | {"train_steps_total", "worker_tasks_total",
                                 "worker_task_ns_total", "gc_collections_total",
                                 "gc_pause_ns_total"}, sorted(moved)
    assert first["compiles_total"] > 0  # the step's own compile came before the first read
    assert fit_run["m"]["rows"] == 32


def test_compiles_total_moves_by_one_when_the_batchs_shape_changes(fit_run):
    before, changed, same = fit_run["m"]["compiles"]
    assert changed == before + 1
    assert same == changed


def test_a_workers_profile_holds_the_loops_laps_and_the_other_threads_tasks(fit_run):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        fit_run["trace_dir"], "plugins", "profile", "*", "*.xplane.pb")))[-1]
    by_name = {}  # a line of the host plane is a thread (all named `python` by the OS)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                by_name.setdefault(ev.name, []).append(((plane.name, thread), dict(ev.stats)))
    loop_threads = {thread for thread, _ in by_name.get("train.loop.dispatch", ())}
    assert len(loop_threads) == 1, loop_threads  # the loop's thread, and only it
    for lap in ("train.loop.user", "train.loop.dispatch"):
        assert {thread for thread, _ in by_name[lap]} == loop_threads
    polls = [thread for thread, stats in by_name.get("worker.task", ())
             if stats.get("task") == "poll_session"]
    assert polls and not set(polls) & loop_threads, (polls, sorted(by_name))
    assert any(stats.get("generation") == 2 for _, stats in by_name.get("worker.gc", ()))
    assert telemetry.CLOCK_SYNC in by_name  # ring events can be placed on this profile


def test_setup_stamps_are_ordered_and_sum_to_the_time_from_fit_to_the_loop(fit_run):
    m, driver = fit_run["m"], fit_run["driver_setup"]
    phases = ["train.setup.worker_group", "train.setup.backend", "train.setup.session"]
    assert set(phases) <= set(driver), driver
    starts = {e["name"]: e["ts_ns"] for e in fit_run["ring"]}
    assert [starts[p] for p in phases] == sorted(starts[p] for p in phases)
    worker = m["reads"][-1]["setup_seconds"]
    assert {"worker.boot.ready", "worker.boot.first_task",
            "train.setup.loop_entered"} <= set(worker), worker
    boot = m["boot"]
    if "worker.boot.spawn" in boot:  # where there is a /proc
        assert boot["worker.boot.spawn"][0] <= boot["worker.boot.ready"][0]
        assert sum(boot["worker.boot.spawn"]) == boot["worker.boot.ready"][0]
    assert sum(boot["worker.boot.ready"]) == boot["worker.boot.first_task"][0]
    measured = m["first_line"] - fit_run["before_fit"]
    stamped = sum(driver[p] for p in phases) + worker["train.setup.loop_entered"]
    assert abs(measured - stamped) < 0.5, (measured, stamped, driver, worker)


def test_the_collectors_callback_is_registered_once_a_process(fit_run):
    assert fit_run["m"]["gc_callbacks"] == 1
    n = gc.callbacks.count(core_worker._on_gc)
    try:
        core_worker.count_collections()
        core_worker.count_collections()
        assert gc.callbacks.count(core_worker._on_gc) == 1
    finally:
        if not n:
            gc.callbacks.remove(core_worker._on_gc)


def test_a_long_collection_is_a_ring_event_and_every_one_is_counted():
    before = core_worker.process_counters()
    telemetry.enable()
    try:
        telemetry.drain()
        core_worker._on_gc("start", {"generation": 0, "collected": 0, "uncollectable": 0})
        core_worker._on_gc("stop", {"generation": 0, "collected": 3, "uncollectable": 0})
        assert not [e for e in telemetry.drain() if e["name"] == "worker.gc"]  # a young one: counted only
        core_worker._on_gc("start", {"generation": 2, "collected": 0, "uncollectable": 0})
        time.sleep(0.003)
        core_worker._on_gc("stop", {"generation": 2, "collected": 7, "uncollectable": 0})
        events = [e for e in telemetry.drain() if e["name"] == "worker.gc"]
    finally:
        telemetry.reset_forced()
    after = core_worker.process_counters()
    assert after["gc_collections_total"] == before["gc_collections_total"] + 2
    assert after["gc_pause_ns_total"] >= before["gc_pause_ns_total"] + 3_000_000
    assert len(events) == 1 and events[0]["args"]["generation"] == 2
    assert events[0]["dur_ns"] >= 3_000_000


def test_a_long_collection_under_the_rings_lock_does_not_block_its_thread():
    """The collector runs wherever the interpreter stops, also in `_append` and `drain`
    with `telemetry._lock` held: its callback may not wait for that lock."""
    done = threading.Event()

    def collect_under_the_lock():
        with telemetry._lock:
            core_worker._on_gc("start", {"generation": 2, "collected": 0, "uncollectable": 0})
            time.sleep(0.003)
            core_worker._on_gc("stop", {"generation": 2, "collected": 1, "uncollectable": 0})
        done.set()

    telemetry.enable()
    try:
        telemetry.drain()
        t = threading.Thread(target=collect_under_the_lock, daemon=True)
        t.start()
        assert done.wait(5.0), "the collector's callback waits for the lock its thread holds"
        assert telemetry.pending() == 1
        telemetry.event("after", "test")  # the next append carries the waiting record in
        names = [e["name"] for e in telemetry.drain()]
    finally:
        telemetry.reset_forced()
    assert names == ["worker.gc", "after"]


def test_a_loop_whose_thread_ended_without_a_session_stops_counting():
    cfg, tx, state = _tiny()
    step = make_train_step(cfg, tx, donate=False)
    batch = _batch(cfg)

    def two_steps():
        for _ in range(2):
            step(state, batch)

    before = train.metrics()
    t = threading.Thread(target=two_steps)
    t.start()
    t.join()
    first = train.metrics()  # finds the thread dead: its closed laps go to the ended threads' sums
    ended = dict(session._retired)
    assert not [l for l in session._loops if l.thread is t]
    assert first["train_steps_total"] == before["train_steps_total"] + 2
    assert first["train_loop_dispatch_ns_total"] > before["train_loop_dispatch_ns_total"]
    time.sleep(0.05)
    train.metrics()
    assert session._retired == ended  # and its open `user` lap counts no further


def test_the_step_callable_keeps_lower_compile_and_donation():
    cfg, tx, state = _tiny()
    step = make_train_step(cfg, tx)
    batch = _batch(cfg)
    lowered = step.lower(state, batch)
    compiled = lowered.compile()
    assert "jit_step" in compiled.as_text().splitlines()[0]  # the profile's module name
    assert step._cache_size() == 0  # the jit's own attributes come through
    kept = jax.tree.leaves(state.params)[0]
    new_state, metrics = step(state, batch)
    assert step._cache_size() == 1
    assert kept.is_deleted()  # the state was donated to the step
    assert np.isfinite(float(metrics["loss"]))
    kept = jax.tree.leaves(new_state.params)[0]
    make_train_step(cfg, tx, donate=False)(new_state, batch)
    assert not kept.is_deleted()  # and is not where the caller says so


def test_outside_a_session_the_callable_counts_into_the_same_integers():
    cfg, tx, state = _tiny()
    step = make_train_step(cfg, tx)
    state, _ = step(state, _batch(cfg))
    a = train.metrics()
    t0 = time.perf_counter_ns()
    for i in range(5):
        state, m = step(state, _batch(cfg, seed=i))
        float(m["loss"])
    wall = time.perf_counter_ns() - t0
    b = train.metrics()
    assert b["train_steps_total"] - a["train_steps_total"] == 5
    assert b["compiles_total"] == a["compiles_total"]
    assert abs(sum(b[k] - a[k] for k in LAPS) - wall) < 1_000_000
    hist = telemetry.get_histogram("train_step_interval_seconds")._export()
    assert sum(v["count"] for v in hist["values"].values()) >= 5


def _fresh_intervals(monkeypatch):
    """The histogram of step intervals a process shares, replaced by one of this test's own: the
    threshold is read from the steps the test drives and from no other test's."""
    from ray_tpu.util.metrics import Histogram

    hist = Histogram("train_step_interval_seconds_of_a_test", boundaries=session._STEP_INTERVAL_BOUNDARIES)
    monkeypatch.setattr(session, "_step_interval", lambda: hist)
    return hist


class _SteppedClock:
    """`time` as `telemetry` and `session` read it, with `perf_counter_ns` on the driven thread in the test's
    hand: it advances by what the step callable is told (`sleep`) and by nothing else, so a step's length is
    the entry of `seconds_of_step` whatever the machine's load. Every other thread reads the real clock."""

    def __init__(self):
        self.thread, self.now_ns = None, time.perf_counter_ns()

    def __getattr__(self, name):  # everything else is `time`'s own
        return getattr(time, name)

    def perf_counter_ns(self):
        return self.now_ns if threading.get_ident() == self.thread else time.perf_counter_ns()

    def sleep(self, seconds):
        self.now_ns += round(seconds * 1e9)


def _drive(monkeypatch, seconds_of_step):
    """A step callable under the loop's clock, called once for every entry of `seconds_of_step`
    on a thread (and so a loop) of its own, each call as long on the loop's clock as its entry says
    (`_SteppedClock`). -> what `train.metrics()` read before and after."""
    clock = _SteppedClock()
    for module in (telemetry, session):  # the laps' clock, and the instant `_os_numbers` reads
        monkeypatch.setattr(module, "time", clock)
    step = session.CountedStep(clock.sleep)
    reads = []

    def run():
        clock.thread = threading.get_ident()
        try:
            reads.append(train.metrics())
            for seconds in seconds_of_step:
                step(seconds)
            reads.append(train.metrics())
        finally:
            session._end_loop()

    t = threading.Thread(target=run)
    t.start()
    t.join()
    return reads


def test_a_slow_step_leaves_one_line_and_one_count(monkeypatch, caplog):
    """40 steps of 2 ms, one of 150 (an injected stall), 9 of 2 ms again: past the 16th step the
    threshold is 4 x the median read from the histogram's buckets, so the stall, and no other
    step, is one warning line of the worker's log (the interval and the median, the laps it
    went to, the collector's pauses and the compiles of that step, the operating system's
    numbers since the threshold was read) and one count of `train_slow_steps_total`."""
    _fresh_intervals(monkeypatch)
    with caplog.at_level("WARNING", logger=session.LOGGER.name):
        before, after = _drive(monkeypatch, [0.002] * 40 + [0.15] + [0.002] * 9)
    assert after["train_steps_total"] - before["train_steps_total"] == 50
    assert after["train_slow_steps_total"] - before["train_slow_steps_total"] == 1
    lines = [r.getMessage() for r in caplog.records if r.name == session.LOGGER.name]
    assert len(lines) == 1 and "\n" not in lines[0], lines
    line = lines[0]
    found = re.search(r"step (\d+) took ([\d.]+) ms entry to entry against a median of ([\d.]+) ms; "
                      r"laps dispatch ([\d.]+) report ([\d.]+) data ([\d.]+) user ([\d.]+) ms; "
                      r"collector pauses ([\d.]+) ms, compiles (\d+); in the (\d+) steps and ([\d.]+) ms since", line)
    assert found, line
    step, took, median, dispatch, report, data, user, _, compiles, since_steps, since_ms = map(float, found.groups())
    assert step == 41 and 150 <= took < 600 and 1.5 < median < took / session.SLOW_FACTOR
    assert abs(dispatch + report + data + user - took) < 0.5  # the laps divide the interval (ms, rounded)
    assert dispatch >= 150  # `time.sleep` stalled inside the callable: the program's own lap
    assert compiles == 0 and since_steps == 41 - session.FIRST_REFRESH and since_ms > took
    if os.path.exists("/proc/thread-self/schedstat"):  # Linux: every field of the operating system's
        assert "process CPU" in line and "loop thread CPU" in line and "run-queue wait" in line
        assert re.search(r"voluntary switches \d+, involuntary \d+$", line), line


def test_no_step_is_slow_before_the_threshold_is_read_and_the_record_costs_no_system_call_a_step(monkeypatch, caplog):
    """A stall among a loop's first steps (where a program compiles) is no record: the threshold
    is read at the 16th step. And the operating system is asked for its numbers at a refresh
    and after a slow step, never at a step's entry."""
    _fresh_intervals(monkeypatch)
    asked = []
    real = session._os_numbers
    monkeypatch.setattr(session, "_os_numbers", lambda steps: asked.append(steps) or real(steps))
    with caplog.at_level("WARNING", logger=session.LOGGER.name):
        before, after = _drive(monkeypatch, [0.001] * 5 + [0.1] + [0.001] * (session.FIRST_REFRESH + session.REFRESH_EVERY))
    assert after["train_slow_steps_total"] == before["train_slow_steps_total"]
    assert not [r for r in caplog.records if r.name == session.LOGGER.name]
    assert asked == [session.FIRST_REFRESH, session.FIRST_REFRESH + session.REFRESH_EVERY]  # two refreshes in 86 steps


def test_a_lap_inside_a_lap_hands_the_thread_back_to_the_one_that_was_open():
    seen = {}

    def run():
        loop = session._loop()
        try:
            assert loop.clock.lap == session.USER
            with telemetry.lap("train.loop.data"):
                assert loop.clock.lap == session.DATA
                with telemetry.lap("train.loop.report"):
                    assert loop.clock.lap == session.REPORT
                    time.sleep(0.002)
                assert loop.clock.lap == session.DATA
            assert loop.clock.lap == session.USER
            assert telemetry.lap("no.such.lap") is telemetry._NOOP
            seen.update(loop.clock.read())
        finally:
            session._end_loop()

    before = train.metrics()
    t = threading.Thread(target=run)
    t.start()
    t.join()
    assert seen["train_loop_report_ns_total"] >= 2_000_000
    after = train.metrics()  # an ended thread's laps stay in the sums
    assert after["train_loop_report_ns_total"] >= before["train_loop_report_ns_total"] + 2_000_000


def _stripped(text: str) -> str:
    """A program's text without where it was written: `op_name`s, source locations."""
    text = re.sub(r"loc\([^\n]*\)", "", text)  # StableHLO, where it prints them
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)  # HLO: op_name, stack frame
    head, sep, body = text.partition("\nFileNames\n")  # HLO: the tables the frames index
    return head + body[body.index("\n\n\n"):] if sep else text


def test_the_callable_lowers_to_what_the_bare_jit_of_the_step_lowers_to():
    """Scopes and the wrapper are metadata: the program is the one a plain `jax.jit`
    of the same arithmetic gives, without the `model` and `optimizer` scopes."""
    cfg, tx, state = _tiny()
    batch = _batch(cfg)

    def step(state, batch):
        (loss, aux), grads = jax.value_and_grad(llama.loss_fn, has_aux=True)(
            state.params, batch, cfg)
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics = dict(aux)
        metrics["grad_norm"] = optax.global_norm(grads)
        return train.TrainState(state.step + 1, new_params, new_opt), metrics

    bare = jax.jit(step, donate_argnums=(0,)).lower(state, batch)
    ours = make_train_step(cfg, tx).lower(state, batch)
    assert _stripped(ours.as_text()) == _stripped(bare.as_text())
    assert _stripped(ours.compile().as_text()) == _stripped(bare.compile().as_text())


def test_cluster_status_and_the_status_row_show_the_train_loops_clock(rt):
    from ray_tpu.scripts.cli import _render_status
    from ray_tpu.util import state as state_api

    cfg, tx, state = _tiny()
    step = make_train_step(cfg, tx)
    for i in range(3):
        state, m = step(state, _batch(cfg, seed=i))
        float(m["loss"])
    session.record_setup("train.setup.worker_group", time.time_ns(), 1_500_000_000)
    status = state_api.cluster_status()
    tn = status["train"]
    assert tn["steps"] >= 3 and tn["slow_steps"] == train.metrics()["train_slow_steps_total"]
    assert set(tn["loop_ms_per_step"]) == {"dispatch", "report", "data", "user"}
    assert tn["loop_ms_per_step"]["dispatch"] > 0
    assert tn["compiles"] >= 1 and tn["gc_collections"] >= 0
    assert tn["setup_seconds"]["train.setup.worker_group"] == 1.5
    assert "group_failures" in tn
    row = [ln for ln in _render_status(status).splitlines() if ln.startswith("train      steps=")]
    assert row and "loop/step[dispatch:" in row[0] and "worker_group:1.5s" in row[0], row
    assert f" slow={tn['slow_steps']} " in row[0], row


def test_the_head_and_loss_share_leaves_out_what_is_fused_into_its_neighbours():
    """`train_head_loss_pct` as its file defines it (benchmarks/metrics/): an operation
    counts for the head where none of its parts is the optimizer's or a layer's."""
    import importlib
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    with open(os.path.join(root, "benchmarks", "metrics", "train_head_loss_pct.json")) as f:
        metric = json.load(f)
    reader = importlib.import_module(f"benchmarks.readers.{metric['reader']}")
    scopes = {"logits": ["lm_head", "loss"], "mtp_logits": ["lm_head", "loss", "mtp"],
              "adam_over_the_head": ["lm_head", "optimizer"],
              "final_norm_with_the_last_layer": ["lm_head", "mlp", "moe_shared"],
              "a_constant_in_attention": ["attn", "loss", "mla_q", "while"],
              "a_constant_in_the_scan": ["checkpoint", "loss", "ssm_scan"],
              "experts": ["mlp", "moe_experts"]}
    trace = {"busy_s": 10.0, "op_seconds": dict.fromkeys(scopes, 1.0), "op_scopes": scopes}
    assert reader.read({"result": {"trace": trace}}, **metric["args"]) == pytest.approx(20.0)
    only_layers = {op: sc for op, sc in scopes.items() if "logits" not in op}
    assert reader.read({"result": {"trace": {**trace, "op_scopes": only_layers}}},
                       **metric["args"]) is None  # nothing of its own: nothing to read
    assert reader.read({"result": {"trace": {**trace, "op_scopes": {}}}}, **metric["args"]) is None
    assert reader.read({"result": {}}, **metric["args"]) is None

