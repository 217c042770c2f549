"""readers/counter_ratio.py on a hand-made context, and the metric files that
use it on the counters an engine of PR 25 returns and on one from before."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.readers import counter_delta, counter_ratio  # noqa: E402

BEFORE = {"decode_steps_total": 100, "decode_slot_steps_total": 400, "admitted_total": 10,
          "queue_wait_ns_total": 5_000_000, "ingress_ns_total": 0, "ingress_requests_total": 0,
          "loop_admit_ns_total": 0, "loop_grow_ns_total": 0, "loop_dispatch_ns_total": 0,
          "loop_fetch_ns_total": 0, "loop_emit_ns_total": 0, "loop_idle_ns_total": 0,
          "prefill_ns_total": 0, "prefill_calls_total": 2, "prefill_tokens_total": 1000,
          "compiles_total": 7, "compile_ns_total": 3_000_000_000}
AFTER = {"decode_steps_total": 300, "decode_slot_steps_total": 2000, "admitted_total": 14,
         "queue_wait_ns_total": 13_000_000, "ingress_ns_total": 0, "ingress_requests_total": 0,
         "loop_admit_ns_total": 400_000_000, "loop_grow_ns_total": 100_000_000,
         "loop_dispatch_ns_total": 900_000_000, "loop_fetch_ns_total": 8_000_000_000,
         "loop_emit_ns_total": 600_000_000, "loop_idle_ns_total": 3_000_000_000,
         "prefill_ns_total": 250_000_000, "prefill_calls_total": 7, "prefill_tokens_total": 4000,
         "compiles_total": 7, "compile_ns_total": 3_000_000_000}


def ctx(before=BEFORE, after=AFTER):
    return {"result": {"counters": {"before": before, "after": after}},
            "config": {"engine": {"max_num_seqs": 32}}}


def metric(name):
    with open(os.path.join(HERE, "..", "metrics", f"{name}.json")) as f:
        m = json.load(f)
    reader = {"counter_ratio": counter_ratio, "counter_delta": counter_delta}[m["reader"]]
    return lambda c: reader.read(c, **m["args"])


@pytest.mark.parametrize("name,want", [
    ("serve_batch_occupancy_pct", 100.0 * 1600 / (200 * 32)),
    ("serve_queue_wait_ms", 8.0 / 4),
    # admission's 400 ms hold the 250 ms of prefill, which are not host time
    ("serve_engine_host_ms_per_step", (400 - 250 + 100 + 900 + 600) / 200),
    ("serve_prefill_share_pct", 100.0 * 250 / (400 + 100 + 900 + 8000 + 600)),
    ("serve_compiles_in_window", 0),
    ("serve_prefill_ms_per_call", 250.0 / 5),
    ("serve_prefill_tokens_per_s", 3000 / 0.25),
    ("serve_compile_ns_in_window", 0),
])
def test_metric_files_on_hand_made_counters(name, want):
    assert metric(name)(ctx()) == pytest.approx(want)


def test_none_when_the_denominator_did_not_move():
    # no request came through the proxy in the window: no way in to average
    assert metric("serve_ingress_ms")(ctx()) is None


@pytest.mark.parametrize("name", [
    "serve_batch_occupancy_pct", "serve_queue_wait_ms", "serve_ingress_ms",
    "serve_engine_host_ms_per_step", "serve_prefill_share_pct", "serve_compiles_in_window",
    "serve_prefill_ms_per_call", "serve_prefill_tokens_per_s", "serve_compile_ns_in_window"])
def test_none_on_a_program_from_before_the_counters(name):
    old = {"num_active": 3, "num_preemptions": 0}  # what the parent's metrics() has
    assert metric(name)(ctx(old, old)) is None
    assert metric(name)({"result": {}, "config": {"engine": {"max_num_seqs": 32}}}) is None


def test_a_missing_counter_under_minus_gives_none():
    before = {k: v for k, v in BEFORE.items() if k != "prefill_ns_total"}
    assert metric("serve_engine_host_ms_per_step")(ctx(before, AFTER)) is None


def test_sums_and_scale():
    got = counter_ratio.read(ctx(), numerator=["loop_admit_ns_total", "loop_grow_ns_total"],
                             denominator=["admitted_total", "decode_steps_total"], scale=2.0)
    assert got == pytest.approx(2.0 * 500_000_000 / 204)
