"""Tests of the benchmark's own arithmetic. Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Not part of the repository's tier-1 suite: they test the yardstick, not the program.
"""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.lib import flops, loadgen, modelcfg, reference, trace_reduce, wordtok  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
DENSE = dict(d_model=4096, n_heads=32, n_kv_heads=8, d_ff=14336, vocab_size=32768, n_layers=2)
MOE = dict(DENSE, vocab_size=32000, n_experts=8, moe_top_k=2, n_layers=1)


def test_flops_dense_layer_by_hand():
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096; gate, up, down 4096x14336 each
    attn = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096
    mlp = 3 * 4096 * 14336
    assert flops.layer_matmul_params(DENSE) == {"attention_projections": attn, "mlp": mlp}
    assert attn + mlp == 218_103_808
    # one token attending to 1024.5 positions on average (causal, s = 2048):
    # scores and values are 2 x 2 x 4096 operations a position
    forward = 2 * (2 * 218_103_808 + 4 * 4096 * 1024.5) + 2 * 4096 * 32768
    assert flops.forward_flops_per_token(DENSE, 1024.5) == forward
    assert flops.train_flops_per_token(DENSE, 2048) == 3 * forward
    assert 3.4e9 < 3 * forward < 3.6e9


def test_flops_moe_layer_by_hand():
    # a token meets 2 of 8 experts and the 4096x8 router
    mlp = 2 * 3 * 4096 * 14336 + 4096 * 8
    assert flops.layer_matmul_params(MOE)["mlp"] == mlp
    # but the chip holds all 8: 8 x 176.2 M + attention 41.9 M + router, in bf16
    layer = 8 * 3 * 4096 * 14336 + 4096 * 8 + 41_943_040 + 2 * 4096
    emb = 2 * 32000 * 4096 + 4096
    assert flops.weight_bytes(MOE, 2) == 2 * (layer + emb)
    assert flops.kv_bytes_per_token(DENSE) == 2 * 2 * 8 * 128 * 2


def test_peaks_known_and_unknown_kind():
    assert flops.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks_for("TPU v9 imaginary")


def test_trace_reduce_by_hand():
    # one device; times in ns. A while loop [0, 100] wraps a fusion [0, 40] and
    # an all-gather [40, 60]; a copy [50, 70] overlaps the all-gather's second
    # half; then nothing until a fusion [90, 100]; a second, idle-bounded op at [150, 200].
    ops = [(0, 100, "%while.1 = (f32[2]) while(f32[2] %x), body=%b"),
           (0, 40, "%fusion.1 = f32[8]{0} fusion(f32[8] %p), kind=kLoop"),
           (40, 20, "%all-gather.1 = f32[16]{0} all-gather(f32[8] %fusion.1)"),
           (50, 20, "%copy.1 = f32[16]{0} copy(f32[16] %q)"),
           (90, 10, "%fusion.2 = f32[8]{0} fusion(f32[8] %p), kind=kLoop"),
           (150, 50, "%fusion.1 = f32[8]{0} fusion(f32[8] %p), kind=kLoop")]
    host = [(95, 60, "dispatch_and_wait")]
    r = trace_reduce.reduce({"/device:TPU:0": {"XLA Ops": ops}, "/host:CPU": {"python": host}}, 1)
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx(150e-9)          # [0, 100] and [150, 200]
    assert r["idle_share"] == pytest.approx(0.25)
    # the wrapper is left out of the sum by name; fusion.1 ran twice
    assert "%while.1 = (f32[2]) while(f32[2] %x), body=%b" not in r["op_seconds"]
    assert r["top_ops"][0][0].startswith("fusion.1 [fusion]")
    assert r["top_ops"][0][1] == pytest.approx(90e-9)
    assert r["collective_s"] == pytest.approx(20e-9)
    assert r["collective_exposed_s"] == pytest.approx(10e-9)   # [40, 50]: the copy hides the rest
    assert r["idle_gaps"][0][0].startswith("host: dispatch_and_wait")
    assert r["idle_gaps"][0][1] == pytest.approx(50e-9)
    # serving: the profiler recorded for 400 ns, and the device had no more to do
    whole = trace_reduce.reduce({"/device:TPU:0": {"XLA Ops": ops}}, 1, min_window_s=400e-9)
    assert whole["window_s"] == pytest.approx(400e-9) and whole["busy_s"] == pytest.approx(150e-9)
    assert whole["idle_share"] == pytest.approx(1 - 150 / 400)


def test_trace_reduce_on_the_recorded_trace():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        planes = json.load(f)["planes"]
    r = trace_reduce.reduce(planes, 1)
    events = planes["/device:TPU:0"]["XLA Ops"]
    # the union, another way: sweep over sorted edges
    edges = sorted([(s, 1) for s, d, _ in events] + [(s + d, -1) for s, d, _ in events])
    depth, busy, last = 0, 0.0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert 0.36 < r["busy_s"] < r["window_s"] < 0.38      # one 372 ms step
    assert 0.0 < r["idle_share"] < 0.02
    assert sum(r["op_seconds"].values()) <= r["busy_s"]   # no nanosecond counted twice
    assert sum(r["op_seconds"].values()) > 0.97 * r["busy_s"]
    kernels = sum(s for n, s in r["op_seconds"].items() if 'custom_call_target="tpu_custom_call"' in n)
    assert 0.08 < kernels / r["busy_s"] < 0.13            # flash attention, forward twice (remat) and backward
    assert len(r["top_ops"]) == 10 and r["top_ops"][0][1] >= r["top_ops"][-1][1]


def test_wordtok_round_trip(tmp_path):
    from ray_tpu.llm.server import render_chat_template
    from ray_tpu.llm.tokenizer import get_tokenizer

    vocab = 1000
    tok = get_tokenizer("hf:" + wordtok.write(str(tmp_path / "tok"), vocab))
    assert tok.vocab_size == vocab and tok.eos_token_id == wordtok.EOS_ID
    ids = [5, 77, 10, 100, 999, 0]
    text = tok.decode(ids)
    assert text.split() == [wordtok.word(i) for i in ids]       # N ids -> N words
    assert tok.encode(text) == ids                               # N words -> N ids
    every = tok.decode(list(range(vocab))).split()
    assert len(every) == vocab - 1                               # all but end-of-sequence
    assert tok.decode([3, wordtok.EOS_ID, 4]) == "w3 w4"
    prompt = wordtok.prompt_words(np.random.default_rng(0), 300, vocab)
    chat = tok.encode(render_chat_template([{"role": "user", "content": prompt}]))
    assert len(chat) == 302 and chat[0] == chat[-1] == wordtok.UNKNOWN_ID
    # streamed deltas of the re-decoded text are one word a token
    assert [len(tok.decode(ids[:n]).split()) for n in range(1, 6)] == [1, 2, 3, 4, 5]


def test_open_schedule_same_seed_same_schedule_and_same_work_for_every_order():
    a = loadgen.open_schedule(7, 30.0, 4.0, [126, 1022], [32, 256])
    b = loadgen.open_schedule(7, 30.0, 4.0, [126, 1022], [32, 256])
    c = loadgen.open_schedule(2**31 + 11, 30.0, 4.0, [126, 1022], [32, 256])
    assert a == b and a != c and len(a) == 120
    for key in ("prompt_len", "max_tokens"):
        assert sorted(i[key] for i in a) == sorted(i[key] for i in c)
    assert min(i["prompt_len"] for i in a) >= 126 and max(i["prompt_len"] for i in a) <= 1022
    gaps = lambda s: sorted(np.round(np.diff([i["due_s"] for i in s]), 9))  # noqa: E731
    assert a[0]["due_s"] == 0.0 and 25.0 < a[-1]["due_s"] < 30.0
    assert len(set(np.round(gaps(a), 6))) > 100                  # exponential gaps, not a metronome


def test_summarize_reports_lateness_and_times_from_due():
    run = {"w0": 100.0, "w1": 110.0, "attempted": 3, "unfinished": 0, "drain_s": 0.5}
    rec = lambda due, sent, frames, n, **kw: dict(  # noqa: E731
        due=due, sent=sent, frames=frames, words=sum(w for _, w in frames), max_tokens=n,
        finish="length", error=None, cut=False, **kw)
    records = [rec(100.0, 100.002, [(100.5, 1), (100.6, 1), (100.7, 1)], 3),
               rec(101.0, 101.3, [(101.9, 1), (109.9, 1), (110.4, 1)], 3),   # sent 300 ms late
               dict(rec(102.0, 102.0, [], 3), error="http 500")]
    s = loadgen.summarize(records, run)
    assert s["failed"] == 1 and s["attempted"] == 3 and s["completed"] == 2
    assert s["tokens_in_window"] == 5                            # the frame at 110.4 is after the window
    assert s["generator_late_max_ms"] == pytest.approx(300.0)
    assert s["ttft_p50_ms"] == pytest.approx(700.0)              # from DUE: (500 + 900) / 2
    assert s["tpot_p50_ms"] == pytest.approx((100.0 + 4250.0) / 2)


def test_summarize_counts_a_request_without_a_first_token_as_starved_not_as_a_latency():
    run = {"w0": 0.0, "w1": 30.0, "attempted": 3, "unfinished": 0, "drain_s": 0.0}
    rec = lambda sent, frames, cut: dict(  # noqa: E731
        due=sent, sent=sent, frames=frames, words=sum(w for _, w in frames), max_tokens=4,
        finish=None, error=None, cut=cut)
    s = loadgen.summarize([rec(0.0, [(0.2, 1), (0.3, 1)], True), rec(0.0, [(0.4, 1), (0.6, 1)], True),
                           rec(0.0, [], True)], run)
    assert s["starved"] == 1 and s["ttft_samples"] == 2 and s["failed"] == 0
    assert s["ttft_p90_ms"] < 500.0                              # not the window's 30 s


def _manifests():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        yield "BENCHMARK.json", json.load(f)
    with open(os.path.join(BENCH, "proposed.json")) as f:
        yield "proposed.json", json.load(f)


@pytest.mark.parametrize("name,manifest", list(_manifests()))
def test_manifest_agrees_with_the_files_it_names(name, manifest):
    load = lambda *parts: json.load(open(os.path.join(BENCH, *parts)))  # noqa: E731
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        cell = load("workloads", w["name"] + ".json")
        assert {k: cell[k] for k in ("config", "traffic", "chips", "why")} == \
            {k: w[k] for k in ("config", "traffic", "chips", "why")}
        assert os.path.exists(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
        assert os.path.exists(os.path.join(BENCH, "rehearsal", w["config"] + ".json"))
        config = load("configs", w["config"] + ".json")
        entry = configs[w["config"]]
        assert config["source"] == entry["source"] and config["reduced"] == entry["reduced"]
        assert entry["file"] == f"benchmarks/configs/{w['config']}.json"
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
        reader = load("metrics", m["name"] + ".json")["reader"]
        assert os.path.exists(os.path.join(BENCH, "readers", reader + ".py"))
        # a cell that reports the metric reports the end-to-end metric it moves
        moved = next(e for e in manifest["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))


def _tiny(n_experts):
    published = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
                 "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 128,
                 "max_position_embeddings": 64, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
                 "tie_word_embeddings": False, "program": {"dtype": "float32"}}
    if n_experts:
        published.update(num_local_experts=n_experts, num_experts_per_tok=2)
        published["program"]["moe_capacity_factor"] = n_experts / 2  # dropless
    return modelcfg.model_keys(published)


@pytest.mark.parametrize("n_experts", [0, 4])
def test_reference_agrees_with_the_program_in_float32(n_experts):
    """The plain reference against models/llama.py at a toy size, dense and
    mixture of experts (the program at the dropless capacity E/k): the two
    share no code, so agreement to float32 rounding says both are the model."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    keys = _tiny(n_experts)
    cfg = modelcfg.model_config(keys)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 128, (2, 33)), jnp.int32)
    ours = reference.forward(params, tokens, keys)
    theirs = llama.forward(params, tokens, cfg)[0]
    assert float(jnp.abs(ours - theirs).max()) < 2e-4 * float(jnp.abs(ours).max())
    # and the bfloat16 yardstick is the same code, coarser, by about bfloat16's step
    coarse = reference.next_token_losses(params, tokens, keys, jnp.bfloat16)
    exact = reference.next_token_losses(params, tokens, keys)
    rms = float(jnp.sqrt(jnp.mean(jnp.square(coarse - exact))))
    assert 1e-4 < rms < 1e-1
