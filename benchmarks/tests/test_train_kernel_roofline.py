"""`readers/train_kernel_roofline.py` by hand. Run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Not part of the repository's tier-1 suite: it tests the yardstick, not the program."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.lib import flops_lfm2_moe  # noqa: E402
from benchmarks.readers import train_kernel_roofline  # noqa: E402

MODEL = dict(d_model=2048, n_heads=32, n_kv_heads=8, d_ff=11776, d_ff_expert=1536, n_experts=64, moe_top_k=4,
             experts_held=(0, 8), layer_pattern="C-*ECECECE", vocab_size=8192)
PATTERN = r"^%?\w*flash_attention_"


def _ctx(ops, flops="flops_lfm2_moe", rehearse=False, traced_steps=5):
    result = {"traced_steps": traced_steps, "tokens_per_step": 32768, "seq": 8192, "chips": 1,
              "device": {"kind": "TPU v5 lite"}, "trace": {"busy_s": 3.0, "op_seconds": ops}}
    return {"result": result, "config": {"trainer": {"flops": flops}}, "model": MODEL, "rehearse": rehearse}


OPS = {"%flash_attention_fwd.2 = (bf16[4,32,8192,128]) custom-call(...)": 0.09,
       "%jvp_flash_attention_fwd_.3 = (bf16[4,32,8192,128]) custom-call(...)": 0.09,
       "%transpose_jvp_flash_attention_bwd_dq__.1 = bf16[4,32,8192,128] custom-call(...)": 0.12,
       "%flash_attention_bwd_dkv.1 = (bf16[4,8,8192,128]) custom-call(...)": 0.14,
       "%fusion.7 = bf16[4,8192,2048] fusion(...)": 2.0,
       "%rope_fwd.1 = bf16[4] custom-call(...)": 0.5}


def test_needed_seconds_at_the_peaks_over_the_kernels_traced_seconds():
    work = flops_lfm2_moe.attention_step_work(MODEL, 32768, 8192)
    # six products of the causal half, 32 heads of 64: 3.30 TFLOP = 16.7 ms at 197 TFLOP/s;
    # q, k, v, o, dO read and dq, dk, dv written: 1.0 GB = 1.2 ms at 819 GB/s: compute-bound
    assert work["flops"] == pytest.approx(3.2989e12, rel=1e-4) and work["bytes"] == 1_006_632_960
    needed = work["flops"] / 197e12
    assert needed > work["bytes"] / 819e9
    got = train_kernel_roofline.read(_ctx(OPS), PATTERN, "attention_step_work")
    assert got == pytest.approx(100 * 5 * needed / 0.44) and 18 < got < 20
    # a bytes-bound work function is measured against the bandwidth
    thin = lambda model, tokens, seq: {"flops": 1.0, "bytes": 819e9}  # noqa: E731  one second of HBM
    flops_lfm2_moe.thin_work = thin
    try:
        assert train_kernel_roofline.read(_ctx(OPS), PATTERN, "thin_work") == pytest.approx(100 * 5 / 0.44)
    finally:
        del flops_lfm2_moe.thin_work


@pytest.mark.parametrize("ctx,work", [
    (_ctx({"%fusion.7 = bf16[4] fusion(...)": 2.0}), "attention_step_work"),  # no such kernel: the XLA path
    (_ctx(OPS), "no_such_function"),
    (_ctx(OPS, flops="flops_solar_open2"), "attention_step_work"),  # a family whose file lacks it
    (_ctx(OPS, flops=None), "attention_step_work"),
    (_ctx(OPS, rehearse=True), "attention_step_work"),
    (_ctx(OPS, traced_steps=None), "attention_step_work"),
])
def test_nothing_to_read_is_none_and_raises_nothing(ctx, work):
    assert train_kernel_roofline.read(ctx, PATTERN, work) is None


def test_no_trace_is_none():
    ctx = _ctx(OPS)
    ctx["result"]["trace"] = None
    assert train_kernel_roofline.read(ctx, PATTERN, "attention_step_work") is None
