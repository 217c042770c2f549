"""The block-diffusion cell's yardstick by hand: `lib/flops_sdar_moe.py`'s counts at the cell's
size, and `drivers/train_diffusion.py`'s result against `drivers/train_family.py`'s on the
rehearsal. Run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Not part of the repository's tier-1 suite: it tests the yardstick, not the program."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import flops_sdar_moe as flops  # noqa: E402

# the cell's model group, by hand (configs/sdar-30b-a3b-train-ep8.json)
MODEL = dict(d_model=2048, n_heads=16, n_kv_heads=4, attn_head_dim=128, attn_heads_held=(32, 4), n_layers=5,
             vocab_size=18992, n_experts=128, moe_top_k=8, experts_held=(0, 8), d_ff_expert=768, diffusion_block=4)


def test_a_training_token_by_hand():
    projections = 2 * 2048 * (32 * 128 + 4 * 128 + 4 * 128 + 32 * 128)  # q, k, v, o: 37.75 MFLOP a ROW
    router, routed = 2 * 2048 * 128, 2 * 3 * 2048 * 768 * 8 / 8  # a row's 8 assignments, an eighth of them here
    assert projections + router + routed == pytest.approx(47.71e6, rel=1e-3)
    core = 4 * 128 * 32 * (8192 + 4)  # scores and weighted values over the keys a token's TWO rows keep
    assert core == pytest.approx(134.3e6, rel=1e-3)
    head = 2 * 2048 * 18992
    layer = 2 * (projections + router + routed) + core  # both rows through every product; the core counted once
    assert flops.train_flops_per_token(MODEL, 8192) == 3 * (5 * layer + head) == pytest.approx(3.679e9, rel=1e-3)
    assert flops.train_flops_per_token({**MODEL, "n_layers": 6}, 8192) == pytest.approx(4.37e9, rel=1e-3)  # ISSUE 50's six layers
    assert flops.train_flops_per_token(MODEL, 8192) * 8192 == pytest.approx(30.14e12, rel=1e-3)  # a step
    assert set(flops.forward_flops_per_token(MODEL, 4096.5)) == {"attention", "experts", "head"}
    assert flops.grouped_products_flops(MODEL, 1000) == 3 * 2 * 1000 * 3 * 2048 * 768


def test_the_attention_cores_need_is_the_kept_scores_and_the_kernels_walk_288_tiles_for_256():
    from ray_tpu.ops.flash_attention import tile_counts

    work = flops.block_diffusion_attention_step_work(MODEL, 8192, 8192)
    kept = 8192 * (8192 + 4)  # a sequence's scores under the mask: every clean key up to the block's end, once noised, once clean
    assert work["flops"] == 5 * 32 * 6 * 2 * 128 * kept == pytest.approx(16.50e12, rel=1e-3)
    assert work["bytes"] == 5 * 6 * 2 * 16384 * 128 * (32 + 4)  # q, o, dO, dq and k, v, dk, dv over 16,384 rows
    for kernel in ("fwd", "dq"):
        counts = tile_counts(16384, 16384, False, 512, 512, kernel=kernel, block_diffusion=4)
        assert counts.tiles_computed == 288 == 2 * 136 + 16 and counts.tiles_needed == kept / 512**2 == pytest.approx(256.1, abs=0.05)
    assert tile_counts(16384, 16384, False, 512, 512, n_rep=8, kernel="dkv", block_diffusion=4).tiles_computed == 8 * 288
    assert tile_counts(16384, 16384, True, 512, 512).tiles_computed == 528  # the triangle over the doubled row


_RESULTS = """
import json, sys
sys.path.insert(0, {root!r})
from benchmarks import run
if __name__ == "__main__":
    import importlib
    out = {{}}
    for cell in ("sdar30b-train-ep8share-s8192", "trinitymini-train-ep16share-s16384"):
        ctx = run.make_context(cell, 3000000007, 2.0, True, True)
        ctx["log"] = lambda line: None
        result = importlib.import_module("benchmarks.drivers." + ctx["cell"]["driver"]).run(ctx)
        out[cell] = {{"driver": ctx["cell"]["driver"], "keys": sorted(result),
                      "nested": {{k: sorted(result[k]) for k in ("end_to_end", "device", "trace")}},
                      "series": sorted(result["series"]), "tokens_per_step": result["tokens_per_step"],
                      "seq": result["seq"], "correct": result["correct"]}}
    print("RESULTS " + json.dumps(out))
"""


def test_the_diffusion_drivers_result_has_the_family_drivers_keys(tmp_path):
    """Both drivers on their rehearsals, traced, in one child process: the result a reader is
    handed has the same keys, so that every existing reader works on the new driver's unchanged."""
    script = tmp_path / "results.py"
    script.write_text(_RESULTS.format(root=ROOT))
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="1")
    out = subprocess.run([sys.executable, str(script)], cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(next(ln for ln in out.stdout.splitlines() if ln.startswith("RESULTS "))[8:])
    mine, theirs = got["sdar30b-train-ep8share-s8192"], got["trinitymini-train-ep16share-s16384"]
    assert (mine["driver"], theirs["driver"]) == ("train_diffusion", "train_family")
    assert mine["keys"] == theirs["keys"] and mine["nested"] == theirs["nested"]
    assert {"end_to_end", "device", "correct", "series", "tokens_per_step", "seq", "chips", "traced_steps", "trace"} <= set(mine["keys"])
    assert "op_scopes" in mine["nested"]["trace"]
    assert set(mine["series"]) == {"step_s", "held_assignments", "fullest_held_expert_rows", "masked_tokens"}
    assert set(theirs["series"]) == {"step_s", "held_assignments", "fullest_held_expert_rows"}
    assert mine["tokens_per_step"] == 2 * 64 == mine["seq"] * 2  # training tokens: batch x seq, not the 2 x as many rows
    assert mine["correct"] and theirs["correct"]
