"""HF safetensors checkpoint IO (models/checkpoint.py).

The transformers cross-check is the load-bearing test: it proves the weight
mapping matches the real HF Llama convention (not just our own round-trip).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import checkpoint as ckpt_io
from ray_tpu.models import llama
from ray_tpu.models.config import ModelConfig

TINY = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=48, max_seq_len=128, remat=False, dtype="float32")


def _cfg(**kw):
    return ModelConfig(name="tiny-ckpt", **{**TINY, **kw})


def test_roundtrip_exact(tmp_path):
    cfg = _cfg()
    params = llama.init(jax.random.PRNGKey(0), cfg)
    ckpt_io.save_llama_params(params, cfg, str(tmp_path / "ckpt"))
    # cfg comes from the written config.json, not passed in
    loaded = ckpt_io.load_llama_params(str(tmp_path / "ckpt"), param_dtype=jnp.float32)
    flat_a = jax.tree.leaves(params)
    flat_b = jax.tree.leaves(loaded)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _saved_tensors(ckpt_dir):
    from safetensors.numpy import load_file

    out = {}
    for name in os.listdir(ckpt_dir):
        if name.endswith(".safetensors"):
            out.update(load_file(os.path.join(ckpt_dir, name)))
    return out


def test_roundtrip_layers_by_hf_name(tmp_path):
    """Row i of every stacked leaf is saved under HF's `model.layers.{i}.` names
    (and read back from them): a passed cfg, distinct values per layer."""
    cfg = _cfg()
    params = llama.init(jax.random.PRNGKey(1), cfg)
    ckpt_io.save_llama_params(params, cfg, str(tmp_path / "ckpt"))
    saved = _saved_tensors(str(tmp_path / "ckpt"))
    d, ly = cfg.d_model, params["layers"]
    for i in range(cfg.n_layers):
        pre = f"model.layers.{i}."
        for hf, want in (
            ("input_layernorm.weight", ly["attn_norm"][i]),
            ("self_attn.q_proj.weight", ly["wq"][i].reshape(d, -1).T),
            ("self_attn.k_proj.weight", ly["wk"][i].reshape(d, -1).T),
            ("self_attn.v_proj.weight", ly["wv"][i].reshape(d, -1).T),
            ("self_attn.o_proj.weight", ly["wo"][i].reshape(-1, d).T),
            ("mlp.gate_proj.weight", ly["w_gate"][i].T),
            ("mlp.up_proj.weight", ly["w_up"][i].T),
            ("mlp.down_proj.weight", ly["w_down"][i].T),
        ):
            np.testing.assert_array_equal(saved[pre + hf], np.asarray(want))
    loaded = ckpt_io.load_llama_params(
        str(tmp_path / "ckpt"), cfg=cfg, param_dtype=jnp.float32)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_load_sharded_tp2(tmp_path):
    from jax.sharding import Mesh

    cfg = _cfg()
    params = llama.init(jax.random.PRNGKey(2), cfg)
    ckpt_io.save_llama_params(params, cfg, str(tmp_path / "ckpt"))
    devs = np.asarray(jax.devices()[:2]).reshape(1, 1, 2)
    mesh = Mesh(devs, ("dp", "ep", "tp"))
    loaded = ckpt_io.load_llama_params(
        str(tmp_path / "ckpt"), mesh=mesh, param_dtype=jnp.float32)
    # wq is sharded over tp on the heads axis
    wq = loaded["layers"]["wq"]
    assert len(wq.sharding.device_set) == 2
    tokens = jnp.asarray([[1, 5, 9, 3]], jnp.int32)
    ref_logits, _ = llama.forward(params, tokens, cfg)
    got_logits, _ = llama.forward(loaded, tokens, cfg)
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)


def test_hf_transformers_parity(tmp_path):
    """Weights exported by the REAL transformers LlamaForCausalLM load into our
    pytree and reproduce its logits — proves the mapping, not just a roundtrip."""
    torch = pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")

    hf_cfg = tr.LlamaConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=48,
        max_position_embeddings=128, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False, attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = tr.LlamaForCausalLM(hf_cfg).eval()
    src = str(tmp_path / "hf")
    model.save_pretrained(src, safe_serialization=True)

    cfg = ckpt_io.config_from_hf(src, remat=False, dtype="float32")
    assert cfg.n_layers == 2 and cfg.n_kv_heads == 2 and cfg.rope_theta == 10000.0
    params = ckpt_io.load_llama_params(src, cfg, param_dtype=jnp.float32)

    ids = [[1, 7, 23, 40, 5, 61]]
    with torch.no_grad():
        ref = model(torch.tensor(ids)).logits.numpy()
    got, _ = llama.forward(params, jnp.asarray(ids, jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(got, np.float32), ref, rtol=2e-3, atol=2e-3)


def test_engine_loads_checkpoint_deterministic_tokens(tmp_path, rt):
    """End-to-end VERDICT bar: tiny real safetensors checkpoint -> tp=2 mesh ->
    deterministic greedy tokens, identical to an engine fed the params directly."""
    from ray_tpu.llm import JaxLLMEngine, LLMConfig, SamplingParams

    cfg = _cfg(dtype="float32")
    params = llama.init(jax.random.PRNGKey(3), cfg)
    src = str(tmp_path / "ckpt")
    ckpt_io.save_llama_params(params, cfg, src)

    def greedy(engine):
        engine.start()
        out = engine.generate_sync(
            "hello tpu", SamplingParams(max_tokens=8, temperature=0.0,
                                        stop_token_ids=[-1]))
        return out.token_ids

    common = dict(max_num_seqs=2, max_model_len=64, dtype="float32",
                  tensor_parallel_size=2)
    from_ckpt = greedy(JaxLLMEngine(LLMConfig(model_source=src, **common)))
    from_params = greedy(JaxLLMEngine(
        LLMConfig(model_source=cfg, **common), params=params))
    assert from_ckpt == from_params
    assert len(from_ckpt) == 8
    # determinism across a fresh engine on the same checkpoint
    again = greedy(JaxLLMEngine(LLMConfig(model_source=src, **common)))
    assert again == from_ckpt


def test_sharded_index_file(tmp_path):
    """Checkpoints split across N safetensors files load via the index."""
    from safetensors.numpy import save_file

    cfg = _cfg()
    params = llama.init(jax.random.PRNGKey(4), cfg)
    src = str(tmp_path / "one")
    ckpt_io.save_llama_params(params, cfg, src)
    # re-split the single file into two + an index
    from safetensors import safe_open

    dst = str(tmp_path / "split")
    os.makedirs(dst)
    with safe_open(os.path.join(src, "model.safetensors"), framework="numpy") as h:
        keys = sorted(h.keys())
        half = len(keys) // 2
        parts = [keys[:half], keys[half:]]
        weight_map = {}
        for n, part in enumerate(parts, start=1):
            fname = f"model-{n:05d}-of-00002.safetensors"
            save_file({k: h.get_tensor(k) for k in part}, os.path.join(dst, fname))
            weight_map.update({k: fname for k in part})
    with open(os.path.join(dst, "model.safetensors.index.json"), "w") as f:
        json.dump({"weight_map": weight_map}, f)
    with open(os.path.join(dst, "config.json"), "w") as f:
        json.dump(ckpt_io.config_to_hf(cfg), f)
    loaded = ckpt_io.load_llama_params(dst, param_dtype=jnp.float32)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_init_state_from_checkpoint(tmp_path):
    from ray_tpu.train.step import init_state, make_optimizer

    cfg = _cfg()
    params = llama.init(jax.random.PRNGKey(5), cfg)
    src = str(tmp_path / "ckpt")
    ckpt_io.save_llama_params(params, cfg, src)
    state = init_state(jax.random.PRNGKey(0), cfg, make_optimizer(),
                       checkpoint_dir=src)
    np.testing.assert_array_equal(np.asarray(state.params["embed"]),
                                  np.asarray(params["embed"]))
    assert state.opt_state is not None


# ------------------------------------------------------------------------- MoE

MOE_TINY = dict(**TINY, n_experts=4, moe_top_k=2)


def test_moe_roundtrip_exact(tmp_path):
    """Mixtral-layout MoE checkpoints round-trip (router + per-expert w1/w2/w3),
    and config.json carries num_local_experts/num_experts_per_tok."""
    cfg = _cfg(**MOE_TINY)
    params = llama.init(jax.random.PRNGKey(6), cfg)
    src = str(tmp_path / "ckpt")
    ckpt_io.save_llama_params(params, cfg, src)
    with open(os.path.join(src, "config.json")) as f:
        hf = json.load(f)
    assert hf["model_type"] == "mixtral"
    assert hf["num_local_experts"] == 4 and hf["num_experts_per_tok"] == 2
    # trained dispatch semantics survive the round-trip (extension keys beat
    # the dropless mixtral defaults)
    re_cfg = ckpt_io.config_from_hf(src)
    assert re_cfg.moe_capacity_factor == cfg.moe_capacity_factor
    assert re_cfg.moe_top1_renorm == cfg.moe_top1_renorm
    # cfg reconstructed from config.json, not passed in
    loaded = ckpt_io.load_llama_params(src, param_dtype=jnp.float32)
    flat_a = jax.tree.leaves(params)
    flat_b = jax.tree.leaves(loaded)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moe_roundtrip_layers_by_hf_name(tmp_path):
    cfg = _cfg(**MOE_TINY)
    params = llama.init(jax.random.PRNGKey(7), cfg)
    src = str(tmp_path / "ckpt")
    ckpt_io.save_llama_params(params, cfg, src)
    saved = _saved_tensors(src)
    ly = params["layers"]
    for i in range(cfg.n_layers):
        moe_pre = f"model.layers.{i}.block_sparse_moe."
        np.testing.assert_array_equal(saved[moe_pre + "gate.weight"],
                                      np.asarray(ly["router"][i].T))
        for j in range(cfg.n_experts):
            for hf, field in (("w1", "w_gate"), ("w3", "w_up"), ("w2", "w_down")):
                np.testing.assert_array_equal(
                    saved[moe_pre + f"experts.{j}.{hf}.weight"],
                    np.asarray(ly[field][i, j].T))
    loaded = ckpt_io.load_llama_params(src, cfg=cfg, param_dtype=jnp.float32)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("top_k", [1, 2])
def test_hf_mixtral_parity(tmp_path, top_k):
    """Weights exported by the REAL transformers MixtralForCausalLM load into our
    MoE pytree and reproduce its logits WITH DEFAULT load options. Gating parity:
    softmax over all E + top-k renormalization equals Mixtral's softmax over the
    top-k logits (the normalizer cancels); k=1 exercises moe_top1_renorm (the
    Switch convention would underweight every MLP output). Dropless capacity
    (factor E/k) is the config_from_hf default for mixtral checkpoints — no
    override needed, matching how the engine loads a real model dir."""
    torch = pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")

    hf_cfg = tr.MixtralConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=48,
        num_local_experts=4, num_experts_per_tok=top_k,
        max_position_embeddings=128, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False, sliding_window=None,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = tr.MixtralForCausalLM(hf_cfg).eval()
    src = str(tmp_path / "hf")
    model.save_pretrained(src, safe_serialization=True)

    cfg = ckpt_io.config_from_hf(src, remat=False, dtype="float32")
    assert cfg.n_experts == 4 and cfg.moe_top_k == top_k
    assert cfg.moe_top1_renorm and cfg.moe_capacity_factor == 4.0 / top_k
    params = ckpt_io.load_llama_params(src, cfg, param_dtype=jnp.float32)

    ids = [[1, 7, 23, 40, 5, 61]]
    with torch.no_grad():
        ref = model(torch.tensor(ids)).logits.numpy()
    got, _ = llama.forward(params, jnp.asarray(ids, jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(got, np.float32), ref, rtol=2e-3, atol=2e-3)
