"""From a configuration file to the program's model settings.

A configuration file carries the model's sizes under the keys of its published
`config.json`, so that what was kept and what was cut can be read against the
source. The program's ModelConfig names them differently; this is the one
mapping. The optional `program` group of the file holds settings the source
does not have (remat policy, activation type, the expert capacity factor).
"""

PUBLISHED_TO_PROGRAM = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "moe_top_k",
}


def model_keys(config: dict) -> dict:
    """The program's ModelConfig fields, as a plain dict (no ray_tpu import:
    flops.py and reference.py read the same dict)."""
    keys = {ours: config[theirs] for theirs, ours in PUBLISHED_TO_PROGRAM.items()
            if theirs in config}
    keys.update(config.get("program", {}))
    keys.setdefault("name", config.get("name", "benchmark-model"))
    head_dim = config.get("head_dim")
    if head_dim is not None and head_dim * keys["n_heads"] != keys["d_model"]:
        raise ValueError("the program derives head_dim as d_model / n_heads; "
                         f"the file says {head_dim}")
    return keys


def model_config(keys: dict):
    """The program's ModelConfig (imports ray_tpu; call where jax may load)."""
    from ray_tpu.models.config import ModelConfig

    return ModelConfig(**keys)
