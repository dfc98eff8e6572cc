"""The program's `Transformer` at a configuration's published sizes, and the
plain names (weight_tables/llama.py) of its leaves."""

from __future__ import annotations


def model(config: dict, context: int, overrides: dict):
    """The program's model at the file's sizes. What the file does not name
    stays at the program's default."""
    from tf_yarn_tpu.models.transformer import Transformer, TransformerConfig

    if config["num_attention_heads"] * config["head_dim"] != config["hidden_size"]:
        raise ValueError("the program derives head_dim from hidden_size")
    return Transformer(TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=context,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), **overrides,
    ))


def plain_name(path):
    """(plain name, index in that name's list or None) of a leaf of the
    program's tree, from its path."""
    keys = [getattr(k, "key", str(k)) for k in path]
    layer = next((int(k.split("_")[1]) for k in keys
                  if k.startswith("layer_")), None)
    name = keys[-1] if keys[-1] in ("embedding", "lm_head") else keys[-2]
    return name, layer
