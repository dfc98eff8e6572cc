"""The program's `HybridLM` at a configuration's published sizes, and the
plain names (weight_tables/granite_hybrid.py) of its leaves."""

from __future__ import annotations

# layer index -> its place among the layers of its kind; set by `model`,
# which the task calls before it names a leaf.
_NTH = {}

RENAMED = {"norm": "gate_norm"}


def model(config: dict, context: int, overrides: dict):
    from tf_yarn_tpu.models.hybrid import HybridConfig, HybridLM

    # The file keeps the published list whole; its cut is the depth.
    kinds = tuple(config["layer_types"][:config["num_hidden_layers"]])
    told = {
        "num_hidden_layers": len(kinds), "position_embedding_type": "nope",
        "tie_word_embeddings": True, "mamba_n_groups": 1,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "attention_bias": False, "hidden_act": "silu",
        "normalization_function": "rmsnorm",
    }
    for key, value in told.items():
        if config[key] != value:
            raise ValueError(f"{key}: the program has {value!r}, "
                             f"the file {config[key]!r}")
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    if inner != config["mamba_expand"] * config["hidden_size"]:
        raise ValueError("mamba_expand x hidden_size != heads x d_head")
    seen = {}
    for index, kind in enumerate(kinds):
        _NTH[index] = seen.get(kind, 0)
        seen[kind] = _NTH[index] + 1
    return HybridLM(HybridConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=kinds, max_seq_len=context,
        norm_eps=float(config["rms_norm_eps"]),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        attention_multiplier=float(config["attention_multiplier"]),
        mamba_heads=config["mamba_n_heads"],
        mamba_head_dim=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk=config["mamba_chunk_size"],
        num_experts=config["num_local_experts"],
        num_experts_here=config["num_local_experts_here"],
        expert_offset=int(config.get("local_expert_offset", 0)),
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["intermediate_size"],
        d_shared=config["shared_intermediate_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]), **overrides,
    ))


def plain_name(path):
    """(plain name, index in that name's list or None) of a leaf of the
    program's tree, from its path: a mixer's leaves are listed over the
    layers of its kind, a layer's norms and experts over all layers."""
    keys = [getattr(k, "key", str(k)) for k in path]
    layer = next((int(k.split("_")[1]) for k in keys
                  if k.startswith("layer_")), None)
    if layer is None:
        return ("embedding" if keys[-1] == "embedding" else keys[-2]), None
    module = keys[keys.index(f"layer_{layer}") + 1]
    if module in ("mixer_norm", "moe_norm"):
        return module, layer
    if module == "moe":
        return keys[-1], layer
    if module == "attn":
        return keys[-2], _NTH[layer]
    return RENAMED.get(keys[-1], keys[-1]), _NTH[layer]
