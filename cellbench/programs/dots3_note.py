"""The program's `LatentLM` at a configuration's published sizes, and the
plain names (weight_tables/dots3_note.py) of its leaves."""

from __future__ import annotations

# layer index -> (its kind's prefix, its place among the layers of its
# kind); set by `model`, which the task calls before it names a leaf.
_NTH = {}
_FIRST_DENSE = [1]

RENAMED = {"lm_head": "head", "w_gate": "dense_gate", "w_up": "dense_up",
           "w_down": "dense_down"}


def _kind(config: dict, pre: str):
    from tf_yarn_tpu.models.latent import AttentionSizes

    return AttentionSizes(
        n_heads=config[pre + "num_attention_heads"],
        q_rank=config[pre + "q_lora_rank"],
        kv_rank=config[pre + "kv_lora_rank"],
        d_nope=config[pre + "qk_nope_head_dim"],
        d_rope=config[pre + "qk_rope_head_dim"],
        d_v=config[pre + "v_head_dim"],
        rope_theta=float(config[pre + "rope_theta"]),
    )


def model(config: dict, context: int, overrides: dict):
    from tf_yarn_tpu.models.latent import SLIDING, LatentConfig, LatentLM

    # The file keeps the published list whole; its cut is the depth.
    kinds = tuple(config["layer_types"][:config["num_hidden_layers"]])
    told = {
        "attention_bias": False, "attention_gate_type": "headwise",
        "swa_attention_gate_type": "headwise", "hidden_act": "silu",
        "moe_layer_freq": 1, "n_shared_experts": 1, "rope_scaling": None,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "tie_word_embeddings": False,
        "num_key_value_heads": config["num_attention_heads"],
        "swa_num_key_value_heads": config["swa_num_attention_heads"],
    }
    for key, value in told.items():
        if config[key] != value:
            raise ValueError(f"{key}: the program has {value!r}, "
                             f"the file {config[key]!r}")
    seen = {}
    for index, kind in enumerate(kinds):
        pre = "swa_" if kind == SLIDING else ""
        _NTH[index] = (pre, seen.get(kind, 0))
        seen[kind] = _NTH[index][1] + 1
    _FIRST_DENSE[0] = config["first_k_dense_replace"]
    return LatentLM(LatentConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=kinds, max_seq_len=context,
        norm_eps=float(config["rms_norm_eps"]),
        full=_kind(config, ""), sliding=_kind(config, "swa_"),
        rescale_latents=bool(config["apply_mla_qkv_lora_rescale"]),
        window=config["sliding_window_size"],
        index_heads=config["index_n_heads"], index_dim=config["index_head_dim"],
        index_rope_dim=config["qk_rope_head_dim"],
        index_topk=config["index_topk"],
        first_dense=config["first_k_dense_replace"],
        d_ff_dense=config["intermediate_size"],
        num_experts=config["n_routed_experts"],
        num_experts_here=config["n_routed_experts_here"],
        expert_offset=int(config.get("routed_expert_offset", 0)),
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_intermediate_size"] * config["n_shared_experts"],
        norm_topk=bool(config["norm_topk_prob"]),
        routed_scale=float(config["routed_scaling_factor"]), **overrides,
    ))


def plain_name(path):
    """(plain name, index in that name's list or None) of a leaf of the
    program's tree, from its path: an attention's leaves are listed over the
    layers of its kind (the sliding kind's under `swa_`), a layer's norms
    over all layers, the dense ffn over the leading layers and the experts
    over the layers after them."""
    keys = [getattr(k, "key", str(k)) for k in path]
    layer = next((int(k.split("_")[1]) for k in keys
                  if k.startswith("layer_")), None)
    if layer is None:
        name = keys[-2] if keys[-1] == "scale" else keys[-1]
        return RENAMED.get(name, name), None
    module = keys[keys.index(f"layer_{layer}") + 1]
    if module in ("attn_norm", "ffn_norm"):
        return module, layer
    if module == "dense":
        return RENAMED[keys[-2]], layer
    if module == "moe":
        return keys[-1], layer - _FIRST_DENSE[0]
    pre, nth = _NTH[layer]
    if keys[-2] == "index_k_norm":
        return "index_k_" + keys[-1], nth
    leaf = keys[-2] if keys[-1] == "scale" else keys[-1]
    return pre + leaf, nth
