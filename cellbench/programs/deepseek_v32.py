"""The program's `LatentLM` at a `deepseek_v32` configuration's published
sizes — every layer `full_attention` with the indexer, no gate and no
rescale, YaRN, group-limited sigmoid routing — and the plain names
(weight_tables/deepseek_v32.py) of its leaves."""

from __future__ import annotations

import math

_FIRST_DENSE = [1]

RENAMED = {"lm_head": "head", "w_gate": "dense_gate", "w_up": "dense_up",
           "w_down": "dense_down"}
# What the program implements, by key: a file that says otherwise is refused.
TOLD = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
        "n_shared_experts": 1, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "tie_word_embeddings": False,
        "model_type": "deepseek_v32"}


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def model(config: dict, context: int, overrides: dict):
    from tf_yarn_tpu.models.latent import (
        FULL, AttentionSizes, LatentConfig, LatentLM)
    from tf_yarn_tpu.models.transformer import RotaryRecipe

    told = dict(TOLD, num_key_value_heads=config["num_attention_heads"])
    for key, value in told.items():
        if config[key] != value:
            raise ValueError(f"{key}: the program has {value!r}, "
                             f"the file {config[key]!r}")
    scaling = config["rope_scaling"]
    if scaling["type"] != "yarn":
        raise ValueError(f"rope_scaling.type: the program has 'yarn', "
                         f"the file {scaling['type']!r}")
    if config["n_routed_experts"] % config["n_group"]:
        raise ValueError(f"n_group: {config['n_group']} groups do not divide "
                         f"{config['n_routed_experts']} experts")
    factor = float(scaling["factor"])
    d_rope = config["qk_rope_head_dim"]
    _FIRST_DENSE[0] = config["first_k_dense_replace"]
    return LatentLM(LatentConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=(FULL,) * config["num_hidden_layers"], max_seq_len=context,
        norm_eps=float(config["rms_norm_eps"]),
        full=AttentionSizes(
            n_heads=config["num_attention_heads"],
            q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
            d_nope=config["qk_nope_head_dim"], d_rope=d_rope,
            d_v=config["v_head_dim"], rope_theta=float(config["rope_theta"]),
            rotary=RotaryRecipe(
                float(config["rope_theta"]), d_rope, factor=factor,
                original_max=scaling["original_max_position_embeddings"],
                beta_fast=float(scaling["beta_fast"]),
                beta_slow=float(scaling["beta_slow"]),
                # cos and sin carry mscale / mscale_all_dim: 1 as published
                attention_factor=yarn_mscale(factor, scaling["mscale"])
                / yarn_mscale(factor, scaling["mscale_all_dim"])),
            mscale=yarn_mscale(factor, scaling["mscale_all_dim"])),
        rescale_latents=False, gated=(),
        index_heads=config["index_n_heads"], index_dim=config["index_head_dim"],
        index_rope_dim=d_rope, index_topk=config["index_topk"],
        first_dense=config["first_k_dense_replace"],
        d_ff_dense=config["intermediate_size"],
        num_experts=config["n_routed_experts"],
        num_experts_here=config["n_routed_experts_here"],
        expert_offset=int(config.get("routed_expert_offset", 0)),
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_intermediate_size"] * config["n_shared_experts"],
        norm_topk=bool(config["norm_topk_prob"]),
        routed_scale=float(config["routed_scaling_factor"]),
        n_group=config["n_group"], topk_group=config["topk_group"],
        **overrides,
    ))


def plain_name(path):
    """(plain name, index in that name's list or None) of a leaf of the
    program's tree, from its path: an attention's leaves and a layer's norms
    are listed over all layers, the dense ffn over the leading layers and
    the experts over the layers after them."""
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
    if keys[-2] == "index_k_norm":
        return "index_k_" + keys[-1], layer
    return (keys[-2] if keys[-1] == "scale" else keys[-1]), layer
