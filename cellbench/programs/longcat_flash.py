"""The program's `LongcatLM` at a configuration's published sizes, and the
plain names (weight_tables/longcat_flash.py) of its leaves."""

from __future__ import annotations

RENAMED = {"lm_head": "head", "w_gate": "dense_gate", "w_up": "dense_up",
           "w_down": "dense_down"}


def model(config: dict, context: int, overrides: dict):
    from tf_yarn_tpu.models.latent import PLAIN, AttentionSizes
    from tf_yarn_tpu.models.longcat import LongcatConfig, LongcatLM

    told = {"attention_bias": False, "attention_method": "MLA",
            "zero_expert_type": "identity",
            "mla_scale_kv_lora": config["mla_scale_q_lora"]}
    for key, value in told.items():
        if config[key] != value:
            raise ValueError(f"{key}: the program has {value!r}, "
                             f"the file {config[key]!r}")
    return LongcatLM(LongcatConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=(PLAIN,) * config["num_layers"], max_seq_len=context,
        norm_eps=float(config["rms_norm_eps"]),
        full=AttentionSizes(
            n_heads=config["num_attention_heads"],
            q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
            d_nope=config["qk_nope_head_dim"],
            d_rope=config["qk_rope_head_dim"], d_v=config["v_head_dim"],
            rope_theta=float(config["rope_theta"])),
        rescale_latents=bool(config["mla_scale_q_lora"]),
        d_ff_dense=config["ffn_hidden_size"],
        num_experts=config["n_routed_experts"],
        num_experts_here=config["n_routed_experts_here"],
        expert_offset=int(config.get("routed_expert_offset", 0)),
        num_zero_experts=config["zero_expert_num"],
        experts_per_token=config["moe_topk"],
        d_expert=config["expert_ffn_hidden_size"],
        routed_scale=float(config["routed_scaling_factor"]), **overrides,
    ))


def plain_name(path):
    """(plain name, index in that name's list or None) of a leaf of the
    program's tree, from its path: what a sublayer holds (`attn_<i>`,
    `attn_norm_<i>`, `ffn_norm_<i>`, `dense_<i>`) is listed two a layer, at
    `2 layer + i`; the expert branch's leaves one a layer."""
    keys = [getattr(k, "key", str(k)) for k in path]
    layer = next((int(k.split("_")[1]) for k in keys
                  if k.startswith("layer_")), None)
    if layer is None:
        name = keys[-2] if keys[-1] == "scale" else keys[-1]
        return RENAMED.get(name, name), None
    module = keys[keys.index(f"layer_{layer}") + 1]
    if module == "moe":
        return keys[-1], layer
    kind, sublayer = module.rsplit("_", 1)
    nth = 2 * layer + int(sublayer)
    if kind in ("attn_norm", "ffn_norm"):
        return kind, nth
    if kind == "dense":
        return RENAMED[keys[-2]], nth
    return (keys[-2] if keys[-1] == "scale" else keys[-1]), nth
