"""The program's `LagunaLM` at a configuration's published sizes, and the
plain names (weight_tables/laguna.py) of its leaves."""

from __future__ import annotations

# layer index -> (its attention kind's prefix, its place among the layers of
# that kind, its place among the layers of its feed-forward's kind); set by
# `model`, which the task calls before it names a leaf.
_NTH = {}

RENAMED = {"lm_head": "head", "wq": "q", "wk": "k", "wv": "v", "wg": "gate",
           "wo": "o", "w_gate": "dense_gate", "w_up": "dense_up",
           "w_down": "dense_down"}


def _recipe(config: dict, kind: str):
    from tf_yarn_tpu.models.laguna import RotaryRecipe

    told = config["rope_parameters"][kind]
    n = int(round(told["partial_rotary_factor"] * config["head_dim"]))
    if told["rope_type"] == "default":
        return RotaryRecipe(float(told["rope_theta"]), n)
    if told["rope_type"] != "yarn":
        raise ValueError(f"rope_type: the program has default and yarn, "
                         f"the file {told['rope_type']!r}")
    return RotaryRecipe(
        float(told["rope_theta"]), n, factor=float(told["factor"]),
        original_max=told["original_max_position_embeddings"],
        beta_fast=float(told["beta_fast"]), beta_slow=float(told["beta_slow"]),
        attention_factor=float(told["attention_factor"]))


def model(config: dict, context: int, overrides: dict):
    from tf_yarn_tpu.models.laguna import SLIDING, LagunaConfig, LagunaLM

    told = {"attention_bias": False, "gating": True,
            "tie_word_embeddings": False,
            "moe_apply_router_weight_on_input": False}
    for key, value in told.items():
        if config[key] != value:
            raise ValueError(f"{key}: the program has {value!r}, "
                             f"the file {config[key]!r}")
    # The file keeps the published lists whole; its cut is the depth.
    depth = config["num_hidden_layers"]
    kinds = tuple(config["layer_types"][:depth])
    ffns = tuple(config["mlp_layer_types"][:depth])
    seen = {}
    for index, (kind, ffn) in enumerate(zip(kinds, ffns)):
        _NTH[index] = ("swa_" if kind == SLIDING else "", seen.get(kind, 0),
                       seen.get(ffn, 0))
        seen[kind] = seen.get(kind, 0) + 1
        seen[ffn] = seen.get(ffn, 0) + 1
    return LagunaLM(LagunaConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=kinds,
        heads=tuple(config["num_attention_heads_per_layer"][:depth]),
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        window=config["sliding_window"], max_seq_len=context,
        norm_eps=float(config["rms_norm_eps"]),
        full_rotary=_recipe(config, "full_attention"),
        sliding_rotary=_recipe(config, "sliding_attention"),
        mlp_types=ffns, d_ff_dense=config["intermediate_size"],
        num_experts=config["num_experts"],
        num_experts_here=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"],
        routed_scale=float(config["moe_routed_scaling_factor"]), **overrides,
    ))


def plain_name(path):
    """(plain name, index in that name's list or None) of a leaf of the
    program's tree, from its path: an attention's leaves are listed over the
    layers of its kind (the sliding kind's under `swa_`), a layer's norms
    over all layers, a feed-forward's over the layers of its kind."""
    keys = [getattr(k, "key", str(k)) for k in path]
    layer = next((int(k.split("_")[1]) for k in keys
                  if k.startswith("layer_")), None)
    if layer is None:
        name = keys[-2] if keys[-1] == "scale" else keys[-1]
        return RENAMED.get(name, name), None
    module = keys[keys.index(f"layer_{layer}") + 1]
    pre, nth, ffn_nth = _NTH[layer]
    if module in ("attn_norm", "ffn_norm"):
        return module, layer
    if module == "dense":
        return RENAMED[keys[-2]], ffn_nth
    if module == "moe":
        return keys[-1], ffn_nth
    leaf = keys[-2] if keys[-1] == "kernel" else keys[-1]
    return pre + RENAMED[leaf], nth
