"""What every served language model shares: the trunk around its layers, and
what it declares to the engine (docs/Serving.md "What a model declares").

A model is its config, its block and a list of layers; `DecoderLM` is the
rest. `HybridLM`, `LatentLM`, `LongcatLM` and `LagunaLM` subclass it;
`Transformer` (three layer loops and a head in another precision: the
training model) keeps its own call and declares the same contract.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tf_yarn_tpu.models.transformer import EMBED, VOCAB, RMSNorm, _partitioned


@dataclasses.dataclass(frozen=True)
class ServingContract:
    """Everything `models/decode_engine.py` and `serving/scheduler.py` read
    of a model's class. `model.serving_contract()` is host arithmetic on the
    config: no trace, no params."""

    # Cache leaf name -> (kind, sequence axis from the end of the shape):
    # decode_engine.py "Cache leaves by kind".
    leaf_kinds: Mapping[str, Tuple[str, Optional[int]]]
    # `(window, query_block)` of each attention sublayer as a prefill from
    # an empty cache runs it (`transformer.prefill_key_pairs`).
    prefill_layers: Tuple[Tuple[int, int], ...]
    # Row t of a prefill's cache depends on tokens <= t alone: the engine
    # may pad a prompt past its length (`DecodeEngine.ceiling_prefill`).
    rows_causal: bool
    # The prefill is told where its prompt ends (`prompt_len`, a traced
    # scalar): a model that writes a `ring` from the rows that end there.
    takes_prompt_len: bool
    # The step hands the layers `count_mask` and returns what they counted
    # (`paged_state_step`), whether or not anything is held once a slot.
    counts: bool
    # Names of what the attention layers sum into `cache_stats`, in order.
    reads: Tuple[str, ...] = ()
    # A cached row has no head axis and is read as one KV head of its width
    # by `paged_decode_attention`, whose implementation the engine picks.
    pool_rows_are_one_kv_head: bool = False
    # One expert layer's row of the step's `counts` (`moe.ExpertRow`).
    experts: Optional[Any] = None

    @property
    def n_attention_layers(self) -> int:
        """What the scheduler divides the rows read by."""
        return len(self.prefill_layers)


def contract_of(model) -> ServingContract:
    """`model.serving_contract()`; a model without one is refused by name:
    nothing is guessed, and nothing defaults to the slower rule."""
    declare = getattr(model, "serving_contract", None)
    if declare is None:
        raise ValueError(
            f"{type(model).__name__} does not declare serving_contract() "
            "(models/trunk.py: its cache leaves by kind, which prefill and "
            "which step it takes); the serving path does not guess them")
    return declare()


class LayerCall(NamedTuple):
    """What the trunk hands every block beside the stream; a block takes
    what it reads. `positions` [B, S]: the call's own, from 0. `count_mask`
    [B * S]: the tokens whose routing and cache reads the layers count
    (`moe_stats`, `cache_stats`). `paged_ctx`: the paged step's
    (`transformer.PagedContext`). `prompt_len` (a prefill's; a traced
    scalar): where the prompt ends when what follows is pad."""

    positions: Any = None
    count_mask: Any = None
    paged_ctx: Any = None
    prompt_len: Any = None


class DecoderLM(nn.Module):
    """tokens [B, S] int32 -> logits [B, S, vocab] (float32), or the normed
    hidden states (`return_hidden`). `decode=True` keeps the cache
    (`models/decode_engine.py` drives it); `paged_ctx` besides is the paged
    step's call: tokens [slots, 1], what is held once a slot with a leading
    slot axis in `cache`, the paged leaves in the `kv_pool` collection.
    A subclass gives `config` (`vocab_size`, `d_model`, `n_layers`, `dtype`,
    `param_dtype`, `norm_config()`), `layer(index, **module)` and
    `serving_contract()`, and says below where it differs."""

    config: Any
    # Whether the blocks read `LayerCall.positions`.
    block_positions = True
    # Which rows reach the norm and the head: all, or of a decode call of
    # more than one token (a prefill) the last alone, [B, 1, vocab].
    head_on_prefill_last_row = False
    # granite's: the embedded tokens times `config.embedding_multiplier`;
    # the head is the embedding's transpose, over `config.logits_scaling`.
    scaled_embedding = False
    tied_head = False

    @nn.nowrap
    def layer(self, index: int, **module) -> nn.Module:
        """Layer `index`'s block, `block(x, LayerCall) -> x`, built with
        `**module` (`decode`, `name`)."""
        raise NotImplementedError

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True,
                 return_hidden: bool = False, decode: bool = False,
                 count_mask=None, paged_ctx=None, prompt_len=None):
        cfg = self.config
        embedding = self.param(
            "embedding",
            _partitioned((VOCAB, EMBED))(nn.initializers.normal(stddev=0.02)),
            (cfg.vocab_size, cfg.d_model), cfg.param_dtype,
        )
        with jax.named_scope("embed"):
            x = embedding.astype(cfg.dtype)[tokens]
            if self.scaled_embedding:
                x = (x * cfg.embedding_multiplier).astype(cfg.dtype)
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape) \
            if self.block_positions else None
        call = LayerCall(positions, count_mask, paged_ctx, prompt_len)
        for index in range(cfg.n_layers):
            x = self.layer(index, decode=decode, name=f"layer_{index}")(
                x, call)
        if self.head_on_prefill_last_row and decode \
                and tokens.shape[1] > 1 and not return_hidden:
            # [S, vocab] float32 of the other rows would be 0.8 GB at a
            # 2048-token bucket over the whole vocabulary.
            x = x[:, -1:]
        x = RMSNorm(cfg.norm_config(), name="final_norm")(x)
        if return_hidden:
            return x
        with jax.named_scope("lm_head"):
            if self.tied_head:
                return jnp.einsum(
                    "bsd,vd->bsv", x, embedding.astype(cfg.dtype),
                    preferred_element_type=jnp.float32,
                ) / cfg.logits_scaling
            head = self.param(
                "lm_head",
                _partitioned((EMBED, VOCAB))(nn.initializers.lecun_normal()),
                (cfg.d_model, cfg.vocab_size), cfg.param_dtype,
            )
            return jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)
