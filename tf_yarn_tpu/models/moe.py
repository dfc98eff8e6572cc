"""Mixture-of-Experts layer with expert parallelism (the `ep` mesh axis).

GShard/Switch-style top-1 routing with capacity-bounded one-hot dispatch —
the TPU MoE recipe: dispatch/combine are einsums (MXU work, static
shapes), expert FFNs are batched matmuls with the expert axis annotated
("expert" → ep in parallel.sharding.LOGICAL_RULES), so XLA places one
expert group per ep shard and inserts the all-to-alls itself. No analog
exists in the reference (SURVEY.md §2.5: expert parallelism — NO).

`DroplessMoE` is the other equation, the one served models use
(models/hybrid.py, models/latent.py, models/longcat.py): a router over all
experts of the deployment in float32, the `top_k` largest, gates over those
alone (a softmax of their logits, or their sigmoid scores, chosen under a
correction bias — over all experts or inside the best few of the router's
groups — normalised and scaled) or a softmax over every output of
the router, no capacity and no dropped token, plus a shared expert every
token passes and, where the router is wider than the experts that exist,
zero-compute experts that hand a token back. It is told which experts this
chip holds and returns their part of the sum.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tf_yarn_tpu.models.transformer import EMBED, MLP, TransformerConfig, _partitioned

EXPERT = "expert"


class MoEMlp(nn.Module):
    """Drop-in replacement for the dense SwiGLU block when
    `config.moe_experts > 0`.

    Returns the combined output; the Switch load-balancing loss is sown
    into the "intermediates" collection as `moe_aux_loss` (collected by
    models.common.lm_loss and scaled by `config.moe_aux_weight`).
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, d = x.shape
        n_exp = cfg.moe_experts
        tokens = x.reshape(b * s, d)
        n_tokens = tokens.shape[0]
        capacity = max(1, int(cfg.moe_capacity_factor * n_tokens / n_exp))

        router = self.param(
            "router",
            _partitioned((EMBED, None))(nn.initializers.normal(stddev=0.02)),
            (d, n_exp),
            cfg.param_dtype,
        )
        # Router math in f32: tiny, numerically sensitive.
        logits = jnp.einsum(
            "td,de->te", tokens.astype(jnp.float32), router.astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)
        expert_idx = jnp.argmax(probs, axis=-1)  # top-1 (switch)
        gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]

        # Capacity-bounded position of each token within its expert.
        onehot = jax.nn.one_hot(expert_idx, n_exp, dtype=jnp.float32)  # [T,E]
        position = jnp.cumsum(onehot, axis=0) * onehot - 1.0  # [T,E], -1 elsewhere
        in_capacity = (position >= 0) & (position < capacity)
        onehot = onehot * in_capacity
        gate = gate * jnp.sum(onehot, axis=-1)  # dropped tokens gate to 0

        # dispatch [T, E, C]: token t -> slot (e, c).
        pos_onehot = jax.nn.one_hot(
            jnp.clip(position, 0, capacity - 1).astype(jnp.int32), capacity,
            dtype=jnp.float32,
        )  # [T, E, C]
        dispatch = onehot[:, :, None] * pos_onehot

        expert_inputs = jnp.einsum(
            "tec,td->ecd", dispatch.astype(cfg.dtype), tokens
        )  # [E, C, D]

        # Batched SwiGLU over the (ep-sharded) expert axis.
        def expert_param(name, shape, axis_names):
            return self.param(
                name,
                _partitioned((EXPERT, *axis_names))(nn.initializers.lecun_normal()),
                (n_exp, *shape),
                cfg.param_dtype,
            )

        w_gate = expert_param("w_gate", (d, cfg.d_ff), (EMBED, MLP))
        w_up = expert_param("w_up", (d, cfg.d_ff), (EMBED, MLP))
        w_down = expert_param("w_down", (cfg.d_ff, d), (MLP, EMBED))
        h = nn.silu(
            jnp.einsum("ecd,edf->ecf", expert_inputs, w_gate.astype(cfg.dtype))
        ) * jnp.einsum("ecd,edf->ecf", expert_inputs, w_up.astype(cfg.dtype))
        expert_out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(cfg.dtype))

        combined = jnp.einsum(
            "tec,ecd->td", dispatch.astype(cfg.dtype), expert_out
        ) * gate[:, None].astype(cfg.dtype)

        # Switch aux loss: fraction-of-tokens x mean-router-prob per expert.
        frac_tokens = jnp.mean(onehot, axis=0)
        frac_probs = jnp.mean(probs, axis=0)
        aux_loss = n_exp * jnp.sum(frac_tokens * frac_probs)

        self.sow("intermediates", "moe_aux_loss", aux_loss)
        return combined.reshape(b, s, d).astype(cfg.dtype)


# Rows of one product of the sorted form: a tile holds one expert's tokens.
TILE = 128
# How many times the sorted form's rows the one product over every held
# expert may compute before `DroplessMoE` sorts its tokens by expert.
ROWS_OVER = 4


def sorts_by_expert(held: int, tokens: int, top_k: int) -> bool:
    """Whether `DroplessMoE` computes `held` experts over `tokens` tokens of
    `top_k` choices each in the sorted form (`sorted_experts`), from the
    shapes alone: where every held expert over every token (held x tokens
    rows) is more than `ROWS_OVER` times the sorted buffer (the assignments
    and a tile of padding an expert). A prefill of 1024 tokens or more with
    all 256 experts of a layer held at 8 a token does (6x, 11x at 2048); a
    decode step does not (the padding outweighs 64 tokens), nor any call of
    a model that holds 16-18 experts of 8-12 a token (under held / top_k,
    2.25 at most)."""
    return held * tokens > ROWS_OVER * (tokens * top_k + held * TILE)


# Bytes of experts' matrices that a loop over the touched experts has to be
# expected to skip, an iteration it runs, before `DroplessMoE` takes it. An
# iteration costs 5.8 us more than its expert's part of the one product (a
# v5e, 32 tokens over 88 MB experts: two products that each start their
# stream anew, at 89 % of the HBM peak where the one product holds 92 %, and
# 2 us of the loop's own; PERF.md section 5, PR 46), in which the chip
# streams 4.8 MB: three times that, so that the loop is taken where it is
# expected to save twice what it costs and not where the two forms tie.
LOOP_SKIPS_BYTES = 16 * 2**20


def loops_over_touched(held: int, tokens: int, top_k: int, outputs: int,
                       expert_bytes: int) -> bool:
    """Whether `DroplessMoE` computes `held` experts of `expert_bytes` each
    (both matrices as held) over `tokens` tokens of `top_k` choices among
    the router's `outputs` in a loop over the experts that some token chose
    (`touched_experts`), from the shapes alone: not where it sorts
    (`sorts_by_expert`), and where the matrices the loop is expected to skip
    for each iteration it runs outweigh `LOOP_SKIPS_BYTES`. Under even
    routing an expert goes untouched with probability `u = (1 - top_k /
    outputs) ** tokens`, so the loop runs `(1 - u) held` iterations and
    skips `u held` experts: `u / (1 - u)` of an expert an iteration. A step
    of 32 slots at 8 of 256 over 88 MB experts does (u 0.36: 50 MB), and one
    of 64 at 12 of 768 over 75 MB (0.37: 43 MB), as does a prefill of as few
    tokens; 64 slots at 8 of 256 do not (u 0.13: 7 MB of 46 MB experts, 1 MB
    of 6 MB ones), nor 32 at 10 of 72 (0.008), nor any call of 128 tokens
    or more (under 1 %)."""
    if sorts_by_expert(held, tokens, top_k):
        return False
    untouched = (1.0 - top_k / outputs) ** tokens
    return untouched * expert_bytes > LOOP_SKIPS_BYTES * (1.0 - untouched)


def touched_experts(x, w_in, w_out, weights, touched, dtype):
    """`sum_e weights[t, e] E_e(x[t])` over the held experts that `touched`
    marks, the others' matrices never read: x [T, D], w_in [held, D, 2 F],
    w_out [held, F, D], weights [T, held] (a token's gate for each held
    expert it chose, zero elsewhere), touched [held] bool -> ([T, D]
    float32, the number of experts multiplied).

    A loop over the marked experts, each multiplying all T rows by its two
    matrices, sliced where they lie: nothing is sorted, scattered or padded,
    since T is a step's slots. The products and their types are the one
    product's over every held expert; the experts' float32 partial sums are
    added in another order."""
    t, d = x.shape
    width = w_in.shape[2] // 2
    with jax.named_scope("moe/dispatch"):
        # The marked experts first, in their own order.
        order = jnp.argsort(~touched, stable=True)
        n_touched = jnp.sum(touched, dtype=jnp.int32)

    def one_expert(index, out):
        expert = order[index]
        w_a = jax.lax.dynamic_index_in_dim(w_in, expert, 0, False)
        w_b = jax.lax.dynamic_index_in_dim(w_out, expert, 0, False)
        with jax.named_scope("moe/experts"):
            hidden = jnp.einsum("td,df->tf", x, w_a.astype(dtype),
                                preferred_element_type=jnp.float32)
            act = nn.silu(hidden[:, :width]) * hidden[:, width:]
        with jax.named_scope("moe/combine"):
            gate = jax.lax.dynamic_index_in_dim(weights, expert, 1, True)
            return out + jnp.einsum(
                "tf,fd->td", (act * gate).astype(dtype), w_b.astype(dtype),
                preferred_element_type=jnp.float32)

    out = jax.lax.fori_loop(
        0, n_touched, one_expert, jnp.zeros((t, d), jnp.float32))
    return out, n_touched


def sorted_experts(x, w_in, w_out, local, gates, dtype):
    """`sum_j gates[t, j] E_local[t, j](x[t])` over the chosen experts that
    are held, each token computed by its own experts alone: x [T, D], w_in
    [held, D, 2 F], w_out [held, F, D], local [T, k] (the chosen experts'
    places among the held; outside [0, held) = not held), gates [T, k] ->
    [T, D] float32.

    The T k assignments are sorted by expert into a buffer in which each
    expert's tokens start on a tile of `TILE` rows (the rest of its last
    tile is padding: a zero row with a zero gate), so a tile is one
    expert's, and a loop over the tiles in use multiplies each by its
    expert's two matrices, sliced where they lie. Dropless: the buffer holds
    T k rows and `TILE - 1` of padding an expert, whatever the routing. The
    arithmetic is the assignments', not held x T: 1 / 32 of it at 256 held
    and 8 a token."""
    t, d = x.shape
    held, _, two_width = w_in.shape
    width = two_width // 2
    k = local.shape[1]
    n_tiles = -(-t * k // TILE) + held
    rows = n_tiles * TILE
    with jax.named_scope("moe/dispatch"):
        flat = local.reshape(-1)
        expert = jnp.where((flat >= 0) & (flat < held), flat, held)
        order = jnp.argsort(expert, stable=True)
        by_expert = expert[order]
        counts = jnp.sum(
            expert[:, None] == jnp.arange(held)[None, :], axis=0,
            dtype=jnp.int32)
        padded = -(-counts // TILE) * TILE
        ends = jnp.cumsum(padded)
        first_row = jnp.concatenate([ends - padded, jnp.full((1,), rows)])
        first_place = jnp.concatenate(
            [jnp.cumsum(counts) - counts, jnp.zeros((1,), jnp.int32)])
        # An assignment to an expert not held goes to the spare last row.
        row = jnp.where(
            by_expert < held,
            first_row[by_expert] + jnp.arange(t * k) - first_place[by_expert],
            rows)
        row_token = jnp.full((rows + 1,), t, jnp.int32).at[row].set(
            (order // k).astype(jnp.int32))
        row_gate = jnp.zeros((rows + 1,), jnp.float32).at[row].set(
            gates.reshape(-1)[order])
        tile_expert = jnp.minimum(jnp.searchsorted(
            ends, jnp.arange(n_tiles) * TILE, side="right"), held - 1)
        sorted_x = jnp.concatenate(
            [x, jnp.zeros((1, d), x.dtype)])[row_token[:rows]]

    def one_tile(index, out):
        at = index * TILE
        w_a = jax.lax.dynamic_index_in_dim(w_in, tile_expert[index], 0, False)
        w_b = jax.lax.dynamic_index_in_dim(w_out, tile_expert[index], 0, False)
        with jax.named_scope("moe/experts"):
            hidden = jnp.einsum(
                "td,df->tf", jax.lax.dynamic_slice_in_dim(sorted_x, at, TILE),
                w_a.astype(dtype), preferred_element_type=jnp.float32)
            act = nn.silu(hidden[:, :width]) * hidden[:, width:]
        with jax.named_scope("moe/combine"):
            gate = jax.lax.dynamic_slice_in_dim(row_gate, at, TILE)
            part = jnp.einsum(
                "tf,fd->td", (act * gate[:, None]).astype(dtype),
                w_b.astype(dtype), preferred_element_type=jnp.float32)
            return jax.lax.dynamic_update_slice_in_dim(out, part, at, 0)

    # The tiles in use, not all the buffer could hold: a dynamic trip count.
    out = jax.lax.fori_loop(
        0, ends[-1] // TILE, one_tile, jnp.zeros((rows + 1, d), jnp.float32))
    with jax.named_scope("moe/combine"):
        place = jnp.zeros((t * k,), jnp.int32).at[order].set(row)
        return jnp.sum(out[place].reshape(t, k, d), axis=1)


def within_best_groups(choice, n_group: int, topk_group: int):
    """Group-limited selection (`noaux_tc` with `n_group` > 1): `choice`
    [T, E] (`s + b`) with every entry outside the `topk_group` best groups
    at -inf; a group's score is the sum of its two largest entries, and of
    equal groups the earlier is kept (`jax.lax.top_k`'s order)."""
    with jax.named_scope("moe/groups"):
        t, outputs = choice.shape
        grouped = choice.reshape(t, n_group, outputs // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, best = jax.lax.top_k(group_score, topk_group)
        kept = jnp.any(best[:, :, None] == jnp.arange(n_group), axis=1)
        return jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(
            t, outputs)


@dataclasses.dataclass(frozen=True)
class ExpertRow:
    """One expert layer's row of a step's `counts`, as a model declares it
    (`trunk.ServingContract.experts`): `[assignments | held experts... |
    zero? | streamed?]`, as `DroplessMoE` sows them: the counted tokens'
    assignments over all experts; the tokens that reached each of the
    `held`; the assignments to zero-compute outputs (`zero`); the trip count
    of the loop over touched experts (`streamed`). `stack_counts` builds a
    step's rows and `split_counts` names their parts: nobody else knows the
    columns."""

    held: int
    zero: bool
    top_k: int
    outputs: int
    # Bytes of one expert's matrices as a server holds them
    # (`DecodeEngine.hold_params`: in the narrower of the two types).
    expert_bytes: int

    @classmethod
    def of(cls, config, num_zero_experts: int = 0) -> "ExpertRow":
        """Of a config with `DroplessMoE`'s fields under the models' names."""
        itemsize = min(jnp.dtype(config.param_dtype).itemsize,
                       jnp.dtype(config.dtype).itemsize)
        return cls(
            held=config.num_experts_here, zero=num_zero_experts > 0,
            top_k=config.experts_per_token,
            outputs=config.num_experts + num_zero_experts,
            expert_bytes=3 * config.d_model * config.d_expert * itemsize)

    def streamed(self, tokens: int) -> bool:
        """Whether a step of `tokens` slots loops (`loops_over_touched`)."""
        return loops_over_touched(
            self.held, tokens, self.top_k, self.outputs, self.expert_bytes)


def stack_counts(stats):
    """A step's `counts`, a row a layer, from what the expert layers sowed
    into `moe_stats` (`counts`, and `streamed` where they loop), in the
    layers' order; [0, 0] where none counted."""
    by_name = {"counts": [], "streamed": []}
    for path, leaf in jax.tree_util.tree_leaves_with_path(stats):
        by_name[path[-2].key].append(leaf)  # .../<name>/0: sown once a call
    if not by_name["counts"]:
        return jnp.zeros((0, 0), jnp.int32)
    counts = jnp.stack(by_name["counts"])
    if by_name["streamed"]:
        counts = jnp.concatenate(
            [counts, jnp.stack(by_name["streamed"])[:, None]], axis=1)
    return counts


def split_counts(counts, row: ExpertRow, tokens: int):
    """A step's `counts` (`stack_counts`, read back) of `tokens` slots by
    name: (assignments [layers], load [layers, held], zero [layers] | None,
    streamed [layers] | None). A width other than the declared one is an
    error: no column is guessed."""
    streamed = row.streamed(tokens)
    if counts.shape[1] != 1 + row.held + row.zero + streamed:
        raise ValueError(
            f"a step of {tokens} slots counted rows of {counts.shape[1]}: "
            f"not what {row} declares (streamed: {streamed})")
    return (counts[:, 0], counts[:, 1:1 + row.held],
            counts[:, 1 + row.held] if row.zero else None,
            counts[:, -1] if streamed else None)


class DroplessMoE(nn.Module):
    """`moe(x) = sum_i g_i W_out,i (silu(a_i) * b_i)`, `[a_i | b_i] = x W_in,i`
    over the `top_k` chosen experts; plus `shared(x)`, one SwiGLU of width
    `d_shared` that every token passes (0 = none).

    `scoring="softmax"`: the experts of largest router logit, `g = softmax`
    over those `top_k` logits alone. `scoring="sigmoid"` (the `noaux_tc`
    recipe): `s = sigmoid(logits)`; the `top_k` largest of `s + b`, `b` the
    float32 correction bias `router_bias`, taken inside groups: the router's
    outputs lie in `n_group` groups of equal size, a group's score is the
    sum of its two largest `s + b`, the `topk_group` best groups are kept
    and the choice is made inside them (`moe/groups`; `n_group` 1 = over
    all experts); `g_i = s_i`, over `sum_chosen s` where `norm_topk`, times
    `routed_scale`.
    `scoring="softmax_all"`: `p = softmax(logits)` over every output of the
    router; the `top_k` largest of `p + b`; `g_i = p_i` (over their sum where
    `norm_topk`) times `routed_scale`.

    `num_zero_experts` > 0 widens the router (and the bias) by that many
    outputs past the `num_experts` that have matrices: a chosen output
    `i >= num_experts` is a zero-compute expert that returns its input, so
    the layer adds `(sum of those g_i) x` (`moe/identity`). That term needs
    the router alone, which every chip holds whole: every chip computes it
    alike, and where the shares of a layer are summed it counts once, as
    the shared expert does.

    The router is as wide as the deployment (`num_experts`), and this chip
    holds `num_experts_here` of them starting at `expert_offset`: the sum
    runs over the chosen experts that are held here, and the rest of it is
    the other chips'. Nothing stands in for them: with fewer than all
    experts held, the result is this chip's addend of the all-reduce.

    One function of x [T, D], for prefill and decode alike, so a serving
    step hands it every slot's token together. Held experts are computed
    for all T tokens and weighted by a [T, held] matrix that is zero where
    a token did not choose the expert: no token is dropped and no
    [T, E, C] dispatch tensor exists. At decode (T = slots) that streams
    each held expert's matrices once, which is what the step is bound by.
    The same sum has two more schedules, chosen from the shapes alone.
    Where that product would be many times the assignments' rows
    (`sorts_by_expert`: a prefill with every expert of a layer held), the
    tokens are sorted by expert and each is computed by its own experts
    alone (`sorted_experts`). Where so few tokens choose among so many
    outputs that a good part of the held experts is nobody's choice, and
    an expert's matrices are large (`loops_over_touched`: a step of 32-64
    slots over 16 experts of 75-88 MB), a loop multiplies all T tokens by
    each expert that some token chose and reads no other
    (`touched_experts`): the step streams the touched experts' matrices
    once, two thirds of what is held. Dropless still, and the same
    products in the same types on all three.

    `count_mask` [T] marks the tokens whose routing is counted into the
    mutable `moe_stats` collection (`ExpertRow`'s columns); without that
    collection nothing is counted. A step marks its active slots. The loop
    then runs over the experts that a marked token chose, so that what it
    streams is what `counts` says was reached, and sows its trip count
    beside them (`streamed`); a token left out is a free slot's, whose row
    is written to the trash block and read by nobody: it gets the part of
    its sum that the marked tokens' experts cover.
    """

    num_experts: int
    num_experts_here: int
    top_k: int
    d_expert: int
    d_shared: int = 0
    expert_offset: int = 0
    scoring: str = "softmax"
    norm_topk: bool = True
    routed_scale: float = 1.0
    num_zero_experts: int = 0
    n_group: int = 1
    topk_group: int = 1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, count_mask=None):
        t, d = x.shape
        held, width = self.num_experts_here, self.d_expert
        if self.scoring not in ("softmax", "sigmoid", "softmax_all"):
            raise ValueError(f"scoring: {self.scoring!r}")
        outputs = self.num_experts + self.num_zero_experts
        if not 0 < held <= self.num_experts - self.expert_offset:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset + held})"
                f" are not among the router's {self.num_experts}"
            )
        if self.n_group > 1 and (
                self.scoring != "sigmoid" or outputs % self.n_group
                or not 0 < self.topk_group <= self.n_group
                or self.top_k > self.topk_group * (outputs // self.n_group)):
            raise ValueError(
                f"n_group {self.n_group} / topk_group {self.topk_group}: "
                f"groups are the sigmoid scoring's, divide the router's "
                f"{outputs} outputs evenly and hold top_k {self.top_k} "
                "in those kept")
        normal = nn.initializers.lecun_normal()

        with jax.named_scope("moe/router"):
            router = self.param(
                "router", _partitioned((EMBED, None))(normal),
                (d, outputs), self.param_dtype,
            )
            # float32 at full precision: which ten are largest decides
            # whole expert outputs, not a rounding.
            logits = jnp.einsum(
                "td,de->te", x.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            if self.scoring == "softmax":
                top_logits, top_index = jax.lax.top_k(logits, self.top_k)
                gates = jax.nn.softmax(top_logits, axis=-1)
                if self.routed_scale != 1.0:
                    gates = gates * self.routed_scale
            else:
                bias = self.param("router_bias", nn.initializers.zeros_init(),
                                  (outputs,), jnp.float32)
                scores = nn.sigmoid(logits) if self.scoring == "sigmoid" \
                    else jax.nn.softmax(logits, axis=-1)
                choice = scores + bias
                if self.n_group > 1:
                    choice = within_best_groups(
                        choice, self.n_group, self.topk_group)
                _, top_index = jax.lax.top_k(choice, self.top_k)
                gates = jnp.take_along_axis(scores, top_index, axis=-1)
                if self.norm_topk:
                    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
                gates = gates * self.routed_scale
        with jax.named_scope("moe/dispatch"):
            # weights [T, held]: a token's gate for each held expert it
            # chose, zero elsewhere.
            local = top_index - self.expert_offset
            chose = local[:, :, None] == jnp.arange(held)[None, None, :]
            weights = jnp.sum(jnp.where(chose, gates[:, :, None], 0.0), axis=1)
            if count_mask is not None:
                # `ExpertRow`'s columns, over the counted tokens.
                counted = chose & count_mask[:, None, None]
                counts = [
                    jnp.sum(count_mask, dtype=jnp.int32)[None] * self.top_k,
                    jnp.sum(counted, axis=(0, 1), dtype=jnp.int32)]
                if self.num_zero_experts:
                    counts.append(jnp.sum(
                        (top_index >= self.num_experts) & count_mask[:, None],
                        dtype=jnp.int32)[None])
                self.sow("moe_stats", "counts", jnp.concatenate(counts))
        with jax.named_scope("moe/experts"):
            w_in = self.param(
                "w_in", _partitioned((EXPERT, EMBED, MLP))(normal),
                (held, d, 2 * width), self.param_dtype,
            )
            w_out = self.param(
                "w_out", _partitioned((EXPERT, MLP, EMBED))(normal),
                (held, width, d), self.param_dtype,
            )

        if sorts_by_expert(held, t, self.top_k):
            # Many experts over many tokens (a prefill with every expert of
            # the layer held): each token by its own experts alone.
            out = sorted_experts(x, w_in, w_out, local, gates, self.dtype)
        elif loops_over_touched(held, t, self.top_k, outputs,
                                3 * d * width * w_in.dtype.itemsize):
            # Few tokens over few large experts (a decode step): a third of
            # the held experts is chosen by nobody and is not read.
            with jax.named_scope("moe/dispatch"):
                touched = jnp.any(
                    chose if count_mask is None else counted, axis=(0, 1))
            out, streamed = touched_experts(
                x, w_in, w_out, weights, touched, self.dtype)
            if count_mask is not None:
                self.sow("moe_stats", "streamed", streamed)
        else:
            with jax.named_scope("moe/experts"):
                hidden = jnp.einsum("td,edf->etf", x, w_in.astype(self.dtype),
                                    preferred_element_type=jnp.float32)
                act = nn.silu(hidden[..., :width]) * hidden[..., width:]
            with jax.named_scope("moe/combine"):
                # The gate goes onto the activation, so that the weighted
                # sum over experts is the contraction of one matmul.
                act = (act * weights.T[:, :, None]).astype(self.dtype)
                out = jnp.einsum("etf,efd->td", act, w_out.astype(self.dtype),
                                 preferred_element_type=jnp.float32)
        if self.num_zero_experts:
            with jax.named_scope("moe/identity"):
                handed_back = jnp.sum(jnp.where(
                    top_index >= self.num_experts, gates, 0.0), axis=-1)
                out = out + handed_back[:, None] * x.astype(jnp.float32)
        if self.d_shared:
            with jax.named_scope("moe/shared"):
                s_in = self.param(
                    "shared_in", _partitioned((EMBED, MLP))(normal),
                    (d, 2 * self.d_shared), self.param_dtype,
                )
                s_out = self.param(
                    "shared_out", _partitioned((MLP, EMBED))(normal),
                    (self.d_shared, d), self.param_dtype,
                )
                hidden = jnp.einsum("td,df->tf", x, s_in.astype(self.dtype),
                                    preferred_element_type=jnp.float32)
                act = nn.silu(hidden[:, :self.d_shared]) \
                    * hidden[:, self.d_shared:]
                out = out + jnp.einsum(
                    "tf,fd->td", act.astype(self.dtype),
                    s_out.astype(self.dtype),
                    preferred_element_type=jnp.float32,
                )
        return out.astype(self.dtype)
