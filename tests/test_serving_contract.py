"""The seam between a model and the engine that serves it
(`models/trunk.py`): one trunk, one declared contract.

The six tiny configurations answer the engine's questions as they did before
the contract existed (`PARENT`, recorded on the parent commit 681cbb8 with
its probes: `inspect.signature`, `getattr` with a default, attribute reads),
and their parameter trees are the parent's leaf for leaf
(tests/fixtures/param_trees_as_before.json; to record anew, on a `git
archive` of the parent: `PYTHONPATH=<parent> python
tests/test_serving_contract.py <out.json>` with this file). The expert
counts row has one owner (`moe.ExpertRow`, `stack_counts`, `split_counts`).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.append(ROOT)  # cellbench's tables; tf_yarn_tpu from PYTHONPATH

from cellbench import agent  # noqa: E402
from tf_yarn_tpu.models import decode_engine, moe  # noqa: E402

DATA = os.path.join(ROOT, "cellbench", "tests", "data")
CONFIGS = os.path.join(ROOT, "cellbench", "configs")
FIXTURE = os.path.join(ROOT, "tests", "fixtures", "param_trees_as_before.json")
BLOCK, BUCKET, SLOTS = 8, 32, 2

# (counted_step, ceiling_prefill, slot_state_leaves, /stats
# decode_engine.paged_attention on the CPU, prefill_key_pairs(32, 20), the
# prefill takes a length), as the parent's engine answered.
PARENT = {
    "tiny_serve": (False, True, (), "plain", (2048, 420), False),
    "tiny_granite": (True, False, ("conv_state", "ssm_state"), "plain",
                     (1024, 210), False),
    "tiny_dots3": (True, True, ("window_latent",), "model", (5120, 852), True),
    "tiny_longcat": (True, True, (), "plain", (4096, 840), False),
    "tiny_laguna": (True, True, ("window_key", "window_value"), "plain",
                    (5120, 816), True),
    "tiny_dsv32": (True, True, (), "model", (3072, 630), True),
}
CASES = tuple(PARENT)


def _sizes(case, directory=DATA):
    with open(os.path.join(directory, case + ".json")) as fh:
        return json.load(fh)


def _tree(case):
    """path -> "dtype[shape]" of every leaf `model.init` makes."""
    model = agent.build_model(_sizes(case))
    abstract = meta.unbox(jax.eval_shape(
        lambda rng, tokens: model.init(rng, tokens),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((1, 8), jnp.int32)))
    return {
        "/".join(str(getattr(k, "key", k)) for k in path):
            f"{leaf.dtype}{list(leaf.shape)}".replace(" ", "")
        for path, leaf in jax.tree_util.tree_leaves_with_path(abstract)}


@pytest.mark.parametrize("case", CASES)
def test_the_engine_answers_as_the_parents_probes_did(case):
    sizes = _sizes(case)
    model = agent.build_model(sizes)
    variables = agent.program_variables(model, sizes, 42)
    contract = model.serving_contract()
    engine = decode_engine.DecodeEngine(model, prompt_buckets=(BUCKET,))
    assert engine.contract == contract
    # every leaf of the decode cache is declared, by its own kind
    row = decode_engine._decode_cache_aval(model, variables)
    layout = decode_engine.cache_layout(model, row)
    named = {lay.name: lay.kind for lay in jax.tree_util.tree_leaves(layout)}
    assert named and all(
        contract.leaf_kinds[name][0] == kind for name, kind in named.items())
    pool = engine.make_paged_pool(
        variables, SLOTS * (model.config.max_seq_len // BLOCK) + 1, BLOCK)
    engine.paged_attention_kernel(pool)
    counted, ceiling, held, attention, pairs, told = PARENT[case]
    assert (engine.counted_step(variables), engine.ceiling_prefill(variables),
            engine.slot_state_leaves(variables),
            engine.stats["paged_attention"],
            engine.prefill_key_pairs(BUCKET, 20)) == (
        counted, ceiling, held, attention, pairs)
    # the prefill takes a length exactly where a ring is declared
    assert contract.takes_prompt_len == told == any(
        kind == "ring" for kind, _axis in contract.leaf_kinds.values())
    # what the scheduler reads: the reads' names where the step returns
    # them, the expert row where the layers count, the layers to divide by
    assert contract.n_attention_layers == len(contract.prefill_layers) > 0
    assert bool(contract.reads) == (case not in ("tiny_serve", "tiny_granite"))
    assert (contract.experts is not None) == contract.counts == counted
    if contract.counts:
        # the step's `counts` is as wide as the contract says, traced only
        out = jax.eval_shape(
            decode_engine.build_paged_state_step_fn(
                model, BLOCK, 0.0, None, None),
            variables, pool, engine.make_slot_state(variables, SLOTS),
            jax.ShapeDtypeStruct((SLOTS, model.config.max_seq_len // BLOCK),
                                 jnp.int32),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
            *decode_engine.feed_avals(SLOTS),
            jax.ShapeDtypeStruct((SLOTS,), bool))
        row = contract.experts
        assert out[4].shape[1] == 1 + row.held + row.zero + row.streamed(SLOTS)
        assert len(out) == 5 + bool(contract.reads)
        assert not contract.reads or out[5].shape == (len(contract.reads),)


@pytest.mark.parametrize("case", CASES)
def test_the_parameter_tree_is_the_parents(case):
    """Paths, shapes and types: the weight tables name leaves by path."""
    with open(FIXTURE) as fh:
        assert _tree(case) == json.load(fh)[case]


@pytest.mark.parametrize("name,loops", [
    ("granite4h_small_serve_1chip", False),
    ("dots3_note_serve_1chip", False),
    ("longcat_flash_serve_1chip", True),
    ("laguna_xs2_serve_1chip", False),
    ("deepseek_v32_serve_1chip", True),
])
def test_the_row_declared_at_the_benchmarks_sizes(name, loops):
    """Whether a step's rows end in the loop's trip count, from the config
    alone, is what `DroplessMoE` does at the step's shapes
    (`tests/test_moe.py` `FORMS`, traced there)."""
    sizes = _sizes(name, CONFIGS)
    model = agent.build_model(sizes)
    config, row = model.config, model.serving_contract().experts
    slots = sizes["serving"]["max_slots"]
    assert row.streamed(slots) is loops
    zero = name.startswith("longcat")
    assert (row.held, row.zero) == (config.num_experts_here, zero)


def _sown(layers, held, zero, streamed, rng):
    """`moe_stats` as `layers` expert layers sow it, and the same by name."""
    want = (rng.integers(0, 99, layers), rng.integers(0, 9, (layers, held)),
            rng.integers(0, 9, layers) if zero else None,
            rng.integers(0, held + 1, layers) if streamed else None)
    stats = {}
    for i in range(layers):
        parts = [want[0][i:i + 1], want[1][i]]
        if zero:
            parts.append(want[2][i:i + 1])
        sown = {"counts": (jnp.asarray(np.concatenate(parts), jnp.int32),)}
        if streamed:
            sown["streamed"] = (jnp.asarray(want[3][i], jnp.int32),)
        stats[f"layer_{i}"] = {"moe": sown}
    return stats, want


@pytest.mark.parametrize("zero", [False, True])
@pytest.mark.parametrize("streamed", [False, True])
def test_the_counts_row_is_built_and_split_by_its_owner(
        monkeypatch, zero, streamed):
    """`stack_counts` of what the layers sowed, read back through
    `split_counts`, is what they sowed by name; a row of another width than
    the one declared is refused, not guessed at."""
    monkeypatch.setattr(moe, "LOOP_SKIPS_BYTES", 0 if streamed else 2 ** 60)
    row = moe.ExpertRow(held=4, zero=zero, top_k=2, outputs=6,
                        expert_bytes=1024)
    assert row.streamed(3) is streamed
    stats, want = _sown(3, 4, zero, streamed, np.random.default_rng(3))
    counts = np.asarray(moe.stack_counts(stats))
    assert counts.shape == (3, 5 + zero + streamed)
    got = moe.split_counts(counts, row, 3)
    for a, b in zip(got, want):
        assert (a is None and b is None) or np.array_equal(a, b)
    with pytest.raises(ValueError, match="declares"):
        moe.split_counts(counts[:, :-1], row, 3)
    assert moe.stack_counts({}).shape == (0, 0)


if __name__ == "__main__":
    with open(sys.argv[1], "w") as out:
        json.dump({"jax": jax.__version__, "recorded": "the parent commit",
                   **{case: _tree(case) for case in CASES}},
                  out, indent=1, sort_keys=True)
