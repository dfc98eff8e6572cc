"""A prefill is told its prompt's length only where the model writes a ring
there (the contract's `takes_prompt_len`): the prefill, pack and step programs
of the models that hold no ring — the Llama-shaped one, LongCat's, the hybrid
— are the programs they were. Two proofs: the prefill lowers to the text of
the two-argument form whatever length the engine is handed, and the three
programs' lowered texts have the digests recorded on the parent commit
(tests/fixtures/programs_as_before.json; to record anew, on a `git archive`
of the parent: `PYTHONPATH=<parent> python tests/test_programs_as_before.py
<out.json>` with this file).

Since PR 45 the fixture also holds the three programs of the models with
rings and of the one in which every layer selects (`SPAN_CASES`, recorded on
that PR's parent bcc1cda): the key span of a prefill's attention leaves every
pack and step program, every prefill whose span is the whole call (these
tiny buckets) and every selecting layer the text they had. The Llama-shaped
prefills (`tiny_serve`, `tiny_granite`) left the cache path there and were
recorded anew.

Since PR 46 `DroplessMoE` loops over the held experts that a token reached
where the shapes say it pays (`moe.loops_over_touched`): no tiny
configuration's shapes do (8 to 16 small experts), so no digest was recorded
anew, `tiny_longcat`'s and `tiny_dsv32`'s included: every expert layer here
lowers to the one product's text, and the loop is held to the one product
by `tests/test_moe.py`.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.append(ROOT)  # cellbench's tables; tf_yarn_tpu from PYTHONPATH

from cellbench import agent  # noqa: E402
from tf_yarn_tpu.models import decode_engine  # noqa: E402

DATA = os.path.join(ROOT, "cellbench", "tests", "data")
FIXTURE = os.path.join(ROOT, "tests", "fixtures", "programs_as_before.json")
CASES = ("tiny_serve", "tiny_longcat", "tiny_granite")
RING_CASES = ("tiny_dots3", "tiny_laguna")
SPAN_CASES = RING_CASES + ("tiny_dsv32",)
BLOCK, BUCKET, SLOTS = 8, 32, 2


def _model(case):
    with open(os.path.join(DATA, case + ".json")) as fh:
        sizes = json.load(fh)
    model = agent.build_model(sizes)
    return model, agent.program_variables(model, sizes, 42)


def _texts(model, variables):
    """The lowered text of the prefill at one bucket, of the pack of its
    cache into the pool, and of the one-token step."""
    engine = decode_engine.DecodeEngine(model, prompt_buckets=(BUCKET,))
    tokens = jax.ShapeDtypeStruct((1, BUCKET), jnp.int32)
    prefill = jax.jit(decode_engine.build_prefill_fn(model))
    told = (jnp.asarray(BUCKET - 3, jnp.int32),) \
        if model.serving_contract().takes_prompt_len else ()
    texts = {"prefill": prefill.lower(variables, tokens, *told).as_text()}
    row = jax.eval_shape(prefill, variables, tokens)[0]
    per = model.config.max_seq_len // BLOCK
    pool = engine.make_paged_pool(variables, SLOTS * per + 1, BLOCK)
    texts["pack"] = jax.jit(decode_engine.build_pack_prefill_fn(
        model, BLOCK, BUCKET)).lower(
        pool, jax.ShapeDtypeStruct((BUCKET // BLOCK,), jnp.int32),
        row).as_text()
    host = (jnp.zeros((SLOTS, per), jnp.int32), jnp.zeros((SLOTS,), jnp.int32),
            *decode_engine.all_forced(np.zeros((SLOTS,), np.int32),
                                      np.zeros((SLOTS, 2), np.uint32)),
            jnp.zeros((SLOTS,), bool))
    if engine.counted_step(variables):
        texts["step"] = jax.jit(decode_engine.build_paged_state_step_fn(
            model, BLOCK, 0.0, None, None)).lower(
            variables, pool, engine.make_slot_state(variables, SLOTS),
            *host).as_text()
    else:
        texts["step"] = jax.jit(decode_engine.build_paged_step_fn(
            model, BLOCK, 0.0, None, None)).lower(
            variables, pool, *host).as_text()
    return texts


def _digests(case):
    return {name: hashlib.sha256(text.encode()).hexdigest()
            for name, text in _texts(*_model(case)).items()}


@pytest.mark.parametrize("case", CASES + SPAN_CASES)
def test_programs_without_a_ring_have_the_parents_text(case):
    with open(FIXTURE) as fh:
        before = json.load(fh)
    if before["jax"] != jax.__version__:
        pytest.skip("another jax lowers to another text: digests do not carry")
    assert _digests(case) == before[case]


@pytest.mark.parametrize("case", CASES + RING_CASES)
def test_the_length_is_an_argument_only_where_a_ring_is_written(case):
    """A model that holds no ring: its contract says the prefill takes no
    length, its prefill is the function of (params, tokens) that it was, to
    the letter, and the engine handed a length runs that same program. A
    model with rings: one more argument, a scalar, and still one program a
    bucket."""
    model, variables = _model(case)
    tokens = jnp.zeros((1, BUCKET), jnp.int32)
    built = decode_engine.build_prefill_fn(model)
    contract = model.serving_contract()
    told = contract.takes_prompt_len
    assert told == (case in RING_CASES)
    kinds = {kind for kind, _axis in contract.leaf_kinds.values()}
    assert told == ("ring" in kinds)
    leaves = len(jax.tree_util.tree_leaves(variables))

    def as_before(params, prompt):
        logits, state = model.apply(
            params, prompt, decode=True, mutable=["cache"])
        return state["cache"], logits[:, -1]

    if told:
        lowered = jax.jit(built).lower(
            variables, tokens, jnp.asarray(BUCKET - 3, jnp.int32))
        assert len(jax.tree_util.tree_leaves(lowered.in_avals)) == leaves + 2
    else:
        as_before.__name__ = built.__name__
        lowered = jax.jit(built).lower(variables, tokens)
        assert len(jax.tree_util.tree_leaves(lowered.in_avals)) == leaves + 1
        assert lowered.as_text() == jax.jit(as_before).lower(
            variables, tokens).as_text()
        with pytest.raises(TypeError):
            built(variables, tokens, BUCKET - 3)
    engine = decode_engine.DecodeEngine(model, prompt_buckets=(BUCKET,))
    whole, _ = engine.prefill(variables, np.asarray(tokens))
    short, _ = engine.prefill(variables, np.asarray(tokens), BUCKET - 3)
    assert (engine.stats["prefill_compiles"],
            engine.stats["prefill_cache_hits"]) == (1, 1)
    same = all(bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.tree_util.tree_leaves(whole), jax.tree_util.tree_leaves(short)))
    assert same == (not told)


if __name__ == "__main__":
    with open(sys.argv[1], "w") as out:
        json.dump({"jax": jax.__version__,
                   "recorded": "the parent commit",
                   **{case: _digests(case) for case in CASES + SPAN_CASES}},
                  out, indent=1)
