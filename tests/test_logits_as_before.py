"""The latent and grouped-query models that were served before DeepSeek-V3.2
came (dots3-note, LongCat-Flash, Laguna) compute what they computed: the gate
a kind's property, the rescale, one group of experts and plain RoPE are their
defaults, and their logits — a full forward past `index_topk`, and three
one-token steps over the cache a prefill made, through the absorbed path and
the selection — are bit for bit the parent commit's, in float32 and in
bfloat16 (tests/fixtures/logits_as_before.json; to record anew, on a `git
archive` of the parent: `PYTHONPATH=<parent> python
tests/test_logits_as_before.py <out.json>` with this file; the probe tells
another machine's arithmetic apart).
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

DATA = os.path.join(ROOT, "cellbench", "tests", "data")
FIXTURE = os.path.join(ROOT, "tests", "fixtures", "logits_as_before.json")
CASES = [f"{name}.{dtype}" for name in ("tiny_dots3", "tiny_longcat", "tiny_laguna")
         for dtype in ("float32", "bfloat16")]


def _digest(array):
    return hashlib.sha256(
        np.asarray(array.astype(jnp.float32)).tobytes()).hexdigest()


def _probe():
    probe = jax.jit(lambda x: jax.nn.softmax(jnp.tanh(x @ x.T) @ x, -1))(
        jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)), jnp.float32))
    return hashlib.sha256(np.asarray(probe).tobytes()).hexdigest()


def digests(case):
    name, dtype = case.split(".")
    with open(os.path.join(DATA, name + ".json")) as fh:
        sizes = json.load(fh)
    sizes["model"] = dict(sizes["model"], dtype=dtype, param_dtype=dtype)
    model = agent.build_model(sizes)
    variables = agent.program_variables(model, sizes, 44)
    tokens = jnp.asarray(
        np.random.default_rng(44).integers(0, 256, (2, 43)), jnp.int32)
    forward = jax.jit(model.apply)(variables, tokens)

    @jax.jit
    def decode(variables, tokens):
        # a prefill of 40 from an empty cache, then a token a call
        _, state = model.apply(variables, tokens[:, :40], decode=True,
                               mutable=["cache"])
        steps = []
        for t in range(40, 43):
            logits, state = model.apply(
                {**variables, "cache": state["cache"]}, tokens[:, t:t + 1],
                decode=True, mutable=["cache"])
            steps.append(logits)
        return jnp.concatenate(steps, axis=1)

    return {"forward": _digest(forward), "steps": _digest(decode(variables, tokens))}


@pytest.mark.parametrize("case", CASES)
def test_logits_are_bit_for_bit_the_parents(case):
    with open(FIXTURE) as fh:
        before = json.load(fh)
    if _probe() != before["probe"]:
        pytest.skip("another machine's float arithmetic: digests do not carry")
    assert digests(case) == before[case]


if __name__ == "__main__":
    with open(sys.argv[1], "w") as out:
        json.dump({"probe": _probe(), **{case: digests(case) for case in CASES}},
                  out, indent=1)
