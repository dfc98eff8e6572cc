"""The comparison that decides `correct` for a served model, as a child
process that takes the chip once the server has let go of it.

It reads a sample of the requests the window finished (prompt and served
tokens), makes the weights again from the seed, runs the plain reference
once over each prompt with its served tokens, and reports how far each
served token's logit lies below the reference's best: the mean gap, the
99th percentile and the widest gap. With `lower` in its input (the control)
it reads, at the same positions, the gaps of the tokens that the reference in
that lower precision puts first, in the served tokens' place.

    python -m cellbench.check <input.json>   # last stdout line: the numbers
"""

from __future__ import annotations

import importlib
import json
import sys

ROW_PAD = 128
SEQ_PAD = 512


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def gaps(reference, weights, sizes, samples, lower=None):
    """Per sample, the gaps of the served tokens (`lower` None) or of the
    tokens the lower precision puts first, below the reference's best."""
    import jax.numpy as jnp
    import numpy as np

    out = []
    for sample in samples:
        prompt, served = sample["prompt"], sample["tokens"]
        sequence = np.zeros(_pad(len(prompt) + len(served) - 1, SEQ_PAD), np.int32)
        sequence[:len(prompt)] = prompt
        sequence[len(prompt):len(prompt) + len(served) - 1] = served[:-1]
        rows = np.full(_pad(len(served), ROW_PAD), len(prompt) - 1, np.int32)
        rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        exact = reference.logits(weights, jnp.asarray(sequence), sizes,
                                 jnp.asarray(rows))
        if lower is None:
            chosen = np.asarray(served, np.int32)
        else:
            chosen = np.asarray(jnp.argmax(reference.logits(
                weights, jnp.asarray(sequence), sizes, jnp.asarray(rows),
                lower=lower), -1))[:len(served)]
        exact = np.asarray(exact)[:len(served)]
        out.append(exact.max(-1) - exact[np.arange(len(served)), chosen])
    return out


def summary(per_sample) -> dict:
    import numpy as np

    flat = np.concatenate(per_sample)
    return {"gap_max": float(flat.max()), "gap_mean": float(flat.mean()),
            "gap_p99": float(np.quantile(flat, 0.99)),
            "tokens": int(flat.size), "first_choice_share":
            float((flat == 0).mean())}


def main(argv=None) -> int:
    path = (argv or sys.argv[1:])[0]
    with open(path) as fh:
        task = json.load(fh)
    import jax

    from cellbench import weights as weights_lib

    sizes = task["sizes"]
    reference = importlib.import_module(
        "cellbench.reference." + task["reference"])
    weights = weights_lib.make(sizes, task["seed"])
    result = summary(gaps(reference, weights, sizes, task["samples"],
                          lower=task.get("lower")))
    result["platform"] = jax.devices()[0].platform
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
