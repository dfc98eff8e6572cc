"""One general generator of traffic: the list of requests is a pure
function of (traffic file, vocabulary, seed, seconds).

What a run measures is fixed by the file and `--seconds` alone: how many
requests are due, which of them are members of the time-to-first-token
set, and the histogram of their lengths. Lengths are not drawn: they are
the stratified quantiles ((i + 1/2) / n) of the stated distributions. The
seed decides the token ids, which length meets which arrival, and the gaps
between arrivals (n sorted uniform draws on the span: a Poisson process
given its count).

Groups, each with its own strata, so that each group's lengths are the same
under every seed:
  lead   requests due in [-lead_in_s, 0): they occupy the server when the
         window opens, and are paid in set-up;
  member the first `member_share` of the requests due in [0, seconds);
  tail   the rest of the window: load, and gaps between tokens, but due too
         near the close for a first token to be counted on.
A closed loop has one group, `list`: requests in list order, which callers
take one after another; none is due at a time. The list has no end: it is
made block by block as callers take it, each block `block` requests, a whole
set of strata in an order of its own, so that however far a run gets through
the list, it has met nearly the same lengths, and however fast the program
is, the list does not run out. The order of lengths in the list comes from the
file's `order_seed` and not from `--seed`: a batch job is the same job under
every seed, which changes the token ids (and the weights) alone. (Drawn from
`--seed`, which long prompts happen to hold a slot during the window moves
tokens/s by several percent from seed to seed.)
"""

from __future__ import annotations

import json
import math
from statistics import NormalDist

import numpy as np


def load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def strata(dist: dict, n: int) -> list:
    """The n stratified quantiles of a clipped distribution, ascending."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    normal = NormalDist()
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        value = dist["median"] * math.exp(dist["sigma"] * z)
        out.append(int(round(min(max(value, dist["min"]), dist["max"]))))
    return out


def counts(traffic: dict, seconds: float) -> dict:
    """Sizes of an open loop's groups: a function of the file and the seconds
    alone. (A closed loop's list has no size: see `requests`.)"""
    n = int(round(traffic["rate_per_s"] * seconds))
    num, den = traffic.get("member_share", [2, 3])
    members = n * num // den
    lead = int(round(traffic["rate_per_s"] * traffic.get("lead_in_s", 0)))
    return {"lead": lead, "member": members, "tail": n - members}


def _arrivals(rng, n: int, start: float, end: float) -> list:
    return sorted(float(x) for x in start + (end - start) * rng.random(n))


def requests(traffic: dict, vocab: int, seed: int, seconds: float):
    """[{index, group, due_s, prompt, max_new_tokens}], in due order, with
    `due_s` counted from the opening of the window; for a closed loop (no
    `rate_per_s`) an iterator over its list that never ends, with `due_s`
    None."""
    rng = np.random.default_rng([int(seed), 0x63656C6C])
    if "rate_per_s" not in traffic:
        return _listed(traffic, vocab, rng)
    sizes = counts(traffic, seconds)
    window = _arrivals(rng, sizes["member"] + sizes["tail"], 0.0, seconds)
    due = {
        "lead": _arrivals(rng, sizes["lead"],
                          -float(traffic.get("lead_in_s", 0)), 0.0),
        "member": window[:sizes["member"]],
        "tail": window[sizes["member"]:],
    }
    out = []
    for group, times in due.items():
        prompts = rng.permutation(strata(traffic["prompt_tokens"], len(times)))
        outputs = rng.permutation(strata(traffic["output_tokens"], len(times)))
        for when, n_prompt, n_out in zip(times, prompts, outputs):
            out.append(_request(len(out), group, when, n_prompt, n_out,
                                rng, vocab))
    return out


def _listed(traffic: dict, vocab: int, rng):
    """A closed loop's list, block after block. Block k draws the order of
    its prompt strata, then of its output strata, from `order_seed`; token
    ids come from `--seed`, request by request in list order."""
    block = int(traffic["block"])
    order = np.random.default_rng([int(traffic["order_seed"]), 0x6F726472])
    prompt_strata = strata(traffic["prompt_tokens"], block)
    output_strata = strata(traffic["output_tokens"], block)
    index = 0
    while True:
        prompts = order.permutation(prompt_strata)
        outputs = order.permutation(output_strata)
        for n_prompt, n_out in zip(prompts, outputs):
            yield _request(index, "list", None, n_prompt, n_out, rng, vocab)
            index += 1


def _request(index, group, due_s, n_prompt, n_out, rng, vocab) -> dict:
    return {
        "index": index, "group": group, "due_s": due_s,
        "prompt": rng.integers(0, vocab, int(n_prompt)).tolist(),
        "max_new_tokens": int(n_out),
    }


def histogram(items: list, group: str) -> list:
    """Sorted prompt lengths of one group: what must not move with the seed."""
    return sorted(len(r["prompt"]) for r in items if r["group"] == group)
