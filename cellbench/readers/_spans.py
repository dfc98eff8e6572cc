"""Shared by readers that follow single requests through the program's
spans. Both clocks are the host's perf_counter, which all processes of one
machine share."""

from __future__ import annotations

import bisect


def inside(spans, name, window):
    lo, hi = window
    return [s for s in spans if s["name"] == name
            and lo <= s["start"] and s["start"] + s["dur"] <= hi]


def admissions(run) -> dict:
    """call index -> (admission time, tokens prefilled at admission).

    The program's spans carry its own request ids, not the client's; but it
    admits in the order it accepted (one tier, one priority), so the k-th
    accepted `serving/submit` is the k-th `serving/prefill`. The client's id
    rides on the submit span (`X-Request-Id`)."""
    submits = sorted((s for s in run["spans"] if s["name"] == "serving/submit"
                      and not s["args"].get("error")),
                     key=lambda s: s["start"] + s["dur"])
    prefills = sorted((s for s in run["spans"]
                       if s["name"] == "serving/prefill"),
                      key=lambda s: s["start"])
    out = {}
    for submit, prefill in zip(submits, prefills):
        tag = str(submit["args"].get("request_id") or "")
        if tag.startswith("cell-"):
            out[int(tag[5:])] = (prefill["start"], int(prefill["args"]["prefill"]))
    return out


def live_tokens_per_step(run, window) -> tuple:
    """(mean tokens in the cache of all slots, mean active slots, steps) over
    the model steps that start in `window`: each admitted request holds its
    prefilled tokens and one more for every step since, until its last."""
    steps = sorted(s["start"] for s in run["spans"]
                   if s["name"] == "serving/step")
    first = bisect.bisect_left(steps, window[0])
    last = bisect.bisect_left(steps, window[1])
    if last <= first:
        return None
    calls = {c["index"]: c for c in run["calls"]}
    tokens = slots = 0
    for index, (admitted, prefilled) in admissions(run).items():
        call = calls[index]
        begin = bisect.bisect_left(steps, admitted)
        life = call["prompt_tokens"] - prefilled + call["max_new_tokens"] - 1
        lo, hi = max(begin, first), min(begin + life, last)
        if hi > lo:
            slots += hi - lo
            # sum over steps j in [lo, hi) of prefilled + (j - begin)
            tokens += (hi - lo) * prefilled + \
                ((lo - begin) + (hi - 1 - begin)) * (hi - lo) // 2
    n = last - first
    return tokens / n, slots / n, n
