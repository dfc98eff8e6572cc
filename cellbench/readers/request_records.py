"""The program's own account of single requests, joined to the client's
calls by id: the client sends `X-Request-Id: cell-<index>`, and the program
writes that id on the request's `serving/admission` record (when it takes a
slot) and `serving/first_token` record (when its first token is pushed; its
arguments split the wait into `queue_wait_ms`, `prefill_ms` and `replay_ms`).
No pairing by order: a prefix hit or a chunked prefill opens no
`serving/prefill` span, and is still found here.

`what`:
  "queue_wait_ms" | "prefill_ms" | "replay_ms" | "ttft_ms"
      that part of the median time to first token: the mean of the part over
      the member(s) at the median rank(s) of the client's own TTFT, so the
      three parts add up to the program's view of `ttft_p50_ms`
  "admit_wait"
      the `q` quantile (ms) of due instant -> admission over requests due in
      the window: `sched_queue_wait_p90_ms` by id instead of by order
"""

from cellbench.serve import percentile


def records(run, name) -> dict:
    """call index -> the record's span, for the client's own requests."""
    out = {}
    for span in run["spans"]:
        tag = str(span["args"].get("request_id") or "")
        if span["name"] == name and tag.startswith("cell-"):
            out[int(tag[5:])] = span
    return out


def median_members(run, first_tokens) -> list:
    """The calls at the median rank(s) of the client's TTFT over the timed
    set (members, or all calls due in the window), misses last — the ranks
    `serve.percentile(..., 0.5)` averages."""
    opened, closed = run["window"]
    members = [c for c in run["calls"] if c["group"] == "member"]
    timed = members or [c for c in run["calls"] if c["sent"] is not None
                        and opened <= (c["due"] or c["sent"]) < closed]
    ranked = sorted(
        timed, key=lambda c: (c["first"] - (c["due"] or c["sent"]))
        if c["first"] is not None and c["first"] <= closed else float("inf"))
    if not ranked:
        return []
    middle = [ranked[(len(ranked) - 1) // 2], ranked[len(ranked) // 2]]
    if any(c["first"] is None or c["first"] > closed
           or c["index"] not in first_tokens for c in middle):
        return []
    return middle


def read(run, what, q=0.9):
    if what == "admit_wait":
        opened, closed = run["window"]
        admitted = records(run, "serving/admission")
        waits = []
        for call in run["calls"]:
            due = call["due"] or call["sent"]
            if due is not None and opened <= due < closed \
                    and call["index"] in admitted:
                waits.append((admitted[call["index"]]["start"] - due) * 1e3)
        return percentile(waits, q) if waits else None
    first_tokens = records(run, "serving/first_token")
    middle = median_members(run, first_tokens)
    if not middle:
        return None
    return sum(first_tokens[c["index"]]["args"][what]
               for c in middle) / len(middle)
