"""Quantile (ms) of the wait from a request's due instant (its sending, in a
closed loop) to its admission to a slot, over requests due in the window."""

from cellbench.readers._spans import admissions
from cellbench.serve import percentile


def read(run, q):
    opened, closed = run["window"]
    admitted = admissions(run)
    waits = []
    for call in run["calls"]:
        due = call["due"] or call["sent"]
        if due is not None and opened <= due < closed \
                and call["index"] in admitted:
            waits.append((admitted[call["index"]][0] - due) * 1e3)
    return percentile(waits, q) if waits else None
