"""The program's spans of one name inside the window that took more than
`factor` times their median: how many (`what` = "count"; 0.0 for none), or
the longest span's milliseconds whether it is slow or not (`what` =
"longest_ms"). A single model step of many times the usual length decides a
whole run's tail, and this is what a run has to say about it."""

import statistics

from cellbench.readers._spans import inside


def read(run, name, what, factor=2.0):
    durations = [s["dur"] for s in inside(run["spans"], name, run["window"])]
    if not durations:
        return None
    if what == "longest_ms":
        return 1e3 * max(durations)
    limit = factor * statistics.median(durations)
    return float(sum(1 for d in durations if d > limit))
