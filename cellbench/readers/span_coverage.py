"""Share (%) of the measured window that the program's spans of one name
still cover when the harness reads them, after the window: 100 where the
earliest span of `name` that was kept began at or before the window's
opening, or where the program says it dropped no span at all (`/stats`
`spans_evicted` 0); less where the span ring dropped the window's first
seconds, and every span mean "over the window" is then one over its tail.
`name` is a span written every tick, so the first to go. None where the
program does not count what its ring drops (one ring for all names: what
is kept of one name then says nothing of another), or has no such span."""


def read(run, name):
    dropped = run["stats_close"].get("spans_evicted")
    starts = [s["start"] for s in run["spans"] if s["name"] == name]
    opened, closed = run["window"]
    if dropped is None or not starts or closed <= opened:
        return None
    if dropped == 0:
        return 100.0
    kept_from = min(max(min(starts), opened), closed)
    return 100.0 * (closed - kept_from) / (closed - opened)
