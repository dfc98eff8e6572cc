"""Share (%) of the measured window spent inside the program's spans of one
name that lie in it (a blocking prefill stalls every slot's tick). None
where the program has no such span."""

from cellbench.readers._spans import inside


def read(run, name):
    opened, closed = run["window"]
    spans = inside(run["spans"], name, run["window"])
    if not spans or closed <= opened:
        return None
    return 100.0 * sum(s["dur"] for s in spans) / (closed - opened)
