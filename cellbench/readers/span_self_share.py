"""Share (%) of `outer` spans' time in the window that is not inside their
`inner` spans: the host's own part of a scheduler tick."""

from cellbench.readers._spans import inside


def read(run, outer, inner):
    outer_s = sum(s["dur"] for s in inside(run["spans"], outer, run["window"]))
    inner_s = sum(s["dur"] for s in inside(run["spans"], inner, run["window"]))
    return 100.0 * (outer_s - inner_s) / outer_s if outer_s else None
