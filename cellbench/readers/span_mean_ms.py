"""Mean milliseconds of the program's spans of one name inside the window."""

from cellbench.readers._spans import inside


def read(run, name):
    found = inside(run["spans"], name, run["window"])
    return 1e3 * sum(s["dur"] for s in found) / len(found) if found else None
