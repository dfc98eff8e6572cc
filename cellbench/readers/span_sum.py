"""Seconds spent in the program's spans of one name, before the window
opens (`when="setup"`) or inside it (`when="window"`)."""


def read(run, name, when="setup"):
    opened, closed = run["window"]
    lo, hi = (float("-inf"), opened) if when == "setup" else (opened, closed)
    durations = [s["dur"] for s in run["spans"]
                 if s["name"] == name and lo <= s["start"] < hi]
    return sum(durations) if durations else None
