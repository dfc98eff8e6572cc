"""One field of the program's `/stats` when the window closes, scaled (the
bytes of per-slot state -> GB). None where the program has no such field."""


def read(run, key, scale=1.0):
    value = run["stats_close"].get(key)
    return None if value is None else float(value) * scale
