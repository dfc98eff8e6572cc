"""`scale` x the growth over the window of some `/stats` counters over the
growth of others (a key named twice counts twice): a share in % at the
default scale, a plain ratio at 1. None where the program has no such
counter, or the denominator did not grow."""


def read(run, numerator, denominator, scale=100.0):
    before, after = run["stats_open"], run["stats_close"]
    if not all(key in stats for key in numerator + denominator
               for stats in (before, after)):
        return None

    def grown(keys):
        return sum(after[k] - before[k] for k in keys)

    below = grown(denominator)
    return scale * grown(numerator) / below if below else None
