"""100 x the growth over the window of some `/stats` counters over the
growth of others (e.g. prompt tokens replayed over all tokens stepped)."""


def read(run, numerator, denominator):
    def grown(keys):
        return sum(run["stats_close"][k] - run["stats_open"][k] for k in keys)

    below = grown(denominator)
    return 100.0 * grown(numerator) / below if below else None
