"""Growth over the window of one `/stats` counter over the growth of another
(e.g. live KV tokens read per slot-step). None where the program has no
such counter, or the denominator did not grow."""


def read(run, numerator, denominator):
    before, after = run["stats_open"], run["stats_close"]
    if not all(key in stats for key in (numerator, denominator)
               for stats in (before, after)):
        return None
    below = after[denominator] - before[denominator]
    return (after[numerator] - before[numerator]) / below if below else None
