"""Growth over the window of some top-level `/stats` counters, summed as it
is (a count, or seconds): no ratio. `needs` names counters that came with
the meaning read here: a program without them may have a counter of the
same name that counted something else (`slow_steps` before the tally was
re-based on reads with nothing queued ahead), and reads as nothing. None
where a counter of `keys` or `needs` is missing."""


def read(run, keys, needs=()):
    before, after = run["stats_open"], run["stats_close"]
    if not all(key in stats for key in tuple(keys) + tuple(needs)
               for stats in (before, after)):
        return None
    return float(sum(after[key] - before[key] for key in keys))
