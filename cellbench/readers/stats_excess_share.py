"""Share (%) of the measured window that some intervals took beyond what as
many intervals of another kind would have: (growth of `seconds` - growth of
`count` x the mean of the other kind over the window) over the window's
length. The other kind (`base_seconds` over `base_count`) is what one
interval costs with nothing added: with the scheduler's read-to-read
counters, the time that work queued ahead of a model step (an admission's
prefill, its pack, a state write) added to the ticks it was queued in. 0.0
where no such interval fell in the window; None where the program has no
such counters, or no interval of the other kind fell in the window."""


def read(run, seconds, count, base_seconds, base_count):
    before, after = run["stats_open"], run["stats_close"]
    keys = (seconds, count, base_seconds, base_count)
    if not all(key in stats for key in keys for stats in (before, after)):
        return None
    opened, closed = run["window"]
    grown = {key: after[key] - before[key] for key in keys}
    if not grown[base_count] or closed <= opened:
        return None
    base = grown[base_seconds] / grown[base_count]
    return 100.0 * (grown[seconds] - grown[count] * base) / (closed - opened)
