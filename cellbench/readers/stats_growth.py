"""Growth over the window of every counter of one `/stats` section whose
key ends in `suffix` (e.g. the engine's compiles of every kind)."""


def read(run, section, suffix):
    before, after = run["stats_open"].get(section), run["stats_close"].get(section)
    if not before or not after:
        return None
    return float(sum(after[k] - before.get(k, 0) for k in after
                     if k.endswith(suffix)))
