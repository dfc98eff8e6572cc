"""Device milliseconds per call of the compiled programs whose names start
with one of `programs`, in the traced part of the window."""


def seconds_and_calls(run, programs):
    calls = seconds = 0
    for name, (n, s) in (run["trace"] or {}).get("modules", {}).items():
        if any(name.startswith(p) for p in programs):
            calls, seconds = calls + n, seconds + s
    return seconds, calls


def read(run, programs):
    if not run.get("trace"):
        return None
    seconds, calls = seconds_and_calls(run, programs)
    return 1e3 * seconds / calls if calls else None
