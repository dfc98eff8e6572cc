"""Share (%) of the device's idle time in the traced window that falls under
host spans of the given names (`trace.idle_gaps`: each gap goes to the
deepest host span over its middle, `no_host_span` where there is none). With
the names too coarse to act on, this is how much of the idle time the
program cannot yet put down to a line of code. (A request's record lies
over its whole life on no thread's stack: a gap that has only such a record
over its middle fell between two of the scheduler's spans, and is as
coarse as `no_host_span`.)"""


def read(run, names):
    trace = run.get("trace")
    if not trace or not trace.get("idle_gaps"):
        return None
    gaps = dict(trace["idle_gaps"])  # the ten largest
    idle = trace["window_s"] - trace["busy_s_per_device"][0]
    if idle <= 0:
        return None
    return 100.0 * sum(gaps.get(name, 0.0) for name in names) / idle
