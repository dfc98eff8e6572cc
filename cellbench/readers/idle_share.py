"""Share (%) of the traced window in which no operation ran on the device
(mean over the devices used)."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
