"""Share (%) of its roofline that the operations under one scope reach
inside the compiled programs `programs`: the least time the chip could take
for what they need a call (`cellbench/opcount/<opcount>.py`: the larger of
operations over peak FLOP/s and bytes over peak bytes/s, `peaks.json`) over
their device self time a call, from the run's own `.xplane.pb`
(`cellbench/scopes.py`). A kernel's share where the kernel has a scope of
its own. None where the run has no trace, where no operation is under the
scope (another implementation served, or the program does not name it), or
where the opcount finds nothing to count."""

import importlib

from cellbench import scopes
from cellbench.readers.module_ms import seconds_and_calls
from cellbench.readers.scope_share import seconds_by_path


def read(run, programs, scope, opcount):
    table = seconds_by_path(run, programs)
    if not table:
        return None
    wanted = tuple(scope.split("/"))
    inside = sum(seconds for text, seconds in table.items()
                 if scopes.under(tuple(text.split("/")), wanted))
    _, calls = seconds_and_calls(run, programs)
    need = importlib.import_module("cellbench.opcount." + opcount).count(run)
    if not inside or not calls or need is None:
        return None
    kind = run["device"]["kind"]
    if kind not in run["peaks"]:
        raise KeyError(f"cellbench/peaks.json has no device kind {kind!r}")
    peaks = run["peaks"][kind]
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (inside / calls)
