"""Share (%) of its roofline that a compiled program reaches: the least time
the chip could take for what one call needs — the larger of operations over
peak FLOP/s and bytes over peak bytes/s, both from `cellbench/peaks.json` for
the device the run reports — over the device time of one call. Operations
and bytes come from `cellbench/opcount/<opcount>.py`, which reads the live
arrays' types and the live token counts of this run."""

import importlib

from cellbench.readers.module_ms import seconds_and_calls


def read(run, programs, opcount):
    if not run.get("trace"):
        return None
    kind = run["device"]["kind"]
    if kind not in run["peaks"]:
        raise KeyError(f"cellbench/peaks.json has no device kind {kind!r}")
    peaks = run["peaks"][kind]
    seconds, calls = seconds_and_calls(run, programs)
    need = importlib.import_module("cellbench.opcount." + opcount).count(run)
    if not calls or need is None:
        return None
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)
