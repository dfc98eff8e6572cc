"""From a profiler trace and the program's host spans to numbers: device
busy and idle time, time per device operation, time per program, and the
idle gaps by what the host was doing in them.

Everything below `load_xplane` works on plain lists, so that it can be
checked on a small recorded trace (tests/test_trace.py):
  ops      [(name, start_s, dur_s)]  one device's "XLA Ops" line
  modules  [(name, start_s, dur_s)]  its "XLA Modules" line (one per call)
  spans    [{"name", "start", "dur", "depth"}] host spans, on the same clock
"""

from __future__ import annotations

import glob
import os
import re

SYNC_NAME = "cellbench_sync"
SHORT_GAP_S = 5e-6


def load_xplane(directory: str) -> dict:
    """{"devices": [{"name", "ops", "modules"}], "sync_s": start of the
    `cellbench_sync` annotation on the trace's clock}. Times in seconds from
    the start of the profile."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    devices, sync = [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            devices.append({
                "name": plane.name,
                "ops": _events(lines.get("XLA Ops")),
                "modules": _events(lines.get("XLA Modules")),
            })
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for event in line.events:
                    if event.name == SYNC_NAME:
                        sync = event.start_ns / 1e9
    return {"devices": devices, "sync_s": sync}


def _events(line) -> list:
    if line is None:
        return []
    return [(e.name, e.start_ns / 1e9, e.duration_ns / 1e9) for e in line.events]


def union(intervals) -> list:
    """Merged [(start, end)] of [(start, end)]."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def busy_seconds(ops, window) -> float:
    """Seconds of `window` (start, end) in which an operation ran."""
    lo, hi = window
    clipped = [(max(s, lo), min(s + d, hi)) for _, s, d in ops
               if s + d > lo and s < hi]
    return sum(b - a for a, b in union(clipped))


def op_label(name: str, module: str = "") -> str:
    """`%fusion.13 = bf16[16,1024]{...} fusion(...)` -> `<module>/fusion_bf16_16_1024_`:
    stable over the numbering of one compile."""
    match = re.match(r"%?([\w.-]+?)(?:\.\d+)* = \(?(\w+)\[([\d,]*)\]", name)
    if match:
        op, dtype, dims = match.groups()
        label = f"{op}_{dtype}_{dims.replace(',', '_')}_"
    else:
        label = re.sub(r"[^\w.-]+", "_", name)[:60]
    return f"{module}/{label}" if module else label


def module_name(name: str) -> str:
    """`jit_step(9407150873933322256)` -> `jit_step`."""
    return name.split("(")[0]


def self_times(ops, modules=()) -> dict:
    """label -> seconds, each operation counted without the operations that
    ran inside it (a `while` holds its body's)."""
    spans = sorted(modules, key=lambda m: m[1])
    totals = {}
    stack = []  # [end, label, self seconds]

    def close(until):
        while stack and stack[-1][0] <= until + 0.5e-9:  # the trace's grain
            _, label, own = stack.pop()
            totals[label] = totals.get(label, 0.0) + max(own, 0.0)

    index = 0
    for name, start, dur in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(start)
        while index < len(spans) and spans[index][1] + spans[index][2] <= start:
            index += 1
        module = ""
        if index < len(spans) and spans[index][1] <= start:
            module = module_name(spans[index][0])
        if stack:
            stack[-1][2] -= dur
        stack.append([start + dur, op_label(name, module), dur])
    close(float("inf"))
    return totals


def module_times(modules, window=None) -> dict:
    """program -> (calls, seconds) of the calls that start in `window`."""
    out = {}
    for name, start, dur in modules:
        if window is None or window[0] <= start < window[1]:
            calls, total = out.get(module_name(name), (0, 0.0))
            out[module_name(name)] = (calls + 1, total + dur)
    return out


def idle_gaps(ops, spans, window) -> dict:
    """What the host was doing in each gap between device operations:
    name of the deepest host span over the gap's middle -> seconds."""
    lo, hi = window
    busy = union([(max(s, lo), min(s + d, hi)) for _, s, d in ops
                  if s + d > lo and s < hi])
    edges = [lo] + [t for pair in busy for t in pair] + [hi]
    out = {}
    for start, end in zip(edges[0::2], edges[1::2]):
        if end <= start:
            continue
        if end - start < SHORT_GAP_S:
            name = "between_operations_under_5us"
        else:
            middle = (start + end) / 2
            over = [s for s in spans
                    if s["start"] <= middle < s["start"] + s["dur"]]
            name = max(over, key=lambda s: s["depth"])["name"] if over \
                else "no_host_span"
        out[name] = out.get(name, 0.0) + end - start
    return out


def top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def reduce(loaded: dict, spans: list, sync_perf_s: float, window_perf) -> dict:
    """The numbers the readers use. `spans` and `window_perf` (start, end) are
    on the host's perf_counter; `sync_perf_s` is that clock's reading inside
    the `cellbench_sync` annotation, which ties the two clocks."""
    if not loaded["devices"]:
        raise RuntimeError("the trace holds no device plane")
    if loaded["sync_s"] is None:
        raise RuntimeError(f"the trace holds no {SYNC_NAME} annotation")
    shift = loaded["sync_s"] - sync_perf_s  # perf_counter -> trace clock
    window = (window_perf[0] + shift, window_perf[1] + shift)
    on_trace = [dict(s, start=s["start"] + shift) for s in spans]
    busy = [busy_seconds(d["ops"], window) for d in loaded["devices"]]
    first = loaded["devices"][0]
    in_window = [o for o in first["ops"]
                 if window[0] <= o[1] and o[1] + o[2] <= window[1]]
    return {
        "window_s": window[1] - window[0],
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "modules": {k: list(v) for k, v in
                    module_times(first["modules"], window).items()},
        "device_ops": top(self_times(in_window, first["modules"])),
        "idle_gaps": top(idle_gaps(first["ops"], on_trace, window)),
    }
