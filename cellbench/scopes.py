"""Device time by the scope the program gave its operations.

`jax.named_scope` (and Flax's module names) end up in an operation's
`op_name`, which the profiler keeps as the `tf_op` stat of the event's
*metadata* in the `.xplane.pb` — `jax.profiler.ProfileData` shows an event's
own stats only, so this reads the protocol buffer itself, with the message
classes TensorFlow ships (loaded by file, without importing TensorFlow or
JAX: the parent process stays off both).

  `jit(step)/vmap(Transformer)/layer_3/block/attn/attention/scores/dot_general:`
      -> ("step", "Transformer", "layer_3", "block", "attn", "attention",
          "scores", "dot_general")

Everything below `load` works on plain lists, so that it can be checked on
a hand-made trace (tests/test_readers_tracing.py):
  ops      [(scope path, start_s, dur_s)]   one device's "XLA Ops" line
  modules  [(name, start_s, dur_s)]         its "XLA Modules" line
"""

from __future__ import annotations

import glob
import importlib.util
import os
import re

SCOPE_STAT = "tf_op"


def path_of(op_name: str) -> tuple:
    """The scope path of an `op_name`: transform wrappers (`jit(...)`,
    `vmap(...)`) dropped, the operation's own type after the colon too."""
    name = op_name.rsplit(":", 1)[0] if ":" in op_name else op_name
    name = re.sub(r"[\w.-]*\(|\)", "", name)
    return tuple(part for part in name.split("/") if part)


def under(path: tuple, scope: tuple) -> bool:
    """Whether `scope` occurs in `path` as consecutive parts."""
    n = len(scope)
    return any(path[i:i + n] == scope for i in range(len(path) - n + 1))


def self_seconds(ops, modules, programs) -> dict:
    """scope path -> seconds of the operations that ran inside calls of the
    compiled programs whose names start with one of `programs`, each counted
    without the operations that ran inside it (a `while` holds its body's)."""
    calls = sorted((start, start + dur) for name, start, dur in modules
                   if any(name.startswith(p) for p in programs))
    totals, stack, index = {}, [], 0  # stack of [end, path, own seconds]

    def close(until):
        while stack and stack[-1][0] <= until + 0.5e-9:  # the trace's grain
            _, path, own = stack.pop()
            totals[path] = totals.get(path, 0.0) + max(own, 0.0)

    for path, start, dur in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(start)
        while index < len(calls) and calls[index][1] <= start:
            index += 1
        if index == len(calls) or calls[index][0] > start:
            continue  # another program's operation
        if stack:
            stack[-1][2] -= dur
        stack.append([start + dur, path, dur])
    close(float("inf"))
    return totals


def _xplane_pb2():
    spec = importlib.util.find_spec("tensorflow")  # located, not imported
    if spec is None or not spec.origin:
        return None
    path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    if not os.path.exists(path):
        return None
    module_spec = importlib.util.spec_from_file_location(
        "_cellbench_xplane_pb2", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def load(directory: str):
    """(ops, modules) of the first TPU device in the newest `.xplane.pb`
    under `directory`, or None where there is no such file, no device plane
    or no way to read it. Times in seconds on the trace's clock."""
    paths = sorted(glob.glob(
        os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    pb2 = _xplane_pb2() if paths else None
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(paths[-1], "rb") as fh:
        space.ParseFromString(fh.read())
    planes = sorted((p for p in space.planes
                     if p.name.startswith("/device:TPU:")),
                    key=lambda p: p.name)
    if not planes:
        return None
    plane = planes[0]
    scope_stat = next((key for key, meta in plane.stat_metadata.items()
                       if meta.name == SCOPE_STAT), None)
    paths_by_metadata = {}
    for key, meta in plane.event_metadata.items():
        text = next((plane.stat_metadata[s.ref_value].name if s.ref_value
                     else s.str_value for s in meta.stats
                     if s.metadata_id == scope_stat), "")
        paths_by_metadata[key] = (path_of(text), meta.name)
    lines = {line.name: line for line in plane.lines}

    def events(line, pick):
        if line is None:
            return []
        base = line.timestamp_ns * 1e-9
        return [(paths_by_metadata[e.metadata_id][pick],
                 base + e.offset_ps * 1e-12, e.duration_ps * 1e-12)
                for e in line.events]

    return events(lines.get("XLA Ops"), 0), events(lines.get("XLA Modules"), 1)
