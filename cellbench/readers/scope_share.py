"""Share (%) of the device self time of the compiled programs `programs`
spent in operations under the scope `scope` ("attention", or
"attention/kv_gather": consecutive parts of the operation's scope path),
from the run's own `.xplane.pb` (`cellbench/scopes.py`). Scopes, not
shapes: a change of a tensor's shape renames the operation and leaves its
scope. None where the run has no trace, or where no operation is under
`needs` — a program that does not name its operations yet."""

import os

from cellbench import scopes


def seconds_by_path(run, programs) -> dict:
    """"a/b/c" -> seconds, read once a run (and kept in its record)."""
    if not run.get("trace"):
        return None
    key = "scope_seconds:" + ",".join(programs)
    if key not in run:
        loaded = scopes.load(os.path.join(run["run_dir"], "profile"))
        run[key] = None if loaded is None else {
            "/".join(path): seconds for path, seconds
            in scopes.self_seconds(*loaded, programs).items()}
    return run[key]


def read(run, programs, scope, needs=None):
    table = seconds_by_path(run, programs)
    if not table:
        return None
    paths = {tuple(text.split("/")) if text else (): seconds
             for text, seconds in table.items()}
    if needs and not any(scopes.under(p, tuple(needs.split("/")))
                         for p in paths):
        return None
    total = sum(paths.values())
    inside = sum(seconds for p, seconds in paths.items()
                 if scopes.under(p, tuple(scope.split("/"))))
    return 100.0 * inside / total if total else None
