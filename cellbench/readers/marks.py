"""Seconds between two marks of the harness's own clock: `process_start`,
`ready` (the task's endpoint answers), `warm` (every shape warmed)."""


def read(run, start, end):
    return run["marks"][end] - run["marks"][start]
