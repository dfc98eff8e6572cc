"""A number from the client's own clock or schedule (cellbench/serve.py,
`client_numbers`), by its key."""


def read(run, key):
    value = run["client"].get(key)
    return None if value is None else float(value)
