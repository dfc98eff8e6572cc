"""A field of the device's own report, scaled (peak bytes -> GB)."""


def read(run, key, scale=1.0):
    value = run["device"].get(key)
    return value * scale if value else None
