"""A number the driver left under ``stats``: an engine counter, a registry
reading, a client-side percentile. ``key`` may be dotted."""


def read(ctx, key: str, scale: float = 1.0):
    value = ctx["stats"]
    for part in key.split("."):
        if not isinstance(value, dict) or value.get(part) is None:
            return None
        value = value[part]
    return scale * float(value)
