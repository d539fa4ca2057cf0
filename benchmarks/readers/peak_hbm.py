"""``peak_bytes_in_use`` over ``bytes_limit`` on the fullest chip, percent."""


def read(ctx):
    peak, limit = ctx["memory_peak_bytes"], ctx["memory_limit_bytes"]
    if not peak or not limit:
        return None
    return 100.0 * peak / limit
