"""A number the program attached to its own spans: the numeric argument ``arg``
of the ring's spans named in ``names`` (``readers/spans.py`` says when the ring
records), in the order they started. ``reduce`` is ``max``, ``first`` or
``last`` over them. With ``pct_of_memory_limit`` the result is that number over
``ctx["memory_limit_bytes"]``, in percent: bytes the allocator read
(``feed.stage``'s ``hbm_in_use`` and ``hbm_peak``, taken on the feed's thread
while a round runs) against the chip's limit. A program without the ring, a ring
without these spans, spans without the argument (the parent's; a backend with
no ``memory_stats()``), or no limit where one is asked for: None."""

from readers.spans import ring

REDUCE = {"max": max, "first": lambda values: values[0], "last": lambda values: values[-1]}


def read(ctx, names, arg, reduce, pct_of_memory_limit=False):
    spans = sorted((e for e in ring() if e["name"] in names), key=lambda e: e["start_ns"])
    values = [e.get("args", {}).get(arg) for e in spans]
    values = [v for v in values if isinstance(v, (int, float)) and not isinstance(v, bool)]
    if not values:
        return None
    value = REDUCE[reduce](values)
    if not pct_of_memory_limit:
        return value
    limit = ctx.get("memory_limit_bytes")
    if not limit:
        return None
    return 100.0 * value / limit
