"""A number from the program's own span ring (``consensusml_tpu.obs.tracer``),
which records while a profiler session is open: in a ``--trace 1`` run it holds
the window's spans, on the profiler's clock. ``names`` are the spans counted,
``reduce`` is ``median`` or ``max`` over them, in milliseconds. With ``group_by``
the spans are first summed into groups, one per span of that name and thread
with what follows it there (``feed.pull`` opens a batch: the producer's work
for it is that pull and the stages and drains up to the next); the last group,
cut by the window's end, is left out. A program without the ring, or a ring
without these spans: None."""

import statistics

REDUCE = {"median": statistics.median, "max": max}


def ring():
    try:
        from consensusml_tpu.obs.tracer import get_tracer
    except ImportError:
        return []
    # a ring of before the spans went onto the profiler's clock has no start_ns
    return [e for e in get_tracer().events() if "start_ns" in e]


def read(ctx, names, reduce, group_by=None):
    spans = sorted((e for e in ring() if e["name"] in names), key=lambda e: e["start_ns"])
    if group_by is None:
        values = [e["dur_ns"] for e in spans]
    else:
        groups = {}  # thread -> [sum of each group]
        for e in spans:
            sums = groups.setdefault(e["tid"], [])
            if e["name"] == group_by:
                sums.append(0)
            if sums:
                sums[-1] += e["dur_ns"]
        values = [v for sums in groups.values() for v in sums[:-1]]
    if not values:
        return None
    return REDUCE[reduce](values) / 1e6
