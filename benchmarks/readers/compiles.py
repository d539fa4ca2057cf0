"""Seconds of set-up spent building programs, from the program's compile log
(``consensusml_tpu.obs.compile_log``: one record per top-level program with
its seconds of tracing, lowering and in the backend, and when it ended).
``fields`` are summed over the programs that ended before the window's first
ring span: the reference's own programs, compiled by ``check()`` after the
window, are not set-up. The ring marks the window only if it holds nothing
else: where a program was built among its spans (a ring that already recorded
during set-up, through a sink or an earlier session) the first span is no
window's start, and the answer is None, as it is for a program without the
log or a ring without spans."""


def read(ctx, fields):
    try:
        from consensusml_tpu.obs.compile_log import get_compile_log
        from consensusml_tpu.obs.tracer import get_tracer
    except ImportError:
        return None
    log = get_compile_log()
    # the log's own jax.* spans mark no window; a ring of before the spans
    # went onto the profiler's clock has no start_ns
    spans = [
        e for e in get_tracer().events()
        if "start_ns" in e and not e["name"].startswith("jax.")
    ]
    if log is None or not spans:
        return None
    first = min(e["start_ns"] for e in spans)
    last = max(e["start_ns"] + e["dur_ns"] for e in spans)
    records = log.records()
    if any(first < r["end_ns"] <= last for r in records):
        return None
    before = [r for r in records if r["end_ns"] <= first]
    if not before:
        return None
    return sum(r[f] for r in before for f in fields)
