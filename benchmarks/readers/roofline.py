"""A kernel's (or a program's) share of its roofline: the least time the chip
could take for its work, max(operations / peak, bytes / peak), over its device
time in the trace. ``pattern`` is matched against event names on ``line``
("XLA Ops" for kernels, "XLA Modules" for whole programs); ``flops_key`` and
``bytes_key`` name the work the driver computed from shapes (either may be
absent). Work is per chip. Nothing matched, or no work: None — never 0."""


def read(ctx, pattern: str, line: str = "XLA Ops", flops_key: str | None = None,
         bytes_key: str | None = None):
    seconds, calls = ctx["trace_mod"].matching_seconds(ctx["trace"], pattern, line)
    if not calls or seconds <= 0 or not ctx["peaks"]:
        return None
    ops = ctx["stats"].get(flops_key) if flops_key else None
    moved = ctx["stats"].get(bytes_key) if bytes_key else None
    if not ops and not moved:
        return None
    least = max(
        (ops or 0.0) / ctx["peaks"]["bf16_flops_per_s"],
        (moved or 0.0) / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / seconds
