"""Device time of the operations traced inside named program scopes, a round.

What ``tools/scope_split.py`` does by hand. A device event carries the
instruction's name, not the scope it was traced in, so the driver hands over
``stats["op_scopes"]`` = {instruction name: the ``op_name`` the compiler kept
for it} from the compiled round's own text; an event counts when its
instruction's scope names one of ``scopes`` (substrings, ``"mhc."`` for every
span of that family). Leaf operations only (a ``while`` holds its body); the
union of their intervals per device, averaged over the devices, over
``stats["rounds"]``: milliseconds a round. With ``bytes_key`` the result is
instead the share of the HBM roofline: the least time the chip could take to
move ``stats[bytes_key]`` bytes, over that device time, in percent.

None when the program has no such scopes, the driver no map, or nothing ran
under them (the metric is then left out, never 0).
"""

OPS_LINE = "XLA Ops"


def _instruction(name: str) -> str:
    return name.split(" = ")[0].strip().lstrip("%")


def scoped_seconds(trace: dict, op_scopes: dict, scopes: list, union_seconds) -> float:
    per = []
    for _, lines in sorted(trace["planes"].items()):
        events = sorted((s, s + d, name) for name, s, d in lines.get(OPS_LINE, []))
        hit = []
        for i, (start, end, name) in enumerate(events):
            if i + 1 < len(events) and events[i + 1][0] < end:
                continue  # a container: its body follows
            scope = op_scopes.get(_instruction(name), "")
            if any(s in scope for s in scopes):
                hit.append((start, end))
        if hit:
            per.append(union_seconds(hit))
    return sum(per) / len(per) if per else 0.0


def read(ctx, scopes: list, bytes_key: str | None = None):
    stats = ctx["stats"]
    op_scopes, rounds = stats.get("op_scopes"), stats.get("rounds")
    if not op_scopes or not rounds:
        return None
    seconds = scoped_seconds(ctx["trace"], op_scopes, scopes, ctx["trace_mod"].union_seconds)
    if seconds <= 0:
        return None
    if bytes_key is None:
        return 1e3 * seconds / rounds
    moved = stats.get(bytes_key)
    if not moved or not ctx["peaks"]:
        return None
    return 100.0 * (moved / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
