"""Share of the reserved KV pool that paged decode used: pages mapped at
each decode dispatch, summed, over dispatches times pages reserved (the
engines' counters that each ``WindowReport`` carries, between the window's
first and last rounds)."""


def read(ctx):
    reps = [r for r in ctx["reports"] if getattr(r, "engine_counts", ())]
    if len(reps) < 2:
        return None
    first, last = dict(reps[0].engine_counts), dict(reps[-1].engine_counts)
    used = slots = 0
    for k, c in last.items():
        a = first.get(k)
        if a is None or not c["pages_reserved"]:
            continue
        used += c["pages_in_use_sum"] - a["pages_in_use_sum"]
        slots += (c["decode_calls"] - a["decode_calls"]) * c["pages_reserved"]
    if not slots:
        return None
    return 100.0 * used / slots
