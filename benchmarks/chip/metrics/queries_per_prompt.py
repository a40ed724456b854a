"""Queries per dispatched batch prompt over the window: the server's own
``WindowReport`` counters, admitted queries over dispatched groups."""


def read(ctx):
    groups = sum(w.n_groups for w in ctx["reports"])
    if not groups:
        return None
    return sum(w.n_admitted for w in ctx["reports"]) / groups
