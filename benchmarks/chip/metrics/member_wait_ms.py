"""Mean milliseconds a served request waited for its member: from the start
of the round that dispatched it to its member beginning its group (the
program's stamps, ``served_at - admitted_at``: the round's own planning,
then the wait behind the groups ahead of it), over the requests the
window's rounds dispatched and a member answered."""
import program_spans


def read(ctx):
    reqs = program_spans.served_in_window(ctx)
    if not reqs:
        return None
    return 1e3 * sum(r.served_at - r.admitted_at for r in reqs) / len(reqs)
