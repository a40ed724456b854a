"""Mean milliseconds a served request waited for its window: from its
arrival at the server to the start of the scheduling round that dispatched
it (the program's stamps, ``admitted_at - arrived_at``), over the requests
the window's rounds dispatched and a member answered."""
import program_spans


def read(ctx):
    reqs = program_spans.served_in_window(ctx)
    if not reqs:
        return None
    return 1e3 * sum(r.admitted_at - r.arrived_at for r in reqs) / len(reqs)
