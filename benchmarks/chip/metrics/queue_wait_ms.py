"""Mean milliseconds from a query's due time at the client to the start of
the member's ``invoke_batch`` that served it: window wait, plan, and the
wait for the member's one in-flight batch."""


def read(ctx):
    due = {r.query_idx: r.due for r in ctx["recs"]}
    waits = [inv["t0"] - due[q] for inv in ctx["rec"].invokes for q in inv["batch"]
             if q in due]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
