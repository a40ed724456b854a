"""Device milliseconds per prefill call: the traced executions of the
prefill program, averaged."""


def read(ctx):
    tr = ctx["trace"]
    ex = tr.execs["prefill"] if tr else []
    if not ex:
        return None
    return sum(e.dur_ns for e in ex) / 1e6 / len(ex)
