"""Device milliseconds per decode step: the traced executions of the fused
paged decode program over the steps they ran (``decode_block`` each)."""


def read(ctx):
    tr = ctx["trace"]
    ex = tr.execs["decode"] if tr else []
    if not ex:
        return None
    return sum(e.dur_ns for e in ex) / 1e6 / (len(ex) * ctx["decode_block"])
