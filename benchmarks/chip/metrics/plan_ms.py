"""Mean host milliseconds of one window plan (the policy's ``plan_window``),
from the benchmark's span around it, over the whole window."""


def read(ctx):
    plans = ctx["rec"].plans
    if not plans:
        return None
    return 1e3 * sum(p["t1"] - p["t0"] for p in plans) / len(plans)
