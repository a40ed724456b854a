"""The 95th percentile over all answered requests of ([DONE] - due), from the
client's clock: the tail beside the closed loop's throughput."""
import numpy as np


def read(ctx):
    lat = np.sort([r.done - r.due for r in ctx["recs"] if r.answered])
    if not len(lat):
        return None
    return 1e3 * float(lat[min(len(lat) - 1, int(np.ceil(0.95 * len(lat))) - 1)])
