"""Paged decode attention's share of its roofline: the least time the chip
needs for the live context of the active slots (``work.paged_attention``) over
the kernel's traced time, summed over the decode executions matched to the
host's dispatch records."""
import numpy as np

import work


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    a, module = ctx["arch"], ctx["arch_module"]
    flops = nbytes = 0.0
    kernel_ns = 0
    for e in tr.execs["decode"]:
        if e.record is None or not e.kernel_ns:
            continue
        valid = np.asarray(e.record["valid"])            # (K, slots)
        for slot, context in e.record["context"].items():
            for k in np.nonzero(valid[:, slot])[0]:
                f, b = work.paged_attention(a, context + int(k), module)
                flops += f
                nbytes += b
        kernel_ns += e.kernel_ns
    if not kernel_ns:
        return None
    return work.roofline_share(flops, nbytes, kernel_ns / 1e9, ctx["peak"])
