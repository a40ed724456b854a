"""Prefill flash attention's share of its roofline: causal attention over
the real prompt tokens (``work.flash_attention``, not the padded bucket) over
the kernel's traced time, summed over the prefill executions matched to the
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
    for e in tr.execs["prefill"]:
        if e.record is None or not e.kernel_ns:
            continue
        for n in np.asarray(e.record["lengths"]):
            f, b = work.flash_attention(a, int(n), module)
            flops += f
            nbytes += b
        kernel_ns += e.kernel_ns
    if not kernel_ns:
        return None
    return work.roofline_share(flops, nbytes, kernel_ns / 1e9, ctx["peak"])
