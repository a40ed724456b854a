"""Model FLOP utilization of the traced window: the model flops of the
prefill and decode tokens whose executions lie in the trace
(``work.prefill_flops``, ``work.decode_flops``), over the window's length at
the chip's bf16 peak."""
import numpy as np

import work


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr.window_s <= 0:
        return None
    a = ctx["arch"]
    flops = 0.0
    for e in tr.execs["prefill"]:
        if e.record is not None:
            flops += sum(work.prefill_flops(a, int(n)) for n in np.asarray(e.record["lengths"]))
    for e in tr.execs["decode"]:
        if e.record is None:
            continue
        valid = np.asarray(e.record["valid"])
        for slot, context in e.record["context"].items():
            flops += sum(work.decode_flops(a, context + int(k))
                         for k in np.nonzero(valid[:, slot])[0])
    if not flops:
        return None
    return 100.0 * flops / (tr.window_s * ctx["peak"]["bf16_flops_per_s"])
