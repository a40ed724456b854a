"""From the profiler's trace to what the per-layer readers need.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``; JAX's
own ``ProfileData`` reads it.  On the TPU the device plane
(``/device:TPU:<n>``) has an ``XLA Modules`` line, one event per execution of a
compiled program (named after its jitted function, e.g. ``jit__prefill(…)``),
and an ``XLA Ops`` line, one event per operation executed.  The host planes
carry the benchmark's ``TraceAnnotation`` spans (``bench.*``).  All times are
on the trace's one clock, in nanoseconds.

The reduction keeps, for the traced window:

* busy time: the union of the op intervals, averaged over the chips (ops
  that only hold others, ``while`` and ``call``, are left out, and so is the
  ``Async XLA Ops`` line, whose copies overlap the compute);
* per program of interest (``prefill``, ``decode``), each execution in the
  window, its duration, the time of its Pallas kernels (the ops whose custom
  call target is ``tpu_custom_call``), and the host record of the dispatch that
  started it: the ``bench.prefill`` / ``bench.decode`` spans in the trace are
  paired in order with the harness's records, and each execution with the last
  such span that began before it (every dispatch is waited for before the
  next, so at most one is in flight);
* the device operations that took most time, and the idle gaps labelled by the
  innermost benchmark span open on the host at the gap's midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right
from dataclasses import dataclass, field
from typing import Optional

PROGRAMS = {"prefill": "jit__prefill(", "decode": "jit__decode_k_paged("}
SPAN_OF = {"prefill": "bench.prefill", "decode": "bench.decode"}
KERNEL = 'custom_call_target="tpu_custom_call"'     # a Pallas kernel
CONTAINERS = ("while", "conditional", "call")       # ops that hold other ops
NO_SPAN = "no benchmark span open (HTTP, client, scheduling loop)"


@dataclass
class Exec:
    start_ns: int
    dur_ns: int
    kernel_ns: int = 0
    record: Optional[dict] = None


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    execs: dict = field(default_factory=dict)          # program -> [Exec]
    top_ops: list = field(default_factory=list)        # [[name, seconds]]
    gaps: list = field(default_factory=list)           # [[label, seconds]]
    n_devices: int = 0

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops[:10], "idle_gaps": self.gaps[:10]}


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    return re.sub(r"\.\d+$", "", text.split(" = ", 1)[0].lstrip("%"))


def module_name(text: str) -> str:
    """``jit__decode_k_paged(4903…)`` -> ``decode_k_paged``."""
    return text.split("(", 1)[0].removeprefix("jit_").lstrip("_")


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def reduce(logdir_or_file: str, rec=None, t_start: float = None, t_stop: float = None,
           data=None) -> TraceSummary:
    """``rec`` is the harness's :class:`spans.Recorder` (its prefill and
    decode records are paired with the executions); ``t_start``/``t_stop``
    are the ``perf_counter`` seconds at which the trace started and stopped."""
    if data is None:
        path = logdir_or_file
        if os.path.isdir(path):
            path = find_xplane(path)
        data = load(path)

    device_planes = [p for p in data.planes if re.fullmatch(r"/device:TPU:\d+", p.name)]
    host_spans: list[tuple[int, int, str]] = []
    for p in data.planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    host_spans.append((int(e.start_ns), int(e.end_ns), e.name))
    host_spans.sort()

    busy_total = 0
    lo_hi: list[int] = []
    ops_by_kind: dict[str, float] = {}
    execs = {k: [] for k in PROGRAMS}
    all_busy: list[tuple[int, int]] = []
    for plane in device_planes:
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        modules = sorted((int(m.start_ns), int(m.end_ns), m.name)
                         for m in lines.get("XLA Modules", []))
        m_starts = [s for s, _, _ in modules]
        leaves = []
        kernels = []
        for e in lines.get("XLA Ops", []):
            name = op_name(e.name)
            if name in CONTAINERS:
                continue
            s, t = int(e.start_ns), int(e.end_ns)
            leaves.append((s, t))
            j = bisect_right(m_starts, s) - 1
            prog = module_name(modules[j][2]) if j >= 0 and s < modules[j][1] else "other"
            is_kernel = KERNEL in e.name
            label = f"{prog}:{'pallas_kernel' if is_kernel else name}"
            ops_by_kind[label] = ops_by_kind.get(label, 0.0) + (t - s) / 1e9
            if is_kernel:
                kernels.append((s, t))
        busy = _union(leaves)
        busy_total += sum(t - s for s, t in busy)
        all_busy += busy
        if busy:
            lo_hi += [busy[0][0], busy[-1][1]]
        k_starts = [s for s, _ in kernels]
        for s, t, name in modules:
            prog = next((k for k, v in PROGRAMS.items() if name.startswith(v)), None)
            if prog is None:
                continue
            i = bisect_right(k_starts, s - 1)
            k_ns = 0
            while i < len(kernels) and kernels[i][0] < t:
                k_ns += min(t, kernels[i][1]) - kernels[i][0]
                i += 1
            execs[prog].append(Exec(start_ns=s, dur_ns=t - s, kernel_ns=k_ns))

    n_dev = max(1, len(device_planes))
    if t_start is not None and t_stop is not None:
        window_s = t_stop - t_start
    elif lo_hi:
        window_s = (max(lo_hi) - min(lo_hi)) / 1e9
    else:
        window_s = 0.0
    summary = TraceSummary(window_s=window_s, busy_s=busy_total / 1e9 / n_dev,
                           n_devices=len(device_planes))
    for prog in execs:
        execs[prog].sort(key=lambda x: x.start_ns)
        if rec is not None:
            _pair(execs[prog], host_spans, SPAN_OF[prog],
                  rec.prefills if prog == "prefill" else rec.decodes, t_start)
    summary.execs = execs
    summary.top_ops = sorted(([k, v] for k, v in ops_by_kind.items()),
                             key=lambda kv: -kv[1])
    summary.gaps = _label_gaps(_union(all_busy), host_spans)
    return summary


def _pair(execs, host_spans, span_name, records, t_start) -> None:
    """Pair executions with host dispatch records through the dispatch spans
    in the trace (see the module docstring)."""
    spans = [(s, e) for s, e, n in host_spans if n == span_name]
    if not spans or not records:
        return
    first = 0
    if t_start is not None:
        t0_ns = int(t_start * 1e9)
        first = next((i for i, r in enumerate(records) if r["t0_ns"] >= t0_ns), len(records))
    recs = records[first:]
    # a span cut by the trace's start has no record of its own in the window
    n = min(len(spans), len(recs))
    spans = spans[len(spans) - n:] if len(spans) > len(recs) else spans
    span_starts = [s for s, _ in spans]
    for ex in execs:
        j = bisect_right(span_starts, ex.start_ns) - 1
        if 0 <= j < n:
            ex.record = recs[j]


def _label_gaps(busy, host_spans) -> list:
    starts = [s for s, _, _ in host_spans]
    by_label: dict[str, float] = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        if s1 <= e0:
            continue
        mid = (e0 + s1) // 2
        i = bisect_right(starts, mid)
        label = NO_SPAN
        best = None
        for s, e, n in host_spans[max(0, i - 64): i]:
            if s <= mid < e and (best is None or s > best):
                best, label = s, n
        by_label[label] = by_label.get(label, 0.0) + (s1 - e0) / 1e9
    return sorted(([k, v] for k, v in by_label.items()), key=lambda kv: -kv[1])
