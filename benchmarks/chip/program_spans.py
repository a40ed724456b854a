"""The program's own spans and stamps, reduced for the per-layer readers.

The serving path records its spans itself (``repro.serving.tracing``): while a
profiler session is active each span is an in-memory record on
``time.perf_counter_ns()`` and a ``TraceAnnotation`` on the host plane of the
profiler's trace, carrying the same ``span_id``.  A ``--trace 1`` run profiles
the middle of its window, so the records cover that stretch.  Requests carry
stamps whatever the tracing (``OnlineRequest.admitted_at``, ``served_at``), and
each ``WindowReport`` the engines' cumulative counters (``engine_counts``).
On a program without these every function here returns nothing.

Reductions:

* self time of each span: its length less the part its children cover;
* per request, the window wait and the member wait from the stamps;
* device idle attributed to program spans: the device's busy union from the
  trace (as ``trace_reduce`` computes it), each instant of an idle gap under
  the innermost program span open on the host then, the deepest in the span
  tree, the latest begun among equals;
* the clock check: a record's start less its trace event's start (matched by
  ``span_id``) is one offset for every span, within ``OFFSET_TOL_NS``.

``python3 benchmarks/chip/program_spans.py --workload <cell> --seed <n>
--seconds <s>`` makes one ``--trace 1`` run of ``run.py`` in this process,
keeps the trace that run reads, and prints the result line, then these
reductions as check lines on standard error.
"""
from __future__ import annotations

import argparse
import heapq
import json
import os
import re
import statistics
import sys

import trace_reduce

OFFSET_TOL_NS = 50_000
NON_LEAF = ("http.request", "window.step", "pool.invoke", "engine.serve")
NO_SPAN = "no program span open"


def records() -> list:
    """The program's span records, [] where the program keeps none."""
    try:
        from repro.serving import tracing
    except ImportError:
        return []
    return tracing.records()


def served_in_window(ctx) -> list:
    """Requests the window's rounds dispatched and a member answered, with
    the program's stamps ([] on a program without them)."""
    reports = ctx["reports"]
    if not reports:
        return []
    lo, hi = reports[0].t, reports[-1].t
    return [r for r in ctx["cell"].gw.server.completed
            if getattr(r, "served_at", None) is not None and not r.dropped
            and lo <= r.admitted_at <= hi]


def _depths(recs) -> dict:
    parent = {r.span_id: r.parent_id for r in recs}
    depth: dict = {}
    for sid in parent:
        chain = []
        while sid in parent and sid not in depth:
            chain.append(sid)
            sid = parent[sid]
        d = depth.get(sid, -1)
        for s in reversed(chain):
            d += 1
            depth[s] = d
    return depth


def self_times(recs) -> dict:
    """Span name -> (count, total seconds, self seconds)."""
    kids: dict = {}
    for r in recs:
        kids.setdefault(r.parent_id, []).append(r)
    out: dict = {}
    for r in recs:
        covered, end = 0, r.t0_ns
        for c in sorted(kids.get(r.span_id, []), key=lambda c: c.t0_ns):
            s, e = max(c.t0_ns, end), min(c.t1_ns, r.t1_ns)
            if e > s:
                covered += e - s
                end = e
        n, total, own = out.get(r.name, (0, 0.0, 0.0))
        dur = r.t1_ns - r.t0_ns
        out[r.name] = (n + 1, total + dur / 1e9, own + (dur - covered) / 1e9)
    return out


def host_events(data) -> list:
    """(start_ns, end_ns, name, span_id) of every program span in the trace."""
    out = []
    for p in data.planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "span_id" in stats:
                    out.append((int(e.start_ns), int(e.end_ns), e.name, int(stats["span_id"])))
    out.sort()
    return out


def device_busy(data) -> list:
    """The union of the devices' leaf-op intervals, as ``trace_reduce``."""
    leaves = []
    for p in data.planes:
        if not re.fullmatch(r"/device:TPU:\d+", p.name):
            continue
        for line in p.lines:
            if line.name != "XLA Ops":
                continue
            leaves += [(int(e.start_ns), int(e.end_ns)) for e in line.events
                       if trace_reduce.op_name(e.name) not in trace_reduce.CONTAINERS]
    return trace_reduce._union(leaves)


def clock_offsets(recs, events) -> list:
    """Record start less trace event start, for each span in both."""
    start = {sid: s for s, _, _, sid in events}
    return [r.t0_ns - start[r.span_id] for r in recs if r.span_id in start]


def offset_check(offsets) -> dict:
    med = statistics.median(offsets)
    dev = [abs(o - med) for o in offsets]
    return {"offset_ns": med, "matched": len(offsets), "max_dev_ns": max(dev),
            "over_tol": sum(d > OFFSET_TOL_NS for d in dev)}


def innermost(events, depth) -> list:
    """The host timeline cut into (start, end, label) pieces, each under one
    innermost open program span (``NO_SPAN`` before, between and after)."""
    bounds = sorted({t for s, e, _, _ in events for t in (s, e)})
    open_: dict = {}          # span_id -> ((depth, start), name)
    ends: list = []           # heap of (end, span_id)
    i = 0
    pieces = [(float("-inf"), bounds[0] if bounds else float("inf"), NO_SPAN)]
    for a, b in zip(bounds, bounds[1:]):
        while i < len(events) and events[i][0] <= a:
            s, e, name, sid = events[i]
            open_[sid] = ((depth.get(sid, -1), s), name)
            heapq.heappush(ends, (e, sid))
            i += 1
        while ends and ends[0][0] <= a:
            open_.pop(heapq.heappop(ends)[1], None)
        pieces.append((a, b, max(open_.values())[1] if open_ else NO_SPAN))
    if bounds:
        pieces.append((bounds[-1], float("inf"), NO_SPAN))
    return pieces


def attribute_idle(busy, events, depth) -> dict:
    """Idle seconds between busy intervals, each instant of a gap under the
    innermost program span open on the host then."""
    pieces = innermost(events, depth)
    out: dict = {}
    j = 0
    for (_, g0), (g1, _) in zip(busy, busy[1:]):
        if g1 <= g0:
            continue
        while pieces[j][1] <= g0:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < g1:
            a, b, label = pieces[k]
            overlap = min(b, g1) - max(a, g0)
            if overlap > 0:
                out[label] = out.get(label, 0.0) + overlap / 1e9
            k += 1
    return out


def idle_shares(idle: dict) -> dict:
    total = sum(idle.values())
    if not total:
        return {}
    leaf = sum(v for k, v in idle.items() if k not in NON_LEAF and k != NO_SPAN)
    engine = sum(v for k, v in idle.items() if k.startswith("engine."))
    return {"idle_s": total, "leaf_share": 100.0 * leaf / total,
            "idle_in_engine_share": 100.0 * engine / total}


def trace_lines(recs, data) -> list[str]:
    """Check lines: the clock offset, idle by program span, self times."""
    events = host_events(data)
    lines = []
    offsets = clock_offsets(recs, events)
    if offsets:
        c = offset_check(offsets)
        lines.append(f"program spans: {c['matched']} matched to trace events, clock offset "
                     f"{c['offset_ns']} ns, widest deviation {c['max_dev_ns']} ns, "
                     f"{c['over_tol']} over {OFFSET_TOL_NS} ns")
    idle = attribute_idle(device_busy(data), events, _depths(recs))
    for name, sec in sorted(idle.items(), key=lambda kv: -kv[1]):
        lines.append(f"device idle under {name}: {sec!r} s")
    lines += [f"idle share {k}: {v!r}" for k, v in idle_shares(idle).items()]
    for name, (n, total, own) in sorted(self_times(recs).items()):
        lines.append(f"span {name}: {n} spans, {1e3 * total / n!r} ms each, "
                     f"self {1e3 * own / n!r} ms")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import run

    cell_entry, cfg, mix = run.resolve_cell(args.workload)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    run.set_compile_cache()
    kept = []
    load = trace_reduce.load

    def keep(path):
        kept.append(load(path))
        return kept[-1]

    trace_reduce.load = keep
    try:
        result, lines = run.run(cell_entry, cfg, mix, args.seed, args.seconds, True)
    except run.NoChip as e:
        print(f"program_spans.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    ours = trace_lines(records(), kept[0]) if kept else ["no trace was read"]
    for line in ours + lines:
        print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
