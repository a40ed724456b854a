"""The benchmark's own spans and records around the program's calls.

Nothing of the program is changed: the harness wraps a few bound calls of the
objects it built (the policy's window plan, the member's batch invocation, the
engine's serve, prefill and fused decode, the streaming demultiplexer) so that
each call leaves a record — start and end on ``time.perf_counter()``, and what
the correctness check and the per-layer readers need.  In a traced run each
span is also a ``jax.profiler.TraceAnnotation``, so it lands in the profiler's
trace on the same clock as the device's operations.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time

import jax


class Recorder:
    def __init__(self):
        self.annotate = False
        self.on = False                  # record only inside the window
        self.plans: list[dict] = []      # one per window plan
        self.invokes: list[dict] = []    # one per member batch invocation
        self.prefills: list[dict] = []
        self.decodes: list[dict] = []
        self._local = threading.local()
        self._lock = threading.Lock()

    def reset(self):
        for lst in (self.plans, self.invokes, self.prefills, self.decodes):
            lst.clear()

    def span(self, name: str):
        """A profiler span in a traced run, nothing otherwise."""
        if self.annotate:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


def install(rec: Recorder, policy, member) -> None:
    """Wrap the calls named in the module docstring on these instances."""
    engine = member.engine

    plan_window = policy.plan_window

    @functools.wraps(plan_window)
    def plan(space, query_idx, budget, *a, **kw):
        with rec.span("bench.plan"):
            t0 = time.perf_counter()
            out = plan_window(space, query_idx, budget, *a, **kw)
            t1 = time.perf_counter()
        if rec.on:
            rec.plans.append({"t0": t0, "t1": t1, "groups": [
                (int(s.batch), tuple(int(q) for q in m)) for s, m in out.groups]})
        return out

    policy.plan_window = plan

    invoke_batch = member.invoke_batch

    @functools.wraps(invoke_batch)
    def invoke(wl, batch_idx, *a, **kw):
        entry = {"t0": time.perf_counter(), "batch": tuple(int(q) for q in batch_idx),
                 "requests": []}
        rec._local.invoke = entry
        try:
            with rec.span("bench.invoke_batch"):
                out = invoke_batch(wl, batch_idx, *a, **kw)
        finally:
            rec._local.invoke = None
        entry["t1"] = time.perf_counter()
        entry["answers"] = list(out.answers) if out.answers is not None else None
        if rec.on:
            with rec._lock:
                rec.invokes.append(entry)
        return out

    member.invoke_batch = invoke

    serve = engine.serve

    @functools.wraps(serve)
    def serve_wrapped(requests, *a, **kw):
        entry = getattr(rec._local, "invoke", None)
        if entry is not None:
            entry["requests"].extend(requests)
        with rec.span("bench.engine_serve"):
            return serve(requests, *a, **kw)

    engine.serve = serve_wrapped

    prefill = engine._prefill

    def prefill_wrapped(params, tokens, lengths, max_len):
        t0 = time.perf_counter_ns()
        with rec.span("bench.prefill"):
            out = prefill(params, tokens, lengths, max_len)
        if rec.on:
            rec.prefills.append({"t0_ns": t0, "lengths": lengths})
        return out

    engine._prefill = prefill_wrapped

    decode = engine._decode_k_paged

    def decode_wrapped(params, cache, table, last_tok, active, n_out, limit, **kw):
        # live context per slot at dispatch (the host's own bookkeeping):
        # step k of the fused block attends context[i] + k keys
        context = {i: len(r.tokens) + len(r.out_tokens)
                   for i, r in enumerate(engine.slot_req) if r is not None}
        t0 = time.perf_counter_ns()
        with rec.span("bench.decode"):
            out = decode(params, cache, table, last_tok, active, n_out, limit, **kw)
        if rec.on:
            rec.decodes.append({"t0_ns": t0, "context": context, "valid": out[3]})
        return out

    engine._decode_k_paged = decode_wrapped

    stream_demux = member._stream_demux

    def demux(b, streams):
        on_tokens = stream_demux(b, streams)

        def wrapped(new_ids, done):
            with rec.span("bench.demux"):
                on_tokens(new_ids, done)

        return wrapped

    member._stream_demux = demux
