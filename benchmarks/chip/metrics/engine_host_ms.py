"""Host milliseconds of the paged engine per decode dispatch: the program's
``engine.serve`` spans less the ``engine.fetch`` spans inside them (the host
blocked on the device, then the transfer), over the ``engine.decode``
dispatches inside them.  The spans are recorded while the profiler runs,
so this covers the traced part of the window."""
import program_spans


def read(ctx):
    recs = program_spans.records()
    parent = {r.span_id: r.parent_id for r in recs}
    serve = {r.span_id: r for r in recs if r.name == "engine.serve"}

    def in_serve(r):
        sid = r.parent_id
        while sid and sid not in serve:
            sid = parent.get(sid, 0)
        return bool(sid)

    decodes = sum(1 for r in recs if r.name == "engine.decode" and in_serve(r))
    if not decodes:
        return None
    fetch = sum(r.t1_ns - r.t0_ns for r in recs if r.name == "engine.fetch" and in_serve(r))
    total = sum(r.t1_ns - r.t0_ns for r in serve.values())
    return (total - fetch) / 1e6 / decodes
