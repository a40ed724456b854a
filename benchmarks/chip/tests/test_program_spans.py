"""The readers of the program's own spans, stamps and counters, on synthetic
records, and the clock check between records and trace events."""
import os
from types import SimpleNamespace

import pytest

import program_spans
import run
import trace_reduce
from repro.serving.tracing import Record

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "qwen05b_serve.xplane.pb")


def rec(sid, parent, name, t0, t1, **ids):
    return Record(sid, parent, name, ids, 1, t0, t1)


# an invocation: serve [0, 100] holding an admission with its first-token
# fetch, then two decode dispatches each with its fetch
RECORDS = [
    rec(1, 0, "pool.invoke", 0, 110),
    rec(2, 1, "engine.serve", 0, 100, inv=4),
    rec(3, 2, "engine.admit", 0, 20),
    rec(4, 3, "engine.fetch", 10, 15),
    rec(5, 2, "engine.decode", 20, 22),
    rec(6, 2, "engine.fetch", 22, 50),
    rec(7, 2, "engine.decode", 50, 52),
    rec(8, 2, "engine.fetch", 52, 90),
    # a dispatch of a serve begun before the records (no serve record)
    rec(9, 99, "engine.decode", 200, 202),
    rec(10, 99, "engine.fetch", 202, 300),
]


def test_self_times_subtract_the_children():
    times = program_spans.self_times(RECORDS)
    n, total, own = times["engine.serve"]
    assert n == 1 and total == pytest.approx(100e-9)
    assert own == pytest.approx((100 - 20 - 2 - 28 - 2 - 38) * 1e-9)
    assert times["pool.invoke"][2] == pytest.approx(10e-9)


def test_engine_host_ms_reads_serve_less_fetch_per_dispatch(monkeypatch):
    monkeypatch.setattr(program_spans, "records", lambda: RECORDS)
    value = run.load_reader("engine_host_ms.closed").read({})
    assert value == pytest.approx((100 - 5 - 28 - 38) / 1e6 / 2)


def test_engine_host_ms_is_silent_without_records(monkeypatch):
    monkeypatch.setattr(program_spans, "records", lambda: [])
    assert run.load_reader("engine_host_ms.closed").read({}) is None


def waits_ctx(requests, times=(1.0, 2.0, 3.0)):
    server = SimpleNamespace(completed=requests)
    return {"reports": [SimpleNamespace(t=t) for t in times],
            "cell": SimpleNamespace(gw=SimpleNamespace(server=server))}


def req(arrived, admitted, served, dropped=False):
    return SimpleNamespace(arrived_at=arrived, admitted_at=admitted, served_at=served,
                           dropped=dropped)


def test_wait_readers_split_the_queue_wait():
    ctx = waits_ctx([req(0.5, 1.0, 1.2), req(1.5, 2.0, 2.6),
                     req(0.1, 0.5, 0.6),            # a round before the window
                     req(2.9, 3.0, None, True)])    # dropped, never served
    assert run.load_reader("window_wait_ms.closed").read(ctx) == pytest.approx(500.0)
    assert run.load_reader("member_wait_ms.closed").read(ctx) == pytest.approx(400.0)


def test_wait_readers_are_silent_on_a_program_without_stamps():
    ctx = waits_ctx([SimpleNamespace(arrived_at=0.5, dropped=False)])
    assert run.load_reader("window_wait_ms.closed").read(ctx) is None
    assert run.load_reader("member_wait_ms.closed").read(ctx) is None


def test_kv_used_share_reads_the_window_between_rounds():
    def report(decode, in_use):
        counts = {"decode_calls": decode, "prefill_calls": 0, "pages_reserved": 128,
                  "pages_in_use_sum": in_use}
        return SimpleNamespace(t=0.0, engine_counts=((0, counts),))

    reader = run.load_reader("kv_used_share.closed")
    ctx = {"reports": [report(10, 999), report(30, 999 + 20 * 10)]}
    assert reader.read(ctx) == pytest.approx(100.0 * 10 / 128)
    assert reader.read({"reports": [SimpleNamespace(t=0.0)] * 3}) is None


def test_offset_check_flags_a_skewed_record():
    events = [(s - 7000, e - 7000, n, sid) for sid, _, n, _, _, s, e in RECORDS]
    good = program_spans.offset_check(program_spans.clock_offsets(RECORDS, events))
    assert good["offset_ns"] == 7000 and good["over_tol"] == 0 and good["matched"] == 10
    skewed = list(RECORDS)
    skewed[3] = skewed[3]._replace(t0_ns=skewed[3].t0_ns + 60_000)
    bad = program_spans.offset_check(program_spans.clock_offsets(skewed, events))
    assert bad["over_tol"] == 1 and bad["max_dev_ns"] == 60_000


def test_idle_goes_to_the_deepest_open_span():
    busy = [(0, 10), (20, 30), (40, 50), (70, 80)]
    events = [(0, 100, "http.request", 1), (5, 60, "window.step", 2),
              (12, 28, "engine.serve", 3), (13, 19, "engine.emit", 4),
              (35, 45, "window.plan", 5)]
    depth = {1: 0, 2: 0, 3: 2, 4: 3, 5: 1}
    idle = program_spans.attribute_idle(busy, events, depth)
    # gap 10-20: the round (the later of two roots), the serve, its emit, the
    # serve; gap 30-40: the round, then its plan; gap 50-70: the round, then
    # the request alone
    assert idle == pytest.approx({"window.step": 17e-9, "engine.serve": 2e-9,
                                  "engine.emit": 6e-9, "window.plan": 5e-9,
                                  "http.request": 10e-9})
    shares = program_spans.idle_shares(idle)
    assert shares["leaf_share"] == pytest.approx(100 * 11 / 40)
    assert shares["idle_in_engine_share"] == pytest.approx(100 * 8 / 40)


def test_idle_outside_every_span_is_no_span():
    idle = program_spans.attribute_idle([(0, 10), (20, 30), (90, 95)],
                                        [(12, 15, "engine.emit", 1)], {1: 0})
    assert idle == pytest.approx({program_spans.NO_SPAN: 67e-9, "engine.emit": 3e-9})


def test_device_busy_matches_the_trace_reduction():
    data = trace_reduce.load(TRACE)
    busy = program_spans.device_busy(data)
    summary = trace_reduce.reduce(TRACE, data=data)
    assert sum(e - s for s, e in busy) / 1e9 == pytest.approx(summary.busy_s)
    idle = program_spans.attribute_idle(busy, program_spans.host_events(data), {})
    # the recorded trace has the benchmark's spans only: no program span
    assert set(idle) == {program_spans.NO_SPAN}
