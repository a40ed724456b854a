"""The trace reduction on a short trace recorded on one TPU v5e: the 0.5B
served one MMLU batch prompt (one prefill, two fused decode dispatches, the
first begun before the trace started)."""
import os
from types import SimpleNamespace

import pytest

import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "qwen05b_serve.xplane.pb")


@pytest.fixture(scope="module")
def data():
    return trace_reduce.load(TRACE)


def device_ops(data):
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    return lines["XLA Modules"], lines["XLA Ops"]


def test_executions_and_kernel_time(data):
    s = trace_reduce.reduce(TRACE, data=data)
    assert [len(s.execs["prefill"]), len(s.execs["decode"])] == [1, 2]
    modules, ops = device_ops(data)
    for prog, prefix in trace_reduce.PROGRAMS.items():
        for ex in s.execs[prog]:
            m = next(m for m in modules if int(m.start_ns) == ex.start_ns)
            assert m.name.startswith(prefix) and ex.dur_ns == int(m.end_ns) - int(m.start_ns)
            kernels = [e for e in ops if trace_reduce.KERNEL in e.name
                       and ex.start_ns <= e.start_ns < ex.start_ns + ex.dur_ns]
            assert kernels and ex.kernel_ns == sum(int(e.end_ns) - int(e.start_ns)
                                                   for e in kernels)
            assert 0 < ex.kernel_ns < ex.dur_ns


def test_busy_is_the_union_of_leaf_ops(data):
    s = trace_reduce.reduce(TRACE, data=data)
    _, ops = device_ops(data)
    leaves = [e for e in ops if trace_reduce.op_name(e.name) not in trace_reduce.CONTAINERS]
    assert 0 < s.busy_s <= sum(e.duration_ns for e in leaves) / 1e9
    assert s.busy_s <= s.window_s
    labels = [k for k, _ in s.top_ops]
    assert labels[0] == "decode_k_paged:pallas_kernel"
    assert abs(sum(v for _, v in s.top_ops)
               - sum(e.duration_ns for e in leaves) / 1e9) < 1e-6
    assert all(label.startswith("bench.") or label == trace_reduce.NO_SPAN
               for label, _ in s.gaps)


def test_executions_pair_with_dispatch_records(data):
    rec = SimpleNamespace(prefills=[{"t0_ns": 5, "id": "p"}],
                          decodes=[{"t0_ns": 1, "id": "before"}, {"t0_ns": 7, "id": "d"}])
    s = trace_reduce.reduce(TRACE, rec=rec, t_start=4e-9, t_stop=1.0, data=data)
    assert s.execs["prefill"][0].record["id"] == "p"
    # the first decode began before the trace (no span of its own in it)
    assert [e.record and e.record["id"] for e in s.execs["decode"]] == [None, "d"]


def test_op_and_module_names():
    assert trace_reduce.op_name("%fusion.126 = (f32[4]) fusion(bf16[4] %x)") == "fusion"
    assert trace_reduce.op_name("%dynamic-slice_bitcast_fusion.25 = bf16[2]") == \
        "dynamic-slice_bitcast_fusion"
    assert trace_reduce.module_name("jit__decode_k_paged(4903537541174472928)") == \
        "decode_k_paged"
