"""Program spans and counters of the serving path (repro.serving.tracing):
off, a span costs one shared no-op context; on, one streamed request through
the HTTP front-end on a real paged engine leaves one linked chain of spans
from the handler to the engine's dispatches."""
import json
import re
import threading
import urllib.request

import jax
import pytest

from repro.config import ShardingConfig, get_arch
from repro.http import HttpFrontend, MetricsRegistry
from repro.http.metrics import bind_server_metrics
from repro.models.transformer import Model
from repro.serving import tracing
from repro.serving.batcher import BatchPromptFormatter
from repro.serving.engine import Request, ServingEngine
from repro.serving.online import OnlineConfig, OnlineRobatchServer
from repro.serving.pool import ServedPoolMember, TextTask

ENGINE_SPANS = ("engine.admit", "engine.prepare", "engine.decode", "engine.fetch",
                "engine.emit")


@pytest.fixture
def tracing_on():
    tracing.clear()
    tracing.enable(True)
    try:
        yield
    finally:
        tracing.enable(False)
        tracing.clear()


@pytest.fixture(scope="module")
def tiny_engine():
    model = Model(get_arch("tiny-s"), ShardingConfig(remat="none"))
    params = model.init(jax.random.PRNGKey(0))
    return ServingEngine(model, params, max_slots=2, max_len=256, decode_block=4,
                         paged=True, page_size=16)


@pytest.fixture(scope="module")
def member(tiny_engine, agnews):
    queries = [f"query {i}" for i in range(agnews.n)]
    return ServedPoolMember("tiny", tiny_engine, BatchPromptFormatter("Answer."),
                            TextTask(queries, [""] * agnews.n), 0.1, 0.4,
                            context_len=256, max_answer_tokens=4)


def _server(fitted_rb, member, agnews, **kw):
    # every index of the fitted pool served by the one engine-backed member
    cfg = OnlineConfig(budget_per_s=1e6, window_s=0.03, realtime=True, **kw)
    return OnlineRobatchServer(fitted_rb, [member] * len(fitted_rb.pool), agnews, cfg)


class _Raise:
    def __getattr__(self, name):
        raise AssertionError(f"tracing off read time.{name}")


def test_off_span_is_the_shared_noop_and_costs_nothing(monkeypatch, tiny_engine):
    """Off: no record, no TraceAnnotation built, no clock read, through
    every span site of a whole engine serve."""
    tracing.clear()

    def no_annotation(*a, **kw):
        raise AssertionError("tracing off built a TraceAnnotation")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_annotation)
    monkeypatch.setattr(tracing, "time", _Raise())
    sp = tracing.span("x", rid=1)
    assert sp is tracing.OFF
    with sp as inner:
        inner.tag(rid=2)
    tiny_engine.serve([Request(rid=0, tokens=[1, 2, 3], max_new=6)])
    assert tracing.records() == []


def test_on_spans_link_parents_within_and_across_threads(tracing_on):
    with tracing.span("a", rid=7) as a:
        with tracing.span("b") as b:
            b.tag(inv=3)
        parent = tracing.current_id()
    t = threading.Thread(target=lambda: tracing.span("c", parent=parent).__enter__()
                         .__exit__(None, None, None))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    recs = {r.name: r for r in tracing.records()}
    assert recs["a"].parent_id == 0 and recs["a"].ids == {"rid": 7}
    assert recs["b"].parent_id == a.span_id and recs["b"].ids == {"inv": 3}
    assert recs["c"].parent_id == a.span_id and recs["c"].thread != recs["a"].thread
    assert recs["a"].t0_ns <= recs["b"].t0_ns <= recs["b"].t1_ns <= recs["a"].t1_ns
    assert tracing.current_id() == 0


def test_streamed_request_leaves_one_linked_chain(tracing_on, fitted_rb, member, agnews):
    fe = HttpFrontend(_server(fitted_rb, member, agnews), port=0).start()
    try:
        body = {"messages": [{"role": "user", "content": "x"}], "query_idx": 5,
                "stream": True}
        req = urllib.request.Request(
            f"http://127.0.0.1:{fe.port}/v1/chat/completions",
            data=json.dumps(body).encode(), headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.read().rstrip().endswith(b"data: [DONE]")
    finally:
        fe.stop()
    recs = tracing.records()
    by_id = {r.span_id: r for r in recs}

    def children(rec, name):
        return [r for r in recs if r.parent_id == rec.span_id and r.name == name]

    def inside(child, parent):
        return parent.t0_ns <= child.t0_ns <= child.t1_ns <= parent.t1_ns

    (http,) = [r for r in recs if r.name == "http.request"]
    rid = http.ids["rid"]
    (invoke,) = [r for r in recs if r.name == "pool.invoke" and rid in r.ids["rids"]]
    assert invoke.ids["rids"] == (rid,) and invoke.thread != http.thread
    step = by_id[invoke.parent_id]
    assert step.name == "window.step" and step.thread != invoke.thread
    assert inside(invoke, step) and inside(invoke, http)
    assert children(step, "window.plan") and children(step, "window.dispatch")
    (serve,) = children(invoke, "engine.serve")
    assert inside(serve, invoke)
    for name in ENGINE_SPANS:
        spans = children(serve, name)
        assert spans and all(inside(s, serve) for s in spans), name
    inv = serve.ids["inv"]
    assert [r.ids["inv"] for r in children(invoke, "pool.parse")] == [inv]
    demux = [r for r in recs if r.name == "pool.demux"]
    assert demux and all(by_id[r.parent_id].name in ("engine.admit", "engine.emit")
                         for r in demux)


@pytest.fixture
def no_persistent_cache():
    """A compile served from JAX's persistent cache is no backend compile."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_new_prompt_bucket_raises_compiles_total(no_persistent_cache, fitted_rb, member,
                                                 agnews, tiny_engine):
    srv = _server(fitted_rb, member, agnews)
    reg = MetricsRegistry()
    bind_server_metrics(reg, srv)

    def prefill_compiles():
        srv.step(0.0)            # a round with nothing pending: the metrics tick
        m = re.search(r'robatch_compiles_total\{program="_prefill"\} (\d+)', reg.render())
        return int(m.group(1)) if m else 0

    try:
        tiny_engine.serve([Request(rid=0, tokens=[1] * 20, max_new=2)])
        before = prefill_compiles()
        # a prompt in a bucket this engine has not prefilled yet
        tiny_engine.serve([Request(rid=1, tokens=[1] * 200, max_new=2)])
        assert prefill_compiles() == before + 1
        assert tracing.compile_counts()["_prefill"] >= before + 1
    finally:
        srv.close()


def test_engine_counts_reach_the_metrics(fitted_rb, member, agnews, tiny_engine):
    # the engine behind the first member only: each engine counted once
    srv = OnlineRobatchServer(fitted_rb, [member] + list(fitted_rb.pool[1:]), agnews,
                              OnlineConfig(budget_per_s=1e6))
    reg = MetricsRegistry()
    bind_server_metrics(reg, srv)
    try:
        tiny_engine.serve([Request(rid=0, tokens=[1, 2, 3], max_new=6)])
        rep = srv.step(0.0)
        text = reg.render()
    finally:
        srv.close()
    counts = dict(rep.engine_counts)[0]
    assert counts["decode_calls"] == tiny_engine.n_decode_calls
    assert counts["pages_reserved"] == 32
    for program in ("decode", "prefill"):
        m = re.search(rf'robatch_engine_calls_total{{member="tiny",program="{program}"}} '
                      r"(\d+)", text)
        assert m and int(m.group(1)) == counts[f"{program}_calls"] > 0
    assert 'robatch_kv_pages{member="tiny",kind="reserved"} 32' in text


def test_kv_occupancy_reports_reservation_against_use(tiny_engine):
    e = tiny_engine
    e.serve([Request(rid=0, tokens=list(range(1, 40)), max_new=9)])
    occ = e.kv_occupancy()
    assert occ["pages_reserved"] == e.max_slots * (e.max_len // e.page_size)
    assert occ["reserved_bytes"] == occ["pages_reserved"] * occ["page_bytes"]
    assert occ["decode_calls"] == e.n_decode_calls
    assert occ["prefill_calls"] == e.n_prefill_calls
    used = occ["pages_in_use_sum"] / (occ["decode_calls"] * occ["pages_reserved"])
    assert 0 < used < 0.5        # a 48-token request maps 3-4 of 32 pages
