"""HTTP front-end: OpenAI wire schema, SSE streaming at decode_block cadence,
the live ingress bridge, Prometheus metrics, and parity with the in-process
serving path."""
import json
import re
import threading
import urllib.error
import urllib.request

import pytest

from repro.http import HttpFrontend, MetricsRegistry
from repro.http.protocol import (ApiError, completion_response,
                                 parse_chat_body, resolve_query_idx)
from repro.serving.online import (OnlineConfig, OnlineRobatchServer,
                                  StreamSink, WindowReport)


def _server(rb, pool, wl, **kw):
    cfg = OnlineConfig(budget_per_s=kw.pop("budget_per_s", 1e6),
                       window_s=kw.pop("window_s", 0.03), realtime=True, **kw)
    return OnlineRobatchServer(rb, pool, wl, cfg)


@pytest.fixture(scope="module")
def frontend(fitted_rb, pool, agnews):
    fe = HttpFrontend(_server(fitted_rb, pool, agnews), port=0).start()
    yield fe
    fe.stop()


@pytest.fixture(scope="module")
def base(frontend):
    return f"http://127.0.0.1:{frontend.port}"


def _post(base, payload, timeout=30.0):
    req = urllib.request.Request(
        base + "/v1/chat/completions", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def _get_json(base, path, timeout=10.0):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return json.loads(r.read())


def _sse_frames(resp):
    """Parse an SSE stream into its data payloads ([DONE] stays a sentinel)."""
    frames = []
    for line in resp:
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        payload = line[len(b"data: "):]
        frames.append("DONE" if payload == b"[DONE]" else json.loads(payload))
    return frames


# ---------------------------------------------------------------------------
# wire basics
# ---------------------------------------------------------------------------

def test_models_lists_pool_with_prices(base, pool):
    body = _get_json(base, "/v1/models")
    assert body["object"] == "list"
    names = [m["id"] for m in body["data"]]
    assert names == [m.name for m in pool]
    for m in body["data"]:
        assert m["pricing"]["input_per_1m_tokens"] > 0
        assert m["pricing"]["output_per_1m_tokens"] > 0


def test_unary_completion_roundtrip(base, pool):
    with _post(base, {"messages": [{"role": "user", "content": "#5"}],
                      "query_idx": 5}) as r:
        body = json.loads(r.read())
    assert body["object"] == "chat.completion"
    assert body["choices"][0]["finish_reason"] == "stop"
    content = body["choices"][0]["message"]["content"]
    ext = body["robatch"]
    # deterministic simulated content: "[member] qN utility=..."
    assert content == (f"[{pool[ext['model_idx']].name}] q{ext['query_idx']} "
                       f"utility={ext['utility']:.3f}")
    assert body["usage"]["total_tokens"] > 0
    assert body["id"].startswith("chatcmpl-") and body["created"] == 0


def test_bad_request_gets_openai_error_envelope(base):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(base, {"messages": []})
    assert ei.value.code == 400
    err = json.loads(ei.value.read())["error"]
    assert err["type"] == "invalid_request_error" and err["message"]


def test_unknown_route_404s(base):
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(base + "/v2/nope", timeout=10)
    assert ei.value.code == 404


def test_healthz_reports_members_and_breakers(base, pool):
    body = _get_json(base, "/healthz")
    assert body["status"] in ("ok", "degraded")
    assert [m["name"] for m in body["members"]] == [m.name for m in pool]
    for m in body["members"]:
        assert m["breaker"] == "closed"
        assert m["available"] >= 1


# ---------------------------------------------------------------------------
# SSE streaming contract
# ---------------------------------------------------------------------------

def test_stream_frames_role_chunks_finish_done(base):
    with _post(base, {"messages": [{"role": "user", "content": "#9"}],
                      "query_idx": 9, "stream": True}) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        frames = _sse_frames(r)
    assert frames[-1] == "DONE"
    chunks = frames[:-1]
    assert all(f["object"] == "chat.completion.chunk" for f in chunks)
    assert chunks[0]["choices"][0]["delta"] == {"role": "assistant"}
    deltas = [c["choices"][0]["delta"].get("content") for c in chunks[1:-1]]
    # the wire contract the bench gate also pins: >= 2 content chunks before
    # the finish frame (decode_block cadence / StreamSink split guarantee)
    assert len(deltas) >= 2 and all(deltas)
    final = chunks[-1]["choices"][0]
    assert final["finish_reason"] == "stop" and final["delta"] == {}
    assert chunks[-1]["usage"]["total_tokens"] > 0
    assert chunks[-1]["robatch"]["model"] is not None


def test_stream_content_matches_unary(base):
    q = 17
    with _post(base, {"messages": [{"role": "user", "content": f"#{q}"}],
                      "query_idx": q, "stream": True}) as r:
        frames = _sse_frames(r)
    streamed = "".join(c["choices"][0]["delta"].get("content", "")
                       for c in frames[:-1])
    with _post(base, {"messages": [{"role": "user", "content": f"#{q}"}],
                      "query_idx": q}) as r:
        unary = json.loads(r.read())["choices"][0]["message"]["content"]
    assert streamed == unary


def test_concurrent_clients_all_complete(base):
    results, errors = [], []
    lock = threading.Lock()

    def client(c):
        try:
            for i in range(3):
                q = 30 + c * 3 + i
                stream = (c + i) % 2 == 0
                body = {"messages": [{"role": "user", "content": f"#{q}"}],
                        "query_idx": q, "stream": stream}
                with _post(base, body) as r:
                    if stream:
                        frames = _sse_frames(r)
                        ok = frames[-1] == "DONE" and len(frames) >= 5
                    else:
                        ok = bool(json.loads(r.read())["choices"][0]
                                  ["message"]["content"])
                with lock:
                    results.append(ok)
        except Exception as e:   # noqa: BLE001 — collected for the assert
            with lock:
                errors.append(e)

    threads = [threading.Thread(target=client, args=(c,)) for c in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors
    assert len(results) == 18 and all(results)


# ---------------------------------------------------------------------------
# parity with the in-process serving path
# ---------------------------------------------------------------------------

def test_unary_parity_with_inprocess_serve(fitted_rb, pool, agnews):
    """The same seeded requests produce bit-identical chat.completion bodies
    over the wire and through ``Gateway.serve`` — deterministic ids, content,
    routing and billing; wall-clock latency is the single timing field."""
    from repro.api.gateway import Gateway

    qs = [3, 11, 42, 7]
    window = 0.03
    gw = Gateway(pool, agnews, artifacts=fitted_rb)
    fe = gw.serve_http(OnlineConfig(budget_per_s=1e6, window_s=window,
                                    realtime=True))
    try:
        base = f"http://127.0.0.1:{fe.port}"
        got = []
        for q in qs:       # sequential: each request rides its own window
            with _post(base, {"messages": [{"role": "user", "content": "x"}],
                              "query_idx": q}) as r:
                got.append(json.loads(r.read()))
    finally:
        fe.stop()

    test_idx = agnews.subset_indices("test")
    arrivals = [(i * window * 2, int(test_idx[q])) for i, q in enumerate(qs)]
    gw.serve(arrivals, OnlineConfig(budget_per_s=1e6, window_s=window))
    by_rid = {r.rid: r for r in gw.server.completed}
    assert sorted(by_rid) == list(range(len(qs)))
    for rid, http_body in enumerate(got):
        req = by_rid[rid]
        want = completion_response(req, pool[req.model].name, agnews)
        lat_http = http_body["robatch"].pop("latency_s")
        lat_proc = want["robatch"].pop("latency_s")
        assert lat_http >= 0.0 and lat_proc >= 0.0
        assert http_body == want


# ---------------------------------------------------------------------------
# Prometheus metrics surface
# ---------------------------------------------------------------------------

_METRIC_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+=\"[^\"]*\""
    r"(,[a-zA-Z0-9_]+=\"[^\"]*\")*\})? (-?[0-9.e+-]+|\+Inf|NaN)$")


def test_metrics_prometheus_text_parses(base, pool):
    # drive some traffic first so counters are non-trivial
    with _post(base, {"messages": [{"role": "user", "content": "#2"}],
                      "query_idx": 2}) as r:
        r.read()
    with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    helped, typed, seen = set(), set(), set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            helped.add(line.split()[2])
        elif line.startswith("# TYPE "):
            parts = line.split()
            assert parts[3] in ("counter", "gauge", "histogram")
            typed.add(parts[2])
        else:
            assert _METRIC_RE.match(line), f"unparseable sample: {line!r}"
            name = re.split(r"[{ ]", line)[0]
            base_name = re.sub(r"_(bucket|sum|count)$", "", name)
            seen.add(base_name if base_name in typed else name)
    # every sample belongs to a declared family and vice versa
    assert seen <= typed == helped
    for fam in ("robatch_requests_total", "robatch_pending_requests",
                "robatch_cost_dollars_total", "robatch_breaker_state",
                "robatch_cache_entries", "robatch_http_requests_total",
                "robatch_request_latency_seconds"):
        assert fam in typed, f"{fam} missing from /metrics"
    # satellite: per-member scheduling-pressure gauges, one per pool member
    for m in pool:
        assert f'robatch_member_pressure{{member="{m.name}"}}' in text
    # histogram buckets are cumulative and end at +Inf == _count
    bucket_lines = [ln for ln in text.splitlines()
                    if ln.startswith("robatch_http_request_seconds_bucket")]
    assert any('le="+Inf"' in ln for ln in bucket_lines)


def test_cache_hits_is_a_counter_and_queue_waits_are_split(base):
    import time

    for _ in range(2):           # the second is an exact response-cache hit
        with _post(base, {"messages": [{"role": "user", "content": "#17"}],
                          "query_idx": 17}) as r:
            r.read()
    deadline = time.monotonic() + 5.0
    while True:                  # the hit is exported at its round's end
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        hits = re.search(r"^robatch_cache_hits_total (\S+)$", text, re.M)
        if (hits and float(hits.group(1)) >= 1) or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    types = dict(line.split()[2:4] for line in text.splitlines()
                 if line.startswith("# TYPE "))
    assert types["robatch_cache_hits_total"] == "counter"
    assert float(hits.group(1)) >= 1
    assert types["robatch_queue_wait_seconds"] == "histogram"
    for stage in ("window", "member"):
        m = re.search(rf'^robatch_queue_wait_seconds_count{{stage="{stage}"}} (\d+)$',
                      text, re.M)
        assert m and int(m.group(1)) >= 1, stage
    assert types["robatch_engine_calls_total"] == "counter"
    assert types["robatch_compiles_total"] == "counter"


def test_metrics_registry_binds_to_gateway_serve(fitted_rb, pool, agnews):
    """Gateway.serve(metrics=...) populates the same registry the HTTP
    surface renders — no front-end required."""
    from repro.api.gateway import Gateway

    gw = Gateway(pool, agnews, artifacts=fitted_rb)
    reg = MetricsRegistry()
    test_idx = agnews.subset_indices("test")
    arrivals = [(0.05 * i, int(test_idx[i])) for i in range(8)]
    gw.serve(arrivals, OnlineConfig(budget_per_s=1e6, window_s=0.25),
             metrics=reg)
    text = reg.render()
    m = re.search(r'robatch_requests_total\{outcome="served"\} (\d+)', text)
    assert m and int(m.group(1)) == 8
    assert "robatch_windows_total" in text


# ---------------------------------------------------------------------------
# ingress bridge + StreamSink semantics (no HTTP involved)
# ---------------------------------------------------------------------------

def test_stream_sink_splits_unstreamed_content_into_two_chunks():
    sink = StreamSink()
    sink.finish("hello world", split=True)
    kinds = []
    while not sink.q.empty():
        kinds.append(sink.q.get_nowait())
    deltas = [p for k, p in kinds if k == "delta"]
    assert len(deltas) == 2 and "".join(deltas) == "hello world"
    assert kinds[-1] == ("done", None)


def test_stream_sink_emits_only_uncovered_tail():
    sink = StreamSink()
    sink.push("hello ")
    sink.finish("hello world", split=True)
    out = []
    while not sink.q.empty():
        out.append(sink.q.get_nowait())
    assert out == [("delta", "hello "), ("delta", "world"), ("done", None)]


def test_bridge_drains_pending_on_stop(fitted_rb, pool, agnews):
    """Stopping the bridge must not strand a waiter: pending requests are
    served (or force-dropped) before run_bridge returns."""
    srv = _server(fitted_rb, pool, agnews, window_s=0.02)
    stop = threading.Event()
    t = threading.Thread(target=srv.run_bridge, args=(stop,), daemon=True)
    t.start()
    test_idx = agnews.subset_indices("test")
    reqs = [srv.submit_request(int(test_idx[i])) for i in range(4)]
    stop.set()
    t.join(timeout=30)
    assert not t.is_alive()
    for r in reqs:
        assert r.done_event.wait(1.0)
    srv.close()


# ---------------------------------------------------------------------------
# protocol unit coverage: body parsing and the query-resolution ladder
# ---------------------------------------------------------------------------

def test_parse_chat_body_validates_shape():
    ok = parse_chat_body(json.dumps(
        {"messages": [{"role": "user", "content": "hi"}], "stream": True,
         "query_idx": 4}).encode())
    assert ok == {"content": "hi", "stream": True, "model": None,
                  "query_idx": 4, "gen": None}
    for bad in (b"not json", b"[]", b'{"messages": []}',
                b'{"messages": [{"role": "assistant", "content": "x"}]}',
                b'{"messages": [{"role": "user", "content": "x"}], '
                b'"query_idx": "seven"}'):
        with pytest.raises(ApiError):
            parse_chat_body(bad)


def test_resolve_query_idx_ladder():
    universe = [100, 101, 102, 103]
    text_index = {"what is 2+2": 102}

    def resolve(content, query_idx=None):
        return resolve_query_idx({"content": content, "query_idx": query_idx},
                                 universe, text_index)

    assert resolve("anything", query_idx=2) == 102     # explicit position
    assert resolve("what is 2+2") == 102               # exact text (index 0 ok)
    assert resolve("#1") == 101 and resolve("q3") == 103
    h = resolve("free-form question")                  # stable hash fallback
    assert h in universe and h == resolve("free-form question")
    with pytest.raises(ApiError):
        resolve("x", query_idx=99)


# ---------------------------------------------------------------------------
# engine streaming hook: decode_block cadence
# ---------------------------------------------------------------------------

def test_engine_on_tokens_hook_fires_per_decode_block():
    import jax

    from repro.config import ShardingConfig, get_arch
    from repro.models.transformer import Model
    from repro.serving.engine import Request, ServingEngine

    cfg = get_arch("tiny-s")
    model = Model(cfg, ShardingConfig(remat="none"))
    params = model.init(jax.random.PRNGKey(0))
    k = 4
    eng = ServingEngine(model, params, max_slots=2, max_len=128,
                        decode_block=k, eos_id=-1)
    blocks = {0: [], 1: []}
    done_flags = {0: [], 1: []}

    def hook(rid):
        def on_tokens(toks, done):
            blocks[rid].append(list(toks))
            done_flags[rid].append(done)
        return on_tokens

    reqs = [Request(rid=i, tokens=[1, 2, 3 + i], max_new=9,
                    on_tokens=hook(i)) for i in range(2)]
    eng.serve(reqs)
    for r in reqs:
        got = blocks[r.rid]
        assert sum(got, []) == r.out_tokens       # hook saw every token once
        assert len(got) >= 3                      # prefill + >= 2 decode blocks
        assert all(len(b) <= k for b in got)      # never more than one block
        assert done_flags[r.rid][-1] and not any(done_flags[r.rid][:-1])


# ---------------------------------------------------------------------------
# WindowReport.summary (satellite)
# ---------------------------------------------------------------------------

def test_window_report_summary_includes_kv_occupancy():
    rep = WindowReport(t=1.5, n_pending=3, n_admitted=2, n_groups=1,
                       spent=0.25, replica_counts=(1, 2),
                       kv_pages=((0, 10, 4, 1), (2, 5, 0, 0)))
    line = rep.summary()
    assert "t=1.50s" in line and "admitted=2" in line
    assert "replicas=[1, 2]" in line
    assert rep.kv_occupancy == 15
    assert "kv_pages[15 live: m0:10p/4sh/1cow m2:5p/0sh/0cow]" in line
    # simulated pools carry no kv telemetry — the field stays out of the line
    assert "kv_pages" not in WindowReport(t=0.0).summary()


# ---------------------------------------------------------------------------
# generation parsing + the documented unsupported-field contract
# ---------------------------------------------------------------------------

def _chat(**extra):
    body = {"messages": [{"role": "user", "content": "hi"}]}
    body.update(extra)
    return json.dumps(body).encode()


def test_parse_chat_body_builds_generation_config():
    from repro.serving.generation import GenerationConfig

    got = parse_chat_body(_chat(temperature=0.7, top_p=0.9, seed=5,
                                max_tokens=64))
    assert got["gen"] == GenerationConfig(max_new=64, temperature=0.7,
                                          top_p=0.9, seed=5)
    # any single sampling field is enough; the rest default
    assert parse_chat_body(_chat(seed=3))["gen"] == GenerationConfig(seed=3)
    assert parse_chat_body(_chat(max_completion_tokens=8))["gen"].max_new == 8
    # n=1 is the one accepted value of n (it's what we already do)
    assert parse_chat_body(_chat(n=1))["gen"] is None


def test_parse_chat_body_rejects_unsupported_openai_fields():
    """The documented subset contract: fields the batch-prompt plane cannot
    honor come back as a structured 400 pointing at the docs, never a
    silent ignore."""
    for field, value in (("logprobs", True), ("top_logprobs", 3),
                         ("logit_bias", {"50256": -100}), ("tools", [{}]),
                         ("tool_choice", "auto"), ("functions", [{}]),
                         ("function_call", "none"), ("stop", ["\n"]),
                         ("presence_penalty", 0.5),
                         ("frequency_penalty", 0.5), ("n", 2)):
        with pytest.raises(ApiError) as ei:
            parse_chat_body(_chat(**{field: value}))
        assert ei.value.status == 400
        assert ei.value.err_type == "unsupported_field_error"
        assert field.split("_")[0] in str(ei.value)
    # explicit null is indistinguishable from absent — accepted
    assert parse_chat_body(_chat(logprobs=None))["gen"] is None


def test_parse_chat_body_validates_sampling_ranges():
    for bad in (dict(temperature=-0.5), dict(temperature=2.5),
                dict(temperature="hot"), dict(top_p=0.0), dict(top_p=1.2),
                dict(seed=1.5), dict(seed=True), dict(max_tokens=0),
                dict(max_tokens="many")):
        with pytest.raises(ApiError) as ei:
            parse_chat_body(_chat(**bad))
        assert ei.value.status == 400


def test_frontend_stop_reports_clean_thread_exit(fitted_rb, pool, agnews):
    """A graceful stop joins the serving loop and the HTTP acceptor and
    records the clean exit in ``threads_leaked`` — the launcher's shutdown
    marker (``serve http: shutdown clean`` vs ``shutdown LEAKED``) keys off
    this list, so a wedged thread can never masquerade as a clean exit."""
    fe = HttpFrontend(_server(fitted_rb, pool, agnews), port=0).start()
    with _post(f"http://127.0.0.1:{fe.port}",
               {"messages": [{"role": "user", "content": "#1"}],
                "query_idx": 1}) as r:
        assert json.loads(r.read())["choices"]
    fe.stop()
    assert fe.threads_leaked == [], \
        f"graceful stop leaked threads: {fe.threads_leaked}"
