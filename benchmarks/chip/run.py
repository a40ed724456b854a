"""Chip benchmark of the RoBatch serving path, one cell per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``) through ``BENCHMARK.json``.  Set-up builds, from the
program's public constructors, the served path a client sees: ``Model`` at the
configuration's published widths in bf16 with seeded weights, a paged
``ServingEngine``, a ``ServedPoolMember``, the modeling stage fitted against
the calibrated simulator stand-in, and ``Gateway.serve_http`` on loopback.  It
warms every program shape the mix can reach.  The window then drives streamed
chat completions from a seeded client in this process for ``--seconds``.

With ``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics (each read by
``metrics/<name>.py``) and the device trace's breakdown.  ``correct`` compares
what the window served with a plain float32 reference (``reference.py``) and
the client's answers with the served tokens; the numbers compared are printed,
each beside its limit, as the last lines of standard error and under ``checks``
in the result.  Without a TPU, or with fewer chips than the cell asks for, the
command exits 2 before serving anything.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
DRAIN_TIMEOUT_S = 120.0
BURST_S = 2.0              # the budget bucket's depth, in seconds of budget
WARM_HTTP_REQUESTS = 3     # requests through the HTTP path before the window
TRACE_S = 6.0              # the traced part of a --trace 1 window
SERVED_TOKENS = 1000       # served tokens the reference compares in each run
ROUTER, CORESET = "knn", 64   # the modeling stage fitted in set-up
BOS, EOS = 257, 258        # the byte-level wire format's <bos> and <eos> ids


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve_cell(name: str) -> tuple[dict, dict, dict]:
    """(cell, configuration file, traffic mix) for a cell of BENCHMARK.json."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, config["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return dict(cell, bench=bench), cfg, mix


def set_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless the
    environment names one; every program of the run is kept there."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX's compilation events (traces, backend compiles and
    persistent-cache loads) from its own monitoring hooks."""

    def __init__(self):
        import jax

        self.counts = {"trace": 0, "backend_compile": 0, "cache_load": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.counts["trace"] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.counts["backend_compile"] += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_load"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


# ----------------------------------------------------------------- building
def model_config(cfg: dict):
    from repro.config import ModelConfig

    hf = cfg["hf_config"]
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=hf["num_hidden_layers"],
        d_model=hf["hidden_size"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["intermediate_size"],
        vocab_size=hf["vocab_size"], activation="swiglu", norm="rmsnorm",
        norm_eps=hf["rms_norm_eps"], qkv_bias=True, rope_theta=hf["rope_theta"],
        tie_embeddings=hf["tie_word_embeddings"], dtype=cfg["serving"]["dtype"],
        source=cfg["source"])


def check_layout(model, abstract) -> None:
    """The program's parameter tree must have the layout the reference reads."""
    import jax

    prog = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    same = (jax.tree.structure(prog) == jax.tree.structure(abstract)
            and all(p.shape == a.shape and p.dtype == a.dtype for p, a in
                    zip(jax.tree.leaves(prog), jax.tree.leaves(abstract))))
    if not same:
        raise SystemExit("the program's parameter layout differs from reference.abstract_params")


class Cell:
    """Everything set-up builds for one run."""

    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float):
        import reference
        import spans
        import traffic
        import weights
        from repro.api import Gateway, RunSpec
        from repro.core.robatch import Robatch
        from repro.data.simulator import POOL_SPECS, SimulatedModel
        from repro.data.workload import BenchmarkSpec, Workload
        from repro.models.transformer import Model
        from repro.serving.batcher import BatchPromptFormatter
        from repro.serving.engine import ServingEngine
        from repro.serving.pool import ServedPoolMember, TextTask

        self.cfg, self.mix, self.seed = cfg, mix, seed
        serving = cfg["serving"]
        self.arch = reference.arch(cfg)
        model = Model(model_config(cfg))
        abstract = reference.abstract_params(self.arch)
        check_layout(model, abstract)
        params = weights.build(abstract, self.arch["d"], self.arch["tied"], seed)
        self.engine = ServingEngine(
            model, params, max_slots=serving["slots"], max_len=serving["max_len"],
            decode_block=serving["decode_block"], paged=True,
            page_size=serving["page_size"])

        self.t = traffic.generate(mix, seed, seconds)
        task = mix["task"]
        spec = BenchmarkSpec(task["name"], task["task"], task["n_classes"],
                             task["sys_tokens"], tuple(task["query_tokens"]),
                             tuple(task["out_tokens"]), tuple(task["difficulty"]),
                             task["n_topics"], task["sensitivity"])
        self.wl = Workload(name=task["name"], spec=spec, embeddings=self.t.embeddings,
                           difficulty=self.t.difficulty, topic=self.t.topic,
                           in_tokens=self.t.in_tokens, out_tokens=self.t.out_tokens,
                           sys_tokens=task["sys_tokens"], split=self.t.split)
        stand = cfg["stand_in"]
        sim_spec = next(s for s in POOL_SPECS[stand["family"]] if s["name"] == stand["name"])
        # the stand-in's context is the engine's: the calibration caps batch
        # sizes by it, as it would for the served member
        sim = SimulatedModel(**dict(sim_spec, context_len=serving["max_len"]))
        self.member = ServedPoolMember(
            cfg["name"], self.engine, BatchPromptFormatter(self.t.system_prompt),
            TextTask(self.t.queries, self.t.answers), sim.c_in, sim.c_out,
            serving["max_len"], max_answer_tokens=mix["answer_tokens_per_query"])
        self.rb = Robatch([sim], self.wl, router_kind=ROUTER, coreset_size=CORESET,
                          seed=seed).fit()
        self.gw = Gateway([self.member], self.wl, RunSpec(seed=seed), artifacts=self.rb)
        self.policy = self.gw.policy()
        self.rec = spans.Recorder()
        spans.install(self.rec, self.policy, self.member)

        load = mix["load"]
        b1 = ((task["sys_tokens"] + float(self.t.in_tokens.mean())) * sim.c_in
              + float(self.t.out_tokens.mean()) * sim.c_out) / 1e6
        if load["budget_share"] is None:
            self.budget_per_s = 1e6          # unlimited: nothing is ever shed
        else:
            self.budget_per_s = load["budget_share"] * load["rate_qps"] * b1
        self.batch_sizes = ([1] if load["budget_share"] is None
                            else [int(b) for b in self.rb.calibrations[0].grid])

    # ------------------------------------------------------------- warm-up
    def warm_prompts(self) -> list[tuple[int, int]]:
        """(prompt length, max_new) pairs whose serving hits every prefill
        bucket and every decode horizon the mix can reach, and no other."""
        import traffic

        e = self.engine
        per_q = self.mix["answer_tokens_per_query"]
        lo, hi = traffic.prompt_bounds(self.mix, self.batch_sizes)
        hi = min(hi, e.max_len - 2)
        m_max = (per_q + 1) * max(self.batch_sizes)
        K = e.decode_block
        lengths = set()
        x = lo
        while x <= hi:                       # each prefill bucket in [lo, hi]
            lengths.add(min(e._bucket_len(x), hi))
            x = e._bucket_len(x) + 1
        h = e._bucket_len(lo + 1 + K)
        while True:                          # each decode horizon reachable
            lengths.add(max(lo, min(hi, h - K - 1)))
            if h >= min(e.max_len, e._bucket_len(hi + m_max + K)):
                break
            h = e._bucket_len(h + 1)
        lengths.add(lo)
        return [(n, m_max) for n in sorted(lengths)]

    def warm(self) -> None:
        import jax

        from repro.serving.engine import Request

        for i, (n, m) in enumerate(self.warm_prompts()):
            toks = [self.engine.tok.bos] + [ord("w")] * (n - 1)
            self.engine.serve([Request(rid=-1 - i, tokens=toks, max_new=m)])
        jax.block_until_ready(self.engine.cache)


# ----------------------------------------------------------------- window
def serve_window(cell: Cell, seconds: float, trace: bool, counter: CompileCounter):
    """Serve the window; returns (setup seconds, window start, client records,
    window reports, server, compile counts inside the window, tracer)."""
    import client
    from repro.serving.online import OnlineConfig

    load = cell.mix["load"]
    fe = cell.gw.serve_http(OnlineConfig(budget_per_s=cell.budget_per_s,
                                         window_s=load["window_s"],
                                         burst_s=BURST_S, realtime=True),
                            pool=[cell.member], port=0)
    try:
        # the HTTP and scheduling code paths once, with queries no client sends
        warm_q = cell.t.split["train"][:WARM_HTTP_REQUESTS]
        client.run_load(fe.port, cell.t.queries, warm_q, loop="open", seconds=60.0,
                        due_s=[0.0] * len(warm_q))
        # the window starts with a full budget bucket: it refills from empty
        # in BURST_S seconds
        time.sleep(BURST_S)
        n_windows0 = len(cell.gw.server.windows)
        cell.rec.reset()
        cell.rec.annotate = trace
        cell.rec.on = True
        before = counter.snapshot()
        tracer = Tracer(seconds) if trace else None
        setup_s = time.perf_counter() - PROCESS_T0
        t0, recs = client.run_load(
            fe.port, cell.t.queries, cell.t.order, loop=load["loop"],
            seconds=seconds, due_s=cell.t.due_s, clients=load.get("clients", 0),
            timeout_s=DRAIN_TIMEOUT_S,
            on_start=tracer.start_at if tracer else None)
        if tracer:
            tracer.join()
        cell.rec.on = False
        after = counter.snapshot()
        reports = list(cell.gw.server.windows[n_windows0:])
        srv = cell.gw.server
    finally:
        fe.stop()
    compiles = {k: after[k] - before[k] for k in after}
    return setup_s, t0, recs, reports, srv, compiles, tracer


class Tracer:
    """Profiles a sub-window of the run (the profiler's output of a whole
    window would be hundreds of MB): ``TRACE_S`` seconds from the middle."""

    def __init__(self, seconds: float):
        self.trace_s = min(TRACE_S, seconds)
        self.offset = max(0.0, (seconds - self.trace_s) / 2)
        self.thread = None
        self.t_start = self.t_stop = None
        self.error = None

    def start_at(self, t0: float) -> None:
        import jax

        def work():
            try:
                time.sleep(max(0.0, t0 + self.offset - time.perf_counter()))
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                jax.profiler.start_trace(TRACE_DIR)
                self.t_start = time.perf_counter()
                time.sleep(self.trace_s)
                self.t_stop = time.perf_counter()
                jax.profiler.stop_trace()
            except Exception as e:   # noqa: BLE001 — reported with the result
                self.error = repr(e)

        self.thread = threading.Thread(target=work, name="bench-tracer", daemon=True)
        self.thread.start()

    def join(self) -> None:
        self.thread.join()


# ----------------------------------------------------------------- metrics
def percentile(values, q: float) -> float:
    """The q-th percentile (nearest rank) of all values."""
    import numpy as np

    v = np.sort(np.asarray(values, float))
    return float(v[min(len(v) - 1, int(np.ceil(q / 100.0 * len(v))) - 1)])


def billed_usd(cell: Cell, rec) -> float:
    """Dollars billed for the window's batch prompts (Eq. 4): every prompt
    token at the input price and every generated token at the output price,
    counted from what the engine was given and gave back."""
    m = cell.member
    tokens_in = sum(len(r.tokens) for inv in rec.invokes for r in inv["requests"])
    tokens_out = sum(len(r.out_tokens) for inv in rec.invokes for r in inv["requests"])
    return (tokens_in * m.c_in + tokens_out * m.c_out) / 1e6


def end_to_end(cell: Cell, t0: float, seconds: float, recs, reports, setup_s) -> dict:
    answered = [r for r in recs if r.answered]
    in_window = [r for r in answered if r.done <= t0 + seconds]
    spent = billed_usd(cell, cell.rec)
    out = {
        "setup_s": (setup_s, "s"),
        "queries_per_s": (len(in_window) / seconds, "queries/s"),
        "usd_per_kquery": (1000.0 * spent / max(1, len(answered)), "USD/kquery"),
    }
    if cell.mix["load"]["loop"] == "open" and answered:
        out["latency_p95_ms"] = (1e3 * percentile([r.done - r.due for r in answered], 95), "ms")
    return out


def load_reader(name: str):
    """``metrics/<name>.py``, else the reader of the quantity the name
    suffixes (``plan_ms.open`` -> ``metrics/plan_ms.py``)."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(f"metric_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise SystemExit(f"no reader metrics/{name}.py for per-layer metric {name!r}")


def per_layer(cell: Cell, cell_entry: dict, ctx: dict) -> dict:
    out = {}
    for m in cell_entry["bench"]["per_layer"]:
        if "workloads" in m and cell_entry["name"] not in m["workloads"]:
            continue
        value = load_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = (value, m["unit"])
    return out


# ------------------------------------------------------------- correctness
def parse_answers(tokens, b: int) -> list[str]:
    """The benchmark's own reading of served tokens: byte tokens up to EOS,
    split on the one-byte separator, stripped; missing answers are empty."""
    if EOS in tokens:
        tokens = tokens[: tokens.index(EOS)]
    raw = bytes(t for t in tokens if t < 256)
    parts = [p.decode("utf-8", errors="replace").strip() for p in raw.split(b";")][:b]
    return parts + [""] * (b - len(parts))


def batch_prompt(cell: Cell, batch) -> list[int]:
    """The benchmark's own batch prompt for these queries: <bos>, the system
    prompt, each query behind "\\nQ<i>:", then the answer cue "\\nA:"."""
    text = cell.t.system_prompt + "".join(
        f"\nQ{i + 1}:{cell.t.queries[q]}" for i, q in enumerate(batch)) + "\nA:"
    return [BOS] + list(text.encode())


def check_window(cell: Cell, recs, rec) -> dict:
    """Exact checks: the plan was executed as planned, every query once, in
    the batch prompt its group asks for, and each client's streamed text
    equals its answer read from the served tokens."""
    planned: dict = {}
    state_batch: dict = {}
    for p in rec.plans:
        for b, members in p["groups"]:
            planned[members] = planned.get(members, 0) + 1
            state_batch[members] = b
    invoked: dict = {}
    position: dict = {}
    prompt_bad = 0
    for inv in rec.invokes:
        invoked[inv["batch"]] = invoked.get(inv["batch"], 0) + 1
        (req,) = inv["requests"]
        if list(req.tokens) != batch_prompt(cell, inv["batch"]):
            prompt_bad += 1
        answers = parse_answers(list(req.out_tokens), len(inv["batch"]))
        for pos, q in enumerate(inv["batch"]):
            # the client is told the planned state's batch size, which a
            # plan's last partial group of that state does not fill
            position.setdefault(q, []).append((state_batch.get(inv["batch"]), answers[pos]))
    plan_bad = sum(abs(planned.get(k, 0) - invoked.get(k, 0))
                   for k in set(planned) | set(invoked))
    answer_bad = 0
    unanswered = 0
    for r in recs:
        if r.error is not None and "shed" in r.error:
            continue                              # refused by the budget: failed, not wrong
        if not r.answered:
            unanswered += 1
            continue
        served = position.get(r.query_idx, [])
        if len(served) != 1:
            plan_bad += 1
            continue
        b, text = served[0]
        if (r.text != text or r.ext.get("query_idx") != r.query_idx
                or r.ext.get("batch") != b):
            answer_bad += 1
    return {"plan_mismatches": plan_bad, "prompt_mismatches": prompt_bad,
            "answer_mismatches": answer_bad, "unanswered": unanswered}


def sample_served(cell: Cell, rec, n_tokens: int, seed: int):
    """Served requests drawn from the seed, the longest first, until they
    hold ``n_tokens`` served tokens."""
    import traffic

    reqs = [r for inv in rec.invokes for r in inv["requests"]]
    if not reqs:
        return []
    longest = max(range(len(reqs)), key=lambda i: len(reqs[i].tokens) + len(reqs[i].out_tokens))
    order = [longest] + [int(i) for i in traffic.rng_for(seed, "sample").permutation(len(reqs))
                         if i != longest]
    out, total = [], 0
    for i in order:
        if total >= n_tokens:
            break
        out.append((list(reqs[i].tokens), list(reqs[i].out_tokens)))
        total += len(reqs[i].out_tokens)
    return out


def logit_gaps(arch: dict, served, seed: int, length: int, int8: bool = False) -> list:
    """Per served token, how far its reference logit lies below the
    reference's best (with ``int8``: the int8 control's token in its place)."""
    import reference
    import weights

    params = weights.build(reference.abstract_params(arch), arch["d"], arch["tied"], seed)
    gaps = reference.served_gaps(params, arch, served, length, int8=int8)
    del params
    return gaps


def gap_stats(gaps) -> dict:
    """Readings of the served tokens' gaps: the widest, the mean, and the
    share of tokens that are not the reference's best."""
    import numpy as np

    g = np.concatenate(gaps) if gaps else np.full(1, np.inf)
    return {"max": float(g.max()), "mean": float(g.mean()),
            "flip_share": float((g > 0).mean()), "tokens": int(g.size)}


def judge(numbers: dict, limits: dict) -> bool:
    """``correct``: every number compared at or under its limit."""
    return all(numbers[k] <= limits[k] for k in limits)


# ------------------------------------------------------------------- main
def require_chips(chips: int):
    """The devices, their description and the peaks of their kind; NoChip
    where JAX finds no TPU or fewer chips than the cell asks for."""
    import jax

    import work

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if device["platform"] != "tpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {device['platform']} ({device['kind']})")
    return devs, device, work.peaks(device["kind"])


def run(cell_entry: dict, cfg: dict, mix: dict, seed: int, seconds: float,
        trace: bool) -> tuple[dict, list[str]]:
    """One run of one cell; returns (result object, check lines)."""
    import trace_reduce

    devs, device, peak = require_chips(cell_entry["chips"])
    counter = CompileCounter()
    cell = Cell(cfg, mix, seed, seconds)
    cell.warm()
    setup_s, t0, recs, reports, srv, compiles, tracer = serve_window(
        cell, seconds, trace, counter)
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    lines = [f"compiles in window: {compiles}",
             f"exact cache hits in window: {sum(w.n_cache_hits for w in reports)}",
             f"coalesced duplicates in window: {sum(w.n_coalesced for w in reports)}",
             f"windows {len(reports)} groups {sum(w.n_groups for w in reports)} "
             f"admitted {sum(w.n_admitted for w in reports)} "
             f"shed {sum(w.n_shed for w in reports)} failed {sum(w.n_failed for w in reports)}",
             f"billed: benchmark {billed_usd(cell, cell.rec)!r} USD, server "
             f"{sum(w.spent for w in reports)!r} USD",
             f"generator late: max {max((r.late_s for r in recs), default=0):.6f} s, "
             f"median {statistics.median([r.late_s for r in recs]) if recs else 0:.6f} s"]

    if trace:
        ctx = dict(cell=cell, rec=cell.rec, recs=recs, reports=reports, t0=t0,
                   seconds=seconds, arch=cell.arch, peak=peak,
                   decode_block=cell.engine.decode_block, trace=None)
        if tracer.error is None and tracer.t_start is not None:
            ctx["trace"] = trace_reduce.reduce(TRACE_DIR, cell.rec, tracer.t_start,
                                               tracer.t_stop)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        else:
            lines.append(f"trace failed: {tracer.error}")
        metrics = per_layer(cell, cell_entry, ctx)
    else:
        metrics = end_to_end(cell, t0, seconds, recs, reports, setup_s)

    checks = check_window(cell, recs, cell.rec)
    served = sample_served(cell, cell.rec, SERVED_TOKENS, seed)
    # free the program's state before the reference runs: it needs the memory
    length = cell.engine.max_len
    srv.close()
    del cell.engine.params, cell.engine.cache, srv
    cell.member.engine = None
    arch = cell.arch
    del cell
    gc.collect()
    gaps = gap_stats(logit_gaps(arch, served, seed, length) if served else [])
    limits = dict(mix["limits"])
    numbers = dict(checks, logit_gap_mean=gaps["mean"])
    correct = judge(numbers, limits)

    answered = sum(1 for r in recs if r.answered)
    result = {
        "correct": bool(correct),
        "attempted": len(recs),
        "failed": len(recs) - answered,
        "metrics": {k: {"value": float(v), "unit": u} for k, (v, u) in metrics.items()},
        "device": device,
    }
    if trace and ctx.get("trace") is not None:
        tr = ctx["trace"]
        result["device"]["busy_s"] = tr.busy_s
        result["device"]["window_s"] = tr.window_s
        result["breakdown"] = tr.breakdown()
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    lines.append(f"served tokens compared: {gaps['tokens']} in {len(served)} requests; "
                 f"gap max {gaps['max']!r}, mean {gaps['mean']!r}, share of tokens off "
                 f"the reference's best {gaps['flip_share']!r}")
    lines += [f"check {k}: {numbers[k]!r} limit {limits[k]!r}" for k in limits]
    return result, lines


class NoChip(RuntimeError):
    pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative integer")
    cell_entry, cfg, mix = resolve_cell(args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    set_compile_cache()
    try:
        result, lines = run(cell_entry, cfg, mix, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps({k: v for k, v in result.items() if k != "checks"} | {"checks": result["checks"]}))
    for line in lines:
        print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
