"""Bring-up check of the serving path on one TPU chip.

    python chip_smoke.py [--seed N]

One process, two phases, through the entry points a user calls:

1. **engine** — ``serve engine --arch qwen1.5-0.5b`` at its published widths
   in bf16 (random weights from ``--seed``): paged KV, page size 16, 8 slots,
   ``max_len`` 1024, 8-token decode blocks, 16 batch prompts of 4 queries
   under one system prompt.  Checks that every request finishes, that the
   shared prefix was stored once, that the compiled prefill and fused decode
   programs hold the Pallas kernels, and that those kernels agree with the
   float32 oracles of ``repro.kernels.ref`` at the shapes served.
2. **http** — the router's path: ``Gateway.from_spec`` over the trained tiny
   pool, ``serve_http`` on an ephemeral port, 8 chat completions (2 of them
   streamed) from client threads.  Checks that every response is a complete
   200, that no dispatch failed and no request was dropped, that every
   breaker is closed and that shutdown is clean.

Each phase prints its device kind, compile and run seconds, tokens served,
peak device memory and whether the kernels were present.  The last line of a
passing run is ``{"ok": true, "device": {...}}``; any failed check exits
non-zero.  Without a TPU the script exits non-zero before serving anything.
"""
import argparse
import json
import os
import sys
import threading
import time
import urllib.request

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

ENGINE_ARGS = ["--arch", "qwen1.5-0.5b", "--requests", "16", "--batch-prompt", "4",
               "--max-new", "32", "--slots", "8", "--max-len", "1024",
               "--decode-block", "8", "--page-size", "16"]
# bf16 kernel outputs against float32 oracles: |got - want| <= ATOL + RTOL*|want|.
# The kernels accumulate in float32; what is left is bf16 rounding of the
# probabilities fed to the MXU and of the output (2^-8 relative), on O(1) values.
ATOL = RTOL = 2e-2
TINY_STEPS = 20          # tiny-pool training steps: the router's utilities do not matter here
N_HTTP, N_STREAMED = 8, 2


class Checks:
    """Prints one line per check and remembers the failures."""

    def __init__(self, phase: str):
        self.phase = phase
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"{self.phase}: check {name}: {'passed' if ok else 'FAILED'}"
              f"{' — ' + detail if detail else ''}", flush=True)
        if not ok:
            self.failed.append(f"{self.phase}/{name}")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    reads included), from its own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration_secs, **_):
        if event in self.EVENTS:
            with self._lock:
                self.seconds += duration_secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


def record_served_shapes(engine, shapes: dict) -> None:
    """Wrap the engine's prefill and fused paged decode so the last call of
    each leaves its argument shapes (with their shardings) in ``shapes``."""
    import jax

    def abstract(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        return x

    for name in ("_prefill", "_decode_k_paged"):
        fn = getattr(engine, name)

        def call(*args, _fn=fn, _name=name, **kw):
            shapes[_name] = (_fn, jax.tree.map(abstract, args), kw)
            return _fn(*args, **kw)

        setattr(engine, name, call)


def kernels_in_programs(shapes: dict, check: Checks) -> bool:
    """Compile each recorded program at its served shapes and look for the
    Pallas kernels (``tpu_custom_call``) in the executable."""
    present = True
    for name, (fn, args, kw) in sorted(shapes.items()):
        compiled = fn.lower(*args, **kw).compile()
        ok = "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        check(f"kernel in {name}", ok,
              f"temp {mem.temp_size_in_bytes} B, arguments "
              f"{mem.argument_size_in_bytes} B")
        present &= ok
    check("programs recorded", len(shapes) == 2, ", ".join(sorted(shapes)))
    return present and len(shapes) == 2


def within_tolerance(got, want):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.abs(got - want)
    return bool(np.all(err <= ATOL + RTOL * np.abs(want))), float(err.max())


def kernels_match_oracles(cfg, shapes: dict, seed: int, check: Checks) -> None:
    """Run ``paged_attention_pallas`` and ``flash_attention_pallas`` at the
    shapes the engine served, on seeded bf16 inputs, against the float32
    oracles (``decode_attention_ref`` over the gathered pages, ``mha_ref``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import ShardingConfig
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.ops import pack_pages, pool_geometry
    from repro.kernels.paged_attention import paged_attention_pallas
    from repro.kernels.ref import decode_attention_ref, mha_ref

    rng = np.random.default_rng(seed)
    H, Hk, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    _, (_, cache, table, *_), _ = shapes["_decode_k_paged"]
    B, n_cols = table.shape
    pool = next(x for x in jax.tree.leaves(cache) if x.ndim == 5)
    L, P = pool.shape[:2]
    ps = pool_geometry(pool, D)[2]
    layer = int(rng.integers(L))
    q = normal(B, 1, H, D)
    k_pages, v_pages = normal(L, P, Hk, ps, D), normal(L, P, Hk, ps, D)
    lengths = rng.integers(1, n_cols * ps + 1, B).astype(np.int32)
    tab = rng.permutation(P)[: B * n_cols].reshape(B, n_cols).astype(np.int32)
    tab[np.arange(n_cols)[None] * ps >= lengths[:, None]] = P     # unmapped
    got = jax.jit(paged_attention_pallas)(q, pack_pages(k_pages), pack_pages(v_pages),
                                          jnp.asarray(tab), jnp.asarray(lengths), layer)
    safe = jnp.asarray(np.minimum(tab, P - 1))

    def rows(pages):                        # (B, n_cols * ps, Hk, D) in f32
        return (pages[layer, safe].transpose(0, 1, 3, 2, 4)
                .reshape(B, n_cols * ps, Hk, D).astype(jnp.float32))

    with jax.default_matmul_precision("highest"):
        want = decode_attention_ref(q.astype(jnp.float32), rows(k_pages),
                                    rows(v_pages), jnp.asarray(lengths))
    ok, err = within_tolerance(got, want)
    check("paged_attention_pallas vs decode_attention_ref", ok,
          f"B={B} pages={L}x{P}x{ps} layer={layer} table_cols={n_cols} "
          f"max_abs_err={err:.3e}")

    _, (_, tokens, *_), _ = shapes["_prefill"]
    Bp, L = tokens.shape
    sc = ShardingConfig()
    q, k, v = normal(Bp, L, H, D), normal(Bp, L, Hk, D), normal(Bp, L, Hk, D)
    got = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, q_block=sc.attn_q_block,
        kv_block=sc.attn_kv_block))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = mha_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                       v.astype(jnp.float32), causal=True)
    ok, err = within_tolerance(got, want)
    check("flash_attention_pallas vs mha_ref", ok,
          f"B={Bp} S={L} max_abs_err={err:.3e}")


def memory_line(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_in_use={stats.get('bytes_in_use')}")


def phase_engine(dev, clock: CompileClock, seed: int) -> Checks:
    import jax

    from repro.launch import serve

    check = Checks("engine")
    args = serve.engine_parser().parse_args(ENGINE_ARGS + ["--seed", str(seed)])
    t0, c0 = time.perf_counter(), clock.seconds
    cfg, engine, reqs = serve.build_engine(args)
    jax.block_until_ready(engine.params)
    setup_s = time.perf_counter() - t0
    print(f"engine: device_kind={dev.device_kind} arch={cfg.name} "
          f"layers={cfg.n_layers} d_model={cfg.d_model} heads={cfg.n_heads} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype} slots={engine.max_slots} "
          f"max_len={engine.max_len} page_size={engine.page_size} "
          f"decode_block={engine.decode_block} setup_s={setup_s:.3f}", flush=True)

    shapes: dict = {}
    record_served_shapes(engine, shapes)
    t0, c_serve = time.perf_counter(), clock.seconds
    engine.serve(reqs)
    wall_s = time.perf_counter() - t0
    serve_compile_s = clock.seconds - c_serve
    run_s = wall_s - serve_compile_s
    tokens = sum(len(r.out_tokens) for r in reqs)
    occ = engine.kv_occupancy()
    print(f"engine: compile_s={clock.seconds - c0:.3f} (serve {serve_compile_s:.3f}) "
          f"serve_wall_s={wall_s:.3f} run_s={run_s:.3f} requests={len(reqs)} "
          f"tokens_served={tokens} prefill_calls={engine.n_prefill_calls} "
          f"decode_calls={engine.n_decode_calls} pages_peak={occ['peak_pages']}/"
          f"{occ['pages_reserved']} prefix_shares={occ['prefix_shares']} "
          f"cow_forks={occ['cow_forks']} persistent_cache_hits={clock.cache_hits} "
          f"{memory_line(dev)}", flush=True)
    print(f"engine: smoke figure, not a metric: {tokens / run_s:.1f} tokens/s "
          f"outside compilation", flush=True)

    n_out = [len(r.out_tokens) for r in reqs]
    check("every request finished with 1..max_new tokens",
          all(r.done for r in reqs) and all(1 <= n <= args.max_new for n in n_out),
          f"tokens per request {min(n_out)}..{max(n_out)}")
    check("prefix shares above 0", occ["prefix_shares"] > 0,
          f"{occ['prefix_shares']} shares")
    present = kernels_in_programs(shapes, check)
    print(f"engine: kernels_present={present}", flush=True)
    kernels_match_oracles(cfg, shapes, seed, check)
    return check


def post_chat(port: int, content: str, stream: bool) -> dict:
    body = json.dumps({"messages": [{"role": "user", "content": content}],
                       "stream": stream}).encode()
    req = urllib.request.Request(f"http://127.0.0.1:{port}/v1/chat/completions",
                                 data=body,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        status = resp.status
        raw = resp.read().decode()
    if not stream:
        reply = json.loads(raw)
        complete = reply["choices"][0]["finish_reason"] == "stop"
        return {"status": status, "complete": complete}
    frames = [line[len("data: "):] for line in raw.splitlines()
              if line.startswith("data: ")]
    events = [json.loads(f) for f in frames[:-1]]
    complete = (frames[-1:] == ["[DONE]"] and bool(events)
                and all("error" not in e for e in events)
                and events[-1]["choices"][0]["finish_reason"] == "stop")
    return {"status": status, "complete": complete}


def phase_http(dev, clock: CompileClock, seed: int) -> Checks:
    from repro.api import Gateway, PoolSpec, RunSpec
    from repro.serving.online import OnlineConfig

    check = Checks("http")
    spec = RunSpec(pool=PoolSpec(kind="tiny", steps=TINY_STEPS, n_train=16,
                                 n_test=16, seed=seed),
                   router="knn", coreset_size=8, grid_multiple=2, seed=seed)
    t0, c0 = time.perf_counter(), clock.seconds
    gw = Gateway.from_spec(spec)
    # the fit's profiling calls serve too: record from here on, so the
    # kernel check sees a decode program even if every HTTP answer ends at
    # its first token
    shapes: dict = {}
    tokens = [0]
    lock = threading.Lock()
    for member in gw.pool:
        record_served_shapes(member.engine, shapes)
        serve_fn = member.engine.serve

        def counted(reqs, *a, _serve=serve_fn, **kw):
            out = _serve(reqs, *a, **kw)
            with lock:
                tokens[0] += sum(len(r.out_tokens) for r in reqs)
            return out

        member.engine.serve = counted
    gw.fit()
    setup_s = time.perf_counter() - t0
    print(f"http: device_kind={dev.device_kind} pool={[m.name for m in gw.pool]} "
          f"train_steps={TINY_STEPS} setup_s={setup_s:.3f} "
          f"setup_compile_s={clock.seconds - c0:.3f}", flush=True)
    tokens[0] = 0

    task = gw.pool[0].task
    queries = [task.queries[i] for i in gw.wl.subset_indices("test")[:N_HTTP]]
    results: list = [None] * N_HTTP
    # an unlimited budget: no request may be shed
    fe = gw.serve_http(OnlineConfig(budget_per_s=1e6, window_s=0.05,
                                    realtime=True), port=0)
    t0, c_serve = time.perf_counter(), clock.seconds
    try:
        def client(i):
            try:
                results[i] = post_chat(fe.port, queries[i], stream=i < N_STREAMED)
            except Exception as e:   # noqa: BLE001 — reported as a failed check
                results[i] = {"status": None, "complete": False, "error": repr(e)}

        threads = [threading.Thread(target=client, args=(i,)) for i in range(N_HTTP)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        alive = [t for t in threads if t.is_alive()]
    finally:
        fe.stop()
    wall_s = time.perf_counter() - t0
    serve_compile_s = clock.seconds - c_serve
    srv = gw.server
    n_failed = sum(w.n_failed for w in srv.windows)
    n_dropped = sum(r.dropped for r in srv.completed)
    print(f"http: compile_s={clock.seconds - c0:.3f} (serve {serve_compile_s:.3f}) "
          f"serve_wall_s={wall_s:.3f} run_s={wall_s - serve_compile_s:.3f} "
          f"requests={N_HTTP} streamed={N_STREAMED} completed={len(srv.completed)} "
          f"windows={len(srv.windows)} tokens_served={tokens[0]} "
          f"{memory_line(dev)}", flush=True)

    check("client threads finished", not alive, f"{len(alive)} still running")
    bad = [(i, r) for i, r in enumerate(results)
           if not r or r["status"] != 200 or not r["complete"]]
    check("every response is a complete 200", not bad, repr(bad[:3]))
    check("no dispatch failed", n_failed == 0, f"sum WindowReport.n_failed={n_failed}")
    check("no request dropped", n_dropped == 0 and len(srv.completed) == N_HTTP,
          f"{n_dropped} dropped of {len(srv.completed)} completed")
    states = [br.state.value for br in srv.breakers]
    check("every breaker closed", all(s == "closed" for s in states), repr(states))
    check("stop() clean", not fe.threads_leaked, repr(fe.threads_leaked))
    present = kernels_in_programs(shapes, check)
    print(f"http: kernels_present={present}", flush=True)
    return check


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every weight, prompt and kernel input")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    from repro.launch.serve import enable_compile_cache

    enable_compile_cache()
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}", flush=True)
    clock = CompileClock()
    failed = phase_engine(dev, clock, args.seed).failed
    failed += phase_http(dev, clock, args.seed).failed
    if failed:
        print(f"chip_smoke: FAILED {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
